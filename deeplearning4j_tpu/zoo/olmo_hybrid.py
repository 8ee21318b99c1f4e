"""Olmo Hybrid (allenai/Olmo-Hybrid-7B, ``model_type: olmo_hybrid``): a
hybrid decoder of Gated DeltaNet layers (arXiv:2412.06464, as
flash-linear-attention's ``GatedDeltaNet`` lays them out, with negative
eigenvalues: ``linear_allow_neg_eigval``) and softmax-attention layers
with QK-norm, in OLMo 2's reordered-norm block (arXiv:2501.00656): ``x +=
RMSNorm(mixer(x)); x += RMSNorm(ffn(x))``, a dense gated-SiLU feed-forward
in every layer and an untied head. ``layer_types`` names each layer,
``"linear_attention"`` or ``"full_attention"``; the published 32 repeat
(linear, linear, linear, full). No layer sees positions (``rope_theta`` is
``null``): the linear layers carry order.

Built from nn/decoder.py as an ordinary ``MultiLayerNetwork``: token
embedding, one :class:`HybridDecoderBlock` a layer type (``mixer="gdn"``
with keys of 96 and values of 192, or ``"gqa"`` with ``qk_norm``), a normed
head; served through ``ServingModel(kind="generate", paged=True)`` like any
decoder (serving/generate.py's block protocol). A stream's cache is one
state slot of 2.35 MB a linear layer, whatever its length, and 61,440 B a
token over four full layers.

``init()`` draws every leaf and an optimiser state, which billions of
parameters do not survive on one chip: :meth:`network` builds the net
without parameters, for a caller that brings its own (``net.params``, one
dict a layer, as ``init()`` would lay them out).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

from deeplearning4j_tpu.nn import InputType, MultiLayerNetwork
from deeplearning4j_tpu.nn.decoder import (HybridDecoderBlock,
                                           NormedLogitsLayer,
                                           TokenEmbeddingLayer)
from deeplearning4j_tpu.zoo.models import ZooModel

LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass
class OlmoHybrid(ZooModel):
    """Defaults are the published 7B sizes; ``tiny()`` is the test size."""

    vocab_size: int = 100352
    hidden_size: int = 3840
    layer_types: Tuple[str, ...] = (LINEAR, LINEAR, LINEAR, FULL) * 8
    n_heads: int = 30
    n_kv_heads: int = 30
    linear_heads: int = 30
    key_head_dim: int = 96
    value_head_dim: int = 192
    conv_size: int = 4
    ffn_size: int = 11008
    eps: float = 1e-6
    max_length: int = 512
    param_dtype: str = "float32"

    @classmethod
    def tiny(cls, **kw):
        """Hidden 64, one whole period: three linear layers (2 heads, keys
        of 32 and values of 64: a state slot packs the two heads, as it
        packs two 192-wide heads of the published size) and a full one (4
        heads of 16)."""
        for k, v in dict(vocab_size=96, hidden_size=64,
                         layer_types=(LINEAR, LINEAR, LINEAR, FULL),
                         n_heads=4, n_kv_heads=4, linear_heads=2,
                         key_head_dim=32, value_head_dim=64, ffn_size=128,
                         max_length=96).items():
            kw.setdefault(k, v)
        return cls(**kw)

    def conf(self):
        lb = self._builder().list()
        lb.layer(TokenEmbeddingLayer(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            param_dtype=self.param_dtype, max_position=self.max_length))
        for kind in self.layer_types:
            full = kind == FULL
            if not full and kind != LINEAR:
                raise ValueError(f"unknown layer type {kind!r}")
            lb.layer(HybridDecoderBlock(
                hidden_size=self.hidden_size, norm="post", ffn="dense",
                mixer="gqa" if full else "gdn",
                n_heads=self.n_heads if full else self.linear_heads,
                n_kv_heads=self.n_kv_heads, qk_norm=full,
                head_dim=(self.hidden_size // self.n_heads if full
                          else self.key_head_dim),
                value_dim=self.value_head_dim, conv_size=self.conv_size,
                eps=self.eps, param_dtype=self.param_dtype,
                ffn_size=self.ffn_size))
        lb.layer(NormedLogitsLayer(n_in=self.hidden_size,
                                   n_out=self.vocab_size, eps=self.eps,
                                   param_dtype=self.param_dtype))
        lb.set_input_type(InputType.recurrent(1, self.max_length))
        return lb.build()

    def network(self) -> MultiLayerNetwork:
        """The net without parameters (module doc)."""
        return MultiLayerNetwork(self.conf())
