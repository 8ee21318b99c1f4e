"""Jamba (ai21labs/AI21-Jamba2-3B, ``model_type: jamba``; Jamba report,
arXiv:2403.19887; Mamba, arXiv:2312.00752): a hybrid decoder of selective
state-space (Mamba) layers and a few softmax-attention layers with grouped
key/value heads, a dense gated-SiLU feed-forward in every layer
(``num_experts`` 1) and the head tied to the embedding. No layer sees
positions: the state-space layers carry order. Layer ``i``, counted from 0,
is attention where ``i % attn_layer_period == attn_layer_offset`` (7 and 21
of 28) and a Mamba layer elsewhere.

Built from nn/decoder.py as an ordinary ``MultiLayerNetwork``: token
embedding, ``n_layers`` :class:`HybridDecoderBlock` (``mixer="mamba"`` or
``"gqa"``), a normed head with ``tied=True``; served through
``ServingModel(kind="generate", paged=True)`` like any decoder
(serving/generate.py's block protocol). A stream's cache is one state slot
of 10.1 MB over the 26 Mamba layers, whatever its length, and 1,024 B a
token in the two attention layers.

``init()`` draws every leaf and an optimiser state, which 3 billion
parameters do not survive on one chip: :meth:`network` builds the net
without parameters, for a caller that brings its own (``net.params``, one
dict a layer, as ``init()`` would lay them out) and then calls :meth:`tie`,
which hands the head the embedding's own array.
"""

from __future__ import annotations

import dataclasses

from deeplearning4j_tpu.nn import InputType, MultiLayerNetwork
from deeplearning4j_tpu.nn.decoder import (HybridDecoderBlock,
                                           NormedLogitsLayer,
                                           TokenEmbeddingLayer)
from deeplearning4j_tpu.zoo.models import ZooModel


@dataclasses.dataclass
class Jamba(ZooModel):
    """Defaults are the published 3B sizes; ``tiny()`` is the test size."""

    vocab_size: int = 65536
    hidden_size: int = 2560
    n_layers: int = 28
    n_heads: int = 20
    n_kv_heads: int = 1
    head_dim: int = 128
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    ffn_size: int = 8192
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    expand: int = 2
    conv_bias: bool = True
    eps: float = 1e-6
    max_length: int = 384
    param_dtype: str = "float32"

    @classmethod
    def tiny(cls, **kw):
        """Hidden 64, one whole period of four layers: Mamba, attention
        (4 heads over 1 key/value head), Mamba, Mamba."""
        for k, v in dict(vocab_size=96, hidden_size=64, n_layers=4,
                         n_heads=4, n_kv_heads=1, head_dim=16,
                         attn_layer_period=4, attn_layer_offset=1,
                         ffn_size=128, d_state=8, dt_rank=8,
                         max_length=96).items():
            kw.setdefault(k, v)
        return cls(**kw)

    def is_attention(self, i: int) -> bool:
        """Layer ``i``, from 0 (module doc)."""
        return i % self.attn_layer_period == self.attn_layer_offset

    def conf(self):
        lb = self._builder().list()
        lb.layer(TokenEmbeddingLayer(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            param_dtype=self.param_dtype, max_position=self.max_length))
        for i in range(self.n_layers):
            lb.layer(HybridDecoderBlock(
                hidden_size=self.hidden_size, ffn="dense",
                mixer="gqa" if self.is_attention(i) else "mamba",
                n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
                head_dim=self.head_dim, eps=self.eps,
                param_dtype=self.param_dtype, conv_size=self.d_conv,
                d_state=self.d_state, dt_rank=self.dt_rank,
                expand=self.expand, conv_bias=self.conv_bias,
                ffn_size=self.ffn_size))
        lb.layer(NormedLogitsLayer(n_in=self.hidden_size,
                                   n_out=self.vocab_size, eps=self.eps,
                                   param_dtype=self.param_dtype, tied=True))
        lb.set_input_type(InputType.recurrent(1, self.max_length))
        return lb.build()

    def network(self) -> MultiLayerNetwork:
        """The net without parameters (module doc)."""
        return MultiLayerNetwork(self.conf())

    @staticmethod
    def tie(net: MultiLayerNetwork) -> MultiLayerNetwork:
        """Hand the head the embedding's matrix, by reference: call after
        ``net.params`` is set (or after ``init()`` at a small size)."""
        net.params[-1] = net.layers[-1].tie(net.params[-1], net.params[0])
        return net
