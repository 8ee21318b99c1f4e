"""Kimi Linear (moonshotai/Kimi-Linear-48B-A3B-Instruct; Kimi Linear
report, arXiv:2510.26692): a hybrid decoder of KDA (gated delta-rule linear
attention) and MLA (latent attention) layers in the pattern KDA, KDA, KDA,
MLA, a leading dense gated-SiLU feed-forward and routed experts with one
shared expert after it. THIS configuration's latent layers see no positions:
it carries the 64 "rope" dims unrotated (``rope=False``, the block's
default; ``mla_use_nope`` in the published config) and keeps one full-rank
``Wq`` (``q_lora_rank`` 0). The block itself rotates and takes a low-rank
query where a configuration says so (zoo/glm4_moe_lite.py).

Built from nn/decoder.py as an ordinary ``MultiLayerNetwork``: token
embedding, ``n_layers`` :class:`HybridDecoderBlock`, a normed untied head;
served through ``ServingModel(kind="generate", paged=True)`` like any
decoder (serving/generate.py's block protocol).

A chip may hold a SHARE of the model: ``n_local_experts`` of the router's
``n_experts`` from ``expert_offset`` in every expert layer, and a slice of
the vocabulary (``vocab_size`` is then the slice). Routing is over all the
experts; what the absent ones would add is left out.

``init()`` draws every leaf and an optimiser state, which a 4-billion-
parameter share does not survive on one chip: :meth:`network` builds the net
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


@dataclasses.dataclass
class KimiLinear(ZooModel):
    """Defaults are the published 48B-A3B sizes; ``tiny()`` is the test
    size. ``full_attn_layers`` counts layers from 1, as the published
    config does."""

    vocab_size: int = 163840
    hidden_size: int = 2304
    n_layers: int = 27
    n_heads: int = 32
    full_attn_layers: Tuple[int, ...] = (4, 8, 12, 16, 20, 24, 27)
    first_dense_layers: int = 1
    ffn_size: int = 9216
    expert_size: int = 1024
    n_experts: int = 256
    n_local_experts: int = 0        # 0 = all of them are held here
    expert_offset: int = 0
    top_k: int = 8
    routed_scale: float = 2.446
    n_shared_experts: int = 1
    kda_head_dim: int = 128
    conv_size: int = 4
    gate_rank: int = 128
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    eps: float = 1e-5
    max_length: int = 1024
    param_dtype: str = "float32"

    @classmethod
    def tiny(cls, **kw):
        """Hidden 64: a leading dense layer, then KDA, KDA, MLA with 8
        experts, top-2."""
        for k, v in dict(vocab_size=96, hidden_size=64, n_layers=3,
                         n_heads=2, full_attn_layers=(3,), ffn_size=128,
                         expert_size=32, n_experts=8, top_k=2,
                         kda_head_dim=16, gate_rank=8, kv_lora_rank=24,
                         qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
                         max_length=96).items():
            kw.setdefault(k, v)
        return cls(**kw)

    def conf(self):
        lb = self._builder().list()
        lb.layer(TokenEmbeddingLayer(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            param_dtype=self.param_dtype, max_position=self.max_length))
        for i in range(1, self.n_layers + 1):
            moe = i > self.first_dense_layers
            lb.layer(HybridDecoderBlock(
                hidden_size=self.hidden_size, n_heads=self.n_heads,
                mixer="mla" if i in self.full_attn_layers else "kda",
                ffn="moe" if moe else "dense", eps=self.eps,
                param_dtype=self.param_dtype, head_dim=self.kda_head_dim,
                conv_size=self.conv_size, gate_rank=self.gate_rank,
                kv_lora_rank=self.kv_lora_rank, qk_nope_dim=self.qk_nope_dim,
                qk_rope_dim=self.qk_rope_dim, v_head_dim=self.v_head_dim,
                ffn_size=self.expert_size if moe else self.ffn_size,
                n_experts=self.n_experts if moe else 0,
                n_local_experts=self.n_local_experts if moe else 0,
                expert_offset=self.expert_offset if moe else 0,
                top_k=self.top_k, routed_scale=self.routed_scale,
                shared_size=(self.n_shared_experts * self.expert_size
                             if moe else 0)))
        lb.layer(NormedLogitsLayer(n_in=self.hidden_size,
                                   n_out=self.vocab_size, eps=self.eps,
                                   param_dtype=self.param_dtype))
        lb.set_input_type(InputType.recurrent(1, self.max_length))
        return lb.build()

    def network(self) -> MultiLayerNetwork:
        """The net without parameters (module doc)."""
        return MultiLayerNetwork(self.conf())
