"""GLM-4.7-Flash (zai-org/GLM-4.7-Flash, ``model_type: glm4_moe_lite``): a
decoder of latent-attention (MLA) layers with rotary positions on the 64
rope dims and a low-rank query, a leading dense gated-SiLU feed-forward,
then 64 routed experts (top-4, sigmoid router with a selection bias) and one
shared expert a layer, and one next-token-prediction (MTP) module.

Built from nn/decoder.py as an ordinary ``MultiLayerNetwork``: token
embedding, ``n_layers`` :class:`HybridDecoderBlock` (the block Kimi Linear's
latent layers are, with ``rope`` and ``q_lora_rank`` set), a normed untied
head; served through ``ServingModel(kind="generate", paged=True)`` like any
decoder. Every layer caches token rows, so the prefix cache, chunked prefill
and speculation are served.

:meth:`mtp` builds the MTP module (:class:`NextTokenModule`) as a
``SelfDraft`` for ``Generator(self_draft=)``: the model drafts one token a
step for itself from its own last hidden state.

``init()`` draws every leaf and an optimiser state, which the served cut
(4.5 billion parameters) does not survive on one chip: :meth:`network`
builds the net without parameters, for a caller that brings its own.
"""

from __future__ import annotations

import dataclasses

from deeplearning4j_tpu.nn import InputType, MultiLayerNetwork
from deeplearning4j_tpu.nn.decoder import (HybridDecoderBlock,
                                           NextTokenModule,
                                           NormedLogitsLayer, SelfDraft,
                                           TokenEmbeddingLayer)
from deeplearning4j_tpu.zoo.models import ZooModel


@dataclasses.dataclass
class Glm4MoeLite(ZooModel):
    """Defaults are the published sizes; ``tiny()`` is the test size."""

    vocab_size: int = 154880
    hidden_size: int = 2048
    n_layers: int = 47
    n_heads: int = 20
    first_dense_layers: int = 1
    ffn_size: int = 10240
    expert_size: int = 1536
    n_experts: int = 64
    n_local_experts: int = 0        # 0 = all of them are held here
    expert_offset: int = 0
    top_k: int = 4
    routed_scale: float = 1.8
    n_shared_experts: int = 1
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_dim: int = 192
    qk_rope_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    eps: float = 1e-5
    max_length: int = 1024
    param_dtype: str = "float32"

    @classmethod
    def tiny(cls, **kw):
        """Hidden 64: a leading dense layer and two expert layers of 8
        experts, top-2; 2 heads of 16 + 8 key dims, query rank 24."""
        for k, v in dict(vocab_size=96, hidden_size=64, n_layers=3,
                         n_heads=2, ffn_size=128, expert_size=32,
                         n_experts=8, top_k=2, q_lora_rank=24,
                         kv_lora_rank=24, qk_nope_dim=16, qk_rope_dim=8,
                         v_head_dim=16, max_length=96).items():
            kw.setdefault(k, v)
        return cls(**kw)

    def _block(self, moe: bool) -> HybridDecoderBlock:
        return HybridDecoderBlock(
            hidden_size=self.hidden_size, n_heads=self.n_heads, mixer="mla",
            ffn="moe" if moe else "dense", eps=self.eps,
            param_dtype=self.param_dtype, q_lora_rank=self.q_lora_rank,
            kv_lora_rank=self.kv_lora_rank, qk_nope_dim=self.qk_nope_dim,
            qk_rope_dim=self.qk_rope_dim, v_head_dim=self.v_head_dim,
            rope=True, rope_theta=self.rope_theta,
            ffn_size=self.expert_size if moe else self.ffn_size,
            n_experts=self.n_experts if moe else 0,
            n_local_experts=self.n_local_experts if moe else 0,
            expert_offset=self.expert_offset if moe else 0,
            top_k=self.top_k, routed_scale=self.routed_scale,
            shared_size=(self.n_shared_experts * self.expert_size
                         if moe else 0))

    def conf(self):
        lb = self._builder().list()
        lb.layer(TokenEmbeddingLayer(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            param_dtype=self.param_dtype, max_position=self.max_length))
        for i in range(self.n_layers):
            lb.layer(self._block(moe=i >= self.first_dense_layers))
        lb.layer(NormedLogitsLayer(n_in=self.hidden_size,
                                   n_out=self.vocab_size, eps=self.eps,
                                   param_dtype=self.param_dtype))
        lb.set_input_type(InputType.recurrent(1, self.max_length))
        return lb.build()

    def network(self) -> MultiLayerNetwork:
        """The net without parameters (module doc)."""
        return MultiLayerNetwork(self.conf())

    def mtp(self, params=None):
        """The MTP module as a self-draft: one more expert layer of the same
        attention behind ``W_eh``, with ``params`` (as
        ``NextTokenModule.initialize`` lays them out) or none yet."""
        return SelfDraft(NextTokenModule(
            block=self._block(moe=True), eps=self.eps,
            param_dtype=self.param_dtype), params)
