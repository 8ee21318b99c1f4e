"""Attention layers: SelfAttention, LearnedSelfAttention, RecurrentAttention.

Reference parity: org/deeplearning4j/nn/conf/layers/{SelfAttentionLayer,
LearnedSelfAttentionLayer,RecurrentAttentionLayer}.java and the SameDiff-backed
impls under org/deeplearning4j/nn/layers/ (these are SameDiffLayer subclasses
in the reference, bottoming out in the multiHeadDotProductAttention declarable
op) — path-cite, mount empty this round. SURVEY.md §5.7: attention in the
reference exists only as these single-device layers.

TPU-native: sequences are [batch, time, features]; the attention core is
``ops.attention`` — exact einsum path or the Pallas flash kernel, picked
automatically (``flash="auto"``, the default: flash from 1024 tokens on
TPU; see ``ops.attention.FLASH_MIN_SEQ``). The reference cannot
handle long sequences at all.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import activations as act
from deeplearning4j_tpu.nn import weights as winit
from deeplearning4j_tpu.nn.layers import Layer, register_layer
from deeplearning4j_tpu.ops import attention as attn_ops


@dataclasses.dataclass(frozen=True)
class BaseAttentionLayer(Layer):
    n_in: int = 0
    n_out: int = 0
    n_heads: int = 1
    head_size: Optional[int] = None  # default n_out // n_heads
    project_input: bool = True
    weight_init: str = "xavier"
    flash: Any = "auto"  # True | False | "auto" (measured-crossover dispatch)
    causal: bool = False  # autoregressive mask (decoder-only stacks)

    @property
    def _head_size(self) -> int:
        if self.head_size is not None:
            return self.head_size
        if self.n_out % self.n_heads:
            raise ValueError("n_out must be divisible by n_heads (or set head_size)")
        return self.n_out // self.n_heads

    def _proj_params(self, key):
        hd = self.n_heads * self._head_size
        kq, kk, kv, ko = jax.random.split(key, 4)
        wi = self.weight_init
        return {
            "Wq": winit.init(kq, wi, (self.n_in, hd)),
            "Wk": winit.init(kk, wi, (self.n_in, hd)),
            "Wv": winit.init(kv, wi, (self.n_in, hd)),
            "Wo": winit.init(ko, wi, (hd, self.n_out)),
        }

    def _check_unprojected(self):
        if self.n_in != self.n_out:
            raise ValueError("project_input=False requires n_in == n_out")
        if self.n_heads != 1:
            raise ValueError("project_input=False requires n_heads == 1")


@register_layer
@dataclasses.dataclass(frozen=True)
class SelfAttentionLayer(BaseAttentionLayer):
    """Self attention over a [B,T,F] sequence → [B,T,n_out].

    conf/layers/SelfAttentionLayer.java parity: with ``project_input`` the
    layer learns Wq/Wk/Wv/Wo; without, q=k=v=input (requires n_in==n_out,
    single head). ``mask`` is a (B,T) padding mask: masked keys are never
    attended to and masked output steps are zeroed.
    """

    def initialize(self, key, input_shape):
        if not self.project_input:
            self._check_unprojected()
            return {}, {}
        return self._proj_params(key), {}

    def has_params(self):
        return self.project_input

    def apply(self, params, state, x, *, training=False, key=None, mask=None):
        x = self._maybe_dropout(x, training, key)
        if self.project_input:
            y = attn_ops.multi_head_dot_product_attention(
                x, x, x, params["Wq"], params["Wk"], params["Wv"], params["Wo"],
                n_heads=self.n_heads, mask=mask, flash=self.flash,
                causal=self.causal,
            )
        else:
            q = x[:, None]  # single head
            amask = None if mask is None else mask[:, None, None, :]
            y = attn_ops.dot_product_attention(
                q, q, q, mask=amask, causal=self.causal)[:, 0]
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        return y, state

    def output_shape(self, input_shape):
        return (input_shape[0], self.n_out)


@register_layer
@dataclasses.dataclass(frozen=True)
class LearnedSelfAttentionLayer(BaseAttentionLayer):
    """Attention with n_queries LEARNED query vectors → [B, n_queries, n_out].

    conf/layers/LearnedSelfAttentionLayer.java parity: pools a variable-length
    sequence into a fixed number of steps; the time axis is consumed.
    """

    n_queries: int = 1

    def initialize(self, key, input_shape):
        kq, kp = jax.random.split(key)
        if self.project_input:
            params = self._proj_params(kp)
            params["Q"] = winit.init(kq, self.weight_init, (self.n_queries, self.n_in))
        else:
            self._check_unprojected()
            params = {"Q": winit.init(kq, self.weight_init, (self.n_queries, self.n_in))}
        return params, {}

    def apply(self, params, state, x, *, training=False, key=None, mask=None):
        x = self._maybe_dropout(x, training, key)
        b = x.shape[0]
        queries = jnp.broadcast_to(params["Q"], (b,) + params["Q"].shape)
        if self.project_input:
            y = attn_ops.multi_head_dot_product_attention(
                queries, x, x, params["Wq"], params["Wk"], params["Wv"],
                params["Wo"], n_heads=self.n_heads, mask=mask,
            )
        else:
            amask = None if mask is None else mask[:, None, None, :]
            y = attn_ops.dot_product_attention(
                queries[:, None], x[:, None], x[:, None], mask=amask
            )[:, 0]
        return y, state

    def output_shape(self, input_shape):
        return (self.n_queries, self.n_out)


@register_layer
@dataclasses.dataclass(frozen=True)
class RecurrentAttentionLayer(BaseAttentionLayer):
    """Recurrent cell whose step attends over the full input sequence with the
    previous hidden state as query:

        a_t = MHA(q = h_{t-1}, k = v = x)
        h_t = activation(x_t Wx + a_t Wr + b)

    conf/layers/RecurrentAttentionLayer.java parity (a SameDiffLayer in the
    reference). The K/V projections are hoisted out of the ``lax.scan`` so the
    scan body is two small matmuls + one attention row.
    """

    activation: str = "tanh"

    def initialize(self, key, input_shape):
        hd = self.n_heads * self._head_size
        kx, kr, kq, kk, kv, ko = jax.random.split(key, 6)
        wi = self.weight_init
        return {
            "Wx": winit.init(kx, wi, (self.n_in, self.n_out)),
            "Wr": winit.init(kr, wi, (self.n_out, self.n_out)),
            "b": jnp.zeros((self.n_out,), jnp.float32),
            "Wq": winit.init(kq, wi, (self.n_out, hd)),
            "Wk": winit.init(kk, wi, (self.n_in, hd)),
            "Wv": winit.init(kv, wi, (self.n_in, hd)),
            "Wo": winit.init(ko, wi, (hd, self.n_out)),
        }, {}

    def apply(self, params, state, x, *, training=False, key=None, mask=None):
        x = self._maybe_dropout(x, training, key)
        b, t, _ = x.shape
        h, dh = self.n_heads, self._head_size
        # hoisted K/V: (B, H, T, Dh)
        kproj = (x @ params["Wk"]).reshape(b, t, h, dh).transpose(0, 2, 1, 3)
        vproj = (x @ params["Wv"]).reshape(b, t, h, dh).transpose(0, 2, 1, 3)
        kmask = None if mask is None else mask[:, None, None, :].astype(bool)
        fn = act.resolve(self.activation)
        xw = x @ params["Wx"]  # hoisted input projection (B,T,n_out)

        def step(h_prev, xw_t):
            q = (h_prev @ params["Wq"]).reshape(b, h, 1, dh)
            a = attn_ops.dot_product_attention(q, kproj, vproj, mask=kmask)
            a = a.transpose(0, 2, 1, 3).reshape(b, h * dh) @ params["Wo"]
            h_new = fn(xw_t + a @ params["Wr"] + params["b"])
            return h_new, h_new

        h0 = jnp.zeros((b, self.n_out), x.dtype)
        _, ys = jax.lax.scan(step, h0, jnp.swapaxes(xw, 0, 1))
        y = jnp.swapaxes(ys, 0, 1)
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        return y, state

    def output_shape(self, input_shape):
        return (input_shape[0], self.n_out)
