"""Transformer encoder layers: BERT embeddings + encoder blocks.

Reference parity: the reference has no native transformer *layer* classes —
BERT runs there as a TF-imported SameDiff graph (BASELINE config #4,
SURVEY.md §3.3: TFGraphMapper.importGraph → SameDiff exec) over the attention
declarable ops. Here the encoder is a first-class layer family so BERT builds
natively in MultiLayerNetwork/ComputationGraph, with the TF-import path
(deeplearning4j_tpu.samediff) as the parity route.

TPU-native: [B,T,H] layout; each block is two residual sublayers whose
matmuls XLA tiles onto the MXU; attention picks the exact or Pallas flash
path by sequence length (``flash="auto"``, the default — flash from
1024 tokens on TPU, ops.attention.FLASH_MIN_SEQ). The Pallas path takes (B,T) padding
masks since r14 (key blocks masked inside the kernel, masked-vs-exact
equivalence pinned in tests/test_kernels.py); only full [B,1|H,Tq,Tk]
attention masks still force the exact path.
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
from deeplearning4j_tpu.ops import nn as nnops
from deeplearning4j_tpu.ops import random as randops


def _layer_norm(x, gamma, beta, eps=1e-12):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * gamma + beta


@register_layer
@dataclasses.dataclass(frozen=True)
class BertEmbeddingLayer(Layer):
    """BERT input embeddings: word + learned position + token-type, then
    LayerNorm + dropout. Input: (B,T) int token ids, or (B,T,2) stacked
    [token_ids, segment_ids] for sentence pairs."""

    vocab_size: int = 0
    hidden_size: int = 0
    max_position: int = 512
    type_vocab_size: int = 2
    init_range: float = 0.02

    def initialize(self, key, input_shape):
        kw, kp, kt = jax.random.split(key, 3)
        r = self.init_range
        return {
            "word": jax.random.normal(kw, (self.vocab_size, self.hidden_size)) * r,
            "pos": jax.random.normal(kp, (self.max_position, self.hidden_size)) * r,
            "type": jax.random.normal(kt, (self.type_vocab_size, self.hidden_size)) * r,
            "gamma": jnp.ones((self.hidden_size,), jnp.float32),
            "beta": jnp.zeros((self.hidden_size,), jnp.float32),
        }, {}

    def apply(self, params, state, x, *, training=False, key=None):
        if x.ndim == 3:
            tokens = x[..., 0].astype(jnp.int32)
            segments = x[..., 1].astype(jnp.int32)
        else:
            tokens = x.astype(jnp.int32)
            segments = jnp.zeros_like(tokens)
        t = tokens.shape[1]
        h = (
            jnp.take(params["word"], tokens, axis=0)
            + params["pos"][None, :t]
            + jnp.take(params["type"], segments, axis=0)
        )
        h = _layer_norm(h, params["gamma"], params["beta"])
        return self._maybe_dropout(h, training, key), state

    def embed_step(self, params, tokens, positions):
        """One decode-step embedding: ``tokens`` (B,) int ids at per-row
        ``positions`` (B,) → (B, H). Same word+pos+type-0 sum and LayerNorm
        as ``apply`` on a (B, T) batch, so an incrementally-embedded token
        matches the full-sequence embedding at that position exactly
        (serving/generate.py KV-cache decode)."""
        h = (jnp.take(params["word"], tokens.astype(jnp.int32), axis=0)
             + jnp.take(params["pos"], positions.astype(jnp.int32), axis=0)
             + params["type"][0])
        return _layer_norm(h, params["gamma"], params["beta"])

    def embed_window(self, params, tokens, positions):
        """Windowed decode embedding: ``tokens`` (B, W) ids at per-row
        ``positions`` (B, W) → (B, W, H). The speculative-decoding verify
        window (serving/generate.py): the same word+pos+type-0 sum and
        LayerNorm as :meth:`embed_step`, so every window token embeds
        exactly as it would one step at a time."""
        h = (jnp.take(params["word"], tokens.astype(jnp.int32), axis=0)
             + jnp.take(params["pos"], positions.astype(jnp.int32), axis=0)
             + params["type"][0])
        return _layer_norm(h, params["gamma"], params["beta"])

    def output_shape(self, input_shape):
        return (input_shape[0], self.hidden_size)


@register_layer
@dataclasses.dataclass(frozen=True)
class TransformerEncoderBlock(Layer):
    """One post-LN transformer encoder block (BERT layout):

        h = LN(x + Dropout(MHA(x)));  out = LN(h + Dropout(FFN(h)))

    ``mask``: (B,T) padding mask — masked keys are never attended to.
    ``causal=True`` adds the autoregressive mask (decoder-only / GPT
    style), which is also what enables the KV-cache ``prefill`` /
    ``decode_step`` serving path (serving/generate.py).
    """

    hidden_size: int = 0
    n_heads: int = 1
    ffn_size: int = 0  # default 4*hidden
    activation: str = "gelu"
    attn_dropout: float = 0.0
    hidden_dropout: float = 0.0
    init_range: float = 0.02
    flash: Any = "auto"  # True | False | "auto" (measured-crossover dispatch)
    pre_norm: bool = False  # pre-LN variant (GPT-style)
    causal: bool = False  # autoregressive mask (decoder-only LM)

    @property
    def _ffn(self):
        return self.ffn_size or 4 * self.hidden_size

    def initialize(self, key, input_shape):
        hs = self.hidden_size
        ks = jax.random.split(key, 6)
        r = self.init_range
        n = jax.random.normal
        return {
            "Wq": n(ks[0], (hs, hs)) * r, "bq": jnp.zeros((hs,)),
            "Wk": n(ks[1], (hs, hs)) * r, "bk": jnp.zeros((hs,)),
            "Wv": n(ks[2], (hs, hs)) * r, "bv": jnp.zeros((hs,)),
            "Wo": n(ks[3], (hs, hs)) * r, "bo": jnp.zeros((hs,)),
            "ln1_g": jnp.ones((hs,)), "ln1_b": jnp.zeros((hs,)),
            "W1": n(ks[4], (hs, self._ffn)) * r, "b1": jnp.zeros((self._ffn,)),
            "W2": n(ks[5], (self._ffn, hs)) * r, "b2": jnp.zeros((hs,)),
            "ln2_g": jnp.ones((hs,)), "ln2_b": jnp.zeros((hs,)),
        }, {}

    def _qkv(self, params, x):
        """Per-head Q/K/V projections: (B,T,H) → three (B,nh,T,dh). Shared
        by the full forward and the KV-cache prefill/decode paths so the
        cached K/V are bit-identical to the recomputed ones."""
        b, t, hs = x.shape
        nh = self.n_heads
        dh = hs // nh
        split = lambda y: jnp.transpose(y.reshape(b, t, nh, dh), (0, 2, 1, 3))
        q = split(x @ params["Wq"] + params["bq"])
        k = split(x @ params["Wk"] + params["bk"])
        v = split(x @ params["Wv"] + params["bv"])
        return q, k, v

    def _proj_out(self, params, o):
        b, nh, t, dh = o.shape
        o = jnp.transpose(o, (0, 2, 1, 3)).reshape(b, t, nh * dh)
        return o @ params["Wo"] + params["bo"]

    def _mha(self, params, x, mask):
        t = x.shape[1]
        q, k, v = self._qkv(params, x)
        if attn_ops.resolve_flash(self.flash, t, t, mask):
            o = attn_ops.flash_attention(q, k, v, causal=self.causal,
                                         mask=mask)
        else:
            amask = None if mask is None else mask[:, None, None, :].astype(bool)
            o = attn_ops.dot_product_attention(q, k, v, mask=amask,
                                               causal=self.causal)
        return self._proj_out(params, o)

    def _attn_input(self, params, x):
        """What the attention sublayer sees: LN(x) pre-norm, x post-norm."""
        return (_layer_norm(x, params["ln1_g"], params["ln1_b"])
                if self.pre_norm else x)

    def _finish(self, params, x, a, k1=None, k2=None, training=False):
        """Residual + LayerNorm + FFN composition after the attention
        output ``a`` — the ONE copy shared by ``apply``, ``prefill``, and
        ``decode_step``, so the bit-exact cache==recompute contract cannot
        drift between paths."""

        def drop(h, k):
            # sublayer-output dropout at hidden_dropout (a different rate
            # from Layer.dropout, which is input dropout)
            if training and self.hidden_dropout > 0.0 and k is not None:
                return randops.dropout(h, k, self.hidden_dropout,
                                       training=True)
            return h

        if self.pre_norm:
            h = x + drop(a, k1)
            f = self._ffn_block(
                params, _layer_norm(h, params["ln2_g"], params["ln2_b"]))
            return h + drop(f, k2)
        h = _layer_norm(x + drop(a, k1), params["ln1_g"], params["ln1_b"])
        return _layer_norm(h + drop(self._ffn_block(params, h), k2),
                           params["ln2_g"], params["ln2_b"])

    def apply(self, params, state, x, *, training=False, key=None, mask=None):
        k1 = k2 = None
        if key is not None:
            k1, k2 = jax.random.split(key)
        a = self._mha(params, self._attn_input(params, x), mask)
        out = self._finish(params, x, a, k1, k2, training)
        if mask is not None:
            out = out * mask[..., None].astype(out.dtype)
        return out, state

    # --------------------------------------------------- KV-cache decoding
    # Serving substrate (serving/generate.py): ``prefill`` runs the causal
    # forward over the whole prompt once and captures per-position K/V;
    # ``decode_step`` then extends the sequence one token at a time, each
    # step one small attention row over the cache instead of a full T×T
    # recompute. Both reuse ``_qkv``/``_proj_out`` and the exact sublayer
    # math of ``apply``, so greedy decode through the cache reproduces the
    # full-recompute decode exactly (tests/test_serving.py).

    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32):
        """Empty K/V cache for ``batch`` rows and ``max_len`` positions."""
        dh = self.hidden_size // self.n_heads
        z = jnp.zeros((batch, self.n_heads, max_len, dh), dtype)
        return {"k": z, "v": z}

    def _ffn_block(self, params, h):
        fn = act.resolve(self.activation)
        return fn(h @ params["W1"] + params["b1"]) @ params["W2"] + params["b2"]

    def prefill(self, params, x, cache, mask=None):
        """Causal forward over the prompt (B,T,H), writing K/V for positions
        [0, T) into ``cache`` (T <= cache max_len). Returns (out, cache).
        Inference-only (no dropout); ``mask`` is the (B,T) padding mask.
        Padding positions write garbage K/V but every later read is masked
        to ``k_pos <= position`` and generation overwrites position
        ``length`` before first attending to it, so they are never seen."""
        if not self.causal:
            raise ValueError("prefill/decode_step need causal=True blocks")
        q, k, v = self._qkv(params, self._attn_input(params, x))
        zero = (0, 0, 0, 0)
        cache = {
            "k": jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), zero),
            "v": jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), zero),
        }
        amask = None if mask is None else mask[:, None, None, :].astype(bool)
        o = attn_ops.dot_product_attention(q, k, v, mask=amask, causal=True)
        return self._finish(params, x, self._proj_out(params, o)), cache

    # ------------------------------------------------- paged KV-cache path
    # Serving substrate for the paged/block pool (serving/paged.py): the
    # K/V of EVERY stream live in one slot-flat pool per layer — shape
    # (S, H*Dh) with S = num_blocks * block_size, a token's heads side by
    # side in one row — and each stream's page table (``tables``,
    # (B, max_blocks)) names the blocks behind its logical positions.
    # Projections, sublayer math and the attention mask are the SAME code
    # the contiguous path runs; the attention itself walks the table block
    # chunk by block chunk as far as the longest stream reaches
    # (ops/attention.paged_attention), so paged decode gives the
    # contiguous decode's tokens, and its logits to the rounding of a
    # float32 sum (tests/test_paged_decode.py).

    def init_pool(self, num_slots: int, dtype=jnp.float32):
        """Empty slot-flat K/V pool for this layer: (S, H*Dh) each. Rows
        of the whole hidden width, because a TPU lays an (S, H, Dh) array
        of Dh < 128 out with S on the lanes, and every program that
        scatters or gathers slots then copies the whole pool to a
        slot-major layout and back (PERF.md, PR 28). Two DISTINCT buffers
        — the pools are donated through the decode executables, and
        aliased k/v would be the same buffer donated twice."""
        shape = (num_slots, self.hidden_size)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def _pool_write(self, pool, slots_flat, k, v):
        """Scatter the K/V of N tokens, (B, H, T, Dh) with B*T = N, as
        (N, H*Dh) rows at flat slot indices (N,). Trash-block collisions
        (padding writes) are garbage-on-garbage — every read is
        position-masked before the softmax."""
        rows = lambda y: jnp.transpose(y, (0, 2, 1, 3)).reshape(
            -1, self.hidden_size).astype(pool["k"].dtype)
        return {"k": pool["k"].at[slots_flat].set(rows(k)),
                "v": pool["v"].at[slots_flat].set(rows(v))}

    def prefill_paged(self, params, x, pool, slots, mask=None):
        """Causal forward over the prompt (B,T,H), scattering each
        position's K/V into the paged ``pool`` at ``slots`` (B,T) —
        positions outside a stream's reservation point at the trash block.
        The attention itself runs over the in-register q/k/v exactly like
        :meth:`prefill`, so the hidden states (and therefore the prompt's
        next-token logits) are bit-identical to the contiguous prefill."""
        if not self.causal:
            raise ValueError("prefill/decode_step need causal=True blocks")
        q, k, v = self._qkv(params, self._attn_input(params, x))
        pool = self._pool_write(pool, slots.reshape(-1), k, v)
        amask = None if mask is None else mask[:, None, None, :].astype(bool)
        o = attn_ops.dot_product_attention(q, k, v, mask=amask, causal=True)
        return self._finish(params, x, self._proj_out(params, o)), pool

    def prefill_resume_paged(self, params, x_w, pool, tables, positions,
                             block_size, limits=None):
        """Resume-from-position prefill (the shared-prefix KV path,
        serving/paged.py): prefill a prompt SUFFIX — ``x_w`` (B, W, H)
        at per-row absolute ``positions`` (B, W) starting wherever each
        stream's prefix-cache hit ends — against K/V the cached blocks
        already hold for the skipped head. Write-then-attend through the
        page table with every query masked to ``k_pos <= position`` is
        exactly the windowed decode semantics, which equals the
        whole-prompt causal prefill (the verify-window contract), so
        resumed prefill commits the same K/V and the same tokens as
        recomputing the prefix: a thin, documented delegation, kept as
        its own entry point because the CALLING contract differs
        (positions resume mid-prompt; ``limits`` is the last PROMPT
        position, trashing the lockstep-chunk padding columns)."""
        return self.decode_window_paged(params, x_w, pool, tables,
                                        positions, block_size,
                                        limits=limits)

    def decode_window_paged(self, params, x_w, pool, tables, positions,
                            block_size, limits=None):
        """W autoregressive steps in ONE call: ``x_w`` (B, W, H) are the
        window tokens' hidden states at per-row ``positions`` (B, W),
        ``tables`` (B, max_blocks) the streams' page tables over blocks
        of ``block_size`` slots. Writes the window's K/V at each token's
        slot, then attends every window query over ``k_pos <= position``
        through the page table — W=1 is the plain paged decode step; W>1
        is the speculative-decode verify window (each query attends the
        window tokens before it plus the whole committed prefix, exactly
        the sequential-step semantics). ``limits`` (B,): each stream's
        last valid position — writes past it (a finished row riding a
        still-decoding batch, or a verify window overhanging a stream's
        final token) redirect to the trash block so they can never
        clobber a live slot. Returns (out (B, W, H), pool)."""
        q, k, v = self._qkv(params, self._attn_input(params, x_w))
        wslots = attn_ops.paged_slots(tables, positions, block_size)
        if limits is not None:
            wslots = jnp.where(positions <= limits[:, None], wslots, 0)
        pool = self._pool_write(pool, wslots.reshape(-1), k, v)
        o = attn_ops.paged_attention(q, pool["k"], pool["v"], tables,
                                     positions, block_size)
        return self._finish(params, x_w, self._proj_out(params, o)), pool

    def decode_step(self, params, x_t, cache, positions):
        """One autoregressive step: ``x_t`` (B,1,H) is the new token's
        hidden state, ``positions`` (B,) its per-row position. Writes this
        step's K/V at each row's position (per-row scatter — the written
        slot is exactly the new value, every other slot exactly the old,
        and the update is O(B·H·Dh), not a full-cache rewrite) and attends
        the single query over ``k_pos <= position``. Returns
        (out (B,1,H), cache)."""
        q, k, v = self._qkv(params, self._attn_input(params, x_t))  # T=1
        L = cache["k"].shape[2]
        rows = jnp.arange(x_t.shape[0])
        new_k = cache["k"].at[rows, :, positions].set(
            k[:, :, 0].astype(cache["k"].dtype))
        new_v = cache["v"].at[rows, :, positions].set(
            v[:, :, 0].astype(cache["v"].dtype))
        amask = (jnp.arange(L)[None, :]
                 <= positions[:, None])[:, None, None, :]
        o = attn_ops.dot_product_attention(q, new_k, new_v, mask=amask)
        out = self._finish(params, x_t, self._proj_out(params, o))
        return out, {"k": new_k, "v": new_v}

    def output_shape(self, input_shape):
        return (input_shape[0], self.hidden_size)


@register_layer
@dataclasses.dataclass(frozen=True)
class TimeStepLayer(Layer):
    """Select one time step from (B,T,F) → (B,F). index=0 is BERT's [CLS]
    readout (the reference does this with a SubsetVertex-style slice)."""

    index: int = 0

    def has_params(self):
        return False

    def apply(self, params, state, x, *, training=False, key=None):
        return x[:, self.index], state

    def output_shape(self, input_shape):
        return (input_shape[-1],)
