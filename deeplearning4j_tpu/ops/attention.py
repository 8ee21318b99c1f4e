"""Attention ops: dot-product attention, multi-head attention, flash attention.

Reference parity: libnd4j declarable ops
``ops/declarable/generic/nn/dot_product_attention.cpp`` and
``multi_head_dot_product_attention.cpp`` (path-cite, mount empty this round),
surfaced on the JVM as ``SDNN.dotProductAttention`` /
``multiHeadDotProductAttention`` and consumed by the DL4J attention layers
(org/deeplearning4j/nn/conf/layers/SelfAttentionLayer.java et al.).

TPU-native design:
- Layout is [batch, heads, seq, head_dim] — seq x head_dim are the trailing
  two dims so the (s, d) tiles map straight onto the MXU; the reference's
  [batch, nIn, time] NCW layout is a BLAS-era artifact.
- The exact path is three einsums + softmax that XLA fuses; the flash path is
  a Pallas kernel (online softmax, O(S) memory) for long sequences — the
  reference has NO long-context story (SURVEY.md §5.7: truncated BPTT only),
  so this is where the TPU build goes past parity.
- Backward of the flash path is the standard flash-attention backward
  recomputation, written as a blockwise ``lax.scan`` that XLA fuses; no
  S x S attention matrix is ever materialized in fwd or bwd.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.ops.registry import op

_NEG_BIG = -1e30


def online_softmax_update(q, k, v, m, l, acc, scale, q_pos=None, k_pos=None,
                          kv_mask=None, qk="...qd,...kd->...qk",
                          pv="...qk,...kd->...qd"):
    """One online-softmax block update (the flash-attention inner step).

    q: [..., sq, d]; k/v: [..., bk, d]; m/l: [..., sq] f32; acc: [..., sq, d]
    f32. If q_pos/k_pos are given, applies the causal mask k_pos <= q_pos.
    ``kv_mask``: optional per-key padding mask broadcastable to s's
    [..., sq, bk] (1/True = attend). ``qk``/``pv`` are the two contractions
    as einsum specs, for a caller whose K/V block lies in another layout
    (the paged pool's [b, k, h, d]) and must not be transposed to this
    one; s, m, l and acc keep the layout above. Shared by the
    blockwise-scan forward, the ring-attention body and the paged decode
    pass so the numerically subtle m/l/acc correction exists exactly once.
    """
    s = jnp.einsum(qk, q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    causal = q_pos is not None
    if causal:
        s = jnp.where(k_pos[None, :] <= q_pos[:, None], s, _NEG_BIG)
    if kv_mask is not None:
        s = jnp.where(kv_mask, s, _NEG_BIG)
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_cur)
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    if causal or kv_mask is not None:
        # fully-masked rows: keep the spurious exp(0) mass out of l/acc
        p = jnp.where(s <= _NEG_BIG / 2, 0.0, p)
    l_new = corr * l + jnp.sum(p, axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        pv, p, v.astype(jnp.float32))
    return m_new, l_new, acc_new


# ---------------------------------------------------------------------------
# Exact reference implementation
# ---------------------------------------------------------------------------


@op("dot_product_attention", "attention", aliases=("dotProductAttention",))
def dot_product_attention(
    q,
    k,
    v,
    mask=None,
    scale: Optional[float] = None,
    causal: bool = False,
    with_weights: bool = False,
):
    """Scaled dot-product attention, exact (materializes the S×S matrix).

    q: [..., Sq, D], k: [..., Sk, D], v: [..., Sk, Dv].
    mask: broadcastable to [..., Sq, Sk]; 1/True = attend, 0/False = blocked
    (ND4J mask semantics). ``scale=None`` → 1/sqrt(D) ("scaled" attention,
    the reference op's ``scaled=1`` arg).
    """
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    s = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.promote_types(q.dtype, jnp.float32))
    s = s * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        q_pos = jnp.arange(sq)[:, None] + (sk - sq)
        k_pos = jnp.arange(sk)[None, :]
        s = jnp.where(k_pos <= q_pos, s, _NEG_BIG)
    if mask is not None:
        s = jnp.where(jnp.asarray(mask, dtype=bool), s, _NEG_BIG)
    w = jax.nn.softmax(s, axis=-1)
    if causal or mask is not None:
        # fully-masked rows: softmax of uniform -1e30 is uniform — zero those
        # rows instead (matches the flash path's empty-accumulator semantics)
        valid = jnp.any(s > _NEG_BIG / 2, axis=-1, keepdims=True)
        w = jnp.where(valid, w, 0.0)
    out = jnp.einsum("...qk,...kv->...qv", w.astype(v.dtype), v)
    if with_weights:
        return out, w
    return out


# ---------------------------------------------------------------------------
# Flash attention — Pallas forward kernel
# ---------------------------------------------------------------------------


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_s, l_s, *,
                      scale, causal, block_q, block_k, nk, kv_offset,
                      mask_ref=None):
    from jax.experimental import pallas as pl

    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, _NEG_BIG)
        l_s[...] = jnp.zeros_like(l_s)

    q = q_ref[0].astype(jnp.float32)  # (bq, d)
    k = k_ref[0].astype(jnp.float32)  # (bk, d)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (bq, bk)

    if causal:
        q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + kv_offset
        k_pos = ki * block_k + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(k_pos <= q_pos, s, _NEG_BIG)
    if mask_ref is not None:
        # (1, 1, bk) per-key padding block, broadcast over the bq query rows
        s = jnp.where(mask_ref[0] > 0.0, s, _NEG_BIG)

    m_prev = m_s[:, 0]  # (bq,)
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])  # (bq, bk)
    if causal or mask_ref is not None:
        # fully-masked rows: keep p's spurious exp(0) mass out of l/acc
        p = jnp.where((s <= _NEG_BIG / 2), 0.0, p)
    l_new = corr * l_s[:, 0] + jnp.sum(p, axis=-1)
    acc[...] = acc[...] * corr[:, None] + jax.lax.dot_general(
        p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_s[...] = jnp.broadcast_to(m_new[:, None], m_s.shape)
    l_s[...] = jnp.broadcast_to(l_new[:, None], l_s.shape)

    @pl.when(ki == nk - 1)
    def _fin():
        l = l_s[:, 0]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[...] / safe_l[:, None]).astype(o_ref.dtype)
        # lse is (bq, 1): Mosaic requires the block's sublane dim divisible by
        # 8, which a rank-2 (1, bq) block can't satisfy — so lse is rank-3.
        lse_ref[0] = (m_s[:, 0] + jnp.log(safe_l))[:, None]


def _flash_fwd_pallas(q, k, v, scale, causal, block_q, block_k, interpret,
                      mask=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    nq, nk = sq // bq, sk // bk

    base = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        nk=nk, kv_offset=sk - sq,
    )
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
        pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
    ]
    operands = [qf, kf, vf]
    if mask is None:
        kernel = base
    else:
        # (B, Sk) padding mask, one key block per (batch, ki) — the head
        # axis folds away in the index map (bh // h). Passed rank-3,
        # (B, 1, Sk) in (1, 1, bk) blocks: Mosaic wants a block's last two
        # dims (8, 128)-divisible or equal to the array's, and a rank-2
        # (1, bk) block of a (B, Sk) mask is neither once B > 1.
        def kernel(q_ref, k_ref, v_ref, m_ref, o_ref, lse_ref, acc, m_s,
                   l_s):
            base(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_s, l_s,
                 mask_ref=m_ref)

        in_specs.append(
            pl.BlockSpec((1, 1, bk),
                         lambda bh, qi, ki, h=h: (bh // h, 0, ki)))
        operands.append(mask.astype(jnp.float32)[:, None, :])
    o, lse = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    return o.reshape(b, h, sq, d), lse.reshape(b, h, sq)  # lse (BH,Sq,1) → (B,H,Sq)


def _flash_fwd_jnp(q, k, v, scale, causal, block_k, mask=None):
    """Blockwise online-softmax forward in pure JAX (lax.scan over KV blocks).

    Same math as the Pallas kernel; used off-TPU and anywhere Pallas can't run.
    ``mask``: optional (B, Sk) padding mask. Returns (out, lse)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bk = min(block_k, sk)
    nk = sk // bk
    kb = jnp.moveaxis(k.reshape(b, h, nk, bk, d), 2, 0)  # (nk, b,h,bk,d)
    vb = jnp.moveaxis(v.reshape(b, h, nk, bk, d), 2, 0)
    mb = None if mask is None else jnp.moveaxis(
        (mask > 0).reshape(b, nk, bk), 1, 0)             # (nk, b, bk)
    qf = q.astype(jnp.float32)
    q_pos = jnp.arange(sq) + (sk - sq)

    def body(carry, inp):
        m, l, acc, j = carry
        kj, vj = inp[0], inp[1]
        kv_mask = None if mb is None else inp[2][:, None, None, :]
        kp = j * bk + jnp.arange(bk) if causal else None
        m, l, acc = online_softmax_update(
            qf, kj, vj, m, l, acc, scale,
            q_pos=q_pos if causal else None, k_pos=kp, kv_mask=kv_mask)
        return (m, l, acc, j + 1), None

    m0 = jnp.full((b, h, sq), _NEG_BIG, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, d), jnp.float32)
    seqs = (kb, vb) if mb is None else (kb, vb, mb)
    (m, l, acc, _), _ = lax.scan(body, (m0, l0, a0, jnp.int32(0)), seqs)
    safe_l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / safe_l[..., None]).astype(q.dtype)
    return out, m + jnp.log(safe_l)


def _flash_bwd(scale, causal, block_k, res, do, mask=None):
    """Flash-attention backward: blockwise recomputation over KV blocks.
    ``mask``: optional (B, Sk) padding mask, reapplied to the recomputed
    scores exactly as in the forward."""
    q, k, v, o, lse = res
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bk = min(block_k, sk)
    nk = sk // bk
    qf, of, dof = (t.astype(jnp.float32) for t in (q, o, do))
    delta = jnp.sum(dof * of, axis=-1)  # (b,h,sq)
    kb = jnp.moveaxis(k.reshape(b, h, nk, bk, d), 2, 0)
    vb = jnp.moveaxis(v.reshape(b, h, nk, bk, d), 2, 0)
    mb = None if mask is None else jnp.moveaxis(
        (mask > 0).reshape(b, nk, bk), 1, 0)             # (nk, b, bk)
    q_pos = jnp.arange(sq) + (sk - sq)

    def body(carry, inp):
        dq, j = carry
        kj, vj = inp[0], inp[1]
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kj.astype(jnp.float32)) * scale
        if causal:
            k_pos = j * bk + jnp.arange(bk)
            s = jnp.where(k_pos[None, None, None, :] <= q_pos[None, None, :, None], s, _NEG_BIG)
        if mb is not None:
            s = jnp.where(inp[2][:, None, None, :], s, _NEG_BIG)
        p = jnp.exp(s - lse[..., None])
        if causal or mb is not None:
            # fully-masked rows have s == lse == -1e30 → exp(0) = 1; their
            # forward output is zeroed, so their gradient mass must be too
            p = jnp.where(s <= _NEG_BIG / 2, 0.0, p)
        dv_j = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
        dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vj.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, kj.astype(jnp.float32))
        dk_j = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
        return (dq, j + 1), (dk_j, dv_j)

    dq0 = jnp.zeros((b, h, sq, d), jnp.float32)
    seqs = (kb, vb) if mb is None else (kb, vb, mb)
    (dq, _), (dkb, dvb) = lax.scan(body, (dq0, jnp.int32(0)), seqs)
    dk = jnp.moveaxis(dkb, 0, 2).reshape(b, h, sk, d)
    dv = jnp.moveaxis(dvb, 0, 2).reshape(b, h, sk, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, use_pallas):
    o, _ = _flash_fwd_dispatch(q, k, v, scale, causal, block_q, block_k, use_pallas)
    return o


def _flash_fwd_dispatch(q, k, v, scale, causal, block_q, block_k, use_pallas):
    if use_pallas == "interpret":
        return _flash_fwd_pallas(q, k, v, scale, causal, block_q, block_k, True)
    if use_pallas and jax.default_backend() == "tpu":
        return _flash_fwd_pallas(q, k, v, scale, causal, block_q, block_k, False)
    return _flash_fwd_jnp(q, k, v, scale, causal, block_k)


def _flash_vjp_fwd(q, k, v, scale, causal, block_q, block_k, use_pallas):
    o, lse = _flash_fwd_dispatch(q, k, v, scale, causal, block_q, block_k, use_pallas)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(scale, causal, block_q, block_k, use_pallas, res, do):
    return _flash_bwd(scale, causal, block_k, res, do)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# -- padding-masked variant (the r14 gap burn-down: nn/transformer.py used
# to force the exact path for any masked batch) ------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_masked(q, k, v, mask, scale, causal, block_q, block_k,
                  use_pallas):
    o, _ = _flash_masked_fwd_dispatch(q, k, v, mask, scale, causal, block_q,
                                      block_k, use_pallas)
    return o


def _flash_masked_fwd_dispatch(q, k, v, mask, scale, causal, block_q,
                               block_k, use_pallas):
    if use_pallas == "interpret":
        return _flash_fwd_pallas(q, k, v, scale, causal, block_q, block_k,
                                 True, mask=mask)
    if use_pallas and jax.default_backend() == "tpu":
        return _flash_fwd_pallas(q, k, v, scale, causal, block_q, block_k,
                                 False, mask=mask)
    return _flash_fwd_jnp(q, k, v, scale, causal, block_k, mask=mask)


def _flash_masked_vjp_fwd(q, k, v, mask, scale, causal, block_q, block_k,
                          use_pallas):
    o, lse = _flash_masked_fwd_dispatch(q, k, v, mask, scale, causal,
                                        block_q, block_k, use_pallas)
    return o, (q, k, v, o, lse, mask)


def _flash_masked_vjp_bwd(scale, causal, block_q, block_k, use_pallas, res,
                          do):
    q, k, v, o, lse, mask = res
    dq, dk, dv = _flash_bwd(scale, causal, block_k, (q, k, v, o, lse), do,
                            mask=mask)
    return dq, dk, dv, jnp.zeros_like(mask)


_flash_masked.defvjp(_flash_masked_vjp_fwd, _flash_masked_vjp_bwd)


# From 1024 tokens the S x S score matrix of the exact path is what the
# online softmax exists to avoid. The only timing behind this threshold is
# the r3 table (2026-07: blockwise/exact 1.08 at 1024, 1.29 at 2048, fwd+bwd,
# bf16), taken on a backend whose name was not "tpu" — so by the tests in
# this module it timed the jnp blockwise path, not the Pallas kernel. The
# kernel first compiled on a chip in PR 21 and has not been ranked against
# the exact path (PERF.md, open questions).
FLASH_MIN_SEQ = 1024


def resolve_flash(flash, seq_q, seq_k, mask=None) -> bool:
    """Auto-dispatch rule for the attention layers: ``flash`` may be True,
    False, or "auto" (pick the Pallas path when the measured crossover says
    it wins — TPU backend, seq >= FLASH_MIN_SEQ). A (B, Tk) PADDING mask is
    flash-eligible since r14 (the kernel masks key blocks in-place); full
    [B, 1|H, Tq, Tk] attention masks still force the exact path."""
    if flash not in (True, False, "auto"):
        raise ValueError(
            f"flash must be True, False, or 'auto'; got {flash!r}")
    if mask is not None and jnp.asarray(mask).ndim != 2:
        return False
    if flash == "auto":
        return (jax.default_backend() == "tpu"
                and min(seq_q, seq_k) >= FLASH_MIN_SEQ)
    return bool(flash)


@op("flash_attention", "attention")
def flash_attention(
    q,
    k,
    v,
    scale: Optional[float] = None,
    causal: bool = False,
    block_q: int = 512,
    block_k: int = 512,
    use_pallas=True,
    mask=None,
):
    """Memory-efficient attention: [B,H,S,D] → [B,H,S,D], O(S) memory.

    Pallas kernel on TPU (``use_pallas="interpret"`` forces the interpreter for
    CPU tests), blockwise lax.scan elsewhere. ``mask``: optional (B, Sk)
    PADDING mask (1 = attend) applied to key blocks inside the kernel —
    masked-vs-exact equivalence is pinned in tests/test_kernels.py. Sequence
    lengths must divide the effective block sizes; callers fall back to
    ``dot_product_attention`` otherwise (the nn layers do this
    automatically).
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    sq, sk = q.shape[2], k.shape[2]
    bq, bk = min(block_q, sq), min(block_k, sk)
    if mask is not None:
        mask = jnp.asarray(mask)
        if mask.ndim != 2:
            raise ValueError(
                "flash_attention mask must be a (B, Sk) padding mask; full "
                f"attention masks take the exact path (got ndim {mask.ndim})")
    if sq % bq or sk % bk:
        amask = None if mask is None else mask[:, None, None, :]
        return dot_product_attention(q, k, v, mask=amask, scale=scale,
                                     causal=causal)
    if mask is not None:
        return _flash_masked(q, k, v, mask.astype(jnp.float32),
                             float(scale), bool(causal), bq, bk, use_pallas)
    return _flash(q, k, v, float(scale), bool(causal), bq, bk, use_pallas)


# ---------------------------------------------------------------------------
# Multi-head attention (ND4J multiHeadDotProductAttention parity)
# ---------------------------------------------------------------------------


def _split_heads(x, n_heads):
    b, t, f = x.shape
    return jnp.transpose(x.reshape(b, t, n_heads, f // n_heads), (0, 2, 1, 3))


def _merge_heads(x):
    b, h, t, dh = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(b, t, h * dh)


@op("multi_head_dot_product_attention", "attention",
    aliases=("multiHeadDotProductAttention", "mha"))
def multi_head_dot_product_attention(
    queries,
    keys,
    values,
    Wq,
    Wk,
    Wv,
    Wo,
    n_heads: int,
    mask=None,
    scale: Optional[float] = None,
    causal: bool = False,
    flash="auto",
):
    """Projected multi-head attention over [B, T, F] sequences.

    Wq/Wk/Wv: (F, H*Dh); Wo: (H*Dh, Fout). ``mask`` is a [B, Tk] padding mask
    (ND4J semantics: 1 = valid) or a full [B, 1|H, Tq, Tk] attention mask.
    ``flash``: True | False | "auto" (measured-crossover dispatch — see
    :func:`resolve_flash`).
    """
    q = _split_heads(queries @ Wq, n_heads)
    k = _split_heads(keys @ Wk, n_heads)
    v = _split_heads(values @ Wv, n_heads)
    if resolve_flash(flash, q.shape[2], k.shape[2], mask):
        pmask = None if mask is None else jnp.asarray(mask)
        o = flash_attention(q, k, v, scale=scale, causal=causal, mask=pmask)
    else:
        amask = None
        if mask is not None:
            mask = jnp.asarray(mask)
            amask = mask[:, None, None, :] if mask.ndim == 2 else mask
        o = dot_product_attention(q, k, v, mask=amask, scale=scale, causal=causal)
    return _merge_heads(o) @ Wo


# ---------------------------------------------------------------------------
# Paged KV-cache attention (serving/paged.py substrate)
# ---------------------------------------------------------------------------

#: K/V rows (streams x positions) one turn of :func:`paged_attention`
#: gathers, and the fewest positions a turn covers. Measured on a v5e
#: (PERF.md, PR 28): a gathered row costs about 30 ns up to 8,192 rows a
#: turn and twice that at 16,384; a turn costs some 10 us of its own; and a
#: shorter chunk stops nearer the longest stream's end. 2,048 rows (64
#: positions at 32 streams, 256 at 8) is within a tenth of the best chunk
#: on chat and on document traffic at both batch sizes.
PAGED_CHUNK_ROWS = 2048
PAGED_CHUNK_MIN_POSITIONS = 64


def paged_chunk_blocks(batch: int, max_blocks: int, block_size: int) -> int:
    """Whole blocks of the page table one turn of :func:`paged_attention`
    reads per stream: about ``PAGED_CHUNK_ROWS`` gathered rows a turn over
    the batch, at least ``PAGED_CHUNK_MIN_POSITIONS`` positions, at most the
    table. From static shapes only, so it is the same number inside the
    traced program and on the host that counts what a step read."""
    positions = max(PAGED_CHUNK_ROWS // int(batch), PAGED_CHUNK_MIN_POSITIONS)
    return max(1, min(positions // int(block_size), int(max_blocks)))


def paged_slots(tables, positions, block_size: int):
    """Flat pool slot of each logical position: ``tables`` (B, max_blocks)
    int32 page tables, ``positions`` (B, W) int32 -> (B, W) int32,
    ``tables[b, p // bs] * bs + p % bs``. A position past the table lands
    in the reserved trash block 0."""
    blk = jnp.take_along_axis(tables, positions // block_size, axis=1,
                              mode="fill", fill_value=0)
    return blk * block_size + positions % block_size


def paged_attention(q, k_pool, v_pool, tables, positions, block_size: int,
                    scale=None):
    """One decode/verify attention over a paged KV pool, read as far as
    the streams reach.

    ``q``: (B, H, W, Dh) — W query tokens per stream (1 for plain decode,
    the speculation window for verify, a prompt chunk for resumed /
    chunked prefill). ``k_pool``/``v_pool``: (S, H*Dh) slot-flat pools of
    one layer, one token's heads side by side in a row, block ``n``'s
    tokens at slots ``[n * bs, (n + 1) * bs)``.
    ``tables``: (B, max_blocks) int32 page tables (unallocated entries
    point at the trash block 0). ``positions``: (B, W) int32 — the logical
    position of each query token; key position ``p`` is attended iff
    ``p <= positions[b, w]`` (the causal-over-cache rule, identical to the
    contiguous ``decode_step``).

    The pass walks the table in chunks of :func:`paged_chunk_blocks` whole
    blocks and stops after the chunk that holds the largest position of
    the batch: a ``fori_loop`` whose trip count is data, so ONE program
    serves every context length and reads what the longest stream holds,
    not the declared maximum. Each turn gathers whole blocks, contracts
    ``q`` against them in the pool's own [b, k, h, d] layout and folds
    them into a float32 running (max, sum, accumulator) through
    :func:`online_softmax_update`. Every live key is attended in float32;
    against the dense softmax over all positions only the order of the
    sums differs (tokens identical, logits to rounding: docs/SERVING.md).

    Shared-prefix note (serving/paged.py): several rows of ``tables`` may
    hold the same physical blocks (a refcounted prefix-cache hit). The
    gather is read-only and position-masked per stream, so sharing is
    invisible here — K/V rows at position ``p`` are a pure function of
    the token prefix up to ``p``, which is what made the blocks
    shareable."""
    b, h, w, dh = q.shape
    bs = int(block_size)
    width = tables.shape[1]
    cb = paged_chunk_blocks(b, width, bs)
    chunk = cb * bs
    n_chunks = -(-width // cb)
    tables = jnp.pad(tables, ((0, 0), (0, n_chunks * cb - width)))  # trash
    kb = jnp.asarray(k_pool).reshape(-1, bs, h * dh)  # whole blocks
    vb = jnp.asarray(v_pool).reshape(-1, bs, h * dh)
    if scale is None:
        scale = 1.0 / (dh ** 0.5)
    qf = jnp.asarray(q, jnp.float32)
    turns = jnp.minimum(jnp.max(positions) // chunk + 1, n_chunks)

    def turn(i, carry):
        t = lax.dynamic_slice_in_dim(tables, i * cb, cb, axis=1)  # (B, cb)
        kc = kb[t].reshape(b, chunk, h, dh)
        vc = vb[t].reshape(b, chunk, h, dh)
        k_pos = i * chunk + jnp.arange(chunk)
        live = (k_pos[None, None, :] <= positions[:, :, None])[:, None]
        return online_softmax_update(qf, kc, vc, *carry, scale, kv_mask=live,
                                     qk="bhqd,bkhd->bhqk",
                                     pv="bhqk,bkhd->bhqd")

    m0 = jnp.full((b, h, w), _NEG_BIG, jnp.float32)
    l0 = jnp.zeros((b, h, w), jnp.float32)
    a0 = jnp.zeros((b, h, w, dh), jnp.float32)
    _, l, acc = lax.fori_loop(0, turns, turn, (m0, l0, a0))
    safe_l = jnp.where(l == 0.0, 1.0, l)
    return (acc / safe_l[..., None]).astype(q.dtype)


def latent_paged_attention(q_abs, pool, tables, positions, block_size: int,
                           v_dim: int, scale: float):
    """Decode attention of a latent (MLA) layer in absorbed form over its
    paged latent rows.

    ``pool``: (S, R) rows ``[c_t | kr_t]`` — the compressed key/value of a
    token and its shared extra key dims, one row a token for ALL heads (R
    the width the caller stores: nn/decoder.py pads rows and queries with
    zeros to whole 128-lane tiles, which add 0 to every score).
    ``q_abs``: (B, H, W, R) queries already carried into that row space,
    ``[qc_h W_uk,h^T | qr_h]``. Every head attends the same rows, so the
    heads are W more queries of one head whose key is the row and whose
    value is the row too: :func:`paged_attention` runs as it is, page table,
    chunking, trip count and float32 online softmax shared with the
    per-head K/V pools, and the first ``v_dim`` numbers of what it returns
    are ``sum_t p_t c_t``, (B, H, W, v_dim). The caller expands that
    through ``W_uv``. ``scale`` is the expanded form's (1/sqrt of the
    per-head query width), not 1/sqrt(R)."""
    b, h, w, r = q_abs.shape
    o = paged_attention(q_abs.reshape(b, 1, h * w, r), pool, pool, tables,
                        jnp.tile(positions, (1, h)), block_size, scale=scale)
    return o.reshape(b, h, w, r)[..., :v_dim]


def grouped_paged_attention(q, pool, tables, positions, block_size: int,
                            n_kv_heads: int, scale=None):
    """Decode attention of grouped query heads over paged key/value rows.

    ``pool``: (S, n_kv_heads * 2 * Dh) rows ``n_kv_heads x [k_t | v_t]``, one
    row a token. ``q``: (B, H, W, Dh), ``H`` a multiple of ``n_kv_heads``;
    head ``h`` reads key/value head ``h // (H / n_kv_heads)``. The query
    heads of a group are more queries of the group's one head, whose key
    AND value is the whole ``[k | v]`` part of the row, as a latent row is
    both in :func:`latent_paged_attention`: a query is carried as
    ``[q | 0]``, the zeros meet ``v`` and add 0 to a score, and the last
    ``Dh`` numbers of what :func:`paged_attention` returns are ``sum_t p_t
    v_t``. Page table, chunking, trip count and the float32 online softmax
    are that one pass's; no second walk of the table, no key or value pool
    sliced out of the rows. -> (B, H, W, Dh)."""
    b, h, w, dh = q.shape
    per = h // n_kv_heads
    if scale is None:
        scale = dh ** -0.5
    qz = jnp.concatenate([q, jnp.zeros_like(q)], axis=-1)
    o = paged_attention(qz.reshape(b, n_kv_heads, per * w, 2 * dh), pool,
                        pool, tables, jnp.tile(positions, (1, per)),
                        block_size, scale=scale)
    return o.reshape(b, h, w, 2 * dh)[..., dh:]
