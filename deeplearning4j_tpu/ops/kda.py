"""Kimi Delta Attention (KDA): gated delta-rule linear attention with a
per-channel decay (Kimi Linear report, arXiv:2510.26692, section 3;
flash-linear-attention ``KimiDeltaAttention``).

Per head, with a state ``S`` of (dk, dv) float32 numbers, a key ``k_t`` and
query ``q_t`` of dk numbers, a value ``v_t`` of dv, a per-channel log decay
``g_t <= 0`` (dk) and a write strength ``beta_t`` in (0, 1):

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

Three forms of the same recurrence live here:

- :func:`kda_recurrent` — one ``lax.scan`` step a token; the oracle.
- :func:`kda_chunked` — the prefill: chunks of 64 tokens in the WY/UT form.
  Inside a chunk the updates ``u_t = beta_t (v_t - S'_t^T k_t)`` solve one
  unit-lower-triangular system ``(I + A) U = beta (V - K~ S_0)`` with
  ``A[t, i] = beta_t sum_d k_t[d] k_i[d] exp(G_t[d] - G_i[d])`` (``G`` the
  running sum of ``g`` in the chunk), so a chunk costs a few matrix
  products and the state is touched once a chunk. Every ``exp`` is of a
  number <= 0: pairs in different 16-token sub-blocks split the decay about
  the row block's first token, pairs inside one sub-block take it as it is.
- :func:`kda_step` — the decode step, one token against the state.

A token with ``beta = 0`` and ``g = 0`` leaves the state as it was: that is
how padded positions and finished rows are kept from moving a live state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 64
SUB = 16


def kda_step(q, k, v, g, beta, state):
    """One token: q, k, g (B, H, dk); v (B, H, dv); beta (B, H); state
    (B, H, dk, dv) float32 -> (o (B, H, dv), state)."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    s = state * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - jnp.einsum("bhk,bhkv->bhv", k, s))
    s = s + k[..., None] * u[..., None, :]
    return jnp.einsum("bhk,bhkv->bhv", q, s), s


def kda_recurrent(q, k, v, g, beta, state):
    """The recurrence a token at a time: q, k, g (B, T, H, dk); v
    (B, T, H, dv); beta (B, T, H) -> (o (B, T, H, dv), state)."""
    def one(s, x):
        o, s = kda_step(*x, s)
        return s, o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    state, o = lax.scan(one, state.astype(jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1), state


def _pair_products(x, k, gc):
    """``P[t, i] = sum_d x_t[d] k_i[d] exp(G_t[d] - G_i[d])`` for ``i <= t``
    within a chunk (0 above the diagonal): x, k, gc (..., C, d), gc the
    inclusive running sum of the log decay. No exponent is positive."""
    *lead, c, d = x.shape
    ns = c // SUB
    blk = lambda a: a.reshape(*lead, ns, SUB, d)
    xb, kb, gb = blk(x), blk(k), blk(gc)
    # the reference point of row block I: the running sum just before it
    ref = jnp.concatenate([jnp.zeros_like(gb[..., :1, 0, :]),
                           gb[..., :-1, -1, :]], axis=-2)      # (ns, d)
    rows = xb * jnp.exp(gb - ref[..., :, None, :])             # <= 1
    # columns as row block I sees them: exp(ref_I - G_i), i before block I
    before = (jnp.arange(c)[None, :] < (jnp.arange(ns) * SUB)[:, None])
    expo = ref[..., :, None, :] - gc[..., None, :, :]          # (ns, C, d)
    cols = jnp.where(before[..., None],
                     k[..., None, :, :] * jnp.exp(jnp.minimum(expo, 0.0)),
                     0.0)
    off = jnp.einsum("...nsd,...ncd->...nsc", rows, cols)      # (ns,SUB,C)
    # pairs inside one sub-block: the decay between them, taken whole
    tri = jnp.tril(jnp.ones((SUB, SUB), bool))
    dec = jnp.exp(jnp.minimum(gb[..., :, None, :] - gb[..., None, :, :], 0.0))
    diag = jnp.sum(xb[..., :, None, :] * kb[..., None, :, :] * dec, axis=-1)
    diag = jnp.where(tri, diag, 0.0)                           # (ns,SUB,SUB)
    eye = jnp.eye(ns, dtype=diag.dtype)
    full = off.reshape(*lead, ns, SUB, ns, SUB) \
        + diag[..., :, :, None, :] * eye[:, None, :, None]
    return full.reshape(*lead, c, c)


def _unit_lower_solve(a, rhs):
    """``(I + a) x = rhs`` for a strictly lower triangular ``a`` (..., C, C)
    and ``rhs`` (..., C, R), by forward substitution in 16-row blocks.

    The diagonal blocks ``D_i`` are inverted row by row, 15 steps on an
    array laid out (16, 16, systems) so that no 16 x 16 matrix meets the
    chip's (8, 128) tiles. With ``Dinv`` the block-diagonal of those
    inverses and ``M = Dinv a_off`` (``a_off``: ``a`` below the diagonal
    blocks), ``x = y - M x`` with ``y = Dinv rhs``; ``M`` is block-strictly
    lower, so starting from ``x = y`` block ``i`` is exact after ``i``
    sweeps: block forward substitution written as ``C / 16 - 1`` whole
    C x C products. XLA's own triangular solve took 65 ms a layer on a v5e
    for the prefill's 8,192 systems of 64 rows, two thirds of the whole
    prefill (PERF.md, PR 29). Float32 in three bfloat16 passes: the products
    are small, and a substitution rounded to one pass would carry its error
    forward."""
    hi = lax.Precision.HIGH
    *lead, c, _ = a.shape
    ns = c // SUB
    ab = a.reshape(*lead, ns, SUB, ns, SUB)
    eye_b = jnp.eye(ns, dtype=a.dtype)[:, None, :, None]
    diag = jnp.einsum("...isjt,ij->...ist", ab, jnp.eye(ns, dtype=a.dtype))
    d = jnp.moveaxis(diag.reshape(-1, SUB, SUB), 0, -1)      # (SUB, SUB, N)
    eye = jnp.eye(SUB, dtype=a.dtype)

    def row(i, inv):            # row i of the inverse from the rows above it
        d_i = lax.dynamic_index_in_dim(d, i, 0, keepdims=False)   # (SUB, N)
        new = eye[i][:, None] - jnp.sum(d_i[:, None, :] * inv, axis=0)
        return lax.dynamic_update_index_in_dim(inv, new, i, 0)

    inv = lax.fori_loop(1, SUB, row,
                        jnp.broadcast_to(eye[:, :, None], d.shape))
    inv = jnp.moveaxis(inv, -1, 0).reshape(*lead, ns, SUB, SUB)
    dinv = (inv[..., :, :, None, :] * eye_b).reshape(*lead, c, c)
    a_off = (ab * (1.0 - eye_b)).reshape(*lead, c, c)
    mm = lambda p, q: jnp.einsum("...st,...tr->...sr", p, q, precision=hi)
    y, m = mm(dinv, rhs), mm(dinv, a_off)
    x = y
    for _ in range(ns - 1):
        x = y - mm(m, x)
    return x


def kda_chunked(q, k, v, g, beta, state, chunk: int = CHUNK):
    """The recurrence chunk-wise: the same arguments and results as
    :func:`kda_recurrent`. ``T`` is padded to whole chunks with tokens that
    leave the state alone."""
    f32 = jnp.float32
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % chunk
    if pad:
        z = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        q, k, v, g, beta = z(q), z(k), z(v), z(g), z(beta)
    n = (t + pad) // chunk
    # (B, T, H, d) -> (B, H, n, C, d)
    split = lambda a: jnp.moveaxis(
        a.astype(f32).reshape(b, n, chunk, h, -1), 3, 1)
    q, k, v, g = split(q), split(k), split(v), split(g)
    beta = split(beta[..., None])                               # (..., C, 1)
    gc = jnp.cumsum(g, axis=-2)
    a = beta * jnp.where(jnp.tril(jnp.ones((chunk, chunk), bool), -1),
                         _pair_products(k, k, gc), 0.0)
    aqk = _pair_products(q, k, gc)
    decay = jnp.exp(gc)                                         # <= 1
    rhs = jnp.concatenate([beta * k * decay, beta * v], axis=-1)
    sol = _unit_lower_solve(a, rhs)
    w, uv = sol[..., :dk], sol[..., dk:]
    qd = q * decay
    last = gc[..., -1:, :]
    kdec = k * jnp.exp(last - gc)                               # <= 1
    gamma = jnp.exp(last[..., 0, :])                            # (B,H,n,dk)

    def one(s, x):
        w_c, uv_c, qd_c, aqk_c, kdec_c, gamma_c = x
        u = uv_c - jnp.einsum("bhck,bhkv->bhcv", w_c, s)
        o = jnp.einsum("bhck,bhkv->bhcv", qd_c, s) \
            + jnp.einsum("bhci,bhiv->bhcv", aqk_c, u)
        s = gamma_c[..., None] * s \
            + jnp.einsum("bhck,bhcv->bhkv", kdec_c, u)
        return s, o

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (w, uv, qd, aqk, kdec, gamma))
    state, o = lax.scan(one, state.astype(f32), xs)             # o (n,B,H,C,dv)
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, n * chunk, dv)
    return jnp.moveaxis(o, 1, 2)[:, :t], state


def causal_conv(x, w, tail=None):
    """Depthwise causal convolution over time: x (B, T, C), w (K, C) ->
    (B, T, C), ``y_t = sum_j w[j] x_{t-K+1+j}``. ``tail`` (B, K-1, C) are the
    inputs before the first (zeros when there are none)."""
    kk = w.shape[0]
    if tail is None:
        tail = jnp.zeros((x.shape[0], kk - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    t = x.shape[1]
    return sum(xp[:, j:j + t] * w[j] for j in range(kk))


def conv_tail(x, lengths, width: int):
    """The last ``width`` inputs of each row before position ``lengths``
    (zeros before the start): x (B, T, C) -> (B, width, C); what the decode
    step's convolution needs of the prompt."""
    idx = lengths[:, None] - width + jnp.arange(width)[None, :]  # (B, width)
    got = jnp.take_along_axis(x, jnp.maximum(idx, 0)[..., None], axis=1)
    return jnp.where((idx >= 0)[..., None], got, 0)
