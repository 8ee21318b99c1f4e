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
  On a TPU, with heads in whole 128-lane tiles, it is one Pallas kernel
  (``kda_prefill``): a chunk's intermediates and the running state stay on
  the chip, and a row's walk ends with the chunk its length ends in; the XLA
  form runs everywhere else and computes every chunk (docs/KERNELS.md).
- :func:`kda_step` — the decode step, one token against the state.
  :func:`kda_step_paged` runs it on the streams' slots of a state pool: on
  a TPU, with heads in whole 128-lane tiles and one token a row, one Pallas
  kernel (``kda_decode``) whose state block is the row's slot, read and
  written in place; everywhere else the rows are gathered, stepped and
  scattered.

A token with ``beta = 0`` and ``g = 0`` leaves the state as it was: that is
how padded positions and finished rows are kept from moving a live state.

The short convolution in front of a state mixer (KDA's q, k, v; ops/ssm.py's
x) lives here once: :func:`causal_conv` over whole prompts,
:func:`conv_tail` for what a decode step needs of them, and
:func:`conv_step_paged`, the decode step on the streams' slots of a tail
pool: on a TPU, one token a row and channels in whole lane tiles, one
Pallas kernel (``conv_step``) that reads a live stream's last K-1 inputs,
convolves and writes the new tail back in place; everywhere else gather,
``causal_conv``, ``conv_tail``, scatter.
"""

from __future__ import annotations

import functools
import math

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


def live_chunks(lengths, chunk: int = CHUNK):
    """Chunks of a row that hold a token, ``ceil(lengths / chunk)``: the
    length of its walk (an int or an array of lengths)."""
    return (lengths + chunk - 1) // chunk


def _kda_chunked_xla(q, k, v, g, beta, state, lengths=None,
                     chunk: int = CHUNK):
    """:func:`kda_chunked` as XLA programs: every chunk of the window is
    computed, whatever the lengths."""
    f32 = jnp.float32
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    if lengths is not None:
        live = (jnp.arange(t)[None, :] < lengths[:, None]).astype(f32)
        g, beta = g * live[..., None, None], beta * live[..., None]
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
    if lengths is not None:     # what the kernel writes where it computes nothing
        held = jnp.arange(n)[:, None] < live_chunks(lengths, chunk)[None, :]
        o = jnp.where(held[:, :, None, None, None], o, 0.0)
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, n * chunk, dv)
    return jnp.moveaxis(o, 1, 2)[:, :t], state


# -- the prefill as one Pallas TPU kernel -----------------------------------
HEAD_GROUP = 8      # heads of one grid step (docs/KERNELS.md: measured)


def _kda_chunk(q, k, v, g, beta, st, exact: bool):
    """One chunk of one head, on values held on the chip: q, k, g (C, dk),
    v (C, dv), beta (C, 1), ``st`` the state transposed (dv, dk), all
    float32 -> (o (C, dv), the state after the chunk). The arithmetic of
    :func:`_kda_chunked_xla`, product for product; ``exact`` keeps float32
    operands where a TPU takes one bfloat16 pass (the interpreter on a
    CPU, as XLA's default precision does there)."""
    f32 = jnp.float32
    c, dk = k.shape
    ns = c // SUB
    mxu = (lambda a: a) if exact else (lambda a: a.astype(jnp.bfloat16))

    def mm(a, b_, dims=((1,), (0,))):           # one pass, float32 sums
        return lax.dot_general(mxu(a), mxu(b_), (dims, ((), ())),
                               preferred_element_type=f32)

    dot = functools.partial(jnp.dot, preferred_element_type=f32)

    def pieces(a, n):       # a = sum of n bfloat16 pieces, to 8 n bits
        out = []
        for _ in range(n):
            out.append(a.astype(jnp.bfloat16))
            a = a - out[-1].astype(f32)
        return out

    def mm_hi(a, b_):       # float32 in three bfloat16 passes (Precision.HIGH)
        if exact:
            return dot(a, b_)
        (ah, al), (bh, bl) = pieces(a, 2), pieces(b_, 2)
        return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))

    def running_sum(x):     # along the chunk: 0/1 weights, so exact products
        tri = (col <= row).astype(f32 if exact else jnp.bfloat16)
        return sum(dot(tri, p) for p in ([x] if exact else pieces(x, 3)))

    def of_block(x, at):
        """Row ``at`` of each 16-row block of ``x``, held by all its rows."""
        return jnp.concatenate(
            [jnp.broadcast_to(x[i * SUB + at:i * SUB + at + 1],
                              (SUB, x.shape[1])) for i in range(ns)], axis=0)

    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    rel = col - (row // SUB) * SUB              # column within the row's block
    row1 = lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    gc = running_sum(g)                         # of the log decay
    # row block I sees the others about the running sum just before it
    ref = jnp.concatenate(
        [jnp.zeros((SUB, dk), f32)]
        + [jnp.broadcast_to(gc[i * SUB - 1:i * SUB], (SUB, dk))
           for i in range(1, ns)], axis=0)
    erow = jnp.exp(gc - ref)                                    # <= 1
    rows = jnp.concatenate([k * erow, q * erow], axis=0)        # (2C, dk)
    blk2 = lax.broadcasted_iota(jnp.int32, (2 * c, c), 0) % c // SUB
    off = jnp.zeros((2 * c, c), f32)
    for i in range(1, ns):
        r_i = gc[i * SUB - 1:i * SUB]
        cols = jnp.where(row1 < i * SUB,
                         k * jnp.exp(jnp.minimum(r_i - gc, 0.0)), 0.0)
        off = jnp.where(blk2 == i, mm(rows, cols, ((1,), (1,))), off)
    akk_off, aqk_off = off[:c], off[c:]
    # pairs inside a sub-block take the decay whole, a column at a time; the
    # same column eliminates below itself in the inverse of (I + A)'s
    # diagonal blocks: forward substitution, the sums in the XLA form's order
    dkk = jnp.zeros((c, c), f32)
    dqk = jnp.zeros((c, c), f32)
    inv = (row == col).astype(f32)
    for j in range(SUB):
        kd = of_block(k, j) * jnp.exp(jnp.minimum(gc - of_block(gc, j), 0.0))
        ck = jnp.sum(k * kd, axis=1, keepdims=True)
        cq = jnp.sum(q * kd, axis=1, keepdims=True)
        dkk = jnp.where(rel == j, ck, dkk)
        dqk = jnp.where(rel == j, cq, dqk)
        if j < SUB - 1:
            below = jnp.where(row1 % SUB > j, beta * ck, 0.0)
            inv = inv - below * of_block(inv, j)
    aqk = jnp.where(col <= row, aqk_off + dqk, 0.0)
    decay = jnp.exp(gc)                                         # <= 1
    rhs = jnp.concatenate([beta * k * decay, beta * v], axis=1)
    y, m = mm_hi(inv, rhs), mm_hi(inv, beta * akk_off)
    sol = y
    for _ in range(ns - 1):
        sol = y - mm_hi(m, sol)
    w, uv = sol[:, :dk], sol[:, dk:]
    u = uv - mm(w, st, ((1,), (1,)))
    o = mm(q * decay, st, ((1,), (1,))) + mm(aqk, u)
    last = gc[c - 1:c]
    kdec = k * jnp.exp(last - gc)                               # <= 1
    return o, st * jnp.exp(last) + mm(u, kdec, ((0,), (0,)))


def _kda_kernel(len_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref,
                o_ref, s_ref, *, chunk, heads, dk, dv, exact):
    """Grid (row, head group, chunk), the chunk innermost and sequential:
    the state's output block stays on the chip across it, transposed."""
    from jax.experimental import pallas as pl

    b, hg, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n = len_ref[b]

    @pl.when(c == 0)
    def _():
        for j in range(heads):
            s_ref[0, j] = s0_ref[0, j].T

    @pl.when(c * chunk >= n)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(c * chunk < n)
    def _():
        at = c * chunk + lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
        live = at < n
        lane = lax.broadcasted_iota(jnp.int32, beta_ref.shape[1:], 1)
        for j in range(heads):
            lk, lv = slice(j * dk, (j + 1) * dk), slice(j * dv, (j + 1) * dv)
            beta = jnp.sum(jnp.where(lane == hg * heads + j, beta_ref[0], 0.0),
                           axis=1, keepdims=True)
            o, st = _kda_chunk(
                q_ref[0, :, lk], k_ref[0, :, lk], v_ref[0, :, lv],
                jnp.where(live, g_ref[0, :, lk], 0.0),
                jnp.where(live, beta, 0.0), s_ref[0, j], exact)
            o_ref[0, :, lv] = o
            s_ref[0, j] = st

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        for j in range(heads):
            s_ref[0, j] = s_ref[0, j].T


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret", "head_group",
                                    "exact"))
def _kda_chunked_pallas(q, k, v, g, beta, state, lengths=None,
                        chunk: int = CHUNK, interpret: bool = False,
                        head_group: int = HEAD_GROUP, exact=None):
    """:func:`kda_chunked` as one kernel (module doc): needs ``dk`` and
    ``dv`` in whole 128-lane tiles. Jitted, so that a program of twenty such
    layers traces and lowers the kernel once and calls it twenty times.
    ``exact`` (float32 operands in every product) is the interpreter's way
    unless said: a test runs the chip's bfloat16 passes on a CPU with it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    if lengths is None:
        lengths = jnp.full((b,), t, jnp.int32)
    pad = -t % chunk
    flat = lambda a: jnp.pad(a.astype(f32).reshape(b, t, -1),
                             ((0, 0), (0, pad), (0, 0)))
    n = (t + pad) // chunk
    hg = math.gcd(h, head_group)

    def tokens(width, heads=True):
        # a chunk behind the row's last is not fetched: the block index stays
        return pl.BlockSpec(
            (1, chunk, width),
            lambda i, j, c, n_ref: (
                i, jnp.minimum(c, jnp.maximum(
                    live_chunks(n_ref[i], chunk) - 1, 0)), j if heads else 0))

    states = pl.BlockSpec((1, hg, dk, dv), lambda i, j, c, n_ref: (i, j, 0, 0))
    o, s = pl.pallas_call(
        functools.partial(_kda_kernel, chunk=chunk, heads=hg, dk=dk, dv=dv,
                          exact=interpret if exact is None else exact),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, h // hg, n),
            in_specs=[tokens(hg * dk), tokens(hg * dk), tokens(hg * dv),
                      tokens(hg * dk), tokens(h, heads=False), states],
            out_specs=[pl.BlockSpec((1, chunk, hg * dv),
                                    lambda i, j, c, n_ref: (i, c, j)),
                       states]),
        out_shape=[jax.ShapeDtypeStruct((b, t + pad, h * dv), f32),
                   jax.ShapeDtypeStruct((b, h, dk, dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="kda_prefill",
    )(lengths.astype(jnp.int32), flat(q), flat(k), flat(v), flat(g),
      flat(beta), state.astype(f32))
    return o[:, :t].reshape(b, t, h, dv), s


def _kernel_shapes(*arrays) -> bool:
    """Where the Pallas kernels run: a TPU, every array's last dim (a head,
    the convolution's channels) in whole 128-lane tiles."""
    return (jax.default_backend() == "tpu"
            and all(a.shape[-1] % 128 == 0 for a in arrays))


def kda_chunked(q, k, v, g, beta, state, lengths=None, chunk: int = CHUNK):
    """The recurrence chunk-wise: the arguments and results of
    :func:`kda_recurrent`, plus ``lengths`` (B,): a token at or behind its
    row's length leaves the state alone, and ``o`` is 0 in every chunk that
    starts there (a row of length 0 returns the state it was given). ``T``
    is padded to whole chunks. On a TPU, with heads in whole 128-lane
    tiles, one kernel walks the chunks the lengths cover
    (:func:`_kda_chunked_pallas`); everywhere else the XLA form runs."""
    if _kernel_shapes(q, v):
        return _kda_chunked_pallas(q, k, v, g, beta, state, lengths, chunk)
    return _kda_chunked_xla(q, k, v, g, beta, state, lengths, chunk)


# -- the decode step in place in the pool ------------------------------------
DECODE_HEAD_GROUP = 16  # heads of one grid step (docs/KERNELS.md: measured)


def _kda_decode_kernel(slot_ref, x_ref, v_ref, s_in, o_ref, s_out, t_ref, *,
                       heads):
    """Grid (row, head group). ``x_ref`` holds the group's q | k | exp(g) |
    beta k, a head a row (4 x heads, dk); transposed once a grid step they
    are the columns that meet the state's rows. The state block is the
    row's slot of the pool, read and written in place; a dead row (slot 0)
    keeps the trash slot's first block as it is and computes nothing."""
    from jax.experimental import pallas as pl

    live = slot_ref[pl.program_id(0)] != 0

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        s_out[...] = s_in[...]

    @pl.when(live)
    def _():
        t_ref[0:4 * heads, :] = x_ref[0, 0]
        cols = t_ref[...].T                       # (dk, 128): a head a lane
        for j in range(heads):
            col = lambda n: cols[:, n * heads + j:n * heads + j + 1]
            s = s_in[0, j] * col(2)
            d = v_ref[0, 0, j:j + 1, :] \
                - jnp.sum(s * col(1), axis=0, keepdims=True)
            s = s + col(3) * d
            s_out[0, j] = s
            o_ref[0, 0, j:j + 1, :] = jnp.sum(s * col(0), axis=0,
                                              keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret", "head_group"))
def _kda_decode_pallas(q, k, v, g, beta, pool, slots, interpret: bool = False,
                       head_group: int = DECODE_HEAD_GROUP):
    """:func:`kda_step` on the slots of a pool, in place: q, k, g (B, H,
    dk), v (B, H, dv), beta (B, H), ``pool`` (slots, H, dk, dv) float32,
    ``slots`` (B,) with every dead row on the trash slot 0 -> (o (B, H,
    dv), pool). The state's block index is ``slots[row]`` for input and
    output alike and the pool is aliased input to output, so a live
    stream's state moves once each way and no other slot is touched; dead
    rows share one block of the trash slot, which is fetched once a run of
    them. Float32 on the vector unit throughout. Jitted, so that a program
    of twenty such layers lowers the kernel once."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    b, h, dk = q.shape
    dv = v.shape[-1]
    hg = math.gcd(h, min(head_group, 32))     # 4 x hg rows of a 128-row tile
    ng = h // hg
    grp = lambda a: a.astype(f32).reshape(b, ng, hg, -1)
    x = jnp.concatenate([grp(q), grp(k), jnp.exp(grp(g)),
                         grp(beta.astype(f32)[..., None] * k)], axis=2)
    # a dead row's blocks are those of the dead step before it: not fetched
    group = lambda i, j, s: jnp.where(s[i] == 0, 0, j)
    rows = lambda i, j, s: (jnp.where(s[i] == 0, 0, i), group(i, j, s), 0, 0)
    states = pl.BlockSpec((1, hg, dk, dv),
                          lambda i, j, s: (s[i], group(i, j, s), 0, 0))
    o, pool = pl.pallas_call(
        functools.partial(_kda_decode_kernel, heads=hg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, ng),
            in_specs=[pl.BlockSpec((1, 1, 4 * hg, dk), rows),
                      pl.BlockSpec((1, 1, hg, dv), rows), states],
            out_specs=[pl.BlockSpec((1, 1, hg, dv),
                                    lambda i, j, s: (i, j, 0, 0)), states],
            scratch_shapes=[pltpu.VMEM((128, dk), f32)]),
        out_shape=[jax.ShapeDtypeStruct((b, ng, hg, dv), f32),
                   jax.ShapeDtypeStruct(pool.shape, f32)],
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name="kda_decode",
    )(slots.astype(jnp.int32), x, grp(v), pool)
    return o.reshape(b, h, dv), pool


def kda_step_paged(q, k, v, g, beta, pool, slots, live):
    """The decode window on the streams' slots of a state pool: q, k, g
    (B, W, H, dk), v (B, W, H, dv), beta (B, W, H), ``pool`` (slots, H, dk,
    dv) float32, ``slots`` (B,), ``live`` (B, W) bool -> (o (B, W, H, dv),
    pool). A token that is not live moves no state. On a TPU, with heads in
    whole 128-lane tiles and a window of one token, each live row's state
    is read from its slot and written back there by one kernel
    (:func:`_kda_decode_pallas`) and a dead row names the trash slot;
    everywhere else the rows are gathered, stepped and scattered."""
    if q.shape[1] == 1 and _kernel_shapes(q, v):
        o, pool = _kda_decode_pallas(
            q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], pool,
            jnp.where(live[:, 0], slots, 0))
        return o[:, None], pool
    m = live.astype(jnp.float32)
    o, s = kda_recurrent(q, k, v, g * m[..., None, None],
                         beta * m[..., None], pool[slots])
    return o, pool.at[slots].set(s)


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


# -- the convolution's decode step in place in the tail pool -----------------
TAIL_ROWS = 8   # rows of one tile: slots of a flat pool's block, rows of x


def _taps(seen, w_ref):
    """``sum_j w[j] seen[j]`` in :func:`causal_conv`'s order."""
    y = seen[0] * w_ref[0:1, :]
    for j in range(1, len(seen)):
        y = y + seen[j] * w_ref[j:j + 1, :]
    return y


def _conv_slots_kernel(x_ref, live_ref, w_ref, t_in, y_ref, t_out, *, c):
    """Grid (group of TAIL_ROWS slots) over a flat pool: a tile of it holds
    a lane tile of 8 slots, so the group is read whole, its live slots
    stepped on whole tiles (``x_ref``: their rows' inputs, in slot order)
    and written back whole, a slot no live row names as it was read."""
    live = live_ref[:, 0:1] != 0
    width = w_ref.shape[0] - 1
    seen = [t_in[:, j * c:(j + 1) * c] for j in range(width)] + [x_ref[...]]
    y_ref[...] = jnp.where(live, _taps(seen, w_ref), 0.0)
    for j in range(width):
        t_out[:, j * c:(j + 1) * c] = jnp.where(live, seen[j + 1], seen[j])


def _conv_rows_kernel(slot_ref, x_ref, w_ref, t_in, y_ref, t_out):
    """Grid (block of TAIL_ROWS rows, row of the block) over a pool of
    (slots, K-1, C): the tail block is the row's slot, read and written in
    place; a dead row (slot 0) keeps the trash slot as it is and reads 0."""
    from jax.experimental import pallas as pl

    r = pl.program_id(1)
    live = slot_ref[pl.program_id(0) * TAIL_ROWS + r] != 0
    at = pl.ds(r, 1)
    width = w_ref.shape[0] - 1

    @pl.when(jnp.logical_not(live))
    def _():
        y_ref[at, :] = jnp.zeros((1, y_ref.shape[1]), y_ref.dtype)
        t_out[...] = t_in[...]

    @pl.when(live)
    def _():
        seen = [t_in[0, j:j + 1, :] for j in range(width)] + [x_ref[at, :]]
        y_ref[at, :] = _taps(seen, w_ref)
        for j in range(width):
            t_out[0, j:j + 1, :] = seen[j + 1]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _conv_step_pallas(raw, w, pool, slots, interpret: bool = False):
    """One token a row on the slots of a tail pool, in place: ``raw`` (B,
    C), ``w`` (K, C), ``pool`` (slots, K-1, C) or flat (slots, (K-1) C)
    float32, ``slots`` (B,) with every dead row on the trash slot 0 -> (y
    (B, C), pool); a dead row's y is 0. The pool is aliased input to output
    and walked as the device tiles it. (slots, K-1, C) is tiled a slot: the
    tail's block index is ``slots[row]`` for input and output alike, a live
    stream's tail moves once each way and no other slot is touched. A flat
    pool is tiled 8 slots a tile (Mosaic takes no block and no copy of one
    row of it), so it is walked 8 slots a grid step, every group read and
    written back whole, the rows' inputs gathered into slot order before
    the kernel and y back into row order behind it: its cost goes by the
    pool's slots, not by the live rows. Jitted, so that a program of 26
    such layers lowers the kernel once."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    b, c = raw.shape
    kk = w.shape[0]
    raw, w, slots = raw.astype(f32), w.astype(f32), slots.astype(jnp.int32)
    call = functools.partial(
        pl.pallas_call, input_output_aliases={3: 1}, interpret=interpret,
        name="conv_step")
    if pool.ndim == 2:
        n = -(-pool.shape[0] // TAIL_ROWS) * TAIL_ROWS
        named = (jnp.arange(n)[:, None] == slots[None, :]) \
            & (slots != 0)[None, :]                         # (slot, row)
        live = jnp.broadcast_to(
            jnp.any(named, axis=1).astype(jnp.int32)[:, None], (n, 128))
        block = lambda width: pl.BlockSpec((TAIL_ROWS, width),
                                           lambda g: (g, 0))
        y, pool = call(
            functools.partial(_conv_slots_kernel, c=c),
            grid=(n // TAIL_ROWS,),
            in_specs=[block(c), block(128),
                      pl.BlockSpec((kk, c), lambda g: (0, 0)),
                      block(pool.shape[1])],
            out_specs=[block(c), block(pool.shape[1])],
            out_shape=[jax.ShapeDtypeStruct((n, c), f32),
                       jax.ShapeDtypeStruct(pool.shape, f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
        )(raw[jnp.argmax(named, axis=1)], live, w, pool)
        return y[slots], pool
    pad = -b % TAIL_ROWS
    block = pl.BlockSpec((TAIL_ROWS, c), lambda i, r, s: (i, 0))
    tails = pl.BlockSpec((1,) + pool.shape[1:],
                         lambda i, r, s: (s[i * TAIL_ROWS + r], 0, 0))
    y, pool = call(
        _conv_rows_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=((b + pad) // TAIL_ROWS, TAIL_ROWS),
            in_specs=[block, pl.BlockSpec((kk, c), lambda i, r, s: (0, 0)),
                      tails],
            out_specs=[block, tails]),
        out_shape=[jax.ShapeDtypeStruct((b + pad, c), f32),
                   jax.ShapeDtypeStruct(pool.shape, f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(jnp.pad(slots, (0, pad)), jnp.pad(raw, ((0, pad), (0, 0))), w, pool)
    return y[:b], pool


def _conv_step_xla(raw, w, pool, slots, live):
    """:func:`conv_step_paged` as XLA programs: the rows' tails gathered,
    :func:`causal_conv`, :func:`conv_tail` of what each row has now seen,
    and the scatter back (a token that is not live leaves its row's tail as
    it was)."""
    width = w.shape[0] - 1
    before = pool[slots].reshape(raw.shape[0], width, raw.shape[2])
    seen = jnp.concatenate([before, raw], axis=1)
    tail = conv_tail(seen, width + jnp.sum(live, axis=1), width)
    return (causal_conv(raw, w, before),
            pool.at[slots].set(tail.reshape((-1,) + pool.shape[1:])))


def conv_step_paged(raw, w, pool, slots, live):
    """The decode window's convolution on the streams' slots of a tail
    pool: ``raw`` (B, W, C) the projections before the convolution, ``w``
    (K, C), ``pool`` (slots, K-1, C) or flat (slots, (K-1) C) float32,
    ``slots`` (B,), ``live`` (B, W) bool -> (y (B, W, C), pool). ``y`` is
    ``causal_conv(raw, w, tail)`` before any bias or activation, and a
    stream's new tail its last K-1 inputs with the live tokens of the
    window behind them. On a TPU, with a window of one token and the
    channels in whole 128-lane tiles (the state kernels' predicate), each
    live row's tail is read from its slot and written back there by one
    kernel (:func:`_conv_step_pallas`) and a dead row names the trash slot;
    everywhere else the rows are gathered, convolved and scattered
    (:func:`_conv_step_xla`)."""
    if raw.shape[1] == 1 and _kernel_shapes(raw):
        y, pool = _conv_step_pallas(raw[:, 0], w, pool,
                                    jnp.where(live[:, 0], slots, 0))
        return y[:, None], pool
    return _conv_step_xla(raw, w, pool, slots, live)
