"""The selective state-space scan of Mamba (arXiv:2312.00752, section 3.2;
as Jamba stacks it, arXiv:2403.19887).

Per channel ``c`` of ``Ch`` and state ``n`` of ``N``, with an input ``x_t``
(already through the short convolution and SiLU), a step ``dt_t > 0``, the
input-dependent ``B_t`` and ``C_t`` (N numbers a token, shared by every
channel), the decay rates ``A < 0`` (Ch, N) and the skip ``D`` (Ch):

    s_t[n, c] = exp(dt_t[c] A[c, n]) s_{t-1}[n, c] + dt_t[c] B_t[n] x_t[c]
    y_t[c]    = (sum_n s_t[n, c] C_t[n] + D[c] x_t[c]) silu(z_t[c])

with ``z`` the mixer's output gate, applied in the same pass.

Nothing is contracted but the 16 states of a channel, so this is work for
the vector unit, one exponential a state a token. A state is KEPT (N, Ch),
the channels on the lanes: a TPU tile is 8 x 128, and 16 states on the lanes
would fill an eighth of it.

- :func:`selective_scan` — the prefill. On a TPU, with the channels in
  whole 128-lane tiles, one Pallas kernel (``ssm_scan``): the float32 state
  of a block of channels stays in VMEM while the row's positions go by in
  chunks, and a row's walk ends with the 8 positions its length ends in, so
  no (rows, positions, Ch, N) array exists anywhere. Elsewhere the same
  recurrence as one ``lax.scan`` step a token.
- :func:`selective_step_paged` — the decode step on the streams' slots of a
  state pool. On a TPU, one token a row, one Pallas kernel (``ssm_step``)
  whose state block is the row's slot, read and written in place (the pool
  aliased input to output, as ops/kda.py's ``kda_decode``); elsewhere the
  rows are gathered, stepped and scattered.

A token with ``dt = 0`` leaves the state as it was (``exp(0) = 1`` and it
adds 0): that is how padded positions and finished rows are kept from moving
a live state. Everything is float32. The short convolution in front of the
scan and its tail are ops/kda.py's ``causal_conv`` and ``conv_tail``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
CHUNK = 128     # positions of one grid step of ``ssm_scan`` (a lane each)
SUB = 8         # positions whose outputs are one tile; a walk ends at one
CHANNELS = 512  # channels of one grid step of ``ssm_scan``
ROWS = 8        # rows whose inputs are one block of ``ssm_step``
LANES = 128


def _gate(y, z):
    return y * (z * jax.nn.sigmoid(z))


def _selective_scan_xla(x, dt, a, bm, cm, d, state, lengths, z):
    """:func:`selective_scan` a token at a time; ``a`` is (N, Ch)."""
    if lengths is not None:
        live = jnp.arange(x.shape[1])[None, :] < lengths[:, None]
        dt = jnp.where(live[..., None], dt, 0.0)

    def one(s, inp):
        x_t, dt_t, b_t, c_t = inp             # (B, Ch), (B, Ch), (B, N) x 2
        s = jnp.exp(dt_t[:, None, :] * a) * s \
            + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        return s, jnp.sum(s * c_t[:, :, None], axis=1)

    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, bm, cm))
    state, y = lax.scan(one, state, xs)
    y = _gate(jnp.moveaxis(y, 0, 1) + d * x, z)
    return (y if lengths is None else jnp.where(live[..., None], y, 0.0),
            state)


# -- the prefill as one Pallas TPU kernel -----------------------------------
def _scan_kernel(len_ref, x_ref, dt_ref, z_ref, bc_ref, a_ref, d_ref, s0_ref,
                 y_ref, s_ref, *, n_state: int):
    """Grid (row, channel block, chunk), the chunk innermost and sequential:
    the state's output block stays in VMEM across it. ``bc_ref`` holds the
    chunk's B over C, (2N, CHUNK): a position's are one column, which meets
    the state's rows without a transpose."""
    from jax.experimental import pallas as pl

    i, c = pl.program_id(0), pl.program_id(2)
    n = len_ref[i]

    @pl.when(c == 0)
    def _():
        s_ref[...] = s0_ref[...]

    a, skip, bc_all = a_ref[...], d_ref[...], bc_ref[0]     # (2N, CHUNK)
    width = x_ref.shape[2]
    row = lax.broadcasted_iota(jnp.int32, (SUB, width), 0)
    for g in range(CHUNK // SUB):
        start = c * CHUNK + g * SUB
        at = slice(g * SUB, (g + 1) * SUB)

        @pl.when(start >= n)
        def _():
            y_ref[0, at, :] = jnp.zeros((SUB, width), F32)

        @pl.when(start < n)
        def _():
            live = start + row < n
            xs = x_ref[0, at, :]
            dts = jnp.where(live, dt_ref[0, at, :], 0.0)
            dtx = dts * xs
            s = s_ref[0]
            ys = jnp.zeros((SUB, width), F32)
            for k in range(SUB):
                t = g * SUB + k
                s = jnp.exp(dts[k:k + 1] * a) * s \
                    + dtx[k:k + 1] * bc_all[:n_state, t:t + 1]
                y = jnp.sum(s * bc_all[n_state:, t:t + 1], axis=0,
                            keepdims=True)
                ys = jnp.where(row == k, y, ys)
            s_ref[0] = s
            ys = _gate(ys + skip * xs, z_ref[0, at, :])
            y_ref[0, at, :] = jnp.where(live, ys, 0.0)


def _channel_block(ch: int) -> int:
    """The widest block of whole lane tiles up to :data:`CHANNELS` that
    divides ``ch`` (``ch`` itself where it is no whole tiles: the
    interpreter only)."""
    if ch % LANES:
        return ch
    return max(w for w in range(LANES, min(CHANNELS, ch) + 1, LANES)
               if ch % w == 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _selective_scan_pallas(x, dt, a, bm, cm, d, state, lengths, z,
                           interpret: bool = False):
    """:func:`selective_scan` as one kernel (module doc); ``a`` is (N, Ch).
    Jitted, so that a program of 26 such layers lowers the kernel once."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, ch = x.shape
    n = a.shape[0]
    if lengths is None:
        lengths = jnp.full((b,), t, jnp.int32)
    pad = -t % CHUNK
    tp = t + pad
    over = lambda v: jnp.pad(v.astype(F32), ((0, 0), (0, pad), (0, 0)))
    # B over C with the positions on the lanes: (B, 2N, T)
    bc = jnp.moveaxis(over(jnp.concatenate([bm, cm], axis=-1)), 1, 2)
    cb = _channel_block(ch)

    # a chunk behind the row's last is not fetched: the block index stays
    last = lambda i, c, n_ref: jnp.minimum(
        c, jnp.maximum((n_ref[i] + CHUNK - 1) // CHUNK - 1, 0))
    tokens = pl.BlockSpec((1, CHUNK, cb),
                          lambda i, j, c, n_ref: (i, last(i, c, n_ref), j))
    states = pl.BlockSpec((1, n, cb), lambda i, j, c, n_ref: (i, 0, j))
    per_channel = lambda rows: pl.BlockSpec(
        (rows, cb), lambda i, j, c, n_ref: (0, j))
    y, s = pl.pallas_call(
        functools.partial(_scan_kernel, n_state=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, ch // cb, tp // CHUNK),
            in_specs=[tokens] * 3 + [
                pl.BlockSpec((1, 2 * n, CHUNK),
                             lambda i, j, c, n_ref: (i, 0, last(i, c, n_ref))),
                per_channel(n), per_channel(1), states],
            out_specs=[pl.BlockSpec((1, CHUNK, cb),
                                    lambda i, j, c, n_ref: (i, c, j)),
                       states]),
        out_shape=[jax.ShapeDtypeStruct((b, tp, ch), F32),
                   jax.ShapeDtypeStruct((b, n, ch), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="ssm_scan",
    )(lengths.astype(jnp.int32), over(x), over(dt), over(z), bc,
      a.astype(F32), d.astype(F32).reshape(1, ch), state.astype(F32))
    return y[:, :t], s


def _kernel_shapes(x, a) -> bool:
    """Where the Pallas kernels run: a TPU, channels in whole 128-lane
    tiles, states in whole 8-sublane tiles."""
    return (jax.default_backend() == "tpu" and x.shape[-1] % LANES == 0
            and a.shape[-1] % 8 == 0)


def selective_scan(x, dt, A, B, C, D, state, lengths, z):
    """The recurrence over whole rows (module doc): x, dt, z (B, T, Ch); A
    (Ch, N); B, C (B, T, N); D (Ch,); ``state`` (B, N, Ch) float32;
    ``lengths`` (B,), or None for whole rows -> (y (B, T, Ch), state). A
    position at or behind its row's length moves no state and its ``y`` is
    0 (a row of length 0 returns the state it was given)."""
    args = (x.astype(F32), dt.astype(F32), jnp.transpose(A).astype(F32),
            B.astype(F32), C.astype(F32), D.astype(F32), state.astype(F32),
            lengths, z.astype(F32))
    if _kernel_shapes(x, A):
        return _selective_scan_pallas(*args)
    return _selective_scan_xla(*args)


# -- the decode step in place in the pool ------------------------------------
def _step_kernel(slot_ref, x_ref, dt_ref, z_ref, bc_ref, a_ref, d_ref, s_in,
                 y_ref, s_out, *, n_state: int):
    """Grid (block of ROWS rows, row of the block). The state block is the
    row's slot of the pool, read and written in place; a dead row (slot 0)
    keeps the trash slot as it is and computes nothing."""
    from jax.experimental import pallas as pl

    r = pl.program_id(1)
    live = slot_ref[pl.program_id(0) * ROWS + r] != 0
    at = pl.ds(r, 1)

    @pl.when(jnp.logical_not(live))
    def _():
        y_ref[at, :] = jnp.zeros((1, y_ref.shape[1]), F32)
        s_out[...] = s_in[...]

    @pl.when(live)
    def _():
        x, dt, bc = x_ref[at, :], dt_ref[at, :], bc_ref[0]
        s = jnp.exp(dt * a_ref[...]) * s_in[0] \
            + (dt * x) * bc[:n_state, 0:1]
        s_out[0] = s
        y = jnp.sum(s * bc[n_state:, 0:1], axis=0, keepdims=True) \
            + d_ref[...] * x
        y_ref[at, :] = _gate(y, z_ref[at, :])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _selective_step_pallas(x, dt, a, bm, cm, d, pool, slots, z,
                           interpret: bool = False):
    """One token a row on the slots of a pool, in place: x, dt, z (B, Ch);
    ``a`` (N, Ch); bm, cm (B, N); ``pool`` (slots, N, Ch) float32; ``slots``
    (B,) with every dead row on the trash slot 0 -> (y (B, Ch), pool). The
    state's block index is ``slots[row]`` for input and output alike and
    the pool is aliased input to output, so a live stream's state moves once
    each way and no other slot is touched. Jitted, so that a program of 26
    such layers lowers the kernel once."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, ch = x.shape
    n = a.shape[0]
    pad = -b % ROWS
    rows = lambda v: jnp.pad(v.astype(F32), ((0, pad), (0, 0)))
    slots = jnp.pad(slots.astype(jnp.int32), (0, pad))
    # a row's B over C, each a sublane, held by every lane of one tile
    bc = jnp.broadcast_to(rows(jnp.concatenate([bm, cm], axis=-1))[..., None],
                          (b + pad, 2 * n, LANES))
    block = pl.BlockSpec((ROWS, ch), lambda i, r, s: (i, 0))
    whole = lambda k: pl.BlockSpec((k, ch), lambda i, r, s: (0, 0))
    states = pl.BlockSpec((1, n, ch), lambda i, r, s: (s[i * ROWS + r], 0, 0))
    y, pool = pl.pallas_call(
        functools.partial(_step_kernel, n_state=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=((b + pad) // ROWS, ROWS),
            in_specs=[block] * 3 + [
                pl.BlockSpec((1, 2 * n, LANES),
                             lambda i, r, s: (i * ROWS + r, 0, 0)),
                whole(n), whole(1), states],
            out_specs=[block, states]),
        out_shape=[jax.ShapeDtypeStruct((b + pad, ch), F32),
                   jax.ShapeDtypeStruct(pool.shape, F32)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name="ssm_step",
    )(slots, rows(x), rows(dt), rows(z), bc, a.astype(F32),
      d.astype(F32).reshape(1, ch), pool)
    return y[:b], pool


def selective_step_paged(x, dt, A, B, C, D, pool, slots, live, z):
    """The decode window on the streams' slots of a state pool: x, dt, z
    (B, W, Ch); A (Ch, N); B, C (B, W, N); D (Ch,); ``pool`` (slots, N, Ch)
    float32; ``slots`` (B,); ``live`` (B, W) bool -> (y (B, W, Ch), pool).
    A token that is not live moves no state. On a TPU, with a window of one
    token, each live row's state is read from its slot and written back
    there by one kernel (:func:`_selective_step_pallas`) and a dead row
    names the trash slot; everywhere else the rows are gathered, stepped
    and scattered."""
    a = jnp.transpose(A).astype(F32)
    if x.shape[1] == 1 and _kernel_shapes(x, A):
        y, pool = _selective_step_pallas(
            x[:, 0], dt[:, 0], a, B[:, 0], C[:, 0], D, pool,
            jnp.where(live[:, 0], slots, 0), z[:, 0])
        return y[:, None], pool
    y, s = _selective_scan_xla(
        x.astype(F32), jnp.where(live[..., None], dt.astype(F32), 0.0), a,
        B.astype(F32), C.astype(F32), D.astype(F32), pool[slots], None,
        z.astype(F32))
    return jnp.where(live[..., None], y, 0.0), pool.at[slots].set(s)
