"""Fused LSTM cell Pallas kernel + the scan-fused sequence path.

Reference parity: the cuDNN LSTM kernel the source framework's
CudnnLSTMHelper dispatches to (path-cite, mount empty) — one fused kernel
per step doing the recurrent matmul and the whole gate/elementwise block,
instead of separate GEMM + pointwise launches.

TPU-native shape (docs/KERNELS.md):

- The input projection ``x @ W + b`` for ALL timesteps stays hoisted out of
  the scan as one big MXU matmul (the r1 design — nn/recurrent.py); the
  kernel fuses what remains on the critical path: ``z = xp_t + h @ U``
  (the (B,H)x(H,4H) recurrent product) plus the sigmoid/tanh gate block and
  the c/h state update, in ONE Pallas program — the per-step HLO the exact
  path leaves as matmul + 10 pointwise ops becomes a single kernel with the
  gate math running on the VPU while the MXU product's tiles drain.
- The sequence path is the same ``lax.scan`` the exact path uses, with the
  fused cell as the body — XLA still sees one compiled loop (TBPTT
  segments and masks compose unchanged).
- Backward is a hand-written VJP from the saved (xp, h, c, U) residuals —
  the standard LSTM adjoint, written once in jnp so XLA fuses it; the scan
  transposes it into BPTT automatically.

Gate order is a static parameter: nn/recurrent.py's layers split z as
[i, f, o, g]; the ONNX-semantics ops/rnn.py ``lstm_layer`` splits as
[i, o, f, g]. Only the default sigmoid/tanh activation pair has a kernel —
exotic activations take the exact path (dispatch gate in
:func:`supports`).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

_F32 = jnp.float32
ORDER_IFOG: Tuple[str, ...] = ("i", "f", "o", "g")   # DL4J layer order
ORDER_IOFG: Tuple[str, ...] = ("i", "o", "f", "g")   # ONNX lstm_layer order


def supports(xp, u, gate_activation: str, activation: str) -> bool:
    """Kernel GEOMETRY gate: default sigmoid/tanh cell, f32/bf16,
    (B,4H)x(H,4H). Whether a block fits VMEM is the Mosaic compiler's
    verdict on the chip, not a guess made here (see conv.py)."""
    if gate_activation.lower() != "sigmoid" or activation.lower() != "tanh":
        return False
    if xp.dtype not in (jnp.float32, jnp.bfloat16) or u.dtype != xp.dtype:
        return False
    if xp.ndim != 2 or u.ndim != 2:
        return False
    h = u.shape[0]
    if u.shape[1] != 4 * h or xp.shape[1] != 4 * h:
        return False
    if jax.default_backend() == "tpu" and h % 128:
        return False  # compiled Mosaic wants lane-aligned H; exact otherwise
    return True


def _gates(z, h, order):
    """Slice z (..., 4H) into the i/f/o/g roles per the static order."""
    idx = {role: order.index(role) for role in ("i", "f", "o", "g")}
    pick = lambda r: lax.slice_in_dim(z, idx[r] * h, (idx[r] + 1) * h,  # noqa: E731
                                      axis=z.ndim - 1)
    return pick("i"), pick("f"), pick("o"), pick("g")


def _cell_kernel(xp_ref, h_ref, c_ref, u_ref, ho_ref, co_ref, *, hidden,
                 order):
    z = xp_ref[...].astype(_F32) + lax.dot_general(
        h_ref[...].astype(_F32), u_ref[...].astype(_F32),
        (((1,), (0,)), ((), ())), preferred_element_type=_F32)
    zi, zf, zo, zg = _gates(z, hidden, order)
    i = jax.nn.sigmoid(zi)
    f = jax.nn.sigmoid(zf)
    o = jax.nn.sigmoid(zo)
    g = jnp.tanh(zg)
    c_new = f * c_ref[...].astype(_F32) + i * g
    ho_ref[...] = (o * jnp.tanh(c_new)).astype(ho_ref.dtype)
    co_ref[...] = c_new.astype(co_ref.dtype)


def valid_b_tile(b: int, b_tile) -> bool:
    """Shape guard for one batch-tile candidate: a positive divisor of the
    batch (rows are independent, so any divisor is equivalence-safe).
    ``None`` (whole batch, the registered default) is always valid."""
    if b_tile is None:
        return True
    return isinstance(b_tile, int) and 0 < b_tile <= b and b % b_tile == 0


def shape_signature(b: int, h: int) -> str:
    """Canonical tuning-database signature for one cell geometry (the
    kernel program depends on (B, H) only — the scan length T does not
    change the per-step kernel, so winners apply across sequence
    lengths). Shared by tuning/space.py and the dispatch sites."""
    return f"b={int(b)};h={int(h)}"


def valid_b_tiles(b: int, limit: int = 8):
    """Candidate batch tiles for the cell kernel: divisors of ``b`` up to
    ``limit`` distinct values plus ``None`` (whole batch) — the enumerable
    half of the LSTM tile search space (tuning/space.py)."""
    divs = [d for d in range(1, b + 1) if b % d == 0 and d < b]
    return [None] + divs[:limit]


def _cell_pallas(xp, h, c, u, order, interpret, b_tile=None):
    """``b_tile`` blocks the batch axis: grid over B/bt row blocks, each
    running the (bt, H) x (H, 4H) recurrent product with U replicated —
    the tuned alternative to the whole-batch single program (None). Rows
    are independent, so tiling is exactly output-equivalent; the knob
    trades recurrent-matmul MXU geometry against per-block overhead and
    is ranked by benchmarks/autotune.py (docs/AUTOTUNE.md)."""
    from jax.experimental import pallas as pl

    b, hidden = h.shape
    kernel = functools.partial(_cell_kernel, hidden=hidden, order=order)
    if b_tile is not None and b_tile != b:
        if not valid_b_tile(b, b_tile):
            raise ValueError(
                f"b_tile {b_tile!r} invalid for batch {b} "
                "(must be a positive divisor)")
        bt = b_tile
        four_h = 4 * hidden
        return pl.pallas_call(
            kernel,
            grid=(b // bt,),
            in_specs=[
                pl.BlockSpec((bt, four_h), lambda t: (t, 0)),
                pl.BlockSpec((bt, hidden), lambda t: (t, 0)),
                pl.BlockSpec((bt, hidden), lambda t: (t, 0)),
                pl.BlockSpec((hidden, four_h), lambda t: (0, 0)),
            ],
            out_specs=[pl.BlockSpec((bt, hidden), lambda t: (t, 0)),
                       pl.BlockSpec((bt, hidden), lambda t: (t, 0))],
            out_shape=[jax.ShapeDtypeStruct((b, hidden), xp.dtype),
                       jax.ShapeDtypeStruct((b, hidden), xp.dtype)],
            interpret=interpret,
        )(xp, h, c, u)
    return pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((b, hidden), xp.dtype),
                   jax.ShapeDtypeStruct((b, hidden), xp.dtype)],
        interpret=interpret,
    )(xp, h, c, u)


def _cell_exact(xp, h, c, u, order):
    """Same math in plain jnp (fp32 accumulation) — the VJP recompute
    body and the autotuner's exact candidate."""
    z = xp.astype(_F32) + h.astype(_F32) @ u.astype(_F32)
    zi, zf, zo, zg = _gates(z, h.shape[-1], order)
    i = jax.nn.sigmoid(zi)
    f = jax.nn.sigmoid(zf)
    o = jax.nn.sigmoid(zo)
    g = jnp.tanh(zg)
    c_new = f * c.astype(_F32) + i * g
    h_new = o * jnp.tanh(c_new)
    return h_new, c_new, (i, f, o, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def lstm_cell_fused(xp, h, c, u, order, mode, b_tile=None):
    """One LSTM step: ``xp`` (B, 4H) pre-projected input (+ bias), ``h``/
    ``c`` (B, H), ``u`` (H, 4H). Returns (h_new, c_new) in xp's dtype.
    ``mode``: "pallas" | "interpret" (see kernels.dispatch); ``b_tile`` is
    the tuned batch tile for the kernel program (None = whole batch)."""
    h_new, c_new = _cell_fwd_impl(xp, h, c, u, order, mode, b_tile)
    return h_new, c_new


def _cell_fwd_impl(xp, h, c, u, order, mode, b_tile=None):
    if mode not in ("pallas", "interpret"):
        raise ValueError(f"mode must be 'pallas' or 'interpret', got {mode!r}")
    return _cell_pallas(xp, h, c, u, order, mode == "interpret", b_tile)


def _cell_vjp_fwd(xp, h, c, u, order, mode, b_tile=None):
    out = _cell_fwd_impl(xp, h, c, u, order, mode, b_tile)
    return out, (xp, h, c, u)


def _cell_vjp_bwd(order, mode, b_tile, res, cts):
    """The LSTM adjoint from recomputed gates (one fused elementwise block
    + two matmuls — XLA fuses it; the scan transpose turns it into BPTT)."""
    xp, h, c, u = res
    dh, dc = (t.astype(_F32) for t in cts)
    _h_new, c_new, (i, f, o, g) = _cell_exact(xp, h, c, u, order)
    tc = jnp.tanh(c_new)
    d_o = dh * tc * o * (1.0 - o)
    dct = dc + dh * o * (1.0 - tc * tc)
    d_f = dct * c.astype(_F32) * f * (1.0 - f)
    d_i = dct * g * i * (1.0 - i)
    d_g = dct * i * (1.0 - g * g)
    parts = {"i": d_i, "f": d_f, "o": d_o, "g": d_g}
    dz = jnp.concatenate([parts[r] for r in order], axis=-1)   # (B, 4H)
    dxp = dz.astype(xp.dtype)
    dh_prev = (dz @ u.astype(_F32).T).astype(h.dtype)
    dc_prev = (dct * f).astype(c.dtype)
    du = (h.astype(_F32).T @ dz).astype(u.dtype)
    return dxp, dh_prev, dc_prev, du


lstm_cell_fused.defvjp(_cell_vjp_fwd, _cell_vjp_bwd)


def lstm_sequence_exact(xp, h0, c0, u, order=ORDER_IFOG):
    """The reference for :func:`lstm_sequence_fused`: the same scan with
    :func:`_cell_exact` as its body. Returns ys (T, B, H)."""

    def body(carry, xt):
        h, c, _ = _cell_exact(xt, carry[0], carry[1], u, order)
        h, c = h.astype(xp.dtype), c.astype(xp.dtype)
        return (h, c), h

    return lax.scan(body, (h0, c0), xp)[1]


def lstm_sequence_fused(xp, h0, c0, u, order=ORDER_IFOG, mode="pallas",
                        b_tile=None):
    """Whole-sequence fused path: ``xp`` (T, B, 4H) time-major pre-projected
    inputs, states (B, H). One ``lax.scan`` whose body is the fused cell.
    Returns (ys (T, B, H), (h_fin, c_fin)). Mask/TBPTT handling stays with
    the callers (nn/recurrent.py wraps the step, ops/rnn.py masks the
    outputs) so the kernel path and the exact path share that logic.
    ``b_tile`` threads the tuned batch tile into every step's kernel."""

    def body(carry, xt):
        h, c = carry
        h_new, c_new = lstm_cell_fused(xt, h, c, u, order, mode, b_tile)
        return (h_new, c_new), h_new

    (h_fin, c_fin), ys = lax.scan(body, (h0, c0), xp)
    return ys, (h_fin, c_fin)
