"""Hand-tiled Pallas conv2d: forward + input/filter gradients.

Reference parity: the cuDNN conv kernels the source framework dispatches to
(ops/declarable/platform/cudnn/conv2d.cu, path-cite, mount empty) and the
cuDNN paper's tiling playbook (arXiv:1410.0759): a convolution is a sum of
``kh*kw`` shifted matmuls — each kernel tap contributes one
``(positions, Cin) x (Cin, Cout)`` product that lands on the MXU. TVM
(arXiv:1802.04799) calls this the *spatial pack* schedule; here it is ONE
Pallas program per (image, group):

- **Forward**: the padded image block sits in VMEM; for every static tap
  ``(ki, kj)`` a strided window slice feeds one fp32-accumulated
  ``dot_general``. Stride / dilation / groups are index arithmetic, not
  special cases.
- **Filter gradient**: the same tap decomposition transposed —
  ``dW[ki,kj] = patch(ki,kj)^T @ dY`` — accumulated across the batch grid
  dimension into one output block (the classic wgrad kernel).
- **Input gradient**: algebraically a forward convolution of the
  stride-dilated ``dY`` with the spatially-flipped, I/O-transposed filter —
  so it REUSES the forward kernel (one kernel body to trust, two math
  duties), exactly how XLA's own conv transpose rule works.

The exact path (``lax.conv_general_dilated`` in ops/nn.py) stays the
reference; ``custom_vjp`` here is proven value- and grad-equivalent against
it in tests/test_kernels.py (Pallas interpreter on CPU). Accumulation is
fp32 regardless of input dtype (the MXU contract).

VMEM: one (image, group) program holds the padded image slice, the filter
and an fp32 accumulator of ``row_tile`` (or all ``OH``) output rows. Whether
that fits is the Mosaic compiler's call, made on the chip: nothing here
guesses a budget. ``auto`` only reaches this kernel through a tuning-database
winner, which ``benchmarks/autotune.py`` can only commit for a candidate that
compiled and ran on that backend; a forced ``pallas`` call the compiler
refuses raises its error (docs/KERNELS.md records what v5e accepted).

Tile parameterization (the autotuner's first search space — ISSUE 11,
docs/AUTOTUNE.md): ``row_tile`` splits the forward program's output rows
into blocks of ``row_tile`` rows — a third grid dimension whose block
computes ``(row_tile*OW, Cg) x (Cg, Og)`` tap products instead of the whole
``(OH*OW, Cg)`` product, shrinking the fp32 accumulator and changing the
MXU tile geometry (TVM's schedule knob, arXiv:1802.04799 §4). ``None``
keeps the historical whole-OH block and is the REGISTERED DEFAULT;
:func:`valid_row_tiles` is the shape guard the measurement driver consults,
so a non-dividing tile is never measured; a candidate the backend's compiler
refuses is recorded as rejected by the driver (tuning/measure.py). Tile
winners come from ``benchmarks/autotune.py`` through the tuning database;
CPU equivalence at non-default tiles is pinned in tests/test_kernels.py.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

_F32 = jnp.float32

def _pair(v):
    return (v, v) if isinstance(v, int) else tuple(v)


def resolve_padding(padding, in_hw, k_hw, strides, dilation):
    """'SAME'/'VALID'/int/(ph, pw) -> explicit ((lo, hi), (lo, hi)) pixels
    (the ND4J symmetric convention for numeric pads; SAME computes the
    XLA-compatible asymmetric split)."""
    if padding == "VALID":
        return ((0, 0), (0, 0))
    out = []
    for i in range(2):
        k_eff = (k_hw[i] - 1) * dilation[i] + 1
        if padding == "SAME":
            o = -(-in_hw[i] // strides[i])
            pad = max((o - 1) * strides[i] + k_eff - in_hw[i], 0)
            out.append((pad // 2, pad - pad // 2))
        else:
            p = _pair(padding)[i]
            out.append((p, p))
    return tuple(out)


def _out_size(in_size, pad, k, stride, dil):
    eff = (k - 1) * dil + 1
    return (in_size + pad[0] + pad[1] - eff) // stride + 1


def valid_row_tile(oh: int, row_tile) -> bool:
    """Shape guard for one row-tile candidate: a positive divisor of the
    output height (Pallas blocks are uniform; a non-dividing tile would
    write out of bounds). ``None`` (whole-OH) is always valid."""
    if row_tile is None:
        return True
    return isinstance(row_tile, int) and 0 < row_tile <= oh \
        and oh % row_tile == 0


def shape_signature(x_shape, w_shape, strides, padding, dilation,
                    groups) -> str:
    """Canonical tuning-database signature for one conv geometry — ONE
    builder shared by the search space (tuning/space.py) and the ``auto``
    dispatch site (ops/nn.py), so a measured winner and its trace-time
    lookup can never drift apart."""
    def part(v):
        if isinstance(v, (tuple, list)):
            return "x".join(str(int(x)) for x in v)
        return str(v)

    pad = padding if isinstance(padding, str) else part(_pair(padding))
    return (f"x={part(x_shape)};w={part(w_shape)};s={part(strides)};"
            f"p={pad};d={part(dilation)};g={int(groups)}")


def valid_row_tiles(oh: int, limit: int = 8):
    """The candidate row tiles for an output height: every divisor of
    ``oh`` up to ``limit`` distinct values (smallest first), plus ``None``
    (whole OH, the registered default). This is the enumerable half of the
    conv tile search space (tuning/space.py)."""
    divs = [d for d in range(1, oh + 1) if oh % d == 0 and d < oh]
    return [None] + divs[:limit]


def supports(x, w, data_format, feature_group_count,
             preferred_element_type) -> bool:
    """Geometry/dtype gate for the Pallas conv path (exact otherwise)."""
    if data_format != "NHWC" or preferred_element_type is not None:
        return False
    if x.ndim != 4 or w.ndim != 4:
        return False
    if x.dtype not in (jnp.float32, jnp.bfloat16) or w.dtype != x.dtype:
        return False
    cin = x.shape[-1]
    if cin % feature_group_count or w.shape[3] % feature_group_count:
        return False
    if w.shape[2] * feature_group_count != cin:
        return False
    return True


# ---------------------------------------------------------------------------
# forward kernel (also serves the input gradient — see conv2d_input_grad)
# ---------------------------------------------------------------------------


def _fwd_kernel(x_ref, w_ref, o_ref, *, oh, ow, kh, kw, sh, sw, dh, dw):
    """One (image, group) block: out[oh, ow, og] accumulated tap by tap."""
    xb = x_ref[0].astype(_F32)                       # (Hp, Wp, Cg)
    cg = xb.shape[-1]
    og = o_ref.shape[-1]
    acc = jnp.zeros((oh * ow, og), _F32)
    for ki in range(kh):
        for kj in range(kw):
            r0, c0 = ki * dh, kj * dw
            patch = lax.slice(
                xb,
                (r0, c0, 0),
                (r0 + (oh - 1) * sh + 1, c0 + (ow - 1) * sw + 1, cg),
                (sh, sw, 1),
            )                                        # (OH, OW, Cg)
            acc = acc + lax.dot_general(
                patch.reshape(oh * ow, cg),
                w_ref[ki, kj].astype(_F32),          # (Cg, Og)
                (((1,), (0,)), ((), ())),
                preferred_element_type=_F32,
            )
    o_ref[0] = acc.reshape(oh, ow, og).astype(o_ref.dtype)


def _fwd_kernel_tiled(x_ref, w_ref, o_ref, *, rt, ow, kh, kw, sh, sw, dh,
                      dw):
    """Row-tiled forward block: output rows [t*rt, (t+1)*rt) of one
    (image, group) — the tap products shrink to (rt*OW, Cg) x (Cg, Og).
    The padded image stays a whole VMEM block (the strided tap windows of
    neighbouring row tiles overlap, so input rows cannot be block-split);
    each tile reads its window through a dynamic row slice."""
    from jax.experimental import pallas as pl

    t = pl.program_id(2)
    cg = x_ref.shape[-1]
    og = o_ref.shape[-1]
    row0 = t * (rt * sh)                          # first input row of tile
    win_h = (rt - 1) * sh + 1
    win_w = (ow - 1) * sw + 1
    acc = jnp.zeros((rt * ow, og), _F32)
    for ki in range(kh):
        for kj in range(kw):
            win = x_ref[0, pl.dslice(row0 + ki * dh, win_h),
                        pl.dslice(kj * dw, win_w), :].astype(_F32)
            patch = lax.slice(win, (0, 0, 0), win.shape, (sh, sw, 1))
            acc = acc + lax.dot_general(
                patch.reshape(rt * ow, cg),
                w_ref[ki, kj].astype(_F32),       # (Cg, Og)
                (((1,), (0,)), ((), ())),
                preferred_element_type=_F32,
            )
    o_ref[0] = acc.reshape(rt, ow, og).astype(o_ref.dtype)


def _fwd_pallas(xp, w, strides, dilation, groups, interpret, out_dtype,
                row_tile=None):
    """``xp`` is ALREADY padded (N, Hp, Wp, Cin); w (kh, kw, Cg, Cout).
    ``row_tile`` selects the tiled program (grid over output-row blocks);
    ``None`` keeps the historical whole-OH block."""
    from jax.experimental import pallas as pl

    n, hp, wp, cin = xp.shape
    kh, kw, cg, cout = w.shape
    og = cout // groups
    sh, sw = strides
    dh, dw = dilation
    oh = _out_size(hp, (0, 0), kh, sh, dh)
    ow = _out_size(wp, (0, 0), kw, sw, dw)
    if row_tile is not None and row_tile != oh:
        if not valid_row_tile(oh, row_tile):
            raise ValueError(
                f"row_tile {row_tile!r} invalid for output height {oh} "
                "(must be a positive divisor)")
        rt = row_tile
        kernel = functools.partial(
            _fwd_kernel_tiled, rt=rt, ow=ow, kh=kh, kw=kw, sh=sh, sw=sw,
            dh=dh, dw=dw)
        return pl.pallas_call(
            kernel,
            grid=(n, groups, oh // rt),
            in_specs=[
                pl.BlockSpec((1, hp, wp, cg), lambda i, g, t: (i, 0, 0, g)),
                pl.BlockSpec((kh, kw, cg, og), lambda i, g, t: (0, 0, 0, g)),
            ],
            out_specs=pl.BlockSpec((1, rt, ow, og),
                                   lambda i, g, t: (i, t, 0, g)),
            out_shape=jax.ShapeDtypeStruct((n, oh, ow, cout), out_dtype),
            interpret=interpret,
        )(xp, w)
    kernel = functools.partial(
        _fwd_kernel, oh=oh, ow=ow, kh=kh, kw=kw, sh=sh, sw=sw, dh=dh, dw=dw)
    return pl.pallas_call(
        kernel,
        grid=(n, groups),
        in_specs=[
            pl.BlockSpec((1, hp, wp, cg), lambda i, g: (i, 0, 0, g)),
            pl.BlockSpec((kh, kw, cg, og), lambda i, g: (0, 0, 0, g)),
        ],
        out_specs=pl.BlockSpec((1, oh, ow, og), lambda i, g: (i, 0, 0, g)),
        out_shape=jax.ShapeDtypeStruct((n, oh, ow, cout), out_dtype),
        interpret=interpret,
    )(xp, w)


# ---------------------------------------------------------------------------
# filter-gradient kernel (wgrad)
# ---------------------------------------------------------------------------


def _wgrad_kernel(x_ref, dy_ref, o_ref, *, oh, ow, kh, kw, sh, sw, dh, dw):
    """dW[ki, kj] += patch(ki, kj)^T @ dY, accumulated over the batch grid
    dimension (out block revisited per image; init at image 0)."""
    from jax.experimental import pallas as pl

    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    xb = x_ref[0].astype(_F32)                       # (Hp, Wp, Cg)
    cg = xb.shape[-1]
    og = o_ref.shape[-1]
    dyb = dy_ref[0].astype(_F32).reshape(oh * ow, og)
    for ki in range(kh):
        for kj in range(kw):
            r0, c0 = ki * dh, kj * dw
            patch = lax.slice(
                xb,
                (r0, c0, 0),
                (r0 + (oh - 1) * sh + 1, c0 + (ow - 1) * sw + 1, cg),
                (sh, sw, 1),
            ).reshape(oh * ow, cg)
            o_ref[ki, kj] += lax.dot_general(
                patch, dyb, (((0,), (0,)), ((), ())),
                preferred_element_type=_F32,
            )


def _wgrad_pallas(xp, dy, kh, kw, strides, dilation, groups, interpret):
    from jax.experimental import pallas as pl

    n, hp, wp, cin = xp.shape
    _, oh, ow, cout = dy.shape
    cg = cin // groups
    og = cout // groups
    sh, sw = strides
    dh, dw = dilation
    kernel = functools.partial(
        _wgrad_kernel, oh=oh, ow=ow, kh=kh, kw=kw, sh=sh, sw=sw, dh=dh,
        dw=dw)
    # grid (groups, n): n is the fastest-varying (sequential) dimension, so
    # the (kh, kw, cg, og) output block is revisited image after image and
    # the += accumulation is well-defined
    return pl.pallas_call(
        kernel,
        grid=(groups, n),
        in_specs=[
            pl.BlockSpec((1, hp, wp, cg), lambda g, i: (i, 0, 0, g)),
            pl.BlockSpec((1, oh, ow, og), lambda g, i: (i, 0, 0, g)),
        ],
        out_specs=pl.BlockSpec((kh, kw, cg, og), lambda g, i: (0, 0, 0, g)),
        out_shape=jax.ShapeDtypeStruct((kh, kw, cg, cout), _F32),
        interpret=interpret,
    )(xp, dy)


# ---------------------------------------------------------------------------
# the differentiable op
# ---------------------------------------------------------------------------


def _dy_for_input_grad(dy, x_hw, pads, k_hw, strides, dilation):
    """Stride-dilate dy and pad it so the FORWARD kernel computes dx.

    dx = conv(dilate(dy, stride), flip(w)^T) with pads
    ``lo' = eff_k - 1 - lo`` and ``hi' = H + lo - len(dilated dy)`` — the
    standard transposed-convolution derivation; a negative hi' trims dy
    rows that never influenced the output."""
    n, oh, ow, c = dy.shape
    sh, sw = strides
    odh, odw = (oh - 1) * sh + 1, (ow - 1) * sw + 1
    if (sh, sw) != (1, 1):
        dil = jnp.zeros((n, odh, odw, c), dy.dtype)
        dy = dil.at[:, ::sh, ::sw].set(dy)
    spec = []
    for i, (size, odl) in enumerate(((x_hw[0], odh), (x_hw[1], odw))):
        eff = (k_hw[i] - 1) * dilation[i] + 1
        lo = eff - 1 - pads[i][0]
        hi = size + pads[i][0] - odl
        spec.append((lo, hi))
    trim = [slice(None), slice(None), slice(None), slice(None)]
    padw = [(0, 0), (0, 0), (0, 0), (0, 0)]
    for ax, (lo, hi) in enumerate(spec, start=1):
        tlo, thi = max(0, -lo), max(0, -hi)
        if tlo or thi:
            trim[ax] = slice(tlo, dy.shape[ax] - thi)
        padw[ax] = (max(0, lo), max(0, hi))
    dy = dy[tuple(trim)]
    return jnp.pad(dy, padw)


def _flip_transpose_w(w, groups):
    """w (kh, kw, Cg, g*Og) -> (kh, kw, Og, g*Cg): spatial flip + per-group
    I/O transpose (the transposed-conv weight layout)."""
    kh, kw, cg, cout = w.shape
    og = cout // groups
    wg = w.reshape(kh, kw, cg, groups, og)[::-1, ::-1]
    return jnp.transpose(wg, (0, 1, 4, 3, 2)).reshape(kh, kw, og,
                                                      groups * cg)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def conv2d_pallas(x, w, strides, pads, dilation, groups, interpret,
                  row_tile=None):
    """NHWC x HWIO convolution on the Pallas kernels. ``pads`` is the
    explicit ((lo, hi), (lo, hi)) form from :func:`resolve_padding`;
    ``interpret`` runs the Pallas interpreter (CPU correctness mode);
    ``row_tile`` is the tuned output-row tile for the forward program
    (None = whole OH — the registered default; winners come from the
    tuning database through ``auto`` dispatch, docs/AUTOTUNE.md)."""
    return _conv_fwd_impl(x, w, strides, pads, dilation, groups, interpret,
                          row_tile)


def _conv_fwd_impl(x, w, strides, pads, dilation, groups, interpret,
                   row_tile=None):
    xp = jnp.pad(x, ((0, 0), pads[0], pads[1], (0, 0)))
    return _fwd_pallas(xp, w, strides, dilation, groups, interpret, x.dtype,
                       row_tile)


def _conv_vjp_fwd(x, w, strides, pads, dilation, groups, interpret,
                  row_tile=None):
    out = _conv_fwd_impl(x, w, strides, pads, dilation, groups, interpret,
                         row_tile)
    return out, (x, w)


def _conv_vjp_bwd(strides, pads, dilation, groups, interpret, row_tile,
                  res, dy):
    x, w = res
    kh, kw = w.shape[0], w.shape[1]
    # input gradient: forward kernel over the stride-dilated dy. The tuned
    # row_tile applies to the FORWARD product only — the dx conv has a
    # different output height (the input's), so a forward tile need not
    # divide it; the gradient programs keep their whole-block schedule.
    dyp = _dy_for_input_grad(dy, (x.shape[1], x.shape[2]), pads, (kh, kw),
                             strides, dilation)
    wt = _flip_transpose_w(w, groups)
    dx = _fwd_pallas(dyp, wt, (1, 1), dilation, groups, interpret, x.dtype)
    # filter gradient: the wgrad kernel over the padded input
    xp = jnp.pad(x, ((0, 0), pads[0], pads[1], (0, 0)))
    dw = _wgrad_pallas(xp, dy, kh, kw, strides, dilation, groups,
                       interpret).astype(w.dtype)
    return dx, dw


conv2d_pallas.defvjp(_conv_vjp_fwd, _conv_vjp_bwd)
