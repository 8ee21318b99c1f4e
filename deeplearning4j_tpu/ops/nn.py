"""Neural-net ops: convolution, pooling, normalization, softmax, losses, attention.

Reference parity: libnd4j declarable ops under ops/declarable/generic/nn/**
(convo/conv2d.cpp, pooling/maxpool2d.cpp, batchnorm.cpp, softmax.cpp,
loss/*.cpp, attention ops) and their cuDNN/oneDNN platform helpers
(ops/declarable/platform/cudnn/conv2d.cu, batchnorm.cu …) — path-cite, mount
empty this round.

TPU-native: XLA *is* the vendor library (SURVEY.md §2.1 N5). Convolutions lower
to the ``convolution`` HLO which XLA tiles onto the MXU; pooling is
``reduce-window``; batchnorm is a fused multiply-add chain XLA folds into the
adjacent conv. Default data format is **NHWC** (TPU-preferred; C maps to the
128-lane dimension) — the reference's NCHW default is a cuDNN-era artifact.
Matmul/conv accept bf16 inputs with fp32 accumulation.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from deeplearning4j_tpu.ops.registry import op

# checkpoint_name tags let selective-remat policies (util/xla_tuning.py)
# target the expensive conv/dot outputs by name: 'save_conv' keeps these and
# recomputes the cheap BN/elementwise epilogue in the backward pass. The tag
# is an identity outside a jax.checkpoint region. The names are shared with
# the policy definitions — a drift would silently degrade 'save_conv' to
# full recompute (the +32% r5-rejected behaviour), so there is one source.
from deeplearning4j_tpu.util.xla_tuning import CONV_OUT as _CONV_OUT
from deeplearning4j_tpu.util.xla_tuning import DOT_OUT as _DOT_OUT

# ---------------------------------------------------------------------------
# Convolutions
# ---------------------------------------------------------------------------


def _pair(v):
    return (v, v) if isinstance(v, int) else tuple(v)


def _accf(x):
    """Accumulation dtype: fp32 unless the input is already fp64 (gradcheck)."""
    return x.astype(jnp.promote_types(x.dtype, jnp.float32))



def _conv_padding(padding):
    """'SAME'/'VALID', or explicit symmetric (ph, pw) pixels (ND4J style)."""
    if isinstance(padding, str):
        return padding
    return [(p, p) for p in _pair(padding)]


@op("conv2d", "conv")
def conv2d(
    x,
    w,
    b=None,
    strides=(1, 1),
    padding="SAME",
    dilation=(1, 1),
    data_format="NHWC",
    feature_group_count=1,
    preferred_element_type=None,
):
    """2-D convolution.

    x: [N,H,W,C] (NHWC) or [N,C,H,W] (NCHW); w: [kH,kW,Cin/groups,Cout] (HWIO).
    Reference: libnd4j generic/nn/convo/conv2d.cpp (+ cudnn/conv2d.cu fast path);
    here a single ``convolution`` HLO on the MXU — or the hand-tiled Pallas
    kernel engine (ops/kernels/conv.py) when the ``kernel_impl`` dispatch
    seam selects it (docs/KERNELS.md): NHWC f32/bf16 geometries with full
    stride/dilation/groups support, custom VJP running the Pallas
    input/filter-gradient kernels, proven fwd/grad-equivalent to this exact
    path in tests/test_kernels.py.
    """
    from deeplearning4j_tpu.ops import kernels as _kern
    from deeplearning4j_tpu.ops.kernels import conv as _kconv

    if _kconv.supports(jnp.asarray(x), jnp.asarray(w), data_format,
                       feature_group_count, preferred_element_type):
        strides_p, dil_p = _pair(strides), _pair(dilation)
        mode, tuned = _kern.dispatch(
            True,
            op="conv2d",
            sig=_kconv.shape_signature(x.shape, w.shape, strides_p,
                                       padding, dil_p,
                                       feature_group_count),
            dtype=str(x.dtype))
        if mode is not None:
            pads = _kconv.resolve_padding(
                padding, (x.shape[1], x.shape[2]), (w.shape[0], w.shape[1]),
                strides_p, dil_p)
            out = _kconv.conv2d_pallas(x, w, strides_p, pads, dil_p,
                                       feature_group_count,
                                       mode == "interpret",
                                       tuned.get("row_tile"))
            if b is not None:
                out = out + b.reshape(1, 1, 1, -1).astype(out.dtype)
            return checkpoint_name(out, _CONV_OUT)
    dn = lax.conv_dimension_numbers(
        x.shape,
        w.shape,
        (data_format, "HWIO", data_format),
    )
    # preferred_element_type stays None by default: the MXU accumulates bf16
    # convolutions in fp32 in hardware, and a forced fp32 output dtype breaks
    # the conv transpose (gradient) rule for bf16 inputs.
    out = lax.conv_general_dilated(
        x,
        w,
        window_strides=_pair(strides),
        padding=_conv_padding(padding),
        rhs_dilation=_pair(dilation),
        dimension_numbers=dn,
        feature_group_count=feature_group_count,
        preferred_element_type=preferred_element_type,
    ).astype(x.dtype)
    if b is not None:
        bshape = (1, 1, 1, -1) if data_format == "NHWC" else (1, -1, 1, 1)
        out = out + b.reshape(bshape).astype(out.dtype)
    return checkpoint_name(out, _CONV_OUT)


@op("conv1d", "conv")
def conv1d(x, w, b=None, stride=1, padding="SAME", dilation=1, data_format="NWC"):
    """1-D convolution. x: [N,W,C]; w: [kW,Cin,Cout]."""
    x4 = jnp.expand_dims(x, 1 if data_format == "NWC" else 2)
    w4 = jnp.expand_dims(w, 0)
    df = "NHWC" if data_format == "NWC" else "NCHW"
    pad = padding if isinstance(padding, str) else (0, padding)
    out = conv2d(x4, w4, b, strides=(1, stride), padding=pad, dilation=(1, dilation), data_format=df)
    return jnp.squeeze(out, 1 if data_format == "NWC" else 2)


@op("conv3d", "conv")
def conv3d(x, w, b=None, strides=(1, 1, 1), padding="SAME", dilation=(1, 1, 1), data_format="NDHWC"):
    """3-D convolution. x: [N,D,H,W,C]; w: [kD,kH,kW,Cin,Cout]."""
    dn = lax.conv_dimension_numbers(x.shape, w.shape, (data_format, "DHWIO", data_format))
    if not isinstance(padding, str):
        padding = [(p, p) for p in (padding if len(padding) == 3 else (padding,) * 3)]
    out = lax.conv_general_dilated(
        x, w,
        window_strides=tuple(strides) if not isinstance(strides, int) else (strides,) * 3,
        padding=padding,
        rhs_dilation=tuple(dilation) if not isinstance(dilation, int) else (dilation,) * 3,
        dimension_numbers=dn,
    ).astype(x.dtype)
    if b is not None:
        bshape = (1, 1, 1, 1, -1) if data_format.endswith("C") else (1, -1, 1, 1, 1)
        out = out + b.reshape(bshape).astype(out.dtype)
    return checkpoint_name(out, _CONV_OUT)


@op("depthwise_conv2d", "conv", aliases=("sconv2d_depthwise",))
def depthwise_conv2d(x, w, b=None, strides=(1, 1), padding="SAME", dilation=(1, 1), data_format="NHWC"):
    """Depthwise conv; w: [kH,kW,C,multiplier]."""
    c = x.shape[-1] if data_format == "NHWC" else x.shape[1]
    kh, kw, cin, mult = w.shape
    w = w.reshape(kh, kw, 1, cin * mult)
    return conv2d(
        x, w, b, strides=strides, padding=padding, dilation=dilation,
        data_format=data_format, feature_group_count=c,
    )


@op("separable_conv2d", "conv", aliases=("sconv2d",))
def separable_conv2d(x, depth_w, point_w, b=None, strides=(1, 1), padding="SAME", data_format="NHWC"):
    y = depthwise_conv2d(x, depth_w, None, strides=strides, padding=padding, data_format=data_format)
    return conv2d(y, point_w, b, strides=(1, 1), padding="VALID", data_format=data_format)


@op("deconv2d", "conv", aliases=("conv2d_transpose",))
def deconv2d(x, w, b=None, strides=(1, 1), padding="SAME", data_format="NHWC"):
    """Transposed convolution; w: [kH,kW,Cout,Cin] per HWIO with I=Cout of fwd."""
    dn = lax.conv_dimension_numbers(x.shape, w.shape, (data_format, "HWIO", data_format))
    out = lax.conv_transpose(
        x, w, strides=_pair(strides),
        padding=padding if isinstance(padding, str) else [(p, p) for p in _pair(padding)],
        dimension_numbers=dn,
    ).astype(x.dtype)
    if b is not None:
        bshape = (1, 1, 1, -1) if data_format == "NHWC" else (1, -1, 1, 1)
        out = out + b.reshape(bshape).astype(out.dtype)
    return checkpoint_name(out, _CONV_OUT)


@op("upsampling2d", "conv")
def upsampling2d(x, scale=2, data_format="NHWC"):
    sh, sw = _pair(scale)
    if data_format == "NHWC":
        return jnp.repeat(jnp.repeat(x, sh, axis=1), sw, axis=2)
    return jnp.repeat(jnp.repeat(x, sh, axis=2), sw, axis=3)


@op("im2col", "conv")
def im2col(x, kernel, strides=(1, 1), padding=(0, 0), dilation=(1, 1)):
    """Patch extraction (reference: helpers/im2col). On TPU conv does NOT go
    through im2col+GEMM — XLA convs hit the MXU directly — but the op exists
    for parity and for unfold-style models."""
    kh, kw = _pair(kernel)
    n, h, w, c = x.shape
    ph, pw = _pair(padding)
    x = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    patches = lax.conv_general_dilated_patches(
        x.transpose(0, 3, 1, 2),
        filter_shape=(kh, kw),
        window_strides=_pair(strides),
        padding="VALID",
        rhs_dilation=_pair(dilation),
    )
    return patches


# ---------------------------------------------------------------------------
# Pooling — reduce-window HLOs
# ---------------------------------------------------------------------------


def _pool_dims(kernel, strides, data_format):
    kh, kw = _pair(kernel)
    sh, sw = _pair(strides)
    if data_format == "NHWC":
        return (1, kh, kw, 1), (1, sh, sw, 1)
    return (1, 1, kh, kw), (1, 1, sh, sw)


def _pool_padding(padding, data_format="NHWC"):
    if isinstance(padding, str):
        return padding
    ph, pw = _pair(padding)
    if data_format == "NHWC":
        return [(0, 0), (ph, ph), (pw, pw), (0, 0)]
    return [(0, 0), (0, 0), (ph, ph), (pw, pw)]


@op("maxpool2d", "pooling", aliases=("max_pool2d", "maxpool"))
def max_pool2d(x, kernel=(2, 2), strides=None, padding="VALID", data_format="NHWC"):
    strides = strides or kernel
    window, strd = _pool_dims(kernel, strides, data_format)
    return lax.reduce_window(
        x, -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min,
        lax.max, window, strd, _pool_padding(padding, data_format),
    )


@op("avgpool2d", "pooling", aliases=("avg_pool2d", "avgpool"))
def avg_pool2d(x, kernel=(2, 2), strides=None, padding="VALID", data_format="NHWC"):
    strides = strides or kernel
    window, strd = _pool_dims(kernel, strides, data_format)
    pad = _pool_padding(padding, data_format)
    summed = lax.reduce_window(x, 0.0, lax.add, window, strd, pad)
    if padding == "VALID":
        kh, kw = _pair(kernel)
        return summed / (kh * kw)
    counts = lax.reduce_window(jnp.ones_like(x), 0.0, lax.add, window, strd, pad)
    return summed / counts


@op("pnormpool2d", "pooling")
def pnorm_pool2d(x, kernel=(2, 2), strides=None, padding="VALID", p=2, data_format="NHWC"):
    strides = strides or kernel
    window, strd = _pool_dims(kernel, strides, data_format)
    pad = _pool_padding(padding, data_format)
    s = lax.reduce_window(jnp.abs(x) ** p, 0.0, lax.add, window, strd, pad)
    return s ** (1.0 / p)


@op("global_avg_pool", "pooling", aliases=("globalavgpool",))
def global_avg_pool(x, data_format="NHWC", keepdims=False):
    axes = (1, 2) if data_format == "NHWC" else (2, 3)
    return jnp.mean(x, axis=axes, keepdims=keepdims)


@op("global_max_pool", "pooling", aliases=("globalmaxpool",))
def global_max_pool(x, data_format="NHWC", keepdims=False):
    axes = (1, 2) if data_format == "NHWC" else (2, 3)
    return jnp.max(x, axis=axes, keepdims=keepdims)


@op("maxpool3d", "pooling")
def max_pool3d(x, kernel=(2, 2, 2), strides=None, padding="VALID"):
    strides = strides or kernel
    k = (1,) + tuple(kernel) + (1,)
    s = (1,) + tuple(strides) + (1,)
    return lax.reduce_window(x, -jnp.inf, lax.max, k, s, padding)


@op("avgpool3d", "pooling")
def avg_pool3d(x, kernel=(2, 2, 2), strides=None, padding="VALID"):
    strides = strides or kernel
    k = (1,) + tuple(kernel) + (1,)
    s = (1,) + tuple(strides) + (1,)
    summed = lax.reduce_window(x, 0.0, lax.add, k, s, padding)
    if padding == "VALID":
        import math

        return summed / math.prod(kernel)
    counts = lax.reduce_window(jnp.ones_like(x), 0.0, lax.add, k, s, padding)
    return summed / counts


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------


@op("batchnorm", "norm", aliases=("batch_norm", "batchnorm_new"))
def batchnorm(x, mean, variance, gamma=None, beta=None, eps=1e-5, axis=-1):
    """Normalize with given statistics (inference form / post-stats train form).

    Reference: generic/nn/batchnorm.cpp + cudnn/batchnorm.cu; on TPU this is a
    scale-shift chain XLA fuses into the adjacent conv."""
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    inv = lax.rsqrt(_accf(variance) + eps).reshape(shape)
    out = (_accf(x) - mean.reshape(shape)) * inv
    if gamma is not None:
        out = out * gamma.reshape(shape)
    if beta is not None:
        out = out + beta.reshape(shape)
    return out.astype(x.dtype)


def _paired_sums(a, b, reduce_axes):
    """sum(a) and sum(b) in ONE variadic reduce → one pass over the data.

    XLA does not merge sibling reduces of the same operand into one fusion
    (profiled: ResNet-50 BN backward read each activation twice); the variadic
    reduce HLO forces a single read."""
    zero = jnp.zeros((), a.dtype)
    return lax.reduce((a, b), (zero, zero),
                      lambda acc, v: (acc[0] + v[0], acc[1] + v[1]),
                      reduce_axes)


@functools.lru_cache(maxsize=None)
def _bn_train_fused(momentum, eps, axis):
    """Single-pass batchnorm training fwd/bwd (cudnn/batchnorm.cu parity —
    the cuDNN fast path computes E[x], E[x^2] in one sweep; so do we).

    Forward: one stats pass (sum, sum-of-squares) + one normalize pass.
    Backward: one paired-reduction pass (sum(dy), sum(dy*xhat)) + one dx pass.
    The naive autodiff version costs ~2x the passes; on ResNet-50/B256 this
    fusion is worth ~10% of the whole train step."""

    def _geom(x):
        ax = axis % x.ndim
        red = tuple(i for i in range(x.ndim) if i != ax)
        shape = [1] * x.ndim
        shape[ax] = x.shape[ax]
        n = 1
        for i in red:
            n *= x.shape[i]
        return red, shape, float(n)

    def _fwd_impl(x, gamma, beta, rm, rv):
        red, shape, n = _geom(x)
        xf = _accf(x)
        s, s2 = _paired_sums(xf, xf * xf, red)
        mean = s / n
        var = jnp.maximum(s2 / n - mean * mean, 0.0)
        inv = lax.rsqrt(var + eps)
        out = ((xf - mean.reshape(shape)) * (inv * _accf(gamma)).reshape(shape)
               + _accf(beta).reshape(shape)).astype(x.dtype)
        unbiased = var * (n / max(n - 1.0, 1.0))
        new_mean = momentum * rm + (1.0 - momentum) * mean.astype(rm.dtype)
        new_var = momentum * rv + (1.0 - momentum) * unbiased.astype(rv.dtype)
        return out, new_mean, new_var, mean, inv

    @jax.custom_vjp
    def bn(x, gamma, beta, rm, rv):
        out, new_mean, new_var, _, _ = _fwd_impl(x, gamma, beta, rm, rv)
        return out, new_mean, new_var

    def fwd(x, gamma, beta, rm, rv):
        out, new_mean, new_var, mean, inv = _fwd_impl(x, gamma, beta, rm, rv)
        return (out, new_mean, new_var), (x, gamma, mean, inv)

    def bwd(res, cts):
        x, gamma, mean, inv = res
        dout, dm_ema, dv_ema = cts
        red, shape, n = _geom(x)
        xf = _accf(x)
        dyf = _accf(dout)
        xhat = (xf - mean.reshape(shape)) * inv.reshape(shape)
        g, g2 = _paired_sums(dyf, dyf * xhat, red)
        dgamma = g2.astype(gamma.dtype)
        dbeta = g.astype(gamma.dtype)
        ginv = _accf(gamma) * inv
        dx = ginv.reshape(shape) * (dyf - (g / n).reshape(shape)
                                    - xhat * (g2 / n).reshape(shape))
        # EMA outputs' cotangents (zero in normal training — states are not
        # differentiated — but custom_vjp must be total): new_mean/new_var
        # depend on x too. Fuses into the dx pass; negligible when zero.
        one_m = 1.0 - momentum
        dx = dx + (one_m / n) * _accf(dm_ema).reshape(shape)
        scale = one_m * (n / max(n - 1.0, 1.0)) * 2.0 / n
        dx = dx + scale * _accf(dv_ema).reshape(shape) * (xhat / inv.reshape(shape))
        return (dx.astype(x.dtype), dgamma, dbeta,
                momentum * dm_ema, momentum * dv_ema)

    bn.defvjp(fwd, bwd)
    return bn


@op("batchnorm_train", "norm")
def batchnorm_train(x, gamma, beta, running_mean, running_var, momentum=0.9, eps=1e-5, axis=-1):
    """Training-mode batchnorm: batch statistics + EMA update, single-pass
    fused stats and a hand-written VJP (see _bn_train_fused).

    Returns (out, new_running_mean, new_running_var)."""
    fn = _bn_train_fused(float(momentum), float(eps), int(axis))
    return fn(x, gamma, beta, running_mean, running_var)


@op("layernorm", "norm", aliases=("layer_norm",))
def layernorm(x, gamma=None, beta=None, eps=1e-5, axis=-1):
    xf = _accf(x)
    mean = jnp.mean(xf, axis=axis, keepdims=True)
    var = jnp.var(xf, axis=axis, keepdims=True)
    out = (xf - mean) * lax.rsqrt(var + eps)
    if gamma is not None:
        out = out * gamma
    if beta is not None:
        out = out + beta
    return out.astype(x.dtype)


@op("rmsnorm", "norm")
def rmsnorm(x, gamma=None, eps=1e-6, axis=-1):
    xf = _accf(x)
    ms = jnp.mean(jnp.square(xf), axis=axis, keepdims=True)
    out = xf * lax.rsqrt(ms + eps)
    if gamma is not None:
        out = out * gamma
    return out.astype(x.dtype)


@op("standardize", "norm")
def standardize(x, axis=-1, eps=1e-5):
    mean = jnp.mean(x, axis=axis, keepdims=True)
    std = jnp.std(x, axis=axis, keepdims=True)
    return (x - mean) / (std + eps)


@op("lrn", "norm", aliases=("local_response_normalization",))
def lrn(x, depth_radius=5, bias=1.0, alpha=1.0, beta=0.5):
    """Local response normalization over channels (NHWC last axis)."""
    sq = jnp.square(x)
    c = x.shape[-1]
    pads = [(0, 0)] * (x.ndim - 1) + [(depth_radius, depth_radius)]
    sq = jnp.pad(sq, pads)
    window = [1] * (x.ndim - 1) + [2 * depth_radius + 1]
    strides = [1] * x.ndim
    sums = lax.reduce_window(sq, 0.0, lax.add, window, strides, "VALID")
    return x / jnp.power(bias + alpha * sums, beta)


@op("l2_normalize", "norm")
def l2_normalize(x, axis=-1, eps=1e-12):
    return x * lax.rsqrt(jnp.maximum(jnp.sum(jnp.square(x), axis=axis, keepdims=True), eps))


@op("moments", "norm")
def moments(x, axes, keepdims=False):
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=axes, keepdims=True)
    if not keepdims:
        mean = jnp.squeeze(mean, axes)
        var = jnp.squeeze(var, axes)
    return mean, var


# ---------------------------------------------------------------------------
# Softmax family
# ---------------------------------------------------------------------------

op("softmax", "softmax")(lambda x, axis=-1: jax.nn.softmax(x, axis=axis))
op("log_softmax", "softmax")(lambda x, axis=-1: jax.nn.log_softmax(x, axis=axis))


@op("softmax_derivative", "softmax")
def softmax_derivative(x, grad, axis=-1):
    s = jax.nn.softmax(x, axis=axis)
    return s * (grad - jnp.sum(grad * s, axis=axis, keepdims=True))


# ---------------------------------------------------------------------------
# Loss ops — reference: ops/declarable/generic/loss/*.cpp and
# org/nd4j/linalg/lossfunctions/impl/*.java. All support per-example weights
# and return mean-over-batch by default (ND4J's default reduction).
# ---------------------------------------------------------------------------


def _weighted_mean(per_example, weights):
    if weights is not None:
        # weights align on LEADING axes (numpy broadcasting is trailing):
        # per-example (B,) weights gate a (B,T) sequence loss by broadcasting
        # over time, and the normalizer counts the broadcast weights so the
        # result stays a true weighted mean.
        if weights.ndim < per_example.ndim:
            weights = weights.reshape(
                weights.shape + (1,) * (per_example.ndim - weights.ndim))
        wfull = jnp.broadcast_to(weights, per_example.shape)
        # reciprocal-MULTIPLY normalizer, not a divide: XLA strength-reduces
        # jnp.mean's divide-by-constant into multiply-by-reciprocal, so a
        # runtime divide here would land one ulp off the unweighted mean.
        # With the multiply, a 0/1-weighted padded batch is BIT-identical to
        # the unpadded jnp.mean path — the invariant shape bucketing
        # (data/bucketing.py) is built on. All-zero weights yield loss 0
        # (0 * the clamped reciprocal); fractional weight sums below 1 keep
        # their true normalizer.
        return jnp.sum(per_example * wfull) * (
            1.0 / jnp.maximum(jnp.sum(wfull), 1e-12))
    return jnp.mean(per_example)


@op("softmax_cross_entropy", "loss", aliases=("softmax_cross_entropy_loss", "mcxent"))
def softmax_cross_entropy(logits, labels, weights=None, label_smoothing=0.0):
    """Softmax cross-entropy with one-hot labels [batch, classes]."""
    if label_smoothing > 0.0:
        k = labels.shape[-1]
        labels = labels * (1.0 - label_smoothing) + label_smoothing / k
    logp = jax.nn.log_softmax(_accf(logits), axis=-1)
    per = -jnp.sum(labels * logp, axis=-1)
    return _weighted_mean(per, weights)


@op("sparse_softmax_cross_entropy", "loss")
def sparse_softmax_cross_entropy(logits, label_indices, weights=None):
    logp = jax.nn.log_softmax(_accf(logits), axis=-1)
    per = -jnp.take_along_axis(logp, label_indices[..., None], axis=-1)[..., 0]
    return _weighted_mean(per, weights)


@op("sigmoid_cross_entropy", "loss", aliases=("xent",))
def sigmoid_cross_entropy(logits, labels, weights=None):
    z = _accf(logits)
    per = jnp.maximum(z, 0) - z * labels + jnp.log1p(jnp.exp(-jnp.abs(z)))
    per = jnp.sum(per, axis=tuple(range(1, per.ndim))) if per.ndim > 1 else per
    return _weighted_mean(per, weights)


@op("mse_loss", "loss", aliases=("mean_sqerr_loss", "l2_loss_per_example"))
def mse_loss(predictions, labels, weights=None):
    per = jnp.mean(jnp.square(predictions - labels), axis=tuple(range(1, predictions.ndim)))
    return _weighted_mean(per, weights)


@op("mae_loss", "loss", aliases=("absolute_difference_loss", "l1"))
def mae_loss(predictions, labels, weights=None):
    per = jnp.mean(jnp.abs(predictions - labels), axis=tuple(range(1, predictions.ndim)))
    return _weighted_mean(per, weights)


@op("huber_loss", "loss")
def huber_loss(predictions, labels, delta=1.0, weights=None):
    err = predictions - labels
    abs_err = jnp.abs(err)
    quad = jnp.minimum(abs_err, delta)
    per = 0.5 * quad**2 + delta * (abs_err - quad)
    per = jnp.mean(per, axis=tuple(range(1, per.ndim))) if per.ndim > 1 else per
    return _weighted_mean(per, weights)


@op("hinge_loss", "loss")
def hinge_loss(predictions, labels, weights=None):
    """labels in {0,1} mapped to ±1 (ND4J convention)."""
    signed = 2.0 * labels - 1.0
    per = jnp.mean(jnp.maximum(0.0, 1.0 - signed * predictions), axis=tuple(range(1, predictions.ndim)))
    return _weighted_mean(per, weights)


@op("squared_hinge_loss", "loss")
def squared_hinge_loss(predictions, labels, weights=None):
    signed = 2.0 * labels - 1.0
    per = jnp.mean(jnp.square(jnp.maximum(0.0, 1.0 - signed * predictions)), axis=tuple(range(1, predictions.ndim)))
    return _weighted_mean(per, weights)


@op("log_loss", "loss")
def log_loss(predictions, labels, eps=1e-7, weights=None):
    p = jnp.clip(predictions, eps, 1.0 - eps)
    per = -jnp.mean(labels * jnp.log(p) + (1.0 - labels) * jnp.log1p(-p), axis=tuple(range(1, predictions.ndim)))
    return _weighted_mean(per, weights)


@op("poisson_loss", "loss")
def poisson_loss(predictions, labels, weights=None):
    per = jnp.mean(predictions - labels * jnp.log(jnp.maximum(predictions, 1e-12)), axis=tuple(range(1, predictions.ndim)))
    return _weighted_mean(per, weights)


@op("kl_divergence", "loss", aliases=("kld",))
def kl_divergence(predictions, labels, eps=1e-12, weights=None):
    per = jnp.sum(
        labels * (jnp.log(jnp.maximum(labels, eps)) - jnp.log(jnp.maximum(predictions, eps))),
        axis=-1,
    )
    return _weighted_mean(per, weights)


@op("cosine_distance_loss", "loss")
def cosine_distance_loss(predictions, labels, axis=-1, weights=None):
    num = jnp.sum(predictions * labels, axis=axis)
    np_ = jnp.sqrt(jnp.sum(jnp.square(predictions), axis=axis))
    nl = jnp.sqrt(jnp.sum(jnp.square(labels), axis=axis))
    per = 1.0 - num / jnp.maximum(np_ * nl, 1e-12)
    return _weighted_mean(per, weights)


@op("l2_loss", "loss")
def l2_loss(x):
    return 0.5 * jnp.sum(jnp.square(x))


@op("ctc_loss", "loss")
def ctc_loss(log_probs, labels, logit_lengths, label_lengths, blank_id=0):
    """CTC loss (reference: cudnn ctcloss helper). Uses optax's TPU-friendly
    implementation (dynamic-programming over lax.scan)."""
    import optax

    logit_paddings = (
        jnp.arange(log_probs.shape[1])[None, :] >= logit_lengths[:, None]
    ).astype(jnp.float32)
    label_paddings = (
        jnp.arange(labels.shape[1])[None, :] >= label_lengths[:, None]
    ).astype(jnp.float32)
    return jnp.mean(
        optax.ctc_loss(log_probs, logit_paddings, labels, label_paddings, blank_id=blank_id)
    )


# ---------------------------------------------------------------------------
# Attention — reference: generic/nn/multi_head_dot_product_attention.cpp and
# dot_product_attention.cpp (the only attention in the reference, single
# device). The TPU-native blockwise/ring variants live in
# deeplearning4j_tpu/parallel/ring_attention.py.
# ---------------------------------------------------------------------------


@op("dot_product_attention", "attention")
def dot_product_attention(q, k, v, mask=None, scale=None, is_causal=False):
    """Scaled dot-product attention.

    q,k,v: [..., T, d]. Computes softmax(q kᵀ · scale + mask) v with fp32
    softmax accumulation (bf16-safe)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / float(d) ** 0.5
    acc = jnp.promote_types(q.dtype, jnp.float32)
    logits = jnp.einsum("...qd,...kd->...qk", q, k, preferred_element_type=acc) * scale
    if is_causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        causal = jnp.tril(jnp.ones((tq, tk), dtype=bool), k=tk - tq)
        logits = jnp.where(causal, logits, -1e30)
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    weights = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum(
        "...qk,...kd->...qd", weights, v, preferred_element_type=acc
    ).astype(q.dtype)


@op("multihead_attention", "attention")
def multi_head_attention(x_q, x_kv, wq, wk, wv, wo, num_heads, mask=None, is_causal=False):
    """Two-input MHA convenience form: project, split heads, attend, merge.

    x_q: [B,Tq,D], x_kv: [B,Tk,D]; wq/wk/wv: [D, H*dh]; wo: [H*dh, D].
    NOTE: deliberately NOT named multi_head_dot_product_attention — that
    name (the ND4J-parity three-input q/k/v op with flash auto-dispatch)
    belongs to ops/attention.py; registering both under one name silently
    shadowed whichever imported first (review finding, round 3)."""
    b, tq, _ = x_q.shape
    tk = x_kv.shape[1]

    def split(x, w):
        y = jnp.einsum("btd,dh->bth", x, w)
        return y.reshape(b, -1, num_heads, y.shape[-1] // num_heads).transpose(0, 2, 1, 3)

    q, k, v = split(x_q, wq), split(x_kv, wk), split(x_kv, wv)
    ctx = dot_product_attention(q, k, v, mask=mask, is_causal=is_causal)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, tq, -1)
    return jnp.einsum("bth,hd->btd", ctx, wo)


# ---------------------------------------------------------------------------
# Embedding / misc nn
# ---------------------------------------------------------------------------


@op("embedding_lookup", "nn_misc")
def embedding_lookup(table, ids):
    return jnp.take(table, ids, axis=0)


@op("bias_add", "nn_misc")
def bias_add(x, b, data_format="NHWC"):
    if data_format == "NCHW" and x.ndim == 4:
        return x + b.reshape(1, -1, 1, 1)
    return x + b


@op("xw_plus_b", "nn_misc", aliases=("linear_layer",))
def xw_plus_b(x, w, b):
    acc = jnp.promote_types(x.dtype, jnp.float32)
    out = jnp.matmul(x, w, preferred_element_type=acc).astype(x.dtype)
    return checkpoint_name(out + b.astype(out.dtype), _DOT_OUT)


@op("batch_dot", "nn_misc")
def batch_dot(a, b):
    return jnp.einsum("b...i,b...i->b", a, b)


@op("weighted_cross_entropy_with_logits", "loss")
def weighted_cross_entropy_with_logits(targets, logits, pos_weight):
    """TF semantics (generic/loss/weighted_cross_entropy_with_logits.cpp,
    path-cite): like sigmoid CE with positive targets scaled by pos_weight.
    Elementwise (no reduction), as in TF/the reference."""
    z = _accf(logits)
    t = _accf(targets)
    log1p = jnp.log1p(jnp.exp(-jnp.abs(z)))
    return ((1 - t) * z
            + (1 + (pos_weight - 1) * t) * (log1p + jnp.maximum(-z, 0)))


@op("col2im", "conv")
def col2im(patches, output_shape, kernel, strides=(1, 1), padding=(0, 0),
           dilation=(1, 1)):
    """Inverse of im2col: scatter-add patches back to the image
    (helpers/col2im, path-cite). im2col is linear, so its exact adjoint
    comes from jax.linear_transpose — no throwaway forward evaluation, and
    XLA lowers it to the same conv-transpose machinery the backward pass
    uses."""
    shape = jax.ShapeDtypeStruct(
        tuple(int(s) for s in output_shape), patches.dtype)
    transpose = jax.linear_transpose(
        lambda x: im2col(x, kernel, strides, padding, dilation), shape)
    return transpose(patches)[0]


# ------------------------------------------------------------- TF grad ops
# The reference's *Grad kernels (ReluGrad, FusedBatchNormGrad,
# Conv2DBackprop*, libnd4j ops/declarable/generic/nn/**_bp.cpp, path-cite)
# as first-class registry ops, so tf.gradients-exported TRAINING graphs
# import into serializable SameDiff graphs. The conv backprops are the
# jax.vjp of this file's own forward ops — XLA emits the same
# transposed/dilated conv HLO a hand-written kernel would.


@op("relu_grad", "transform_float", differentiable=False)
def relu_grad(dy, f):
    """TF ReluGrad: f is the relu OUTPUT (y>0 ⟺ x>0, either works)."""
    return dy * (f > 0).astype(dy.dtype)


@op("relu6_grad", "transform_float", differentiable=False)
def relu6_grad(dy, f):
    return dy * ((f > 0) & (f < 6)).astype(dy.dtype)


@op("tanh_grad", "transform_float", differentiable=False)
def tanh_grad(y, dy):
    """TF TanhGrad input order: (y, dy)."""
    return dy * (1.0 - y * y)


@op("sigmoid_grad", "transform_float", differentiable=False)
def sigmoid_grad(y, dy):
    return dy * y * (1.0 - y)


@op("bias_add_grad", "reduce", differentiable=False)
def bias_add_grad(dy, data_format="NHWC"):
    ax = -1 if data_format.endswith("C") else 1
    red = tuple(i for i in range(dy.ndim) if i != ax % dy.ndim)
    return jnp.sum(dy, axis=red)


@op("conv2d_backprop_input", "conv", differentiable=False)
def conv2d_backprop_input(w, dy, input_sizes, strides=(1, 1), padding="SAME",
                          dilation=(1, 1), data_format="NHWC"):
    x0 = jnp.zeros(tuple(int(s) for s in input_sizes), dy.dtype)
    _, vjp = jax.vjp(
        lambda xx: conv2d(xx, w, None, strides=strides, padding=padding,
                          dilation=dilation, data_format=data_format), x0)
    return vjp(dy)[0]


@op("conv2d_backprop_filter", "conv", differentiable=False)
def conv2d_backprop_filter(x, dy, filter_sizes, strides=(1, 1),
                           padding="SAME", dilation=(1, 1),
                           data_format="NHWC"):
    w0 = jnp.zeros(tuple(int(s) for s in filter_sizes), dy.dtype)
    _, vjp = jax.vjp(
        lambda ww: conv2d(x, ww, None, strides=strides, padding=padding,
                          dilation=dilation, data_format=data_format), w0)
    return vjp(dy)[0]


@op("maxpool2d_grad", "pooling", differentiable=False)
def maxpool2d_grad(x, dy, kernel=(2, 2), strides=(2, 2), padding="VALID",
                   data_format="NHWC"):
    _, vjp = jax.vjp(
        lambda xx: max_pool2d(xx, kernel=kernel, strides=strides,
                              padding=padding, data_format=data_format), x)
    return vjp(dy)[0]


@op("avgpool2d_grad", "pooling", differentiable=False)
def avgpool2d_grad(x, dy, kernel=(2, 2), strides=(2, 2), padding="VALID",
                   data_format="NHWC"):
    _, vjp = jax.vjp(
        lambda xx: avg_pool2d(xx, kernel=kernel, strides=strides,
                              padding=padding, data_format=data_format), x)
    return vjp(dy)[0]


@op("fused_batch_norm_grad", "norm", differentiable=False)
def fused_batch_norm_grad(dy, x, scale, mean_in, var_in, epsilon=1e-3,
                          is_training=True):
    """FusedBatchNormGrad(V2/V3) math → (dx, dscale, doffset).

    Training mode recomputes the batch moments from x rather than trusting
    the reserve-space convention (TF's reserve_space_2 is plain variance on
    CPU but inverse-stddev on GPU — recomputation sidesteps the split, at
    one extra fused reduction). Inference mode uses the passed population
    stats. NHWC; reductions in fp32."""
    xf = _accf(x)
    dyf = _accf(dy)
    red = tuple(range(x.ndim - 1))
    n = 1.0
    for i in red:
        n *= x.shape[i]
    if is_training:
        s, s2 = _paired_sums(xf, xf * xf, red)
        mean = s / n
        var = jnp.maximum(s2 / n - mean * mean, 0.0)
    else:
        mean, var = _accf(mean_in), _accf(var_in)
    inv = lax.rsqrt(var + epsilon)
    xhat = (xf - mean) * inv
    dsum, dxhat_sum = _paired_sums(dyf, dyf * xhat, red)
    dscale = dxhat_sum
    doffset = dsum
    if is_training:
        dx = (_accf(scale) * inv / n) * (n * dyf - dsum - xhat * dxhat_sum)
    else:
        dx = dyf * _accf(scale) * inv
    return (dx.astype(x.dtype), dscale.astype(scale.dtype),
            doffset.astype(scale.dtype))


@op("softmax_cross_entropy_with_logits_grad", "loss", differentiable=False)
def softmax_cross_entropy_with_logits_grad(logits, labels):
    """TF SoftmaxCrossEntropyWithLogits: (per-example loss, backprop)."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    log_softmax = logits - lse
    loss = -jnp.sum(labels * log_softmax, axis=-1)
    backprop = jnp.exp(log_softmax) - labels
    return loss, backprop


@op("strided_slice_grad", "gather_scatter", differentiable=False)
def strided_slice_grad(dy, shape, spec):
    """TF StridedSliceGrad: scatter dy into zeros(shape) at the slice the
    forward took. ``spec`` is the getitem spec format: ("e",) ellipsis,
    ("n",) new_axis, ("i", i) shrink, ("s", b, e, st) slice."""
    if any(s[0] == "e" for s in spec) and any(s[0] == "n" for s in spec):
        raise NotImplementedError("StridedSliceGrad with ellipsis + new_axis")
    # new_axis entries add a size-1 dim to dy the input never had: squeeze
    # them (dy axis index = count of preceding dy-producing entries)
    squeeze = []
    dy_axis = 0
    for s in spec:
        if s[0] == "n":
            squeeze.append(dy_axis)
            dy_axis += 1
        elif s[0] in ("s", "e"):
            dy_axis += 1
    if squeeze:
        dy = jnp.squeeze(dy, axis=tuple(squeeze))
    idx = tuple(
        Ellipsis if s[0] == "e"
        else s[1] if s[0] == "i"
        else slice(s[1], s[2], s[3])
        for s in spec if s[0] != "n")
    return jnp.zeros(tuple(int(d) for d in shape), dy.dtype).at[idx].set(dy)


@op("normalize_moments", "norm", differentiable=False)
def normalize_moments(counts, mean_ss, variance_ss, shift=None):
    """TF NormalizeMoments: sufficient statistics → (mean, variance)."""
    divisor = 1.0 / counts
    if shift is not None:
        shifted_mean = mean_ss * divisor
        mean = shifted_mean + shift
    else:
        shifted_mean = mean = mean_ss * divisor
    variance = variance_ss * divisor - shifted_mean * shifted_mean
    return mean, variance


@op("log_poisson_loss", "loss")
def log_poisson_loss(log_input, targets, compute_full_loss=False):
    """TF nn.log_poisson_loss: exp(c) − z·c (+ Stirling when full)."""
    loss = jnp.exp(log_input) - targets * log_input
    if compute_full_loss:
        stirling = (targets * jnp.log(jnp.maximum(targets, 1e-12))
                    - targets + 0.5 * jnp.log(2.0 * jnp.pi
                                              * jnp.maximum(targets, 1.0)))
        loss = loss + jnp.where(targets >= 1.0, stirling, 0.0)
    return loss


# ---------------------------------------------------------------------------
# Round-5 tail: morphological / argmax pooling / 3-D transposed conv
# (reference: libnd4j generic/nn/convo dilation2d.cpp, deconv3d.cpp,
#  max_pool_with_argmax.cpp, upsampling3d.cpp, relu_layer.cpp — path-cites,
#  mount empty this round).
# ---------------------------------------------------------------------------

def _patches2d(x, kh, kw, strides, rates, padding):
    """(B,Ho,Wo,kh*kw,C) window view via static shifted slices — XLA folds
    these into one gather; no im2col materialization at conv time."""
    sh, sw = strides
    rh, rw = rates
    b, h, w, c = x.shape
    eff_kh, eff_kw = (kh - 1) * rh + 1, (kw - 1) * rw + 1
    if padding == "SAME":
        ho = -(-h // sh)
        wo = -(-w // sw)
        pad_h = max((ho - 1) * sh + eff_kh - h, 0)
        pad_w = max((wo - 1) * sw + eff_kw - w, 0)
        pads = ((0, 0), (pad_h // 2, pad_h - pad_h // 2),
                (pad_w // 2, pad_w - pad_w // 2), (0, 0))
    else:
        ho = (h - eff_kh) // sh + 1
        wo = (w - eff_kw) // sw + 1
        pads = ((0, 0), (0, 0), (0, 0), (0, 0))
    neg = jnp.asarray(-jnp.inf if jnp.issubdtype(x.dtype, jnp.floating)
                      else jnp.iinfo(x.dtype).min, x.dtype)
    xp = jnp.pad(x, pads, constant_values=neg)
    cols = []
    for dy in range(kh):
        for dx in range(kw):
            y0, x0 = dy * rh, dx * rw
            cols.append(lax.slice(
                xp, (0, y0, x0, 0),
                (b, y0 + (ho - 1) * sh + 1, x0 + (wo - 1) * sw + 1, c),
                (1, sh, sw, 1)))
    return jnp.stack(cols, axis=3), pads  # (B,Ho,Wo,kh*kw,C)


@op("dilation2d", "conv")
def dilation2d(x, filter, strides=(1, 1), rates=(1, 1), padding="SAME"):
    """Grayscale morphological dilation (TF nn.dilation2d / reference
    dilation2d op): out = max over window of (x + filter). x: NHWC,
    filter: (kh, kw, C)."""
    filter = jnp.asarray(filter, x.dtype)
    kh, kw, _ = filter.shape
    pat, _ = _patches2d(x, kh, kw, _pair(strides), _pair(rates), padding)
    return jnp.max(pat + filter.reshape(1, 1, 1, kh * kw, -1), axis=3)


@op("erosion2d", "conv")
def erosion2d(x, filter, strides=(1, 1), rates=(1, 1), padding="SAME"):
    """Morphological erosion: min over window of (x - filter) — the TF
    duality erosion(x, f) = -dilation(-x, reverse(f))."""
    filter = jnp.asarray(filter, x.dtype)
    rev = filter[::-1, ::-1, :]
    return -dilation2d(-x, rev, strides=strides, rates=rates,
                       padding=padding)


@op("max_pool_with_argmax", "pooling", differentiable=False)
def max_pool_with_argmax(x, kernel=(2, 2), strides=None, padding="VALID",
                         include_batch_in_index=False):
    """Max pooling returning (values, argmax) with TF's flat-index
    convention: idx = ((b*H + y)*W + x)*C + c (b term only when
    ``include_batch_in_index``). Reference max_pool_with_argmax, path-cite."""
    kh, kw = _pair(kernel)
    strides = _pair(strides if strides is not None else kernel)
    b, h, w, c = x.shape
    pat, pads = _patches2d(x, kh, kw, strides, (1, 1), padding)
    vals = jnp.max(pat, axis=3)
    arg = jnp.argmax(pat, axis=3)                       # window-local k
    ho, wo = arg.shape[1], arg.shape[2]
    ky, kx = arg // kw, arg % kw
    oy = jnp.arange(ho).reshape(1, ho, 1, 1) * strides[0] - pads[1][0]
    ox = jnp.arange(wo).reshape(1, 1, wo, 1) * strides[1] - pads[2][0]
    iy = jnp.clip(oy + ky, 0, h - 1)
    ix = jnp.clip(ox + kx, 0, w - 1)
    ci = jnp.arange(c).reshape(1, 1, 1, c)
    flat = (iy * w + ix) * c + ci
    if include_batch_in_index:
        flat = flat + jnp.arange(b).reshape(b, 1, 1, 1) * (h * w * c)
    return vals, flat


@op("deconv3d", "conv", aliases=("conv3d_transpose",))
def deconv3d(x, w, b=None, strides=(1, 1, 1), padding="SAME"):
    """3-D transposed convolution, NDHWC; w: [kD,kH,kW,C,Cout] (DHWIO with
    I = x's channel count, the forward conv's output channels) — reference
    deconv3d, path-cite."""
    if isinstance(strides, int):
        strides = (strides,) * 3
    strides = tuple(strides)
    if len(strides) != 3:
        raise ValueError(f"deconv3d strides must be length 3, got {strides}")
    dn = lax.conv_dimension_numbers(x.shape, w.shape, ("NDHWC", "DHWIO", "NDHWC"))
    out = lax.conv_transpose(
        x, w, strides=tuple(strides),
        padding=padding if isinstance(padding, str)
        else [(p, p) for p in padding],
        dimension_numbers=dn,
    ).astype(x.dtype)
    if b is not None:
        out = out + b.reshape(1, 1, 1, 1, -1).astype(out.dtype)
    return out


@op("upsampling3d", "conv")
def upsampling3d(x, scale=2):
    """Nearest-neighbour 3-D upsampling, NDHWC (reference upsampling3d)."""
    if isinstance(scale, int):
        scale = (scale,) * 3
    sd, sh, sw = scale
    return jnp.repeat(jnp.repeat(jnp.repeat(x, sd, axis=1), sh, axis=2),
                      sw, axis=3)


@op("relu_layer", "nn_misc")
def relu_layer(x, w, b=None):
    """relu(x @ w + b) — the reference's fused relu_layer op (path-cite)."""
    y = x @ w
    if b is not None:
        y = y + b
    return jax.nn.relu(y)


@op("mean_pairwssqerr_loss", "loss")
def mean_pairwssqerr_loss(predictions, labels, weights=None):
    """Mean pairwise squared error (TF losses.mean_pairwise_squared_error /
    reference mean_pairwssqerr_loss): per sample, the mean over ordered
    element pairs (i != j) of (d_i - d_j)^2 / 2 where d = prediction - label,
    computed via the identity sum_{i,j}(d_i-d_j)^2 = 2n*sum d^2 - 2(sum d)^2
    (verified against the explicit O(n^2) loop in tests)."""
    d = (_accf(predictions) - _accf(labels)).reshape(predictions.shape[0], -1)
    n = d.shape[1]
    if n < 2:
        return jnp.zeros(())
    sum_sq = jnp.sum(d * d, axis=1)
    sq_sum = jnp.square(jnp.sum(d, axis=1))
    per = (n * sum_sq - sq_sum) / (n * (n - 1))
    return _weighted_mean(per, weights)


@op("ctc_beam_search_decoder", "decoder", differentiable=False)
def ctc_beam_search_decoder(log_probs, sequence_lengths=None, beam_width=16,
                            top_paths=1, blank_index=0):
    """CTC prefix beam search (reference ctc_beam op / TF
    ctc_beam_search_decoder). Host-side numpy — decoding is a serving-path
    utility, not a training op (the training op is the registered
    ``ctc_loss``). log_probs: (B, T, C) log-softmax outputs. Returns
    (decoded, log_prob): a length-B list of up-to-``top_paths`` label lists,
    and a (B, top_paths) array of path log-probabilities."""
    import numpy as _np

    lp = _np.asarray(log_probs, _np.float64)
    bsz, tmax, _ = lp.shape
    if sequence_lengths is None:
        sequence_lengths = [tmax] * bsz
    sequence_lengths = _np.asarray(sequence_lengths)
    NEG = -_np.inf

    def lse(a, b):
        if a == NEG:
            return b
        if b == NEG:
            return a
        m = max(a, b)
        return m + _np.log(_np.exp(a - m) + _np.exp(b - m))

    all_paths, all_logp = [], []
    for b in range(bsz):
        # prefix -> (log p ending in blank, log p ending in non-blank)
        beams = {(): (0.0, NEG)}
        for t in range(int(sequence_lengths[b])):
            step = lp[b, t]
            new = {}
            for prefix, (pb, pnb) in beams.items():
                total = lse(pb, pnb)
                # extend with blank: prefix unchanged
                nb, nn = new.get(prefix, (NEG, NEG))
                new[prefix] = (lse(nb, total + step[blank_index]), nn)
                # repeat last symbol: only the non-blank mass collapses
                if prefix:
                    last = prefix[-1]
                    nb, nn = new.get(prefix, (NEG, NEG))
                    new[prefix] = (nb, lse(nn, pnb + step[last]))
                for s in _np.argsort(step)[::-1][:beam_width]:
                    s = int(s)
                    if s == blank_index:
                        continue
                    ext = prefix + (s,)
                    nb, nn = new.get(ext, (NEG, NEG))
                    if prefix and s == prefix[-1]:
                        new[ext] = (nb, lse(nn, pb + step[s]))
                    else:
                        new[ext] = (nb, lse(nn, total + step[s]))
            ranked = sorted(new.items(), key=lambda kv: -lse(*kv[1]))
            beams = dict(ranked[:beam_width])
        ranked = sorted(beams.items(), key=lambda kv: -lse(*kv[1]))[:top_paths]
        all_paths.append([list(p) for p, _ in ranked])
        row = [lse(*v) for _, v in ranked]
        row += [NEG] * (top_paths - len(row))
        all_logp.append(row)
    return all_paths, _np.asarray(all_logp, _np.float32)


@op("nll_loss", "loss")
def nll_loss(log_probs, target, weight=None, reduction="mean",
             ignore_index=None):
    """Negative log-likelihood over class axis 1 (ONNX
    NegativeLogLikelihoodLoss / torch F.nll_loss semantics).
    log_probs: (N, C, d...); target: (N, d...) int. ``reduction`` mean is
    weight-normalized (sum of per-element weights), per the spec."""
    lp = _accf(log_probs)
    target = jnp.asarray(target)
    tc = jnp.expand_dims(target, 1)                     # (N, 1, d...)
    safe_t = jnp.clip(tc, 0, lp.shape[1] - 1)
    picked = -jnp.take_along_axis(lp, safe_t, axis=1)[:, 0]   # (N, d...)
    if weight is not None:
        w_el = jnp.asarray(weight, lp.dtype)[jnp.clip(
            target, 0, lp.shape[1] - 1)]
    else:
        w_el = jnp.ones_like(picked)
    if ignore_index is not None:
        keep = (target != ignore_index).astype(lp.dtype)
        w_el = w_el * keep
    picked = picked * w_el
    if reduction == "none":
        return picked
    if reduction == "sum":
        return jnp.sum(picked)
    # weight-normalized mean; an all-ignored batch (weight sum exactly 0)
    # returns 0, not sum/1e-12 garbage (torch F.nll_loss returns nan there,
    # ONNX leaves it undefined — 0 is the useful total-loss contribution)
    w_sum = jnp.sum(w_el)
    return jnp.where(w_sum > 0, jnp.sum(picked) / jnp.maximum(w_sum, 1e-12),
                     jnp.zeros((), lp.dtype))


@op("max_unpool2d", "pooling", differentiable=False)
def max_unpool2d(x, indices, output_shape):
    """Scatter pooled values back to their argmax positions (ONNX
    MaxUnpool): ``indices`` are row-major flat positions into the FULL
    output tensor (the ONNX MaxPool Indices convention); everything else
    is zero. Duplicate indices: last write wins."""
    x = jnp.asarray(x)
    total = 1
    for s in output_shape:
        total *= int(s)
    flat = jnp.zeros((total,), x.dtype)
    flat = flat.at[jnp.asarray(indices).reshape(-1)].set(x.reshape(-1))
    return flat.reshape(tuple(output_shape))
