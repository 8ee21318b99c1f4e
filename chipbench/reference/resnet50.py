"""Plain float32 reference of ResNet-50 training (He et al. 2015, "Deep
Residual Learning for Image Recognition", table 1 and section 3.4) with Adam
(Kingma & Ba 2014, algorithm 1 in the "efficient" form of its section 2).

Straightforward ``jax.numpy``/``lax``: no kernels, no fused batch norm, no
casts. It imports nothing of ``deeplearning4j_tpu`` and makes its own weights
from the seed; the benchmark hands the same arrays to the program.

Departures from the paper, each because the program under test does it and a
reference of other semantics would compare nothing:
- ``SAME`` padding as TensorFlow/Keras place it (the 7x7/2 stem pads (2, 3),
  the 3x3/2 max-pool (0, 1)); the paper's Caffe model pads symmetrically.
- stride 2 sits on the first 1x1 of a stage's first block (the paper's own
  placement; torchvision's "v1.5" moved it to the 3x3).
- batch norm uses the biased batch variance, eps 1e-5, trainable scale and
  shift, on every convolution, and the convolutions carry no bias.
- the loss is the mean softmax cross-entropy over the batch; no weight decay.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

STAGES = (("res2", 3, (64, 64, 256), 1), ("res3", 4, (128, 128, 512), 2),
          ("res4", 6, (256, 256, 1024), 2), ("res5", 3, (512, 512, 2048), 2))
BN_EPS = 1e-5
ADAM = {"lr": 1e-3, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8}
HIGHEST = lax.Precision.HIGHEST


def conv_names():
    """Every convolution as (name, kernel, c_in, c_out, stride), in forward
    order — the one list the weights, the forward and the FLOP count are
    all built from."""
    out = [("stem", 7, 3, 64, 2)]
    c_in = 64
    for stage, blocks, (f1, f2, f3), stride in STAGES:
        for i in range(blocks):
            b = f"{stage}{chr(ord('a') + i)}"
            s = stride if i == 0 else 1
            out.append((f"{b}_a", 1, c_in, f1, s))
            out.append((f"{b}_b", 3, f1, f2, 1))
            out.append((f"{b}_c", 1, f2, f3, 1))
            if i == 0:
                out.append((f"{b}_sc", 1, c_in, f3, s))
            c_in = f3
    return out


def make_weights(seed: int, cfg: dict):
    """He-normal convolutions; batch-norm scales 1 + N(0, 0.1) and shifts
    N(0, 0.1), classifier N(0, 0.01) with a N(0, 0.01) bias, so that no term
    sits at a value (0 or 1) that would hide its being dropped. One jitted
    call on the device, float32."""
    classes = cfg["num_classes"]

    @jax.jit
    def build(key):
        w = {}
        convs = conv_names()
        keys = jax.random.split(key, len(convs) + 1)
        for k, (name, ksz, c_in, c_out, _s) in zip(keys, convs):
            kw, kg, kb = jax.random.split(k, 3)
            std = (2.0 / (ksz * ksz * c_in)) ** 0.5
            w[f"{name}_conv"] = std * jax.random.normal(
                kw, (ksz, ksz, c_in, c_out), jnp.float32)
            w[f"{name}_gamma"] = 1.0 + 0.1 * jax.random.normal(
                kg, (c_out,), jnp.float32)
            w[f"{name}_beta"] = 0.1 * jax.random.normal(
                kb, (c_out,), jnp.float32)
        kw, kb = jax.random.split(keys[-1])
        w["fc_w"] = 0.01 * jax.random.normal(kw, (2048, classes), jnp.float32)
        w["fc_b"] = 0.01 * jax.random.normal(kb, (classes,), jnp.float32)
        return w

    return build(jax.random.PRNGKey(seed % (2 ** 31)))


def make_batches(seed: int, cfg: dict, n: int, batch: int) -> list:
    """``n`` distinct (images, one-hot labels) batches from the seed, made
    on the device in one jitted call, the images in the compute type: what
    a staged cell trains on. Standard-normal images, uniform labels."""
    size, classes = cfg["image_size"], cfg["num_classes"]
    shape = (n, batch, size, size, cfg["num_channels"])

    @jax.jit
    def build(key):
        kx, ky = jax.random.split(key)
        x = jax.random.normal(kx, shape, jnp.dtype(cfg["compute_dtype"]))
        y = jax.nn.one_hot(jax.random.randint(ky, (n, batch), 0, classes),
                           classes, dtype=jnp.float32)
        return x, y

    xs, ys = build(jax.random.PRNGKey(seed % (2 ** 31)))
    return [(xs[i], ys[i]) for i in range(n)]


def forward_flops(cfg: dict) -> int:
    """Forward multiply-adds x 2 of the convolutions and the classifier for
    one image (batch norm, ReLU and pooling are not counted: under 1%).
    ``SAME`` padding: a stride halves a side, rounding up."""
    size = -(-cfg["image_size"] // 2)           # after the 7x7/2 stem
    total = 0
    for name, k, c_in, c_out, stride in conv_names():
        if name == "stem":
            out = size
            size = -(-size // 2)                # the 3x3/2 max-pool follows
        elif name.endswith("_a"):
            block_in = size
            size = out = -(-size // stride)
        elif name.endswith("_sc"):
            out = -(-block_in // stride)
        else:
            out = size
        total += 2 * k * k * c_in * c_out * out * out
    return total + 2 * 2048 * cfg["num_classes"]


def train_flops_per_example(cfg: dict) -> int:
    """Forward + backward = 3 x forward (the backward pass computes a
    gradient for the input and one for the weights of every product).
    Recomputation never counts."""
    return 3 * forward_flops(cfg)


def _round_to(x, dtype, top):
    """Round to an 8-bit float type about a per-tensor scale and back."""
    s = jnp.max(jnp.abs(x)) / top + 1e-30
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8(x):
    """What an fp8 matrix unit is fed: forward operands in float8_e4m3,
    the gradients that flow back through them in float8_e5m2 (the split
    fp8 training uses)."""
    return _round_to(x, jnp.float8_e4m3fn, 448.0)


_fp8.defvjp(lambda x: (_fp8(x), None),
            lambda _res, g: (_round_to(g, jnp.float8_e5m2, 57344.0),))


def _conv(x, w, stride, lower):
    if lower == "fp8":
        x, w = _fp8(x), _fp8(w)
    return lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)


def _bn(x, gamma, beta):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + BN_EPS) * gamma + beta


def _conv_bn(w, name, x, stride, relu, lower):
    y = _bn(_conv(x, w[f"{name}_conv"], stride, lower),
            w[f"{name}_gamma"], w[f"{name}_beta"])
    return jnp.maximum(y, 0.0) if relu else y


def _block(w, b, x, stride, project, lower):
    y = _conv_bn(w, f"{b}_a", x, stride, True, lower)
    y = _conv_bn(w, f"{b}_b", y, 1, True, lower)
    y = _conv_bn(w, f"{b}_c", y, 1, False, lower)
    sc = _conv_bn(w, f"{b}_sc", x, stride, False, lower) if project else x
    return jnp.maximum(y + sc, 0.0)


def logits(w, x, lower=None, remat=True):
    """Training-mode forward (batch statistics). ``lower="fp8"`` feeds every
    convolution and the classifier fp8-rounded operands — the control.
    ``remat`` recomputes each block in the backward pass so that the float32
    activations of 256 images fit beside nothing else on one 16 GB chip."""
    x = x.astype(jnp.float32)
    x = _conv_bn(w, "stem", x, 2, True, lower)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    for stage, blocks, _f, stride in STAGES:
        for i in range(blocks):
            fn = functools.partial(_block, b=f"{stage}{chr(ord('a') + i)}",
                                   stride=stride if i == 0 else 1,
                                   project=i == 0, lower=lower)
            blk = (lambda w_, x_, fn=fn: fn(w_, x=x_))
            x = (jax.checkpoint(blk) if remat else blk)(w, x)
    x = jnp.mean(x, axis=(1, 2))
    fc = w["fc_w"]
    if lower == "fp8":
        x, fc = _fp8(x), _fp8(fc)
    return jnp.matmul(x, fc, precision=HIGHEST) + w["fc_b"]


def loss_fn(w, x, y, lower=None, remat=True):
    """Mean softmax cross-entropy; ``y`` one-hot (B, classes)."""
    lg = logits(w, x, lower, remat)
    logp = lg - jax.scipy.special.logsumexp(lg, axis=-1, keepdims=True)
    return -jnp.mean(jnp.sum(y * logp, axis=-1))


def adam_step(w, m, v, g, t):
    """Adam, step ``t`` counted from 1."""
    b1, b2, lr, eps = (ADAM[k] for k in ("beta1", "beta2", "lr", "eps"))
    m = jax.tree_util.tree_map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree_util.tree_map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_,
                               v, g)
    alpha = lr * (1 - b2 ** t) ** 0.5 / (1 - b1 ** t)
    w = jax.tree_util.tree_map(
        lambda w_, m_, v_: w_ - alpha * m_ / (jnp.sqrt(v_) + eps), w, m, v)
    return w, m, v


def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(a))) for k, a in tree.items()}


@functools.partial(jax.jit, static_argnames=("lower", "remat"))
def _step(w, m, v, x, y, t, lower=None, remat=True):
    loss, g = jax.value_and_grad(loss_fn)(w, x, y, lower, remat)
    w2, m, v = adam_step(w, m, v, g, t)
    return w2, m, v, loss, _norms(g)


def follow(w0, batches, lower=None, remat=True, fault=None, keep=()):
    """Train through ``batches`` (a list of (x, y)) from ``w0`` and return
    what the benchmark compares: each step's loss, the per-leaf norm of the
    first gradient, the first gradient itself of the leaves in ``keep``
    (their direction is compared; read from Adam's first moment after one
    step, which is a tenth of it), the per-leaf norm of the parameters'
    change over all the steps, and each leaf's count of numbers. ``fault`` plants one of the faults the
    benchmark's check has to catch, in the reference put in the program's
    place: ``"half_batch"`` takes loss and gradient over the first half of
    the rows only (the second half of every batch repeats the first, which
    gives the same batch statistics, loss and gradient from the same
    compiled step)."""
    zeros = jax.tree_util.tree_map(jnp.zeros_like, w0)
    w, m, v = w0, zeros, zeros
    losses, g1, first = [], None, None
    for t, (x, y) in enumerate(batches, start=1):
        if fault == "half_batch":
            n = x.shape[0] // 2
            x = jnp.concatenate([x[:n], x[:n]])
            y = jnp.concatenate([y[:n], y[:n]])
        w, m, v, loss, gn = _step(w, m, v, x, y, jnp.float32(t),
                                  lower=lower, remat=remat)
        losses.append(float(loss))
        if t == 1:
            g1 = {k: float(a) for k, a in gn.items()}
            first = {k: m[k] / (1.0 - ADAM["beta1"]) for k in keep}
    change = _norms(jax.tree_util.tree_map(lambda a, b: a - b, w, w0))
    return {"losses": losses, "grad_norms": g1, "first_grads": first,
            "change_norms": {k: float(a) for k, a in change.items()},
            "sizes": {k: int(a.size) for k, a in w0.items()}}
