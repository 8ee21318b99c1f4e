"""Whole-conv-mix isolated measurement (the r5 conv re-derivation, 2026-07;
a device measurement — run it on the chip).

ONE jitted scan whose body runs every ResNet-50 conv instance
(count-weighted, per-instance weights so CSE cannot merge them), fwd and
fwd+bwd variants. Per-iter time is ~tens of ms, so the two-point fit sits
far above dispatch jitter. The conv consumer is sum(y*y): a plain
sum(conv(x, w)) folds algebraically in XLA and reports impossible TF/s.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

B = 256
SHAPES = [
    (224, 3, 64, 7, 2, 1), (56, 64, 64, 1, 1, 1), (56, 256, 64, 1, 1, 2),
    (56, 64, 64, 3, 1, 3), (56, 64, 256, 1, 1, 3), (56, 64, 256, 1, 1, 1),
    (56, 256, 128, 1, 1, 1), (56, 128, 128, 3, 2, 1), (28, 512, 128, 1, 1, 3),
    (28, 128, 128, 3, 1, 3), (28, 128, 512, 1, 1, 4), (56, 256, 512, 1, 2, 1),
    (28, 512, 256, 1, 1, 1), (28, 256, 256, 3, 2, 1), (14, 1024, 256, 1, 1, 5),
    (14, 256, 256, 3, 1, 5), (14, 256, 1024, 1, 1, 6), (28, 512, 1024, 1, 2, 1),
    (14, 1024, 512, 1, 1, 1), (14, 512, 512, 3, 2, 1), (7, 2048, 512, 1, 1, 2),
    (7, 512, 512, 3, 1, 2), (7, 512, 2048, 1, 1, 3), (14, 1024, 2048, 1, 2, 1),
]

rng = np.random.default_rng(0)
xs, ws, flops = [], [], 0
for h, cin, cout, k, s, count in SHAPES:
    xs.append(jnp.asarray(rng.normal(size=(B, h, h, cin)), jnp.bfloat16))
    # one DISTINCT weight tensor per instance: the conv must run count
    # times (same weights would CSE into one conv)
    ws.append([jnp.asarray(rng.normal(size=(k, k, cin, cout)) * 0.05,
                           jnp.bfloat16) for _ in range(count)])
    flops += count * 2 * B * (h // s) ** 2 * k * k * cin * cout


def convs(xs, ws, eps):
    acc = jnp.float32(0)
    for (h, cin, cout, k, s, count), x, wlist in zip(SHAPES, xs, ws):
        for w in wlist:
            dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                            ("NHWC", "HWIO", "NHWC"))
            y = lax.conv_general_dilated(x + eps.astype(x.dtype), w,
                                         (s, s), "SAME",
                                         dimension_numbers=dn)
            # nonlinear reduce: sum(conv) folds algebraically; y*y cannot
            acc = acc + jnp.sum(
                y.astype(jnp.float32) * y.astype(jnp.float32))
    return acc


def train(xs, ws, eps):
    def loss(ws):
        return convs(xs, ws, eps)
    l, gs = jax.value_and_grad(loss)(ws)
    return l + sum(jnp.sum(g).astype(jnp.float32)
                   for gl in gs for g in gl)


def per_iter(fn, klo=2, khi=8):
    def make(iters):
        @jax.jit
        def many(xs, ws):
            def body(c, s):
                return c + fn(xs, ws, s), None
            out, _ = lax.scan(body, jnp.float32(0),
                              jnp.arange(iters, dtype=jnp.float32) * 1e-6)
            return out
        return many

    lo, hi = make(klo), make(khi)
    float(lo(xs, ws)); float(hi(xs, ws))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter(); float(lo(xs, ws)); tl = time.perf_counter() - t0
        t0 = time.perf_counter(); float(hi(xs, ws)); th = time.perf_counter() - t0
        if th > tl:
            best = min(best, (th - tl) / (khi - klo))
    if best == float("inf"):
        raise RuntimeError(
            "two-point fit degenerate in all 3 attempts (jitter exceeds "
            "the device-time delta) — refusing to report")
    return best


fwd = per_iter(convs)
tr = per_iter(train)
print(f"isolated conv mix (count-weighted, B={B}, bf16):")
print(f"  fwd      {fwd*1e3:7.2f} ms/iter  -> {flops/fwd/1e12:5.1f} TF/s")
print(f"  fwd+bwd  {tr*1e3:7.2f} ms/iter  -> {3*flops/tr/1e12:5.1f} TF/s "
      f"(3x fwd FLOPs)")
print(f"  fwd FLOPs of the mix: {flops/1e12:.2f} TF")
