"""Fusion-boundary A/B sweep for the flagship ResNet-50 train step.

The r5 trace (2026-07) decomposed the 107.3 ms device step into ≈35.5 ms
irreducible conv compute + ≈35.2 ms bandwidth-floor non-conv work + ≈36 ms
fusion-context cost (convs in the fused step run at ~half their isolated
efficiency). This harness times the CANDIDATES that attack that cost —
per-stage selective-remat policies, optimization-barrier placement, and
process-global XLA flag sets — with the repo's established same-session
methodology and emits a ranked table.

It measures the device step, so it runs on a TPU only: with no TPU it exits
non-zero and prints no table (docs/FUSION_TUNING.md).

Methodology: every timing is a TWO-POINT FIT — wall(K_hi steps) −
wall(K_lo steps) over (K_hi − K_lo) steps with completion forced by a host
fetch — which cancels the fixed per-call dispatch and fetch cost. Each
candidate is median-of-3 fits with the spread reported as ``noise``; a
short trace adds the per-step XPlane device total.

XLA flag candidates are process-global and unknown flags ABORT the XLA
client, so each runs in a fresh subprocess (``--one``); a flag set the build
rejects is recorded as invalid rather than crashing the sweep. A chip
belongs to one process at a time, so those children run FIRST, one after
another, and only then does this process touch JAX for the in-process
candidates.

Usage::

    python benchmarks/fusion_sweep.py                  # the r05 flagship sizes
    python benchmarks/fusion_sweep.py --batch 128 --json sweep.json
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

# runnable as `python benchmarks/fusion_sweep.py` from the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (name, remat_policy, stage_barriers) — the in-process candidates
POLICY_CANDIDATES = [
    ("baseline", None, False),
    ("remat:full_stage", "full", False),
    ("remat:save_conv", "save_conv", False),
    ("remat:save_conv_dots", "save_conv_dots", False),
    ("remat:save_all", "save_all", False),
    ("barriers:stage", None, True),
    ("remat:save_conv+barriers", "save_conv", True),
]


def _build_net(policy, barriers, batch, image, classes, dtype):
    from deeplearning4j_tpu.zoo import ResNet50

    net = ResNet50(num_classes=classes, input_shape=(image, image, 3),
                   compute_dtype=dtype, remat_policy=policy,
                   stage_barriers=barriers).init()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, image, image, 3)).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[rng.integers(0, classes, batch)]
    return net, x, y


def _steps_wall(net, x, y, k):
    """Wall time of k pipelined steps, completion forced by the score fetch."""
    t0 = time.perf_counter()
    for _ in range(k):
        net._fit_batch(x, y)
    float(net.score_value)
    return time.perf_counter() - t0


def measure(policy, barriers, *, batch, image, classes, dtype, k_lo, k_hi,
            repeats=3, xplane=True):
    """One candidate -> dict with per-step ms (two-point fit, median-of-N),
    noise fraction, and the XPlane device total when a device plane exists."""
    import jax

    from deeplearning4j_tpu.util.profiler import (device_trace,
                                                  xplane_device_ms)

    net, x, y = _build_net(policy, barriers, batch, image, classes, dtype)
    x = jax.device_put(x)
    y = jax.device_put(y)
    for _ in range(3):  # warm past compile + sharding commitment
        net._fit_batch(x, y)
    float(net.score_value)
    fits = []
    for _ in range(repeats):
        t_lo = _steps_wall(net, x, y, k_lo)
        t_hi = _steps_wall(net, x, y, k_hi)
        if t_hi > t_lo:
            fits.append((t_hi - t_lo) / (k_hi - k_lo))
    if not fits:
        raise RuntimeError(
            "two-point fit degenerate in every repeat (jitter exceeds the "
            "step-time delta) — refusing to report")
    fits.sort()
    med = fits[len(fits) // 2]
    noise = (fits[-1] - fits[0]) / 2.0 / med if len(fits) > 1 else 0.0
    dev_ms = None
    if xplane:
        with tempfile.TemporaryDirectory() as d:
            with device_trace(d):
                _steps_wall(net, x, y, 3)
            ms = xplane_device_ms(d)
            if ms > 0:
                dev_ms = round(ms / 3.0, 3)
    return {
        "step_ms": round(med * 1e3, 3),
        "img_per_sec": round(batch / med, 1),
        "noise_frac": round(noise, 4),
        "xplane_ms": dev_ms,
        "fits_ms": [round(f * 1e3, 3) for f in fits],
    }


def _run_flag_candidate(name, flags, args):
    """Run one candidate in a subprocess with XLA_FLAGS appended (flags are
    process-global; unknown ones abort the client — per-build validity is
    part of the result)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + flags).strip()
    spec = {"policy": None, "barriers": False, "batch": args.batch,
            "image": args.image, "classes": args.classes, "dtype": args.dtype,
            "k_lo": args.k_lo, "k_hi": args.k_hi}
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--one", json.dumps(spec)],
        env=env, capture_output=True, text=True, timeout=3600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    lines = [l for l in out.stdout.strip().splitlines() if l.startswith("{")]
    if out.returncode != 0 or not lines:
        tail = (out.stderr or out.stdout).strip().splitlines()[-1:]
        return {"error": f"rejected by this build: {' '.join(tail)[:200]}"}
    return json.loads(lines[-1])


def _require_tpu():
    """This harness measures the device step: no TPU, no table."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"fusion_sweep measures the device step and found no TPU "
                 f"(platform {dev.platform!r}): nothing measured")
    return dev


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--image", type=int, default=None)
    ap.add_argument("--classes", type=int, default=None)
    ap.add_argument("--dtype", default=None)
    ap.add_argument("--k-lo", type=int, default=None)
    ap.add_argument("--k-hi", type=int, default=None)
    ap.add_argument("--json", default=None, help="write full results here")
    ap.add_argument("--skip-flags", action="store_true",
                    help="skip the subprocess XLA-flag candidates")
    ap.add_argument("--one", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.one:  # subprocess worker: one candidate, one JSON line
        _require_tpu()
        spec = json.loads(args.one)
        r = measure(spec["policy"], spec["barriers"], batch=spec["batch"],
                    image=spec["image"], classes=spec["classes"],
                    dtype=spec["dtype"], k_lo=spec["k_lo"], k_hi=spec["k_hi"])
        print(json.dumps(r))
        return

    # the r05 flagship sizes
    args.batch = args.batch or 256
    args.image = args.image or 224
    args.classes = args.classes or 1000
    args.dtype = args.dtype or "bfloat16"
    args.k_lo = args.k_lo or 8
    args.k_hi = args.k_hi or 40

    from deeplearning4j_tpu.util.xla_tuning import XLA_FLAG_CANDIDATES

    flag_results = []
    if not args.skip_flags:  # children first: this process is still off JAX
        for name, flags in XLA_FLAG_CANDIDATES:
            print(f"[sweep] {name} ({flags}) ...", file=sys.stderr, flush=True)
            r = _run_flag_candidate(name, flags, args)
            flag_results.append({"candidate": name, "xla_flags": flags, **r})

    import jax

    dev = _require_tpu()
    results = []
    for name, policy, barriers in POLICY_CANDIDATES:
        print(f"[sweep] {name} ...", file=sys.stderr, flush=True)
        try:
            r = measure(policy, barriers, batch=args.batch, image=args.image,
                        classes=args.classes, dtype=args.dtype,
                        k_lo=args.k_lo, k_hi=args.k_hi)
        except Exception as e:  # noqa: BLE001 — a candidate failing is data
            r = {"error": f"{type(e).__name__}: {e}"}
        results.append({"candidate": name, **r})
    results += flag_results

    ok = [r for r in results if "step_ms" in r]
    ok.sort(key=lambda r: r["step_ms"])
    base = next((r for r in ok if r["candidate"] == "baseline"), None)
    header = (f"fusion sweep: ResNet-50 B={args.batch} {args.image}px "
              f"{args.dtype} ({dev.device_kind} x{len(jax.devices())}, "
              f"two-point fit K={args.k_lo}/{args.k_hi}, median-of-3)")
    print(header)
    print("| candidate | step ms | img/s | vs baseline | noise | xplane ms |")
    print("|---|---|---|---|---|---|")
    for r in ok:
        rel = (f"{base['step_ms'] / r['step_ms']:.3f}x" if base else "—")
        xp = r["xplane_ms"] if r["xplane_ms"] is not None else "—"
        print(f"| {r['candidate']} | {r['step_ms']} | {r['img_per_sec']} "
              f"| {rel} | ±{100 * r['noise_frac']:.1f}% | {xp} |")
    for r in results:
        if "error" in r:
            print(f"| {r['candidate']} | INVALID: {r['error'][:90]} |")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"config": vars(args),
                       "device": {"platform": dev.platform,
                                  "kind": dev.device_kind,
                                  "count": len(jax.devices())},
                       "results": results}, f, indent=2)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
