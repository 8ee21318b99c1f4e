"""Compile-once A/B sweep: shape bucketing x persistent compilation cache.

PR 1 tuned the device step (fusion_sweep.py), PR 2 the host pipeline
(host_pipeline_sweep.py); this harness measures the remaining systematic
waste — XLA RECOMPILATION — and the two levers ISSUE 3 builds against it:

  bucketing   ragged batches pad to a fixed bucket set (data/bucketing.py):
              a ragged-tail epoch (N % B != 0) must trace the train step
              exactly ONCE (0 extra compiles) vs >= 1 extra without
  cache       the persistent on-disk compilation cache
              (util/compile_cache.py): a second PROCESS against the same
              cache dir deserializes executables instead of recompiling —
              cold-start wall drops and backend-compile counts collapse

Every cell runs in a fresh child process (compile state is process-global;
only a cold process measures cold start honestly). The child trains a
ragged-tail epoch on the LeNet-5 bench model (flagship-independent, no
BatchNorm — bucketing's bit-identity regime) and reports trace counts from
the CompileWatcher, process-global backend compiles, persistent-cache hits,
and launch-to-first-step wall. Wall cells are median-of-3 with the standard
``noise`` field.

Usage::

    python benchmarks/compile_cache_sweep.py             # full table
    python benchmarks/compile_cache_sweep.py --runs 1    # quick look
    python benchmarks/compile_cache_sweep.py --json out.json
    python benchmarks/compile_cache_sweep.py --ci        # assert-mode:
        # one shared cache dir, two processes: the second's backend-compile
        # count must DROP and its cache hits must be > 0; bucketed ragged
        # epoch must add 0 extra traces while unbucketed adds >= 1.
        # Exits nonzero on violation (the CI cache leg runs this).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile

# runnable as `python benchmarks/compile_cache_sweep.py` from the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import _med3  # noqa: E402

_CHILD = r"""
import json, sys, time
T0 = time.perf_counter()
import jax
jax.config.update("jax_platforms", "cpu")
import os
cfg = json.loads(sys.argv[1])
if os.environ.get("JAX_COMPILATION_CACHE_DIR"):   # run_child places it
    from deeplearning4j_tpu.util.compile_cache import enable_persistent_cache
    enable_persistent_cache()
import numpy as np
from deeplearning4j_tpu.data import ArrayDataSetIterator
from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.layers import (ConvolutionLayer, DenseLayer,
                                          OutputLayer, SubsamplingLayer)
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.util import get_watcher

w = get_watcher()   # install monitoring hooks BEFORE any compile happens
b = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-3))
     # explicit on BOTH axes: cfg decides, never an ambient env default
     .batch_buckets(tuple(cfg["buckets"]) if cfg["buckets"] else None)
     .seq_buckets(None))
conf = (b.list()
        .layer(ConvolutionLayer(n_out=8, kernel_size=(5, 5), padding="VALID",
                                activation="relu"))
        .layer(SubsamplingLayer(kernel_size=(2, 2)))
        .layer(DenseLayer(n_out=32, activation="relu"))
        .layer(OutputLayer(n_in=32, n_out=10))
        .set_input_type(InputType.convolutional(28, 28, 1))
        .build())
net = MultiLayerNetwork(conf).init()
rng = np.random.default_rng(0)
B, N = cfg["batch"], cfg["n"]
x = rng.normal(size=(N, 28, 28, 1)).astype(np.float32)
y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, N)]
t_first = None
it = ArrayDataSetIterator(x, y, batch=B)
for epoch in range(2):
    it.reset()
    for ds in it:
        net._fit_batch(ds.features, ds.labels)
        if t_first is None:
            float(net.score_value)
            t_first = time.perf_counter() - T0
float(net.score_value)
counts = w.counts()
print(json.dumps({
    "cold_start_s": round(t_first, 3),
    "total_s": round(time.perf_counter() - T0, 3),
    "step_traces": w.traces.get("MultiLayerNetwork.train_step", 0),
    "backend_compiles": counts["backend_compiles"],
    # jax logs a backend_compile event even on a persistent-cache hit; the
    # honest recompile count subtracts the hits
    "uncached_compiles": counts["uncached_compiles"],
    "compile_seconds": round(w.backend_compile_seconds, 3),
    "persistent_cache_hits": w.persistent_cache_hits,
}))
"""


def run_child(buckets, cache_dir, batch=8, n=20):
    cfg = {"buckets": buckets, "batch": batch, "n": n}
    # scrub inherited knobs: an ambient DL4J_TPU_BUCKETS would bucket the
    # "unbucketed" baseline, an ambient JAX_COMPILATION_CACHE_DIR would
    # un-uncache the nocache cells — only the arguments control the A/B.
    # The throwaway cache is placed from outside, through JAX's variable
    # (util/compile_cache.py: the program sets no directory in code).
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("DL4J_TPU_")
           and k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if cache_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    out = subprocess.run(
        [sys.executable, "-c", _CHILD, json.dumps(cfg)], env=env,
        capture_output=True, text=True, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    lines = [l for l in out.stdout.strip().splitlines() if l.startswith("{")]
    if not lines:
        raise RuntimeError(
            f"child failed (rc={out.returncode}):\n{out.stderr[-2000:]}")
    return json.loads(lines[-1])


def sweep(runs: int, batch: int, n: int):
    """Full table: {no cache, cache cold, cache warm} x {bucketing off/on}."""
    rows = []
    for buckets in (None, [batch]):
        label = f"bucketing={'on' if buckets else 'off'}"
        td = tempfile.mkdtemp(prefix="dl4j_cc_sweep_")
        try:
            samples = {"nocache": [], "cold": [], "warm": []}

            def one():
                shutil.rmtree(td, ignore_errors=True)
                os.makedirs(td, exist_ok=True)
                samples["nocache"].append(run_child(buckets, None, batch, n))
                samples["cold"].append(run_child(buckets, td, batch, n))
                samples["warm"].append(run_child(buckets, td, batch, n))
                return samples["warm"][-1]["cold_start_s"] / \
                    samples["cold"][-1]["cold_start_s"]

            ratio, noise = _med3(one, runs=runs) if runs > 1 else (one(), "n/a")
            med = lambda key, field: sorted(  # noqa: E731
                s[field] for s in samples[key])[len(samples[key]) // 2]
            rows.append({
                "config": label,
                "step_traces_ragged_epoch": med("nocache", "step_traces"),
                "nocache_cold_start_s": med("nocache", "cold_start_s"),
                "cache_cold_start_s": med("cold", "cold_start_s"),
                "cache_warm_start_s": med("warm", "cold_start_s"),
                "warm_over_cold": round(ratio, 4),
                "warm_over_cold_noise": noise,
                "cold_uncached_compiles": med("cold", "uncached_compiles"),
                "warm_uncached_compiles": med("warm", "uncached_compiles"),
                "warm_cache_hits": med("warm", "persistent_cache_hits"),
            })
        finally:
            shutil.rmtree(td, ignore_errors=True)
    return rows


def print_table(rows):
    cols = ["config", "step_traces_ragged_epoch", "nocache_cold_start_s",
            "cache_cold_start_s", "cache_warm_start_s", "warm_over_cold",
            "cold_uncached_compiles", "warm_uncached_compiles",
            "warm_cache_hits"]
    widths = {c: max(len(c), *(len(str(r[c])) for r in rows)) for c in cols}
    print("  ".join(c.ljust(widths[c]) for c in cols))
    for r in rows:
        print("  ".join(str(r[c]).ljust(widths[c]) for c in cols))


def ci_check(batch: int, n: int) -> int:
    """Assert-mode for the CI cache leg: exits nonzero on any violation."""
    failures = []
    td = tempfile.mkdtemp(prefix="dl4j_cc_ci_")
    try:
        cold = run_child([batch], td, batch, n)
        warm = run_child([batch], td, batch, n)
    finally:
        shutil.rmtree(td, ignore_errors=True)
    print(f"cold: {json.dumps(cold)}")
    print(f"warm: {json.dumps(warm)}")
    if not warm["uncached_compiles"] < cold["uncached_compiles"]:
        failures.append(
            f"warm-process compile count did not drop "
            f"({warm['uncached_compiles']} vs {cold['uncached_compiles']} "
            "uncached compiles)")
    if not warm["persistent_cache_hits"] > 0:
        failures.append("warm process saw 0 persistent-cache hits")
    bucketed = run_child([batch], None, batch, n)
    unbucketed = run_child(None, None, batch, n)
    print(f"bucketed ragged epoch:   traces={bucketed['step_traces']}")
    print(f"unbucketed ragged epoch: traces={unbucketed['step_traces']}")
    if bucketed["step_traces"] != 1:
        failures.append(
            f"bucketed ragged epoch traced {bucketed['step_traces']}x "
            "(want exactly 1 — 0 extra compiles)")
    if unbucketed["step_traces"] < 2:
        failures.append(
            f"unbucketed ragged epoch traced {unbucketed['step_traces']}x "
            "(want >= 2 — the ragged tail must cost a compile)")
    for f in failures:
        print(f"FAIL: {f}", file=sys.stderr)
    if not failures:
        print("compile-cache CI check: OK")
    return 1 if failures else 0


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--runs", type=int, default=3,
                    help="median-of-N for the wall cells (default 3)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--n", type=int, default=20,
                    help="examples per epoch (N %% batch != 0 => ragged)")
    ap.add_argument("--json", help="also write rows as JSON to this path")
    ap.add_argument("--ci", action="store_true",
                    help="assert-mode (cache-hit drop + 0-extra-compile "
                         "bucketing); exits nonzero on violation")
    args = ap.parse_args()
    if args.n % args.batch == 0:
        ap.error("--n must not be divisible by --batch (ragged tail needed)")
    if args.ci:
        sys.exit(ci_check(args.batch, args.n))
    rows = sweep(args.runs, args.batch, args.n)
    print_table(rows)
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(rows, fh, indent=2)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
