#!/usr/bin/env bash
# Shell transcription of .github/workflows/ci.yml (VERDICT r5 weak #5: the
# YAML itself has never executed on a GitHub runner). Each step below mirrors
# one `steps:` entry so the job's commands and env are exercised locally;
# what CANNOT be validated here is the Actions plumbing itself (checkout@v4,
# setup-python@v5, the pip resolve against pypi.org and the apt install on
# the ubuntu-latest image) — those steps degrade to presence checks.
#
#   bash .github/ci_local.sh              # full suite, exact CI env
#   bash .github/ci_local.sh -m 'not slow'  # extra pytest args pass through
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== step: checkout (actions/checkout@v4) =="
test -d .git && echo "repo present: $(git rev-parse --short HEAD)"

echo "== step: setup-python (actions/setup-python@v5, wants 3.12) =="
python --version

echo "== step: Install (pip install jax ... torch) =="
# No network installs locally; validate the dependency set the step would
# produce by importing every package it names.
python - <<'EOF'
import importlib
for mod in ("jax", "flax", "optax", "orbax.checkpoint", "chex", "einops",
            "numpy", "PIL", "pyarrow", "pytest", "tensorflow", "torch"):
    importlib.import_module(mod)
    print(f"  import {mod}: ok")
EOF

echo "== step: Native build deps (g++, libjpeg, libpng) =="
g++ --version | head -1
# the native runtime self-compiles on first import; jpeg/png headers gate
# the image leg (native/__init__.py degrades without them)
for h in /usr/include/jpeglib.h /usr/include/png.h; do
    if [ -e "$h" ]; then echo "  $h: present"; else echo "  $h: MISSING (image leg will skip)"; fi
done

echo "== step: Host-pipeline tests (2-worker multiprocess ETL leg) =="
# ISSUE 2: the async host-pipeline suite under a FORCED 2-worker executor —
# DL4J_TPU_ETL_WORKERS pins the worker count so the multiprocess merge path
# (not the auto-sized or serial fallback) is what the bit-identity tests hit.
DL4J_TPU_ETL_WORKERS=2 \
JAX_PLATFORMS=cpu \
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m pytest tests/test_host_pipeline.py -q

echo "== step: Compile-cache tests (persistent cache, two runs warm/cold) =="
# ISSUE 3: the bucketing/compile-once suite twice against ONE persistent
# compilation_cache_dir (second run starts warm), then the sweep's --ci
# assertions: warm-process compile count drops (cache hits > 0), bucketed
# ragged epoch adds 0 extra traces, unbucketed adds >= 1.
CC_DIR=$(mktemp -d /tmp/dl4j-ci-compile-cache.XXXXXX)
# The cache is placed from outside through JAX's own variables
# (util/compile_cache.py: the program sets no directory in code).
export JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0
export JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES=-1
JAX_PLATFORMS=cpu JAX_COMPILATION_CACHE_DIR="$CC_DIR" \
    python -m pytest tests/test_compile_cache.py -q
JAX_PLATFORMS=cpu JAX_COMPILATION_CACHE_DIR="$CC_DIR" \
    python -m pytest tests/test_compile_cache.py -q
unset JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES
JAX_PLATFORMS=cpu python benchmarks/compile_cache_sweep.py --ci
rm -rf "$CC_DIR"

echo "== step: Telemetry smoke (2-step fit, /metrics + /healthz, trace schema) =="
# ISSUE 4: full observability chain — a 2-step fit through mp-ETL + prefetch
# + bucketed dispatch with the health monitor on, then the script curls the
# live server's /metrics (Prometheus text incl. compile/step-time/queue-
# depth gauges) and /healthz, and validates the merged Chrome trace loads
# with spans from >= 3 distinct PIDs/threads (event schema check).
JAX_PLATFORMS=cpu python benchmarks/telemetry_smoke.py

echo "== step: Fault-tolerance smoke (ETL kill + NaN rollback + host SIGKILL) =="
# ISSUE 6: every injected fault takes its recovery path on the REAL
# mechanism — SIGKILLed ETL worker's chunk restarts (bit-identical output),
# NaN batch rolls back to the last good checkpoint and completes, and a
# 2-process elastic pod survives one host SIGKILLed mid-epoch (survivor
# regroups + re-shards); recoveries visible on /healthz + /metrics.
JAX_PLATFORMS=cpu python benchmarks/fault_smoke.py

echo "== step: GSPMD sharded-fit bit-identity + ZeRO memory =="
# ISSUE 7: the deterministic lane mode must make an 8-virtual-device
# sharded fit BIT-identical to the single-device fit (params, Adam
# moments, RNG key) on dense MLN / multi-io CG / TBPTT-LSTM topologies,
# ZeRO must cut optimizer-state bytes/device ~8x, elastic reshard must
# recompile onto the shrunken mesh, and the sharded cost report must
# expose honest per-device + global totals.
JAX_PLATFORMS=cpu \
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m pytest tests/test_gspmd_identity.py -q

echo "== step: Serving smoke (model server + continuous batching + drain) =="
# ISSUE 8: the HTTP model server (dense classifier + causal BERT-tiny
# KV-cache decoder) under concurrent mixed-model traffic — all 200s, p99
# under the sanity bound, steady-state serving.recompiles_total delta 0,
# bit-identical classify responses, 429/404 shed contract, /metrics +
# /healthz serving surfaces, graceful drain -> 503.
JAX_PLATFORMS=cpu python benchmarks/serving_smoke.py

echo "== step: Resilience smoke (reload storm + fault recoveries + brownout) =="
# ISSUE 13: the serving resilience layer end-to-end on real HTTP — 5
# rolling reloads under mixed traffic (zero shed, zero recompiles, version
# advancing), corrupt archive -> 409 with the old version still serving,
# serving_worker_crash -> 500 + flight cause + supervised restart,
# serving_compute_error -> breaker open (503 + Retry-After) then half-open
# probe closes, serving_slow_batch -> deadline shed behind the stall, SLO
# exhaustion -> batch-lane brownout while interactive serves, clean drain.
JAX_PLATFORMS=cpu python benchmarks/resilience_smoke.py

echo "== step: Decode smoke (paged KV + speculative + int8 + prefix cache over HTTP) =="
# ISSUE 15: the planet-scale decode path on real HTTP — mixed-length
# paged+speculative traffic TOKEN-IDENTICAL to the non-speculative greedy
# reference with 0 steady-state recompiles, pool exhaustion -> first-class
# 429 + Retry-After + pool_exhausted flight cause + block reuse after the
# shed, paged concurrent streams beating the contiguous-cache ceiling,
# int8 serving alongside fp32 (resident + archive bytes >= 3.5x below
# fp32, gauge-asserted), spec_accept_rate/draft_accept_rate surfaces.
# Plus the ISSUE 16 legs: prefix-heavy traffic (shared system prompt)
# token-identical cold AND warm with hit_rate > 0, 0 recompiles and the
# 429 contract intact under prefix sharing; long-prompt chunked-prefill
# burst with bounded interactive latency.
JAX_PLATFORMS=cpu python benchmarks/decode_smoke.py

echo "== step: Fleet smoke (2-worker prefix-affinity routing over real processes) =="
# ISSUE 18: the disaggregated serving fleet end-to-end — a FleetRouter over
# 2 real worker processes: mixed classify+generate traffic all-200s and
# token-identical to a single-process oracle loaded from the same archives,
# 0 steady-state recompiles per worker, prefix-affinity routing decisions
# and per-worker prefix_cache_hit_rate >= the single-process value scraped
# from the fleet /metrics fan-in, one worker SIGKILLed mid-burst with zero
# request loss after client retry + respawn back into the ring, and a
# fleet-wide rolling reload under live traffic with zero shed and every
# worker's version advancing.
JAX_PLATFORMS=cpu python benchmarks/fleet_smoke.py

echo "== step: Kernel-engine equivalence (Pallas interpret, fused optimizer) =="
# ISSUE 9: the hot-path kernel suite with the dispatch knob FORCED to
# pallas — off-TPU that is the Pallas interpreter, bit-faithful to the
# kernel block program — under 8 virtual devices for the ZeRO-sharded
# fused-buffer leg: conv fwd/grads grid vs lax conv, LSTM cell/sequence/
# TBPTT trajectories vs the exact scan, fused optimizer bit-identity vs
# per-leaf, dynamic loss-scale skip/grow, masked flash vs exact.
DL4J_TPU_KERNEL_IMPL=pallas \
JAX_PLATFORMS=cpu \
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m pytest tests/test_kernels.py -q

echo "== step: Compression smoke (conservation + t->0 identity + wire ratio) =="
# ISSUE 10: the encoded gradient all-reduce on 8 virtual devices —
# error-feedback conservation bit-exact, threshold->0 fit bit-identical to
# the uncompressed deterministic lane path, wire-bytes counter > 0 and
# sparse ratio < 0.1 once the adaptive threshold reaches its target band.
JAX_PLATFORMS=cpu \
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python benchmarks/compression_smoke.py

echo "== step: Autotune smoke (sweep + planted gates + warm DB + dispatch) =="
# ISSUE 11: the autotuning machinery end-to-end — cold sweep with a
# planted-slow candidate (loses) and a planted-wrong candidate (rejected
# by the equivalence gate), deterministic DB across independent cold
# sweeps, warm process re-measures nothing, and kernel_impl=auto dispatch
# resolves through the armed database at trace time.
JAX_PLATFORMS=cpu \
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python benchmarks/autotune_smoke.py

echo "== step: Pipeline smoke (3D mesh: bytes/device + trajectory + compose) =="
# ISSUE 14: the pipeline-parallel fit() on the (data=2, model=2, pipe=2)
# 8-virtual-device mesh — a model whose replicated param+optimizer
# footprint busts a per-device budget places at ~1/pipe_stages
# bytes/device and trains; the fit tracks the unpipelined trajectory and
# is BIT-identical across data folds with the pipe placement fixed;
# grad_compression t->0 composes bit-identically under ZeRO; the bubble
# fraction equals the GPipe schedule expression (computed, never timed).
JAX_PLATFORMS=cpu \
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python benchmarks/pipeline_smoke.py

echo "== step: Perf-regression gate (BENCH bands + injected-regression self-test) =="
# ISSUE 5: the committed BENCH_r*.json trajectory becomes machine-checked
# bands (noise-aware, direction-aware); the latest record must pass, and
# the self-test must prove the gate FAILS on a synthetic regression.
python benchmarks/regression_gate.py --ci

echo "== step: Test (pytest, JAX_PLATFORMS=cpu, 8 virtual devices) =="
_pytest_t0=$(date +%s)
JAX_PLATFORMS=cpu \
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m pytest tests/ -q "$@"
_pytest_wall=$(( $(date +%s) - _pytest_t0 ))
echo "pytest wall-clock: ${_pytest_wall}s"

# Tier-1 runtime guard (ISSUE 11 satellite): the driver's tier-1 command
# runs `-m 'not slow'` under a hard 870s timeout — a run that creeps past
# it stops reporting results at all, so the budget must never regress
# SILENTLY. When this script is invoked with the tier-1 marker set, fail
# loudly at 850s: new heavy tests must be `slow`-marked (ROADMAP) or a
# cheap sibling must take their seam over.
case "$*" in
  *"not slow"*)
    if [ "${_pytest_wall}" -gt 850 ]; then
        echo "TIER-1 RUNTIME GUARD: wall-clock ${_pytest_wall}s exceeds" \
             "the 850s guard (hard driver timeout: 870s)." >&2
        echo "slow-mark the offenders (pytest --durations=30) before the" \
             "budget dies silently." >&2
        exit 1
    fi
    echo "tier-1 runtime guard: ${_pytest_wall}s <= 850s budget guard"
    ;;
esac
