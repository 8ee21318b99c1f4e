"""Runtime environment flags (Nd4jEnvironmentVars / ND4JSystemProperties /
native Environment parity — SURVEY.md §5.6 tiers (b) and (c)).

Three config tiers, mirroring the reference:
(a) model configs — Jackson-JSON builder DSL → `nn/conf.py` (JSON round-trip);
(b) runtime flags — environment variables read here at import and mutable at
    runtime through :class:`Environment` (the reference's
    ``Nd4j.getEnvironment()`` singleton);
(c) backend toggles — forwarded to JAX/XLA where an equivalent exists.

Recognized variables (DL4J_TPU_* namespace; reference names in comments):

- ``DL4J_TPU_DEBUG``       — verbose op logging hooks    (SD_DEBUG / debug mode)
- ``DL4J_TPU_VERBOSE``     — DEBUG-level logging on the 'deeplearning4j_tpu'
  logger (SD_VERBOSE)
- ``DL4J_TPU_PROFILING``   — install OpProfiler at import (profiling mode)
- ``DL4J_TPU_NAN_PANIC``   — raise on NaN/Inf op outputs  (ProfilerConfig.nanPanic)
- ``DL4J_TPU_COMPUTE_DTYPE`` — default compute dtype for new configs
  ("float32" | "bfloat16")   (ND4J default dtype)
- ``DL4J_TPU_REMAT_POLICY`` — default selective-remat policy for new configs
  ("none" | "full" | "save_conv" | … — see util/xla_tuning.py; TPU-native,
  no reference equivalent). The fusion-sweep harness uses this to A/B
  policies without code changes.
- ``DL4J_TPU_SYNC_EVERY`` — default ``sync_every`` for new configs (≥1):
  fit() fetches the per-step loss to the host every N steps and dispatches
  TrainingListener callbacks in coalesced batches instead of risking a
  device sync per iteration (docs/HOST_PIPELINE.md; TPU-native, no
  reference equivalent — the JVM listener bus had no device round-trip).
- ``DL4J_TPU_ETL_WORKERS`` — worker-process count for the multiprocess
  TransformProcess executor (datavec/executor.py); 0/unset = one per host
  core, capped at 8 (the reference sizes Spark executors the same way).
- ``DL4J_TPU_BUCKETS`` — default shape-bucketing spec for new configs
  ("pow2" | "batch=8,16,32;seq=pow2" — data/bucketing.py,
  docs/COMPILE_CACHE.md): ragged batches pad to a fixed bucket set so the
  jitted step compiles once per bucket. TPU-native; the closest reference
  knob is cudnnAlgoMode's compile-once-per-shape algo selection.
- ``DL4J_TPU_TELEMETRY`` — unified telemetry registry (util/telemetry.py,
  docs/OBSERVABILITY.md): counters/gauges/histograms, cross-process trace
  spans, /metrics + /healthz on the UI server. Default ON (a span is two
  clock reads and a locked append; its cost on the chip: not measured); set to
  0/false to strip every recording hook.
- ``DL4J_TPU_TRACE_SAMPLE`` — serving request-trace head-sampling keep
  fraction in [0, 1] (serving/scheduler.py,
  docs/OBSERVABILITY.md#request-tracing--slos): the fraction of healthy
  requests whose per-phase spans (queue wait / batch fill / compute /
  per-token decode) land on the merged trace. Slow, shed, and errored
  requests are ALWAYS kept regardless of the dice; ``0`` disables
  request tracing entirely. Unset = 0.02. The flight recorder is independent of
  this knob and always records.
- ``DL4J_TPU_FAULTS`` — chaos knob for the elastic runtime
  (util/faults.py, docs/FAULT_TOLERANCE.md): arm injectable faults as
  ``"kind[@step][:arg]"`` pairs, e.g.
  ``"kill_etl_worker,inject_nan@5,stall_prefetch:3.0"``. Kinds:
  ``kill_etl_worker`` (SIGKILL a transform worker), ``stall_prefetch``
  (wedge the producer thread), ``drop_heartbeat`` (membership sees this
  host die), ``inject_nan`` (poison one batch), ``sigkill_host`` (kill
  this process). Read once at first injector access; unknown kinds raise.
  Unset = no faults (the injector costs one dict lookup per seam).
- ``DL4J_TPU_PEAK_FLOPS`` — the accelerator's peak FLOP/s, either a bare
  number (``1.97e14``) or a per-dtype table (``bf16=1.97e14,fp32=9.85e13``
  — TPU peaks differ ~2x by dtype, so a bf16 run must not compute MFU
  against the fp32 roof). Enables MFU (model FLOPs utilization) in
  ``net.cost_report()`` (which looks up its conf's compute dtype in the
  table), the ``/costs`` route, and the
  ``train.model_flops_utilization`` telemetry gauge (util/cost_model.py,
  docs/OBSERVABILITY.md). Unset = throughput is still reported,
  utilization is not (no silent guesses about the hardware).
- ``DL4J_TPU_KERNEL_IMPL`` — default hot-path kernel dispatch for new
  configs and direct op calls ("auto" | "exact" | "pallas" —
  ops/kernels/, docs/KERNELS.md): ``auto`` takes the XLA-HLO exact path
  unless the tuning database holds a measured Pallas winner for the call
  site on this backend, ``exact`` pins the exact path, ``pallas`` forces
  the hand-tiled conv/LSTM kernels (Mosaic-compiled on TPU, where a
  compiler refusal raises; the Pallas interpreter elsewhere — the
  correctness-test mode).
- ``DL4J_TPU_FUSED_UPDATE`` — default ``fused_update`` for new configs:
  the optimizer apply runs over dtype-grouped contiguous buffers in the
  donated train step instead of walking the param tree per leaf
  (docs/KERNELS.md#fused-optimizer-apply).
- ``DL4J_TPU_TUNING_DB`` — directory of the persistent autotuning
  database (tuning/database.py, docs/AUTOTUNE.md): measured winners keyed
  by (op, shape-signature, dtype, backend, topology), written by
  ``benchmarks/autotune.py`` sweeps and consulted at trace time by
  ``kernel_impl=auto`` dispatch (conv/LSTM impl + tile parameters) and by
  conf-time knob defaulting (an unset ``remat_policy`` takes the measured
  winner). Every entry is equivalence-gated before commit — the r6
  honesty convention made executable. Empty/unset = off (auto then takes
  the exact path everywhere).
- ``DL4J_TPU_PIPE_STAGES`` — default ``pipe_stages`` for new configs
  (parallel/pipelined.py, docs/DISTRIBUTED.md#pipeline-parallelism):
  partition the net into N pipeline stages at its ``stage_boundary()``
  markers and let ``PipelinedTrainer`` place the stacked stage params
  over the mesh 'pipe' axis — "model too big for one chip" as a config
  knob. 0/unset = off. Inert on single-device ``fit()``.
- ``DL4J_TPU_GRAD_COMPRESSION`` — default ``grad_compression`` for new
  configs ("none" | "threshold" | "bitmap" | "onebit" —
  parallel/compression.py, docs/DISTRIBUTED.md#gradient-compression):
  ParallelWrapper then runs the encoded gradient all-reduce — per-worker
  encode(grad + error-feedback residual), all-reduce of the quantized
  payload, dense decode before the update. The reference's
  EncodedGradientsAccumulator threshold/bitmap wire machinery, collapsed
  into the one jit-compiled GSPMD step.

The persistent compilation cache has no knob of its own: JAX's
``JAX_COMPILATION_CACHE_DIR`` places it, and entry points that call
``util.compile_cache.enable_persistent_cache()`` fall back to
``<checkout>/.jax_cache`` when it is unset (docs/COMPILE_CACHE.md).
"""

from __future__ import annotations

import os
from typing import Optional


def _env_bool(name: str, default: bool = False) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


def _env_int(name: str, default: int, floor: int = 0) -> int:
    v = os.environ.get(name)
    if v is None or not v.strip():
        return default
    try:
        n = int(v.strip())
    except ValueError:
        raise ValueError(f"{name} must be an integer, got {v!r}") from None
    if n < floor:
        raise ValueError(f"{name} must be >= {floor}, got {n}")
    return n


class Environment:
    """Mutable runtime-flag singleton (Nd4j.getEnvironment() parity)."""

    _instance: Optional["Environment"] = None

    def __init__(self):
        self.debug = _env_bool("DL4J_TPU_DEBUG")
        self.verbose = _env_bool("DL4J_TPU_VERBOSE")
        self.profiling = _env_bool("DL4J_TPU_PROFILING")
        self.nan_panic = _env_bool("DL4J_TPU_NAN_PANIC")
        self.default_compute_dtype = os.environ.get(
            "DL4J_TPU_COMPUTE_DTYPE", "float32")
        self.default_remat_policy = (
            os.environ.get("DL4J_TPU_REMAT_POLICY") or None)
        if self.default_remat_policy == "none":
            self.default_remat_policy = None
        self.default_sync_every = _env_int("DL4J_TPU_SYNC_EVERY", 1, floor=1)
        # hot-path kernel engine defaults (ops/kernels/, docs/KERNELS.md);
        # None = the ops-level resolver's own env/auto fallback applies
        self.default_kernel_impl = (
            os.environ.get("DL4J_TPU_KERNEL_IMPL") or None)
        self.default_fused_update = _env_bool("DL4J_TPU_FUSED_UPDATE")
        # encoded gradient collectives default (parallel/compression.py);
        # validated by the conf Builder so a typo fails at config build
        self.default_grad_compression = (
            os.environ.get("DL4J_TPU_GRAD_COMPRESSION") or None)
        # pipeline parallelism default (parallel/pipelined.py): stage
        # count for new configs; 0 = off
        self.default_pipe_stages = _env_int("DL4J_TPU_PIPE_STAGES", 0,
                                            floor=0)
        # autotuning database (tuning/database.py; the authoritative read
        # is database_dir() — surfaced here so crash dumps show the knob)
        self.tuning_db_dir = os.environ.get("DL4J_TPU_TUNING_DB") or None
        self.etl_workers = _env_int("DL4J_TPU_ETL_WORKERS", 0, floor=0)
        self.default_buckets = os.environ.get("DL4J_TPU_BUCKETS") or None
        self.telemetry = _env_bool("DL4J_TPU_TELEMETRY", default=True)
        # request-trace head-sampling keep fraction (authoritative parse is
        # serving.scheduler.trace_sample_rate — memoized per raw string;
        # surfaced here so crash dumps show the knob)
        self.trace_sample = os.environ.get("DL4J_TPU_TRACE_SAMPLE") or None
        # armed-faults spec (authoritative parse lives in util/faults.py's
        # injector; surfaced here so crash dumps show the chaos config)
        self.fault_spec = os.environ.get("DL4J_TPU_FAULTS") or None
        self._profiler = None

    @property
    def peak_flops(self):
        """DL4J_TPU_PEAK_FLOPS as FLOP/s (None when unset/unparsable).
        Read live — ONE parser, in util/cost_model.py, serves this property,
        cost_report(), and the MFU gauges; a typo degrades to "no MFU", it
        never crashes training startup for an observability-only knob."""
        from deeplearning4j_tpu.util.cost_model import peak_flops_from_env

        return peak_flops_from_env()

    @classmethod
    def get_instance(cls) -> "Environment":
        if cls._instance is None:
            cls._instance = Environment()
            cls._instance._apply()
        return cls._instance

    # -- setters mirroring Nd4j.getEnvironment().setDebug/setVerbose ---------
    def set_debug(self, v: bool) -> "Environment":
        self.debug = v
        return self._apply()

    def set_verbose(self, v: bool) -> "Environment":
        self.verbose = v
        return self._apply()

    def set_profiling(self, v: bool) -> "Environment":
        self.profiling = v
        return self._apply()

    def set_nan_panic(self, v: bool) -> "Environment":
        self.nan_panic = v
        return self._apply()

    def set_telemetry(self, v: bool) -> "Environment":
        self.telemetry = v
        return self._apply()

    def _apply(self) -> "Environment":
        """Install/remove the profiler hook + logger level to match flags."""
        import logging

        from deeplearning4j_tpu.util.profiler import OpProfiler

        # only drive the logger level while a verbosity flag is ON; never
        # clobber an application's own configuration otherwise
        logger = logging.getLogger("deeplearning4j_tpu")
        if self.verbose or self.debug:
            logger.setLevel(logging.DEBUG)
            self._set_logger_level = True
        elif getattr(self, "_set_logger_level", False):
            logger.setLevel(logging.NOTSET)
            self._set_logger_level = False

        # share the OpProfiler SINGLETON so flag-driven and user-driven
        # profiling never install competing exec_op hooks; only touch its
        # config while the FLAGS own the hook — a user-started profiler's
        # settings are never clobbered by unrelated setter calls
        # unified telemetry switch: the module reads DL4J_TPU_TELEMETRY
        # itself at singleton creation; the setter keeps them in sync at
        # runtime. Only push when THIS flag changed — an unrelated setter
        # (set_debug etc.) must not clobber a direct telemetry.set_enabled()
        if self.telemetry != getattr(self, "_telemetry_applied", None):
            from deeplearning4j_tpu.util import telemetry as _telemetry

            _telemetry.set_enabled(self.telemetry)
            self._telemetry_applied = self.telemetry

        want_hook = self.profiling or self.nan_panic or self.debug
        prof = OpProfiler.get_instance()
        if want_hook:
            prof.config.profile_ops = self.profiling or self.debug
            prof.config.check_for_nan = self.nan_panic
            prof.config.check_for_inf = self.nan_panic
            prof.start()
            self._profiler = prof
        elif self._profiler is not None:
            prof.stop()
            self._profiler = None
        return self

    def profiler(self):
        return self._profiler


def get_environment() -> Environment:
    """``Nd4j.getEnvironment()`` parity."""
    return Environment.get_instance()
