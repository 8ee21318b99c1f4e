"""Profiling + correctness guards: op profiler, Chrome trace, NaN panic.

Reference parity (SURVEY.md §5.1–5.2):
- OpProfiler / ProfilerConfig      org/nd4j/linalg/profiler/{OpProfiler,ProfilerConfig}.java
  (per-op wall time + invocation counts, enabled on the executioner via
  profilingConfigurableHookIn/Out)
- ProfilingListener (Chrome trace) org/nd4j/autodiff/listeners/profiler/ProfilingListener.java
- NaN/Inf panic                    OpExecutionerUtil.checkForAny via ProfilerConfig.nanPanic
- PerformanceTracker (bandwidth)   org/nd4j/linalg/memory/PerformanceTracker-style counters

TPU-native notes: under jit there is no per-op host boundary to hook — XLA
fuses the graph — so per-op timing instruments the *eager/by-name* dispatch
path (exec_op), exactly where the reference hooks DefaultOpExecutioner, and
whole-step timing comes from the listeners. For kernel-level depth the JAX
profiler (jax.profiler.trace → TensorBoard/XPlane) is exposed via
``device_trace``; the Chrome-trace exporter writes the same
chrome://tracing JSON the reference's ProfilingListener produces.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import time
from collections import defaultdict
from typing import Any, Dict, List, Optional

import jax
import numpy as np


@dataclasses.dataclass
class ProfilerConfig:
    """ProfilerConfig.java parity."""

    profile_ops: bool = True
    check_for_nan: bool = False      # nanPanic
    check_for_inf: bool = False
    stack_trace: bool = False        # record call sites per op


class OpProfiler:
    """Singleton per-op timing/count profiler (OpProfiler.getInstance parity).

    Wraps the registry's exec_op; use ``start()``/``stop()`` or the
    ``profile()`` context manager. Times are host wall-clock including device
    sync (the honest eager number)."""

    _instance: Optional["OpProfiler"] = None

    def __init__(self, config: Optional[ProfilerConfig] = None):
        self.config = config or ProfilerConfig()
        self.reset()
        self._orig_exec = None

    @classmethod
    def get_instance(cls) -> "OpProfiler":
        if cls._instance is None:
            cls._instance = OpProfiler()
        return cls._instance

    def reset(self):
        self.invocations: Dict[str, int] = defaultdict(int)
        self.total_ns: Dict[str, int] = defaultdict(int)
        # chrome trace events; ts/dur in WALL ns (time.time_ns) so this
        # trace and the telemetry trace share one timebase and load into
        # one Perfetto view (export subtracts telemetry.trace_epoch_ns())
        self.events: List[dict] = []

    # -- hook ---------------------------------------------------------------
    def start(self):
        """Install the exec hook (profilingHookIn/Out parity)."""
        from deeplearning4j_tpu.ops import registry

        if self._orig_exec is not None:
            return self
        orig = registry.exec_op
        cfg = self.config
        prof = self

        def wrapped(name, *args, **kwargs):
            t0 = time.time_ns()
            out = orig(name, *args, **kwargs)
            out = jax.block_until_ready(out)
            t1 = time.time_ns()
            if cfg.profile_ops:
                prof.invocations[name] += 1
                prof.total_ns[name] += t1 - t0
                prof.events.append({
                    "name": name, "ph": "X", "pid": 0, "tid": 0,
                    "ts": t0, "dur": t1 - t0,  # wall ns; export converts
                })
            if cfg.check_for_nan or cfg.check_for_inf:
                _panic_check(name, out, cfg)
            return out

        registry.exec_op = wrapped
        self._orig_exec = orig
        return self

    def stop(self):
        from deeplearning4j_tpu.ops import registry

        if self._orig_exec is not None:
            registry.exec_op = self._orig_exec
            self._orig_exec = None
        return self

    @contextlib.contextmanager
    def profile(self):
        self.start()
        try:
            yield self
        finally:
            self.stop()

    # -- reporting ----------------------------------------------------------
    def summary(self) -> str:
        """printOutDashboard parity: per-op totals sorted by time."""
        rows = sorted(self.total_ns.items(), key=lambda kv: -kv[1])
        lines = [f"{'op':<32}{'calls':>8}{'total ms':>12}{'mean us':>12}"]
        for name, ns in rows:
            n = self.invocations[name]
            lines.append(
                f"{name:<32}{n:>8}{ns / 1e6:>12.3f}{ns / 1e3 / max(n, 1):>12.1f}")
        return "\n".join(lines)

    def write_chrome_trace(self, path: str):
        """ProfilingListener parity: chrome://tracing JSON. Timestamps are
        exported relative to the process-shared trace epoch
        (telemetry.trace_epoch_ns()), so this file and a
        ``Telemetry.write_chrome_trace`` file from the same run load into
        ONE Perfetto view on the same wall-clock timeline."""
        from deeplearning4j_tpu.util.telemetry import trace_epoch_ns

        t0 = trace_epoch_ns()
        if self.events:
            t0 = min(t0, min(e["ts"] for e in self.events))
        out = [dict(e, ts=(e["ts"] - t0) / 1e3, dur=e["dur"] / 1e3)
               for e in self.events]
        with open(path, "w") as f:
            json.dump({"traceEvents": out, "displayTimeUnit": "ms"}, f)


class NaNPanicError(FloatingPointError):
    pass


def _panic_check(name, out, cfg):
    leaves = jax.tree_util.tree_leaves(out)
    for leaf in leaves:
        arr = np.asarray(leaf)
        if not np.issubdtype(arr.dtype, np.floating):
            continue
        if cfg.check_for_nan and np.isnan(arr).any():
            raise NaNPanicError(f"NaN produced by op {name!r} (nanPanic)")
        if cfg.check_for_inf and np.isinf(arr).any():
            raise NaNPanicError(f"Inf produced by op {name!r} (infPanic)")


def check_numerics(tree, where: str = ""):
    """OpExecutionerUtil.checkForAny parity, usable on any pytree (params,
    grads) from user code or listeners. The error names the pytree KEY-PATH
    of every offending leaf (``jax.tree_util.tree_flatten_with_path``) with
    its shape and nan/inf counts — not just the enclosing ``where`` label —
    so a single bad layer is identifiable without a debugger."""
    bad = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        arr = np.asarray(leaf)
        if not np.issubdtype(arr.dtype, np.floating):
            continue
        finite = np.isfinite(arr)
        if finite.all():
            continue
        key = jax.tree_util.keystr(path)
        n_nan = int(np.isnan(arr).sum())
        n_inf = int(np.isinf(arr).sum())
        bad.append(f"{where}{key} shape={tuple(arr.shape)} "
                   f"nan={n_nan} inf={n_inf}")
    if bad:
        raise NaNPanicError(
            "non-finite values at " + "; ".join(bad))


@contextlib.contextmanager
def device_trace(logdir: str):
    """Kernel-level device profile via the JAX profiler (TensorBoard/XPlane
    format — the depth the reference never had)."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# XPlane readers, on JAX's own (``jax.profiler.ProfileData``): the reader
# chipbench/trace.py uses, so the program and the benchmark read a trace
# one way
# ---------------------------------------------------------------------------
def _xplane_lines(logdir: str):
    """Yield (plane name, line name, [(name, start_ns, dur_ns)]) over every
    *.xplane.pb under ``logdir``."""
    import glob as _glob

    from jax.profiler import ProfileData

    for p in _glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True):
        for plane in ProfileData.from_file(p).planes:
            for line in plane.lines:
                yield plane.name, line.name, [
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events]


def xplane_device_ms(logdir: str, plane_substr: str = "/device:",
                     by_name: bool = False):
    """Total device-busy milliseconds summed over every *.xplane.pb under
    ``logdir`` for planes whose name contains ``plane_substr`` (XLA device
    planes are '/device:TPU:0'-style; pass '/host:' for host traces): the
    plane's ``XLA Ops`` line (one event per operation) where it has one,
    else its busiest line (nested tracing appears on separate lines and
    must not be double-counted). ``by_name=True`` adds a per-event-name
    breakdown dict."""
    per_plane: Dict[str, Dict[str, list]] = defaultdict(dict)
    for plane, line, events in _xplane_lines(logdir):
        if plane_substr in plane:
            per_plane[plane][line] = events
    total_ns = 0.0
    names: Dict[str, float] = defaultdict(float)
    for lines in per_plane.values():
        events = lines.get("XLA Ops") or max(
            lines.values(), key=lambda ev: sum(e[2] for e in ev),
            default=[])
        for n, _s, d in events:
            total_ns += d
            names[n] += d
    ms = total_ns / 1e6
    if by_name:
        return ms, {k: v / 1e6 for k, v in
                    sorted(names.items(), key=lambda kv: -kv[1])}
    return ms


def xplane_mapped_ms(logdir: str, resolve) -> Dict[Any, float]:
    """Group device/host-thread event time by ``resolve(event_name) -> key``
    (None = not counted) over every plane/line under ``logdir``, returning
    {key: total ms}. Used by util/cost_model.py with the compiled module's
    instruction→(layer, direction) map, so each HLO-named profiler event
    lands on its layer row.

    Dedup: on one thread line the CPU backend nests spans (a ``call`` thunk
    wraps the fused kernel's own span); only the OUTERMOST *mapped* event of
    any overlap chain is counted, so wrapped kernels are never billed
    twice. Lines are independent, which is exactly the granularity
    needed."""
    totals: Dict[Any, float] = defaultdict(float)
    for _plane, _line, events in _xplane_lines(logdir):
        mapped = []
        for name, start, dur in events:
            key = resolve(name)
            if key is not None:
                # sort key: by start, LONGEST first on ties, so the
                # outermost event of an equal-start chain wins
                mapped.append((start, -dur, key))
        mapped.sort()
        covered_end = float("-inf")
        for start, neg_dur, key in mapped:
            if start >= covered_end:  # outermost of this overlap chain
                totals[key] += -neg_dur / 1e6
                covered_end = start - neg_dur
    return dict(totals)


class StepTimer:
    """Whole-train-step Chrome-trace recorder: use as a TrainingListener.
    Produces one 'X' event per iteration (the reference ProfilingListener's
    per-op rows collapse into one fused-step row under XLA — that is the
    point of whole-graph compilation). Wall-clock timebase, shared with the
    OpProfiler and Telemetry exporters (one Perfetto timeline)."""

    def __init__(self):
        self.events: List[dict] = []
        self._last = None

    def iteration_done(self, model, iteration, epoch):
        now = time.time_ns()
        if self._last is not None:
            self.events.append({
                "name": f"train_step[{iteration}]", "ph": "X", "pid": 0,
                "tid": 0, "ts": self._last, "dur": now - self._last,
            })
        self._last = now

    def write_chrome_trace(self, path: str):
        from deeplearning4j_tpu.util.telemetry import trace_epoch_ns

        t0 = trace_epoch_ns()
        if self.events:
            t0 = min(t0, min(e["ts"] for e in self.events))
        out = [dict(e, ts=(e["ts"] - t0) / 1e3, dur=e["dur"] / 1e3)
               for e in self.events]
        with open(path, "w") as f:
            json.dump({"traceEvents": out, "displayTimeUnit": "ms"}, f)
