"""CompileWatcher — recompile observability for the compile-once subsystem.

XLA recompilation is the systematic cost this layer makes visible: every
ragged last batch, TBPTT remainder, eval batch size, and fresh process pays
a full trace+compile unless shape bucketing / the persistent compilation
cache / AOT warmup (docs/COMPILE_CACHE.md) absorbs it. The reference-era
analogue is cuDNN algo re-selection on shape change (``cudnnAlgoMode``);
here the unit of waste is a whole XLA program.

Two complementary signals are collected:

- **Traces, per function with per-shape attribution** — the network/session
  classes call :func:`note_trace` INSIDE their to-be-jitted step/forward
  bodies. The Python body only executes while JAX is tracing, so each call
  is exactly one retrace of that function, and the abstract shapes of the
  traced arguments say which input signature caused it. Zero overhead on
  the compiled hot path (the call does not exist in the jitted program).
- **Backend compiles + persistent-cache hits, process-global** — via
  ``jax.monitoring`` events (``/jax/core/compile/backend_compile_duration``,
  ``/jax/compilation_cache/cache_hits``). These count every XLA compile in
  the process including sub-jits, and how many were served from the on-disk
  cache (util/compile_cache.py).

Surfaced through ``RecompileListener`` (nn/listeners.py), the StatsListener
``compile`` record group (util/stats.py) and the chip benchmark's
``compiles_in_window`` and ``setup_cache_hit_share`` (PERF.md section 3).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Tuple

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_TRACE_DUR = "/jax/core/compile/jaxpr_trace_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

_listeners_installed = False
_install_lock = threading.Lock()


def _install_monitoring_listeners():
    """Register the jax.monitoring hooks ONCE per process (jax.monitoring has
    no per-listener removal) and forward into the live singleton, so
    reset()/replacement keeps working."""
    global _listeners_installed
    with _install_lock:
        if _listeners_installed:
            return
        import jax.monitoring as monitoring

        def on_event(event, **kw):
            w = CompileWatcher._instance
            if w is not None and event == _CACHE_HIT:
                w.persistent_cache_hits += 1

        def on_duration(event, duration, **kw):
            w = CompileWatcher._instance
            if w is None:
                return
            if event == _BACKEND_COMPILE:
                w.backend_compiles += 1
                w.backend_compile_seconds += duration
            elif event == _TRACE_DUR:
                w.jaxpr_trace_seconds += duration

        monitoring.register_event_listener(on_event)
        monitoring.register_event_duration_secs_listener(on_duration)
        _listeners_installed = True


def _shape_of(x) -> Any:
    """Abstract signature of one traced argument (works on tracers, arrays,
    None, and nested lists/dicts — kept shallow and cheap)."""
    if x is None:
        return None
    if isinstance(x, (list, tuple)):
        return tuple(_shape_of(v) for v in x)
    if isinstance(x, dict):
        return tuple(sorted((k, _shape_of(v)) for k, v in x.items()))
    shape = getattr(x, "shape", None)
    if shape is None:
        return type(x).__name__
    return (tuple(shape), str(getattr(x, "dtype", "?")))


class CompileWatcher:
    """Counts traces/compiles per function with per-shape attribution.

    Use the process singleton (:meth:`get_instance` / module-level
    :func:`get_watcher`); instruments call :func:`note_trace` at trace time.
    ``scope()`` gives delta-counting for tests and harnesses."""

    _instance: Optional["CompileWatcher"] = None

    def __init__(self):
        self.traces: Dict[str, int] = {}
        self.shapes: Dict[str, Dict[Any, int]] = {}
        self.events: List[Tuple[float, str, Any]] = []  # (wall_s, fn, sig)
        self.backend_compiles = 0
        self.backend_compile_seconds = 0.0
        self.jaxpr_trace_seconds = 0.0
        self.persistent_cache_hits = 0
        self._lock = threading.Lock()
        # per-thread trace tally: note_trace runs ON the thread that
        # triggered the trace (jit tracing is synchronous), so this lets a
        # serving worker count only the traces ITS batches caused — a
        # rolling reload's shadow warmup compiling on another thread must
        # not show up as steady-state serving recompiles (serving/model.py)
        self._tls = threading.local()

    @classmethod
    def get_instance(cls) -> "CompileWatcher":
        if cls._instance is None:
            cls._instance = cls()
        _install_monitoring_listeners()
        return cls._instance

    # ------------------------------------------------------------- recording
    def note_trace(self, fn_name: str, *traced_args) -> None:
        sig = tuple(_shape_of(a) for a in traced_args)
        self._tls.traces = getattr(self._tls, "traces", 0) + 1
        with self._lock:
            self.traces[fn_name] = self.traces.get(fn_name, 0) + 1
            per = self.shapes.setdefault(fn_name, {})
            per[sig] = per.get(sig, 0) + 1
            self.events.append((time.time(), fn_name, sig))

    # --------------------------------------------------------------- queries
    def total_traces(self) -> int:
        return sum(self.traces.values())

    def thread_traces(self) -> int:
        """Traces noted on the CALLING thread since it first traced (0 for
        a thread that never did). Delta this around a region to count only
        the traces that region itself caused — immune to concurrent
        compilation on other threads (a reload's shadow warmup, another
        model's cold start)."""
        return getattr(self._tls, "traces", 0)

    def counts(self) -> Dict[str, Any]:
        """One JSON-able snapshot of every counter. ``uncached_compiles``
        subtracts persistent-cache hits from the backend-compile event count:
        jax emits ``backend_compile_duration`` even when the executable is
        deserialized from the on-disk cache, so the raw count alone does not
        drop on a warm process — the difference is what actually recompiled."""
        return {
            "traces": dict(self.traces),
            "total_traces": self.total_traces(),
            "backend_compiles": self.backend_compiles,
            "uncached_compiles": max(
                0, self.backend_compiles - self.persistent_cache_hits),
            "backend_compile_seconds": round(self.backend_compile_seconds, 4),
            "jaxpr_trace_seconds": round(self.jaxpr_trace_seconds, 4),
            "persistent_cache_hits": self.persistent_cache_hits,
        }

    def summary(self) -> str:
        lines = [
            f"CompileWatcher: {self.total_traces()} traces, "
            f"{self.backend_compiles} backend compiles "
            f"({self.backend_compile_seconds:.2f}s), "
            f"{self.persistent_cache_hits} persistent-cache hits"
        ]
        for fn in sorted(self.traces):
            lines.append(f"  {fn}: {self.traces[fn]} trace(s)")
            for sig, n in self.shapes.get(fn, {}).items():
                lines.append(f"    x{n}  {sig}")
        return "\n".join(lines)

    def reset(self) -> None:
        with self._lock:
            self.traces.clear()
            self.shapes.clear()
            self.events.clear()
            self.backend_compiles = 0
            self.backend_compile_seconds = 0.0
            self.jaxpr_trace_seconds = 0.0
            self.persistent_cache_hits = 0

    def scope(self) -> "CompileScope":
        """Delta counter: ``with watcher.scope() as s: ...; s.traces``."""
        return CompileScope(self)


class CompileScope:
    """Counts traces/compiles between ``__enter__`` and the read point —
    the regression-test primitive (``assert scope.traces == N``)."""

    def __init__(self, watcher: CompileWatcher):
        self.watcher = watcher
        self._t0: Dict[str, int] = {}
        self._c0 = 0
        self._h0 = 0

    def __enter__(self) -> "CompileScope":
        self._t0 = dict(self.watcher.traces)
        self._c0 = self.watcher.backend_compiles
        self._h0 = self.watcher.persistent_cache_hits
        return self

    def __exit__(self, *exc):
        return False

    @property
    def traces(self) -> int:
        return sum(
            n - self._t0.get(fn, 0) for fn, n in self.watcher.traces.items()
        )

    def traces_of(self, fn_name: str) -> int:
        return self.watcher.traces.get(fn_name, 0) - self._t0.get(fn_name, 0)

    @property
    def backend_compiles(self) -> int:
        return self.watcher.backend_compiles - self._c0

    @property
    def persistent_cache_hits(self) -> int:
        return self.watcher.persistent_cache_hits - self._h0


def get_watcher() -> CompileWatcher:
    """The process CompileWatcher (installs monitoring hooks on first use)."""
    return CompileWatcher.get_instance()


def note_trace(fn_name: str, *traced_args) -> None:
    """Record one retrace of ``fn_name`` — call INSIDE the function handed to
    ``jax.jit``; executes only while tracing, never in the compiled program."""
    CompileWatcher.get_instance().note_trace(fn_name, *traced_args)
