"""Serving resilience layer: failure semantics for the model server
(docs/SERVING.md#resilience).

The r13 serving tier proved the performance contracts (bit-identical
batching, zero steady-state recompiles, priority lanes); this module makes
the tier survive the failures production actually sees, applying r11's
standard — every fault kind has its specific recovery asserted
(tests/test_serving_resilience.py) — to the serving path:

- **The shed-error hierarchy** — every way a request can be refused,
  each mapping to one HTTP status the server translates mechanically:
  queue-full/deadline (429), draining (503), circuit-open (503 +
  ``Retry-After`` = the breaker's remaining cooldown), brownout (429),
  dead worker (503). Defined HERE (scheduler.py re-exports them) so the
  breaker/brownout machinery never imports the scheduler.
- **:class:`CircuitBreaker`** — one per model (``BatchScheduler`` owns
  it): consecutive-error or windowed error-rate thresholds OPEN it, after
  which submits fast-fail with :class:`CircuitOpenError` instead of
  queueing work into a model that is failing every batch (doomed work
  holds queue slots, burns device time, and turns one broken model into
  whole-tier latency). After ``cooldown_s`` the breaker goes HALF-OPEN:
  a bounded number of probe requests pass; the first probe batch's
  outcome closes it (success) or re-opens it (failure). The state
  machine is the classic three-state breaker; the clock is injectable so
  tests drive transitions without sleeping.
- **:class:`BrownoutController`** — degraded service before hard
  failure: when the r17 SLO engine (util/slo.py) reports error-budget
  exhaustion, the controller sheds the ``batch`` lane across the
  router's models while ``interactive`` keeps serving — bulk work is
  the load you can shed without breaking a promise; budget recovery
  restores it. Lanes shed in declared order, never ``interactive``
  first.
- **Worker-crash semantics** — :class:`WorkerCrashedError` is what the
  supervised scheduler worker (scheduler.py watchdog) sets on the
  in-flight batch's futures when the worker loop dies: the caller gets a
  loud 500, the flight recorder gets the cause, and the worker restarts
  under ``RetryPolicy`` backoff; ``max_restarts`` exhausted flips the
  model's ``serving.worker.<id>`` health check and fails all queued
  futures with :class:`SchedulerStoppedError` instead of letting them
  hang on a dead queue forever.
- **Reload rejection** — :class:`ModelLoadError` (archive unreadable /
  corrupt: the load never partially registers) and
  :class:`ReloadRejectedError` (structure mismatch, warmup failure, or
  a failed canary — NaN-producing weights never reach traffic; the old
  version keeps serving). Raised by ``ModelRouter.load/reload``
  (router.py).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Iterable, Optional

from deeplearning4j_tpu.util import telemetry as tm

# ------------------------------------------------------------- shed errors
# (scheduler.py re-exports these names; serving/__init__.py exports them)


class ShedError(RuntimeError):
    """Request rejected by load shedding (HTTP 429 + Retry-After)."""

    http_status = 429
    retry_after_s = 1.0
    #: shed-counter/flight-recorder cause when the shed is raised from the
    #: EXECUTE path (model.execute inside a batch cycle) rather than at
    #: submit time — subclasses with an execute-time path override it
    shed_reason = "shed"


class QueueFullError(ShedError):
    """Admission control: the model's queue is at capacity."""


class DeadlineExceededError(ShedError):
    """The request's queueing deadline expired before execution started."""


class PoolExhaustedError(ShedError):
    """The model's paged KV block pool cannot hold this batch's streams
    (serving/paged.py): decode admission sheds with 429 + Retry-After
    instead of OOMing the device. Raised BEFORE any device work — the
    reserved blocks are rolled back, nothing leaks. Its flight-recorder
    cause and per-lane shed counter are first-class (``pool_exhausted``),
    the r13 shed contract with a new cause."""

    shed_reason = "pool_exhausted"


class SchedulerDrainingError(ShedError):
    """The scheduler is draining (SIGTERM) — no new work accepted."""

    http_status = 503


class SchedulerStoppedError(ShedError):
    """The scheduler's worker is permanently gone (shut down, or crashed
    past its restart budget): submit fails fast instead of enqueueing into
    a dead queue where the future would hang forever."""

    http_status = 503


class CircuitOpenError(ShedError):
    """The model's circuit breaker is open: fast-fail instead of queueing
    doomed work. ``retry_after_s`` is the breaker's remaining cooldown."""

    http_status = 503

    def __init__(self, msg: str, retry_after_s: float = 1.0):
        super().__init__(msg)
        self.retry_after_s = max(0.1, float(retry_after_s))


class BrownoutShedError(ShedError):
    """The lane is browned out (SLO error budget exhausted): bulk work is
    shed so the interactive promise survives."""


class FleetUnavailableError(ShedError):
    """The fleet's hash ring is empty — every worker is dead, unhealthy,
    or draining (serving/fleet.py). The front tier answers 503 +
    ``Retry-After``: workers respawn under :data:`FLEET_RESPAWN_POLICY`,
    so the condition is expected to clear."""

    http_status = 503
    retry_after_s = 2.0


class WorkerProxyError(RuntimeError):
    """Every failover attempt to proxy a request hit a connection-level
    failure (refused / reset / truncated response) — the fleet router's
    502. HTTP-level errors from a worker (429/503/…) are NOT this: they
    relay verbatim; only transport failures fail over and, exhausted,
    become a 502."""


class WorkerCrashedError(RuntimeError):
    """Set on the in-flight batch's futures when the scheduler worker loop
    dies — the HTTP 500 path (a crash is a server error, not a shed)."""


class ModelLoadError(RuntimeError):
    """A model archive failed to load cleanly (corrupt/truncated zip,
    structure mismatch with its own config). ``ModelRouter.load`` raises
    this WITHOUT registering anything; ``reload`` raises it with the old
    version still serving. ``__cause__`` carries the underlying error."""


class ReloadRejectedError(RuntimeError):
    """A rolling reload was rejected before the swap — canary failure,
    warmup failure, or parameter-structure mismatch. The old weights keep
    serving; nothing about the live model changed."""


# ------------------------------------------------------- fleet supervision

#: backoff for respawning a dead fleet worker process (serving/fleet.py
#: supervisor) — the scheduler-watchdog convention (WORKER_RESTART_POLICY)
#: lifted to process scope: exponential + jitter so N workers dying at
#: once (an OOM-killer sweep) do not respawn in lockstep, capped so a
#: crash-looping worker settles at one attempt every few seconds while
#: the rest of the ring keeps serving.
def _fleet_respawn_policy():
    from deeplearning4j_tpu.util.faults import RetryPolicy

    return RetryPolicy(max_attempts=8, base_delay=0.2, multiplier=2.0,
                       max_delay=5.0, jitter=0.25)


FLEET_RESPAWN_POLICY = _fleet_respawn_policy()


# --------------------------------------------------------- circuit breaker

#: breaker states, also exported as the ``serving.breaker_state`` gauge
#: (0 = closed, 1 = half_open, 2 = open)
BREAKER_STATES = ("closed", "half_open", "open")


class CircuitBreaker:
    """Per-model three-state circuit breaker (see module docstring).

    Outcomes are recorded per BATCH (the scheduler's unit of compute
    failure — one broken batch fails every rider). Trip conditions, both
    evaluated on ``record_error``:

    - ``consecutive_errors`` failed batches in a row, or
    - error fraction over the last ``window`` batches ≥ ``error_rate``
      once at least ``min_samples`` batches are in the window.

    ``allow()`` is the submit-time gate: a no-op while closed, raises
    :class:`CircuitOpenError` while open (``Retry-After`` = remaining
    cooldown), and while half-open admits up to ``half_open_probes``
    requests whose batch outcome decides the next state. ``clock`` is
    injectable (tests drive the cooldown without sleeping).
    """

    def __init__(self, *, consecutive_errors: int = 3,
                 error_rate: float = 0.5, window: int = 16,
                 min_samples: int = 8, cooldown_s: float = 5.0,
                 half_open_probes: int = 1,
                 clock: Callable[[], float] = time.monotonic,
                 model_id: str = ""):
        self.consecutive_errors = int(consecutive_errors)
        self.error_rate = float(error_rate)
        self.window = int(window)
        self.min_samples = int(min_samples)
        self.cooldown_s = float(cooldown_s)
        self.half_open_probes = int(half_open_probes)
        self.clock = clock
        self.model_id = model_id
        self._lock = threading.Lock()
        self.state = "closed"
        self._outcomes: deque = deque(maxlen=self.window)  # 1 = error
        self._consecutive = 0
        self._opened_at = 0.0
        self._probes_left = 0
        self._half_open_at = 0.0
        self.opens = 0
        self.closes = 0

    # ------------------------------------------------------------- recording
    def _trip_locked(self, now: float, why: str):
        self.state = "open"
        self._opened_at = now
        self._probes_left = 0
        self.opens += 1
        tm.counter("serving.breaker_opens_total", model=self.model_id)
        tm.instant("serving.breaker_open", model=self.model_id, reason=why)

    def record_error(self):
        now = self.clock()
        with self._lock:
            if self.state == "half_open":
                # the probe failed: the fault is still there — back to open
                # for a fresh cooldown
                self._trip_locked(now, "half_open_probe_failed")
                return
            if self.state == "open":
                return
            self._outcomes.append(1)
            self._consecutive += 1
            if self._consecutive >= self.consecutive_errors:
                self._trip_locked(
                    now, f"{self._consecutive} consecutive errors")
                return
            if len(self._outcomes) >= self.min_samples:
                frac = sum(self._outcomes) / len(self._outcomes)
                if frac >= self.error_rate:
                    self._trip_locked(
                        now, f"error rate {frac:.2f} over "
                        f"{len(self._outcomes)} batches")

    def record_success(self):
        with self._lock:
            if self.state == "half_open":
                # the probe batch succeeded: the model recovered
                self.state = "closed"
                self._outcomes.clear()
                self._consecutive = 0
                self._probes_left = 0
                self.closes += 1
                tm.counter("serving.breaker_closes_total",
                           model=self.model_id)
                tm.instant("serving.breaker_close", model=self.model_id)
                return
            self._outcomes.append(0)
            self._consecutive = 0

    # ------------------------------------------------------------ admission
    def allow(self):
        """Submit-time gate: raise :class:`CircuitOpenError` unless this
        request may enter the queue (closed, or an admitted half-open
        probe)."""
        with self._lock:
            if self.state == "closed":
                return
            now = self.clock()
            if self.state == "open":
                remaining = self.cooldown_s - (now - self._opened_at)
                if remaining > 0:
                    raise CircuitOpenError(
                        f"{self.model_id}: circuit open "
                        f"({remaining:.1f}s cooldown left)",
                        retry_after_s=remaining)
                self.state = "half_open"
                self._probes_left = self.half_open_probes
                self._half_open_at = now
                tm.instant("serving.breaker_half_open", model=self.model_id)
            # half_open: admit bounded probes; everyone else waits for the
            # probes' verdict rather than piling onto a maybe-broken model
            if self._probes_left <= 0:
                # an admitted probe can die WITHOUT a batch outcome (shed
                # at the queue, deadline-expired while queued): after one
                # cooldown with no verdict, re-arm the probes — a lost
                # probe must not wedge the breaker half-open forever
                if now - self._half_open_at >= self.cooldown_s:
                    self._probes_left = self.half_open_probes
                    self._half_open_at = now
                else:
                    raise CircuitOpenError(
                        f"{self.model_id}: circuit half-open, probe in "
                        "flight", retry_after_s=1.0)
            self._probes_left -= 1

    # --------------------------------------------------------------- queries
    def state_value(self) -> int:
        return BREAKER_STATES.index(self.state)

    def status(self) -> dict:
        with self._lock:
            recent = list(self._outcomes)
            return {
                "state": self.state,
                "consecutive_errors": self._consecutive,
                "recent_error_fraction": round(
                    sum(recent) / len(recent), 4) if recent else 0.0,
                "opens": self.opens,
                "closes": self.closes,
                "cooldown_s": self.cooldown_s,
            }


# --------------------------------------------------------------- brownout


class BrownoutController:
    """SLO-budget-exhaustion → lane brownout (see module docstring).

    ``install()`` hooks the process SLO engine's breach/recovery
    callbacks (util/slo.py). While ANY objective's budget is exhausted,
    every scheduler in ``router`` sheds ``shed_lanes`` (default: the
    ``batch`` lane — bulk work first, ``interactive`` never) with
    :class:`BrownoutShedError`; when the last exhausted objective
    recovers, the lanes reopen. Idempotent across repeated breaches of
    the same objective.
    """

    def __init__(self, router, shed_lanes: Iterable[str] = ("batch",)):
        self.router = router
        self.shed_lanes = tuple(shed_lanes)
        if "interactive" in self.shed_lanes:
            raise ValueError(
                "brownout must not shed the interactive lane — it exists "
                "to protect it (shed_lanes order: batch before interactive)")
        self._lock = threading.Lock()
        self._exhausted: set = set()
        self.active = False
        self._installed = False

    def install(self) -> "BrownoutController":
        from deeplearning4j_tpu.util import slo

        if not self._installed:
            eng = slo.get_engine()
            eng.on_breach(self._on_breach)
            eng.on_recover(self._on_recover)
            self._installed = True
        return self

    def uninstall(self) -> "BrownoutController":
        """Detach from the process SLO engine and restore the lanes. The
        engine holds strong references to the hooks (→ this controller →
        the router and its weights); a long-lived process that builds
        routers repeatedly must uninstall, or later breaches act on
        shut-down routers kept alive by the hook list."""
        from deeplearning4j_tpu.util import slo

        if self._installed:
            eng = slo.get_engine()
            eng.off_breach(self._on_breach)
            eng.off_recover(self._on_recover)
            self._installed = False
        with self._lock:
            self._exhausted.clear()
            if self.active:
                self._apply(False)
        return self

    def _apply(self, active: bool):
        self.active = active
        self.router.set_brownout(self.shed_lanes if active else ())
        tm.gauge("serving.brownout_active", 1.0 if active else 0.0)
        if active:
            tm.counter("serving.brownouts_total")
            tm.instant("serving.brownout_start",
                       lanes=",".join(self.shed_lanes))
        else:
            tm.instant("serving.brownout_end")

    def _on_breach(self, name: str, detail: str):
        with self._lock:
            first = not self._exhausted
            self._exhausted.add(name)
            if first:
                self._apply(True)

    def _on_recover(self, name: str):
        with self._lock:
            self._exhausted.discard(name)
            if self.active and not self._exhausted:
                self._apply(False)

    def status(self) -> dict:
        with self._lock:
            return {"active": self.active,
                    "shed_lanes": list(self.shed_lanes),
                    "exhausted_objectives": sorted(self._exhausted)}
