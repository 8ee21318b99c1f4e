"""Elastic fault-tolerant training runtime (docs/FAULT_TOLERANCE.md).

ROADMAP item 3's missing composition: the pieces existed — the r7 DCN
bootstrap control plane (parallel/distributed.py), sharded checkpoints
(util/checkpoint.py), the training health monitor (util/health.py) — but a
killed ETL worker, a preempted host, or a NaN step still ended the run.
This module is the supervisor that makes worker loss survivable, the
TPU-native shape of the reference's SharedTrainingMaster deployment story
(workers fall over and rejoin; the Spark driver reschedules partitions):

- :class:`FileMembership` — heartbeat-based membership over a shared
  directory (the natural DCN-adjacent medium: every TPU pod host mounts
  shared storage; on one host it is simply a tmpdir, which is how the
  2-process SIGKILL test drives it). Members heartbeat on a thread;
  the lowest-id live member coordinates; **epoch-boundary regroup**
  shrinks the world when a member misses N heartbeats (and re-admits a
  restarted one at the next boundary), with coordinator failover when
  the coordinator itself dies. The data pipeline re-shards
  deterministically on regroup: batch ``i`` belongs to
  ``i % world == rank`` under the NEW view.
- :class:`ElasticTrainer` — the supervised loop around ``fit()``:
  checkpoint-auto-resume (periodic atomic checkpoints carrying RNG key +
  iterator cursor; on start, restore the newest GOOD checkpoint and
  fast-forward the iterator — proven bit-identical to an uninterrupted
  run), SIGTERM/preemption graceful drain (finish the in-flight step,
  checkpoint, leave the membership, return cleanly), and a ``rollback``
  recovery for health anomalies (util/health.py RollbackSignal): restore
  the last good checkpoint and re-enter the loop instead of raising.
- Fault-injection seams (util/faults.py) are consulted on the real code
  paths — NaN poisoning of a real batch, SIGKILL of the real process —
  so tests/test_elastic.py proves each recovery actually fires.

CPU-backend honesty (same stance as the r7 DCN dryrun): with world > 1 each
process steps its own replica — this jaxlib's CPU backend rejects
cross-process collectives, so membership/checkpoint/regroup (the control
plane this module adds) is what the multi-process tests prove; on real
ICI/DCN hardware the data plane is the GSPMD all-reduce underneath
ParallelWrapper, bootstrapped by ``distributed.initialize``.

    trainer = ElasticTrainer(net, "/ckpts/run1", checkpoint_every=200)
    trainer.fit(iterator, epochs=10)       # resumes automatically
"""

from __future__ import annotations

import json
import os
import signal
import threading
import time
import weakref
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from deeplearning4j_tpu.util import faults as fl
from deeplearning4j_tpu.util import telemetry as tm
from deeplearning4j_tpu.util.checkpoint import ShardedCheckpointer
from deeplearning4j_tpu.util.faults import RetryPolicy
from deeplearning4j_tpu.util.health import RollbackSignal, TrainingHealthMonitor


class MembershipError(RuntimeError):
    """Membership protocol failure: barrier deadline exhausted, or this
    member was evicted from the published view (presumed dead while alive —
    rejoin at the next epoch boundary with a fresh trainer)."""


@dataclass(frozen=True)
class MembershipView:
    """One agreed epoch-scoped membership: sorted member ids, this member's
    rank within them. ``world`` is the new world size the data pipeline
    re-shards to (batch i belongs to ``i % world == rank``)."""

    epoch: int
    members: tuple
    rank: int

    @property
    def world(self) -> int:
        return len(self.members)

    def owns_batch(self, index: int) -> bool:
        return index % self.world == self.rank


def _atomic_write(path: str, payload: dict):
    tmp = f"{path}.tmp-{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)


class FileMembership:
    """Heartbeat membership over a shared directory.

    Each member atomically rewrites ``hb-<id>.json`` (id, seq, wall ts)
    every ``heartbeat_interval`` seconds from a daemon thread; a member
    whose newest heartbeat is older than ``miss_threshold x interval`` (or
    who posted a ``left-<id>`` marker — graceful leave) is dead. The
    ``drop_heartbeat`` fault (util/faults.py) makes the thread skip beats,
    which is exactly what a wedged host looks like from outside.

    :meth:`regroup` is the epoch-boundary join/leave barrier: every member
    posts ``ready-<epoch>-<id>``; the lowest-id LIVE member coordinates,
    waiting (bounded by ``barrier_timeout``) until every live member is
    ready — a member that dies while awaited is dropped — then publishes
    ``view-<epoch>.json``; everyone adopts it. If the coordinator dies
    mid-barrier the next-lowest live member notices (stale heartbeat) and
    takes over, so a SIGKILLed coordinator cannot hang the survivors.
    """

    def __init__(self, directory: str, process_id: int, world_size: int = 1,
                 heartbeat_interval: float = 0.5, miss_threshold: int = 4,
                 barrier_timeout: float = 120.0,
                 join_grace: Optional[float] = None,
                 injector=None, log_fn=print):
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self.process_id = int(process_id)
        #: members expected at the INITIAL join barrier; the coordinator
        #: holds the first view open for them up to ``join_grace`` seconds
        #: (default: half the barrier timeout), so a slow-booting member is
        #: not evicted before its first heartbeat lands
        self.world_size = int(world_size)
        self.heartbeat_interval = heartbeat_interval
        self.miss_threshold = miss_threshold
        self.barrier_timeout = barrier_timeout
        self.join_grace = (join_grace if join_grace is not None
                           else barrier_timeout / 2)
        #: fault source for the beat thread (tests hand one member a private
        #: injector so drop_heartbeat targets exactly that member)
        self.injector = injector if injector is not None else fl.get_injector()
        self.log = log_fn
        self.view: Optional[MembershipView] = None
        self.regroups = 0
        self._seq = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._skip_beats = 0

    # ------------------------------------------------------------ heartbeats
    def _hb_path(self, member: int) -> str:
        return os.path.join(self.directory, f"hb-{member}.json")

    def _beat(self):
        self._seq += 1
        _atomic_write(self._hb_path(self.process_id),
                      {"id": self.process_id, "seq": self._seq,
                       "ts": time.time()})
        tm.counter("elastic.heartbeats_total")

    def _beat_loop(self):
        while not self._stop.wait(self.heartbeat_interval):
            fault = self.injector.fire(fl.DROP_HEARTBEAT)
            if fault is not None:
                # a dropped-heartbeat window long enough to be declared dead
                self._skip_beats = int(fault.arg or (self.miss_threshold + 2))
            if self._skip_beats > 0:
                self._skip_beats -= 1
                tm.counter("elastic.heartbeats_dropped_total")
                continue
            self._beat()

    def start(self) -> "FileMembership":
        left = os.path.join(self.directory, f"left-{self.process_id}")
        if os.path.exists(left):  # rejoin after a previous graceful leave
            os.unlink(left)
        self._beat()
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._beat_loop, name="dl4j-tpu-heartbeat", daemon=True)
            self._thread.start()
        return self

    def stop(self, graceful: bool = True):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        if graceful:
            _atomic_write(os.path.join(
                self.directory, f"left-{self.process_id}"),
                {"id": self.process_id, "ts": time.time()})

    # -------------------------------------------------------------- liveness
    def alive(self) -> List[int]:
        """Member ids with a fresh heartbeat and no leave marker. Always
        includes self (a process that is asking is alive by definition).

        Freshness compares heartbeat-file MTIMES against each other — all
        stamps come from the one filesystem clock the members share — with
        this member's own latest beat as the "now" reference, so cross-host
        wall-clock skew cannot declare a live member dead. One interval of
        slack covers the reference's own age."""
        fresh_s = (self.miss_threshold + 1) * self.heartbeat_interval
        stamps = {}
        for name in os.listdir(self.directory):
            if not name.startswith("hb-") or ".tmp-" in name:
                continue
            try:
                member = int(name[len("hb-"):].split(".")[0])
                stamps[member] = os.stat(
                    os.path.join(self.directory, name)).st_mtime
            except (OSError, ValueError):
                continue  # mid-replace race: treat as missing this scan
        ref = stamps.get(self.process_id, max(stamps.values(), default=0.0))
        out = {self.process_id}
        for member, ts in stamps.items():
            if os.path.exists(os.path.join(self.directory, f"left-{member}")):
                continue
            if ref - ts <= fresh_s:
                out.add(member)
        return sorted(out)

    # --------------------------------------------------------------- regroup
    def _view_path(self, epoch: int) -> str:
        return os.path.join(self.directory, f"view-{epoch}.json")

    def _ready_ids(self, epoch: int) -> List[int]:
        prefix = f"ready-{epoch}-"
        out = []
        for n in os.listdir(self.directory):
            if not n.startswith(prefix):
                continue
            try:
                out.append(int(n[len(prefix):]))
            except ValueError:
                continue  # a peer's in-flight ".tmp-<pid>" atomic write
        return sorted(out)

    def regroup(self, epoch: int,
                timeout: Optional[float] = None) -> MembershipView:
        """Epoch-boundary barrier + view agreement (see class docstring)."""
        _atomic_write(os.path.join(
            self.directory, f"ready-{epoch}-{self.process_id}"),
            {"id": self.process_id, "ts": time.time()})
        t0 = time.monotonic()
        deadline = t0 + (timeout or self.barrier_timeout)
        with tm.span("elastic.regroup", epoch=epoch):
            while True:
                view = self._try_adopt(epoch)
                if view is None and min(self.alive()) == self.process_id:
                    view = self._coordinate(epoch, time.monotonic() - t0)
                if view is not None:
                    return self._install(view)
                if time.monotonic() > deadline:
                    raise MembershipError(
                        f"member {self.process_id}: no view for epoch "
                        f"{epoch} within {timeout or self.barrier_timeout}s "
                        f"(alive={self.alive()}, "
                        f"ready={self._ready_ids(epoch)})")
                time.sleep(self.heartbeat_interval / 4)

    def _try_adopt(self, epoch: int) -> Optional[MembershipView]:
        path = self._view_path(epoch)
        if not os.path.exists(path):
            return None
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            return None  # mid-replace read; next poll sees it whole
        members = tuple(sorted(int(m) for m in data["members"]))
        if self.process_id not in members:
            raise MembershipError(
                f"member {self.process_id} evicted from epoch-{epoch} view "
                f"{members} (presumed dead); rejoin at the next boundary")
        return MembershipView(epoch=epoch, members=members,
                              rank=members.index(self.process_id))

    def _coordinate(self, epoch: int,
                    elapsed: float = 0.0) -> Optional[MembershipView]:
        """Coordinator body for one poll: publish the view once every LIVE
        member is ready (the dead are dropped by their stale heartbeats).
        Returns None while still waiting on a live, not-yet-ready member."""
        alive = set(self.alive())
        ready = set(self._ready_ids(epoch))
        if not (alive <= ready):
            return None  # someone live has not reached the barrier yet
        if (self.view is None and len(alive) < self.world_size
                and elapsed < self.join_grace):
            # initial join barrier: expected members may not have booted
            # far enough to write a first heartbeat — hold the view open
            return None
        members = tuple(sorted(alive))
        # exclusive-create publish: if two members momentarily both believe
        # they are the lowest live id (liveness scans race), the SECOND
        # publish fails and that coordinator adopts the existing view
        # instead — one view per epoch can ever exist, so a split brain
        # degrades to (at worst) a loud eviction, never two conflicting
        # views silently training overlapping shards
        payload = {"epoch": epoch, "members": list(members),
                   "coordinator": self.process_id, "ts": time.time()}
        path = self._view_path(epoch)
        tmp = f"{path}.tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        try:
            try:
                os.link(tmp, path)  # atomic full-content fail-if-exists
            except FileExistsError:
                return self._try_adopt(epoch)  # lost the race: adopt theirs
            except OSError:
                # no hard links on this mount (object-store FUSE): portable
                # exclusive create — readers tolerate a partial JSON by
                # re-polling, so non-atomic content is benign
                try:
                    fd = os.open(path, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
                except FileExistsError:
                    return self._try_adopt(epoch)
                with os.fdopen(fd, "w") as f:
                    json.dump(payload, f)
        finally:
            try:
                os.unlink(tmp)
            except OSError:
                pass
        return MembershipView(epoch=epoch, members=members,
                              rank=members.index(self.process_id))

    def _install(self, view: MembershipView) -> MembershipView:
        prev = self.view
        if prev is not None and prev.members != view.members:
            self.regroups += 1
            tm.counter("elastic.regroups_total")
            tm.instant("elastic.regroup_event", epoch=view.epoch,
                       world=view.world, members=str(list(view.members)))
            if self.log:
                self.log(f"ELASTIC regroup at epoch {view.epoch}: "
                         f"{list(prev.members)} -> {list(view.members)} "
                         f"(rank {view.rank}/{view.world})")
        self.view = view
        # world/rank Prometheus series come ONLY from the scrape-time
        # collector (collect_elastic_gauges) — pushing stored gauges here
        # too would emit a second, label-less series for the same fact
        tm.set_health("elastic.membership", True,
                      f"epoch {view.epoch}: rank {view.rank}/{view.world}")
        # sweep only READY litter from two epochs back; published VIEW
        # files are kept for the run's lifetime (a few bytes per epoch):
        # a member rolling back 2+ epochs after an anomaly re-adopts the
        # historical view instantly instead of deadlocking at a barrier
        # no peer will ever re-post ready markers for
        for name in os.listdir(self.directory):
            if name.startswith("ready-"):
                try:
                    old = int(name[len("ready-"):].split("-")[0])
                except ValueError:
                    continue
                if old <= view.epoch - 2:
                    try:
                        os.unlink(os.path.join(self.directory, name))
                    except OSError:
                        pass
        return view

    def status(self) -> dict:
        v = self.view
        return {
            "process_id": self.process_id,
            "alive": self.alive(),
            "world": v.world if v else None,
            "rank": v.rank if v else None,
            "members": list(v.members) if v else None,
            "epoch": v.epoch if v else None,
            "regroups": self.regroups,
            "heartbeat_interval_s": self.heartbeat_interval,
            "miss_threshold": self.miss_threshold,
        }


# --------------------------------------------------------------- publisher
class _ArchivePublisher:
    """Single background writer for the train→serve publish seam
    (docs/SERVING.md#resilience): the training thread drops a same-step
    host-array ``ModelSerializer.snapshot`` and returns to stepping; this
    thread pays the DEFLATE + atomic replace. ONE pending slot, latest
    wins — a disk slower than the checkpoint cadence collapses
    intermediate publishes instead of queueing behind them (the watcher
    only ever wants the newest weights anyway)."""

    def __init__(self, path: str, log_fn=None):
        self.path = path
        self.log = log_fn
        self._cv = threading.Condition()
        self._pending = None  # (snapshot, step) | None
        self._busy = False
        self._stop = False
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="elastic-publish")
        self._thread.start()

    def publish(self, snap: dict, step: int):
        with self._cv:
            self._pending = (snap, step)
            self._cv.notify_all()

    def _loop(self):
        from deeplearning4j_tpu.util.model_serializer import ModelSerializer

        while True:
            with self._cv:
                while self._pending is None and not self._stop:
                    self._cv.wait(timeout=0.2)
                if self._pending is None:
                    return  # stopped with nothing left to write
                (snap, step), self._pending = self._pending, None
                self._busy = True
            try:
                with tm.span("elastic.publish", step=step):
                    ModelSerializer.write_snapshot(snap, self.path)
                tm.counter("elastic.publishes_total")
                tm.gauge("elastic.last_publish_step", step)
            except Exception as e:  # noqa: BLE001 — serving seam
                tm.counter("elastic.publish_errors_total")
                if self.log:
                    self.log(f"ELASTIC publish to {self.path} failed at "
                             f"step {step}: {e!r}")
            finally:
                with self._cv:
                    self._busy = False
                    self._cv.notify_all()

    def flush(self, timeout: float = 60.0) -> bool:
        """Block until everything handed to :meth:`publish` is on disk —
        fit() calls this before returning so the FINAL weights' archive is
        durable when training ends."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while (self._pending is not None or self._busy) \
                    and time.monotonic() < deadline:
                self._cv.wait(timeout=0.1)
            return self._pending is None and not self._busy

    def stop(self, timeout: float = 60.0):
        """Flush, then end the writer thread. Each fit() tears its
        publisher down (and lazily recreates on the next publish) so a
        process that builds trainers repeatedly does not accumulate idle
        publisher threads. A flush that times out is LOUD — the
        "final weights durable when fit() returns" contract just broke,
        and the watcher would otherwise serve stale weights with zero
        signal."""
        if not self.flush(timeout=timeout):
            tm.counter("elastic.publish_flush_timeouts_total")
            if self.log:
                self.log(f"ELASTIC publish flush timed out after "
                         f"{timeout}s — the final archive at {self.path} "
                         "may be stale")
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        self._thread.join(timeout=timeout)


# ----------------------------------------------------------------- trainer
_ACTIVE: "weakref.WeakValueDictionary[int, ElasticTrainer]" = \
    weakref.WeakValueDictionary()
_ACTIVE_SEQ = 0


def current_status() -> Dict[str, dict]:
    """Live elastic-runtime status for /healthz's membership section
    (util/ui_server.py) and the telemetry default collector."""
    return {f"trainer-{k}": t.status() for k, t in sorted(_ACTIVE.items())}


class ElasticTrainer:
    """Supervised elastic training loop (module docstring has the story).

    ``model``: a MultiLayerNetwork / ComputationGraph, or a ParallelWrapper
    (the wrapper's sharded step is supervised; its inner model is what gets
    checkpointed). ``membership=None`` runs single-member (world 1) with
    every other protection — auto-resume, drain, rollback — still active.

    Knobs: ``checkpoint_every`` steps between periodic checkpoints
    (asynchronous by default: the commit I/O overlaps the next steps;
    ``async_checkpoint=False`` forces blocking saves); ``monitor`` a
    TrainingHealthMonitor to install (default: one with ``action="rollback"``
    when ``rollback_on_anomaly``); ``max_rollbacks`` bounds restore loops so
    a deterministically-NaN model still fails loudly; ``drain_signals`` are
    trapped for graceful drain (finish step -> checkpoint -> leave), the
    SIGTERM every preemption notice delivers.
    """

    def __init__(self, model, directory: str, checkpoint_every: int = 200,
                 keep: int = 3, membership: Optional[FileMembership] = None,
                 monitor=None, rollback_on_anomaly: bool = True,
                 max_rollbacks: int = 3, async_checkpoint: bool = True,
                 initial_checkpoint: bool = True,
                 retry: Optional[RetryPolicy] = None,
                 publish_archive: Optional[str] = None,
                 drain_signals=(signal.SIGTERM,), log_fn=print):
        global _ACTIVE_SEQ
        from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

        self.wrapper = model if isinstance(model, ParallelWrapper) else None
        self.net = model.model if self.wrapper is not None else model
        # retry=None means "checkpointer default" (_IO_RETRY), not "no
        # retry" — passing None through would silently disable the retried
        # checkpoint I/O this runtime's whole contract depends on
        if retry is None:
            self.ckpt = ShardedCheckpointer(directory, keep=keep,
                                            log_fn=log_fn)
        else:
            self.ckpt = ShardedCheckpointer(directory, keep=keep,
                                            retry=retry, log_fn=log_fn)
        self.checkpoint_every = checkpoint_every
        self.membership = membership
        self.rollback_on_anomaly = rollback_on_anomaly
        self.max_rollbacks = max_rollbacks
        self.async_checkpoint = async_checkpoint
        #: blocking save at fit() start guaranteeing a rollback target
        #: before the first anomaly can hit; False skips it (a startup-cost
        #: escape hatch when rollback protection is not wanted)
        self.initial_checkpoint = initial_checkpoint
        #: train→serve seam (docs/SERVING.md#resilience): every checkpoint
        #: cadence ALSO publishes a ModelSerializer archive here (atomic
        #: tmp+os.replace — a watching ModelRouter.watch() poller reloads
        #: it under live traffic, never reading a torn file). The training
        #: thread captures a same-step HOST snapshot right at the
        #: checkpoint point (the device→host copy is mandatory — the next
        #: step donates the param buffers, the checkpointer's
        #: _host_snapshot rule); a background publisher thread pays the
        #: DEFLATE + write, so the step loop never stalls on compression
        #: (latest-wins: a slow disk collapses intermediate publishes
        #: instead of queueing behind them).
        self.publish_archive = publish_archive
        self._publisher: Optional[_ArchivePublisher] = None
        if self.publish_archive is not None:
            # commit correlation for the serving watcher's trace: one
            # instant per durable checkpoint commit (async commits fire
            # this from the background committer)
            self.ckpt.add_commit_hook(
                lambda step: tm.instant("elastic.commit", step=step,
                                        publish=str(self.publish_archive)))
        self.drain_signals = tuple(drain_signals)
        self.log = log_fn
        if monitor is None and rollback_on_anomaly:
            monitor = TrainingHealthMonitor(action="rollback", log_fn=log_fn)
        self.monitor = monitor

        self.state = "idle"
        self.rollbacks = 0
        self.resumed_from: Optional[int] = None
        self.drained = False
        self._drain_requested = False
        self._batch_in_epoch = 0
        self._steps_since_ckpt = 0
        self._view: Optional[MembershipView] = None
        self._is_graph = hasattr(self.net, "topo")
        _ACTIVE_SEQ += 1
        _ACTIVE[_ACTIVE_SEQ] = self

    # ------------------------------------------------------------- stepping
    def _step(self, ds):
        if self.wrapper is not None:
            self.wrapper.step_batch(ds)
        elif self._is_graph:
            from deeplearning4j_tpu.nn.computation_graph import _mask_dict

            feats = (list(ds.features)
                     if isinstance(ds.features, (list, tuple))
                     else [ds.features])
            labs = (list(ds.labels) if isinstance(ds.labels, (list, tuple))
                    else [ds.labels])
            self.net._fit_batch(
                feats, labs,
                mask=_mask_dict(ds, self.net.conf.inputs,
                                "features_mask", "features_masks"),
                label_mask=_mask_dict(ds, self.net.conf.outputs,
                                      "labels_mask", "labels_masks"))
        else:
            self.net._fit_batch(
                ds.features, ds.labels,
                mask=getattr(ds, "features_mask", None),
                label_mask=getattr(ds, "labels_mask", None))

    def _end_epoch(self):
        if self.wrapper is not None:
            self.wrapper.end_epoch()
        else:
            self.net._end_epoch()

    @staticmethod
    def _poison(ds):
        """inject_nan: a REAL poisoned batch — the NaN flows through the
        actual forward/backward so the detection and rollback exercised are
        the production ones, not a simulation of them."""
        import copy

        bad = copy.copy(ds)
        feats = ds.features
        if isinstance(feats, (list, tuple)):
            bad.features = [np.full(np.shape(f), np.nan, np.float32)
                            for f in feats]
        else:
            bad.features = np.full(np.shape(feats), np.nan, np.float32)
        return bad

    # ---------------------------------------------------------- checkpoints
    def _checkpoint(self, block: bool = False):
        # under sync_every>1 per-step losses are queued: flush so the
        # monitor evaluates (and can veto, via RollbackSignal) every step
        # up to this point BEFORE it is committed as a "good" checkpoint
        disp = getattr(self.net, "_dispatcher", None)
        if disp is not None:
            disp.flush()
        # pipelined trainers keep the live state stage-stacked on device
        # (parallel/pipelined.py); pull it back into the net's model layout
        # (bit-exact unstack) so the checkpoint — and the publish snapshot
        # right after — carry the CURRENT weights
        sync = getattr(self.wrapper, "sync_model", None)
        if sync is not None:
            sync()
        meta = {
            "batch_in_epoch": self._batch_in_epoch,
            "epoch": self.net.epoch,
            "world": self._view.world if self._view else 1,
            "rank": self._view.rank if self._view else 0,
        }
        self.ckpt.save(self.net.iteration, self.net, extra_meta=meta,
                       block=block or not self.async_checkpoint)
        if self.publish_archive is not None:
            self._publish()
        self._steps_since_ckpt = 0

    def _publish(self):
        """Hand this checkpoint's weights to the background publisher: the
        HOST snapshot is captured HERE on the training thread so archive
        and checkpoint carry the same step (and so no device ref outlives
        the next step's donation); the DEFLATE + atomic write happen on
        the publisher thread. A publish failure is loud but must not kill
        training — the checkpoint itself already committed; the watcher
        simply keeps serving the previous version."""
        from deeplearning4j_tpu.util.model_serializer import ModelSerializer

        try:
            snap = ModelSerializer.snapshot(self.net)
        except Exception as e:  # noqa: BLE001 — serving seam, not training
            tm.counter("elastic.publish_errors_total")
            if self.log:
                self.log(f"ELASTIC publish snapshot failed at step "
                         f"{self.net.iteration}: {e!r}")
            return
        if self._publisher is None:
            self._publisher = _ArchivePublisher(self.publish_archive,
                                                log_fn=self.log)
        self._publisher.publish(snap, self.net.iteration)

    def _resume(self) -> Optional[int]:
        step = self.ckpt.restore_latest_good(self.net)
        if step is None:
            return None
        meta = self.ckpt.load_meta(step)
        self._batch_in_epoch = int(meta.get("batch_in_epoch", 0))
        self.resumed_from = step
        tm.counter("elastic.resumes_total")
        tm.instant("elastic.resume", step=step, epoch=self.net.epoch,
                   batch_in_epoch=self._batch_in_epoch)
        if self.log:
            self.log(f"ELASTIC resume from checkpoint step {step} "
                     f"(epoch {self.net.epoch}, "
                     f"batch {self._batch_in_epoch})")
        return step

    def _rollback(self, sig: RollbackSignal):
        if self.rollbacks >= self.max_rollbacks:
            raise RuntimeError(
                f"elastic rollback budget exhausted "
                f"({self.max_rollbacks}); last anomaly: {sig}") from sig
        self.ckpt.wait_until_finished()
        step = self.ckpt.restore_latest_good(self.net)
        if step is None:
            raise RuntimeError(
                "health anomaly with no checkpoint to roll back to"
            ) from sig
        self.rollbacks += 1
        meta = self.ckpt.load_meta(step)
        self._batch_in_epoch = int(meta.get("batch_in_epoch", 0))
        self._steps_since_ckpt = 0
        if self.monitor is not None:
            self.monitor.reset()  # bands described the poisoned run
        tm.counter("elastic.rollbacks_total")
        tm.instant("elastic.rollback", step=step, kind=sig.kind)
        tm.set_health("elastic.rollback", True,
                      f"rolled back to step {step} after {sig.kind}")
        if self.log:
            self.log(f"ELASTIC rollback to checkpoint step {step} after "
                     f"{sig.kind} ({sig.detail}); "
                     f"{self.max_rollbacks - self.rollbacks} budget left")

    # ---------------------------------------------------------------- drain
    def _on_drain_signal(self, signum, frame):
        self._drain_requested = True
        tm.counter("elastic.drain_signals_total")
        if self.log:
            self.log(f"ELASTIC drain requested (signal {signum}): finishing "
                     "the in-flight step, checkpointing, leaving")

    # ------------------------------------------------------------------ fit
    def fit(self, iterator, epochs: int = 1):
        """Supervised fit: resume -> (regroup -> shard -> step/checkpoint)*
        -> final checkpoint. Returns the model. ``self.drained`` tells a
        CLI wrapper to exit 0 (preemption honored, work saved).

        NOTE: unlike ``MultiLayerNetwork.fit`` (which runs ``epochs`` MORE
        epochs), ``epochs`` here is the ABSOLUTE target epoch count — the
        loop runs until ``model.epoch == epochs``. That is what makes
        resume idempotent: however many times the process is killed and
        restarted with the same call, the total work is the same. A model
        already at the target trains zero steps."""
        injector = fl.get_injector()
        net = self.net
        self.state = "running"
        self._drain_requested = False
        self.drained = False

        installed_monitor = False
        if self.monitor is not None and self.monitor not in net.listeners:
            net.listeners.append(self.monitor)
            installed_monitor = True
        old_handlers = {}
        if threading.current_thread() is threading.main_thread():
            for sig in self.drain_signals:
                old_handlers[sig] = signal.signal(sig, self._on_drain_signal)
        if self.membership is not None:
            self.membership.start()
        try:
            resumed = self._resume()
            if resumed is None:
                self._batch_in_epoch = 0
                if self.initial_checkpoint:
                    # a rollback target exists before the first anomaly can
                    # hit; after a resume the restored checkpoint already IS
                    # that target — re-saving it would be pure startup I/O
                    self._checkpoint(block=True)
            while net.epoch < epochs:
                if self.membership is not None:
                    prev_view = self._view
                    self._view = self.membership.regroup(net.epoch)
                    if (self.wrapper is not None and prev_view is not None
                            and self._view is not None
                            and self._view.world != prev_view.world):
                        # world changed at the barrier: re-place model state
                        # and recompile the GSPMD step onto the CURRENT
                        # device view (reshard() with no mesh re-derives it
                        # from jax.devices(), which on a real pod reflects
                        # the survivors) — the sharding layout is part of
                        # the compile key, so the shrunken mesh gets its
                        # own executable (docs/DISTRIBUTED.md). On one host
                        # the local device set is unchanged and this is a
                        # cheap re-placement; on a real pod it is the
                        # data-plane half of the regroup.
                        self.wrapper.reshard()
                        tm.instant("elastic.reshard", epoch=net.epoch,
                                   world=self._view.world)
                try:
                    done = self._run_epoch(iterator, injector)
                    if done:
                        self._batch_in_epoch = 0
                        # under sync_every>1 the coalesced dispatcher
                        # flushes HERE, so the monitor's anomaly for a
                        # late-window step can surface from _end_epoch —
                        # it must land in the same rollback catch
                        self._end_epoch()
                        self._checkpoint(block=False)
                except RollbackSignal as sig:
                    self._rollback(sig)
                    continue
                if not done:  # drained mid-epoch
                    break
            self.ckpt.wait_until_finished()
            try:
                self._checkpoint(block=True)
            except RollbackSignal as sig:
                # a drain interrupted a window whose pending losses carry
                # an anomaly: restore the good state, then save THAT
                self._rollback(sig)
                self._checkpoint(block=True)
            if self._drain_requested:
                self.drained = True
                self.state = "drained"
                tm.counter("elastic.drains_total")
                tm.set_health("elastic.drained", True,
                              f"drained at step {net.iteration}")
                if self.log:
                    self.log(f"ELASTIC drained at step {net.iteration} "
                             f"(epoch {net.epoch}); checkpoint committed")
            else:
                self.state = "completed"
            return net
        except BaseException:
            self.state = "failed"
            raise
        finally:
            for sig, h in old_handlers.items():
                signal.signal(sig, h)
            if self.membership is not None:
                self.membership.stop(graceful=True)
            try:
                self.ckpt.wait_until_finished()
            except Exception:  # noqa: BLE001 — don't mask the real error
                pass
            if self._publisher is not None:
                # the final weights' archive must be durable when fit()
                # returns (the watcher's "follows training" contract);
                # stop() also ends the writer thread — the next fit()
                # lazily recreates it
                try:
                    self._publisher.stop()
                except Exception:  # noqa: BLE001 — don't mask the error
                    pass
                self._publisher = None
            if installed_monitor and self.monitor in net.listeners:
                net.listeners.remove(self.monitor)

    def _run_epoch(self, iterator, injector) -> bool:
        """One epoch under the current view. Returns False when a drain
        interrupted it (cursor checkpointed), True when it completed."""
        net = self.net
        if hasattr(iterator, "reset"):
            iterator.reset()
        cursor = self._batch_in_epoch  # batches already done before resume
        for i, ds in enumerate(iterator):
            if i < cursor:
                continue  # fast-forward: the checkpoint covers these
            if self._view is not None and not self._view.owns_batch(i):
                self._batch_in_epoch = i + 1
                continue
            if injector.fire(fl.SIGKILL_HOST, step=net.iteration):
                os.kill(os.getpid(), signal.SIGKILL)  # hard host loss
            fault = injector.fire(fl.INJECT_NAN, step=net.iteration)
            if fault is not None:
                ds = self._poison(ds)
            with tm.span("elastic.step", iteration=net.iteration):
                self._step(ds)
            self._batch_in_epoch = i + 1
            self._steps_since_ckpt += 1
            if self._steps_since_ckpt >= self.checkpoint_every:
                self._checkpoint(block=False)
            if self._drain_requested:
                return False
        return True

    # ---------------------------------------------------------------- status
    def status(self) -> dict:
        out = {
            "state": self.state,
            "epoch": self.net.epoch,
            "iteration": self.net.iteration,
            "checkpoint_dir": self.ckpt.directory,
            "last_checkpoint_step": self.ckpt.latest_step(),
            "checkpoint_every": self.checkpoint_every,
            "rollbacks": self.rollbacks,
            "resumed_from": self.resumed_from,
            "drained": self.drained,
            "publish_archive": self.publish_archive,
        }
        comp = getattr(self.wrapper, "_compressor", None) \
            if self.wrapper is not None else None
        if comp is not None:
            # encoded-collectives surface (docs/DISTRIBUTED.md#gradient-
            # compression): scheme + whether the residual state a regroup/
            # resume must migrate is currently resident. Stats stay
            # device-side here — /healthz must never force a sync.
            out["grad_compression"] = {
                "scheme": comp.scheme,
                "hosts": comp.hosts,
                "residual_resident": self.wrapper._comp_state is not None,
            }
        if self.membership is not None:
            out["membership"] = self.membership.status()
        else:
            out["membership"] = {"world": 1, "rank": 0, "members": [0]}
        return out


def bootstrap_elastic(membership_dir: str, process_id: int,
                      num_processes: int, coordinator: Optional[str] = None,
                      retry: Optional[RetryPolicy] = None,
                      **membership_kw) -> FileMembership:
    """Compose the r7 DCN bootstrap with the membership layer: run
    ``distributed.initialize`` (PJRT gRPC control plane) under the retried
    handshake, then stand up heartbeats over ``membership_dir``. On real
    multi-host hardware this is the full stack — GSPMD collectives for the
    data plane, file heartbeats + epoch regroup for supervision; with
    ``coordinator=None`` (single process / membership-only tests) the jax
    bootstrap is skipped and only the membership layer starts."""
    from deeplearning4j_tpu.parallel import distributed

    if coordinator is not None:
        distributed.initialize(
            coordinator=coordinator, num_processes=num_processes,
            process_id=process_id,
            retry=retry if retry is not None else distributed.BOOTSTRAP_RETRY)
    return FileMembership(membership_dir, process_id=process_id,
                          world_size=num_processes, **membership_kw)


def collect_elastic_gauges() -> list:
    """Telemetry default-collector hook: scrape-time elastic gauges
    (util/telemetry.py install_default_collectors)."""
    out = []
    for name, st in current_status().items():
        lab = {"trainer": name}
        m = st.get("membership") or {}
        if m.get("world") is not None:
            out.append(("elastic.world_size", lab, float(m["world"])))
        if m.get("alive"):
            out.append(("elastic.alive_members", lab, float(len(m["alive"]))))
        out.append(("elastic.rollbacks", lab, float(st["rollbacks"])))
        out.append(("elastic.drained", lab, 1.0 if st["drained"] else 0.0))
    return out
