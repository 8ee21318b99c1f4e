"""Elastic fault-tolerant runtime (docs/FAULT_TOLERANCE.md): RetryPolicy /
FaultInjector behavior, atomic + corruption-tolerant checkpoints,
checkpoint->resume bit-identity (MLN, CG, TBPTT, bucketed), and one test per
injected fault asserting its SPECIFIC recovery path fired — worker restart,
regroup, rollback, graceful drain. No recovery code ships unexercised."""

import json
import os
import signal
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.data import ArrayDataSetIterator
from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.computation_graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.listeners import TrainingListener
from deeplearning4j_tpu.nn.recurrent import LSTM, RnnOutputLayer
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.parallel import ElasticTrainer, FileMembership
from deeplearning4j_tpu.util import ShardedCheckpointer, telemetry as tm
from deeplearning4j_tpu.util.faults import (DROP_HEARTBEAT, INJECT_NAN,
                                            KILL_ETL_WORKER,
                                            STALL_PREFETCH, FaultInjector,
                                            RetryExhaustedError, RetryPolicy,
                                            get_injector, parse_fault_spec)

R = np.random.default_rng(42)


@pytest.fixture(autouse=True)
def _clean_injector():
    get_injector().clear()
    yield
    get_injector().clear()
    # NaN-injection tests flip training.* checks in the PROCESS-GLOBAL
    # health registry; restore them so a later suite's /healthz assertion
    # (e.g. test_serving's 200 contract) sees a healthy process — the r17
    # hygiene convention for process-global check state
    _ok, checks = tm.get_telemetry().health_report()
    for name, c in checks.items():
        if name.startswith("training.") and not c.get("ok"):
            tm.set_health(name, True, "test cleanup (elastic NaN leg)")


def _counter(name):
    return tm.get_telemetry().snapshot()["counters"].get(name, 0.0)


def _leaves_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(bool(np.array_equal(np.asarray(x), np.asarray(y)))
               for x, y in zip(la, lb))


def _mln(seed=0, buckets=None, seq=None, tbptt=0, recurrent=False):
    b = NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-2))
    if buckets is not None:
        b = b.batch_buckets(buckets)
    if seq is not None:
        b = b.seq_buckets(seq)
    if tbptt:
        b = b.tbptt_length(tbptt)
    lb = b.list()
    if recurrent:
        conf = (lb.layer(LSTM(n_in=6, n_out=8))
                .layer(RnnOutputLayer(n_in=8, n_out=3))
                .set_input_type(InputType.recurrent(6, 12)).build())
    else:
        conf = (lb.layer(DenseLayer(n_in=4, n_out=8, activation="relu"))
                .layer(OutputLayer(n_in=8, n_out=2))
                .set_input_type(InputType.feed_forward(4)).build())
    return MultiLayerNetwork(conf).init()


def _cg(seed=3):
    g = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-2))
         .graph_builder().add_inputs("in")
         .add_layer("d1", DenseLayer(n_in=4, n_out=6, activation="tanh"), "in")
         .add_layer("d2", DenseLayer(n_in=4, n_out=6, activation="relu"), "in")
         .add_layer("out", OutputLayer(n_in=12, n_out=2), "d1", "d2")
         .set_outputs("out").set_input_types((4,)).build())
    return ComputationGraph(g).init()


def _dense_iter(batch=8, n=32, f=4, c=2, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, f)).astype(np.float32)
    y = np.eye(c, dtype=np.float32)[rng.integers(0, c, n)]
    return lambda: ArrayDataSetIterator(x, y, batch=batch)


class _SigtermAt(TrainingListener):
    """Deliver a real SIGTERM to ourselves after iteration k completes —
    exactly what a preemption notice does to a training process."""

    def __init__(self, at_iteration):
        self.at_iteration = at_iteration

    def iteration_done(self, model, iteration, epoch):
        if iteration == self.at_iteration:
            os.kill(os.getpid(), signal.SIGTERM)


# ---------------------------------------------------------------------------
# RetryPolicy / FaultInjector
# ---------------------------------------------------------------------------
class TestRetryPolicy:
    def test_backoff_schedule_caps(self):
        p = RetryPolicy(max_attempts=5, base_delay=0.1, multiplier=2.0,
                        max_delay=0.3)
        assert p.delays() == [0.1, 0.2, 0.3, 0.3]
        assert RetryPolicy(max_attempts=1).delays() == []

    def test_succeeds_after_transient_failures(self):
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("transient")
            return "ok"

        p = RetryPolicy(max_attempts=4, base_delay=0.001)
        before = _counter("elastic.retries_total{op=flaky}")
        assert p.run(flaky, name="flaky") == "ok"
        assert len(calls) == 3
        assert _counter("elastic.retries_total{op=flaky}") == before + 2

    def test_exhaustion_raises_with_cause(self):
        def always():
            raise ValueError("permanent")

        with pytest.raises(RetryExhaustedError, match="3 attempt"):
            RetryPolicy(max_attempts=3, base_delay=0.001).run(
                always, name="always")
        try:
            RetryPolicy(max_attempts=2, base_delay=0.001).run(
                always, name="always")
        except RetryExhaustedError as e:
            assert isinstance(e.__cause__, ValueError)

    def test_deadline_cuts_retries_short(self):
        t0 = time.monotonic()
        with pytest.raises(RetryExhaustedError, match="deadline"):
            RetryPolicy(max_attempts=10, base_delay=5.0,
                        deadline=0.01).run(
                lambda: (_ for _ in ()).throw(OSError("x")), name="slow")
        assert time.monotonic() - t0 < 1.0  # did NOT sleep the 5s backoff

    def test_non_retryable_passes_through(self):
        with pytest.raises(KeyError):
            RetryPolicy(max_attempts=3, base_delay=0.001).run(
                lambda: (_ for _ in ()).throw(KeyError("nope")),
                retry_on=(OSError,), name="typed")


class TestFaultInjector:
    def test_parse_env_spec(self):
        faults = parse_fault_spec(
            "kill_etl_worker, inject_nan@5, stall_prefetch:3.5")
        assert [(f.kind, f.at_step, f.arg) for f in faults] == [
            ("kill_etl_worker", None, None), ("inject_nan", 5, None),
            ("stall_prefetch", None, 3.5)]

    def test_parse_unknown_kind_is_loud(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            parse_fault_spec("kill_everything@1")

    def test_step_gate_on_stepless_kind_is_loud(self):
        # kill_etl_worker fires at a site with no step concept: @step would
        # arm a fault that can never fire — a chaos run that tests nothing
        with pytest.raises(ValueError, match="no step concept"):
            parse_fault_spec("kill_etl_worker@2")
        with pytest.raises(ValueError, match="no step concept"):
            get_injector().inject(STALL_PREFETCH, at_step=3)

    def test_step_gating_and_once_semantics(self):
        inj = get_injector()
        inj.inject(INJECT_NAN, at_step=5)
        assert inj.fire(INJECT_NAN, step=4) is None
        assert inj.fire(INJECT_NAN) is None  # step-gated, site has no step
        assert inj.fire(INJECT_NAN, step=6) is not None
        assert inj.fire(INJECT_NAN, step=7) is None  # consumed (count=1)
        assert inj.log == [(INJECT_NAN, 6)]

    def test_repeating_fault(self):
        inj = get_injector()
        inj.inject(STALL_PREFETCH, count=2)
        assert inj.fire(STALL_PREFETCH) is not None
        assert inj.fire(STALL_PREFETCH) is not None
        assert inj.fire(STALL_PREFETCH) is None


# ---------------------------------------------------------------------------
# Checkpoint atomicity / corruption tolerance
# ---------------------------------------------------------------------------
class TestCheckpointer:
    def _fit_and_save(self, tmp_path, steps=2):
        net = _mln(seed=0)
        x, y = (np.ones((8, 4), np.float32),
                np.eye(2, dtype=np.float32)[np.zeros(8, int)])
        ck = ShardedCheckpointer(str(tmp_path / "ck"), keep=3, log_fn=None)
        for _ in range(steps):
            net.fit(x, y, epochs=1)
            ck.save(net.iteration, net,
                    extra_meta={"batch_in_epoch": net.iteration % 2})
        return net, ck

    def test_tmp_orphan_invisible_and_swept(self, tmp_path):
        net, ck = self._fit_and_save(tmp_path)
        # a crash mid-save leaves exactly these; own-pid orphans sweep on
        # the next save, a foreign writer's only once stale (one-writer
        # contract: a LIVE concurrent write must survive the sweep)
        mine = os.path.join(ck.directory, f".tmp-999-{os.getpid()}")
        foreign_live = os.path.join(ck.directory, ".tmp-998-12345")
        foreign_stale = os.path.join(ck.directory, ".tmp-997-12345")
        for d in (mine, foreign_live, foreign_stale):
            os.makedirs(d)
        os.utime(foreign_stale, (time.time() - 7200, time.time() - 7200))
        assert all(s not in ck.all_steps() for s in (997, 998, 999))
        ck.save(net.iteration + 1, net)
        assert not os.path.exists(mine)
        assert os.path.exists(foreign_live)
        assert not os.path.exists(foreign_stale)

    def test_meta_sidecar_roundtrip(self, tmp_path):
        net, ck = self._fit_and_save(tmp_path)
        step = ck.latest_step()
        meta = ck.load_meta(step)
        assert meta["step"] == step
        assert "batch_in_epoch" in meta

    def test_corrupt_newest_skipped_with_warning(self, tmp_path):
        """Regression: truncate every file of the newest checkpoint
        mid-byte — restore must warn + skip to the older good one, never
        crash."""
        import glob

        net, ck = self._fit_and_save(tmp_path, steps=2)
        good_step = ck.all_steps()[0]
        good = MultiLayerNetwork(net.conf).init()
        ck.restore(good, step=good_step)
        newest = os.path.join(ck.directory, str(ck.latest_step()))
        for f in glob.glob(os.path.join(newest, "**", "*"), recursive=True):
            if os.path.isfile(f):
                with open(f, "r+b") as fh:
                    fh.truncate(max(0, os.path.getsize(f) // 3))
        warnings = []
        ck.log = warnings.append
        before = _counter("checkpoint.corrupt_skipped_total")
        net2 = MultiLayerNetwork(net.conf).init()
        assert ck.restore_latest_good(net2) == good_step
        assert _counter("checkpoint.corrupt_skipped_total") == before + 1
        assert warnings and "failed to load" in warnings[0]
        assert _leaves_equal(net2.params, good.params)

    def test_restore_latest_good_none_when_empty(self, tmp_path):
        ck = ShardedCheckpointer(str(tmp_path / "empty"), log_fn=None)
        assert ck.restore_latest_good(_mln()) is None

    def test_async_save_commits_identically(self, tmp_path, wait_until):
        net, ck = self._fit_and_save(tmp_path)
        ck.save(net.iteration + 1, net, block=False)
        # wait_until_finished() joins without a deadline: give it one
        wait_until(lambda: not ck._pending.is_alive(), 60,
                   "the async save's thread ended")
        ck.wait_until_finished()
        sync_net = MultiLayerNetwork(net.conf).init()
        ck.restore(sync_net, step=net.iteration + 1)
        assert _leaves_equal(sync_net.params, net.params)
        assert _leaves_equal(sync_net.opt_states, net.opt_states)

    def test_rng_key_round_trips(self, tmp_path):
        net, ck = self._fit_and_save(tmp_path)
        key = np.asarray(net._rng_key).copy()
        net2 = MultiLayerNetwork(net.conf).init()
        ck.restore(net2)
        assert np.array_equal(np.asarray(net2._rng_key), key)


# ---------------------------------------------------------------------------
# Kill-and-resume bit-identity (acceptance: MLN + CG, TBPTT, bucketed)
# ---------------------------------------------------------------------------
class TestResumeBitIdentity:
    def _drain_and_resume(self, build, data_iter, tmp_path, epochs=3,
                          kill_at=5, checkpoint_every=2):
        """fit() interrupted by a real SIGTERM at step ``kill_at``, resumed
        from its auto-checkpoint in a FRESH model, must end bit-identical
        to an uninterrupted run of the same total step count."""
        ref = build()
        ref.fit(data_iter(), epochs=epochs)

        net = build()
        net.listeners.append(_SigtermAt(kill_at))
        t1 = ElasticTrainer(net, str(tmp_path / "ck"),
                            checkpoint_every=checkpoint_every, log_fn=None)
        t1.fit(data_iter(), epochs=epochs)
        assert t1.drained and net.iteration == kill_at
        assert t1.ckpt.latest_step() == kill_at  # drain checkpointed

        net2 = build()
        t2 = ElasticTrainer(net2, str(tmp_path / "ck"),
                            checkpoint_every=checkpoint_every, log_fn=None)
        t2.fit(data_iter(), epochs=epochs)
        assert t2.resumed_from == kill_at
        assert t2.state == "completed"
        assert net2.iteration == ref.iteration
        assert net2.epoch == ref.epoch
        assert _leaves_equal(net2.params, ref.params)
        assert _leaves_equal(net2.opt_states, ref.opt_states)
        assert np.array_equal(np.asarray(net2._rng_key),
                              np.asarray(ref._rng_key))

    def test_mln_sigterm_resume_bit_identical(self, tmp_path):
        self._drain_and_resume(_mln, _dense_iter(), tmp_path)

    def test_cg_sigterm_resume_bit_identical(self, tmp_path):
        self._drain_and_resume(_cg, _dense_iter(), tmp_path)

    def test_mln_tbptt_resume_bit_identical(self, tmp_path):
        def data():
            rng = np.random.default_rng(1)
            x = rng.standard_normal((8, 12, 6)).astype(np.float32)
            y = rng.standard_normal((8, 12, 3)).astype(np.float32)
            return ArrayDataSetIterator(x, y, batch=4)

        # tbptt_length 4 over T=12: 3 segments (= iterations) per batch
        self._drain_and_resume(
            lambda: _mln(seed=5, tbptt=4, recurrent=True), data, tmp_path,
            epochs=2, kill_at=6, checkpoint_every=3)

    def test_mln_bucketed_resume_bit_identical(self, tmp_path):
        def data():
            rng = np.random.default_rng(2)
            x = rng.standard_normal((21, 4)).astype(np.float32)
            y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 21)]
            # batch 6 over 21 rows: ragged tail pads to the 8-bucket with
            # 0/1 weights — the padded path must resume bit-identically too
            return ArrayDataSetIterator(x, y, batch=6)

        self._drain_and_resume(
            lambda: _mln(seed=6, buckets=(8,)), data, tmp_path,
            epochs=3, kill_at=5, checkpoint_every=2)


# ---------------------------------------------------------------------------
# Every injected fault -> its specific recovery path
# ---------------------------------------------------------------------------
class TestFaultRecoveryPaths:
    def test_kill_etl_worker_restarts_only_that_chunk(self):
        from deeplearning4j_tpu.datavec.executor import (
            MultiProcessTransformExecutor)
        from deeplearning4j_tpu.datavec.transform import (Schema,
                                                          TransformProcess)

        schema = Schema.builder().add_column_double("x").build()
        tp = (TransformProcess.builder(schema)
              .double_column_transform("x", _slow_double).build())
        records = [[float(i)] for i in range(512)]
        serial = tp.execute(records)
        get_injector().inject(KILL_ETL_WORKER)
        before = _counter("etl.worker_restarts_total")
        ex = MultiProcessTransformExecutor(tp, num_workers=4,
                                           min_records_per_worker=64,
                                           timeout=60)
        out = ex.execute(records)
        assert out == serial  # bit-identical in-order merge, kill included
        assert _counter("etl.worker_restarts_total") >= before + 1
        assert "dl4j_etl_worker_restarts_total" in \
            tm.get_telemetry().prometheus_text()

    def test_etl_retries_exhausted_is_loud(self):
        from deeplearning4j_tpu.datavec.executor import (
            MultiProcessTransformExecutor, TransformExecutionError)
        from deeplearning4j_tpu.datavec.transform import (Schema,
                                                          TransformProcess)

        schema = Schema.builder().add_column_double("x").build()
        tp = (TransformProcess.builder(schema)
              .double_column_transform("x", _always_boom).build())
        ex = MultiProcessTransformExecutor(tp, num_workers=2,
                                           min_records_per_worker=64,
                                           timeout=30)
        with pytest.raises(TransformExecutionError,
                           match=r"failed after 3 attempt"):
            ex.execute([[float(i)] for i in range(256)])

    def test_stall_prefetch_diagnostics_and_counter(self):
        from deeplearning4j_tpu.data.prefetch import (AsyncDataSetIterator,
                                                      PrefetchStalledError)

        x = np.zeros((16, 4), np.float32)
        y = np.zeros((16, 2), np.float32)
        get_injector().inject(STALL_PREFETCH, arg=30.0)
        it = AsyncDataSetIterator(ArrayDataSetIterator(x, y, batch=4),
                                  timeout=0.5, device_put=False)
        before = _counter("prefetch.stall_timeouts_total")
        with pytest.raises(PrefetchStalledError) as ei:
            list(it)
        msg = str(ei.value)
        # the post-mortem payload: depth, cursor, producer liveness
        assert "queue depth" in msg
        assert "last successful batch index" in msg
        assert "alive" in msg or "DEAD" in msg
        assert _counter("prefetch.stall_timeouts_total") == before + 1

    def test_inject_nan_rolls_back_and_completes(self, tmp_path):
        data = _dense_iter()
        ref = _mln()
        ref.fit(data(), epochs=3)

        net = _mln()
        get_injector().inject(INJECT_NAN, at_step=6)
        before = _counter("elastic.rollbacks_total")
        tr = ElasticTrainer(net, str(tmp_path / "ck"), checkpoint_every=3,
                            log_fn=None)
        tr.fit(data(), epochs=3)
        assert tr.rollbacks == 1
        assert _counter("elastic.rollbacks_total") == before + 1
        assert tr.state == "completed"
        # the poisoned step was rolled back and replayed clean: the final
        # params are bit-identical to the run that never saw the NaN
        assert _leaves_equal(net.params, ref.params)
        # and an operator sees the recovery: on /healthz' elastic section
        # and among the series a scrape of /metrics returns
        from deeplearning4j_tpu.util.ui_server import UIServer

        section = json.loads(UIServer._healthz()[0])["elastic"]
        assert list(section.values())[-1]["rollbacks"] == 1
        text = UIServer._metrics_text()
        assert "dl4j_elastic_rollbacks_total" in text
        assert "dl4j_elastic_checkpoints_total" in text

    def test_inject_nan_rollback_under_coalesced_dispatch(self, tmp_path):
        """sync_every>1: the poisoned step's loss is detected at a WINDOW
        boundary (possibly the epoch-end flush), and checkpoints flush the
        dispatcher first so a NaN window can never be committed as a good
        rollback target."""
        def build():
            conf = (NeuralNetConfiguration.builder().seed(4)
                    .updater(Adam(1e-2)).sync_every(3).list()
                    .layer(DenseLayer(n_in=4, n_out=8, activation="relu"))
                    .layer(OutputLayer(n_in=8, n_out=2))
                    .set_input_type(InputType.feed_forward(4)).build())
            return MultiLayerNetwork(conf).init()

        data = _dense_iter()
        ref = build()
        ref.fit(data(), epochs=3)
        net = build()
        get_injector().inject(INJECT_NAN, at_step=6)
        tr = ElasticTrainer(net, str(tmp_path / "ck"), checkpoint_every=4,
                            log_fn=None)
        tr.fit(data(), epochs=3)
        assert tr.rollbacks == 1 and tr.state == "completed"
        assert _leaves_equal(net.params, ref.params)

    def test_rollback_budget_exhausts_loudly(self, tmp_path):
        net = _mln()
        get_injector().inject(INJECT_NAN, at_step=2, count=-1)  # every step
        tr = ElasticTrainer(net, str(tmp_path / "ck"), checkpoint_every=2,
                            max_rollbacks=2, log_fn=None)
        with pytest.raises(RuntimeError, match="rollback budget exhausted"):
            tr.fit(_dense_iter()(), epochs=2)
        assert tr.rollbacks == 2
        assert tr.state == "failed"

    def test_drop_heartbeat_shrinks_world_at_regroup(self, tmp_path,
                                                     wait_until):
        d = str(tmp_path / "members")
        # b gets a PRIVATE injector so drop_heartbeat hits exactly ITS beat
        # thread (both members live in this one test process)
        b_injector = FaultInjector()
        b_injector.clear()
        a = FileMembership(d, process_id=0, world_size=2,
                           heartbeat_interval=0.05, miss_threshold=3,
                           barrier_timeout=20.0, log_fn=None)
        b = FileMembership(d, process_id=1, world_size=2,
                           heartbeat_interval=0.05, miss_threshold=3,
                           barrier_timeout=20.0, injector=b_injector,
                           log_fn=None)
        a.start()
        b.start()
        try:
            import threading

            views = {}
            tb = threading.Thread(
                target=lambda: views.setdefault(1, b.regroup(0)))
            tb.start()
            views[0] = a.regroup(0)
            tb.join(timeout=20)
            assert not tb.is_alive()
            assert views[0].world == 2 and views[1].world == 2

            # b's heartbeats drop (the fault fires in ITS beat thread);
            # after the miss threshold, a's next regroup evicts it
            before = _counter("elastic.heartbeats_dropped_total")
            b_injector.inject(DROP_HEARTBEAT, arg=1000)
            wait_until(
                lambda: _counter("elastic.heartbeats_dropped_total") > before,
                10, "b's beat thread dropped a heartbeat")
            wait_until(lambda: a.alive() == [0], 10,
                       "b's last heartbeat left a's freshness window")
            view = a.regroup(1)
            assert view.world == 1 and view.members == (0,)
            assert a.regroups == 1
            assert _counter("elastic.regroups_total") >= 1
        finally:
            a.stop()
            b.stop()

    def test_sigkill_host_survivor_regroups_and_finishes(self, tmp_path,
                                                         child_env):
        """ISSUE acceptance: 2 OS processes, one SIGKILLed mid-epoch; the
        survivor notices the missed heartbeats, regroups to world 1,
        re-shards the batches, and finishes all epochs."""
        _sigkill_pod("--elastic", tmp_path, child_env)

    def test_sigkill_with_grad_compression_migrates_residual(self, tmp_path,
                                                             child_env):
        """Elastic × compression (ISSUE 10 satellite): same 2-process
        SIGKILL scenario, but the data plane is the COMPRESSED
        ParallelWrapper step — the survivor regroups with its
        error-feedback residual/threshold migrated through reshard (the
        iteration trace proves it kept training), and the final checkpoint
        carries the residual EXACTLY (bit-compared in-process against a
        fresh restore)."""
        r = _sigkill_pod("--elastic-compress", tmp_path, child_env)
        assert r["residual_exact"], r  # checkpoint carried the residual
        assert r["wire_bytes"] and r["wire_bytes"] > 0
        assert r["threshold"] and r["threshold"] > 0

    def test_sigkill_with_pipelined_trainer_restores_stacked_state(
            self, tmp_path, child_env):
        """Elastic × pipeline (ISSUE 14 satellite): the 2-process SIGKILL
        scenario with the PIPELINED trainer as the data plane — stacked
        stage params/optimizer state, GPipe microbatch schedule, lane DP.
        The survivor regroups and keeps training (same 4+8+8 iteration
        trace as the plain legs — reshard() migrated the stacked state
        through model layout bit-exactly), and the final checkpoint
        restores the STACKED stage state bit-exactly at the boundary
        (compared in-process against the live trainer's placed leaves)."""
        r = _sigkill_pod("--pipe", tmp_path, child_env)
        assert r["stacked_exact"], r  # checkpoint carried the stacked state
        assert r["pipe_stages"] == 2
        assert 0 < r["bubble_fraction"] < 1


def _sigkill_pod(mode, tmp_path, child_env):
    """Two OS processes of ``_dist_worker.py <mode>`` over one shared
    directory; pid 1 SIGKILLs itself at step 2. Asserts what every data
    plane owes (the victim died hard, the survivor regrouped to world 1 and
    finished the same 4+8+8 trace) and returns the survivor's report."""
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "_dist_worker.py")
    procs = [subprocess.Popen(
        [sys.executable, worker, mode, str(tmp_path / "pod"), str(pid), "2"]
        + (["2"] if pid == 1 else []),  # pid 1 SIGKILLs itself at step 2
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=child_env()) for pid in (0, 1)]
    try:
        out0, err0 = procs[0].communicate(timeout=240)
        out1, _ = procs[1].communicate(timeout=60)
    finally:
        for p in procs:  # a hang fails this test, not the run
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)
    assert procs[1].returncode == -signal.SIGKILL  # died hard, no JSON
    assert not out1.strip()
    assert procs[0].returncode == 0, err0[-1500:]
    r = json.loads([l for l in out0.splitlines() if l.startswith("{")][-1])
    assert r["state"] == "completed"
    assert r["world_final"] == 1 and r["members_final"] == [0]
    assert r["regroups"] >= 1
    assert r["epoch"] == 3 and r["score_finite"]
    # 8 batches/epoch: epoch 0 sharded 2 ways (4 steps), then re-sharded
    # to all 8 for the remaining epochs
    assert r["iteration"] == 4 + 8 + 8
    # the child compiled at the suite's level, on its one device
    assert "xla_backend_optimization_level" in r["xla_flags"]
    assert "device_count" not in r["xla_flags"]
    return r


def _slow_double(v):
    time.sleep(0.005)  # keep workers alive long enough to be killed
    return v * 2.0


def _always_boom(v):
    raise ValueError("deterministic child failure")


# ---------------------------------------------------------------------------
# Drain semantics + status surfaces
# ---------------------------------------------------------------------------
class TestDrainAndSurfaces:
    def test_sigterm_drains_gracefully(self, tmp_path):
        net = _mln()
        net.listeners.append(_SigtermAt(4))
        before = _counter("elastic.drains_total")
        tr = ElasticTrainer(net, str(tmp_path / "ck"), checkpoint_every=10,
                            log_fn=None)
        tr.fit(_dense_iter()(), epochs=3)
        assert tr.drained and tr.state == "drained"
        assert net.iteration == 4  # finished the in-flight step, no more
        assert tr.ckpt.latest_step() == 4  # work saved before leaving
        assert _counter("elastic.drains_total") == before + 1
        ok, checks = tm.get_telemetry().health_report()
        assert checks["elastic.drained"]["ok"]

    def test_healthz_has_elastic_membership_section(self, tmp_path):
        from deeplearning4j_tpu.util.ui_server import UIServer

        net = _mln()
        tr = ElasticTrainer(net, str(tmp_path / "ck"), checkpoint_every=50,
                            log_fn=None)
        tr.fit(_dense_iter()(), epochs=1)
        body, ok = UIServer._healthz()
        payload = json.loads(body)
        section = payload.get("elastic", {})
        assert section, "healthz must carry the elastic membership section"
        st = list(section.values())[-1]
        assert st["state"] == "completed"
        assert st["membership"]["world"] == 1
        assert st["last_checkpoint_step"] == net.iteration
        # scrape-time gauges ride the default collectors
        text = tm.install_default_collectors().prometheus_text()
        assert "dl4j_elastic_world_size" in text

    def test_parallel_wrapper_supervised_bit_identical(self, tmp_path):
        from deeplearning4j_tpu.parallel import ParallelWrapper, TrainingMesh

        n_dev = min(2, len(jax.devices()))
        mesh = lambda: TrainingMesh(  # noqa: E731
            data=n_dev, devices=jax.devices()[:n_dev])
        data = _dense_iter(batch=8)

        ref = _mln(seed=9)
        ParallelWrapper(ref, mesh=mesh()).fit(data(), epochs=2)

        net = _mln(seed=9)
        pw = ParallelWrapper(net, mesh=mesh())
        tr = ElasticTrainer(pw, str(tmp_path / "ck"), checkpoint_every=3,
                            log_fn=None)
        tr.fit(data(), epochs=2)
        assert tr.state == "completed"
        assert net.iteration == ref.iteration
        assert _leaves_equal(net.params, ref.params)
