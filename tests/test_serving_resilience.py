"""Serving resilience layer (ISSUE 13): rolling weight reload under traffic
(zero shed, zero steady-state recompiles, version surface advancing), canary
rejection keeping the old weights serving, supervised scheduler workers
(crash -> loud 500 + flight-recorder cause -> restart; budget exhausted ->
health flip + fail-fast submits), the per-model circuit-breaker state
machine, SLO-brownout lane ordering, the new serving fault kinds'
``DL4J_TPU_FAULTS`` parsing, the train->serve publish/watch seam, and what
the HTTP server answers for each of them."""

import os
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.data.bucketing import BucketingPolicy
from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.serving import (BrownoutController, BrownoutShedError,
                                        CircuitBreaker, CircuitOpenError,
                                        ModelLoadError, ModelRouter,
                                        ReloadRejectedError,
                                        SchedulerDrainingError,
                                        SchedulerStoppedError, ServingModel,
                                        WorkerCrashedError)
from deeplearning4j_tpu.serving.scheduler import BatchScheduler
from deeplearning4j_tpu.util import faults as fl
from deeplearning4j_tpu.util import telemetry as tm
from deeplearning4j_tpu.util.faults import get_injector, parse_fault_spec
from deeplearning4j_tpu.util.model_serializer import ModelSerializer

R = np.random.default_rng(11)


@pytest.fixture(autouse=True)
def _clean_injector():
    get_injector().clear()
    yield
    get_injector().clear()


def _dense_net(seed=0, n_in=10, n_out=4):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
            .batch_buckets((2, 4, 8)).list()
            .layer(DenseLayer(n_in=n_in, n_out=24, activation="relu"))
            .layer(OutputLayer(n_in=24, n_out=n_out, loss="mcxent",
                               activation="softmax"))
            .set_input_type(InputType.feed_forward(n_in)).build())
    return MultiLayerNetwork(conf).init()


def _archive(tmp_path, name, net):
    path = str(tmp_path / name)
    ModelSerializer.write_model(net, path, save_updater=False)
    return path


def _router_with(model_id="m", seed=0, **reg_kw):
    net = _dense_net(seed)
    router = ModelRouter(name=f"resilience-{model_id}")
    model = ServingModel(net, model_id)
    sched = router.register(model, max_wait_ms=0.5, **reg_kw)
    model.warmup()
    return router, net, model, sched


X2 = R.normal(size=(2, 10)).astype(np.float32)


def _counter(name: str, **labels) -> float:
    return tm.get_telemetry().counter_total(name, **labels)


# --------------------------------------------------------------- fault kinds
class TestServingFaultParsing:
    def test_new_kinds_parse(self):
        faults = parse_fault_spec(
            "serving_compute_error@3,serving_worker_crash,"
            "serving_slow_batch:250,reload_corrupt_archive:0.4")
        by_kind = {f.kind: f for f in faults}
        assert by_kind["serving_compute_error"].at_step == 3
        assert by_kind["serving_worker_crash"].at_step is None
        assert by_kind["serving_slow_batch"].arg == 250.0
        assert by_kind["reload_corrupt_archive"].arg == 0.4

    def test_serving_kinds_are_step_gated(self):
        # @nth = the scheduler's batch-cycle number; legal for the three
        # scheduler-sited kinds, illegal for the reload path (no steps)
        for kind in (fl.SERVING_COMPUTE_ERROR, fl.SERVING_WORKER_CRASH,
                     fl.SERVING_SLOW_BATCH):
            assert parse_fault_spec(f"{kind}@2")[0].at_step == 2
        with pytest.raises(ValueError, match="no step concept"):
            parse_fault_spec("reload_corrupt_archive@2")

    def test_unknown_kind_still_raises(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            parse_fault_spec("serving_typo_error")


# ----------------------------------------------------------- circuit breaker
class TestCircuitBreaker:
    def _clocked(self, **kw):
        t = [0.0]
        br = CircuitBreaker(clock=lambda: t[0], model_id="t", **kw)
        return t, br

    def test_opens_on_consecutive_errors(self):
        _t, br = self._clocked(consecutive_errors=3)
        br.record_error()
        br.record_error()
        assert br.state == "closed"
        br.record_error()
        assert br.state == "open"

    def test_opens_on_error_rate(self):
        _t, br = self._clocked(consecutive_errors=100, error_rate=0.5,
                               window=8, min_samples=8)
        for i in range(8):  # alternating: never 100 consecutive, rate 0.5
            (br.record_error if i % 2 else br.record_success)()
        assert br.state == "open"

    def test_open_fast_fails_with_retry_after(self):
        t, br = self._clocked(consecutive_errors=1, cooldown_s=10.0)
        br.record_error()
        with pytest.raises(CircuitOpenError) as ei:
            br.allow()
        assert ei.value.http_status == 503
        assert 9.0 <= ei.value.retry_after_s <= 10.0

    def test_half_open_probe_bounded_then_closes(self):
        t, br = self._clocked(consecutive_errors=1, cooldown_s=5.0,
                              half_open_probes=1)
        br.record_error()
        t[0] = 6.0
        br.allow()  # the probe
        assert br.state == "half_open"
        with pytest.raises(CircuitOpenError):
            br.allow()  # only one probe may fly
        br.record_success()
        assert br.state == "closed"
        br.allow()  # closed again: free passage

    def test_half_open_failure_reopens(self):
        t, br = self._clocked(consecutive_errors=1, cooldown_s=5.0)
        br.record_error()
        t[0] = 6.0
        br.allow()
        br.record_error()
        assert br.state == "open"
        with pytest.raises(CircuitOpenError):
            br.allow()  # fresh cooldown from the failed probe
        assert br.opens == 2

    def test_success_resets_consecutive_count(self):
        _t, br = self._clocked(consecutive_errors=3, min_samples=100)
        for _ in range(2):
            br.record_error()
        br.record_success()
        for _ in range(2):
            br.record_error()
        assert br.state == "closed"


class TestBreakerOnTraffic:
    def test_compute_errors_open_then_half_open_closes(self, wait_until):
        router, _net, _model, sched = _router_with("brk")
        try:
            sched.breaker.consecutive_errors = 2
            sched.breaker.cooldown_s = 0.3
            get_injector().inject(fl.SERVING_COMPUTE_ERROR, count=2)
            for _ in range(2):
                with pytest.raises(RuntimeError, match="injected serving"):
                    router.submit("brk", X2).result(timeout=20)
            assert sched.breaker.state == "open"
            # open = fast-fail 503 + Retry-After, never queued
            with pytest.raises(CircuitOpenError):
                router.submit("brk", X2)
            assert sched.counts["shed_circuit_open"] >= 1
            # cooldown over -> the half-open probe is allowed through
            probe = []

            def admitted():
                try:
                    probe.append(router.submit("brk", X2))
                except CircuitOpenError:
                    return False
                return True

            wait_until(admitted, 5, "half-open probe after the cooldown")
            assert np.asarray(probe[0].result(timeout=20)).shape == (2, 4)
            wait_until(lambda: sched.breaker.state == "closed", 5,
                       "breaker closed by the probe's success")
        finally:
            router.shutdown()

    def test_breaker_disabled_by_knob(self):
        net = _dense_net()
        model = ServingModel(net, "nobrk")
        sched = BatchScheduler(model, breaker=None)
        assert sched.breaker is None
        sched.shutdown()


# --------------------------------------------------------- supervised worker
class TestWorkerWatchdog:
    def test_crash_fails_batch_loudly_and_restarts(self):
        router, _net, _model, sched = _router_with("wd")
        try:
            restarts0 = _counter("serving.worker_restarts_total", model="wd")
            get_injector().inject(fl.SERVING_WORKER_CRASH, count=1)
            fut = router.submit("wd", X2)
            with pytest.raises(WorkerCrashedError):
                fut.result(timeout=20)
            # the crash is on the flight recorder with its cause
            recs = sched.flight.dump()
            assert any(r["status"] == "error"
                       and str(r["cause"]).startswith("worker_crash")
                       for r in recs)
            assert _counter("serving.worker_restarts_total",
                            model="wd") == restarts0 + 1
            # restarted worker keeps serving
            out = np.asarray(router.submit("wd", X2).result(timeout=20))
            assert out.shape == (2, 4)
            assert sched.stats()["worker_restarts"] == 1
            assert sched.stats()["worker_alive"]
        finally:
            router.shutdown()

    def test_restart_budget_exhaustion_flips_health_and_fails_fast(self):
        router, _net, _model, sched = _router_with("wd2", max_restarts=0)
        try:
            get_injector().inject(fl.SERVING_WORKER_CRASH, count=3)
            fut = router.submit("wd2", X2)
            with pytest.raises(WorkerCrashedError):
                fut.result(timeout=20)
            deadline = time.time() + 5
            while not sched._worker_dead and time.time() < deadline:
                time.sleep(0.02)
            # health check flipped: the model is declared down
            _ok, checks = tm.get_telemetry().health_report()
            check = checks.get("serving.worker.wd2")
            assert check is not None and check["ok"] is False
            # and a LATER submit fails fast instead of hanging forever
            with pytest.raises(SchedulerStoppedError):
                router.submit("wd2", X2)
        finally:
            router.shutdown()
            # the registry is process-global: restore the check so later
            # suites' /healthz assertions see a healthy process
            tm.set_health("serving.worker.wd2", True, "test cleanup")


class TestSubmitFailFast:
    def test_submit_after_shutdown_fails_fast(self):
        """Satellite: submit() to a stopped scheduler raises a clear
        exception instead of enqueueing into a dead queue forever."""
        router, _net, _model, sched = _router_with("stop")
        router.shutdown()
        with pytest.raises(SchedulerStoppedError, match="stopped"):
            sched.submit(X2)

    def test_shutdown_fails_pending_futures_loudly(self):
        """Satellite: futures queued at shutdown resolve with an exception,
        never hang."""
        net = _dense_net()
        model = ServingModel(net, "pend")
        sched = BatchScheduler(model, max_wait_ms=50.0)
        futs = [sched.submit(X2) for _ in range(3)]  # no worker started
        sched.shutdown()
        for f in futs:
            with pytest.raises(SchedulerDrainingError):
                f.result(timeout=5)


# ------------------------------------------------------------ rolling reload
class TestRollingReload:
    def test_reload_swaps_weights_and_advances_version(self, tmp_path):
        router, _net, model, _sched = _router_with("rl", seed=0)
        try:
            new_net = _dense_net(seed=1)
            path = _archive(tmp_path, "v2.zip", new_net)
            before = np.asarray(router.submit("rl", X2).result(timeout=20))
            assert router.reload("rl", path) == 2
            assert model.version == 2
            after = np.asarray(router.submit("rl", X2).result(timeout=20))
            assert not np.array_equal(before, after)
            # served output == the new net's direct forward, bit-identical
            assert np.array_equal(after, np.asarray(new_net.output(X2)))
            assert router.status()["models"]["rl"]["version"] == 2
        finally:
            router.shutdown()

    def test_corrupt_archive_rejected_old_keeps_serving(self, tmp_path):
        """Satellite: a truncated archive raises a clean ModelLoadError and
        the live model is untouched."""
        router, _net, model, _sched = _router_with("rl2")
        try:
            path = _archive(tmp_path, "good.zip", _dense_net(seed=1))
            data = open(path, "rb").read()
            bad = str(tmp_path / "trunc.zip")
            open(bad, "wb").write(data[: len(data) // 2])
            before = np.asarray(router.submit("rl2", X2).result(timeout=20))
            with pytest.raises(ModelLoadError):
                router.reload("rl2", bad)
            assert model.version == 1
            after = np.asarray(router.submit("rl2", X2).result(timeout=20))
            assert np.array_equal(before, after)
            assert _counter("serving.reload_rejected_total", model="rl2",
                            reason="load_error") >= 1
        finally:
            router.shutdown()

    def test_nan_canary_rejected(self, tmp_path):
        import jax

        router, _net, model, _sched = _router_with("rl3")
        try:
            bad_net = _dense_net(seed=2)
            bad_net.params = jax.tree_util.tree_map(
                lambda a: a * np.nan, bad_net.params)
            path = _archive(tmp_path, "nan.zip", bad_net)
            with pytest.raises(ReloadRejectedError, match="canary"):
                router.reload("rl3", path)
            assert model.version == 1
            out = np.asarray(router.submit("rl3", X2).result(timeout=20))
            assert np.all(np.isfinite(out))
        finally:
            router.shutdown()

    def test_structure_mismatch_rejected(self, tmp_path):
        router, _net, model, _sched = _router_with("rl4")
        try:
            other = _dense_net(seed=0, n_in=6)  # different topology
            path = _archive(tmp_path, "other.zip", other)
            with pytest.raises(ReloadRejectedError, match="topology"):
                router.reload("rl4", path)
            assert model.version == 1
        finally:
            router.shutdown()

    def test_reload_corrupt_archive_fault_fires_on_good_archive(
            self, tmp_path):
        """The injected fault corrupts the READ of a good archive — the
        real truncated-zip mechanism — and the reload is rejected while
        the old version keeps serving."""
        router, _net, model, _sched = _router_with("rl5")
        try:
            path = _archive(tmp_path, "good.zip", _dense_net(seed=1))
            get_injector().inject(fl.RELOAD_CORRUPT_ARCHIVE)
            with pytest.raises(ModelLoadError):
                router.reload("rl5", path)
            assert model.version == 1
            # disarmed after one firing: the SAME archive now reloads fine
            assert router.reload("rl5", path) == 2
        finally:
            router.shutdown()

    def test_load_corrupt_archive_never_partially_registers(self, tmp_path):
        """Satellite: router.load() on a truncated archive raises cleanly
        and the registry holds nothing under that id."""
        router = ModelRouter(name="load-clean")
        path = _archive(tmp_path, "good.zip", _dense_net())
        data = open(path, "rb").read()
        bad = str(tmp_path / "trunc.zip")
        open(bad, "wb").write(data[: len(data) // 3])
        with pytest.raises(ModelLoadError):
            router.load("ghost", bad)
        assert "ghost" not in router.model_ids()
        # a good archive under the same id still loads (no tombstone)
        router.load("ghost", path)
        assert "ghost" in router.model_ids()
        router.shutdown()

    def test_reload_storm_under_traffic_zero_shed_zero_recompiles(
            self, tmp_path):
        """The acceptance case: N>=5 rolling reloads under sustained
        traffic complete with 0 shed requests, 0 steady-state recompiles,
        and the version surface advancing."""
        router, _net, model, _sched = _router_with("storm", queue_limit=512)
        try:
            paths = [_archive(tmp_path, f"v{i}.zip", _dense_net(seed=i))
                     for i in range(1, 6)]
            stop = threading.Event()
            outcome = {"ok": 0, "err": []}

            def traffic():
                while not stop.is_set():
                    try:
                        router.submit("storm", X2).result(timeout=60)
                        outcome["ok"] += 1
                    except Exception as e:  # noqa: BLE001 — recorded
                        outcome["err"].append(repr(e))

            threads = [threading.Thread(target=traffic) for _ in range(3)]
            for t in threads:
                t.start()
            time.sleep(0.2)
            rec0 = _counter("serving.recompiles_total", model="storm")
            versions = [router.reload("storm", p) for p in paths]
            time.sleep(0.2)
            stop.set()
            for t in threads:
                t.join(timeout=30)
            assert versions == [2, 3, 4, 5, 6]
            assert outcome["err"] == []
            assert outcome["ok"] > 0
            assert _counter("serving.recompiles_total",
                            model="storm") - rec0 == 0
            # what answers after the storm is the last archive's weights
            assert np.array_equal(
                np.asarray(router.submit("storm", X2).result(timeout=20)),
                np.asarray(_dense_net(seed=5).output(X2)))
        finally:
            router.shutdown()


# ------------------------------------------------------------------ brownout
class TestBrownout:
    def test_lane_ordering_batch_sheds_interactive_serves(self):
        router, _net, _model, sched = _router_with("bo")
        try:
            router.set_brownout(("batch",))
            with pytest.raises(BrownoutShedError) as ei:
                router.submit("bo", X2, lane="batch")
            assert ei.value.http_status == 429
            out = np.asarray(
                router.submit("bo", X2, lane="interactive").result(
                    timeout=20))
            assert out.shape == (2, 4)
            assert sched.counts["shed_brownout"] >= 1
            router.set_brownout(())
            router.submit("bo", X2, lane="batch").result(timeout=20)
        finally:
            router.shutdown()

    def test_interactive_lane_refused_in_shed_set(self):
        router = ModelRouter(name="bo-guard")
        with pytest.raises(ValueError, match="interactive"):
            BrownoutController(router, shed_lanes=("interactive",))

    def test_slo_exhaustion_drives_brownout_and_recovery(self):
        from deeplearning4j_tpu.util import slo

        router, _net, _model, sched = _router_with("bo2")
        ctrl = BrownoutController(router).install()
        try:
            slo.register(slo.SloObjective(
                "bo2-avail", "availability", target=0.999,
                model="synthetic-bo2", windows=(5.0,)))
            tm.counter("serving.completed_total", 1, model="synthetic-bo2",
                       lane="interactive")
            slo.get_engine().evaluate()
            tm.counter("serving.shed_total", 9, model="synthetic-bo2",
                       reason="deadline", lane="interactive")
            slo.get_engine().evaluate()
            assert ctrl.active
            with pytest.raises(BrownoutShedError):
                router.submit("bo2", X2, lane="batch")
            router.submit("bo2", X2, lane="interactive").result(timeout=20)
            # budget recovery (bad traffic ages out of the 5s window): the
            # engine takes the time as an argument, so nobody sits it out
            engine = slo.get_engine()
            engine.evaluate(now=engine.clock() + 6.0)
            assert not ctrl.active
            router.submit("bo2", X2, lane="batch").result(timeout=20)
        finally:
            slo.reset()
            router.shutdown()


# ------------------------------------------------------------ slow batch
class TestSlowBatchFault:
    def test_deadline_sheds_behind_a_stalled_batch(self, wait_until):
        """serving_slow_batch wedges the worker on a real sleep; a request
        whose deadline expires while queued behind it is shed 429, not
        executed late — the contract holds under a wedged worker."""
        router, _net, _model, sched = _router_with("slow")
        try:
            get_injector().inject(fl.SERVING_SLOW_BATCH, arg=400.0)
            slow_fut = router.submit("slow", X2)  # eats the stall
            # the fault has fired: the worker is inside the stalled batch,
            # past the window in which a new request could still join it
            wait_until(lambda: any(kind == fl.SERVING_SLOW_BATCH
                                   for kind, _step in get_injector().log),
                       5, "the worker opened the stalled batch")
            doomed = router.submit("slow", X2, deadline_ms=100.0)
            from deeplearning4j_tpu.serving import DeadlineExceededError

            with pytest.raises(DeadlineExceededError):
                doomed.result(timeout=20)
            # the stalled batch itself completes fine (slow, not broken)
            assert np.asarray(slow_fut.result(timeout=20)).shape == (2, 4)
            assert sched.counts["shed_deadline"] >= 1
        finally:
            router.shutdown()


# ------------------------------------------------------ what HTTP answers
class TestResilienceOverHttp:
    """Each recovery above as the status line and headers a client gets:
    a rejected reload is a 409 and never a 5xx, a crashed batch a loud 500,
    an open breaker a 503 with Retry-After."""

    @pytest.fixture
    def served(self, request):
        """A server over one dense model whose id no other router of this
        process has used: scrape-time gauges are keyed by model id and a
        stopped router lives until it is collected."""
        from deeplearning4j_tpu.serving import ModelServer

        mid = f"http-{request.node.name.split('_')[1]}"
        router, net, model, sched = _router_with(mid)
        server = ModelServer(router, port=0).start()
        yield server, net, model, sched, mid
        server.stop()

    def test_rejected_reload_is_409_and_old_version_keeps_answering(
            self, served, tmp_path, http_json):
        server, net, model, _sched, mid = served
        infer = f"{server.url}/v1/models/{mid}/infer"
        reload_ = f"{server.url}/v1/models/{mid}/reload"
        good = _archive(tmp_path, "v2.zip", _dense_net(seed=1))
        data = open(good, "rb").read()
        bad = str(tmp_path / "trunc.zip")
        open(bad, "wb").write(data[: len(data) // 2])
        code, body, _h = http_json(reload_, {"path": bad})
        assert code == 409 and body["error"] == "ModelLoadError"
        code, body, _h = http_json(infer, {"inputs": X2.tolist()})
        assert code == 200 and model.version == 1
        assert np.array_equal(np.asarray(body["outputs"], np.float32),
                              np.asarray(net.output(X2)))
        # the injected fault truncates the READ of a good archive: rejected
        # once, then the same archive reloads clean
        get_injector().inject(fl.RELOAD_CORRUPT_ARCHIVE)
        assert http_json(reload_, {"path": good})[0] == 409
        code, body, _h = http_json(reload_, {"path": good})
        assert code == 200 and body["version"] == 2
        code, st, _h = http_json(f"{server.url}/v1/models")
        assert st["models"][mid]["version"] == 2

    def test_crashed_batch_is_a_loud_500_and_the_next_request_is_served(
            self, served, http_json):
        server, _net, _model, sched, mid = served
        infer = f"{server.url}/v1/models/{mid}/infer"
        get_injector().inject(fl.SERVING_WORKER_CRASH, count=1)
        code, body, _h = http_json(infer, {"inputs": X2.tolist()})
        assert code == 500 and "WorkerCrashedError" in str(body["error"])
        code, body, _h = http_json(infer, {"inputs": X2.tolist()})
        assert code == 200 and sched.stats()["worker_restarts"] == 1
        code, dump, _h = http_json(
            f"{server.url}/v1/models/{mid}/debug/requests")
        assert any(r["status"] == "error"
                   and str(r["cause"]).startswith("worker_crash")
                   for r in dump["requests"])

    def test_open_breaker_is_503_with_retry_after_until_a_probe_closes_it(
            self, served, wait_until, http_json):
        server, _net, _model, sched, mid = served
        infer = f"{server.url}/v1/models/{mid}/infer"
        sched.breaker.consecutive_errors = 2
        sched.breaker.cooldown_s = 0.3
        get_injector().inject(fl.SERVING_COMPUTE_ERROR, count=2)
        codes = [http_json(infer, {"inputs": X2.tolist()})[0]
                 for _ in range(2)]
        assert codes == [500, 500] and sched.breaker.state == "open"
        code, body, hdrs = http_json(infer, {"inputs": X2.tolist()})
        assert code == 503 and "CircuitOpenError" in str(body["error"])
        assert int(hdrs["Retry-After"]) >= 1
        _code, text, _h = http_json(f"{server.url}/metrics")
        assert 'dl4j_serving_breaker_state{model="%s"} 2' % mid in text
        wait_until(lambda: http_json(infer, {"inputs": X2.tolist()})[0] == 200,
                   5, "the half-open probe after the cooldown")
        wait_until(lambda: sched.breaker.state == "closed", 5,
                   "breaker closed by the probe's success")


# ------------------------------------------------- review-pass hardening
class TestReviewHardening:
    def test_crash_with_partially_resolved_batch_no_watchdog_death(self):
        """A crash AFTER _run_batch resolved some riders must not re-fail
        FINISHED futures — that raises inside the watchdog's own handler,
        killing it with _worker_dead never set (the exact hang the
        watchdog exists to prevent)."""
        net = _dense_net()
        model = ServingModel(net, "prt")
        sched = BatchScheduler(model, max_wait_ms=50.0)  # no worker
        try:
            f_done = sched.submit(X2)
            f_pend = sched.submit(X2)
            batch = [sched._queues["interactive"].popleft()
                     for _ in range(2)]
            batch[0].future.set_running_or_notify_cancel()
            batch[0].future.set_result("resolved-before-crash")
            with sched._cv:
                sched._current_batch = batch
            assert sched._on_worker_crash(RuntimeError("boom")) is True
            assert f_done.result(timeout=5) == "resolved-before-crash"
            with pytest.raises(WorkerCrashedError):
                f_pend.result(timeout=5)
        finally:
            sched.shutdown()

    def test_half_open_lost_probe_rearms_after_cooldown(self):
        """An admitted probe shed before any batch outcome (queue full,
        deadline) must not wedge the breaker half-open forever: one
        cooldown with no verdict re-arms the probes."""
        t = [0.0]
        br = CircuitBreaker(clock=lambda: t[0], model_id="t",
                            consecutive_errors=1, cooldown_s=5.0,
                            half_open_probes=1)
        br.record_error()
        t[0] = 6.0
        br.allow()  # the probe — then lost, no outcome ever recorded
        with pytest.raises(CircuitOpenError):
            br.allow()
        t[0] = 12.0  # a full cooldown with no verdict
        br.allow()   # fresh probe admitted instead of wedging forever
        br.record_success()
        assert br.state == "closed"

    def test_slo_reset_ends_active_brownout(self):
        """reset() drops exhausted objectives — the brownout hung off
        their breach must see the recovery, not stay shed forever with
        the hook list emptied under it."""
        from deeplearning4j_tpu.util import slo

        router, _net, _model, _sched = _router_with("rst")
        ctrl = BrownoutController(router).install()
        try:
            slo.register(slo.SloObjective(
                "rst-avail", "availability", target=0.999,
                model="synthetic-rst", windows=(5.0,)))
            tm.counter("serving.completed_total", 1, model="synthetic-rst",
                       lane="interactive")
            slo.get_engine().evaluate()
            tm.counter("serving.shed_total", 9, model="synthetic-rst",
                       reason="deadline", lane="interactive")
            slo.get_engine().evaluate()
            assert ctrl.active
            slo.reset()
            assert not ctrl.active
            router.submit("rst", X2, lane="batch").result(timeout=20)
        finally:
            slo.reset()
            router.shutdown()

    def test_uninstall_detaches_from_engine(self):
        from deeplearning4j_tpu.util import slo

        router, _net, _model, _sched = _router_with("uni")
        ctrl = BrownoutController(router).install()
        try:
            ctrl.uninstall()
            slo.register(slo.SloObjective(
                "uni-avail", "availability", target=0.999,
                model="synthetic-uni", windows=(5.0,)))
            tm.counter("serving.completed_total", 1, model="synthetic-uni",
                       lane="interactive")
            slo.get_engine().evaluate()
            tm.counter("serving.shed_total", 9, model="synthetic-uni",
                       reason="deadline", lane="interactive")
            slo.get_engine().evaluate()
            assert not ctrl.active  # detached: the breach no longer acts
            router.submit("uni", X2, lane="batch").result(timeout=20)
        finally:
            slo.reset()
            router.shutdown()

    def test_canary_does_not_consume_stepless_serving_fault(self, tmp_path):
        """A stepless armed serving_compute_error targets the live worker
        (batch cycles); the reload canary runs with _step=None and must
        neither fire it (good weights rejected) nor consume it (the
        worker's recovery never exercised)."""
        router, _net, model, _sched = _router_with("cf")
        try:
            path = _archive(tmp_path, "good.zip", _dense_net(seed=1))
            get_injector().inject(fl.SERVING_COMPUTE_ERROR, count=1)
            assert router.reload("cf", path) == 2  # canary untouched
            # the fault is still armed for its documented target
            with pytest.raises(RuntimeError, match="injected serving"):
                router.submit("cf", X2).result(timeout=20)
        finally:
            router.shutdown()


    def test_restart_budget_pays_back_after_healthy_run(self):
        """max_restarts bounds a crash LOOP, not lifetime crashes: after
        restart_reset_batches clean batches the spent budget resets, so a
        rare transient (one crash a day) never accumulates into a
        permanent 503."""
        router, _net, _model, sched = _router_with(
            "payback", max_restarts=1, restart_reset_batches=2)
        try:
            for round_ in range(3):  # 3 crashes, budget 1 — all survive
                get_injector().inject(fl.SERVING_WORKER_CRASH, count=1)
                with pytest.raises(WorkerCrashedError):
                    router.submit("payback", X2).result(timeout=20)
                assert sched._restarts == 1
                for _ in range(2):  # healthy run pays the budget back
                    router.submit("payback", X2).result(timeout=20)
                deadline = time.time() + 5
                while sched._restarts and time.time() < deadline:
                    time.sleep(0.02)
                assert sched._restarts == 0
            assert sched.stats()["worker_alive"]
        finally:
            router.shutdown()

    def test_breaker_ignores_client_shaped_errors(self):
        """A buggy client's malformed payloads (the server's HTTP 400
        family: ValueError/TypeError/KeyError) fail their own batch but
        must NOT feed the breaker — one bad client must not 503 a healthy
        model for everyone."""
        router, _net, model, sched = _router_with("cli")
        try:
            sched.breaker.consecutive_errors = 1
            real_execute = model.execute

            def bad_execute(payloads, **kw):
                raise ValueError("malformed payload")

            model.execute = bad_execute
            with pytest.raises(ValueError):
                router.submit("cli", X2).result(timeout=20)
            assert sched.breaker.state == "closed"

            def broken_execute(payloads, **kw):
                raise RuntimeError("model fault")

            model.execute = broken_execute  # a REAL model fault still trips
            with pytest.raises(RuntimeError):
                router.submit("cli", X2).result(timeout=20)
            assert sched.breaker.state == "open"
            model.execute = real_execute
        finally:
            router.shutdown()

    def test_injector_fast_path_flag(self):
        """fire() short-circuits without the global lock when nothing was
        ever armed — the serving tier calls it every batch cycle."""
        inj = get_injector()
        assert inj._armed_fast is False  # autouse fixture cleared it
        assert inj.fire(fl.SERVING_COMPUTE_ERROR, step=1) is None
        inj.inject(fl.SERVING_COMPUTE_ERROR)
        assert inj._armed_fast is True
        assert inj.fire(fl.SERVING_COMPUTE_ERROR, step=1) is not None
        inj.clear()
        assert inj._armed_fast is False

    def test_watch_untyped_error_is_loud_and_retried(self, tmp_path):
        """An UNTYPED reload failure (transient fs/warmup error) must not
        consume the publish signature: the poller counts it, records an
        anomaly, and retries the SAME publish on the next poll."""
        router, _net, model, _sched = _router_with("wtr")
        try:
            pub = str(tmp_path / "live.zip")
            ModelSerializer.write_model(_dense_net(seed=1), pub,
                                        save_updater=False)
            real_reload = router.reload
            fails = [1]

            def flaky_reload(model_id, path, **kw):
                if fails[0]:
                    fails[0] -= 1
                    raise RuntimeError("transient warmup failure")
                return real_reload(model_id, path, **kw)

            router.reload = flaky_reload
            errs0 = _counter("serving.watch_errors_total", model="wtr")
            router.watch("wtr", pub, interval_s=0.05)
            # the watcher starts from the CURRENT signature: touch the
            # file (atomic rewrite) so there is a new commit to reload
            ModelSerializer.write_model(_dense_net(seed=2), pub,
                                        save_updater=False)
            deadline = time.time() + 20
            while model.version == 1 and time.time() < deadline:
                time.sleep(0.05)
            assert model.version == 2  # retried past the transient error
            assert _counter("serving.watch_errors_total",
                            model="wtr") == errs0 + 1
        finally:
            router.shutdown()


# ----------------------------------------------------- train->serve seam
class TestPublishWatch:
    def test_commit_hook_fires_on_checkpoint(self, tmp_path):
        from deeplearning4j_tpu.util.checkpoint import ShardedCheckpointer

        net = _dense_net()
        ckpt = ShardedCheckpointer(str(tmp_path / "ck"), log_fn=None)
        seen = []
        ckpt.add_commit_hook(seen.append)
        ckpt.save(0, net, block=True)
        assert seen == [0]

    def test_background_publisher_same_step_latest_wins(self, tmp_path):
        """The training thread hands the publisher a HOST-array snapshot
        (device refs would be freed by the next step's donation — the
        checkpointer's _host_snapshot rule); the writer serializes it
        identically to write_model, back-to-back publishes collapse to
        the newest weights, and stop() ends the writer thread."""
        import threading

        import jax
        from deeplearning4j_tpu.parallel.elastic import _ArchivePublisher
        from deeplearning4j_tpu.util.model_serializer import ModelSerializer as MS

        net_a, net_b = _dense_net(seed=7), _dense_net(seed=8)
        snap_b = MS.snapshot(net_b)
        # host copy, not device refs: every leaf is a materialized ndarray
        assert all(isinstance(leaf, np.ndarray)
                   for leaf in jax.tree_util.tree_leaves(snap_b["params"]))
        path = str(tmp_path / "pub.zip")
        pub = _ArchivePublisher(path, log_fn=None)
        pub.publish(MS.snapshot(net_a), 1)
        pub.publish(snap_b, 2)  # latest wins
        assert pub.flush(timeout=30)
        restored = MS.restore_model(path, load_updater=False)
        for got, want in zip(jax.tree_util.tree_leaves(restored.params),
                             jax.tree_util.tree_leaves(net_b.params)):
            assert np.array_equal(np.asarray(got), np.asarray(want))
        pub.stop(timeout=30)
        assert not any(t.name == "elastic-publish"
                       for t in threading.enumerate())

    def test_atomic_archive_write_leaves_no_tmp(self, tmp_path):
        net = _dense_net()
        path = str(tmp_path / "m.zip")
        ModelSerializer.write_model(net, path, save_updater=False)
        assert os.path.exists(path)
        assert [n for n in os.listdir(tmp_path) if ".tmp-" in n] == []
        ModelSerializer.restore_model(path, load_updater=False)

    # slow, and failing at the seed (ROADMAP.md, Design 9): the tier-1 tests
    # of its two halves are test_commit_hook_fires_on_checkpoint and
    # test_background_publisher_same_step_latest_wins above, and
    # TestRollingReload.test_reload_swaps_weights_and_advances_version
    @pytest.mark.slow
    def test_elastic_publish_feeds_watching_router(self, tmp_path):
        """The continuous-deployment loop: ElasticTrainer publishes an
        archive at every checkpoint cadence; a watch()ing router reloads
        it under traffic; the served weights end up the TRAINED ones."""
        from deeplearning4j_tpu.data.iterators import ArrayDataSetIterator
        from deeplearning4j_tpu.parallel.elastic import ElasticTrainer

        rng = np.random.default_rng(0)
        xs = rng.normal(size=(16, 10)).astype(np.float32)
        ys = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 16)]
        it = ArrayDataSetIterator(xs, ys, batch=4)
        pub = str(tmp_path / "live.zip")

        train_net = _dense_net(seed=3)
        router, _net, model, _sched = _router_with("cd", seed=4)
        try:
            router.watch("cd", pub, interval_s=0.1)
            trainer = ElasticTrainer(
                train_net, str(tmp_path / "ck"), checkpoint_every=2,
                membership=None, rollback_on_anomaly=False,
                publish_archive=pub, log_fn=None)
            trainer.fit(it, epochs=2)
            assert _counter("elastic.publishes_total") >= 1
            # wait for the poller to settle on the FINAL publish
            deadline = time.time() + 20
            last = (model.version, time.time())
            while time.time() < deadline:
                v = model.version
                if v > 1 and v == last[0] and time.time() - last[1] > 0.6:
                    break
                if v != last[0]:
                    last = (v, time.time())
                time.sleep(0.05)
            assert model.version > 1
            out = np.asarray(router.submit("cd", xs[:2]).result(timeout=30))
            assert np.array_equal(out, np.asarray(train_net.output(xs[:2])))
            # a rejected publish is remembered, not retry-spun: corrupt the
            # archive in place and assert the version holds
            data = open(pub, "rb").read()
            open(pub, "wb").write(data[: len(data) // 2])
            v_now = model.version
            time.sleep(0.5)
            assert model.version == v_now
            rejected = _counter("serving.reload_rejected_total", model="cd",
                                reason="load_error")
            time.sleep(0.5)
            assert _counter("serving.reload_rejected_total", model="cd",
                            reason="load_error") == rejected  # no spin
        finally:
            router.shutdown()
