"""Request-scope serving observability (ISSUE 12, docs/OBSERVABILITY.md
#request-tracing--slos): request ids + phase spans on the shared trace
timebase, head-based sampling with the slow/shed/error always-keep, the
per-model flight recorder (+ crash-dump section), per-lane latency/shed
attribution, the SLO engine's burn-rate/budget math with the /healthz 503
flip, and a strict Prometheus text-format conformance check over the new
series (extending the r10 newline-escape regression)."""

import json
import os
import re
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.data.bucketing import BucketingPolicy
from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.serving import (DeadlineExceededError, ModelRouter,
                                        ModelServer, QueueFullError,
                                        ServingModel)
from deeplearning4j_tpu.serving.scheduler import (BatchScheduler,
                                                  FlightRecorder,
                                                  trace_sample_rate)
from deeplearning4j_tpu.util import slo
from deeplearning4j_tpu.util import telemetry as tm

R = np.random.default_rng(3)


@pytest.fixture(autouse=True)
def _clean_registry(monkeypatch):
    """Fresh, enabled registry per test; collectors saved/cleared/restored
    (the test_telemetry.py convention); SLO engine reset; full head
    sampling unless the test overrides DL4J_TPU_TRACE_SAMPLE itself."""
    tele = tm.get_telemetry()
    tele.reset()
    was = tele.enabled
    saved_collectors = list(tele._collectors)
    saved_flag = tm._defaults_installed
    tele._collectors.clear()
    tm._defaults_installed = False
    tele.enabled = True
    monkeypatch.setenv("DL4J_TPU_TRACE_SAMPLE", "1")
    slo.reset()
    yield tele
    slo.reset()
    tele.enabled = was
    tele._collectors[:] = saved_collectors
    tm._defaults_installed = saved_flag
    tele.reset()


def _dense_net(seed=0):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
            .batch_buckets((2, 4, 8)).list()
            .layer(DenseLayer(n_in=6, n_out=12, activation="relu"))
            .layer(OutputLayer(n_in=12, n_out=3, loss="mcxent",
                               activation="softmax"))
            .set_input_type(InputType.feed_forward(6)).build())
    return MultiLayerNetwork(conf).init()


@pytest.fixture(scope="module")
def dense_model():
    model = ServingModel(_dense_net(), "dense")
    model.warmup()
    return model


def _events(tele, name=None):
    tele._fold_pending()  # hot-path spans stage off-ring until an export
    evs = [dict(e) for e in tele._events]
    return [e for e in evs if name is None or e["name"] == name]


def _x(n=3):
    return R.normal(size=(n, 6)).astype(np.float32)


class TestRequestIdsAndPhaseSpans:
    def test_request_id_honored_and_phases_ordered(self, dense_model,
                                                   _clean_registry):
        sched = BatchScheduler(dense_model, max_wait_ms=1.0).start()
        fut = sched.submit(_x(), request_id="rid-explicit")
        fut.result(timeout=30)
        sched.drain(timeout=10)
        tele = _clean_registry
        qw = _events(tele, "serving.request.queue_wait")
        bf = _events(tele, "serving.request.batch_fill")
        cp = _events(tele, "serving.request.compute")
        assert qw and bf and cp
        for e in qw + bf + cp:
            assert e["args"]["request_id"] == "rid-explicit"
            assert e["args"]["model"] == "dense"
            assert e["args"]["lane"] == "interactive"
        # phases tile the request's life on ONE wall timebase:
        # queue_wait ends where batch_fill starts, which ends where
        # compute starts
        assert qw[0]["ts"] + qw[0]["dur"] == bf[0]["ts"]
        assert bf[0]["ts"] + bf[0]["dur"] == cp[0]["ts"]
        assert cp[0]["args"]["rows"] == 3
        assert cp[0]["args"]["bucket"] == 4  # 3 rows -> bucket 4

    def test_generated_id_unique_and_recorded(self, dense_model,
                                              _clean_registry):
        sched = BatchScheduler(dense_model, max_wait_ms=1.0).start()
        futs = [sched.submit(_x(1)) for _ in range(3)]
        for f in futs:
            f.result(timeout=30)
        sched.drain(timeout=10)
        ids = {r["id"] for r in sched.flight.dump()}
        assert len(ids) == 3 and all(len(i) == 12 for i in ids)

    def test_worker_thread_rows_and_nesting(self, dense_model,
                                            _clean_registry):
        """ISSUE 12 satellite, PR 39's phases: the worker's phase spans
        land on a model-id-named thread row in write_chrome_trace(), one
        of each a batch; serving.batch nests the execute phases and
        parents the request phase spans (extends the r10 one-timebase
        merge test)."""
        sched = BatchScheduler(dense_model, max_wait_ms=1.0).start()
        sched.submit(_x()).result(timeout=30)
        sched.drain(timeout=10)
        trace = _clean_registry.chrome_trace()
        evs = trace["traceEvents"]
        rows = {e["args"]["name"]: e["tid"] for e in evs
                if e.get("name") == "thread_name"}
        assert "serving-dense" in rows
        worker_tid = rows["serving-dense"]
        named = lambda n: [e for e in evs if e["name"] == n]
        batch = named("serving.batch")
        compute = named("serving.request.compute")
        # a classify batch has no decode to wait on: its forward and
        # fetch are one launch
        inner = ["serving.generate.prep", "serving.generate.launch",
                 "serving.generate.drain"]
        outer = ["serving.worker.fill", "serving.batch.respond"]
        assert len(batch) == 1 and compute
        for name in inner + outer:
            assert len(named(name)) == 1, name
        assert not named("serving.generate.wait")
        assert all(e["tid"] == worker_tid
                   for n in inner + outer + ["serving.batch",
                                             "serving.request.compute"]
                   for e in named(n))
        # nesting: execute phases under the batch, in order, inside it;
        # fill before it, respond after it, on the top level
        b0, b1 = batch[0]["ts"], batch[0]["ts"] + batch[0]["dur"]
        starts = [named(n)[0]["ts"] for n in inner]
        assert starts == sorted(starts)
        for n in inner:
            e = named(n)[0]
            assert e["args"]["parent"] == "serving.batch"
            assert b0 <= e["ts"] and e["ts"] + e["dur"] <= b1
        for n in outer:
            assert "parent" not in named(n)[0]["args"]
        assert named("serving.worker.fill")[0]["ts"] < b0
        assert named("serving.batch.respond")[0]["ts"] >= b1
        assert "parent" not in batch[0]["args"]
        assert compute[0]["args"]["parent"] == "serving.batch"
        # exported trace is Perfetto-loadable and relative-timed
        assert json.loads(json.dumps(trace))["traceEvents"]
        assert all(e["ts"] >= 0 for e in evs if e.get("ph") == "X")

    def test_exec_pad_and_device_spans(self, dense_model, _clean_registry):
        sched = BatchScheduler(dense_model, max_wait_ms=1.0).start()
        sched.submit(_x(3)).result(timeout=30)
        sched.drain(timeout=10)
        pad = _events(_clean_registry, "serving.exec.pad")
        dev = _events(_clean_registry, "serving.exec.device")
        assert pad and dev
        assert pad[0]["args"]["parent"] == "serving.batch"
        assert dev[0]["args"]["padded"] == 4


class TestDecodeTracing:
    @pytest.fixture(scope="class")
    def gen_model(self):
        from deeplearning4j_tpu.zoo.bert import Bert

        bert = Bert.tiny(causal=True, task="mlm", vocab_size=29,
                         max_length=16, hidden_dropout=0.0).init()
        model = ServingModel(bert, "dec", kind="generate",
                             bucketing=BucketingPolicy(batch_buckets=(1, 2),
                                                       seq_buckets=(8,)))
        model.warmup()
        return model

    def test_prefill_span_and_execute_phases(self, gen_model,
                                             _clean_registry):
        """The prefill span keeps its args and times the launch; the
        batch's four execute phases come once each, in order, under
        serving.batch (PR 39 retired the per-token enqueue span)."""
        sched = BatchScheduler(gen_model, max_wait_ms=1.0).start()
        toks = sched.submit(np.asarray([1, 2, 3], np.int32),
                            lane="batch", max_new_tokens=5).result(timeout=60)
        sched.drain(timeout=10)
        assert len(toks) == 5
        prefill = _events(_clean_registry, "serving.generate.prefill")
        assert len(prefill) == 1
        assert prefill[0]["args"]["prefix_hit"] is False
        assert prefill[0]["args"]["chunks"] == 1
        phases = [_events(_clean_registry, f"serving.generate.{n}")
                  for n in ("prep", "launch", "wait", "drain")]
        assert [len(p) for p in phases] == [1, 1, 1, 1]
        assert all(p[0]["args"]["parent"] == "serving.batch"
                   for p in phases)
        # consecutive: each starts where the one before ended
        for a, b in zip(phases, phases[1:]):
            assert a[0]["ts"] + a[0]["dur"] == b[0]["ts"]
        # the prefill is launched inside prep; launch starts once it is out
        assert prefill[0]["ts"] >= phases[0][0]["ts"]
        assert prefill[0]["ts"] + prefill[0]["dur"] >= phases[1][0]["ts"]

    def test_tokens_per_sec_per_request(self, gen_model, _clean_registry):
        sched = BatchScheduler(gen_model, max_wait_ms=1.0).start()
        sched.submit(np.asarray([4, 5], np.int32), lane="batch",
                     max_new_tokens=3).result(timeout=60)
        sched.drain(timeout=10)
        snap = _clean_registry.snapshot()
        key = "serving.decode_tokens_per_sec{lane=batch,model=dec}"
        assert snap["histograms"][key]["count"] == 1
        assert snap["histograms"][key]["max"] > 0
        rec = sched.flight.dump()[-1]
        assert rec["tokens_per_sec"] > 0


class TestSampling:
    def test_rate_zero_disables_all_request_tracing(self, dense_model,
                                                    _clean_registry,
                                                    monkeypatch):
        monkeypatch.setenv("DL4J_TPU_TRACE_SAMPLE", "0")
        sched = BatchScheduler(dense_model, max_wait_ms=1.0).start()
        sched.submit(_x()).result(timeout=30)
        shed = sched.submit(_x(), deadline_ms=-1)
        with pytest.raises(DeadlineExceededError):
            shed.result(timeout=30)
        sched.drain(timeout=10)
        assert not _events(_clean_registry, "serving.request.queue_wait")
        assert not _events(_clean_registry, "serving.request.compute")
        # the flight recorder is independent of sampling: both landed
        statuses = [r["status"] for r in sched.flight.dump()]
        assert sorted(statuses) == ["ok", "shed"]
        assert all(not r["traced"] for r in sched.flight.dump())

    def test_shed_always_kept_at_tiny_rate(self, dense_model,
                                           _clean_registry, monkeypatch):
        """Head sampling at a vanishing rate: a shed request's span is
        still emitted (slow/shed/error are always kept)."""
        monkeypatch.setenv("DL4J_TPU_TRACE_SAMPLE", "1e-9")
        sched = BatchScheduler(dense_model, max_wait_ms=1.0)
        fut = sched.submit(_x(), deadline_ms=-1, request_id="doomed")
        sched.start()
        with pytest.raises(DeadlineExceededError):
            fut.result(timeout=30)
        sched.drain(timeout=10)
        qw = _events(_clean_registry, "serving.request.queue_wait")
        assert [e["args"]["request_id"] for e in qw] == ["doomed"]
        assert qw[0]["args"]["outcome"] == "shed:deadline"

    def test_rate_parse_and_memoization(self, monkeypatch):
        monkeypatch.delenv("DL4J_TPU_TRACE_SAMPLE", raising=False)
        from deeplearning4j_tpu.serving.scheduler import DEFAULT_TRACE_SAMPLE

        assert trace_sample_rate() == DEFAULT_TRACE_SAMPLE
        monkeypatch.setenv("DL4J_TPU_TRACE_SAMPLE", "0.5")
        assert trace_sample_rate() == 0.5
        assert trace_sample_rate() == 0.5  # memoized path
        monkeypatch.setenv("DL4J_TPU_TRACE_SAMPLE", "7")   # clamped
        assert trace_sample_rate() == 1.0
        monkeypatch.setenv("DL4J_TPU_TRACE_SAMPLE", "junk")
        assert trace_sample_rate() == DEFAULT_TRACE_SAMPLE


class TestFlightRecorder:
    def test_ring_is_bounded(self):
        fr = FlightRecorder(capacity=4)
        for i in range(10):
            fr.record({"id": str(i)})
        assert len(fr) == 4
        assert [r["id"] for r in fr.dump()] == ["6", "7", "8", "9"]
        assert [r["id"] for r in fr.dump(last=2)] == ["8", "9"]

    def test_record_schema_and_phases(self, dense_model, _clean_registry):
        sched = BatchScheduler(dense_model, max_wait_ms=1.0,
                               flight_capacity=8).start()
        sched.submit(_x(3), request_id="schema").result(timeout=30)
        sched.drain(timeout=10)
        rec = sched.flight.dump()[-1]
        assert rec["id"] == "schema" and rec["status"] == "ok"
        assert rec["lane"] == "interactive" and rec["rows"] == 3
        assert rec["bucket"] == 4 and rec["cause"] is None
        for k in ("queue_ms", "fill_ms", "compute_ms", "total_ms"):
            assert rec[k] is not None and rec[k] >= 0
        assert rec["total_ms"] >= rec["compute_ms"]
        assert rec["sampled"] is True and rec["traced"] is True

    def test_error_requests_recorded_with_cause(self, dense_model,
                                                _clean_registry):
        sched = BatchScheduler(dense_model, max_wait_ms=1.0)
        fut = sched.submit(_x())
        # poison the batch: the model raises, the request records "error"
        orig = dense_model.execute
        dense_model.execute = lambda *a, **k: (_ for _ in ()).throw(
            RuntimeError("boom"))
        try:
            sched.start()
            with pytest.raises(RuntimeError, match="boom"):
                fut.result(timeout=30)
        finally:
            dense_model.execute = orig
        sched.drain(timeout=10)
        rec = sched.flight.dump()[-1]
        assert rec["status"] == "error" and "boom" in rec["cause"]
        snap = _clean_registry.snapshot()
        assert snap["counters"][
            "serving.request_errors_total{lane=interactive,model=dense}"] == 1

    def test_router_debug_and_crash_dump_section(self, dense_model,
                                                 _clean_registry, tmp_path):
        from deeplearning4j_tpu.serving import UnknownModelError
        from deeplearning4j_tpu.util import CrashReportingUtil

        router = ModelRouter(name="fr")
        router.register(dense_model, max_wait_ms=1.0)
        router.submit("dense", _x(), request_id="dumped").result(timeout=30)
        recs = router.debug_requests("dense", last=5)
        assert recs and recs[-1]["id"] == "dumped"
        with pytest.raises(UnknownModelError):
            router.debug_requests("ghost")
        # the crash dump carries the flight recorder (sys.modules-guarded)
        p = tmp_path / "crash.json"
        CrashReportingUtil.write_crash_dump(_dense_net(), str(p),
                                            RuntimeError("postmortem"))
        info = json.loads(p.read_text())
        flat = info["serving_flight_recorder"]["fr"]["dense"]
        assert any(r["id"] == "dumped" for r in flat)
        router.shutdown()


class TestPerLaneAttribution:
    def test_stats_split_by_lane_with_shed_causes(self, dense_model,
                                                  _clean_registry):
        sched = BatchScheduler(dense_model, max_wait_ms=1.0, queue_limit=2)
        ok = sched.submit(_x(), lane="interactive")
        doomed = sched.submit(_x(), lane="batch", deadline_ms=-1)
        with pytest.raises(QueueFullError):
            sched.submit(_x(), lane="batch")  # admission shed, batch lane
        sched.start()
        ok.result(timeout=30)
        with pytest.raises(DeadlineExceededError):
            doomed.result(timeout=30)
        sched.drain(timeout=10)
        st = sched.stats()
        assert st["lanes"]["interactive"]["completed"] == 1
        assert st["lanes"]["interactive"]["shed"] == {}
        assert st["lanes"]["interactive"]["latency_p99_ms"] > 0
        assert st["lanes"]["batch"]["completed"] == 0
        assert st["lanes"]["batch"]["shed"] == {"deadline": 1,
                                                "queue_full": 1}
        assert st["lanes"]["batch"]["latency_p99_ms"] is None
        # combined totals unchanged (back-compat)
        assert st["completed"] == 1
        assert st["shed"] == {"deadline": 1, "queue_full": 1}

    def test_lane_labeled_gauges_and_shed_counters(self, dense_model,
                                                   _clean_registry):
        sched = BatchScheduler(dense_model, max_wait_ms=1.0).start()
        sched.submit(_x(), lane="interactive").result(timeout=30)
        sched.submit(_x(), lane="batch").result(timeout=30)
        sched.drain(timeout=10)
        snap = _clean_registry.snapshot()
        g = snap["gauges"]
        assert "serving.latency_p99_seconds{lane=interactive,model=dense}" \
            in g
        assert "serving.latency_p99_seconds{lane=batch,model=dense}" in g
        assert "serving.latency_p99_seconds{model=dense}" in g  # combined
        assert snap["counters"][
            "serving.completed_total{lane=batch,model=dense}"] == 1

    def test_router_collect_metrics_per_lane(self, dense_model,
                                             _clean_registry):
        from deeplearning4j_tpu.serving.router import collect_metrics

        router = ModelRouter(name="lanes")
        router.register(dense_model, max_wait_ms=1.0)
        router.submit("dense", _x(), lane="interactive").result(timeout=30)
        rows = {(name, tuple(sorted(lab.items())))
                for name, lab, _v in collect_metrics()}
        assert ("serving.latency_p99_seconds",
                (("lane", "interactive"), ("model", "dense"))) in rows
        assert ("serving.flight_recorder_depth",
                (("model", "dense"),)) in rows
        router.shutdown()


class _FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


class TestSloEngine:
    def test_objective_validation(self):
        with pytest.raises(ValueError, match="unknown SLO kind"):
            slo.SloObjective("x", "p50", target=1.0)
        with pytest.raises(ValueError, match="availability target"):
            slo.SloObjective("x", "availability", target=1.5)
        with pytest.raises(ValueError, match="latency_p99 target"):
            slo.SloObjective("x", "latency_p99", target=-1)
        with pytest.raises(ValueError, match="window"):
            slo.SloObjective("x", "availability", target=0.99, windows=())

    def test_availability_burn_and_budget_math(self, _clean_registry):
        clock = _FakeClock()
        eng = slo.SloEngine(clock=clock)
        eng.register(slo.SloObjective(
            "avail", "availability", target=0.9, model="m1",
            windows=(10.0, 100.0)))
        # t=1000: baseline — 8 good, 0 bad
        tm.counter("serving.completed_total", 8, model="m1", lane="x")
        eng.evaluate()
        # t=1005: 2 shed arrive -> window bad fraction 2/2=1.0 over the
        # fresh traffic... plus 0 new good: burn = 1.0 / 0.1 = 10x
        clock.t += 5
        tm.counter("serving.shed_total", 2, model="m1", reason="deadline")
        doc = eng.evaluate()
        res = doc["objectives"][0]
        assert res["current"] == 0.8  # lifetime 8/(8+2)
        assert res["compliant"] is False
        w10 = res["windows"]["10s"]
        assert w10["bad"] == 2 and w10["good"] == 0
        assert w10["bad_fraction"] == 1.0
        assert w10["burn_rate"] == pytest.approx(10.0, rel=1e-3)
        assert res["budget_remaining"] < 0.0 or res["exhausted"]
        assert res["exhausted"] is True

    def test_window_baseline_is_last_sample_before_cutoff(
            self, _clean_registry):
        """Bad traffic recorded between the window start and the first
        in-window sample must still count: the baseline is the NEWEST
        sample at-or-before the cutoff, not the first one inside the
        window (which already has the bad events baked into its
        cumulative counters — the review-found early-age-out bug)."""
        clock = _FakeClock()
        eng = slo.SloEngine(clock=clock)
        eng.register(slo.SloObjective(
            "avail", "availability", target=0.9, model="mb",
            windows=(60.0,)))
        eng.evaluate()                          # t=1000: baseline (0, 0)
        clock.t += 50                           # events land at ~t=1005...
        tm.counter("serving.shed_total", 9, model="mb", reason="deadline")
        tm.counter("serving.completed_total", 1, model="mb", lane="x")
        res = eng.evaluate()["objectives"][0]   # ...sampled at t=1050
        assert res["exhausted"] is True
        clock.t += 12                           # t=1062: cutoff=1002 — the
        res = eng.evaluate()["objectives"][0]   # sheds are still in-window
        w = res["windows"]["60s"]
        assert w["bad"] == 9.0 and w["good"] == 1.0
        assert res["exhausted"] is True

    def test_burn_exactly_at_budget_is_not_exhausted(self, _clean_registry):
        """burn_rate == 1.0 is a service meeting its SLO to the decimal:
        it must NOT flip /healthz to 503 (strict < 0 on remaining)."""
        clock = _FakeClock()
        eng = slo.SloEngine(clock=clock)
        eng.register(slo.SloObjective(
            "edge", "latency_p99", target=100.0, model="me",
            budget=0.5, windows=(10.0,)))
        tm.gauge("serving.latency_p99_seconds", 0.050, model="me")
        eng.evaluate()                          # compliant sample
        tm.gauge("serving.latency_p99_seconds", 0.200, model="me")
        clock.t += 1
        res = eng.evaluate()["objectives"][0]   # 1 of 2 bad / budget 0.5
        assert res["windows"]["10s"]["burn_rate"] == 1.0
        assert res["budget_remaining"] == 0.0
        assert res["exhausted"] is False
        ok, checks = _clean_registry.health_report()
        assert checks.get("slo.edge", {}).get("ok") is not False

    def test_exhaustion_flips_health_fires_hooks_then_recovers(
            self, _clean_registry):
        clock = _FakeClock()
        eng = slo.SloEngine(clock=clock)
        eng.register(slo.SloObjective(
            "hooked", "availability", target=0.99, model="m2",
            windows=(10.0,)))
        breaches = []
        eng.on_breach(lambda name, detail: breaches.append((name, detail)))
        tm.counter("serving.completed_total", 1, model="m2", lane="x")
        eng.evaluate()
        clock.t += 1
        tm.counter("serving.shed_total", 5, model="m2", reason="queue_full")
        eng.evaluate()
        ok, checks = _clean_registry.health_report()
        assert not ok and checks["slo.hooked"]["ok"] is False
        assert "budget exhausted" in checks["slo.hooked"]["detail"]
        assert breaches and breaches[0][0] == "hooked"
        snap = _clean_registry.snapshot()
        assert snap["counters"][
            "slo.anomalies_total{type=budget_exhausted}"] == 1
        # the bad interval ages out of the window -> health recovers
        clock.t += 50
        tm.counter("serving.completed_total", 20, model="m2", lane="x")
        clock.t += 1
        eng.evaluate()
        clock.t += 9
        eng.evaluate()
        ok, checks = _clean_registry.health_report()
        assert checks["slo.hooked"]["ok"] is True
        assert _clean_registry.snapshot()["counters"][
            "slo.anomalies_total{type=budget_recovered}"] == 1
        assert len(breaches) == 1  # hook fires on the TRANSITION only

    def test_latency_objective_reads_worst_gauge(self, _clean_registry):
        clock = _FakeClock()
        eng = slo.SloEngine(clock=clock)
        eng.register(slo.SloObjective(
            "p99", "latency_p99", target=25.0, model="m3",
            windows=(10.0,), budget=0.5))
        tm.gauge("serving.latency_p99_seconds", 0.010, model="m3",
                 lane="interactive")
        doc = eng.evaluate()
        res = doc["objectives"][0]
        assert res["compliant"] is True and res["current"] == 10.0
        # a second, WORSE lane series: worst-case wins the filter
        tm.gauge("serving.latency_p99_seconds", 0.200, model="m3",
                 lane="batch")
        clock.t += 1
        res = eng.evaluate()["objectives"][0]
        assert res["current"] == 200.0 and res["compliant"] is False
        assert res["windows"]["10s"]["bad_fraction"] == 0.5  # 1 of 2 samples
        assert res["windows"]["10s"]["burn_rate"] == 1.0  # at budget

    def test_healthz_503_and_slo_section_via_http(self, _clean_registry,
                                                  monkeypatch):
        """The synthetic budget-exhausted case: /healthz flips to 503 on
        the SAME probe that sees the exhausted budget, and carries the slo
        section next to the serving one."""
        from deeplearning4j_tpu.util.ui_server import UIServer

        clock = _FakeClock()
        eng = slo.SloEngine(clock=clock)
        monkeypatch.setattr(slo, "_engine", eng)
        eng.register(slo.SloObjective(
            "synthetic", "availability", target=0.999, model="mz",
            windows=(10.0,)))
        tm.counter("serving.completed_total", 1, model="mz", lane="x")
        eng.evaluate()
        clock.t += 1
        tm.counter("serving.shed_total", 9, model="mz", reason="deadline")
        ui = UIServer(port=0)
        ui._start()
        try:
            base = f"http://127.0.0.1:{ui.port}"
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(base + "/healthz")
            assert exc.value.code == 503
            doc = json.loads(exc.value.read().decode())
            assert doc["checks"]["slo.synthetic"]["ok"] is False
            sec = {o["name"]: o for o in doc["slo"]["objectives"]}
            assert sec["synthetic"]["exhausted"] is True
            # /slo route serves the same evaluation document
            r = urllib.request.urlopen(base + "/slo")
            names = [o["name"]
                     for o in json.loads(r.read().decode())["objectives"]]
            assert names == ["synthetic"]
        finally:
            ui.stop()

    def test_scrape_gauges_on_metrics(self, _clean_registry):
        slo.register(slo.SloObjective("scraped", "availability",
                                      target=0.99, model="ms"))
        text = _clean_registry.prometheus_text()
        assert 'dl4j_slo_compliant{slo="scraped"}' in text
        assert 'dl4j_slo_burn_rate{slo="scraped",window="60s"}' in text
        assert 'dl4j_slo_error_budget_remaining{slo="scraped"}' in text

    def test_duplicate_and_reset(self, _clean_registry):
        slo.register(slo.SloObjective("dup", "availability", target=0.9))
        with pytest.raises(ValueError, match="already declared"):
            slo.register(slo.SloObjective("dup", "availability", target=0.9))
        slo.reset()
        slo.register(slo.SloObjective("dup", "availability", target=0.9))


# --------------------------------------------------------------------------
# Strict Prometheus text-format conformance (ISSUE 12 satellite): every
# line of prometheus_text() must parse under the exposition-format grammar,
# histograms must expose monotone cumulative _bucket{le=} + _sum + _count,
# and the new per-lane + SLO series ride along. Regression-protects the
# r10 newline-escape fix: an unescaped newline would fail the line parse.
# --------------------------------------------------------------------------
_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>.*)\})? "
    r"(?P<value>-?(?:[0-9.eE+-]+|inf|nan))$")
_LABEL_RE = re.compile(
    r'^(?P<key>[a-zA-Z_][a-zA-Z0-9_]*)="(?P<val>(?:[^"\\\n]|\\\\|\\"|\\n)*)"$')


def _parse_prometheus(text: str):
    """Strict text-format 0.0.4 parser: returns {series_name: [(labels,
    value)]}; raises AssertionError on any grammar violation."""
    series = {}
    typed = {}
    for lineno, line in enumerate(text.splitlines(), 1):
        if not line:
            continue
        if line.startswith("#"):
            m = re.match(r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) "
                         r"(counter|gauge|histogram|summary|untyped)$", line)
            assert m, f"line {lineno}: bad comment {line!r}"
            typed[m.group(1)] = m.group(2)
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"line {lineno}: unparsable sample {line!r}"
        labels = {}
        raw = m.group("labels")
        if raw is not None:
            assert raw, f"line {lineno}: empty label braces"
            # split on commas OUTSIDE quoted values
            parts = re.findall(r'[a-zA-Z_][a-zA-Z0-9_]*='
                               r'"(?:[^"\\]|\\.)*"', raw)
            assert ",".join(parts) == raw, \
                f"line {lineno}: malformed label block {raw!r}"
            for part in parts:
                lm = _LABEL_RE.match(part)
                assert lm, f"line {lineno}: bad label pair {part!r}"
                labels[lm.group("key")] = lm.group("val")
        float(m.group("value"))  # must be a valid float
        series.setdefault(m.group("name"), []).append(
            (labels, float(m.group("value"))))
    return series, typed


class TestPrometheusConformance:
    def _loaded_text(self, dense_model):
        # serving series (per-lane), an SLO objective, a histogram, and
        # the r10 regression payload (escaped newline in a label value)
        sched = BatchScheduler(dense_model, max_wait_ms=1.0).start()
        sched.submit(_x(), lane="interactive").result(timeout=30)
        sched.submit(_x(), lane="batch").result(timeout=30)
        try:
            sched.submit(_x(), lane="batch", deadline_ms=-1).result(
                timeout=30)
        except DeadlineExceededError:
            pass
        sched.drain(timeout=10)
        slo.register(slo.SloObjective("conf", "availability", target=0.99,
                                      model="dense"))
        tm.counter("esc.total", 1, note='say "hi"\nline two',
                   path="C:\\tmp")
        return tm.install_default_collectors().prometheus_text()

    def test_full_scrape_parses_strictly(self, dense_model,
                                         _clean_registry):
        text = self._loaded_text(dense_model)
        series, typed = _parse_prometheus(text)
        # the new per-lane + SLO series are present and well-typed
        lat = series["dl4j_serving_latency_p99_seconds"]
        lanes = {lab.get("lane") for lab, _v in lat}
        assert {"interactive", "batch", None} <= lanes
        shed = series["dl4j_serving_shed_total"]
        assert any(lab.get("reason") == "deadline"
                   and lab.get("lane") == "batch" for lab, _v in shed)
        assert typed["dl4j_slo_burn_rate"] == "gauge"
        assert any(lab == {"slo": "conf", "window": "3600s"}
                   for lab, _v in series["dl4j_slo_burn_rate"])
        assert series["dl4j_esc_total"][0][0]["note"] == 'say \\"hi\\"\\nline two'

    def test_histogram_series_conform(self, dense_model, _clean_registry):
        text = self._loaded_text(dense_model)
        series, typed = _parse_prometheus(text)
        base = "dl4j_serving_request_latency_seconds"
        assert typed[base] == "histogram"
        # group buckets by their non-le labels; each group must be
        # monotone cumulative, end at +Inf, and match _count
        groups = {}
        for lab, v in series[base + "_bucket"]:
            key = tuple(sorted((k, x) for k, x in lab.items() if k != "le"))
            groups.setdefault(key, []).append((lab["le"], v))
        counts = {tuple(sorted(lab.items())): v
                  for lab, v in series[base + "_count"]}
        sums = {tuple(sorted(lab.items())): v
                for lab, v in series[base + "_sum"]}
        assert groups and set(groups) == set(counts) == set(sums)
        for key, buckets in groups.items():
            assert buckets[-1][0] == "+Inf"
            vals = [v for _le, v in buckets]
            assert vals == sorted(vals), f"non-monotone buckets for {key}"
            assert vals[-1] == counts[key]
            les = [float(le) for le, _v in buckets[:-1]]
            assert les == sorted(les)
