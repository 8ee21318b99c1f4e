"""Unified telemetry + training-health monitors (ISSUE 4,
docs/OBSERVABILITY.md): registry semantics, span attribution + cross-process
merge, subsystem instrumentation, /metrics + /healthz endpoints, health
anomaly detection, and the telemetry-aware crash dump."""

import json
import os
import urllib.request

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.util import telemetry as tm
from deeplearning4j_tpu.util.health import TrainingHealthMonitor


@pytest.fixture(autouse=True)
def _clean_registry():
    """Each test sees a fresh, enabled registry and leaves it enabled.

    Collector wiring is saved/cleared/restored too: collectors survive
    ``reset()`` by design, so any EARLIER test file that installed the
    default collectors (e.g. the elastic suite asserting /metrics gauges)
    would otherwise leak scrape-time series into this file's snapshot
    assertions. Tests here that need the defaults re-install them (the
    module flag is reset alongside)."""
    tele = tm.get_telemetry()
    tele.reset()
    was = tele.enabled
    saved_collectors = list(tele._collectors)
    saved_flag = tm._defaults_installed
    tele._collectors.clear()
    tm._defaults_installed = False
    tele.enabled = True
    yield tele
    tele.enabled = was
    tele._collectors[:] = saved_collectors
    tm._defaults_installed = saved_flag
    tele.reset()


def _tiny_net(sync_every=1, seed=0):
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam

    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(0.01))
            .sync_every(sync_every).list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="relu"))
            .layer(OutputLayer(n_in=8, n_out=2, loss="mcxent",
                               activation="softmax"))
            .set_input_type(InputType.feed_forward(4)).build())
    return MultiLayerNetwork(conf).init()


def _batch(rng, n=16):
    x = rng.standard_normal((n, 4)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, n)]
    return x, y


class TestRegistry:
    def test_counters_gauges_histograms(self, _clean_registry):
        tele = _clean_registry
        tm.counter("a.total", 2)
        tm.counter("a.total", 3)
        tm.counter("a.total", 1, worker="0")
        tm.gauge("g.depth", 7)
        tm.observe("d.seconds", 0.02)
        tm.observe("d.seconds", 0.04)
        snap = tele.snapshot()
        assert snap["counters"]["a.total"] == 5
        assert snap["counters"]["a.total{worker=0}"] == 1
        assert snap["gauges"]["g.depth"] == 7
        h = snap["histograms"]["d.seconds"]
        assert h["count"] == 2 and abs(h["sum"] - 0.06) < 1e-9
        assert h["min"] == 0.02 and h["max"] == 0.04

    def test_disabled_records_nothing(self, _clean_registry):
        tele = _clean_registry
        tele.enabled = False
        tm.counter("x.total")
        tm.gauge("g", 1)
        tm.observe("h", 1.0)
        with tm.span("s"):
            pass
        tm.instant("i")
        tele.enabled = True
        snap = tele.snapshot()
        assert not snap["counters"] and not snap["gauges"]
        assert not tele.drain_events()

    def test_span_nesting_and_attribution(self, _clean_registry):
        tele = _clean_registry
        with tm.span("outer", kind="t"):
            with tm.span("inner"):
                pass
        events = tele.drain_events()
        by_name = {e["name"]: e for e in events}
        assert by_name["inner"]["args"]["parent"] == "outer"
        assert by_name["outer"]["pid"] == os.getpid()
        assert by_name["outer"]["tname"] == "MainThread"
        # inner completed first and sits inside outer's window
        assert by_name["inner"]["ts"] >= by_name["outer"]["ts"]

    def test_live_spans_reach_a_profile_a_forked_child_skips_them(
            self, _clean_registry, tmp_path, monkeypatch):
        """PR 39: span() and step_span() enter a TraceAnnotation of their
        name while a profile is taken, so they land on a host plane of the
        .xplane.pb; deferred spans do not; a forked child (mp-ETL) never
        calls into JAX, and its registry still records the spans."""
        import glob

        from jax.profiler import ProfileData

        def profiled(tag):
            jax.profiler.start_trace(str(tmp_path / tag))
            try:
                with tm.span("t.live"):
                    with tm.step_span("t.step"):
                        pass
                _clean_registry.event_deferred("t.deferred", 0, 1)
            finally:
                jax.profiler.stop_trace()
            path, = glob.glob(str(tmp_path / tag / "**" / "*.xplane.pb"),
                              recursive=True)
            return {e.name for p in ProfileData.from_file(path).planes
                    if p.name.startswith("/host:")
                    for ln in p.lines for e in ln.events}

        seen = profiled("parent")
        assert {"t.live", "t.step"} <= seen and "t.deferred" not in seen
        monkeypatch.setattr(tm, "_in_forked_child", True)
        assert not {"t.live", "t.step"} & profiled("child")
        names = [e["name"] for e in _clean_registry.drain_events()]
        assert names.count("t.live") == names.count("t.step") == 2

    def test_merge_events_keeps_foreign_pids(self, _clean_registry):
        tele = _clean_registry
        fake = [{"name": "etl.transform_chunk", "ph": "X", "pid": 99999,
                 "tid": 1, "tname": "MainThread", "ts": 123, "dur": 45}]
        assert tele.merge_events(fake) == 1
        trace = tele.chrome_trace()
        assert any(e["pid"] == 99999 and e["ph"] == "X"
                   for e in trace["traceEvents"])

    def test_chrome_trace_schema_and_metadata(self, _clean_registry):
        tele = _clean_registry
        with tm.span("work", n=1):
            pass
        tm.instant("marker")
        trace = tele.chrome_trace()
        events = trace["traceEvents"]
        assert any(e["ph"] == "M" and e["name"] == "process_name"
                   for e in events)
        assert any(e["ph"] == "M" and e["name"] == "thread_name"
                   for e in events)
        for e in events:
            assert isinstance(e["name"], str) and e["ph"] in ("X", "i", "M")
            if e["ph"] == "X":
                assert e["dur"] >= 0 and e["ts"] >= 0
        # round-trips through JSON (Perfetto-loadable)
        assert json.loads(json.dumps(trace))["traceEvents"]

    def test_event_ring_bounds_memory(self, _clean_registry, monkeypatch):
        tele = _clean_registry
        monkeypatch.setattr(tele, "max_events", 10)  # the registry is global
        for i in range(25):
            tele.event(f"e{i}", 0, 1)
        assert len(tele.drain_events()) == 10
        assert tele.snapshot()["counters"][
            "telemetry.events_dropped_total"] == 15

    def test_prometheus_text_format(self, _clean_registry):
        tm.counter("c.total", 3, model="mln")
        tm.gauge("g.val", 1.5)
        tm.observe("h.seconds", 0.2)
        tm.set_health("training.finite", True)
        text = _clean_registry.prometheus_text()
        assert "# TYPE dl4j_c_total counter" in text
        assert 'dl4j_c_total{model="mln"} 3' in text
        assert "dl4j_g_val 1.5" in text
        assert "# TYPE dl4j_h_seconds histogram" in text
        assert 'dl4j_h_seconds_bucket{le="+Inf"} 1' in text
        assert "dl4j_h_seconds_count 1" in text
        assert 'dl4j_health_check{check="training.finite"} 1' in text

    def test_prometheus_label_values_escaped(self, _clean_registry):
        """ISSUE 5 satellite: label values escape backslash, double quote,
        and newline per the exposition format — a raw newline in a value
        (e.g. a model description) would split the sample line and make
        the whole scrape unparsable."""
        tm.counter("esc.total", 1, path="C:\\tmp", note='say "hi"',
                   multi="line one\nline two")
        text = _clean_registry.prometheus_text()
        line = next(ln for ln in text.splitlines()
                    if ln.startswith("dl4j_esc_total"))
        assert 'path="C:\\\\tmp"' in line
        assert 'note="say \\"hi\\""' in line
        assert 'multi="line one\\nline two"' in line
        # the sample stayed ONE line ending in its value
        assert line.endswith(" 1")
        assert "line two" not in [ln.strip() for ln in text.splitlines()]

    def test_collectors_feed_scrapes(self, _clean_registry):
        tele = _clean_registry
        tele.register_collector(lambda: [("my.metric", {"k": "v"}, 42)])
        assert 'dl4j_my_metric{k="v"} 42' in tele.prometheus_text()
        assert tele.snapshot()["gauges"]["my.metric{k=v}"] == 42

    def test_broken_collector_never_breaks_scrape(self, _clean_registry):
        tele = _clean_registry

        def broken():
            raise RuntimeError("boom")

        tele.register_collector(broken)
        tm.counter("ok.total")
        assert "dl4j_ok_total" in tele.prometheus_text()


class TestInstrumentation:
    def test_fit_records_step_spans_and_counters(self, rng, _clean_registry):
        net = _tiny_net()
        x, y = _batch(rng)
        for _ in range(3):
            net._fit_batch(x, y)
        snap = _clean_registry.snapshot()
        assert snap["counters"]["train.steps_total{model=mln}"] == 3
        names = [e["name"] for e in _clean_registry.drain_events()]
        assert names.count("mln.train_step") == 3
        # first step retraced -> compile attribution sub-spans
        assert "xla.jaxpr_trace" in names
        assert snap["counters"]["xla.step_retraces_total"] >= 1
        assert snap["histograms"]["train.step_seconds{model=mln}"][
            "count"] == 2  # N-1 cadence intervals

    def test_cg_fit_records_spans(self, rng, _clean_registry):
        from deeplearning4j_tpu.nn import (InputType,
                                           NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.nn.updaters import Adam

        conf = (NeuralNetConfiguration.builder().seed(0)
                .updater(Adam(0.01))
                .graph_builder()
                .add_inputs("in")
                .add_layer("d", DenseLayer(n_in=4, n_out=8,
                                           activation="relu"), "in")
                .add_layer("out", OutputLayer(n_in=8, n_out=2), "d")
                .set_outputs("out")
                .set_input_types(InputType.feed_forward(4)).build())
        from deeplearning4j_tpu.nn.computation_graph import ComputationGraph

        net = ComputationGraph(conf).init()
        x, y = _batch(rng)
        net._fit_batch([x], [y])
        snap = _clean_registry.snapshot()
        assert snap["counters"]["train.steps_total{model=cg}"] == 1
        assert any(e["name"] == "cg.train_step"
                   for e in _clean_registry.drain_events())

    def test_disabled_fit_records_nothing(self, rng, _clean_registry):
        net = _tiny_net()
        x, y = _batch(rng)
        _clean_registry.enabled = False
        net._fit_batch(x, y)
        _clean_registry.enabled = True
        assert not _clean_registry.drain_events()
        assert not _clean_registry.snapshot()["counters"]

    def test_prefetch_gauges_and_thread_spans(self, rng, _clean_registry):
        from deeplearning4j_tpu.data import (ArrayDataSetIterator,
                                             AsyncDataSetIterator)

        x = rng.standard_normal((32, 4)).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 32)]
        it = AsyncDataSetIterator(
            ArrayDataSetIterator(x, y, batch=8), buffer_size=2)
        assert sum(1 for _ in it) == 4
        snap = _clean_registry.snapshot()
        assert snap["counters"]["prefetch.batches_total"] == 4
        assert "prefetch.queue_depth" in snap["gauges"]
        events = _clean_registry.drain_events()
        etl = [e for e in events if e["name"] == "prefetch.etl_wait"]
        assert etl and all(
            e["tname"] == "dl4j-tpu-prefetch" for e in etl)
        # prefetch thread rows are distinct from the main thread's
        main_tid = [e["tid"] for e in events
                    if e["tname"] == "MainThread"]
        assert all(e["tid"] not in main_tid for e in etl)

    @pytest.mark.skipif(not hasattr(os, "fork"), reason="needs fork")
    def test_mp_etl_worker_spans_merge_with_child_pids(self, _clean_registry):
        from deeplearning4j_tpu.datavec import (MultiProcessTransformExecutor,
                                                Schema, TransformProcess)

        sb = Schema.builder()
        sb.add_column_double("v")
        tp = (TransformProcess.builder(sb.build())
              .double_math_op("v", "multiply", 3.0).build())
        records = [[float(i)] for i in range(64)]
        ex = MultiProcessTransformExecutor(tp, num_workers=2,
                                           min_records_per_worker=8)
        out = ex.execute(records)
        assert out == [[i * 3.0] for i in range(64)]
        events = _clean_registry.drain_events()
        chunk_pids = {e["pid"] for e in events
                      if e["name"] == "etl.transform_chunk"}
        assert len(chunk_pids) == 2  # one per worker process
        assert os.getpid() not in chunk_pids
        assert any(e["name"] == "etl.execute"
                   and e["pid"] == os.getpid() for e in events)
        snap = _clean_registry.snapshot()
        assert snap["counters"]["etl.records_total"] == 64

    @pytest.mark.skipif(not hasattr(os, "fork"), reason="needs fork")
    def test_whole_input_pipeline_lands_in_one_trace_and_one_scrape(
            self, rng, _clean_registry, tmp_path):
        """Every instrumented layer in one two-step fit: forked ETL
        workers, the prefetch thread, a bucketed step with the health
        monitor and coalesced listener dispatch. One Chrome trace holds
        rows of three or more (pid, thread) pairs from two or more
        processes, every event well-formed; one scrape holds each layer's
        series; the stats records carry the telemetry group."""
        from deeplearning4j_tpu.data import AsyncDataSetIterator
        from deeplearning4j_tpu.datavec import (
            CollectionRecordReader, ParallelTransformRecordReader,
            RecordReaderDataSetIterator, Schema, TransformProcess)
        from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                           NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.nn.updaters import Adam
        from deeplearning4j_tpu.util import (InMemoryStatsStorage,
                                             StatsListener)

        records = [[float(v) for v in rng.normal(size=4)]
                   + [int(rng.integers(0, 3))] for _ in range(256)]
        sb = Schema.builder()
        sb.add_column_double(*[f"f{i}" for i in range(4)])
        sb.add_column_integer("label")
        tp = (TransformProcess.builder(sb.build())
              .double_math_op("f0", "multiply", 2.0).build())
        reader = ParallelTransformRecordReader(
            CollectionRecordReader(records), tp, num_workers=2)
        reader.executor.min_records_per_worker = 8  # fork on a tiny input
        it = RecordReaderDataSetIterator(reader, batch_size=128,
                                         label_index=4, num_classes=3)
        conf = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-2))
                .sync_every(2).batch_buckets((128,)).list()
                .layer(DenseLayer(n_in=4, n_out=16, activation="relu"))
                .layer(OutputLayer(n_in=16, n_out=3, loss="mcxent",
                                   activation="softmax"))
                .set_input_type(InputType.feed_forward(4)).build())
        net = MultiLayerNetwork(conf).init()
        storage = InMemoryStatsStorage()
        net.set_listeners(TrainingHealthMonitor(window=2, log_fn=None),
                          StatsListener(storage, collect_histograms=False))
        net.fit(AsyncDataSetIterator(it, buffer_size=2), epochs=1)
        assert net.iteration == 2
        assert storage.records and "telemetry" in storage.records[-1]

        text = tm.install_default_collectors().prometheus_text()
        for series in ("dl4j_xla_backend_compiles_total",
                       "dl4j_train_step_seconds_count",
                       "dl4j_prefetch_queue_depth", "dl4j_train_steps_total",
                       "dl4j_etl_chunks_total", "dl4j_health_loss_ewma"):
            assert series in text, series
        ok, checks = _clean_registry.health_report()
        assert ok and "training.finite" in checks

        with open(_clean_registry.write_chrome_trace(
                str(tmp_path / "trace.json"))) as f:
            events = json.load(f)["traceEvents"]
        for e in events:
            assert isinstance(e["name"], str) and e["ph"] in ("X", "i", "M")
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
            assert e["ph"] == "M" or isinstance(e["ts"], (int, float))
            assert e["ph"] != "X" or isinstance(e["dur"], (int, float))
        rows = {(e["pid"], e["tid"]) for e in events if e["ph"] == "X"}
        assert len(rows) >= 3 and len({p for p, _ in rows}) >= 2
        assert {"mln.train_step", "prefetch.etl_wait", "etl.transform_chunk",
                "listeners.flush"} <= {e["name"] for e in events}

    def test_parallel_wrapper_skew_probe(self, rng, _clean_registry):
        from deeplearning4j_tpu.data import ArrayDataSetIterator
        from deeplearning4j_tpu.parallel import ParallelWrapper, TrainingMesh

        net = _tiny_net()
        x = rng.standard_normal((16, 4)).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 16)]
        mesh = TrainingMesh(data=4, devices=jax.devices()[:4])
        pw = ParallelWrapper(net, mesh=mesh, skew_every=2)
        pw.fit(ArrayDataSetIterator(x, y, batch=16), epochs=2)
        snap = _clean_registry.snapshot()
        assert "parallel.straggler_skew_seconds" in snap["gauges"]
        assert snap["gauges"]["parallel.replicas"] == 4
        events = _clean_registry.drain_events()
        replica_rows = {e["tid"] for e in events
                        if e["name"] == "parallel.replica_step"}
        assert len(replica_rows) == 4
        assert any(e["name"] == "parallel.step" for e in events)

    def test_coalesced_flush_span_carries_window(self, rng, _clean_registry):
        net = _tiny_net(sync_every=4)
        net.set_listeners(_CountingListener())
        x, y = _batch(rng)
        for _ in range(4):
            net._fit_batch(x, y)
        events = _clean_registry.drain_events()
        flushes = [e for e in events if e["name"] == "listeners.flush"]
        assert len(flushes) == 1
        assert flushes[0]["args"]["window"] == 4
        assert any(e["name"] == "listeners.loss_fetch" for e in events)


class _CountingListener:
    def __init__(self):
        self.n = 0

    def iteration_done(self, model, iteration, epoch):
        self.n += 1


class TestEndpoints:
    def _server(self, storage=None):
        from deeplearning4j_tpu.util.ui_server import UIServer

        ui = UIServer(port=0)
        if storage is not None:
            ui.attach(storage)
        else:
            ui._start()
        return ui

    def test_metrics_endpoint_prometheus(self, _clean_registry):
        tm.counter("train.steps_total", 5, model="mln")
        tm.gauge("prefetch.queue_depth", 2)
        ui = self._server()
        try:
            r = urllib.request.urlopen(
                f"http://127.0.0.1:{ui.port}/metrics")
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
            assert 'dl4j_train_steps_total{model="mln"} 5' in text
            assert "dl4j_prefetch_queue_depth 2" in text
            # default collectors: compile counters always exported
            assert "dl4j_xla_backend_compiles_total" in text
        finally:
            ui.stop()

    def test_healthz_ok_and_unhealthy(self, _clean_registry):
        ui = self._server()
        try:
            base = f"http://127.0.0.1:{ui.port}"
            r = urllib.request.urlopen(base + "/healthz")
            assert r.status == 200
            doc = json.loads(r.read().decode())
            assert doc["status"] == "ok"
            assert doc["checks"]["devices"]["ok"]
            tm.set_health("training.finite", False, "nan at iteration 7")
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(base + "/healthz")
            assert exc.value.code == 503
            doc = json.loads(exc.value.read().decode())
            assert doc["status"] == "unhealthy"
            assert doc["checks"]["training.finite"]["detail"] \
                == "nan at iteration 7"
        finally:
            ui.stop()


class TestHealthMonitor:
    def test_healthy_run_sets_gauges_and_checks(self, rng, _clean_registry):
        net = _tiny_net(sync_every=4)
        mon = TrainingHealthMonitor(window=4, log_fn=None)
        net.set_listeners(mon)
        x, y = _batch(rng)
        for _ in range(8):
            net._fit_batch(x, y)
        net._dispatcher.flush()
        snap = _clean_registry.snapshot()
        assert snap["gauges"]["health.params_finite"] == 1
        assert snap["gauges"]["health.update_ratio"] > 0
        assert snap["health"]["training.finite"]["ok"]
        assert not mon.anomalies
        st = mon.state()
        assert st["iterations_seen"] == 8
        assert st["last_probe"][0] is True

    def test_non_finite_loss_flags_anomaly(self, _clean_registry):
        mon = TrainingHealthMonitor(window=100, log_fn=None)
        model = _FakeModel(float("nan"))
        mon.iteration_done(model, 1, 0)
        assert mon.anomalies and mon.anomalies[0][1] == "loss_non_finite"
        ok, checks = _clean_registry.health_report()
        assert not ok and not checks["training.finite"]["ok"]
        assert _clean_registry.snapshot()["counters"][
            "health.anomalies_total{type=loss_non_finite}"] == 1

    def test_panic_escalates(self, _clean_registry):
        from deeplearning4j_tpu.util.profiler import NaNPanicError

        mon = TrainingHealthMonitor(window=100, panic=True, log_fn=None)
        with pytest.raises(NaNPanicError, match="loss_non_finite"):
            mon.iteration_done(_FakeModel(float("inf")), 1, 0)

    def test_divergence_detection(self, _clean_registry):
        mon = TrainingHealthMonitor(window=10_000, warmup=5,
                                    divergence_factor=10.0,
                                    band_sigma=1e9,  # isolate divergence
                                    log_fn=None)
        model = _FakeModel(0.1)
        for i in range(1, 20):
            mon.iteration_done(model, i, 0)
        model.score_value = 1e6
        for i in range(20, 60):
            mon.iteration_done(model, i, 0)
        kinds = {k for _, k, _ in mon.anomalies}
        assert "divergence" in kinds
        ok, checks = _clean_registry.health_report()
        assert not checks["training.converging"]["ok"]

    def test_loss_band_anomaly(self, _clean_registry):
        mon = TrainingHealthMonitor(window=10_000, warmup=5, band_sigma=6.0,
                                    log_fn=None)
        model = _FakeModel(1.0)
        rng = np.random.default_rng(0)
        for i in range(1, 40):
            model.score_value = 1.0 + 0.01 * rng.standard_normal()
            mon.iteration_done(model, i, 0)
        assert not mon.anomalies
        model.score_value = 50.0  # far outside 6 sigma of the ~0.01 band
        mon.iteration_done(model, 40, 0)
        assert any(k == "loss_anomaly" for _, k, _ in mon.anomalies)

    def test_nan_params_sentinel(self, rng, _clean_registry):
        net = _tiny_net()
        mon = TrainingHealthMonitor(window=2, log_fn=None)
        net.set_listeners(mon)
        x, y = _batch(rng)
        net._fit_batch(x, y)
        net._fit_batch(x, y)  # window probe at iteration 2: healthy
        assert _clean_registry.snapshot()["gauges"][
            "health.params_finite"] == 1
        # poison one weight on device, then hit the next window boundary
        import jax.numpy as jnp

        net.params[0]["W"] = net.params[0]["W"].at[0, 0].set(jnp.nan)
        net._fit_batch(x, y)
        net._fit_batch(x, y)
        assert any(k == "params_non_finite"
                   for _, k, _ in mon.anomalies)
        assert _clean_registry.snapshot()["gauges"][
            "health.params_finite"] == 0

    def test_probe_survives_structure_change(self, rng, _clean_registry):
        net = _tiny_net()
        mon = TrainingHealthMonitor(window=1, log_fn=None)
        net.set_listeners(mon)
        x, y = _batch(rng)
        net._fit_batch(x, y)
        net2 = _tiny_net(seed=1)
        mon.iteration_done(net2, 1, 0)  # different params tree: no crash


class _FakeModel:
    """Listener-facing model stub (score + empty params)."""

    def __init__(self, score):
        self.score_value = score
        self.params = None
        self.conf = None


class TestEnvKnob:
    def test_env_disables_telemetry(self):
        import subprocess
        import sys

        code = (
            "import jax; jax.config.update('jax_platforms', 'cpu')\n"
            "from deeplearning4j_tpu.util import telemetry as tm\n"
            "assert not tm.enabled()\n"
            "tm.counter('x')\n"
            "with tm.span('s'): pass\n"
            "t = tm.get_telemetry()\n"
            "assert not t.snapshot()['counters'] and not t.drain_events()\n"
            "print('disabled-ok')\n"
        )
        env = dict(os.environ, DL4J_TPU_TELEMETRY="0", JAX_PLATFORMS="cpu")
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=300,
                             cwd=os.path.dirname(os.path.dirname(
                                 os.path.abspath(__file__))))
        assert "disabled-ok" in out.stdout, out.stderr
