"""Profiler, NaN panic, stats storage, crash dump.

Reference test parity: OpProfiler/ProfilerConfig tests and StatsListener →
StatsStorage round-trips (SURVEY.md §5.1/5.5)."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.util import (
    CrashReportingUtil,
    FileStatsStorage,
    InMemoryStatsStorage,
    NaNPanicError,
    OpProfiler,
    ProfilerConfig,
    StatsListener,
    StepTimer,
    check_numerics,
    to_csv,
)


class TestOpProfiler:
    def test_records_op_timings(self):
        from deeplearning4j_tpu.ops import registry

        prof = OpProfiler(ProfilerConfig())
        x = jnp.ones((8, 8))
        with prof.profile():
            registry.exec_op("add", x, x)
            registry.exec_op("add", x, x)
            registry.exec_op("matmul", x, x)
        assert prof.invocations["add"] == 2
        assert prof.invocations["matmul"] == 1
        assert prof.total_ns["add"] > 0
        assert "add" in prof.summary()

    def test_hook_removed_after_stop(self):
        from deeplearning4j_tpu.ops import registry

        prof = OpProfiler(ProfilerConfig())
        with prof.profile():
            pass
        before = len(prof.events)
        registry.exec_op("add", jnp.ones(2), jnp.ones(2))
        assert len(prof.events) == before

    def test_chrome_trace_format(self, tmp_path):
        from deeplearning4j_tpu.ops import registry

        prof = OpProfiler(ProfilerConfig())
        with prof.profile():
            registry.exec_op("sum", jnp.ones((4,)))
        p = tmp_path / "trace.json"
        prof.write_chrome_trace(str(p))
        data = json.loads(p.read_text())
        assert data["traceEvents"][0]["ph"] == "X"
        assert data["traceEvents"][0]["name"] == "sum"

    def test_nan_panic(self):
        from deeplearning4j_tpu.ops import registry

        prof = OpProfiler(ProfilerConfig(check_for_nan=True))
        with prof.profile():
            with pytest.raises(NaNPanicError, match="log"):
                registry.exec_op("log", jnp.asarray([-1.0]))  # NaN

    def test_check_numerics(self):
        check_numerics({"w": jnp.ones(3)})
        with pytest.raises(NaNPanicError, match="w"):
            check_numerics({"w": jnp.asarray([1.0, np.nan])})

    def test_check_numerics_reports_nested_keypath(self):
        """ISSUE 4 satellite: the error names the offending LEAF's pytree
        key-path (tree_flatten_with_path), not just the enclosing label."""
        tree = {"layer0": {"W": jnp.ones((2, 2)), "b": jnp.zeros(2)},
                "layer1": [jnp.ones(3),
                           jnp.asarray([np.inf, 1.0, np.nan])]}
        with pytest.raises(NaNPanicError) as exc:
            check_numerics(tree, where="grads")
        msg = str(exc.value)
        assert "grads['layer1'][1]" in msg  # the exact leaf, not 'layer1'
        assert "nan=1" in msg and "inf=1" in msg
        assert "shape=(3,)" in msg
        assert "layer0" not in msg  # healthy leaves are not blamed

    def test_check_numerics_reports_every_bad_leaf(self):
        with pytest.raises(NaNPanicError) as exc:
            check_numerics({"a": jnp.asarray([np.nan]),
                            "z": jnp.asarray([np.inf])})
        assert "['a']" in str(exc.value) and "['z']" in str(exc.value)


class TestSummary:
    """ISSUE 4 satellite: _summary must be NaN-safe on degenerate arrays."""

    def test_empty_array_returns_nan_safe_summary(self):
        from deeplearning4j_tpu.util.stats import _summary

        s = _summary(np.zeros((0, 4), np.float32), bins=10)
        assert np.isnan(s["mean"]) and np.isnan(s["std"])
        assert np.isnan(s["min"]) and np.isnan(s["max"])
        assert s["l2"] == 0.0
        assert "hist" not in s  # no fabricated histogram for no data

    def test_nonfinite_values_do_not_break_histogram(self):
        from deeplearning4j_tpu.util.stats import _summary

        s = _summary(np.asarray([1.0, np.nan, 2.0, np.inf]), bins=4)
        assert sum(s["hist"]) == 2  # only the finite values binned
        assert s["hist_range"] == [1.0, 2.0]

    def test_all_nonfinite_skips_histogram(self):
        from deeplearning4j_tpu.util.stats import _summary

        s = _summary(np.asarray([np.nan, np.inf]), bins=4)
        assert "hist" not in s  # nothing finite to bin, and no crash

    def test_stats_listener_survives_empty_param_leaf(self, rng):
        """The regression that motivated the fix: a 0-sized leaf in the
        param tree must not crash iteration_done."""
        from deeplearning4j_tpu.util.stats import _summary

        flat = {"layer0.W": np.zeros((0,), np.float32)}
        out = {k: _summary(v, bins=8) for k, v in flat.items()}
        assert np.isnan(out["layer0.W"]["mean"])


class TestStats:
    def _train(self, listener, rng):
        from deeplearning4j_tpu.nn import (
            InputType, MultiLayerNetwork, NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.nn.updaters import Adam

        conf = (NeuralNetConfiguration.builder().seed(0).updater(Adam(0.01))
                .list()
                .layer(DenseLayer(n_in=4, n_out=8, activation="relu"))
                .layer(OutputLayer(n_in=8, n_out=2, loss="mcxent",
                                   activation="softmax"))
                .set_input_type(InputType.feed_forward(4)).build())
        net = MultiLayerNetwork(conf).init()
        net.listeners.append(listener)
        x = rng.standard_normal((16, 4)).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 16)]
        for _ in range(5):
            net._fit_batch(x, y)
        return net

    def test_stats_listener_memory(self, rng):
        storage = InMemoryStatsStorage()
        self._train(StatsListener(storage, frequency=1), rng)
        assert len(storage.records) == 5
        r = storage.records[-1]
        assert "layer0.W" in r["params"]
        assert {"mean", "std", "min", "max", "l2"} <= set(r["params"]["layer0.W"])
        assert "updates" in r
        assert len(storage.scores()) == 5

    def test_file_storage_roundtrip_and_csv(self, rng, tmp_path):
        p = tmp_path / "stats.jsonl"
        storage = FileStatsStorage(str(p))
        self._train(StatsListener(storage, frequency=2,
                                  collect_histograms=False), rng)
        reloaded = FileStatsStorage(str(p))
        assert len(reloaded.records) == len(storage.records) > 0
        csv = tmp_path / "curves.csv"
        to_csv(reloaded, str(csv))
        assert csv.read_text().startswith("session,iteration")

    def test_step_timer_trace(self, rng, tmp_path):
        timer = StepTimer()
        self._train(timer, rng)
        p = tmp_path / "steps.json"
        timer.write_chrome_trace(str(p))
        ev = json.loads(p.read_text())["traceEvents"]
        assert len(ev) == 4  # N-1 intervals
        assert all(e["dur"] > 0 for e in ev)

    def test_profiler_and_telemetry_traces_share_timebase(self, tmp_path):
        """ISSUE 5 satellite: OpProfiler.write_chrome_trace and
        Telemetry.write_chrome_trace subtract the SAME wall-clock origin
        (telemetry.trace_epoch_ns), so the two files load into one Perfetto
        view on one timeline — an op profiled INSIDE a telemetry span must
        land within that span's exported [ts, ts+dur] interval."""
        import jax.numpy as jnp

        from deeplearning4j_tpu.ops import registry
        from deeplearning4j_tpu.util import telemetry as tm
        from deeplearning4j_tpu.util.profiler import (OpProfiler,
                                                      ProfilerConfig)

        tele = tm.get_telemetry()
        tele.reset()
        was = tele.enabled
        tele.enabled = True
        prof = OpProfiler(ProfilerConfig())
        try:
            with prof.profile():
                with tm.span("outer.window"):
                    registry.exec_op("add", jnp.ones(128), jnp.ones(128))
        finally:
            tele.enabled = was
        p1 = tmp_path / "ops.json"
        p2 = tmp_path / "spans.json"
        prof.write_chrome_trace(str(p1))
        tele.write_chrome_trace(str(p2))
        tele.reset()
        op = json.loads(p1.read_text())["traceEvents"][0]
        spans = [e for e in json.loads(p2.read_text())["traceEvents"]
                 if e.get("name") == "outer.window"]
        assert spans, "telemetry span missing from its own trace"
        span = spans[0]
        # same timebase: the op interval nests inside the span interval
        # (small slack for the ns->µs rounding at export)
        assert span["ts"] - 1 <= op["ts"]
        assert op["ts"] + op["dur"] <= span["ts"] + span["dur"] + 1

    def test_crash_dump(self, rng, tmp_path):
        net = self._train(StepTimer(), rng)
        p = tmp_path / "crash.json"
        try:
            raise MemoryError("boom")
        except MemoryError as e:
            CrashReportingUtil.write_crash_dump(net, str(p), e)
        info = json.loads(p.read_text())
        assert info["exception"] == "MemoryError('boom')"
        assert info["param_bytes"]["layer0.W"] > 0
        assert info["config"] == ["DenseLayer", "OutputLayer"]

    def test_crash_dump_config_memory_telemetry(self, rng, tmp_path):
        """ISSUE 4 satellite: a simulated training failure's dump carries
        the full config JSON, memory stats, and the last-N telemetry
        counters/events that were in flight when it died."""
        from deeplearning4j_tpu.util import telemetry as tm

        tele = tm.get_telemetry()
        tele.reset()
        was = tele.enabled
        tele.enabled = True
        try:
            net = self._train(StepTimer(), rng)
            p = tmp_path / "crash2.json"
            try:  # simulate a mid-fit failure
                net._fit_batch(np.full((16, 4), np.nan, np.float32),
                               np.eye(2, dtype=np.float32)[[0] * 16])
                raise FloatingPointError("loss went non-finite")
            except FloatingPointError as e:
                CrashReportingUtil.write_crash_dump(net, str(p), e)
            info = json.loads(p.read_text())
            # config JSON reproduces the topology
            cfg = info["config_json"]
            assert cfg and "layers" in json.dumps(cfg)
            # memory stats: host view of param buffers always present;
            # device stats when the backend reports them (None on CPU)
            assert info["param_bytes"]["layer0.W"] > 0
            assert "device_memory_stats" in info and "hbm" in info
            # telemetry: the training counters + the last-N trace events
            tl = info["telemetry"]
            assert tl["counters"]["train.steps_total{model=mln}"] >= 6
            assert tl["histograms"]["train.step_seconds{model=mln}"][
                "count"] >= 1
            assert tl["recent_events"], "last-N trace events missing"
            assert any(e["name"] == "mln.train_step"
                       for e in tl["recent_events"])
        finally:
            tele.enabled = was
            tele.reset()


class TestXplaneReaders:
    """util/profiler.py's readers stand on JAX's ProfileData, the reader
    the benchmark's chipbench/trace.py uses (PR 39 took out the
    hand-written protobuf parser)."""

    SMALL = "chipbench/testdata/small.xplane.pb"

    def _logdir(self, tmp_path):
        import os
        import shutil

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        shutil.copy(os.path.join(root, self.SMALL), tmp_path)
        return str(tmp_path), os.path.join(root, self.SMALL)

    def test_device_ms_is_the_benchmarks_op_time(self, tmp_path):
        from chipbench import trace
        from deeplearning4j_tpu.util.profiler import xplane_device_ms

        logdir, path = self._logdir(tmp_path)
        # without the host planes no cb:window clips: every op counts whole
        planes = [p for p in trace.read_planes(path)
                  if not p["name"].startswith("/host:")]
        op_s = trace.reduce_trace(planes)["op_s"]
        ms, by_name = xplane_device_ms(logdir, by_name=True)
        assert ms > 0
        assert ms == pytest.approx(sum(op_s.values()) * 1e3, rel=1e-9)
        assert sum(by_name.values()) == pytest.approx(ms, rel=1e-9)

    def test_mapped_ms_counts_the_outermost_of_nested_events(self, tmp_path):
        from chipbench import trace
        from deeplearning4j_tpu.util.profiler import xplane_mapped_ms

        logdir, path = self._logdir(tmp_path)
        # the benchmark's cb:step spans nest in its cb:window span on one
        # host line: one key for both counts the window alone
        got = xplane_mapped_ms(logdir, lambda n: "cb" if n.startswith("cb:")
                               else None)
        window_s = trace.reduce_trace(trace.read_planes(path))["window_s"]
        assert got == {"cb": pytest.approx(window_s * 1e3, rel=1e-9)}
