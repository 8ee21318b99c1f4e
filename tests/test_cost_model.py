"""Cost attribution (util/cost_model.py, ISSUE 5): per-layer FLOPs / bytes /
device-time accounting extracted from the compiled executable, analytic
fallbacks, MFU reporting, and the reporting surfaces (/costs route,
StatsListener cost group, utilization gauges).

The load-bearing invariant: the per-layer table's FLOPs column (and, under
profiling, its device-time column) sums back to the whole-step compiled
totals within 5% — attribution must account for everything, with optimizer
and metadata-stripped ops in explicit (optimizer)/(untagged) rows rather
than silently dropped. And ``source: analytic`` rows appear EXACTLY when
XLA cost analysis is unavailable."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.computation_graph import GraphBuilder
from deeplearning4j_tpu.nn.layers import (ConvolutionLayer, DenseLayer,
                                          OutputLayer, SharedLayer,
                                          SubsamplingLayer)
from deeplearning4j_tpu.nn.recurrent import LSTM, RnnOutputLayer
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.util import cost_model as cm


def _conv_net():
    conf = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-3))
            .list()
            .layer(ConvolutionLayer(n_out=8, kernel_size=(5, 5),
                                    padding="VALID", activation="relu"))
            .layer(SubsamplingLayer(kernel_size=(2, 2)))
            .layer(DenseLayer(n_out=32, activation="relu"))
            .layer(OutputLayer(n_in=32, n_out=10))
            .set_input_type(InputType.convolutional(28, 28, 1)).build())
    return MultiLayerNetwork(conf).init()


def _lstm_net(T=12):
    conf = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-3))
            .list()
            .layer(LSTM(n_in=16, n_out=32))
            .layer(LSTM(n_in=32, n_out=32))
            .layer(RnnOutputLayer(n_in=32, n_out=16))
            .set_input_type(InputType.recurrent(16, T)).build())
    return MultiLayerNetwork(conf).init()


@pytest.fixture
def _clean_published():
    cm.clear_published()
    yield
    cm.clear_published()


class TestHloParser:
    def test_micro_program_reconciles_and_tags(self):
        """The per-instruction cost model reproduces the executable's own
        cost_analysis() total, and named scopes recover (layer, fwd|bwd)."""

        def loss(params, x):
            with cm.layer_scope("dense0"):
                h = jnp.tanh(x @ params["w0"])
            with cm.layer_scope("dense1"):
                h = h @ params["w1"]
            return (h ** 2).sum()

        params = {"w0": jnp.ones((16, 32)), "w1": jnp.ones((32, 4))}
        compiled = jax.jit(jax.value_and_grad(loss)).lower(
            params, jnp.ones((8, 16))).compile()
        totals = cm.compiled_totals(compiled)
        attrib = cm.attribute_hlo(cm.compiled_text(compiled))
        assert attrib.flops_total == pytest.approx(totals["flops"],
                                                   rel=0.05)
        # fwd dot of dense0: 2*8*16*32; its bwd row exists separately
        assert attrib.by_layer[("dense0", "fwd")]["flops"] >= 2 * 8 * 16 * 32
        assert ("dense0", "bwd") in attrib.by_layer
        assert ("dense1", "bwd") in attrib.by_layer
        # transcendentals (tanh) tracked separately, on the right layer
        assert attrib.by_layer[("dense0", "fwd")]["transcendentals"] > 0
        # instruction map exists for runtime grouping
        assert any(tag == "dense0" for tag, _ in attrib.inst_map.values())

    def test_memory_analysis_totals(self):
        compiled = jax.jit(lambda x: (x @ x).sum()).lower(
            jnp.ones((16, 16))).compile()
        totals = cm.compiled_totals(compiled)
        assert totals["argument_size_in_bytes"] >= 16 * 16 * 4
        assert "peak_bytes" in totals

    def test_sanitize_tag(self):
        assert cm.sanitize_tag("res2a/branch 1") == "res2a_branch_1"


class TestMlnCostReport:
    def test_conv_net_flops_sum_to_compiled_total(self):
        net = _conv_net()
        rep = net.cost_report(batch_size=8, publish=False)
        assert rep.source == "xla"
        attributed = sum(r.flops for r in rep.rows)
        assert attributed == pytest.approx(rep.totals["flops"], rel=0.05)
        # the conv forward dominates and is attributed to its own row
        conv = next(r for r in rep.rows if "ConvolutionLayer" in r.layer)
        assert conv.flops_fwd >= 2 * 8 * 24 * 24 * 25 * 8  # 2*B*OH*OW*K*Cout
        assert conv.params == 5 * 5 * 1 * 8 + 8
        # optimizer work is explicit, not hidden in a layer row
        assert any(r.layer == cm.OPTIMIZER_ROW and r.flops > 0
                   for r in rep.rows)
        assert all(r.source == "xla" for r in rep.rows)

    def test_lstm_net_flops_sum_to_compiled_total(self):
        """Acceptance: LSTM model (scan -> while loop in HLO) — the
        attribution still accounts for the whole step within 5%."""
        net = _lstm_net()
        rep = net.cost_report(batch_size=8, publish=False)
        assert rep.source == "xla"
        attributed = sum(r.flops for r in rep.rows)
        assert attributed == pytest.approx(rep.totals["flops"], rel=0.05)
        for tag in ("0_LSTM", "1_LSTM", "2_RnnOutputLayer"):
            row = next(r for r in rep.rows if r.layer == tag)
            assert row.flops > 0, tag

    def test_profile_device_time_columns_sum_to_total(self):
        """Acceptance: per-layer device-time columns reconcile against the
        whole-step device total (same XPlane grouping, independent sums)."""
        net = _conv_net()
        rep = net.cost_report(batch_size=8, profile=True, steps=2,
                              publish=False)
        assert rep.step_time_s and rep.step_time_s > 0
        assert rep.device_time_s and rep.device_time_s > 0
        row_sum = sum(r.device_time_s or 0.0 for r in rep.rows)
        assert row_sum == pytest.approx(rep.device_time_s, rel=0.05)
        # the model rows (not just (untagged)) actually got device time
        tagged = sum((r.device_time_s or 0.0) for r in rep.rows
                     if r.layer not in (cm.UNTAGGED_ROW, cm.OPTIMIZER_ROW))
        assert tagged > 0
        assert rep.examples_per_sec and rep.examples_per_sec > 0

    def test_profile_does_not_advance_model(self):
        """profile=True runs the compiled step on copies: iteration count,
        params, and RNG key of the live model must be untouched."""
        net = _conv_net()
        w_before = np.asarray(net.params[0]["W"]).copy()
        it_before = net.iteration
        key_before = np.asarray(net._rng_key).copy()
        net.cost_report(batch_size=4, profile=True, steps=1, publish=False)
        assert net.iteration == it_before
        assert np.array_equal(np.asarray(net.params[0]["W"]), w_before)
        assert np.array_equal(np.asarray(net._rng_key), key_before)

    def test_mfu_reported_exactly_when_peak_known(self, monkeypatch):
        net = _conv_net()
        rep = net.cost_report(batch_size=8, profile=True, steps=1,
                              peak_flops=1e12, publish=False)
        assert rep.mfu is not None and 0 < rep.mfu < 1
        assert rep.achieved_flops_per_sec == pytest.approx(
            rep.flops_per_step / rep.step_time_s)
        # no peak configured -> no MFU (no silent hardware guesses)
        monkeypatch.delenv("DL4J_TPU_PEAK_FLOPS", raising=False)
        rep2 = net.cost_report(batch_size=8, profile=True, steps=1,
                               publish=False)
        assert rep2.mfu is None
        # env knob path
        monkeypatch.setenv("DL4J_TPU_PEAK_FLOPS", "2.5e13")
        rep3 = net.cost_report(batch_size=8, publish=False)
        assert rep3.peak_flops == 2.5e13

    def test_analytic_rows_exactly_when_xla_unavailable(self, monkeypatch):
        """source=analytic appears on EVERY row when cost analysis is
        absent, and on NO row when it is present."""
        net = _conv_net()
        rep = net.cost_report(batch_size=8, publish=False)
        assert rep.source == "xla"
        assert not any(r.source == "analytic" for r in rep.rows)

        def unavailable(compiled):
            raise cm.CostAnalysisUnavailable("backend without cost model")

        monkeypatch.setattr(cm, "compiled_totals", unavailable)
        rep2 = net.cost_report(batch_size=8, publish=False)
        assert rep2.source == "analytic"
        assert rep2.rows and all(r.source == "analytic" for r in rep2.rows)
        # analytic conv formula: 2*B*OH*OW*KH*KW*Cin*Cout forward
        conv = next(r for r in rep2.rows if "ConvolutionLayer" in r.layer)
        assert conv.flops_fwd == pytest.approx(
            2 * 8 * 24 * 24 * 5 * 5 * 1 * 8)
        assert conv.flops_bwd == pytest.approx(2 * conv.flops_fwd)
        # the estimate lands in the right ballpark of the true total
        assert rep2.flops_per_step == pytest.approx(
            rep.totals["flops"], rel=0.5)

    def test_summary_and_json_round_trip(self):
        net = _conv_net()
        rep = net.cost_report(batch_size=4, publish=False)
        s = rep.summary()
        assert "MFLOP" in s or "GFLOP" in s or "KFLOP" in s
        assert "0_ConvolutionLayer" in s
        d = json.loads(rep.to_json())
        assert d["batch"] == 4
        assert d["layers"][0]["flops"] >= 0
        assert d["source"] == "xla"


class TestCgCostReport:
    def _graph(self, shared=False):
        # square dense so a SharedLayer can re-apply fc1's weights
        b = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-3))
             .graph_builder()
             .add_inputs("in"))
        b.add_layer("fc1", DenseLayer(n_in=32, n_out=32, activation="relu"),
                    "in")
        if shared:
            b.add_layer("fc_shared",
                        SharedLayer(source="fc1",
                                    layer=DenseLayer(n_in=32, n_out=32,
                                                     activation="relu")),
                        "fc1")
            last = "fc_shared"
        else:
            b.add_layer("fc2",
                        DenseLayer(n_in=32, n_out=32, activation="relu"),
                        "fc1")
            last = "fc2"
        b.add_layer("out", OutputLayer(n_in=32, n_out=10), last)
        b.set_outputs("out").set_input_types((32,))
        from deeplearning4j_tpu.nn.computation_graph import ComputationGraph

        return ComputationGraph(b.build()).init()

    def test_graph_flops_sum_to_compiled_total(self):
        net = self._graph()
        rep = net.cost_report(batch_size=8, publish=False)
        assert rep.source == "xla"
        attributed = sum(r.flops for r in rep.rows)
        assert attributed == pytest.approx(rep.totals["flops"], rel=0.05)
        for tag in ("fc1", "fc2", "out"):
            assert next(r for r in rep.rows if r.layer == tag).flops > 0

    def test_shared_weights_layer_appears_in_two_scopes(self):
        """A SharedLayer node computes under its OWN scope with the source
        node's params: two rows, each with real FLOPs, params only on the
        owner — and the column sum still reconciles."""
        net = self._graph(shared=True)
        rep = net.cost_report(batch_size=8, publish=False)
        fc1 = next(r for r in rep.rows if r.layer == "fc1")
        shared = next(r for r in rep.rows if r.layer == "fc_shared")
        assert fc1.flops_fwd > 0 and shared.flops_fwd > 0
        assert fc1.params == 32 * 32 + 32
        assert shared.params == 0  # the source row owns the weights
        # both call sites' backward work exists (grads accumulate into fc1)
        assert fc1.flops_bwd > 0 and shared.flops_bwd > 0
        attributed = sum(r.flops for r in rep.rows)
        assert attributed == pytest.approx(rep.totals["flops"], rel=0.05)

    def test_graph_profile_reconciles(self):
        net = self._graph()
        rep = net.cost_report(batch_size=8, profile=True, steps=2,
                              publish=False)
        row_sum = sum(r.device_time_s or 0.0 for r in rep.rows)
        assert rep.device_time_s and row_sum == pytest.approx(
            rep.device_time_s, rel=0.05)


# slow: half a minute to a minute by itself (ResNet-50 compiled and
# profiled), and failing at the seed with the reconciliation tests it
# repeats at size (ROADMAP.md, Design 9). The tier-1 tests of the same sums
# are TestCgCostReport.test_graph_flops_sum_to_compiled_total and
# test_graph_profile_reconciles above
@pytest.mark.slow
class TestFlagshipResNet50:
    def test_resnet50_flops_and_time_reconcile(self):
        """Acceptance: flagship zoo ResNet-50 (CPU-sized 32px, same graph
        topology as 224px) — per-layer FLOPs AND device-time columns each
        sum to within 5% of the whole-step compiled totals."""
        from deeplearning4j_tpu.zoo import ResNet50

        net = ResNet50(num_classes=16, input_shape=(32, 32, 3)).init()
        rep = net.cost_report(batch_size=4, profile=True, steps=1,
                              publish=False)
        assert rep.source == "xla"
        attributed = sum(r.flops for r in rep.rows)
        assert attributed == pytest.approx(rep.totals["flops"], rel=0.05)
        row_sum = sum(r.device_time_s or 0.0 for r in rep.rows)
        assert rep.device_time_s and row_sum == pytest.approx(
            rep.device_time_s, rel=0.05)
        # every conv stage shows up as its own row with real work
        named = {r.layer for r in rep.rows if r.flops > 0}
        assert any(t.startswith("res2a") for t in named)
        assert any(t.startswith("res5a") for t in named)


class TestSurfaces:
    def test_publish_and_costs_route(self, _clean_published):
        from deeplearning4j_tpu.util.ui_server import UIServer

        net = _conv_net()
        net.cost_report(batch_size=4, name="test_mln", peak_flops=1e12)
        assert "test_mln" in cm.published_reports()

        import urllib.request

        server = UIServer(port=0)
        server._start()
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}/costs") as r:
                body = json.loads(r.read())
            assert "test_mln" in body["reports"]
            rep = body["reports"]["test_mln"]
            assert rep["flops_per_step"] > 0
            assert len(rep["layers"]) >= 4
        finally:
            server.stop()

    def test_stats_listener_cost_group(self, _clean_published):
        from deeplearning4j_tpu.util.stats import (InMemoryStatsStorage,
                                                   StatsListener)

        net = _conv_net()
        net.cost_report(batch_size=4, name="cost_stats")
        storage = InMemoryStatsStorage()
        net.set_listeners(StatsListener(storage, collect_histograms=False))
        x = np.random.default_rng(0).normal(size=(4, 28, 28, 1)).astype(
            np.float32)
        y = np.eye(10, dtype=np.float32)[[0, 1, 2, 3]]
        net.fit(x, y)
        rec = storage.records[-1]
        assert "cost" in rec
        assert rec["cost"]["cost_stats"]["flops_per_step"] > 0
        assert rec["cost"]["cost_stats"]["source"] == "xla"

    def test_utilization_gauges_on_fit(self, _clean_published):
        from deeplearning4j_tpu.util import telemetry as tm

        tele = tm.get_telemetry()
        was = tele.enabled
        tele.enabled = True
        try:
            net = _conv_net()
            net.cost_report(batch_size=4, name="gauges",
                            peak_flops=1e12)
            x = np.random.default_rng(0).normal(
                size=(4, 28, 28, 1)).astype(np.float32)
            y = np.eye(10, dtype=np.float32)[[0, 1, 2, 3]]
            net.fit(x, y, epochs=3)  # >= 2 dispatches arm the cadence path
            gauges = tele.snapshot()["gauges"]
            eps = [v for k, v in gauges.items()
                   if k.startswith("train.examples_per_sec")
                   and "model=mln" in k]
            mfu = [v for k, v in gauges.items()
                   if k.startswith("train.model_flops_utilization")
                   and "model=mln" in k]
            assert eps and eps[0] > 0
            assert mfu and 0 < mfu[0] < 1
        finally:
            tele.enabled = was
