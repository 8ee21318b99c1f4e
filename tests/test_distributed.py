"""Distributed training: compression ops, accumulator, training masters.

Reference test parity: the Spark-master tests run on local[N] in-process and
parameter-server tests on embedded loopback transport (SURVEY.md §4,
"distributed without a cluster") — here the 8-virtual-device CPU mesh plays
that role.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import compression as C
from deeplearning4j_tpu.parallel import (
    AdaptiveThresholdAlgorithm,
    EncodedGradientsAccumulator,
    FixedThresholdAlgorithm,
    ParameterAveragingTrainingMaster,
    ResidualClippingPostProcessor,
    SharedTrainingMaster,
    SparkDl4jMultiLayer,
    TrainingMesh,
    distributed,
)


class TestCompressionOps:
    def test_threshold_roundtrip_with_residual(self, rng):
        g = jnp.asarray(rng.normal(size=(64,)) * 0.01, jnp.float32)
        q, r = C.threshold_encode(g, 1e-2)
        np.testing.assert_allclose(q + r, g, atol=1e-7)
        assert set(np.unique(np.abs(np.asarray(q)))) <= {0.0, np.float32(1e-2)}

    def test_bitmap_roundtrip(self, rng):
        g = jnp.asarray(rng.normal(size=(50,)) * 0.01, jnp.float32)
        packed, residual = C.bitmap_encode(g, 1e-2)
        dec = C.bitmap_decode(packed, 1e-2, (50,))
        np.testing.assert_allclose(dec + residual, g, atol=1e-7)

    def test_sparse_pack_unpack(self, rng):
        g = jnp.asarray(rng.normal(size=(40,)) * 0.01, jnp.float32)
        q, _ = C.threshold_encode(g, 1e-2)
        msg = C.sparse_pack(np.asarray(q), 1e-2)
        back = C.sparse_unpack(msg, 1e-2, (40,))
        np.testing.assert_allclose(back, q, atol=1e-7)
        assert msg.size == int((np.asarray(q) != 0).sum())


class TestAccumulator:
    def test_error_feedback_preserves_signal(self, rng):
        acc = EncodedGradientsAccumulator(
            threshold_algorithm=FixedThresholdAlgorithm(1e-2),
            residual_post_processor=None)
        g = {"w": jnp.asarray(rng.normal(size=(32,)) * 0.005, jnp.float32)}
        residual = acc.init_residual(g)
        t = acc.threshold_algorithm.init_state()
        total = jnp.zeros((32,))
        for it in range(50):
            quant, residual, t, _ = acc.encode(g, residual, t, it)
            total = total + quant["w"]
        # over many steps the transmitted sum approaches the true sum (error
        # feedback: nothing is lost, only delayed)
        np.testing.assert_allclose(total / 50, g["w"], atol=1.2e-2)

    def test_adaptive_threshold_moves_toward_target(self):
        algo = AdaptiveThresholdAlgorithm(initial=1e-3, target_ratio=0.1)
        t = algo.init_state()
        t_dense = algo.update(t, jnp.asarray(0.9))   # too dense → raise t
        t_sparse = algo.update(t, jnp.asarray(0.001))  # too sparse → lower t
        assert float(t_dense) > float(t) > float(t_sparse)

    def test_residual_clipping(self):
        pp = ResidualClippingPostProcessor(max_multiplier=2.0, frequency=1)
        r = {"w": jnp.asarray([10.0, -10.0, 0.5])}
        out = pp.apply(r, jnp.asarray(1.0), jnp.asarray(0))
        np.testing.assert_allclose(out["w"], [2.0, -2.0, 0.5])


def _classifier_and_data(rng, n=256):
    from deeplearning4j_tpu.data import ArrayDataSetIterator
    from deeplearning4j_tpu.nn import (
        InputType,
        MultiLayerNetwork,
        NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam

    conf = (
        NeuralNetConfiguration.builder()
        .seed(7)
        .updater(Adam(0.01))
        .list()
        .layer(DenseLayer(n_in=4, n_out=16, activation="relu"))
        .layer(OutputLayer(n_in=16, n_out=3, loss="mcxent", activation="softmax"))
        .set_input_type(InputType.feed_forward(4))
        .build()
    )
    net = MultiLayerNetwork(conf).init()
    centers = rng.standard_normal((3, 4)) * 3.0
    ys = rng.integers(0, 3, n)
    xs = (centers[ys] + rng.standard_normal((n, 4))).astype(np.float32)
    yoh = np.eye(3, dtype=np.float32)[ys]
    return net, ArrayDataSetIterator(xs, yoh, batch=64), xs, yoh


@pytest.mark.multichip
class TestTrainingMasters:
    def test_parameter_averaging_learns(self, rng):
        net, it, xs, ys = _classifier_and_data(rng)
        master = ParameterAveragingTrainingMaster(
            averaging_frequency=2, mesh=TrainingMesh(data=8))
        s0 = net.score(x=xs, y=ys)
        SparkDl4jMultiLayer(None, net, master).fit(it, epochs=12)
        assert net.score(x=xs, y=ys) < s0 * 0.5
        acc = (np.argmax(net.output(xs), 1) == np.argmax(ys, 1)).mean()
        assert acc > 0.85, acc

    def test_shared_training_learns(self, rng):
        net, it, xs, ys = _classifier_and_data(rng)
        master = SharedTrainingMaster(threshold=1e-3, mesh=TrainingMesh(data=8))
        s0 = net.score(x=xs, y=ys)
        SparkDl4jMultiLayer(None, net, master).fit(it, epochs=12)
        assert net.score(x=xs, y=ys) < s0 * 0.5
        acc = (np.argmax(net.output(xs), 1) == np.argmax(ys, 1)).mean()
        assert acc > 0.85, acc

    def test_shared_training_matches_dense_direction(self, rng):
        # with a huge threshold nothing transmits on step 1 → params unchanged
        net, it, xs, ys = _classifier_and_data(rng)
        master = SharedTrainingMaster(
            threshold=1e3, mesh=TrainingMesh(data=8),
            accumulator=EncodedGradientsAccumulator(
                threshold_algorithm=FixedThresholdAlgorithm(1e3),
                residual_post_processor=None))
        p0 = np.asarray(net.params[0]["W"]).copy()
        from deeplearning4j_tpu.data import ArrayDataSetIterator
        one = ArrayDataSetIterator(xs[:64], ys[:64], batch=64)
        master.fit(net, one, epochs=1)
        np.testing.assert_allclose(np.asarray(net.params[0]["W"]), p0, atol=1e-7)


def _graph_classifier_and_data(rng, n=256):
    from deeplearning4j_tpu.data import ArrayDataSetIterator
    from deeplearning4j_tpu.nn import (
        ComputationGraph,
        InputType,
        NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam

    conf = (
        NeuralNetConfiguration.builder()
        .seed(7)
        .updater(Adam(0.01))
        .graph_builder()
        .add_inputs("in")
        .add_layer("d1", DenseLayer(n_in=4, n_out=16, activation="relu"), "in")
        .add_layer("out", OutputLayer(n_in=16, n_out=3, loss="mcxent",
                                      activation="softmax"), "d1")
        .set_outputs("out")
        .set_input_types(InputType.feed_forward(4))
        .build()
    )
    net = ComputationGraph(conf).init()
    centers = rng.standard_normal((3, 4)) * 3.0
    ys = rng.integers(0, 3, n)
    xs = (centers[ys] + rng.standard_normal((n, 4))).astype(np.float32)
    yoh = np.eye(3, dtype=np.float32)[ys]
    return net, ArrayDataSetIterator(xs, yoh, batch=64), xs, yoh


@pytest.mark.multichip
class TestTrainingMastersComputationGraph:
    """SparkComputationGraph parity: both masters drive a ComputationGraph."""

    def test_parameter_averaging_graph_then_local_fit(self, rng):
        from deeplearning4j_tpu.data import DataSet
        from deeplearning4j_tpu.parallel import SparkComputationGraph

        net, it, xs, ys = _graph_classifier_and_data(rng)
        master = ParameterAveragingTrainingMaster(
            averaging_frequency=2, mesh=TrainingMesh(data=8))
        s0 = net.score(DataSet(xs, ys))
        SparkComputationGraph(None, net, master).fit(it, epochs=12)
        assert net.score(DataSet(xs, ys)) < s0 * 0.5
        acc = (np.argmax(net.output(xs), 1) == np.argmax(ys, 1)).mean()
        assert acc > 0.85, acc
        # regression: master clears _train_step; local fit must lazily re-jit
        net.fit(xs[:64], ys[:64])

    def test_shared_training_graph_learns(self, rng):
        from deeplearning4j_tpu.data import DataSet
        from deeplearning4j_tpu.parallel import SparkComputationGraph

        net, it, xs, ys = _graph_classifier_and_data(rng)
        master = SharedTrainingMaster(threshold=1e-3, mesh=TrainingMesh(data=8))
        s0 = net.score(DataSet(xs, ys))
        SparkComputationGraph(None, net, master).fit(it, epochs=12)
        assert net.score(DataSet(xs, ys)) < s0 * 0.5
        acc = (np.argmax(net.output(xs), 1) == np.argmax(ys, 1)).mean()
        assert acc > 0.85, acc


def _multi_io_graph_and_data(rng, n=256):
    """2-input/2-output CG: each head is predictable from its own input
    (SharedTrainingWrapper.java wraps arbitrary graphs — VERDICT r2 #3)."""
    from deeplearning4j_tpu.data import MultiDataSet
    from deeplearning4j_tpu.nn import (
        ComputationGraph,
        InputType,
        NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.nn.vertices import MergeVertex

    conf = (
        NeuralNetConfiguration.builder().seed(5).updater(Adam(0.01))
        .graph_builder()
        .add_inputs("ina", "inb")
        .add_layer("da", DenseLayer(n_in=4, n_out=12, activation="relu"), "ina")
        .add_layer("db", DenseLayer(n_in=3, n_out=12, activation="relu"), "inb")
        .add_vertex("m", MergeVertex(), "da", "db")
        .add_layer("out1", OutputLayer(n_in=24, n_out=2, loss="mcxent",
                                       activation="softmax"), "m")
        .add_layer("out2", OutputLayer(n_in=24, n_out=3, loss="mcxent",
                                       activation="softmax"), "m")
        .set_outputs("out1", "out2")
        .set_input_types(InputType.feed_forward(4), InputType.feed_forward(3))
        .build()
    )
    net = ComputationGraph(conf).init()
    ca = rng.standard_normal((2, 4)) * 3.0
    cb = rng.standard_normal((3, 3)) * 3.0
    la = rng.integers(0, 2, n)
    lb = rng.integers(0, 3, n)
    xa = (ca[la] + rng.standard_normal((n, 4))).astype(np.float32)
    xb = (cb[lb] + rng.standard_normal((n, 3))).astype(np.float32)
    y1 = np.eye(2, dtype=np.float32)[la]
    y2 = np.eye(3, dtype=np.float32)[lb]
    batches = [
        MultiDataSet(features=[xa[i:i + 64], xb[i:i + 64]],
                     labels=[y1[i:i + 64], y2[i:i + 64]])
        for i in range(0, n, 64)
    ]
    return net, batches, (xa, xb), (y1, y2)


@pytest.mark.multichip
class TestTrainingMastersMultiInOut:
    """Multi-input/multi-output ComputationGraphs under both masters
    (VERDICT r2 next-round #3)."""

    def _assert_learned(self, net, xs, ys):
        o1, o2 = net.output(*xs)
        acc1 = (np.argmax(np.asarray(o1), 1) == np.argmax(ys[0], 1)).mean()
        acc2 = (np.argmax(np.asarray(o2), 1) == np.argmax(ys[1], 1)).mean()
        assert acc1 > 0.85, acc1
        assert acc2 > 0.85, acc2

    def test_shared_training_multi_io(self, rng):
        net, batches, xs, ys = _multi_io_graph_and_data(rng)
        master = SharedTrainingMaster(threshold=1e-3,
                                      mesh=TrainingMesh(data=8))
        s0 = net.score(x=list(xs), y=list(ys))
        master.fit(net, batches, epochs=12)
        assert net.score(x=list(xs), y=list(ys)) < s0 * 0.5
        self._assert_learned(net, xs, ys)

    def test_parameter_averaging_multi_io(self, rng):
        net, batches, xs, ys = _multi_io_graph_and_data(rng)
        master = ParameterAveragingTrainingMaster(
            averaging_frequency=2, mesh=TrainingMesh(data=8))
        s0 = net.score(x=list(xs), y=list(ys))
        master.fit(net, batches, epochs=12)
        assert net.score(x=list(xs), y=list(ys)) < s0 * 0.5
        self._assert_learned(net, xs, ys)


def _masked_recurrent_graph_and_data(rng, n=64, T=12):
    """2-input recurrent CG where input B is noise masked down to t=0; the
    per-input masks must survive the master's shard pipeline."""
    from deeplearning4j_tpu.data import MultiDataSet
    from deeplearning4j_tpu.nn import (
        ComputationGraph,
        InputType,
        NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.recurrent import LSTM, RnnOutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.nn.vertices import MergeVertex

    conf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(0.01))
            .graph_builder()
            .add_inputs("ina", "inb")
            .add_layer("la", LSTM(n_in=4, n_out=10), "ina")
            .add_layer("lb", LSTM(n_in=4, n_out=10), "inb")
            .add_vertex("m", MergeVertex(), "la", "lb")
            .add_layer("out", RnnOutputLayer(n_in=20, n_out=4, loss="mcxent",
                                             activation="softmax"), "m")
            .set_outputs("out")
            .set_input_types(InputType.recurrent(4, T),
                             InputType.recurrent(4, T))
            .build())
    net = ComputationGraph(conf).init()
    ids = rng.integers(0, 4, size=(n, T))
    xa = np.eye(4, dtype=np.float32)[ids]
    sh = np.roll(ids, 1, axis=1)
    sh[:, 0] = ids[:, 0]
    y = np.eye(4, dtype=np.float32)[sh]
    xb = rng.normal(size=(n, T, 4)).astype(np.float32)
    mb = np.zeros((n, T), np.float32)
    mb[:, 0] = 1.0
    mds = MultiDataSet(features=[xa, xb], labels=[y],
                       features_masks=[np.ones((n, T), np.float32), mb])
    return net, mds, xa, xb, y


@pytest.mark.multichip
class TestMastersSequenceMasks:
    """Sequence masks reach the masters' compiled step (review finding:
    the multi-I/O path must not silently drop features_masks)."""

    def test_shared_training_per_input_masks_learns(self, rng):
        net, mds, xa, xb, y = _masked_recurrent_graph_and_data(rng)
        master = SharedTrainingMaster(threshold=1e-4,
                                      mesh=TrainingMesh(data=8))
        master.fit(net, [mds], epochs=300)
        pred = np.argmax(np.asarray(net.output(xa, xb)), axis=-1)
        acc = (pred[:, 1:] == np.argmax(y, -1)[:, 1:]).mean()
        assert acc > 0.85, acc

    def test_parameter_averaging_mask_changes_loss(self, rng):
        """Same data with vs without the mask must give a different first-step
        loss — proves the mask is applied inside the sharded program."""
        from deeplearning4j_tpu.data import MultiDataSet

        net, mds, xa, xb, y = _masked_recurrent_graph_and_data(rng)
        open_mds = MultiDataSet(features=[xa, xb], labels=[y])
        losses = {}
        for name, batch in (("masked", mds), ("open", open_mds)):
            m = ParameterAveragingTrainingMaster(
                averaging_frequency=1, mesh=TrainingMesh(data=8))
            net_i = _masked_recurrent_graph_and_data(rng)[0]
            m.fit(net_i, [batch], epochs=1)
            losses[name] = float(net_i.score_value)
        assert not np.isclose(losses["masked"], losses["open"]), losses


class TestDistributedBootstrap:
    def test_single_process_noop(self):
        distributed.initialize()  # no coordinator, single process: no-op
        assert distributed.process_count() == 1
        assert distributed.is_coordinator()

    def test_global_mesh_shapes(self):
        m = distributed.global_mesh(model=2)
        assert m.model == 2
        assert m.n_devices == len(jax.devices())

    def test_multi_process_bootstrap_and_dp_step(self, child_env):
        """The reference tests its cluster path without a cluster (embedded
        MediaDriver / local[N] Spark — SURVEY.md §4); the equivalent here:
        two real OS processes, coordinator on localhost, global 4-device mesh
        (2 virtual CPU devices per process), 20 data-parallel steps with the
        partitioner-emitted cross-process gradient all-reduce. Params must
        come out IDENTICAL on both processes and fit the target."""
        import json
        import os
        import socket
        import subprocess
        import sys

        with socket.socket() as s:  # free localhost port
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        coordinator = f"127.0.0.1:{port}"
        worker = os.path.join(os.path.dirname(__file__), "_dist_worker.py")
        procs = [
            subprocess.Popen(
                [sys.executable, worker, coordinator, "2", str(pid)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
                env=child_env(device_count=2))
            for pid in (0, 1)
        ]
        outs = []
        try:
            for p in procs:
                out, err = p.communicate(timeout=240)
                assert p.returncode == 0, err[-2000:]
                outs.append(json.loads(
                    [l for l in out.splitlines() if l.startswith("{")][-1]))
        finally:
            for p in procs:  # a hang fails this test, not the run
                if p.poll() is None:
                    p.kill()
                    p.wait(timeout=30)
        # the children compiled at the suite's level
        assert all("xla_backend_optimization_level" in o["xla_flags"]
                   for o in outs), outs
        assert all(o["n_devices_global"] == 4 for o in outs), outs
        assert outs[0]["w"] == outs[1]["w"], outs  # identical replicas
        assert outs[0]["err"] < 0.5, outs  # learning happened
        # (identity of replicas above is the core assertion; 30
        #  gloo-allreduce steps on one host core cannot fully converge)


@pytest.mark.multichip
class TestCompressionAtScale:
    """VERDICT r2 next-round #6: the threshold/residual chain at a real
    parameter count (25M), where encode cost, bitmap density, and residual
    memory actually bite — not the toy gradient sizes of the unit tests."""

    N_PARAMS = 25_000_000

    def _big_net(self, rng):
        from deeplearning4j_tpu.nn import (
            InputType,
            MultiLayerNetwork,
            NeuralNetConfiguration,
        )
        from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.nn.updaters import Sgd

        # 2048*4096 + 4096*4096 + 4096*16 ≈ 25.3M params
        conf = (NeuralNetConfiguration.builder().seed(7).updater(Sgd(0.01))
                .list()
                .layer(DenseLayer(n_in=2048, n_out=4096, activation="relu"))
                .layer(DenseLayer(n_in=4096, n_out=4096, activation="relu"))
                .layer(OutputLayer(n_in=4096, n_out=16, loss="mcxent",
                                   activation="softmax"))
                .set_input_type(InputType.feed_forward(2048))
                .build())
        net = MultiLayerNetwork(conf).init()
        n = sum(int(np.prod(np.shape(p)))
                for lp in net.params for p in lp.values())
        assert n >= self.N_PARAMS, n
        return net

    def test_encode_decode_conservation_25m(self, rng):
        """Accumulator invariant at 25M elements: quantized + new_residual
        == grad + old_residual to fp32 rounding (error feedback loses
        nothing but low bits — subtracting ±t then re-adding loses up to
        ~2e-10 at this scale)."""
        import jax.numpy as jnp

        acc = EncodedGradientsAccumulator(residual_post_processor=None)
        g = jnp.asarray(rng.standard_normal(self.N_PARAMS).astype(np.float32)
                        * 1e-3)
        res = jnp.zeros_like(g)
        thr = jnp.asarray(1e-3, jnp.float32)
        quant, new_res, _, ratio = acc.encode(
            {"g": g}, {"g": res}, thr, jnp.asarray(0))
        np.testing.assert_allclose(
            np.asarray(quant["g"] + new_res["g"]), np.asarray(g),
            atol=1e-9)
        # sane sparsity at threshold=sigma/… : some but not all transmitted
        assert 0.0 < float(ratio) < 1.0
        # transmitted entries move a multiple of t; untransmitted are intact
        nz = np.asarray(quant["g"]) != 0
        assert np.all(np.abs(np.asarray(quant["g"])[nz]) == np.float32(1e-3))

    # slow: two minutes by itself (25M-parameter fit steps), and failing
    # at the seed (ROADMAP.md, Design 9). In tier-1 the conservation test
    # above pins the 25M threshold chain and TestTrainingMasters'
    # small fits hold the master seam
    @pytest.mark.slow
    def test_shared_training_master_25m_steps(self, rng):
        """3 full SharedTrainingMaster steps at 25M params on the 8-device
        mesh: loss finite AND moving (a frozen loss means the threshold
        chain swallowed every gradient), step time within a collapse-
        detection factor of the dense (uncompressed) DP step."""
        import time

        from deeplearning4j_tpu.data import ArrayDataSetIterator
        from deeplearning4j_tpu.parallel import ParallelWrapper

        xs = rng.standard_normal((32, 2048)).astype(np.float32)
        ys = np.eye(16, dtype=np.float32)[rng.integers(0, 16, 32)]
        it = ArrayDataSetIterator(xs, ys, batch=32)

        net = self._big_net(rng)
        master = SharedTrainingMaster(threshold=1e-4,
                                      mesh=TrainingMesh(data=8))
        master.fit(net, it, epochs=1)  # compile + first step
        s_first = float(net.score_value)
        t0 = time.perf_counter()
        master.fit(net, it, epochs=2)
        shared_dt = (time.perf_counter() - t0) / 2
        assert np.isfinite(net.score_value)
        assert float(net.score_value) != s_first  # gradients DO transmit

        net2 = self._big_net(rng)
        pw = ParallelWrapper(net2, mesh=TrainingMesh(data=8))
        pw.fit(it, epochs=1)
        t0 = time.perf_counter()
        pw.fit(it, epochs=2)
        dense_dt = (time.perf_counter() - t0) / 2
        # Measured on this single-core host: shared ≈ 8.4x dense (13.8 s vs
        # 1.6 s) — the 8 virtual devices each encode a full 25M-element
        # gradient copy + carry an (8, 25M) residual, all on ONE core, so
        # this measures host memory bandwidth, not the ICI design. The
        # bound is a collapse detector (e.g. an
        # accidental O(n^2) or per-element host loop), not a perf target.
        assert shared_dt < dense_dt * 20 + 10.0, (shared_dt, dense_dt)
