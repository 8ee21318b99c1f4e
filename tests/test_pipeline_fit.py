"""Pipeline-parallel fit(): PipelinedTrainer on the (data, model, pipe) mesh.

ISSUE 14 acceptance: a model partitioned at its stage_boundary() markers
trains across data x tensor x pipe with param+optimizer bytes/device
≈ 1/pipe_stages, trajectory-equivalent to the unpipelined fit (bit-identical
where the deterministic-lane contract allows — a data-fold change with the
pipe placement FIXED is bitwise; changing the pipe placement itself is the
pinned ~1ulp XLA:CPU fusion boundary, docs/DISTRIBUTED.md), composed with
ZeRO + grad_compression + the fused optimizer engine."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.data import DataSet
from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.computation_graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.updaters import Adam, Sgd
from deeplearning4j_tpu.parallel import (PipelinedTrainer, TrainingMesh,
                                         stage_partition)
from deeplearning4j_tpu.parallel.pipeline import (bubble_fraction,
                                                  pipeline_forward,
                                                  sequential_reference,
                                                  stack_stage_params)

H = 16


def _builder(pipe=True, fused=False, comp=None, thresh=1e-3, updater=None):
    b = (NeuralNetConfiguration.builder().seed(7)
         .updater(updater or Adam(1e-2)))
    if pipe:
        b = b.pipe_stages(2).n_micro(2)
    if fused:
        b = b.fused_update(True)
    if comp:
        b = b.grad_compression(comp, threshold=thresh)
    return b


def _net(pipe=True, width=H, **kw):
    lb = (_builder(pipe=pipe, **kw).list()
          .layer(DenseLayer(n_in=8, n_out=width, activation="relu"))
          .stage_boundary()
          .layer(DenseLayer(n_in=width, n_out=width, activation="tanh"))
          .layer(DenseLayer(n_in=width, n_out=width, activation="relu"))
          .stage_boundary()
          .layer(DenseLayer(n_in=width, n_out=width, activation="tanh"))
          .layer(DenseLayer(n_in=width, n_out=width, activation="relu"))
          .stage_boundary()
          .layer(OutputLayer(n_in=width, n_out=4, loss="mcxent",
                             activation="softmax"))
          .set_input_type(InputType.feed_forward(8)))
    return MultiLayerNetwork(lb.build()).init()


@pytest.fixture
def data(rng):
    xs = rng.standard_normal((16, 8)).astype(np.float32)
    ys = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 16)]
    return xs, ys


def _leaves(t):
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(t)]


def _fit(pt, ds, steps):
    for _ in range(steps):
        pt.step_batch(ds)
    pt.sync_model()
    return pt


# ---------------------------------------------------------------------------
# partition + conf plumbing (no device mesh needed)
# ---------------------------------------------------------------------------


class TestPartition:
    def test_markers_partition_with_preamble(self):
        net = _net()
        part = stage_partition(net, 2)
        assert [k for k, _ in part.pre] == [0]
        assert [[k for k, _ in c] for c in part.stages] == [[1, 2], [3, 4]]
        assert part.post == [] and part.head[0] == 5
        assert part.per_stage == 2

    def test_config_drift_between_stages_rejected(self):
        # identical shapes/updaters but DIFFERENT activation: the stage
        # vmap would silently run stage 0's activation for both — must
        # raise instead (regression: caught computing the wrong model)
        lb = (_builder().list()
              .layer(DenseLayer(n_in=8, n_out=H, activation="relu"))
              .stage_boundary()
              .layer(DenseLayer(n_in=H, n_out=H, activation="tanh"))
              .stage_boundary()
              .layer(DenseLayer(n_in=H, n_out=H, activation="relu"))
              .stage_boundary()
              .layer(OutputLayer(n_in=H, n_out=4, loss="mcxent",
                                 activation="softmax"))
              .set_input_type(InputType.feed_forward(8)))
        net = MultiLayerNetwork(lb.build()).init()
        with pytest.raises(ValueError, match="layer configs differ"):
            stage_partition(net, 2)

    def test_shape_mismatch_rejected(self):
        lb = (_builder().list()
              .layer(DenseLayer(n_in=8, n_out=H, activation="tanh"))
              .stage_boundary()
              .layer(DenseLayer(n_in=H, n_out=2 * H, activation="tanh"))
              .stage_boundary()
              .layer(OutputLayer(n_in=2 * H, n_out=4, loss="mcxent",
                                 activation="softmax"))
              .set_input_type(InputType.feed_forward(8)))
        net = MultiLayerNetwork(lb.build()).init()
        with pytest.raises(ValueError, match="differ"):
            stage_partition(net, 2)

    def test_too_few_chunks_rejected(self):
        net = _net()
        with pytest.raises(ValueError, match="pipe_stages=4 needs"):
            stage_partition(net, 4)

    def test_conf_roundtrip_json_mln_and_cg(self):
        from deeplearning4j_tpu.nn.computation_graph import (
            ComputationGraphConfiguration)
        from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration

        conf = _net().conf
        assert conf.pipe_stages == 2 and conf.n_micro == 2
        back = MultiLayerConfiguration.from_json(conf.to_json())
        assert back.pipe_stages == 2 and back.n_micro == 2
        g = (_builder().graph_builder()
             .add_inputs("in")
             .add_layer("d0", DenseLayer(n_in=8, n_out=4,
                                         activation="tanh"), "in")
             .add_layer("out", OutputLayer(n_in=4, n_out=2, loss="mcxent",
                                           activation="softmax"), "d0")
             .set_outputs("out").set_input_types((8,)).build())
        assert g.pipe_stages == 2 and g.n_micro == 2
        gback = ComputationGraphConfiguration.from_json(g.to_json())
        assert gback.pipe_stages == 2 and gback.n_micro == 2

    def test_env_default(self, monkeypatch):
        from deeplearning4j_tpu import config as cfg

        monkeypatch.setenv("DL4J_TPU_PIPE_STAGES", "4")
        monkeypatch.setattr(cfg.Environment, "_instance", None)
        try:
            conf = (NeuralNetConfiguration.builder().list()
                    .layer(DenseLayer(n_in=4, n_out=4))
                    .layer(OutputLayer(n_in=4, n_out=2, loss="mcxent",
                                       activation="softmax"))
                    .set_input_type(InputType.feed_forward(4)).build())
            assert conf.pipe_stages == 4
        finally:
            monkeypatch.setattr(cfg.Environment, "_instance", None)

    def test_bubble_fraction_schedule_math(self):
        assert bubble_fraction(2, 2) == pytest.approx(1 / 3)
        assert bubble_fraction(4, 12) == pytest.approx(3 / 15)
        assert bubble_fraction(1, 8) == 0.0
        with pytest.raises(ValueError):
            bubble_fraction(2, 0)

    def test_tbptt_rejected(self):
        conf = _net().conf
        conf.tbptt_length = 5
        net = MultiLayerNetwork(conf).init()
        with pytest.raises(NotImplementedError, match="TBPTT"):
            PipelinedTrainer(net, mesh=TrainingMesh(
                data=1, devices=jax.devices()[:1]))

    def test_pipe_axis_must_divide_stages(self, devices):
        net = _net()
        with pytest.raises(ValueError, match="must divide pipe_stages"):
            PipelinedTrainer(net, pipe_stages=2, mesh=TrainingMesh(
                data=1, pipe=4, devices=jax.devices()[:4]))


# ---------------------------------------------------------------------------
# pipeline_forward ragged support (ISSUE 14 satellite)
# ---------------------------------------------------------------------------


@pytest.mark.multichip
class TestRaggedPipelineForward:
    def test_pads_instead_of_raising(self, rng):
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("model",))

        def stage_fn(p, x):
            return jnp.tanh(x @ p["W"] + p["b"])

        params = [
            {"W": jnp.asarray(rng.standard_normal((8, 8)) * 0.4,
                              jnp.float32),
             "b": jnp.asarray(rng.standard_normal(8) * 0.1, jnp.float32)}
            for _ in range(4)
        ]
        # 10 % n_micro(4) != 0: pre-r19 this raised; now the last
        # microbatch pads (repeated rows, sliced off the result)
        x = jnp.asarray(rng.standard_normal((10, 8)), jnp.float32)
        out = pipeline_forward(stage_fn, stack_stage_params(params), x,
                               n_micro=4, mesh=mesh)
        ref = sequential_reference(stage_fn, params, x)
        assert out.shape == (10, 8)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)

    def test_ragged_trainer_loss_exact_weight_machinery(self, rng):
        """The satellite's exactness claim, split into its two honest
        halves: (a) a ragged batch's auto-padding is BIT-identical to
        manually padding the batch and threading explicit 0/1 weights
        through the SAME pipelined program (the padding machinery adds
        nothing beyond the r8 weights — exact gradients), and (b) the
        loss matches the weighted unpipelined loss on the same padded
        batch to ~1 ulp (the per-microbatch gemm shapes re-block on
        XLA:CPU — the pinned r12 boundary; bit-identity between the two
        PROGRAMS is shape-dependent luck, not a contract)."""
        xs = rng.standard_normal((13, 8)).astype(np.float32)
        ys = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 13)]
        net = _net(updater=Sgd(0.05))
        pt = PipelinedTrainer(
            net, mesh=TrainingMesh(data=1, devices=jax.devices()[:1]),
            replicas=1, skew_every=0)
        loss_pipe = float(pt.step_batch(DataSet(xs, ys)))
        pad = lambda a: np.concatenate([a, a[-1:]], axis=0)  # noqa: E731
        # (a) same program, manual pad to 14 rows: the auto-pad rows carry
        # weight 0, so a 14-row batch (its pad row weighted 1 but identical
        # data... ) — instead compare the LANE LOSS bodies directly: run
        # the padded batch through a fresh trainer; row 14 duplicates row
        # 13, so the weighted mean differs — what must be bit-equal is the
        # TRAJECTORY: one step on the ragged batch == one step on the
        # manually padded batch with the duplicate row's weight zeroed.
        net_m = _net(updater=Sgd(0.05))
        pt_m = PipelinedTrainer(
            net_m, mesh=TrainingMesh(data=1, devices=jax.devices()[:1]),
            replicas=1, skew_every=0)
        pt_m._build()
        xp, yp = pad(xs), pad(ys)
        xs_l, ys_l, w_l = pt_m.mesh.pad_lane_batch(xp, yp, 1, micro=2)
        w_l = jnp.asarray(np.array([[1.0] * 13 + [0.0]], np.float32))
        net_m._rng_key, sub = jax.random.split(net_m._rng_key)
        keys = pt_m._lane_keys(sub)
        pp = pt_m._pp
        new_p, _, _, loss_m = pt_m._sharded_step(
            pp["params"], pp["states"], pp["opts"],
            jnp.asarray(0), xs_l, ys_l, keys, w_l)
        assert np.float32(loss_pipe) == np.float32(float(loss_m))
        pt.sync_model()
        manual = pt_m._unstack_tree(new_p, net_m.params)
        for a, b in zip(_leaves(net.params), _leaves(manual)):
            assert np.array_equal(a, b)
        # (b) vs the weighted UNPIPELINED loss: ~1 ulp
        ref = _net(updater=Sgd(0.05))
        w = np.ones(14, np.float32)
        w[13:] = 0.0
        loss_ref, _ = ref._loss(
            ref.params, ref.states, jnp.asarray(xp), jnp.asarray(yp),
            [jax.random.PRNGKey(0)] * len(ref.layers), jnp.asarray(w))
        np.testing.assert_allclose(loss_pipe, float(loss_ref),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# the trainer: trajectory, bit-identity, memory, 3D composition
# ---------------------------------------------------------------------------


@pytest.mark.multichip
class TestPipelinedFit:
    def test_trajectory_and_data_fold_bit_identity(self, data, devices):
        """(data=4, pipe=2) 8-device fit: allclose to the plain unpipelined
        fit AND bit-identical (params, Adam moments, RNG key) to the same
        pipelined program on (data=1, pipe=2) — the r12 lane contract with
        the pipe placement fixed."""
        xs, ys = data
        ds = DataSet(xs, ys)
        ref = _net()
        for _ in range(4):
            ref._fit_batch(xs, ys)
        n8 = _net()
        pt8 = _fit(PipelinedTrainer(n8, mesh=TrainingMesh(data=4, pipe=2),
                                    replicas=4, skew_every=0), ds, 4)
        for a, b in zip(_leaves(n8.params), _leaves(ref.params)):
            np.testing.assert_allclose(a, b, atol=2e-6, rtol=2e-6)
        n1 = _net()
        _fit(PipelinedTrainer(
            n1, mesh=TrainingMesh(data=1, pipe=2,
                                  devices=jax.devices()[:2]),
            replicas=4, skew_every=0), ds, 4)
        for a, b in zip(_leaves(n8.params), _leaves(n1.params)):
            assert np.array_equal(a, b)
        for a, b in zip(_leaves(n8.opt_states), _leaves(n1.opt_states)):
            assert np.array_equal(a, b)
        assert np.array_equal(np.asarray(n8._rng_key),
                              np.asarray(n1._rng_key))
        # layout surface
        lay = pt8.layout["pipeline"]
        assert lay["stages"] == 2 and lay["n_micro"] == 2
        assert lay["bubble_fraction"] == pytest.approx(1 / 3)

    def test_memory_bytes_per_device_ratio(self, devices):
        """Stage params pipe-shard: param+opt bytes ONE device holds on the
        (2, 1, 2) placement land near 1/pipe_stages of the replicated
        footprint (preamble/head replicate — the small remainder)."""
        from deeplearning4j_tpu.parallel import gspmd

        W = 64  # stage leaves 64x64 = 4096 elements >= ZeRO's 1024 floor
        lb = (_builder().list()
              .layer(DenseLayer(n_in=8, n_out=W, activation="relu"))
              .stage_boundary()
              .layer(DenseLayer(n_in=W, n_out=W, activation="tanh"))
              .stage_boundary()
              .layer(DenseLayer(n_in=W, n_out=W, activation="tanh"))
              .stage_boundary()
              .layer(OutputLayer(n_in=W, n_out=4, loss="mcxent",
                                 activation="softmax"))
              .set_input_type(InputType.feed_forward(8)))
        net = MultiLayerNetwork(lb.build()).init()
        pt = PipelinedTrainer(net, mesh=TrainingMesh(data=2, pipe=2,
                                                     devices=jax.devices()[:4]),
                              replicas=2, skew_every=0)
        pt._build()
        per_dev = pt.train_state_bytes_per_device()
        replicated = (gspmd.tree_bytes(net.params)
                      + gspmd.tree_bytes(net.opt_states))
        ratio = per_dev / replicated
        # stage-dominated net: 1/pipe_stages plus the replicated pre/head
        # remainder; ZeRO-data sharding on the moments keeps the total under
        assert ratio < 0.62, (per_dev, replicated, ratio)
        assert pt.param_bytes_per_device() < gspmd.tree_bytes(net.params)

    def test_model_over_a_device_budget_places_and_trains_on_3d_mesh(
            self, data, devices):
        """A stage-dominated net (four 512x512 stage layers) whose
        replicated parameters and moments exceed a declared per-device
        budget places under it on the (data=2, model=2, pipe=2) mesh,
        near 1/pipe_stages of the replicated bytes, and a step runs. The
        bubble of the schedule is computed from it, never timed, and
        published as a gauge."""
        from deeplearning4j_tpu.parallel import gspmd
        from deeplearning4j_tpu.util import telemetry as tm

        net = _net(width=512)
        pt = PipelinedTrainer(net, mesh=TrainingMesh(data=2, model=2, pipe=2),
                              replicas=2, skew_every=0)
        pt._build()
        replicated = (gspmd.tree_bytes(net.params)
                      + gspmd.tree_bytes(net.opt_states))
        budget = int(replicated * 0.75)  # one device cannot hold the model
        per_dev = pt.train_state_bytes_per_device()
        assert per_dev < budget < replicated
        assert per_dev / replicated < 1 / 2 + 0.12
        pt.step_batch(DataSet(*data))
        assert np.isfinite(float(net.score_value))
        assert pt.bubble_fraction == pytest.approx(bubble_fraction(2, 2))
        gauges = [v for (name, _l), v in tm.get_telemetry().gauges.items()
                  if name == "parallel.pipeline_bubble_fraction"]
        assert gauges and gauges[-1] == pytest.approx(1 / 3)

    def test_full_3d_mesh_with_tp_rules(self, data, devices):
        xs, ys = data
        ds = DataSet(xs, ys)
        net = _net()
        pt = _fit(PipelinedTrainer(
            net, mesh=TrainingMesh(data=2, model=2, pipe=2),
            replicas=2, skew_every=0,
            tp_rules=[(r"\['W'\]$", P(None, "model"))]), ds, 4)
        tp_leaves = [v for v in jax.tree_util.tree_leaves(pt._pp["params"])
                     if hasattr(v, "sharding")
                     and "model" in str(v.sharding.spec)]
        assert tp_leaves, "no tensor-parallel sharded leaves"
        ref = _net()
        for _ in range(4):
            ref._fit_batch(xs, ys)
        for a, b in zip(_leaves(net.params), _leaves(ref.params)):
            np.testing.assert_allclose(a, b, atol=5e-6, rtol=5e-6)

    def test_masks_rejected(self, data, devices):
        xs, ys = data
        net = _net()
        pt = PipelinedTrainer(net, mesh=TrainingMesh(data=4, pipe=2),
                              replicas=4, skew_every=0)
        ds = DataSet(xs, ys)
        ds.features_mask = np.ones((16, 1), np.float32)
        with pytest.raises(NotImplementedError, match="masks"):
            pt.step_batch(ds)

    def test_cost_report_per_stage_rows(self, data, devices):
        xs, ys = data
        net = _net()
        pt = _fit(PipelinedTrainer(net, mesh=TrainingMesh(data=4, pipe=2),
                                   replicas=4, skew_every=0),
                  DataSet(xs, ys), 1)
        rep = pt.cost_report(batch_size=16, publish=False)
        names = [r.layer for r in rep.rows]
        assert "pipe:stage0" in names and "pipe:stage1" in names
        assert "(optimizer)" in names
        s0 = next(r for r in rep.rows if r.layer == "pipe:stage0")
        s1 = next(r for r in rep.rows if r.layer == "pipe:stage1")
        assert s0.flops == s1.flops > 0  # identical stages, equal split
        assert rep.devices == 8


@pytest.mark.multichip
class TestCompositions:
    def test_compression_t0_identity_and_checkpoint(self, data, tmp_path,
                                                    devices):
        """threshold→0 compression is the exact identity encode: the
        pipelined compressed fit is BIT-identical to the uncompressed
        pipelined fit. An active threshold ships encoded wire bytes and a
        resident residual that rides ShardedCheckpointer restores
        bit-exactly, with the resumed trajectory bit-identical."""
        from deeplearning4j_tpu.util.checkpoint import ShardedCheckpointer

        xs, ys = data
        ds = DataSet(xs, ys)
        mesh = lambda: TrainingMesh(data=4, pipe=2)  # noqa: E731
        nc = _net(comp="threshold", thresh=0.0)
        _fit(PipelinedTrainer(nc, mesh=mesh(), replicas=4, skew_every=0),
             ds, 3)
        nu = _net()
        _fit(PipelinedTrainer(nu, mesh=mesh(), replicas=4, skew_every=0),
             ds, 3)
        for a, b in zip(_leaves(nc.params), _leaves(nu.params)):
            assert np.array_equal(a, b)
        # active compression: wire accounting + checkpointed residual
        na = _net(comp="threshold", thresh=1e-3)
        pa = _fit(PipelinedTrainer(na, mesh=mesh(), replicas=4,
                                   skew_every=0), ds, 3)
        stats = pa.compression_stats()
        assert stats["wire_bytes"] > 0
        ck = ShardedCheckpointer(str(tmp_path / "ck"), log_fn=None)
        ck.save(na.iteration, na, block=True)
        nb = _net(comp="threshold", thresh=1e-3)
        ck.restore(nb)
        pb = PipelinedTrainer(nb, mesh=mesh(), replicas=4, skew_every=0)
        for _ in range(2):
            pa.step_batch(ds)
            pb.step_batch(ds)
        pa.sync_model()
        pb.sync_model()
        for a, b in zip(_leaves(na.params), _leaves(nb.params)):
            assert np.array_equal(a, b)
        for a, b in zip(_leaves(na._grad_comp_state),
                        _leaves(nb._grad_comp_state)):
            assert np.array_equal(a, b)

    def test_fused_engine_composition(self, data, devices):
        """FusedUpdateEngine composition: the pipeline-layout engine's
        trajectory tracks the unpipelined fused fit (the pipe-placement
        fusion boundary — docs/DISTRIBUTED.md — bounds it away from
        bitwise), re-runs deterministically bit-exact, and threshold→0
        compression over the flat buffers is bit-identical to the
        uncompressed fused fit. sync_model converts the resident masters
        to the net's model-layout engine state bit-exactly (the resync
        invariant): a restore + re-stack round trip reproduces the
        trajectory."""
        xs, ys = data
        ds = DataSet(xs, ys)
        mesh = lambda: TrainingMesh(data=4, pipe=2)  # noqa: E731
        nf = _net(fused=True)
        _fit(PipelinedTrainer(nf, mesh=mesh(), replicas=4, skew_every=0),
             ds, 4)
        ref = _net(fused=True)
        for _ in range(4):
            ref._fit_batch(xs, ys)
        for a, b in zip(_leaves(nf.params), _leaves(ref.params)):
            np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-3)
        # deterministic re-run: same program, same mesh -> bitwise
        nf2 = _net(fused=True)
        _fit(PipelinedTrainer(nf2, mesh=mesh(), replicas=4, skew_every=0),
             ds, 4)
        for a, b in zip(_leaves(nf.params), _leaves(nf2.params)):
            assert np.array_equal(a, b)
        # t->0 over the flat buffers == uncompressed fused, bitwise
        nfc = _net(fused=True, comp="threshold", thresh=0.0)
        _fit(PipelinedTrainer(nfc, mesh=mesh(), replicas=4, skew_every=0),
             ds, 4)
        for a, b in zip(_leaves(nfc.params), _leaves(nf.params)):
            assert np.array_equal(a, b)
        # masters ride sync_model: restore into a fresh net + trainer and
        # continue — bit-identical continuation proves params/masters moved
        # together through both layout conversions
        from deeplearning4j_tpu.util.checkpoint import ShardedCheckpointer
        import tempfile

        with tempfile.TemporaryDirectory() as d:
            ck = ShardedCheckpointer(d, log_fn=None)
            ck.save(nf.iteration, nf, block=True)
            nr = _net(fused=True)
            ck.restore(nr)
            pr = PipelinedTrainer(nr, mesh=mesh(), replicas=4, skew_every=0)
            pf = PipelinedTrainer(nf, mesh=mesh(), replicas=4, skew_every=0)
            for _ in range(2):
                pf.step_batch(ds)
                pr.step_batch(ds)
            pf.sync_model()
            pr.sync_model()
            for a, b in zip(_leaves(nf.params), _leaves(nr.params)):
                assert np.array_equal(a, b)

    def test_remat_policy_through_stages(self, data, devices):
        """Activation checkpointing (the r6 remat machinery) wraps each
        stage body: same values/gradients, only XLA's fwd/bwd liveness
        changes — the pipelined fit under remat_policy='full' tracks the
        un-remat pipelined fit."""
        xs, ys = data
        ds = DataSet(xs, ys)

        def build(policy):
            b = _builder()
            if policy:
                b = b.remat_policy(policy)
            lb = (b.list()
                  .layer(DenseLayer(n_in=8, n_out=H, activation="relu"))
                  .stage_boundary()
                  .layer(DenseLayer(n_in=H, n_out=H, activation="tanh"))
                  .stage_boundary()
                  .layer(DenseLayer(n_in=H, n_out=H, activation="tanh"))
                  .stage_boundary()
                  .layer(OutputLayer(n_in=H, n_out=4, loss="mcxent",
                                     activation="softmax"))
                  .set_input_type(InputType.feed_forward(8)))
            return MultiLayerNetwork(lb.build()).init()

        n_plain = build(None)
        _fit(PipelinedTrainer(n_plain, mesh=TrainingMesh(data=4, pipe=2),
                              replicas=4, skew_every=0), ds, 3)
        n_remat = build("full")
        _fit(PipelinedTrainer(n_remat, mesh=TrainingMesh(data=4, pipe=2),
                              replicas=4, skew_every=0), ds, 3)
        for a, b in zip(_leaves(n_plain.params), _leaves(n_remat.params)):
            np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)

    def test_reshard_and_adopt_after_restore(self, data, devices):
        xs, ys = data
        ds = DataSet(xs, ys)
        net = _net()
        pt = _fit(PipelinedTrainer(net, mesh=TrainingMesh(data=4, pipe=2),
                                   replicas=4, skew_every=0), ds, 2)
        before = _leaves(net.params)
        pt.reshard(TrainingMesh(data=2, pipe=2, devices=jax.devices()[:4]))
        pt.sync_model()
        after = _leaves(net.params)
        for a, b in zip(before, after):
            assert np.array_equal(a, b)  # reshard migrates state bit-exactly
        pt.step_batch(ds)  # and the re-placed step runs
        # external write (a restore): the next step adopts it
        net.params = jax.tree_util.tree_map(np.asarray, net.params)
        pt.step_batch(ds)
        assert np.isfinite(float(net.score_value))

    def test_in_place_external_write_adopted(self, data, devices):
        """Regression (review finding): transfer ``copy_back`` / the Keras
        importer write INTO the existing params list (``net.params[i] =
        ...``), leaving the container id unchanged — the leaf-id
        fingerprint must still detect it, or the trainer keeps training
        the stale stacked state and sync_model() silently overwrites the
        external write."""
        xs, ys = data
        ds = DataSet(xs, ys)
        net = _net()
        pt = _fit(PipelinedTrainer(net, mesh=TrainingMesh(data=4, pipe=2),
                                   replicas=4, skew_every=0), ds, 2)
        # in-place entry write: zero layer 0's weights (container id kept)
        net.params[0] = dict(net.params[0],
                             W=jnp.zeros_like(net.params[0]["W"]))
        pt.step_batch(ds)
        pt.sync_model()
        w = np.abs(np.asarray(net.params[0]["W"])).max()
        # adopted: one Adam step from zeros is lr-scale (~1e-2), not the
        # stale trained magnitude (~0.5)
        assert w < 0.1, f"in-place write ignored (|W|max={w})"

    def test_deterministic_wrapper_rejects_pipe_mesh(self, devices):
        """Regression (review finding): the deterministic lane mode's
        data-only-mesh guard must cover the new 'pipe' axis."""
        from deeplearning4j_tpu.parallel import ParallelWrapper

        net = _net(pipe=False)
        with pytest.raises(ValueError, match="data-only mesh"):
            ParallelWrapper(net, mesh=TrainingMesh(data=2, pipe=2,
                                                   devices=jax.devices()[:4]),
                            deterministic=True)


@pytest.mark.multichip
class TestLinearChainCG:
    def _graph(self):
        g = (_builder().graph_builder()
             .add_inputs("in")
             .add_layer("embed", DenseLayer(n_in=8, n_out=H,
                                            activation="relu"), "in")
             .add_layer("b0", DenseLayer(n_in=H, n_out=H,
                                         activation="tanh"), "embed")
             .add_layer("b1", DenseLayer(n_in=H, n_out=H,
                                         activation="tanh"), "b0")
             .add_layer("out", OutputLayer(n_in=H, n_out=4, loss="mcxent",
                                           activation="softmax"), "b1")
             .set_outputs("out").set_input_types((8,))
             .stage_boundary("embed", "b0", "b1"))
        return ComputationGraph(g.build()).init()

    def test_cg_chain_trains_and_tracks_unpipelined(self, data, devices):
        xs, ys = data
        net = self._graph()
        part = stage_partition(net, 2)
        assert [k for k, _ in part.pre] == ["embed"]
        assert [[k for k, _ in c] for c in part.stages] == [["b0"], ["b1"]]
        pt = _fit(PipelinedTrainer(net, mesh=TrainingMesh(data=4, pipe=2),
                                   replicas=4, skew_every=0),
                  DataSet(xs, ys), 3)
        ref = self._graph()
        for _ in range(3):
            ref._fit_batch([xs], [ys])
        for a, b in zip(_leaves(net.params), _leaves(ref.params)):
            np.testing.assert_allclose(a, b, atol=2e-6, rtol=2e-6)
        assert pt.layout["pipeline"]["stages"] == 2

    def test_non_chain_graph_rejected(self):
        g = (_builder().graph_builder()
             .add_inputs("a", "b")
             .add_layer("d", DenseLayer(n_in=8, n_out=4,
                                        activation="tanh"), "a")
             .add_layer("out", OutputLayer(n_in=4, n_out=2, loss="mcxent",
                                           activation="softmax"), "d")
             .set_outputs("out").set_input_types((8,), (8,)))
        net = ComputationGraph(g.build()).init()
        with pytest.raises(ValueError, match="single-input"):
            stage_partition(net, 2)


@pytest.mark.multichip
def test_partitioner_slice_hazard_documented(devices):
    """Pins the jaxlib SPMD bug the fused path engineers around: slicing a
    pipe-sharded stacked array inside jit on a multi-axis mesh corrupts
    data (strided reads), while the reshape-based flatten the
    pipeline-layout engine uses is exact. If this test ever FAILS on the
    corrupt branch, the workaround can be retired (docs/DISTRIBUTED.md)."""
    from jax import lax
    from jax.sharding import Mesh, NamedSharding

    devs = np.array(jax.devices()[:4]).reshape(2, 1, 1, 2)
    mesh = Mesh(devs, ("data", "model", "seq", "pipe"))
    pipe_spec = NamedSharding(mesh, P("pipe"))
    S, n = 2, 16
    x = np.arange(S * n * n, dtype=np.float32).reshape(S, n, n)
    xs = jax.device_put(x, pipe_spec)

    @jax.jit
    def reshape_roundtrip(stacked):
        stacked = lax.with_sharding_constraint(stacked, pipe_spec)
        flat = stacked.reshape(-1)
        return lax.with_sharding_constraint(flat.reshape(S, n, n),
                                            pipe_spec)

    assert np.array_equal(np.asarray(reshape_roundtrip(xs)), x)

    @jax.jit
    def slice_roundtrip(stacked):
        stacked = lax.with_sharding_constraint(stacked, pipe_spec)
        return lax.with_sharding_constraint(
            jnp.stack([stacked[i] for i in range(S)]), pipe_spec)

    sliced = np.asarray(slice_roundtrip(jax.device_put(x, pipe_spec)))
    if np.array_equal(sliced, x):
        pytest.fail(
            "jaxlib's partitioner now slices pipe-sharded stage axes "
            "correctly — the reshape-only constraint in "
            "parallel/pipelined.py (module docstring) can be retired")
