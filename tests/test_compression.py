"""Encoded gradient collectives on the DP hot path (ISSUE 10).

What CPU can honestly prove (the r6 convention, docs/DISTRIBUTED.md):

- **Error-feedback conservation, bit-exact**: decode(encode(g, res, t)) +
  new_res == g + res with EXACT float equality — the encoder snaps its
  threshold to a power of two (ops/compression.pow2_floor), which makes the
  residual subtraction exact for every element within 7 decades of the
  threshold.
- **threshold→0 bit-identity**: the compressed wrapper at t=0 (the exact
  identity encode) reproduces the uncompressed deterministic lane fit
  BIT-for-bit — params, Adam moments, RNG key.
- **Deterministic wire accounting**: the wire-bytes/ratio stats are pure
  functions of the data, identical across runs.
- **Convergence parity**: a compressed fit on the same data order reaches
  the exact fit's loss neighborhood (error feedback: nothing is lost, only
  delayed).

What CPU cannot prove: that fewer wire bytes are faster — that ranking
belongs to real ICI/DCN hardware (not measured: ROADMAP Speed 9).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.data import DataSet
from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.updaters import Adam, Sgd
from deeplearning4j_tpu.ops import compression as C
from deeplearning4j_tpu.parallel import (GradCompressor, ParallelWrapper,
                                         TrainingMesh, gspmd)
from deeplearning4j_tpu.parallel.compression import (resolve_scheme,
                                                     validate_scheme)
from deeplearning4j_tpu.util.checkpoint import (ShardedCheckpointer,
                                                load_tree_npz,
                                                save_tree_npz)


def _mesh8():
    return TrainingMesh(data=8)


def _mesh1():
    return TrainingMesh(data=1, devices=jax.devices()[:1])


def _leaves(tree):
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(tree)]


def _assert_tree_equal(a, b, what):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb), what
    for i, (u, v) in enumerate(zip(la, lb)):
        assert u.shape == v.shape, (what, i)
        assert (u == v).all(), (
            f"{what} leaf {i} differs: maxdiff "
            f"{np.abs(u.astype(np.float64) - v.astype(np.float64)).max()}")


def _dense_conf(comp=None, threshold=1e-3, target=1e-3, fused=False,
                loss_scale=None, updater=None, seed=7):
    b = NeuralNetConfiguration.builder().seed(seed).updater(
        updater or Adam(0.01))
    if fused:
        b = b.fused_update(True)
    if loss_scale:
        b = b.loss_scale(loss_scale)
    if comp:
        b = b.grad_compression(comp, threshold=threshold,
                               target_sparsity=target)
    return (b.list()
            .layer(DenseLayer(n_in=6, n_out=32, activation="relu"))
            .layer(DenseLayer(n_in=32, n_out=32, activation="tanh"))
            .layer(OutputLayer(n_in=32, n_out=4, loss="mcxent",
                               activation="softmax"))
            .set_input_type(InputType.feed_forward(6)).build())


def _net(**kw):
    return MultiLayerNetwork(_dense_conf(**kw)).init()


def _data(rng, n=32):
    xs = rng.standard_normal((n, 6)).astype(np.float32)
    ys = np.eye(4, dtype=np.float32)[rng.integers(0, 4, n)]
    return xs, ys


# ---------------------------------------------------------------------------
# 1. error-feedback conservation — EXACT
# ---------------------------------------------------------------------------
class TestConservationExact:
    @pytest.mark.parametrize("scale", [1e-6, 1e-3, 1.0, 1e3])
    @pytest.mark.parametrize("threshold", [1e-4, 1e-3, 1e-2, 0.3])
    def test_threshold_encode_exact_conserves_bitwise(self, rng, scale,
                                                      threshold):
        """decode(encode(g, res, t)) + new_res == g + res EXACTLY: the
        pow2-snapped threshold makes the residual subtraction exact (see
        ops/compression.pow2_floor) across 9 decades of gradient scale."""
        g = jnp.asarray(rng.standard_normal(20000) * scale, jnp.float32)
        res = jnp.asarray(rng.standard_normal(20000) * scale * 0.3,
                          jnp.float32)
        carried = g + res
        q, new_res = C.threshold_encode_exact(carried, threshold)
        back = q + new_res  # decode of the dense quantized IS identity
        np.testing.assert_array_equal(np.asarray(back), np.asarray(carried))

    def test_onebit_encode_conserves_bitwise(self, rng):
        g = jnp.asarray(rng.standard_normal(20000) * 0.01, jnp.float32)
        q, r, s = C.onebit_encode(g)
        np.testing.assert_array_equal(np.asarray(q + r), np.asarray(g))
        # the scale is an exact power of two
        e = np.frexp(float(s))
        assert e[0] == 0.5, float(s)
        # only |g| >= s transmitted (the exactness condition)
        qa = np.asarray(q)
        assert (np.abs(np.asarray(g))[qa != 0] >= float(s)).all()

    def test_pow2_floor_is_exact_pow2(self):
        for t in (1e-6, 1e-3, 0.1, 0.5, 1.0, 3.7):
            v = float(C.pow2_floor(t))
            m, _ = np.frexp(np.float32(v))
            assert m == 0.5 or v == 0.0, (t, v)
            assert v <= t < 2 * v, (t, v)

    def test_zero_threshold_is_exact_identity(self, rng):
        g = jnp.asarray(rng.standard_normal(512), jnp.float32)
        q, r = C.threshold_encode_exact(g, 0.0)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(g))
        assert not np.asarray(r).any()

    def test_compressor_step_conserves_through_state(self, rng):
        """The full GradCompressor.encode_combine conserves: what left each
        worker (quantized) plus what stayed (new residual) equals grad +
        old residual, bit-for-bit, every step."""
        comp = GradCompressor(scheme="threshold", initial_threshold=1e-2)
        stacked = {"w": jnp.asarray(
            rng.standard_normal((8, 64)) * 0.01, jnp.float32)}
        state = comp.init_state({"w": np.zeros((64,), np.float32)}, 8)
        for _ in range(5):
            carried = stacked["w"] + state["residual"]["w"]
            _, new_state, _ = comp.encode_combine(
                stacked, state, jnp.asarray(1.0, jnp.float32))
            # reconstruct this step's transmitted payload from conservation
            q = carried - new_state["residual"]["w"]
            np.testing.assert_array_equal(
                np.asarray(q + new_state["residual"]["w"]),
                np.asarray(carried))
            state = new_state


# ---------------------------------------------------------------------------
# 2. threshold→0 bit-identity with the uncompressed path
# ---------------------------------------------------------------------------
@pytest.mark.multichip
class TestThresholdZeroBitIdentity:
    def _fit(self, net, xs, ys, mesh, epochs=3, **kw):
        pw = ParallelWrapper(net, mesh=mesh, skew_every=0, **kw)
        pw.fit([DataSet(xs, ys)], epochs=epochs)
        return pw

    def test_t0_compressed_equals_deterministic(self, rng):
        xs, ys = _data(rng)
        exact = _net()
        self._fit(exact, xs, ys, _mesh8(), deterministic=True, replicas=8)
        comp = _net(comp="threshold", threshold=0.0)
        self._fit(comp, xs, ys, _mesh8(), replicas=8)
        _assert_tree_equal(exact.params, comp.params, "params(t=0)")
        _assert_tree_equal(exact.opt_states, comp.opt_states, "moments(t=0)")
        np.testing.assert_array_equal(np.asarray(exact._rng_key),
                                      np.asarray(comp._rng_key))

    def test_t0_hierarchical_equals_flat(self, rng):
        """pow2 host grouping preserves the pairwise-tree association, so
        the hierarchical mode's t=0 fit is the SAME bits as flat."""
        xs, ys = _data(rng)
        flat = _net(comp="threshold", threshold=0.0)
        self._fit(flat, xs, ys, _mesh8(), replicas=8)
        hier = _net(comp="threshold", threshold=0.0)
        self._fit(hier, xs, ys, _mesh8(), replicas=8, compression_hosts=2)
        _assert_tree_equal(flat.params, hier.params, "params(hier t=0)")

    def test_t0_fused_zero_composes_bit_identical(self, rng):
        """The fused-engine variant (encode on flat per-(rule,dtype)
        buffers, ZeRO-sharded update) at t=0 equals the plain fused
        deterministic fit bit-for-bit."""
        xs, ys = _data(rng)
        exact = _net(fused=True)
        self._fit(exact, xs, ys, _mesh8(), deterministic=True, replicas=8)
        comp = _net(fused=True, comp="threshold", threshold=0.0)
        pw = self._fit(comp, xs, ys, _mesh8(), replicas=8)
        _assert_tree_equal(exact.params, comp.params, "params(fused t=0)")
        # residual really is the flat buffer layout: one (8, total) leaf
        # per (rule, dtype) group
        res = pw._comp_state["residual"]
        assert isinstance(res, list) and len(res) == len(comp._fused.groups)
        for buf, grp in zip(res, comp._fused.groups):
            assert tuple(buf.shape) == (8, grp.total)


# ---------------------------------------------------------------------------
# 3. wire accounting: deterministic, scheme-shaped, gauged
# ---------------------------------------------------------------------------
@pytest.mark.multichip
class TestWireAccounting:
    def test_stats_deterministic_across_runs(self, rng):
        xs, ys = _data(rng)
        runs = []
        for _ in range(2):
            net = _net(comp="threshold")
            pw = ParallelWrapper(net, mesh=_mesh8(), skew_every=0)
            pw.fit([DataSet(xs, ys)], epochs=3)
            runs.append(pw.compression_stats())
        assert runs[0] == runs[1]
        assert runs[0]["wire_bytes"] > 0

    def test_bitmap_ratio_is_nnz_independent(self, rng):
        xs, ys = _data(rng)
        net = _net(comp="bitmap")
        pw = ParallelWrapper(net, mesh=_mesh8(), skew_every=0)
        pw.fit([DataSet(xs, ys)], epochs=2)
        stats = pw.compression_stats()
        # 2 bits/element + one word per leaf: strictly under 0.1, whatever
        # the data did
        assert stats["ratio"] < 0.1
        assert abs(stats["ratio"] - 1 / 16) < 0.05, stats

    def test_onebit_runs_and_reports(self, rng):
        xs, ys = _data(rng)
        net = _net(comp="onebit")
        pw = ParallelWrapper(net, mesh=_mesh8(), skew_every=0)
        pw.fit([DataSet(xs, ys)], epochs=2)
        stats = pw.compression_stats()
        assert np.isfinite(float(net.score_value))
        assert 0 < stats["ratio"] < 0.1

    def test_adaptive_threshold_drives_sparsity_down(self, rng):
        """The adaptive threshold climbs until the transmitted fraction
        reaches the target band — on this dense-gradient toy the sparse
        wire carries under a tenth of the dense bytes within a few dozen
        steps, and every step's bytes land on the
        `parallel.allreduce_wire_bytes_total` counter."""
        from deeplearning4j_tpu.util import telemetry as tm

        wire0 = tm.get_telemetry().counter_total(
            "parallel.allreduce_wire_bytes_total")
        xs, ys = _data(rng, n=64)
        net = _net(comp="threshold", threshold=1e-3, target=1e-2,
                   updater=Sgd(0.05))
        pw = ParallelWrapper(net, mesh=_mesh8(), skew_every=0)
        it = [DataSet(xs[i:i + 8], ys[i:i + 8]) for i in range(0, 64, 8)]
        pw.fit(it, epochs=8)
        stats = pw.compression_stats()
        assert stats["threshold"] > 1e-3  # adapted upward
        assert stats["wire_bytes"] > 0 and stats["ratio"] < 0.1, stats
        assert tm.get_telemetry().counter_total(
            "parallel.allreduce_wire_bytes_total") > wire0
        # sparsity sits inside the adaptive dead band (3x each way),
        # modulo one trailing adjustment step
        sparsity = stats["nnz"] / (stats["workers"] * stats["elements"])
        assert sparsity < 3 * 1e-2 * 1.5, stats

    def test_hierarchical_prices_cross_host_only(self, rng):
        xs, ys = _data(rng)
        net = _net(comp="threshold")
        pw = ParallelWrapper(net, mesh=_mesh8(), skew_every=0,
                             compression_hosts=2)
        pw.fit([DataSet(xs, ys)], epochs=2)
        stats = pw.compression_stats()
        assert stats["workers"] == 2.0  # hosts, not lanes
        assert pw.layout["grad_compression"]["hosts"] == 2

    def test_wrapper_gauges_published(self, rng):
        from deeplearning4j_tpu.util import telemetry as tm

        xs, ys = _data(rng)
        net = _net(comp="threshold")
        pw = ParallelWrapper(net, mesh=_mesh8(), skew_every=0)
        pw.fit([DataSet(xs, ys)], epochs=1)
        pw.compression_stats()  # publish
        tele = tm.get_telemetry()
        metrics = {k[0] for k in tele.gauges}
        assert "parallel.allreduce_wire_bytes" in metrics
        assert "parallel.allreduce_compression_ratio" in metrics


# ---------------------------------------------------------------------------
# 4. convergence parity on a real fit
# ---------------------------------------------------------------------------
@pytest.mark.multichip
class TestConvergenceParity:
    def test_compressed_fit_tracks_exact_fit(self, rng):
        """Same data order, same seeds: the error-feedback compressed fit
        must land in the exact fit's loss neighborhood (nothing lost, only
        delayed)."""
        xs, ys = _data(rng, n=64)
        batches = [DataSet(xs[i:i + 16], ys[i:i + 16])
                   for i in range(0, 64, 16)]

        exact = _net(updater=Sgd(0.1))
        ParallelWrapper(exact, mesh=_mesh8(), deterministic=True,
                        replicas=8, skew_every=0).fit(batches, epochs=15)
        comp = _net(comp="threshold", threshold=1e-3, target=3e-2,
                    updater=Sgd(0.1))
        ParallelWrapper(comp, mesh=_mesh8(), replicas=8,
                        skew_every=0).fit(batches, epochs=15)
        le, lc = float(exact.score_value), float(comp.score_value)
        assert np.isfinite(lc)
        # both learned (initial mcxent ~ ln4 = 1.386) and the compressed
        # endpoint is within tolerance of the exact one
        assert le < 1.0 and lc < 1.0, (le, lc)
        assert abs(lc - le) < 0.25, (le, lc)


# ---------------------------------------------------------------------------
# 5. loss_scale under ParallelWrapper (satellite)
# ---------------------------------------------------------------------------
@pytest.mark.multichip
class TestLossScaleUnderWrapper:
    def test_static_scale_lane_fit_bit_identical_across_meshes(self, rng):
        """The scaled lane step keeps the r12 contract: 8-dev == 1-dev
        BIT-identical with loss_scale='static' on the fused engine."""
        xs, ys = _data(rng)
        nets = []
        for mesh in (_mesh1(), _mesh8()):
            net = _net(fused=True, loss_scale="static")
            ParallelWrapper(net, mesh=mesh, deterministic=True, replicas=8,
                            skew_every=0).fit([DataSet(xs, ys)], epochs=3)
            nets.append(net)
        _assert_tree_equal(nets[0].params, nets[1].params, "params(scaled)")
        _assert_tree_equal(nets[0].opt_states, nets[1].opt_states,
                           "opt(scaled)")

    def test_static_scale_matches_single_host_scaled_path(self, rng):
        """Trajectory test vs the single-host scaled path (the satellite's
        acceptance): same conf fitted through net.fit and through the lane
        wrapper tracks to float tolerance."""
        xs, ys = _data(rng)
        solo = _net(fused=True, loss_scale="static")
        for _ in range(6):
            solo.fit(xs, ys)
        laned = _net(fused=True, loss_scale="static")
        pw = ParallelWrapper(laned, mesh=_mesh8(), deterministic=True,
                             replicas=8, skew_every=0)
        pw.fit([DataSet(xs, ys)], epochs=6)
        for a, b in zip(_leaves(solo.params), _leaves(laned.params)):
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)

    def test_dynamic_scale_automaton_advances_under_wrapper(self, rng):
        xs, ys = _data(rng)
        net = _net(fused=True, loss_scale="dynamic")
        pw = ParallelWrapper(net, mesh=_mesh8(), deterministic=True,
                             replicas=8, skew_every=0)
        pw.fit([DataSet(xs, ys)], epochs=4)
        scale_state = net.opt_states["scale"]
        assert int(scale_state["good"]) == 4  # every step was finite
        assert float(scale_state["scale"]) == 2.0 ** 15
        assert np.isfinite(float(net.score_value))

    def test_masters_still_refuse_scaled_models(self, rng):
        """The guard moved, it did not vanish: a master whose lane grads
        are unscaled must still refuse a scaling policy loudly."""
        net = _net(fused=True, loss_scale="static")
        with pytest.raises(NotImplementedError, match="loss_scale"):
            gspmd.apply_updaters(net, net.params,
                                 jax.tree_util.tree_map(jnp.zeros_like,
                                                        net.params),
                                 net.opt_states, jnp.asarray(0))

    def test_dynamic_plus_compression_rejected(self, rng):
        net = _net(fused=True, loss_scale="dynamic", comp="threshold")
        with pytest.raises(ValueError, match="dynamic"):
            ParallelWrapper(net, mesh=_mesh8(), skew_every=0)

    def test_static_plus_compression_composes(self, rng):
        xs, ys = _data(rng)
        net = _net(fused=True, loss_scale="static", comp="threshold",
                   threshold=0.0)
        pw = ParallelWrapper(net, mesh=_mesh8(), replicas=8, skew_every=0)
        pw.fit([DataSet(xs, ys)], epochs=3)
        exact = _net(fused=True, loss_scale="static")
        ParallelWrapper(exact, mesh=_mesh8(), deterministic=True,
                        replicas=8, skew_every=0).fit([DataSet(xs, ys)],
                                                      epochs=3)
        _assert_tree_equal(exact.params, net.params,
                           "params(scaled, compressed t=0)")


# ---------------------------------------------------------------------------
# 6. cost_report for lane-decomposed wrappers (satellite)
# ---------------------------------------------------------------------------
@pytest.mark.multichip
class TestLaneCostReport:
    def test_deterministic_wrapper_cost_report(self, rng):
        xs, ys = _data(rng)
        net = _net()
        pw = ParallelWrapper(net, mesh=_mesh8(), deterministic=True,
                             replicas=8, skew_every=0)
        pw.fit([DataSet(xs, ys)], epochs=1)
        rep = pw.cost_report(batch_size=32, publish=False)
        assert rep.devices == 8
        if rep.source == "xla":
            assert rep.totals.get("flops", 0) > 0
            tags = {r.layer for r in rep.rows}
            assert any("dense" in t.lower() or "output" in t.lower()
                       or "layer" in t.lower() for t in tags), tags
            assert "(optimizer)" in tags, tags

    def test_compressed_wrapper_cost_report(self, rng):
        xs, ys = _data(rng)
        net = _net(comp="threshold")
        pw = ParallelWrapper(net, mesh=_mesh8(), skew_every=0)
        pw.fit([DataSet(xs, ys)], epochs=1)
        rep = pw.cost_report(batch_size=32, publish=False)
        assert rep.devices == 8
        if rep.source == "xla":
            assert rep.totals.get("flops", 0) > 0


# ---------------------------------------------------------------------------
# 7. residual migration: checkpoint-resume + reshard
# ---------------------------------------------------------------------------
@pytest.mark.multichip
class TestResidualMigration:
    def test_checkpoint_resume_trajectory_exact(self, rng, tmp_path):
        """Stop/restore mid-compressed-fit and continue: the resumed run's
        params, moments, residual, and threshold equal the uninterrupted
        run's bit-for-bit."""
        xs, ys = _data(rng, n=64)
        batches = [DataSet(xs[i:i + 8], ys[i:i + 8])
                   for i in range(0, 64, 8)]
        ckpt = ShardedCheckpointer(str(tmp_path / "ck"), log_fn=None)

        net_a = _net(comp="threshold")
        pw_a = ParallelWrapper(net_a, mesh=_mesh8(), skew_every=0)
        for ds in batches[:4]:
            pw_a.step_batch(ds)
        ckpt.save(net_a.iteration, net_a)
        for ds in batches[4:]:
            pw_a.step_batch(ds)

        net_b = _net(comp="threshold")
        ckpt.restore(net_b)
        assert net_b._grad_comp_state is not None
        pw_b = ParallelWrapper(net_b, mesh=_mesh8(), skew_every=0)
        for ds in batches[4:]:
            pw_b.step_batch(ds)

        _assert_tree_equal(net_a.params, net_b.params, "params(resume)")
        _assert_tree_equal(net_a.opt_states, net_b.opt_states, "opt(resume)")
        _assert_tree_equal(net_a._grad_comp_state, net_b._grad_comp_state,
                           "residual+threshold(resume)")
        # the carried residual is non-trivial (the test would pass
        # vacuously if nothing ever stayed behind)
        assert any(np.asarray(l).any()
                   for l in _leaves(net_a._grad_comp_state))

    def test_checkpoint_without_sidecar_resets_residual(self, rng, tmp_path):
        xs, ys = _data(rng)
        plain = _net()
        ckpt = ShardedCheckpointer(str(tmp_path / "ck"), log_fn=None)
        ckpt.save(0, plain)
        net = _net(comp="threshold")
        pw = ParallelWrapper(net, mesh=_mesh8(), skew_every=0)
        pw.fit([DataSet(xs, ys)], epochs=2)  # residual accumulated
        ckpt.restore(net)
        assert net._grad_comp_state is None
        pw.step_batch(DataSet(xs, ys))  # re-adopts: fresh zeros, no crash
        assert net._grad_comp_state is not None

    def test_reshard_migrates_residual_bit_exact_then_tracks(self, rng):
        """Elastic regroup (8→4 devices) mid-compressed-fit: the lane count
        is fixed, so the residual/threshold MIGRATE BIT-EXACTLY through
        reshard (asserted at the regroup instant), and the continued fit
        tracks the no-regroup run within the r12 lane-fold boundary — a
        2-lanes-per-device shard vectorizes some elementwise tails
        differently than 1-lane-per-device, a pre-existing XLA:CPU
        property measured at ~1 ulp on the UNCOMPRESSED deterministic
        path too (docs/DISTRIBUTED.md)."""
        xs, ys = _data(rng, n=64)
        batches = [DataSet(xs[i:i + 8], ys[i:i + 8])
                   for i in range(0, 64, 8)]

        net_a = _net(comp="threshold")
        pw_a = ParallelWrapper(net_a, mesh=_mesh8(), replicas=8,
                               skew_every=0)
        for ds in batches[:4]:
            pw_a.step_batch(ds)
        mid_state = jax.tree_util.tree_map(np.asarray,
                                           net_a._grad_comp_state)
        for ds in batches[4:]:
            pw_a.step_batch(ds)

        net_b = _net(comp="threshold")
        pw_b = ParallelWrapper(net_b, mesh=_mesh8(), replicas=8,
                               skew_every=0)
        for ds in batches[:4]:
            pw_b.step_batch(ds)
        pw_b.reshard(TrainingMesh(data=4, devices=jax.devices()[:4]))
        # the migration itself is EXACT: nothing about the residual or
        # threshold may change at the regroup boundary
        _assert_tree_equal(mid_state, net_b._grad_comp_state,
                           "residual+threshold at regroup")
        for ds in batches[4:]:
            pw_b.step_batch(ds)

        for a, b in zip(_leaves(net_a.params), _leaves(net_b.params)):
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
        for a, b in zip(_leaves(net_a._grad_comp_state),
                        _leaves(net_b._grad_comp_state)):
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4)

    def test_warmup_does_not_perturb_residual(self, rng):
        """warmup() primes executables on shadow state: the REAL resident
        residual/threshold must come back untouched (the compressed step
        donates its state — a naive warmup would consume and advance
        it)."""
        xs, ys = _data(rng)
        net = _net(comp="threshold")
        pw = ParallelWrapper(net, mesh=_mesh8(), skew_every=0)
        pw.step_batch(DataSet(xs, ys))
        before = jax.tree_util.tree_map(np.asarray, net._grad_comp_state)
        assert pw.warmup([16], input_shape=(6,), label_shape=(4,)) == 1
        _assert_tree_equal(before, net._grad_comp_state, "residual(warmup)")
        pw.step_batch(DataSet(xs, ys))  # still steps fine
        assert np.isfinite(float(net.score_value))

    def test_mismatched_restored_state_fails_loudly(self, rng):
        net = _net(comp="threshold")
        net._grad_comp_state = {"residual": [np.zeros((3, 3), np.float32)],
                                "threshold": np.float32(1e-3)}
        pw = ParallelWrapper(net, mesh=_mesh8(), skew_every=0)
        xs, ys = _data(rng)
        with pytest.raises(ValueError, match="grad-compression state"):
            pw.step_batch(DataSet(xs, ys))


# ---------------------------------------------------------------------------
# 8. knobs: conf round-trip, env default, validation, sidecar format
# ---------------------------------------------------------------------------
class TestKnobsAndFormats:
    def test_conf_json_round_trip_mln(self):
        from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration

        conf = _dense_conf(comp="bitmap", threshold=5e-3, target=1e-2)
        back = MultiLayerConfiguration.from_json(conf.to_json())
        assert back.grad_compression == "bitmap"
        assert back.grad_compression_threshold == 5e-3
        assert back.grad_compression_target == 1e-2

    def test_conf_json_round_trip_cg(self):
        from deeplearning4j_tpu.nn.computation_graph import (
            ComputationGraphConfiguration)

        conf = (NeuralNetConfiguration.builder().seed(3).updater(Adam(0.01))
                .grad_compression("onebit")
                .graph_builder()
                .add_inputs("in")
                .add_layer("out", OutputLayer(n_in=4, n_out=2, loss="mcxent",
                                              activation="softmax"), "in")
                .set_outputs("out")
                .set_input_types(InputType.feed_forward(4))
                .build())
        back = ComputationGraphConfiguration.from_json(conf.to_json())
        assert back.grad_compression == "onebit"

    def test_scheme_validation(self):
        assert validate_scheme(None) is None
        assert validate_scheme("bitmap") == "bitmap"
        with pytest.raises(ValueError, match="grad_compression"):
            validate_scheme("zstd")

    def test_env_default_flows_into_builder(self):
        from deeplearning4j_tpu.config import get_environment

        env = get_environment()
        old = env.default_grad_compression
        try:
            env.default_grad_compression = "bitmap"
            conf = _dense_conf()
            assert conf.grad_compression == "bitmap"
            env.default_grad_compression = "zstd"
            with pytest.raises(ValueError, match="DL4J_TPU_GRAD_COMPRESSION"):
                _dense_conf()
        finally:
            env.default_grad_compression = old

    def test_wrapper_arg_overrides_conf(self, rng):
        net = _net(comp="threshold")
        pw = ParallelWrapper(net, mesh=_mesh8(), skew_every=0,
                             grad_compression="none")
        assert pw._compressor is None
        assert resolve_scheme(None, net.conf) == "threshold"

    def test_sidecar_npz_round_trip(self, tmp_path):
        tree = {"residual": [{"W": np.arange(6, dtype=np.float32)
                              .reshape(2, 3), "b": np.zeros(3)},
                             {}],
                "threshold": np.float32(0.25),
                "none_slot": None}
        path = str(tmp_path / "comp.npz")
        save_tree_npz(path, tree)
        back = load_tree_npz(path)
        assert back["none_slot"] is None
        np.testing.assert_array_equal(back["residual"][0]["W"],
                                      tree["residual"][0]["W"])
        assert float(back["threshold"]) == 0.25
        assert back["residual"][1] == {}

    def test_hosts_must_divide_replicas(self):
        comp = GradCompressor(scheme="threshold", hosts=3)
        with pytest.raises(ValueError, match="divide"):
            comp.exchange_axis(8)

    def test_target_sparsity_threshold_algorithm(self):
        """The proportional-control variant (accumulator.py parity): always
        corrects toward the target — up when too dense, down when too
        sparse — and clips to its bounds."""
        from deeplearning4j_tpu.parallel import (
            TargetSparsityThresholdAlgorithm)

        algo = TargetSparsityThresholdAlgorithm(initial=1e-3,
                                                target_ratio=1e-2,
                                                gain=1.5)
        t = algo.init_state()
        t_up = algo.update(t, jnp.asarray(0.5))    # too dense -> raise
        t_down = algo.update(t, jnp.asarray(1e-4))  # too sparse -> lower
        assert float(t_up) == pytest.approx(1.5e-3)
        assert float(t_down) == pytest.approx(1e-3 / 1.5)
        # converges into a band under alternating pressure, never past
        # the clips
        for _ in range(200):
            t = algo.update(t, jnp.asarray(1.0))
        assert float(t) == algo.max_threshold
        for _ in range(200):
            t = algo.update(t, jnp.asarray(0.0))
        assert float(t) == pytest.approx(algo.min_threshold)
