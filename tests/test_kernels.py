"""Hot-path kernel engine equivalence suite (docs/KERNELS.md).

Every claim the kernel engine makes is proven here against the exact XLA
path, on the CPU container via the Pallas INTERPRETER (``kernel_impl=
"pallas"`` off-TPU == interpret mode — bit-faithful to the kernel's block
program, so kernel==exact proven here holds for the compiled kernel's
math):

- Pallas conv2d forward + input/filter gradients across the
  stride/dilation/groups/padding grid vs ``lax.conv_general_dilated``.
- Fused LSTM cell/sequence (fwd + grads + TBPTT-segment full-fit
  trajectory) vs the exact scan.
- Fused donated optimizer apply: BIT-identical trajectories vs the
  per-leaf walk for SGD/Adam (fp32), composition with the GSPMD
  ParallelWrapper's ZeRO sharding, fp32 master-weight accumulation for
  bf16 param groups, and the dynamic loss-scale step/skip automaton.
- Flash-attention (B, Sk) padding-mask support: masked-vs-exact value and
  gradient equivalence on both the Pallas-interpret and jnp blockwise
  paths (the nn/transformer.py r14 gap burn-down).
- Per-dtype DL4J_TPU_PEAK_FLOPS parsing and the
  ``optimizer_update_share`` report field.

Tolerances: value equivalence 2e-5 absolute on unit-scale inputs (fp32
tap-order reassociation); gradient equivalence 2e-4; full-fit param
trajectories 1e-4 relative after 4 steps (the r12 trajectory-test
convention). Fused-vs-per-leaf fp32 comparisons are exact
(``array_equal``), not allclose — elementwise updater math is
position-independent, so anything less than bit-identity is a bug.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax import lax

from deeplearning4j_tpu.ops import kernels as K
from deeplearning4j_tpu.ops.kernels import conv as kconv
from deeplearning4j_tpu.ops.kernels import lstm as klstm

R = np.random.default_rng(42)


def _leaves(tree):
    return [np.asarray(t) for t in jax.tree_util.tree_leaves(tree)]


def _max_err(a, b):
    return max(float(jnp.max(jnp.abs(x - y))) for x, y in zip(a, b)) \
        if isinstance(a, (list, tuple)) else float(jnp.max(jnp.abs(a - b)))


# ---------------------------------------------------------------------------
# dispatch seam
# ---------------------------------------------------------------------------


class TestDispatch:
    def test_resolution_order(self, monkeypatch):
        monkeypatch.delenv("DL4J_TPU_KERNEL_IMPL", raising=False)
        assert K.resolve_impl() == "auto"
        monkeypatch.setenv("DL4J_TPU_KERNEL_IMPL", "exact")
        assert K.resolve_impl() == "exact"
        with K.impl_scope("pallas"):
            assert K.resolve_impl() == "pallas"
        assert K.resolve_impl() == "exact"

    @pytest.mark.parametrize("backend,forced", [("cpu", "interpret"),
                                                ("tpu", "pallas")])
    def test_auto_without_evidence_is_exact_on_every_backend(
            self, monkeypatch, backend, forced):
        """No tuning-database winner -> auto takes the exact path, on a TPU
        too (PR 21: the chip never ranked these kernels; the one conv it
        timed lost). Forced pallas compiles for real on TPU and interprets
        elsewhere."""
        monkeypatch.delenv("DL4J_TPU_KERNEL_IMPL", raising=False)
        monkeypatch.delenv("DL4J_TPU_TUNING_DB", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        assert K.dispatch(True, op="conv2d", sig="s",
                          dtype="bfloat16") == (None, {})
        assert K.dispatch(True) == (None, {})
        with K.impl_scope("pallas"):
            assert K.dispatch(True) == (forced, {})
            assert K.dispatch(False)[0] is None  # unsupported geometry

    def test_bad_values_raise(self, monkeypatch):
        with pytest.raises(ValueError):
            K.validate_impl("fast")
        monkeypatch.setenv("DL4J_TPU_KERNEL_IMPL", "warp")
        with pytest.raises(ValueError):
            K.resolve_impl()


# ---------------------------------------------------------------------------
# Pallas conv2d vs lax.conv_general_dilated
# ---------------------------------------------------------------------------

_CONV_GRID = [
    # (hw, k, strides, dilation, groups, cin, cout, padding)
    ((9, 9), (3, 3), (1, 1), (1, 1), 1, 4, 6, "SAME"),
    ((10, 8), (3, 2), (2, 2), (1, 1), 1, 4, 6, "VALID"),
    ((11, 11), (3, 3), (2, 1), (2, 2), 2, 4, 6, (1, 2)),
    ((8, 8), (2, 2), (3, 3), (1, 1), 4, 4, 8, "SAME"),   # depthwise-style
    ((7, 7), (1, 1), (1, 1), (1, 1), 1, 3, 5, "VALID"),  # pointwise
    ((12, 6), (5, 3), (1, 2), (2, 1), 1, 2, 4, "SAME"),
]


def _ref_conv(x, w, strides, pads, dil, g):
    dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                    ("NHWC", "HWIO", "NHWC"))
    return lax.conv_general_dilated(
        x, w, strides, list(pads), rhs_dilation=dil, dimension_numbers=dn,
        feature_group_count=g)


class TestPallasConv:
    @pytest.mark.parametrize(
        "hw,k,s,d,g,cin,cout,pad", _CONV_GRID,
        ids=[f"hw{c[0]}k{c[1]}s{c[2]}d{c[3]}g{c[4]}p{c[7]}"
             for c in _CONV_GRID])
    def test_fwd_and_grads_match_exact(self, hw, k, s, d, g, cin, cout, pad):
        x = jnp.asarray(R.normal(size=(2,) + hw + (cin,)).astype(np.float32))
        w = jnp.asarray(
            (R.normal(size=k + (cin // g, cout)) * 0.3).astype(np.float32))
        pads = kconv.resolve_padding(pad, hw, k, s, d)
        out = kconv.conv2d_pallas(x, w, s, pads, d, g, True)
        ref = _ref_conv(x, w, s, pads, d, g)
        assert out.shape == ref.shape
        assert _max_err(out, ref) < 2e-5

        f_p = lambda x, w: jnp.sum(  # noqa: E731
            jnp.sin(kconv.conv2d_pallas(x, w, s, pads, d, g, True)))
        f_r = lambda x, w: jnp.sum(  # noqa: E731
            jnp.sin(_ref_conv(x, w, s, pads, d, g)))
        gp = jax.grad(f_p, argnums=(0, 1))(x, w)
        gr = jax.grad(f_r, argnums=(0, 1))(x, w)
        assert _max_err(list(gp), list(gr)) < 2e-4

    def test_fwd_and_grads_one_case_fast(self):
        """One strided/dilated/grouped case in tier-1 (the full grid runs
        under the CI kernel leg — see the slow mark above)."""
        self.test_fwd_and_grads_match_exact(
            *_CONV_GRID[2][:5], *_CONV_GRID[2][5:])

    def test_ops_conv2d_dispatch(self):
        """ops.nn.conv2d under the forced-pallas scope == exact path,
        including bias and the registry entry point."""
        from deeplearning4j_tpu.ops import nn as nnops

        x = jnp.asarray(R.normal(size=(2, 9, 9, 4)).astype(np.float32))
        w = jnp.asarray((R.normal(size=(3, 3, 4, 6)) * 0.3)
                        .astype(np.float32))
        b = jnp.asarray(R.normal(size=(6,)).astype(np.float32))
        exact = nnops.conv2d(x, w, b, strides=(2, 1), padding="SAME",
                             dilation=(1, 2))
        with K.impl_scope("pallas"):
            pal = nnops.conv2d(x, w, b, strides=(2, 1), padding="SAME",
                               dilation=(1, 2))
        assert _max_err(pal, exact) < 2e-5

    def test_unsupported_geometries_fall_back(self):
        """NCHW / fp64 / preferred_element_type stay on the exact path even
        under forced pallas (supports() gate)."""
        from deeplearning4j_tpu.ops import nn as nnops

        xn = jnp.asarray(R.normal(size=(2, 4, 9, 9)).astype(np.float32))
        wn = jnp.asarray((R.normal(size=(3, 3, 4, 6)) * 0.3)
                         .astype(np.float32))
        with K.impl_scope("pallas"):
            out = nnops.conv2d(xn, wn, data_format="NCHW")
        assert out.shape == (2, 6, 9, 9)
        assert not kconv.supports(xn, wn, "NCHW", 1, None)
        x = jnp.asarray(R.normal(size=(1, 5, 5, 2)).astype(np.float32))
        w = jnp.asarray(R.normal(size=(3, 3, 2, 2)).astype(np.float32))
        assert not kconv.supports(x, w, "NHWC", 1, jnp.float32)

    def test_bf16_inputs_fp32_accumulation(self):
        x = jnp.asarray(R.normal(size=(2, 8, 8, 4))).astype(jnp.bfloat16)
        w = (jnp.asarray(R.normal(size=(3, 3, 4, 8)) * 0.3)
             .astype(jnp.bfloat16))
        pads = kconv.resolve_padding("SAME", (8, 8), (3, 3), (1, 1), (1, 1))
        out = kconv.conv2d_pallas(x, w, (1, 1), pads, (1, 1), 1, True)
        ref = _ref_conv(x, w, (1, 1), pads, (1, 1), 1).astype(jnp.bfloat16)
        assert out.dtype == jnp.bfloat16
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        assert err < 0.1  # bf16 output quantization, fp32 accumulation

    def test_conv_layer_full_fit_trajectory(self):
        """4-step conv-net fit: kernel_impl=pallas trajectory tracks exact
        within 1e-4 relative (the r12 trajectory-test convention)."""
        params = {}
        x = R.normal(size=(8, 10, 10, 3)).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[
            np.random.default_rng(5).integers(0, 4, 8)]
        for impl in ("exact", "pallas"):
            net = _conv_net(impl)
            for _ in range(4):
                net._fit_batch(x, y)
            params[impl] = _leaves(net.params)
        for a, b in zip(params["exact"], params["pallas"]):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


class TestConvRowTiles:
    """Tuned row-tile parameterization (ISSUE 11 satellite): equivalence
    re-proven at two NON-DEFAULT tile points — the autotuner's first
    search space is real, not declared (docs/AUTOTUNE.md)."""

    @pytest.mark.parametrize("row_tile", [1, 2])
    def test_tiled_fwd_and_grads_match_exact(self, row_tile):
        hw, k, s, d, g, cin, cout, pad = _CONV_GRID[1]  # strided, OH=4
        x = jnp.asarray(R.normal(size=(2,) + hw + (cin,)).astype(np.float32))
        w = jnp.asarray(
            (R.normal(size=k + (cin // g, cout)) * 0.3).astype(np.float32))
        pads = kconv.resolve_padding(pad, hw, k, s, d)
        ref = _ref_conv(x, w, s, pads, d, g)
        oh = ref.shape[1]
        assert kconv.valid_row_tile(oh, row_tile), (oh, row_tile)
        out = kconv.conv2d_pallas(x, w, s, pads, d, g, True, row_tile)
        assert _max_err(out, ref) < 2e-5

        f_t = lambda x, w: jnp.sum(jnp.sin(  # noqa: E731
            kconv.conv2d_pallas(x, w, s, pads, d, g, True, row_tile)))
        f_r = lambda x, w: jnp.sum(  # noqa: E731
            jnp.sin(_ref_conv(x, w, s, pads, d, g)))
        gt = jax.grad(f_t, argnums=(0, 1))(x, w)
        gr = jax.grad(f_r, argnums=(0, 1))(x, w)
        assert _max_err(list(gt), list(gr)) < 2e-4

    def test_invalid_tile_raises_and_guard_agrees(self):
        x = jnp.asarray(R.normal(size=(1, 8, 8, 2)).astype(np.float32))
        w = jnp.asarray(R.normal(size=(3, 3, 2, 4)).astype(np.float32))
        pads = kconv.resolve_padding("SAME", (8, 8), (3, 3), (1, 1), (1, 1))
        assert not kconv.valid_row_tile(8, 3)
        with pytest.raises(ValueError, match="row_tile"):
            kconv.conv2d_pallas(x, w, (1, 1), pads, (1, 1), 1, True, 3)
        assert None in kconv.valid_row_tiles(8)
        assert kconv.valid_row_tiles(8)[1:] == [1, 2, 4]


def _conv_net(impl, fused=False, updater=None, seed=3):
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers import (ConvolutionLayer, DenseLayer,
                                              OutputLayer, SubsamplingLayer)
    from deeplearning4j_tpu.nn.updaters import Adam

    b = (NeuralNetConfiguration.builder().seed(seed)
         .updater(updater or Adam(1e-3)).kernel_impl(impl))
    if fused:
        b = b.fused_update(True)
    conf = (b.list()
            .layer(ConvolutionLayer(n_out=6, kernel_size=(3, 3),
                                    stride=(1, 1), activation="relu"))
            .layer(SubsamplingLayer(kernel_size=(2, 2)))
            .layer(ConvolutionLayer(n_out=8, kernel_size=(3, 3),
                                    padding="VALID", activation="relu"))
            .layer(DenseLayer(n_out=16, activation="relu"))
            .layer(OutputLayer(n_in=16, n_out=4))
            .set_input_type(InputType.convolutional(10, 10, 3)).build())
    return MultiLayerNetwork(conf).init()


def _lstm_net(impl, tbptt=0, seed=11):
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.recurrent import LSTM, RnnOutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam

    b = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
         .kernel_impl(impl))
    if tbptt:
        b = b.tbptt_length(tbptt)
    conf = (b.list()
            .layer(LSTM(n_in=6, n_out=12))
            .layer(RnnOutputLayer(n_in=12, n_out=6))
            .set_input_type(InputType.recurrent(6, 8)).build())
    return MultiLayerNetwork(conf).init()


# ---------------------------------------------------------------------------
# fused LSTM cell / sequence
# ---------------------------------------------------------------------------


class TestFusedLstm:
    def _exact_seq(self, xp, h0, c0, U):
        def step(carry, xt):
            h, c = carry
            z = xt + h @ U
            i, f, o, g = jnp.split(z, 4, axis=-1)
            c_new = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
            return (h_new, c_new), h_new

        (hf, cf), ys = lax.scan(step, (h0, c0), xp)
        return ys, (hf, cf)

    def test_cell_and_sequence_match_exact(self):
        T, B, H = 5, 3, 8
        xp = jnp.asarray(R.normal(size=(T, B, 4 * H)).astype(np.float32))
        h0 = jnp.asarray(R.normal(size=(B, H)).astype(np.float32))
        c0 = jnp.asarray(R.normal(size=(B, H)).astype(np.float32))
        U = jnp.asarray((R.normal(size=(H, 4 * H)) * 0.3).astype(np.float32))
        ys, (hf, cf) = klstm.lstm_sequence_fused(
            xp, h0, c0, U, klstm.ORDER_IFOG, "interpret")
        ye, (he, ce) = self._exact_seq(xp, h0, c0, U)
        assert _max_err(ys, ye) < 2e-5
        assert _max_err(cf, ce) < 2e-5

        lk = lambda *a: jnp.sum(jnp.cos(klstm.lstm_sequence_fused(  # noqa
            *a, klstm.ORDER_IFOG, "interpret")[0]))
        le = lambda *a: jnp.sum(jnp.cos(self._exact_seq(*a)[0]))  # noqa
        gk = jax.grad(lk, argnums=(0, 1, 2, 3))(xp, h0, c0, U)
        ge = jax.grad(le, argnums=(0, 1, 2, 3))(xp, h0, c0, U)
        assert _max_err(list(gk), list(ge)) < 2e-4

    @pytest.mark.parametrize("b_tile", [2, 3])
    def test_batch_tiled_cell_matches_exact(self, b_tile):
        """Tuned batch-tile parameterization (ISSUE 11 satellite):
        equivalence re-proven at two NON-DEFAULT tile points, values and
        gradients, through the whole scan-fused sequence path."""
        T, B, H = 4, 6, 8
        xp = jnp.asarray(R.normal(size=(T, B, 4 * H)).astype(np.float32))
        h0 = jnp.asarray(R.normal(size=(B, H)).astype(np.float32))
        c0 = jnp.asarray(R.normal(size=(B, H)).astype(np.float32))
        U = jnp.asarray((R.normal(size=(H, 4 * H)) * 0.3).astype(np.float32))
        assert klstm.valid_b_tile(B, b_tile)
        ys, (hf, cf) = klstm.lstm_sequence_fused(
            xp, h0, c0, U, klstm.ORDER_IFOG, "interpret", b_tile)
        ye, (he, ce) = self._exact_seq(xp, h0, c0, U)
        assert _max_err(ys, ye) < 2e-5
        assert _max_err(cf, ce) < 2e-5

        lk = lambda *a: jnp.sum(jnp.cos(klstm.lstm_sequence_fused(  # noqa
            *a, klstm.ORDER_IFOG, "interpret", b_tile)[0]))
        le = lambda *a: jnp.sum(jnp.cos(self._exact_seq(*a)[0]))  # noqa
        gk = jax.grad(lk, argnums=(0, 1, 2, 3))(xp, h0, c0, U)
        ge = jax.grad(le, argnums=(0, 1, 2, 3))(xp, h0, c0, U)
        assert _max_err(list(gk), list(ge)) < 2e-4
        with pytest.raises(ValueError, match="b_tile"):
            klstm.lstm_cell_fused(xp[0], h0, c0, U, klstm.ORDER_IFOG,
                                  "interpret", 4)

    def test_layer_masked_equivalence(self):
        """nn.recurrent.LSTM with a ragged (B,T) mask: pallas == exact for
        values and gradients (mask passthrough stays in the shared _scan)."""
        from deeplearning4j_tpu.nn.recurrent import LSTM

        lyr = LSTM(n_in=5, n_out=8)
        p, _ = lyr.initialize(jax.random.PRNGKey(0), (None, 5))
        x = jnp.asarray(R.normal(size=(3, 6, 5)).astype(np.float32))
        mask = jnp.asarray((R.random((3, 6)) > 0.3).astype(np.float32))

        def loss(p, impl):
            with K.impl_scope(impl):
                y, _ = lyr.apply_seq(p, x, lyr.init_carry(3), mask=mask)
            return jnp.sum(jnp.sin(y))

        with K.impl_scope("exact"):
            ye, _ = lyr.apply_seq(p, x, lyr.init_carry(3), mask=mask)
        with K.impl_scope("pallas"):
            yp, _ = lyr.apply_seq(p, x, lyr.init_carry(3), mask=mask)
        assert _max_err(yp, ye) < 2e-5
        ge = jax.grad(loss)(p, "exact")
        gp = jax.grad(loss)(p, "pallas")
        assert _max_err(_leaves(gp), _leaves(ge)) < 2e-4

    def test_onnx_lstm_layer_op(self):
        """ops.rnn.lstm_layer (ONNX i,o,f,c gate order + seq_lens) under
        forced pallas == exact."""
        from deeplearning4j_tpu.ops import rnn as rnnops

        T, B, I, H = 6, 3, 5, 7
        x = jnp.asarray(R.normal(size=(T, B, I)).astype(np.float32))
        W = jnp.asarray((R.normal(size=(1, 4 * H, I)) * 0.3)
                        .astype(np.float32))
        Rw = jnp.asarray((R.normal(size=(1, 4 * H, H)) * 0.3)
                         .astype(np.float32))
        b = jnp.asarray((R.normal(size=(1, 8 * H)) * 0.3).astype(np.float32))
        sl = jnp.asarray([6, 4, 2])
        Ye, Yhe, Yce = rnnops.lstm_layer(x, W, Rw, b, sl, hidden_size=H)
        with K.impl_scope("pallas"):
            Yp, Yhp, Ycp = rnnops.lstm_layer(x, W, Rw, b, sl, hidden_size=H)
        assert _max_err(Yp, Ye) < 2e-5
        assert _max_err(Ycp, Yce) < 2e-5

    def test_tbptt_full_fit_trajectory(self):
        """TBPTT-segmented LSTM fit (carries across segments, update per
        segment): pallas trajectory tracks exact within 1e-4."""
        traj = {}
        x = R.normal(size=(4, 8, 6)).astype(np.float32)
        y = np.eye(6, dtype=np.float32)[
            np.random.default_rng(9).integers(0, 6, (4, 8))]
        for impl in ("exact", "pallas"):
            net = _lstm_net(impl, tbptt=4)
            for _ in range(3):
                net._fit_batch(x, y)
            traj[impl] = _leaves(net.params)
        for a, b in zip(traj["exact"], traj["pallas"]):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)

    def test_exotic_activation_falls_back(self):
        """Non-default cell activations have no kernel: supports() is
        False, so forced pallas silently takes the exact path (same
        numbers, no error)."""
        from deeplearning4j_tpu.nn.recurrent import LSTM

        lyr = LSTM(n_in=4, n_out=6, activation="softsign")
        p, _ = lyr.initialize(jax.random.PRNGKey(1), (None, 4))
        x = jnp.asarray(R.normal(size=(2, 5, 4)).astype(np.float32))
        with K.impl_scope("exact"):
            ye, _ = lyr.apply_seq(p, x, lyr.init_carry(2))
        with K.impl_scope("pallas"):
            yp, _ = lyr.apply_seq(p, x, lyr.init_carry(2))
        np.testing.assert_array_equal(np.asarray(ye), np.asarray(yp))


# ---------------------------------------------------------------------------
# fused donated optimizer apply
# ---------------------------------------------------------------------------


class TestFusedOptimizer:
    @pytest.mark.parametrize("updater_name", ["sgd", "adam", "nesterovs",
                                              "rmsprop"])
    def test_bit_trajectory_vs_per_leaf(self, updater_name):
        from deeplearning4j_tpu.nn.updaters import (Adam, Nesterovs, RmsProp,
                                                    Sgd)

        U = {"sgd": Sgd(0.1), "adam": Adam(1e-3),
             "nesterovs": Nesterovs(0.05), "rmsprop": RmsProp(0.01)}[
            updater_name]
        x = R.normal(size=(8, 10, 10, 3)).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[
            np.random.default_rng(1).integers(0, 4, 8)]
        a = _conv_net("exact", fused=False, updater=U)
        b = _conv_net("exact", fused=True, updater=U)
        for _ in range(5):
            a._fit_batch(x, y)
            b._fit_batch(x, y)
        for p, q in zip(_leaves(a.params), _leaves(b.params)):
            np.testing.assert_array_equal(p, q)
        assert float(a.score_value) == float(b.score_value)

    def test_zero_sharded_fused_matches_per_leaf(self):
        """ParallelWrapper + ZeRO over the fused flat buffers == the
        per-leaf wrapper fit (the gspmd.apply_updaters engine branch)."""
        from deeplearning4j_tpu.data import ArrayDataSetIterator
        from deeplearning4j_tpu.parallel import ParallelWrapper, TrainingMesh

        n_dev = min(len(jax.devices()), 8)
        x = R.normal(size=(16, 12)).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[
            np.random.default_rng(2).integers(0, 4, 16)]

        def run(fused):
            from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                               NeuralNetConfiguration)
            from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
            from deeplearning4j_tpu.nn.updaters import Adam

            b = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-3)))
            if fused:
                b = b.fused_update(True)
            conf = (b.list()
                    .layer(DenseLayer(n_in=12, n_out=32, activation="relu"))
                    .layer(OutputLayer(n_in=32, n_out=4))
                    .set_input_type(InputType.feed_forward(12)).build())
            net = MultiLayerNetwork(conf).init()
            pw = ParallelWrapper(
                net, mesh=TrainingMesh(data=n_dev),
                zero_optimizer=True, skew_every=0)
            pw.fit(ArrayDataSetIterator(x, y, batch=16), epochs=3)
            return _leaves(net.params)

        for p, q in zip(run(False), run(True)):
            np.testing.assert_allclose(p, q, rtol=1e-6, atol=1e-7)

    def test_bf16_master_weights(self):
        """bf16 param groups accumulate in an fp32 master: many tiny
        updates that individually round to zero in bf16 must still move
        the params (the mixed-precision raison d'être)."""
        from deeplearning4j_tpu.nn.updaters import FusedUpdateEngine, Sgd

        params = [{"w": jnp.ones((64,), jnp.bfloat16)}]
        grads = [{"w": jnp.full((64,), 1e-4, jnp.bfloat16)}]
        eng = FusedUpdateEngine([Sgd(0.1)], params)
        state = eng.init_state(params)
        assert state["groups"][0]["master"].dtype == jnp.float32
        p = params
        for it in range(200):
            p, state = eng.apply(p, grads, state, jnp.asarray(it))
        # 200 * 0.1 * 1e-4 = 2e-3 drop; a bf16-only accumulator would stay
        # at exactly 1.0 (1.0 - 1e-5 rounds back to 1.0 in bf16). The
        # buffer pads to 512 elements — only the real 64 carry params.
        master = np.asarray(state["groups"][0]["master"])[:64]
        np.testing.assert_allclose(master, 1.0 - 2e-3, rtol=1e-3)
        assert float(p[0]["w"][0].astype(jnp.float32)) < 1.0

    def test_dynamic_loss_scale_step_skip_and_growth(self):
        from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                           NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.nn.updaters import Adam

        conf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-3))
                .fused_update(True)
                .loss_scale("dynamic", value=2.0 ** 8, growth_interval=3)
                .list()
                .layer(DenseLayer(n_in=12, n_out=16, activation="relu"))
                .layer(OutputLayer(n_in=16, n_out=4))
                .set_input_type(InputType.feed_forward(12)).build())
        net = MultiLayerNetwork(conf).init()
        x = R.normal(size=(8, 12)).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[
            np.random.default_rng(3).integers(0, 4, 8)]
        assert float(net.opt_states["scale"]["scale"]) == 2.0 ** 8
        # poisoned batch: the step must be SKIPPED (params bit-unchanged)
        # and the scale halved
        xn = x.copy()
        xn[0, 0] = np.nan
        before = _leaves(net.params)
        net._fit_batch(xn, y)
        after = _leaves(net.params)
        for p, q in zip(before, after):
            np.testing.assert_array_equal(p, q)
        assert float(net.opt_states["scale"]["scale"]) == 2.0 ** 7
        # 3 clean steps: params move and the scale grows back
        net._fit_batch(x, y)
        moved = _leaves(net.params)
        assert any(not np.array_equal(p, q) for p, q in zip(after, moved))
        net._fit_batch(x, y)
        net._fit_batch(x, y)
        assert float(net.opt_states["scale"]["scale"]) == 2.0 ** 8

    def test_static_scale_matches_unscaled(self):
        """Static loss scaling is numerically transparent for fp32: the
        scaled-then-unscaled trajectory tracks the unscaled one."""
        from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                           NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.nn.updaters import Sgd

        def build(policy):
            b = (NeuralNetConfiguration.builder().seed(7).updater(Sgd(0.1))
                 .fused_update(True))
            if policy:
                b = b.loss_scale("static", value=2.0 ** 10)
            conf = (b.list()
                    .layer(DenseLayer(n_in=12, n_out=16, activation="tanh"))
                    .layer(OutputLayer(n_in=16, n_out=4))
                    .set_input_type(InputType.feed_forward(12)).build())
            return MultiLayerNetwork(conf).init()

        x = R.normal(size=(8, 12)).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[
            np.random.default_rng(4).integers(0, 4, 8)]
        a, b = build(False), build(True)
        for _ in range(4):
            a._fit_batch(x, y)
            b._fit_batch(x, y)
        for p, q in zip(_leaves(a.params), _leaves(b.params)):
            np.testing.assert_allclose(p, q, rtol=1e-5, atol=1e-6)
        # the reported loss is the UNSCALED one
        np.testing.assert_allclose(float(a.score_value),
                                   float(b.score_value), rtol=1e-5)

    def test_loss_scale_requires_fused(self):
        from deeplearning4j_tpu.nn import NeuralNetConfiguration

        with pytest.raises(ValueError, match="fused_update"):
            NeuralNetConfiguration.builder().loss_scale("dynamic")

    def test_conf_json_round_trip(self):
        from deeplearning4j_tpu.nn import (InputType,
                                           NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
        from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer

        conf = (NeuralNetConfiguration.builder().seed(1)
                .kernel_impl("pallas").fused_update(True)
                .loss_scale("dynamic", value=1024.0, growth_interval=7)
                .list()
                .layer(DenseLayer(n_in=4, n_out=4))
                .layer(OutputLayer(n_in=4, n_out=2))
                .set_input_type(InputType.feed_forward(4)).build())
        rt = MultiLayerConfiguration.from_json(conf.to_json())
        assert rt.kernel_impl == "pallas"
        assert rt.fused_update is True
        assert rt.loss_scale == "dynamic"
        assert rt.loss_scale_value == 1024.0
        assert rt.loss_scale_growth == 7

    def test_fused_state_serializes(self, tmp_path):
        """ModelSerializer round-trips the fused optimizer state (the flat
        buffers + scale automaton are ordinary pytree leaves)."""
        from deeplearning4j_tpu.util.model_serializer import ModelSerializer

        net = _conv_net("exact", fused=True)
        x = R.normal(size=(8, 10, 10, 3)).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[
            np.random.default_rng(6).integers(0, 4, 8)]
        net._fit_batch(x, y)
        path = str(tmp_path / "fused.zip")
        ModelSerializer.write_model(net, path, save_updater=True)
        restored = ModelSerializer.restore_multi_layer_network(
            path, load_updater=True)
        for p, q in zip(_leaves(net.opt_states),
                        _leaves(restored.opt_states)):
            np.testing.assert_array_equal(p, q)
        # both continue to the SAME next step
        net._fit_batch(x, y)
        restored._fit_batch(x, y)
        for p, q in zip(_leaves(net.params), _leaves(restored.params)):
            np.testing.assert_array_equal(p, q)

    def test_restore_without_updater_state_resyncs_masters(self, tmp_path):
        """Loading a fused model WITHOUT updater state must resync the
        resident master buffers to the loaded params — otherwise the first
        fit() step snaps the trained weights back to init()'s randoms
        (review finding, r14)."""
        from deeplearning4j_tpu.util.model_serializer import ModelSerializer

        net = _conv_net("exact", fused=True)
        x = R.normal(size=(8, 10, 10, 3)).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[
            np.random.default_rng(8).integers(0, 4, 8)]
        for _ in range(3):
            net._fit_batch(x, y)
        path = str(tmp_path / "fused_no_upd.zip")
        ModelSerializer.write_model(net, path, save_updater=False)
        restored = ModelSerializer.restore_multi_layer_network(path)
        trained = _leaves(restored.params)
        for t, p in zip(trained, _leaves(net.params)):
            np.testing.assert_array_equal(t, p)
        restored._fit_batch(x, y)
        # one fresh-moment Adam step moves params ~lr; a master desync
        # would jump them all the way back to the random init (~0.1)
        for t, a in zip(trained, _leaves(restored.params)):
            assert float(np.max(np.abs(t - a))) < 0.02


# ---------------------------------------------------------------------------
# flash-attention padding mask (satellite 1)
# ---------------------------------------------------------------------------


class TestFlashPaddingMask:
    def _qkv(self, B=2, H=3, S=16, D=8):
        mk = lambda: jnp.asarray(  # noqa: E731
            R.normal(size=(B, H, S, D)).astype(np.float32))
        mask = np.ones((B, S), np.float32)
        mask[0, 10:] = 0.0
        mask[1, 3:] = 0.0
        return mk(), mk(), mk(), jnp.asarray(mask)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("use_pallas", ["interpret", False],
                             ids=["pallas-interpret", "jnp-blockwise"])
    def test_masked_matches_exact(self, causal, use_pallas):
        from deeplearning4j_tpu.ops.attention import (dot_product_attention,
                                                      flash_attention)

        q, k, v, mask = self._qkv()
        out = flash_attention(q, k, v, causal=causal, block_q=8, block_k=8,
                              use_pallas=use_pallas, mask=mask)
        ref = dot_product_attention(q, k, v, mask=mask[:, None, None, :],
                                    causal=causal)
        assert _max_err(out, ref) < 2e-5

    def test_masked_gradients_match_exact(self):
        from deeplearning4j_tpu.ops.attention import (dot_product_attention,
                                                      flash_attention)

        q, k, v, mask = self._qkv()
        f1 = lambda q, k, v: jnp.sum(jnp.sin(flash_attention(  # noqa: E731
            q, k, v, block_q=8, block_k=8, use_pallas="interpret",
            mask=mask)))
        f2 = lambda q, k, v: jnp.sum(jnp.sin(dot_product_attention(  # noqa
            q, k, v, mask=mask[:, None, None, :])))
        g1 = jax.grad(f1, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f2, argnums=(0, 1, 2))(q, k, v)
        assert _max_err(list(g1), list(g2)) < 2e-4

    def test_resolve_flash_accepts_padding_masks(self):
        from deeplearning4j_tpu.ops.attention import resolve_flash

        pad = jnp.ones((2, 16))
        full = jnp.ones((2, 1, 16, 16))
        assert resolve_flash(True, 16, 16, pad) is True
        assert resolve_flash(True, 16, 16, full) is False

    def test_mha_masked_flash_vs_exact(self):
        from deeplearning4j_tpu.ops.attention import (
            multi_head_dot_product_attention)

        B, T, F, Hh = 2, 16, 24, 4
        xq = jnp.asarray(R.normal(size=(B, T, F)).astype(np.float32))
        Ws = [jnp.asarray((R.normal(size=(F, F)) * 0.2).astype(np.float32))
              for _ in range(4)]
        mask = np.ones((B, T), np.float32)
        mask[0, 9:] = 0.0
        mask = jnp.asarray(mask)
        o_flash = multi_head_dot_product_attention(
            xq, xq, xq, *Ws, n_heads=Hh, mask=mask, flash=True)
        o_exact = multi_head_dot_product_attention(
            xq, xq, xq, *Ws, n_heads=Hh, mask=mask, flash=False)
        assert _max_err(o_flash, o_exact) < 2e-5


# ---------------------------------------------------------------------------
# per-dtype peak FLOPs + optimizer update share (satellites)
# ---------------------------------------------------------------------------


class TestPeakFlopsTable:
    def test_bare_number(self, monkeypatch):
        from deeplearning4j_tpu.util import cost_model as cm

        monkeypatch.setenv("DL4J_TPU_PEAK_FLOPS", "1.97e14")
        assert cm.peak_flops_from_env() == 1.97e14
        assert cm.peak_flops_from_env("bfloat16") == 1.97e14

    def test_dtype_table(self, monkeypatch):
        from deeplearning4j_tpu.util import cost_model as cm

        monkeypatch.setenv("DL4J_TPU_PEAK_FLOPS",
                           "bf16=1.97e14, fp32=9.85e13")
        assert cm.peak_flops_from_env("bfloat16") == 1.97e14
        assert cm.peak_flops_from_env("bf16") == 1.97e14
        assert cm.peak_flops_from_env("float32") == 9.85e13
        # no dtype: multi-entry table falls back to the fp32 entry
        assert cm.peak_flops_from_env() == 9.85e13
        # unknown dtype: no silent guesses
        assert cm.peak_flops_from_env("int4") is None

    def test_garbage_degrades_to_none(self, monkeypatch):
        from deeplearning4j_tpu.util import cost_model as cm

        monkeypatch.setenv("DL4J_TPU_PEAK_FLOPS", "fast")
        assert cm.peak_flops_from_env("bf16") is None
        monkeypatch.setenv("DL4J_TPU_PEAK_FLOPS", "bf16=oops")
        assert cm.peak_flops_from_env("bf16") is None

    def test_mfu_uses_dtype_peak(self, monkeypatch):
        """A bf16 net's cost_report computes MFU against the bf16 entry."""
        from deeplearning4j_tpu.util import cost_model as cm

        monkeypatch.setenv("DL4J_TPU_PEAK_FLOPS",
                           "bf16=2e14,fp32=1e14")
        assert cm.peak_flops_from_env("bfloat16") == 2e14

    def test_optimizer_update_share(self):
        from deeplearning4j_tpu.util.cost_model import (OPTIMIZER_ROW,
                                                        CostReport, CostRow)

        rows = [
            CostRow(layer="0_conv", device_time_fwd_s=0.006,
                    device_time_bwd_s=0.012),
            CostRow(layer=OPTIMIZER_ROW, device_time_fwd_s=0.002),
        ]
        rep = CostReport(rows=rows, totals={}, batch=8, params_total=1,
                         source="xla")
        assert abs(rep.optimizer_update_share - 0.1) < 1e-12
        assert rep.to_dict()["optimizer_update_share"] == \
            rep.optimizer_update_share
        # no profiled times -> None, never a guess
        rep2 = CostReport(rows=[CostRow(layer="0_conv")], totals={},
                          batch=8, params_total=1, source="xla")
        assert rep2.optimizer_update_share is None


# ---------------------------------------------------------------------------
# Pallas -> Mosaic cross-lowering (no chip needed)
# ---------------------------------------------------------------------------


def _lower_for_tpu(fn, *avals):
    """StableHLO text of ``fn`` cross-lowered for the TPU platform with the
    backend tests answering "tpu": exercises the installed JAX's
    Pallas->Mosaic lowering (BlockSpec rules, vector-op support), not the
    chip's compiler — what the chip's compiler said is in docs/KERNELS.md."""
    from jax import export

    return export.export(jax.jit(fn), platforms=["tpu"])(
        *avals).mlir_module()


def _aval(*shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _value_and_grads(fn, n):
    def loss(*a):
        return jnp.sum(fn(*a).astype(jnp.float32))
    return jax.value_and_grad(loss, argnums=tuple(range(n)))


class TestMosaicCrossLowering:
    """Every Pallas kernel the seams can reach, at the r05 shapes, forward
    and gradient: a lowering refusal is caught here from now on (PR 21 found
    the stride-2 conv and the B>1 flash padding mask refused on first
    contact with a TPU host)."""

    @pytest.fixture(autouse=True)
    def _tpu_backend(self, monkeypatch):
        monkeypatch.delenv("DL4J_TPU_KERNEL_IMPL", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    @pytest.mark.parametrize("masked", [False, True],
                             ids=["unmasked", "padding-mask"])
    def test_flash_attention_b4_seq2048(self, masked):
        from deeplearning4j_tpu.ops.attention import flash_attention

        qkv = _aval(4, 12, 2048, 64)
        if masked:
            fn = lambda q, k, v, m: flash_attention(  # noqa: E731
                q, k, v, causal=True, mask=m)
            avals = (qkv, qkv, qkv, _aval(4, 2048, dtype=jnp.float32))
        else:
            fn = lambda q, k, v: flash_attention(  # noqa: E731
                q, k, v, causal=True)
            avals = (qkv, qkv, qkv)
        assert "tpu_custom_call" in _lower_for_tpu(
            _value_and_grads(fn, 3), *avals)

    @pytest.mark.parametrize("b_tile", [None, 32], ids=["whole", "bt32"])
    def test_lstm_cell_b128_h512(self, b_tile):
        h0 = jnp.zeros((128, 512), jnp.bfloat16)
        fn = lambda xp, u: klstm.lstm_sequence_fused(  # noqa: E731
            xp, h0, h0, u, klstm.ORDER_IFOG, "pallas", b_tile)[0]
        assert "tpu_custom_call" in _lower_for_tpu(
            _value_and_grads(fn, 2), _aval(4, 128, 2048), _aval(512, 2048))

    @pytest.mark.parametrize("x_shape,w_shape", [
        ((8, 56, 56, 64), (3, 3, 64, 64)),
        ((8, 56, 56, 64), (1, 1, 64, 256)),
        ((8, 28, 28, 128), (3, 3, 128, 128)),
    ], ids=["3x3-56", "1x1-56", "3x3-28"])
    def test_conv2d_stride1_through_the_seam(self, x_shape, w_shape):
        from deeplearning4j_tpu.ops import nn as nnops

        def fn(x, w):
            with K.impl_scope("pallas"):
                return nnops.conv2d(x, w)

        assert "tpu_custom_call" in _lower_for_tpu(
            _value_and_grads(fn, 2), _aval(*x_shape), _aval(*w_shape))

    def test_conv2d_stride2_forced_pallas_raises(self):
        """Mosaic (JAX 0.9.0) has no strided vector slice, so the strided
        tap windows of conv.py cannot lower. Forced pallas says so with
        the compiler's own error; auto never gets here (no database
        winner can exist for a program that does not compile)."""
        from deeplearning4j_tpu.ops import nn as nnops

        def fn(x, w):
            with K.impl_scope("pallas"):
                return nnops.conv2d(x, w, strides=(2, 2))

        with pytest.raises(Exception, match="strided_slice|stride"):
            _lower_for_tpu(fn, _aval(8, 56, 56, 256), _aval(1, 1, 256, 128))

    def test_kda_prefill_at_the_cells_shape(self, monkeypatch):
        """``kimiL-chat-open``'s prefill bucket, one layer: 16 rows x 1,024
        positions, 32 heads of 128. On a TPU the chunk walk is one Mosaic
        kernel; the CPU program of the same call holds none."""
        from jax import export

        from deeplearning4j_tpu.ops import kda

        f32 = lambda *shape: _aval(*shape, dtype=jnp.float32)
        x = f32(16, 1024, 32, 128)
        avals = (x, x, x, x, f32(16, 1024, 32), f32(16, 32, 128, 128),
                 _aval(16, dtype=jnp.int32))
        text = _lower_for_tpu(kda.kda_chunked, *avals)
        assert text.count("tpu_custom_call") == 1
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        again = lambda *a: kda.kda_chunked(*a)    # not the cached trace
        text = export.export(jax.jit(again), platforms=["cpu"])(
            *avals).mlir_module()
        assert "custom_call" not in text

    @pytest.mark.parametrize("backend,dk,dv", [
        ("tpu", 16, 8), ("tpu", 128, 64), ("cpu", 128, 128)],
        ids=["tpu-16x8", "tpu-128x64", "cpu-128x128"])
    def test_kda_prefill_elsewhere_is_the_xla_form(self, monkeypatch,
                                                   backend, dk, dv):
        """Heads that are not whole 128-lane tiles, and any backend but the
        TPU, take the XLA form: read from the platform and the shape."""
        from deeplearning4j_tpu.ops import kda

        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        f32 = lambda *shape: _aval(*shape, dtype=jnp.float32)
        text = _lower_for_tpu(
            kda.kda_chunked, f32(2, 128, 2, dk), f32(2, 128, 2, dk),
            f32(2, 128, 2, dv), f32(2, 128, 2, dk), f32(2, 128, 2),
            f32(2, 2, dk, dv), _aval(2, dtype=jnp.int32))
        assert "tpu_custom_call" not in text

    def test_kda_decode_at_the_cells_shape(self, monkeypatch):
        """``kimiL-chat-open``'s decode bucket, one layer: 16 rows of one
        token against a pool of 17 slots, 32 heads of 128. On a TPU the
        step is one Mosaic kernel whose pool result aliases its pool
        operand, and no gather or scatter of the pool is left beside it;
        the CPU program of the same call holds no kernel."""
        from jax import export

        from deeplearning4j_tpu.ops import kda

        f32 = lambda *shape: _aval(*shape, dtype=jnp.float32)
        x = f32(16, 1, 32, 128)
        avals = (x, x, x, x, f32(16, 1, 32), f32(17, 32, 128, 128),
                 _aval(16, dtype=jnp.int32), _aval(16, 1, dtype=jnp.bool_))
        text = _lower_for_tpu(kda.kda_step_paged, *avals)
        assert text.count("tpu_custom_call") == 1
        assert "output_operand_aliases" in text
        assert "stablehlo.gather" not in text
        assert "stablehlo.scatter" not in text
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        again = lambda *a: kda.kda_step_paged(*a)   # not the cached trace
        text = export.export(jax.jit(again), platforms=["cpu"])(
            *avals).mlir_module()
        assert "custom_call" not in text

    @pytest.mark.parametrize("backend,w,dk,dv", [
        ("tpu", 1, 16, 8), ("tpu", 1, 128, 64), ("tpu", 2, 128, 128),
        ("cpu", 1, 128, 128)],
        ids=["tpu-16x8", "tpu-128x64", "tpu-window-2", "cpu-128x128"])
    def test_kda_decode_elsewhere_is_the_xla_form(self, monkeypatch, backend,
                                                  w, dk, dv):
        """Heads that are not whole 128-lane tiles, a window of more than
        one token, and any backend but the TPU gather, step and scatter."""
        from deeplearning4j_tpu.ops import kda

        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        f32 = lambda *shape: _aval(*shape, dtype=jnp.float32)
        text = _lower_for_tpu(
            kda.kda_step_paged, f32(4, w, 2, dk), f32(4, w, 2, dk),
            f32(4, w, 2, dv), f32(4, w, 2, dk), f32(4, w, 2),
            f32(5, 2, dk, dv), _aval(4, dtype=jnp.int32),
            _aval(4, w, dtype=jnp.bool_))
        assert "tpu_custom_call" not in text
        assert "stablehlo.scatter" in text

    def test_ssm_scan_at_the_cells_shape(self, monkeypatch):
        """``jamba2-chat-open``'s prefill bucket, one layer: 64 rows x 256
        positions, 5,120 channels of 16 states. On a TPU the scan is one
        Mosaic kernel and no (rows, positions, channels, states) array is
        made; the CPU program of the same call holds no kernel."""
        from jax import export

        from deeplearning4j_tpu.ops import ssm

        f32 = lambda *shape: _aval(*shape, dtype=jnp.float32)
        x = f32(64, 256, 5120)
        avals = (x, x, f32(5120, 16), f32(64, 256, 16), f32(64, 256, 16),
                 f32(5120), f32(64, 16, 5120), _aval(64, dtype=jnp.int32), x)
        text = _lower_for_tpu(ssm.selective_scan, *avals)
        assert text.count("tpu_custom_call") == 1
        assert "64x256x5120x16" not in text and "64x256x16x5120" not in text
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        again = lambda *a: ssm.selective_scan(*a)   # not the cached trace
        text = export.export(jax.jit(again), platforms=["cpu"])(
            *avals).mlir_module()
        assert "custom_call" not in text
        assert "64x256x5120x16" not in text and "64x256x16x5120" not in text

    def test_ssm_step_at_the_cells_shape(self, monkeypatch):
        """``jamba2-chat-open``'s decode bucket, one layer: 64 rows of one
        token against a pool of 65 slots. On a TPU the step is one Mosaic
        kernel whose pool result aliases its pool operand, and no gather or
        scatter of the pool is left beside it; the CPU program of the same
        call holds no kernel."""
        from jax import export

        from deeplearning4j_tpu.ops import ssm

        f32 = lambda *shape: _aval(*shape, dtype=jnp.float32)
        x = f32(64, 1, 5120)
        avals = (x, x, f32(5120, 16), f32(64, 1, 16), f32(64, 1, 16),
                 f32(5120), f32(65, 16, 5120), _aval(64, dtype=jnp.int32),
                 _aval(64, 1, dtype=jnp.bool_), x)
        text = _lower_for_tpu(ssm.selective_step_paged, *avals)
        assert text.count("tpu_custom_call") == 1
        assert "output_operand_aliases" in text
        assert "stablehlo.gather" not in text
        assert "stablehlo.scatter" not in text
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        again = lambda *a: ssm.selective_step_paged(*a)
        text = export.export(jax.jit(again), platforms=["cpu"])(
            *avals).mlir_module()
        assert "custom_call" not in text

    @pytest.mark.parametrize("backend,w,ch", [
        ("tpu", 1, 192), ("tpu", 2, 256), ("cpu", 1, 256)],
        ids=["tpu-192-channels", "tpu-window-2", "cpu-256"])
    def test_ssm_elsewhere_is_the_xla_form(self, monkeypatch, backend, w, ch):
        """Channels that are not whole 128-lane tiles, a window of more than
        one token, and any backend but the TPU gather, step and scatter."""
        from deeplearning4j_tpu.ops import ssm

        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        f32 = lambda *shape: _aval(*shape, dtype=jnp.float32)
        text = _lower_for_tpu(
            ssm.selective_step_paged, f32(4, w, ch), f32(4, w, ch),
            f32(ch, 8), f32(4, w, 8), f32(4, w, 8), f32(ch), f32(5, 8, ch),
            _aval(4, dtype=jnp.int32), _aval(4, w, dtype=jnp.bool_),
            f32(4, w, ch))
        assert "tpu_custom_call" not in text
        assert "stablehlo.scatter" in text

    @pytest.mark.parametrize("cell,rows,c,pool", [
        ("jamba2-chat-open", 64, 5120, (65, 3 * 5120)),
        ("kimiL-chat-open", 16, 12288, (17, 3, 12288))])
    def test_conv_step_at_the_cells_shape(self, monkeypatch, cell, rows, c,
                                          pool):
        """One state layer's tail pool of each serving cell, as its
        ``init_pool`` makes it: Jamba's flat (a tile of it holds 8 slots, so
        the kernel walks groups of 8 and Pallas would refuse a one-row
        block), Kimi's a slot a block. On a TPU the step is one Mosaic
        kernel whose pool result aliases its pool operand and no scatter
        is left beside it; the CPU program of the same call holds no
        kernel."""
        from jax import export

        from deeplearning4j_tpu.ops import kda

        f32 = lambda *shape: _aval(*shape, dtype=jnp.float32)
        avals = (f32(rows, 1, c), f32(4, c), f32(*pool),
                 _aval(rows, dtype=jnp.int32),
                 _aval(rows, 1, dtype=jnp.bool_))
        text = _lower_for_tpu(kda.conv_step_paged, *avals)
        assert text.count("tpu_custom_call") == 1
        assert "output_operand_aliases" in text
        assert "stablehlo.scatter" not in text
        # the rows' inputs into slot order and y back: of a flat pool only
        assert ('"stablehlo.gather"' in text) == (len(pool) == 2)
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        again = lambda *a: kda.conv_step_paged(*a)  # not the cached trace
        text = export.export(jax.jit(again), platforms=["cpu"])(
            *avals).mlir_module()
        assert "custom_call" not in text

    @pytest.mark.parametrize("backend,w,c", [
        ("tpu", 1, 192), ("tpu", 2, 256), ("cpu", 1, 256)],
        ids=["tpu-192-channels", "tpu-window-2", "cpu-256"])
    def test_conv_step_elsewhere_is_the_xla_form(self, monkeypatch, backend,
                                                 w, c):
        """Channels that are not whole 128-lane tiles, a window of more than
        one token, and any backend but the TPU gather, convolve and
        scatter."""
        from deeplearning4j_tpu.ops import kda

        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        f32 = lambda *shape: _aval(*shape, dtype=jnp.float32)
        text = _lower_for_tpu(
            kda.conv_step_paged, f32(4, w, c), f32(4, c), f32(5, 3 * c),
            _aval(4, dtype=jnp.int32), _aval(4, w, dtype=jnp.bool_))
        assert "tpu_custom_call" not in text
        assert "stablehlo.scatter" in text

    def test_resnet50_forward_reaches_no_kernel(self):
        """The flagship at its default conf on a TPU host: 53 convolutions,
        every one on the exact path — before PR 21, 51 of them routed to
        conv2d_pallas and the first stride-2 one failed to lower, so
        fit() could not even trace."""
        from deeplearning4j_tpu.zoo import ResNet50

        net = ResNet50(num_classes=1000, input_shape=(224, 224, 3),
                       compute_dtype="bfloat16").init()
        shapes = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
        text = _lower_for_tpu(net.make_forward_fn(), shapes(net.params),
                              shapes(net.states),
                              _aval(2, 224, 224, 3, dtype=jnp.float32))
        assert text.count("stablehlo.convolution") == 53
        assert "tpu_custom_call" not in text
