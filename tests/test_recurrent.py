"""Recurrent layer tests — LSTMGradientCheckTests / GravesLSTMTest /
MaskingTests parity (SURVEY.md §4: every layer type has a gradcheck; masks
for variable-length sequences)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.autodiff import gradcheck
from deeplearning4j_tpu.data import DataSet
from deeplearning4j_tpu.nn import (
    InputType,
    MultiLayerNetwork,
    NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.layers import GlobalPoolingLayer, OutputLayer
from deeplearning4j_tpu.nn.recurrent import (
    GRU,
    LSTM,
    Bidirectional,
    GravesLSTM,
    LastTimeStep,
    RnnOutputLayer,
    SimpleRnn,
)
from deeplearning4j_tpu.nn.updaters import Adam


B, T, F, H = 2, 5, 3, 4


@pytest.mark.parametrize("layer_cls", [LSTM, GravesLSTM, GRU, SimpleRnn])
def test_recurrent_gradcheck(layer_cls, rng):
    lyr = layer_cls(n_in=F, n_out=H)
    params, state = lyr.initialize(jax.random.PRNGKey(0), (T, F))
    x = jnp.asarray(rng.standard_normal((B, T, F)))

    def loss(p):
        y, _ = lyr.apply(p, state, x.astype(jax.tree_util.tree_leaves(p)[0].dtype),
                         training=True)
        return jnp.sum(y ** 2)

    # jitted: the finite differences call the loss some 200 times, and an
    # eager scan compiles its body anew on every call (35 s for the LSTM,
    # which is why GravesLSTM, GRU and the bidirectional check below used to
    # be marked slow)
    res = gradcheck.check_model_gradients(jax.jit(loss), params)
    assert res.passed, res


def test_bidirectional_gradcheck_and_shape(rng):
    lyr = Bidirectional(layer=LSTM(n_in=F, n_out=H))
    params, state = lyr.initialize(jax.random.PRNGKey(0), (T, F))
    x = jnp.asarray(rng.standard_normal((B, T, F)))
    y, _ = lyr.apply(params, state, x)
    assert y.shape == (B, T, 2 * H)

    def loss(p):
        out, _ = lyr.apply(p, state, x.astype(jax.tree_util.tree_leaves(p)[0].dtype),
                           training=True)
        return jnp.sum(out ** 2)

    res = gradcheck.check_model_gradients(jax.jit(loss), params)
    assert res.passed, res


def test_mask_state_passthrough(rng):
    """Masked steps must not advance the hidden state: the output at the last
    valid step equals the run on the trimmed sequence."""
    lyr = LSTM(n_in=F, n_out=H)
    params, _ = lyr.initialize(jax.random.PRNGKey(1), (T, F))
    x = jnp.asarray(rng.standard_normal((1, T, F)).astype(np.float32))
    n_valid = 3
    mask = jnp.asarray((np.arange(T) < n_valid)[None].astype(np.float32))
    full, _ = lyr.apply(params, {}, x, mask=mask)
    trimmed, _ = lyr.apply(params, {}, x[:, :n_valid])
    np.testing.assert_allclose(full[:, n_valid - 1], trimmed[:, -1], rtol=2e-5, atol=1e-6)
    # masked tail emits zeros (DL4J zeroes masked activations); the carried
    # state is held, so a later valid step would resume from step n_valid-1
    np.testing.assert_allclose(full[:, n_valid:], np.zeros_like(full[:, n_valid:]))


def test_last_time_step_masked(rng):
    lyr = LastTimeStep()
    x = jnp.asarray(rng.standard_normal((2, 4, 3)).astype(np.float32))
    mask = jnp.asarray(np.array([[1, 1, 0, 0], [1, 1, 1, 1]], np.float32))
    y, _ = lyr.apply({}, {}, x, mask=mask)
    np.testing.assert_allclose(y[0], x[0, 1])
    np.testing.assert_allclose(y[1], x[1, 3])


def _seq_net(last=None):
    return (
        NeuralNetConfiguration.builder()
        .seed(7)
        .updater(Adam(0.02))
        .list()
        .layer(LSTM(n_in=F, n_out=8))
        .layer(last or LastTimeStep())
        .layer(OutputLayer(n_in=8, n_out=2, loss="mcxent", activation="softmax"))
        .set_input_type(InputType.recurrent(F, T))
        .build()
    )


def test_masked_fit_and_output(rng):
    """End-to-end variable-length sequence classification with feature masks
    through MultiLayerNetwork.fit/output (setLayerMaskArrays parity)."""
    n = 64
    lengths = rng.integers(2, T + 1, n)
    xs = rng.standard_normal((n, T, F)).astype(np.float32)
    mask = (np.arange(T)[None] < lengths[:, None]).astype(np.float32)
    xs = xs * mask[:, :, None]
    # label: sign of mean of first feature over valid steps
    means = (xs[:, :, 0] * mask).sum(1) / mask.sum(1)
    labels = (means > 0).astype(int)
    ys = np.eye(2, dtype=np.float32)[labels]

    net = MultiLayerNetwork(_seq_net()).init()
    ds = DataSet(xs, ys, features_mask=mask)
    for _ in range(60):
        net._fit_batch(jnp.asarray(xs), jnp.asarray(ys), mask=jnp.asarray(mask))
    out = np.asarray(net.output(xs, mask=mask))
    acc = (out.argmax(1) == labels).mean()
    assert acc > 0.9, acc

    # masked output must be independent of padding values
    xs2 = xs + (1 - mask[:, :, None]) * 100.0
    out2 = np.asarray(net.output(xs2, mask=mask))
    np.testing.assert_allclose(out, out2, rtol=2e-4, atol=1e-5)


def test_fit_from_dataset_with_masks(rng):
    xs = rng.standard_normal((8, T, F)).astype(np.float32)
    ys = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 8)]
    mask = np.ones((8, T), np.float32)
    mask[:, -2:] = 0
    net = MultiLayerNetwork(_seq_net()).init()
    net.fit([DataSet(xs, ys, features_mask=mask)], epochs=2)
    assert np.isfinite(float(net.score_))


def test_bidirectional_l2_in_network(rng):
    """Bidirectional's nested fwd/bwd params must not break regularization."""
    conf = (
        NeuralNetConfiguration.builder()
        .seed(3)
        .updater(Adam(0.01))
        .l2(1e-3)
        .list()
        .layer(Bidirectional(layer=LSTM(n_in=F, n_out=4)))
        .layer(GlobalPoolingLayer(pooling_type="avg"))
        .layer(OutputLayer(n_in=8, n_out=2, loss="mcxent", activation="softmax"))
        .set_input_type(InputType.recurrent(F, T))
        .build()
    )
    net = MultiLayerNetwork(conf).init()
    xs = rng.standard_normal((8, T, F)).astype(np.float32)
    ys = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 8)]
    net.fit(xs, ys, epochs=2)
    assert np.isfinite(float(net.score_))


@pytest.mark.parametrize("pt,expect_fn", [
    ("sum", lambda x: x.sum(1)),
    ("pnorm", lambda x: (np.abs(x) ** 2).sum(1) ** 0.5),
])
def test_global_pooling_sum_pnorm_rnn(rng, pt, expect_fn):
    x = rng.standard_normal((2, 4, 3)).astype(np.float32)
    y, _ = GlobalPoolingLayer(pooling_type=pt).apply({}, {}, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(y), expect_fn(x), rtol=1e-5)


def test_global_pooling_unknown_type_raises():
    with pytest.raises(ValueError, match="pooling_type"):
        GlobalPoolingLayer(pooling_type="median").apply({}, {}, jnp.ones((2, 3, 4)))


def test_rnn_output_layer_sequence_loss(rng):
    """Per-timestep outputs + masked sequence loss (RnnOutputLayer parity)."""
    conf = (
        NeuralNetConfiguration.builder()
        .seed(11)
        .updater(Adam(0.05))
        .list()
        .layer(SimpleRnn(n_in=F, n_out=8))
        .layer(RnnOutputLayer(n_in=8, n_out=2, loss="mcxent", activation="softmax"))
        .set_input_type(InputType.recurrent(F, T))
        .build()
    )
    net = MultiLayerNetwork(conf).init()
    xs = rng.standard_normal((16, T, F)).astype(np.float32)
    labels = (xs[:, :, 0] > 0).astype(int)
    ys = np.eye(2, dtype=np.float32)[labels]
    for _ in range(80):
        net._fit_batch(jnp.asarray(xs), jnp.asarray(ys))
    out = np.asarray(net.output(xs))
    assert out.shape == (16, T, 2)
    acc = (out.argmax(-1) == labels).mean()
    assert acc > 0.9, acc


def test_stateful_time_stepping(rng):
    """rnnTimeStep parity: feeding a sequence step-by-step through apply_seq
    carries state identically to one full-sequence call."""
    lyr = GRU(n_in=F, n_out=H)
    params, _ = lyr.initialize(jax.random.PRNGKey(2), (T, F))
    x = jnp.asarray(rng.standard_normal((B, T, F)).astype(np.float32))
    full, _ = lyr.apply(params, {}, x)
    carry = lyr.init_carry(B)
    steps = []
    for t in range(T):
        out, carry = lyr.apply_seq(params, x[:, t : t + 1], carry)
        steps.append(out)
    stepped = jnp.concatenate(steps, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(stepped), rtol=2e-5, atol=1e-6)


def test_tbptt_learns_long_sequence(rng):
    # task: output at t mirrors input at t (identity through time) — learnable
    # within any segment; TBPTT must train without materializing full-T BPTT
    from deeplearning4j_tpu.nn import InputType, MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.recurrent import LSTM, RnnOutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam

    conf = (NeuralNetConfiguration.builder().seed(0).updater(Adam(0.02))
            .tbptt_length(8)
            .list()
            .layer(LSTM(n_in=4, n_out=16))
            .layer(RnnOutputLayer(n_in=16, n_out=4, loss="mcxent",
                                  activation="softmax"))
            .set_input_type(InputType.recurrent(4, 32)).build())
    net = MultiLayerNetwork(conf).init()
    assert net.conf.tbptt_length == 8
    ids = rng.integers(0, 4, size=(8, 32))
    x = np.eye(4, dtype=np.float32)[ids]
    y = x.copy()
    losses = []
    for _ in range(30):
        net._fit_batch(jnp.asarray(x), jnp.asarray(y))
        losses.append(float(net.score_value))
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])


def test_tbptt_carries_state_across_segments(rng):
    # task solvable ONLY with memory across segment boundaries: label at
    # every t is the input token at t=0 (long-range copy). With carries
    # flowing across segments the net can solve it; verify loss gets near 0.
    from deeplearning4j_tpu.nn import InputType, MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.recurrent import LSTM, RnnOutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam

    conf = (NeuralNetConfiguration.builder().seed(1).updater(Adam(0.02))
            .tbptt_length(4)
            .list()
            .layer(LSTM(n_in=2, n_out=16))
            .layer(RnnOutputLayer(n_in=16, n_out=2, loss="mcxent",
                                  activation="softmax"))
            .set_input_type(InputType.recurrent(2, 16)).build())
    net = MultiLayerNetwork(conf).init()
    ids = rng.integers(0, 2, size=(16, 16))
    x = np.eye(2, dtype=np.float32)[ids]
    y = np.repeat(x[:, :1], 16, axis=1)  # label = first token, everywhere
    for _ in range(60):
        net._fit_batch(jnp.asarray(x), jnp.asarray(y))
    assert float(net.score_value) < 0.25, float(net.score_value)


def test_rnn_time_step_matches_full_forward(rng):
    from deeplearning4j_tpu.nn import InputType, MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.recurrent import LSTM, RnnOutputLayer

    conf = (NeuralNetConfiguration.builder().seed(2)
            .list()
            .layer(LSTM(n_in=3, n_out=8))
            .layer(RnnOutputLayer(n_in=8, n_out=3, loss="mcxent",
                                  activation="softmax"))
            .set_input_type(InputType.recurrent(3, 6)).build())
    net = MultiLayerNetwork(conf).init()
    x = rng.standard_normal((2, 6, 3)).astype(np.float32)
    full = np.asarray(net.output(x))
    net.rnn_clear_previous_state()
    steps = [np.asarray(net.rnn_time_step(x[:, t])) for t in range(6)]
    np.testing.assert_allclose(np.stack(steps, 1), full, atol=1e-5)
    # clearing state restarts the recurrence
    net.rnn_clear_previous_state()
    again = np.asarray(net.rnn_time_step(x[:, 0]))
    np.testing.assert_allclose(again, steps[0], atol=1e-6)


def test_graves_bidirectional_lstm_layer(rng):
    from deeplearning4j_tpu.nn.recurrent import (
        Bidirectional, GravesBidirectionalLSTM, GravesLSTM)

    layer = GravesBidirectionalLSTM(n_in=4, n_out=6)
    params, state = layer.initialize(jax.random.PRNGKey(0), (5, 4))
    x = jnp.asarray(rng.standard_normal((2, 5, 4)), jnp.float32)
    y, _ = layer.apply(params, state, x)
    assert y.shape == (2, 5, 12)  # concat of both directions
    # equals the explicit Bidirectional(GravesLSTM) with the same key
    ref = Bidirectional(layer=GravesLSTM(n_in=4, n_out=6), mode="concat")
    rp, rs = ref.initialize(jax.random.PRNGKey(0), (5, 4))
    ry, _ = ref.apply(rp, rs, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ry), atol=1e-6)


class TestLSTMBlockOps:
    """Fused lstmBlock family (VERDICT r3 registry-tail item): TF
    BlockLSTM/LSTMBlockCell contract, golden-matched against tf.raw_ops —
    including peepholes, cell clipping, and the seq_len_max semantics
    (outputs zero past the limit, state carried through)."""

    def _data(self, rng, T=5, B=3, I=4, H=6):
        mk = lambda *s: rng.standard_normal(s).astype(np.float32)
        return (mk(T, B, I), mk(B, H) * 0.3, mk(B, H) * 0.3,
                mk(I + H, 4 * H) * 0.2, mk(H) * 0.1, mk(H) * 0.1,
                mk(H) * 0.1, mk(4 * H) * 0.1)

    def test_block_lstm_matches_tf(self, rng):
        tf = pytest.importorskip("tensorflow")
        from deeplearning4j_tpu.ops import registry

        x, cs0, h0, W, wci, wcf, wco, b = self._data(rng)
        golden = tf.raw_ops.BlockLSTM(
            seq_len_max=np.int64(4), x=x, cs_prev=cs0, h_prev=h0, w=W,
            wci=wci, wcf=wcf, wco=wco, b=b, forget_bias=1.0, cell_clip=3.0,
            use_peephole=True)
        ours = registry.exec_op(
            "lstm_block", np.int32(4), x, cs0, h0, W, wci, wcf, wco, b,
            forget_bias=1.0, cell_clip=3.0, use_peephole=True)
        # TF leaves rows at/past seq_len_max UNINITIALIZED (observed
        # garbage) — compare active steps only; our own semantics zero them
        for a, g in zip(ours, golden):
            np.testing.assert_allclose(np.asarray(a)[:4], g.numpy()[:4],
                                       atol=1e-5)
            assert np.all(np.asarray(a)[4:] == 0.0)

    def test_block_cell_matches_tf(self, rng):
        tf = pytest.importorskip("tensorflow")
        from deeplearning4j_tpu.ops import registry

        x, cs0, h0, W, wci, wcf, wco, b = self._data(rng, T=1)
        golden = tf.raw_ops.LSTMBlockCell(
            x=x[0], cs_prev=cs0, h_prev=h0, w=W, wci=wci, wcf=wcf, wco=wco,
            b=b, forget_bias=1.0, cell_clip=-1.0, use_peephole=False)
        ours = registry.exec_op(
            "lstm_block_cell", x[0], cs0, h0, W, wci, wcf, wco, b,
            forget_bias=1.0, cell_clip=-1.0, use_peephole=False)
        for a, g in zip(ours, golden):
            np.testing.assert_allclose(np.asarray(a), g.numpy(), atol=1e-5)

    def test_block_lstm_imports_from_tf_graph(self, rng):
        tf = pytest.importorskip("tensorflow")
        from deeplearning4j_tpu.imports import import_graph_def

        x, cs0, h0, W, wci, wcf, wco, b = self._data(rng)

        def fn(xv):
            out = tf.raw_ops.BlockLSTM(
                seq_len_max=np.int64(5), x=xv, cs_prev=cs0, h_prev=h0, w=W,
                wci=wci, wcf=wcf, wco=wco, b=b, forget_bias=1.0,
                cell_clip=-1.0, use_peephole=False)
            return out.h

        conc = tf.function(fn).get_concrete_function(
            tf.TensorSpec(x.shape, tf.float32))
        from tensorflow.python.framework.convert_to_constants import (
            convert_variables_to_constants_v2,
        )

        frozen = convert_variables_to_constants_v2(conc)
        golden = frozen(tf.constant(x))
        if isinstance(golden, (list, tuple)):
            golden = golden[0]
        golden = np.asarray(golden)
        sd = import_graph_def(frozen.graph.as_graph_def())
        key = sd.tf_name_map[frozen.outputs[0].name]
        in_name = frozen.inputs[0].name.split(":")[0]
        res = np.asarray(sd.output({in_name: x}, [key])[key])
        np.testing.assert_allclose(res, golden, atol=1e-5)
