"""MultiLayerNetwork end-to-end tests — MultiLayerTest / integration parity
(SURVEY.md §4: small-model training to target accuracy)."""

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.data import ArrayDataSetIterator, DataSet, MnistDataSetIterator
from deeplearning4j_tpu.nn import (
    InputType,
    MultiLayerConfiguration,
    MultiLayerNetwork,
    NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.layers import (
    ActivationLayer,
    BatchNormalization,
    ConvolutionLayer,
    DenseLayer,
    DropoutLayer,
    GlobalPoolingLayer,
    LossLayer,
    OutputLayer,
    SubsamplingLayer,
)
from deeplearning4j_tpu.nn.listeners import CollectScoresListener
from deeplearning4j_tpu.nn.updaters import Adam, Sgd


def _mlp_conf(n_in=4, n_hidden=16, n_out=3, updater=None, **kw):
    return (
        NeuralNetConfiguration.builder()
        .seed(42)
        .updater(updater or Adam(0.01))
        .list()
        .layer(DenseLayer(n_in=n_in, n_out=n_hidden, activation="relu"))
        .layer(OutputLayer(n_in=n_hidden, n_out=n_out, loss="mcxent", activation="softmax"))
        .set_input_type(InputType.feed_forward(n_in))
        .build()
    )


def _blobs(rng, n=256, n_classes=3, dim=4, spread=3.0):
    centers = rng.standard_normal((n_classes, dim)) * spread
    ys = rng.integers(0, n_classes, n)
    xs = centers[ys] + rng.standard_normal((n, dim))
    return xs.astype(np.float32), np.eye(n_classes, dtype=np.float32)[ys]


def test_init_shapes_and_param_count():
    net = MultiLayerNetwork(_mlp_conf()).init()
    assert net.params[0]["W"].shape == (4, 16)
    assert net.params[0]["b"].shape == (16,)
    assert net.params[1]["W"].shape == (16, 3)
    assert net.num_params() == 4 * 16 + 16 + 16 * 3 + 3


def test_fit_reduces_score_and_learns_blobs(rng):
    xs, ys = _blobs(rng)
    net = MultiLayerNetwork(_mlp_conf()).init()
    initial = net.score(x=xs, y=ys)
    it = ArrayDataSetIterator(xs, ys, batch=32, shuffle=True)
    net.fit(it, epochs=30)
    final = net.score(x=xs, y=ys)
    assert final < initial * 0.3, f"{initial} -> {final}"
    preds = np.asarray(net.output(xs))
    acc = (preds.argmax(-1) == ys.argmax(-1)).mean()
    assert acc > 0.95, acc


def test_output_is_probabilities(rng):
    xs, ys = _blobs(rng, n=32)
    net = MultiLayerNetwork(_mlp_conf()).init()
    out = np.asarray(net.output(xs))
    np.testing.assert_allclose(out.sum(-1), 1.0, rtol=1e-5)
    assert (out >= 0).all()


def test_evaluate_returns_evaluation(rng):
    xs, ys = _blobs(rng)
    net = MultiLayerNetwork(_mlp_conf()).init()
    it = ArrayDataSetIterator(xs, ys, batch=64)
    net.fit(it, epochs=20)
    ev = net.evaluate(it)
    assert ev.accuracy() > 0.9
    assert ev.confusion_matrix().sum() == len(xs)
    assert "Accuracy" in ev.stats()


def test_listeners_collect_scores(rng):
    xs, ys = _blobs(rng, n=64)
    net = MultiLayerNetwork(_mlp_conf()).init()
    collector = CollectScoresListener()
    net.set_listeners(collector)
    net.fit(ArrayDataSetIterator(xs, ys, batch=32), epochs=2)
    assert len(collector.scores) == 4  # 2 batches x 2 epochs
    assert all(np.isfinite(s) for _, s in collector.scores)


def test_feed_forward_exposes_activations(rng):
    xs, _ = _blobs(rng, n=8)
    net = MultiLayerNetwork(_mlp_conf()).init()
    acts = net.feed_forward(xs)
    assert len(acts) == 3  # input + 2 layers
    assert acts[1].shape == (8, 16)
    assert acts[2].shape == (8, 3)


def test_per_layer_updater_override(rng):
    conf = (
        NeuralNetConfiguration.builder()
        .updater(Adam(0.01))
        .list()
        .layer(DenseLayer(n_in=4, n_out=8, activation="tanh", updater=Sgd(0.0)))
        .layer(OutputLayer(n_in=8, n_out=3))
        .set_input_type(InputType.feed_forward(4))
        .build()
    )
    net = MultiLayerNetwork(conf).init()
    frozen_before = np.asarray(net.params[0]["W"]).copy()
    head_before = np.asarray(net.params[1]["W"]).copy()
    xs, ys = _blobs(rng, n=64)
    net.fit(ArrayDataSetIterator(xs, ys, batch=32), epochs=2)
    np.testing.assert_array_equal(np.asarray(net.params[0]["W"]), frozen_before)
    assert not np.allclose(np.asarray(net.params[1]["W"]), head_before)


def test_l2_regularization_shrinks_weights(rng):
    xs, ys = _blobs(rng, n=128)

    def train(l2):
        conf = (
            NeuralNetConfiguration.builder()
            .seed(7)
            .updater(Sgd(0.05))
            .l2(l2)
            .list()
            .layer(DenseLayer(n_in=4, n_out=16, activation="relu"))
            .layer(OutputLayer(n_in=16, n_out=3))
            .set_input_type(InputType.feed_forward(4))
            .build()
        )
        net = MultiLayerNetwork(conf).init()
        net.fit(ArrayDataSetIterator(xs, ys, batch=64), epochs=30)
        return float(jnp.sum(net.params[0]["W"] ** 2))

    assert train(0.5) < train(0.0) * 0.8


def test_json_roundtrip_reproduces_network(rng):
    conf = _mlp_conf()
    s = conf.to_json()
    conf2 = MultiLayerConfiguration.from_json(s)
    net1 = MultiLayerNetwork(conf).init()
    net2 = MultiLayerNetwork(conf2).init()
    xs, _ = _blobs(rng, n=8)
    np.testing.assert_allclose(
        np.asarray(net1.output(xs)), np.asarray(net2.output(xs)), rtol=1e-6
    )


def test_dropout_changes_training_but_not_inference(rng):
    conf = (
        NeuralNetConfiguration.builder()
        .updater(Sgd(0.1))
        .list()
        .layer(DenseLayer(n_in=4, n_out=64, activation="relu", dropout=0.5))
        .layer(OutputLayer(n_in=64, n_out=3))
        .set_input_type(InputType.feed_forward(4))
        .build()
    )
    net = MultiLayerNetwork(conf).init()
    xs, _ = _blobs(rng, n=16)
    a = np.asarray(net.output(xs))
    b = np.asarray(net.output(xs))
    np.testing.assert_array_equal(a, b)  # inference is deterministic


def test_batchnorm_network_trains_and_infers(rng):
    conf = (
        NeuralNetConfiguration.builder()
        .seed(3)
        .updater(Adam(0.01))
        .list()
        .layer(DenseLayer(n_in=4, n_out=16))
        .layer(BatchNormalization())
        .layer(ActivationLayer(activation="relu"))
        .layer(OutputLayer(n_in=16, n_out=3))
        .set_input_type(InputType.feed_forward(4))
        .build()
    )
    net = MultiLayerNetwork(conf).init()
    xs, ys = _blobs(rng)
    net.fit(ArrayDataSetIterator(xs, ys, batch=64, shuffle=True), epochs=20)
    # running stats must have moved off their init values
    assert not np.allclose(np.asarray(net.states[1]["mean"]), 0.0)
    ev = net.evaluate(ArrayDataSetIterator(xs, ys, batch=64))
    assert ev.accuracy() > 0.9


def test_regression_network(rng):
    xs = rng.standard_normal((256, 3)).astype(np.float32)
    w_true = np.array([[1.0], [-2.0], [0.5]], dtype=np.float32)
    ys = xs @ w_true + 0.01 * rng.standard_normal((256, 1)).astype(np.float32)
    conf = (
        NeuralNetConfiguration.builder()
        .updater(Adam(0.05))
        .list()
        .layer(DenseLayer(n_in=3, n_out=16, activation="tanh"))
        .layer(OutputLayer(n_in=16, n_out=1, loss="mse", activation="identity"))
        .set_input_type(InputType.feed_forward(3))
        .build()
    )
    net = MultiLayerNetwork(conf).init()
    net.fit(ArrayDataSetIterator(xs, ys, batch=64, shuffle=True), epochs=50)
    ev = net.evaluate_regression(ArrayDataSetIterator(xs, ys, batch=64))
    assert ev.r_squared() > 0.95, ev.stats()


# ---------------------------------------------------------------- LeNet MNIST


def _lenet_conf(compute_dtype="float32"):
    """LeNet-5 (BASELINE config #1; reference: dl4j-examples LeNet MNIST)."""
    return (
        NeuralNetConfiguration.builder()
        .seed(123)
        .updater(Adam(1e-3))
        .compute_dtype(compute_dtype)
        .list()
        .layer(ConvolutionLayer(n_out=20, kernel_size=(5, 5), padding="VALID", activation="relu"))
        .layer(SubsamplingLayer(kernel_size=(2, 2)))
        .layer(ConvolutionLayer(n_out=50, kernel_size=(5, 5), padding="VALID", activation="relu"))
        .layer(SubsamplingLayer(kernel_size=(2, 2)))
        .layer(DenseLayer(n_out=500, activation="relu", n_in=4 * 4 * 50))
        .layer(OutputLayer(n_in=500, n_out=10, loss="mcxent", activation="softmax"))
        .set_input_type(InputType.convolutional(28, 28, 1))
        .build()
    )


# slow: six epochs over 2,048 MNIST digits; test_lenet_shapes_one_step below
# is the tier-1 test of the same net, test_fit_reduces_score_and_learns_blobs
# above that fit() learns
@pytest.mark.slow
def test_lenet_mnist_trains_to_high_accuracy():
    train_it = MnistDataSetIterator(batch=64, train=True, n_examples=2048)
    test_it = MnistDataSetIterator(batch=256, train=False, n_examples=512)
    net = MultiLayerNetwork(_lenet_conf()).init()
    net.fit(train_it, epochs=6)
    ev = net.evaluate(test_it)
    assert ev.accuracy() > 0.97, f"LeNet accuracy {ev.accuracy():.4f}\n{ev.stats()}"


def test_lenet_shapes_one_step():
    net = MultiLayerNetwork(_lenet_conf()).init()
    x = np.zeros((2, 28, 28, 1), dtype=np.float32)
    out = np.asarray(net.output(x))
    assert out.shape == (2, 10)
    ds = DataSet(x, np.eye(10, dtype=np.float32)[[0, 1]])
    net.fit(ds.features, ds.labels)
    assert np.isfinite(net.get_score())
