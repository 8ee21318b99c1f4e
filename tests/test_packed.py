"""PackedTrainer: flattened-state training (DL4J flattened-params parity,
TPU-motivated — one buffer per dtype instead of hundreds of leaf handles
per dispatch). Must be numerically identical to the plain step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.util.packed import PackedTrainer, StatePacker


def _mln(seed=7):
    from deeplearning4j_tpu.nn import InputType, MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import (
        BatchNormalization, ConvolutionLayer, DenseLayer, OutputLayer,
        SubsamplingLayer,
    )
    from deeplearning4j_tpu.nn.updaters import Adam

    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
            .list()
            .layer(ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                    activation="relu"))
            .layer(BatchNormalization())
            .layer(SubsamplingLayer(kernel_size=(2, 2)))
            .layer(DenseLayer(n_in=64, n_out=16, activation="relu"))
            .layer(OutputLayer(n_in=16, n_out=3, loss="mcxent",
                               activation="softmax"))
            .set_input_type(InputType.convolutional(8, 8, 2))
            .build())
    return MultiLayerNetwork(conf).init()


def test_state_packer_roundtrip(rng):
    tree = {"a": jnp.asarray(rng.normal(size=(3, 4)).astype(np.float32)),
            "b": {"c": jnp.asarray(rng.integers(0, 9, (5,))),
                  "d": jnp.asarray(rng.normal(size=()).astype(np.float32))}}
    p = StatePacker(tree)
    back = p.unpack(p.pack(tree))
    for k1, k2 in (("a", None), ("b", "c"), ("b", "d")):
        want = tree[k1] if k2 is None else tree[k1][k2]
        got = back[k1] if k2 is None else back[k1][k2]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert got.dtype == want.dtype


def test_packed_matches_plain_mln(rng):
    """Same seed, same data: 4 packed steps == 4 plain steps, to float32
    round-off (identical math, different operand packaging)."""
    xs = rng.normal(size=(8, 8, 8, 2)).astype(np.float32)
    ys = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)]

    plain = _mln()
    packed_net = _mln()
    pt = PackedTrainer(packed_net)
    for _ in range(4):
        plain._fit_batch(jnp.asarray(xs), jnp.asarray(ys))
        pt._fit_batch(jnp.asarray(xs), jnp.asarray(ys))
    pt.unpack_to_model()
    np.testing.assert_allclose(float(pt.score_value),
                               float(plain.score_value), rtol=1e-6)
    for lp, pp in zip(plain.params, packed_net.params):
        for k in lp:
            np.testing.assert_allclose(np.asarray(pp[k]), np.asarray(lp[k]),
                                       atol=1e-6, rtol=1e-5, err_msg=k)


# slow: most of a minute by itself (a ResNet-50 graph compiled twice);
# test_packed_matches_plain_mln above is the tier-1 test of the same
# packed == plain contract on the cheap topology
@pytest.mark.slow
def test_packed_matches_plain_cg(rng):
    from deeplearning4j_tpu.zoo import ResNet50

    xs = rng.normal(size=(2, 32, 32, 3)).astype(np.float32)
    ys = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 2)]
    plain = ResNet50(num_classes=4, input_shape=(32, 32, 3)).init()
    pnet = ResNet50(num_classes=4, input_shape=(32, 32, 3)).init()
    pt = PackedTrainer(pnet)
    for _ in range(2):
        plain._fit_batch(jnp.asarray(xs), jnp.asarray(ys))
        pt._fit_batch(jnp.asarray(xs), jnp.asarray(ys))
    pt.unpack_to_model()
    np.testing.assert_allclose(float(pt.score_value),
                               float(plain.score_value), rtol=1e-5)
    for name in plain.params:
        for k in plain.params[name]:
            np.testing.assert_allclose(
                np.asarray(pnet.params[name][k]),
                np.asarray(plain.params[name][k]),
                atol=1e-5, rtol=1e-4, err_msg=f"{name}/{k}")


def test_unpack_resumes_plain_training_at_right_iteration(rng):
    """After unpack_to_model, plain _fit_batch must continue from the
    ADVANCED iteration counter (Adam bias correction / LR schedules) —
    review finding, round 3."""
    xs = rng.normal(size=(4, 8, 8, 2)).astype(np.float32)
    ys = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 4)]

    a = _mln()
    b = _mln()
    # a: 1 plain + 3 packed + 1 plain;  b: 5 plain
    a._fit_batch(jnp.asarray(xs), jnp.asarray(ys))
    pt = PackedTrainer(a)
    for _ in range(3):
        pt._fit_batch(jnp.asarray(xs), jnp.asarray(ys))
    pt.unpack_to_model()
    a._fit_batch(jnp.asarray(xs), jnp.asarray(ys))
    for _ in range(5):
        b._fit_batch(jnp.asarray(xs), jnp.asarray(ys))
    assert a.iteration == b.iteration == 5
    for lp, pp in zip(b.params, a.params):
        for k in lp:
            np.testing.assert_allclose(np.asarray(pp[k]), np.asarray(lp[k]),
                                       atol=1e-6, rtol=1e-5, err_msg=k)
