"""Model-zoo tests (TestInstantiation in deeplearning4j-zoo parity: build,
init, forward-shape, and a short fit for the flagship)."""

import numpy as np
import pytest

from deeplearning4j_tpu.zoo import (
    AlexNet,
    Darknet19,
    LeNet,
    ResNet50,
    SimpleCNN,
    SqueezeNet,
    UNet,
    VGG16,
    Xception,
)


def _fwd(model, batch=2):
    net = model.init()
    h, w, c = model.input_shape
    x = np.random.default_rng(0).normal(size=(batch, h, w, c)).astype(np.float32)
    return net, net.output(x)


def test_lenet():
    net, out = _fwd(LeNet())
    assert out.shape == (2, 10)
    assert net.num_params() == 431080  # classic LeNet-5-ish param count


def test_simplecnn():
    _, out = _fwd(SimpleCNN(num_classes=7, input_shape=(32, 32, 3)))
    assert out.shape == (2, 7)


def test_alexnet():
    _, out = _fwd(AlexNet(num_classes=5, input_shape=(128, 128, 3)))
    assert out.shape == (2, 5)


def test_vgg16_small():
    _, out = _fwd(VGG16(num_classes=4, input_shape=(32, 32, 3)))
    assert out.shape == (2, 4)


def test_darknet19():
    _, out = _fwd(Darknet19(num_classes=6, input_shape=(64, 64, 3)))
    assert out.shape == (2, 6)


def test_squeezenet():
    _, out = _fwd(SqueezeNet(num_classes=9, input_shape=(64, 64, 3)))
    assert out.shape == (2, 9)


def test_unet():
    model = UNet(input_shape=(64, 64, 3), base_filters=4)
    net, out = _fwd(model)
    assert out.shape == (2, 64, 64, 1)
    assert np.all((np.asarray(out) >= 0) & (np.asarray(out) <= 1))


def test_xception():
    _, out = _fwd(Xception(num_classes=3, input_shape=(64, 64, 3), middle_repeats=1))
    assert out.shape == (2, 3)


def test_resnet50_structure():
    model = ResNet50(num_classes=1000, input_shape=(64, 64, 3))
    net = model.init()
    # Keras ResNet50 (v1, fc1000) has 25,636,712 params; ours differs only in
    # not having the ZeroPadding edge handling -> identical count.
    assert abs(net.num_params() - 25_636_712) < 100_000, net.num_params()
    x = np.random.default_rng(0).normal(size=(2, 64, 64, 3)).astype(np.float32)
    out = net.output(x)
    assert out.shape == (2, 1000)
    np.testing.assert_allclose(np.asarray(out).sum(axis=1), 1.0, atol=1e-3)


# slow: a minute by itself (ResNet-50's train step compiled, 15 steps);
# test_resnet50_structure above is the tier-1 test of the graph, and
# tests/chipbench_tests/test_chipbench_run_train.py trains it
@pytest.mark.slow
def test_resnet50_learns():
    model = ResNet50(num_classes=4, input_shape=(32, 32, 3))
    net = model.init()
    rng = np.random.default_rng(1)
    x = rng.normal(size=(16, 32, 32, 3)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 16)]
    s0 = net.score(x=x, y=y)
    for _ in range(15):
        net.fit(x, y)
    assert net.score(x=x, y=y) < s0


def test_facenet_nn4_small2():
    from deeplearning4j_tpu.zoo import FaceNetNN4Small2

    model = FaceNetNN4Small2(num_classes=11, input_shape=(96, 96, 3))
    net, out = _fwd(model)
    assert out.shape == (2, 11)
    # the embedding the model exists for: 128-d and L2-normalized
    h, w, c = model.input_shape
    x = np.random.default_rng(0).normal(size=(2, h, w, c)).astype(np.float32)
    acts = net.feed_forward(x)
    emb = np.asarray(acts["embed_norm"])
    assert emb.shape == (2, 128)
    np.testing.assert_allclose(np.linalg.norm(emb, axis=1), 1.0, atol=1e-5)
