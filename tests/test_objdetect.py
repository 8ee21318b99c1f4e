"""YOLOv2 output layer + detection decoding + zoo detection models.

Reference test parity: deeplearning4j-core objdetect tests
(Yolo2OutputLayer gradchecks/decoding; SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.objdetect import (
    DetectedObject,
    Yolo2OutputLayer,
    get_predicted_objects,
)

ANCHORS = ((1.0, 1.5), (3.0, 3.0))


def _labels(b=2, sy=4, sx=4, c=3):
    lab = np.zeros((b, sy, sx, 4 + c), np.float32)
    # one object in cell (1,2) of example 0: box from (2.1,1.2) to (3.3,2.0)
    lab[0, 1, 2, :4] = [2.1, 1.2, 3.3, 2.0]
    lab[0, 1, 2, 4 + 1] = 1.0
    return lab


class TestYoloLoss:
    def test_loss_finite_and_differentiable(self, rng):
        layer = Yolo2OutputLayer(anchors=ANCHORS)
        x = jnp.asarray(rng.normal(size=(2, 4, 4, 2 * 8)) * 0.1, jnp.float32)
        lab = jnp.asarray(_labels())

        def loss(x):
            return layer.compute_loss({}, {}, x, lab)

        val, grad = jax.value_and_grad(loss)(x)
        assert np.isfinite(float(val))
        assert np.isfinite(np.asarray(grad)).all()
        assert float(jnp.max(jnp.abs(grad))) > 0

    def test_training_reduces_loss(self, rng):
        layer = Yolo2OutputLayer(anchors=ANCHORS)
        lab = jnp.asarray(_labels())
        x = jnp.asarray(rng.normal(size=(2, 4, 4, 16)) * 0.1, jnp.float32)

        loss_fn = jax.jit(lambda x: layer.compute_loss({}, {}, x, lab))
        g = jax.jit(jax.grad(lambda x: layer.compute_loss({}, {}, x, lab)))
        l0 = float(loss_fn(x))
        for _ in range(200):
            x = x - 0.05 * g(x)
        assert float(loss_fn(x)) < l0 * 0.6

    def test_weighted_loss_ignores_padded(self, rng):
        layer = Yolo2OutputLayer(anchors=ANCHORS)
        x = jnp.asarray(rng.normal(size=(2, 4, 4, 16)), jnp.float32)
        lab = jnp.asarray(_labels())
        w_first = layer.compute_loss({}, {}, x, lab,
                                     weights=jnp.asarray([1.0, 0.0]))
        only_first = layer.compute_loss({}, {}, x[:1], lab[:1])
        np.testing.assert_allclose(float(w_first), float(only_first), rtol=1e-5)


class TestDecoding:
    def test_decode_and_nms(self):
        layer = Yolo2OutputLayer(anchors=ANCHORS)
        out = np.full((1, 4, 4, 16), -8.0, np.float32)  # conf sigmoid ≈ 0
        # confident detection in cell (1,2), anchor 0, class 2
        out[0, 1, 2, 0:5] = [0.0, 0.0, 0.0, 0.0, 8.0]
        out[0, 1, 2, 5:8] = [0.0, 0.0, 4.0]
        # duplicate overlapping detection with lower confidence, anchor 1
        out[0, 1, 2, 8:13] = [0.0, 0.0, -1.2, -0.8, 4.0]
        out[0, 1, 2, 13:16] = [0.0, 0.0, 3.0]
        dets = get_predicted_objects(layer, out, threshold=0.5,
                                     nms_threshold=0.4)[0]
        assert len(dets) >= 1
        d = dets[0]
        assert d.predicted_class == 2
        assert abs(d.center_x - 2.5) < 0.01 and abs(d.center_y - 1.5) < 0.01
        assert abs(d.width - 1.0) < 0.01 and abs(d.height - 1.5) < 0.01
        # the weaker overlapping box was suppressed
        assert all(o.confidence >= 0.9 for o in dets[:1])


# slow: two zoo detectors built and compiled (half a minute and a quarter);
# TestYoloLoss and TestDecoding above are the tier-1 tests of the detection
# loss and decode, tests/test_zoo.py::test_darknet19 of the backbone
@pytest.mark.slow
class TestDetectionZoo:
    def test_tiny_yolo_builds_and_steps(self, rng):
        from deeplearning4j_tpu.zoo import TinyYOLO

        net = TinyYOLO(input_shape=(64, 64, 3), num_classes=3).init()
        x = rng.normal(size=(2, 64, 64, 3)).astype(np.float32)
        out = net.output(x)
        assert out.shape == (2, 2, 2, 5 * (5 + 3))
        lab = np.zeros((2, 2, 2, 4 + 3), np.float32)
        lab[0, 0, 1, :4] = [1.1, 0.2, 1.9, 0.9]
        lab[0, 0, 1, 4] = 1.0
        losses = []
        for _ in range(12):
            net._fit_batch(jnp.asarray(x), jnp.asarray(lab))
            losses.append(float(net.score_value))
        # training loss trend (eval-mode batchnorm stats lag this early)
        assert losses[-1] < losses[0], losses

    def test_inception_resnet_v1_builds(self, rng):
        from deeplearning4j_tpu.zoo import InceptionResNetV1

        net = InceptionResNetV1(input_shape=(96, 96, 3), num_classes=5).init()
        x = rng.normal(size=(1, 96, 96, 3)).astype(np.float32)
        out = net.output(x)
        assert np.asarray(out).shape == (1, 5)
