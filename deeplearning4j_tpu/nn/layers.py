"""Layer configuration + implementation classes.

Reference parity: DL4J splits layer *config* (org/deeplearning4j/nn/conf/layers/
DenseLayer.java, ConvolutionLayer.java, SubsamplingLayer.java,
BatchNormalization.java, DropoutLayer.java, OutputLayer.java …) from layer
*implementation* (org/deeplearning4j/nn/layers/**, with activate()/
backpropGradient() hand-written per layer) — path-cite, mount empty this round.

TPU-native collapse: one frozen dataclass per layer carries the config AND the
pure functions (``initialize``, ``apply``, ``output_shape``). There is no
backpropGradient anywhere — reverse-mode comes from JAX over ``apply``, and the
whole network's forward+backward compiles into a single XLA program
(SURVEY.md §3.1: the reference pays a JNI crossing per op; we pay one device
launch per step).

Conventions:
- ``input_shape``/``output_shape`` exclude the batch dimension.
- CNN data format is NHWC (TPU-preferred); input_shape = (H, W, C).
- ``apply`` returns (output, new_layer_state); state carries non-trainable
  values (batchnorm running stats). Layers without state use {}.
- ``dropout`` on a layer applies to its INPUT during training (DL4J semantics,
  conf/layers/BaseLayer.java#dropOut).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import activations as act
from deeplearning4j_tpu.nn import losses as losses_mod
from deeplearning4j_tpu.nn import weights as winit
from deeplearning4j_tpu.ops import nn as nnops
from deeplearning4j_tpu.ops import random as randops

_LAYER_TYPES: Dict[str, type] = {}


def register_layer(cls):
    _LAYER_TYPES[cls.__name__] = cls
    return cls


def layer_from_dict(d: dict) -> "Layer":
    d = dict(d)
    kind = d.pop("@layer")
    cls = _LAYER_TYPES.get(kind)
    if cls is None:
        # a fresh process restoring an archive (fleet worker, bare
        # `restore_model` script) has only the eagerly-imported layer
        # modules registered; pull in the lazy ones and retry before
        # declaring the type unknown
        import importlib

        for mod in ("recurrent", "objdetect", "moe"):
            try:
                importlib.import_module(f"deeplearning4j_tpu.nn.{mod}")
            except ImportError:
                pass
        try:
            cls = _LAYER_TYPES[kind]
        except KeyError:
            raise KeyError(
                f"unknown layer type {kind!r}; registered: "
                f"{sorted(_LAYER_TYPES)}") from None
    for k, v in list(d.items()):
        if isinstance(v, dict) and "@layer" in v:  # nested wrapper (Bidirectional)
            d[k] = layer_from_dict(v)
    return cls(**d)


@dataclasses.dataclass(frozen=True)
class Layer:
    """Base layer config. Subclasses are pure: no mutable members."""

    name: Optional[str] = None
    dropout: float = 0.0  # input dropout rate (DL4J: dropOut retain prob is legacy; this is a rate)
    l1: float = 0.0
    l2: float = 0.0
    updater: Optional[Any] = None  # per-layer updater override (IUpdater parity)

    # -- API ----------------------------------------------------------------
    def initialize(self, key, input_shape) -> Tuple[dict, dict]:
        return {}, {}

    def apply(self, params, state, x, *, training=False, key=None):
        raise NotImplementedError

    def output_shape(self, input_shape):
        return tuple(input_shape)

    def has_params(self) -> bool:
        return True

    def regularization(self, params) -> jnp.ndarray:
        """L1/L2 penalty on weight params (DL4J applies it to W, not biases).
        Recurses into nested param dicts (e.g. Bidirectional's fwd/bwd) so the
        bias check only ever sees leaf names."""
        reg = jnp.asarray(0.0, dtype=jnp.float32)
        if not params:
            return reg

        def walk(d, reg):
            for name, p in d.items():
                if isinstance(p, dict):
                    reg = walk(p, reg)
                    continue
                if name.startswith("b") or name in ("gamma", "beta", "mean", "var"):
                    continue
                if self.l1:
                    reg = reg + self.l1 * jnp.sum(jnp.abs(p))
                if self.l2:
                    reg = reg + 0.5 * self.l2 * jnp.sum(jnp.square(p))
            return reg

        return walk(params, reg)

    def _maybe_dropout(self, x, training, key):
        if training and self.dropout > 0.0 and key is not None:
            return randops.dropout(x, key, self.dropout, training=True)
        return x

    # -- serialization ------------------------------------------------------
    def to_dict(self) -> dict:
        d = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if f.name == "updater" and v is not None:
                v = v.to_dict()
            d[f.name] = v
        d["@layer"] = type(self).__name__
        return d


@register_layer
@dataclasses.dataclass(frozen=True)
class DenseLayer(Layer):
    """Fully connected layer (conf/layers/DenseLayer.java)."""

    n_in: int = 0
    n_out: int = 0
    activation: str = "identity"
    weight_init: str = "xavier"
    has_bias: bool = True

    def initialize(self, key, input_shape):
        n_in = self.n_in or int(jnp.prod(jnp.array(input_shape)))
        params = {"W": winit.init(key, self.weight_init, (n_in, self.n_out))}
        if self.has_bias:
            params["b"] = jnp.zeros((self.n_out,))
        return params, {}

    def apply(self, params, state, x, *, training=False, key=None):
        x = self._maybe_dropout(x, training, key)
        if x.ndim > 2:
            x = x.reshape(x.shape[0], -1)
        y = nnops.xw_plus_b(x, params["W"], params.get("b", jnp.zeros(params["W"].shape[1], x.dtype)))
        return act.resolve(self.activation)(y), state

    def output_shape(self, input_shape):
        return (self.n_out,)


@register_layer
@dataclasses.dataclass(frozen=True)
class ConvolutionLayer(Layer):
    """2-D convolution (conf/layers/ConvolutionLayer.java; impl used the
    cuDNN helper on GPU — here a single XLA convolution HLO on the MXU)."""

    n_in: int = 0  # input channels (inferred if 0)
    n_out: int = 0  # output channels
    kernel_size: tuple = (3, 3)
    stride: tuple = (1, 1)
    padding: Any = "SAME"  # 'SAME' | 'VALID' | (ph, pw)
    dilation: tuple = (1, 1)
    activation: str = "identity"
    weight_init: str = "relu"
    has_bias: bool = True

    def initialize(self, key, input_shape):
        c_in = self.n_in or input_shape[-1]
        kh, kw = self.kernel_size
        params = {"W": winit.init(key, self.weight_init, (kh, kw, c_in, self.n_out))}
        if self.has_bias:
            params["b"] = jnp.zeros((self.n_out,))
        return params, {}

    def apply(self, params, state, x, *, training=False, key=None):
        x = self._maybe_dropout(x, training, key)
        y = nnops.conv2d(
            x, params["W"], params.get("b"),
            strides=self.stride, padding=self.padding, dilation=self.dilation,
        )
        return act.resolve(self.activation)(y), state

    def output_shape(self, input_shape):
        h, w, _ = input_shape
        kh, kw = self.kernel_size
        sh, sw = self.stride
        if self.padding == "SAME":
            oh, ow = -(-h // sh), -(-w // sw)
        elif self.padding == "VALID":
            eff_kh = (kh - 1) * self.dilation[0] + 1
            eff_kw = (kw - 1) * self.dilation[1] + 1
            oh, ow = (h - eff_kh) // sh + 1, (w - eff_kw) // sw + 1
        else:
            ph, pw = self.padding if not isinstance(self.padding, int) else (self.padding,) * 2
            oh = (h + 2 * ph - kh) // sh + 1
            ow = (w + 2 * pw - kw) // sw + 1
        return (oh, ow, self.n_out)


@register_layer
@dataclasses.dataclass(frozen=True)
class SubsamplingLayer(Layer):
    """Pooling (conf/layers/SubsamplingLayer.java). pooling_type: MAX|AVG|PNORM."""

    kernel_size: tuple = (2, 2)
    stride: Optional[tuple] = None
    padding: Any = "VALID"
    pooling_type: str = "max"
    pnorm: int = 2

    def has_params(self):
        return False

    def apply(self, params, state, x, *, training=False, key=None):
        strides = self.stride or self.kernel_size
        pt = self.pooling_type.lower()
        if pt == "max":
            y = nnops.max_pool2d(x, self.kernel_size, strides, self.padding)
        elif pt in ("avg", "average"):
            y = nnops.avg_pool2d(x, self.kernel_size, strides, self.padding)
        elif pt == "pnorm":
            y = nnops.pnorm_pool2d(x, self.kernel_size, strides, self.padding, p=self.pnorm)
        else:
            raise ValueError(f"unknown pooling_type {self.pooling_type}")
        return y, state

    def output_shape(self, input_shape):
        h, w, c = input_shape
        kh, kw = self.kernel_size
        sh, sw = self.stride or self.kernel_size
        if self.padding == "SAME":
            return (-(-h // sh), -(-w // sw), c)
        if self.padding == "VALID":
            return ((h - kh) // sh + 1, (w - kw) // sw + 1, c)
        ph, pw = self.padding
        return ((h + 2 * ph - kh) // sh + 1, (w + 2 * pw - kw) // sw + 1, c)


@register_layer
@dataclasses.dataclass(frozen=True)
class BatchNormalization(Layer):
    """Batch norm over the channel axis (conf/layers/BatchNormalization.java;
    GPU impl used CudnnBatchNormalizationHelper — here XLA fuses the
    scale-shift into neighbors). State: running mean/var (ema)."""

    n_out: int = 0  # channels (inferred if 0)
    decay: float = 0.9
    eps: float = 1e-5
    gamma_init: float = 1.0
    beta_init: float = 0.0
    lock_gamma_beta: bool = False

    def initialize(self, key, input_shape):
        c = self.n_out or input_shape[-1]
        params = {}
        if not self.lock_gamma_beta:
            # explicit dtype: jnp.full with a Python float is WEAKLY typed,
            # the first update hands back strong f32, and the train step
            # retraces — at step 1 for the params and again at step 2 for
            # the Adam moments built from them (three compiles of the
            # flagship instead of one; seen on the chip in PR 21)
            params = {"gamma": jnp.full((c,), self.gamma_init, jnp.float32),
                      "beta": jnp.full((c,), self.beta_init, jnp.float32)}
        state = {"mean": jnp.zeros((c,)), "var": jnp.ones((c,))}
        return params, state

    def apply(self, params, state, x, *, training=False, key=None):
        gamma = params.get("gamma")
        beta = params.get("beta")
        if training:
            y, new_mean, new_var = nnops.batchnorm_train(
                x, gamma, beta, state["mean"], state["var"],
                momentum=self.decay, eps=self.eps,
            )
            return y, {"mean": new_mean, "var": new_var}
        y = nnops.batchnorm(x, state["mean"], state["var"], gamma, beta, eps=self.eps)
        return y, state

    def has_params(self):
        return not self.lock_gamma_beta


@register_layer
@dataclasses.dataclass(frozen=True)
class ActivationLayer(Layer):
    """Standalone activation (conf/layers/ActivationLayer.java).
    ``activation_args`` forwards extra config to the op (e.g. leakyrelu's
    alpha — Keras LeakyReLU defaults to 0.3, the op to 0.01)."""

    activation: str = "relu"
    activation_args: Optional[dict] = None

    def has_params(self):
        return False

    def apply(self, params, state, x, *, training=False, key=None):
        fn = act.resolve(self.activation)
        if self.activation_args:
            return fn(x, **self.activation_args), state
        return fn(x), state


@register_layer
@dataclasses.dataclass(frozen=True)
class DropoutLayer(Layer):
    """Standalone dropout (conf/layers/DropoutLayer.java)."""

    rate: float = 0.5

    def has_params(self):
        return False

    def apply(self, params, state, x, *, training=False, key=None):
        if training and key is not None:
            x = randops.dropout(x, key, self.rate, training=True)
        return x, state


@register_layer
@dataclasses.dataclass(frozen=True)
class GlobalPoolingLayer(Layer):
    """Global pooling (conf/layers/GlobalPoolingLayer.java): spatial axes for
    CNN (B,H,W,C) input, the time axis (mask-aware) for RNN (B,T,F) input —
    same dual role as the reference."""

    pooling_type: str = "avg"
    pnorm: int = 2

    def has_params(self):
        return False

    def apply(self, params, state, x, *, training=False, key=None, mask=None):
        pt = self.pooling_type.lower()
        if pt not in ("avg", "max", "sum", "pnorm"):
            raise ValueError(f"unknown pooling_type {self.pooling_type!r}")
        if x.ndim == 3:  # (B,T,F) over time
            if mask is not None:
                m = mask[:, :, None].astype(x.dtype)
                if pt == "avg":
                    return jnp.sum(x * m, axis=1) / jnp.maximum(
                        jnp.sum(m, axis=1), 1e-9
                    ), state
                if pt == "sum":
                    return jnp.sum(x * m, axis=1), state
                if pt == "pnorm":
                    return jnp.power(
                        jnp.sum(jnp.power(jnp.abs(x) * m, self.pnorm), axis=1),
                        1.0 / self.pnorm,
                    ), state
                neg = jnp.asarray(jnp.finfo(x.dtype).min, x.dtype)
                return jnp.max(jnp.where(m > 0, x, neg), axis=1), state
            if pt == "avg":
                return jnp.mean(x, axis=1), state
            if pt == "sum":
                return jnp.sum(x, axis=1), state
            if pt == "pnorm":
                return jnp.power(
                    jnp.sum(jnp.power(jnp.abs(x), self.pnorm), axis=1),
                    1.0 / self.pnorm,
                ), state
            return jnp.max(x, axis=1), state
        spatial = tuple(range(1, x.ndim - 1))  # (B,H,W,C) / (B,D,H,W,C)
        if pt == "avg":
            return jnp.mean(x, axis=spatial), state
        if pt == "sum":
            return jnp.sum(x, axis=spatial), state
        if pt == "pnorm":
            return jnp.power(
                jnp.sum(jnp.power(jnp.abs(x), self.pnorm), axis=spatial),
                1.0 / self.pnorm,
            ), state
        return jnp.max(x, axis=spatial), state

    def output_shape(self, input_shape):
        return (input_shape[-1],)


@register_layer
@dataclasses.dataclass(frozen=True)
class EmbeddingLayer(Layer):
    """Index → vector lookup (conf/layers/EmbeddingLayer.java). Input: int ids."""

    n_in: int = 0  # vocab
    n_out: int = 0  # dim
    weight_init: str = "normal"

    def initialize(self, key, input_shape):
        return {"W": winit.init(key, self.weight_init, (self.n_in, self.n_out))}, {}

    def apply(self, params, state, x, *, training=False, key=None):
        return nnops.embedding_lookup(params["W"], x.astype(jnp.int32)), state

    def output_shape(self, input_shape):
        return tuple(input_shape) + (self.n_out,)


@register_layer
@dataclasses.dataclass(frozen=True)
class OutputLayer(DenseLayer):
    """Dense + loss head (conf/layers/OutputLayer.java). The loss pairs with
    the activation for a fused, numerically stable logits path when possible
    (softmax+MCXENT, sigmoid+XENT)."""

    loss: str = "mcxent"
    activation: str = "softmax"

    def compute_loss(self, params, state, x, labels, *, training=True, key=None, weights=None):
        """Loss from layer INPUT x (pre-dense). Uses the fused logits path
        when activation matches the loss's fused pair."""
        x = self._maybe_dropout(x, training, key)
        if x.ndim > 2:
            x = x.reshape(x.shape[0], -1)
        logits = nnops.xw_plus_b(
            x, params["W"], params.get("b", jnp.zeros(params["W"].shape[1], x.dtype))
        )
        logits_fn, act_fn, fused_act = losses_mod.resolve(self.loss)
        if logits_fn is not None and fused_act == self.activation.lower():
            return logits_fn(logits, labels, weights)
        preds = act.resolve(self.activation)(logits)
        if act_fn is None:
            raise ValueError(f"loss {self.loss} requires activation {fused_act}")
        return act_fn(preds, labels, weights)


@register_layer
@dataclasses.dataclass(frozen=True)
class LossLayer(Layer):
    """Loss-only head, no params (conf/layers/LossLayer.java)."""

    loss: str = "mse"
    activation: str = "identity"

    def has_params(self):
        return False

    def apply(self, params, state, x, *, training=False, key=None):
        return act.resolve(self.activation)(x), state

    def compute_loss(self, params, state, x, labels, *, training=True, key=None, weights=None):
        logits_fn, act_fn, fused_act = losses_mod.resolve(self.loss)
        if logits_fn is not None and fused_act == self.activation.lower():
            return logits_fn(x, labels, weights)
        if act_fn is None:
            raise ValueError(f"loss {self.loss} requires activation {fused_act}")
        preds = act.resolve(self.activation)(x)
        return act_fn(preds, labels, weights)


@register_layer
@dataclasses.dataclass(frozen=True)
class ZeroPaddingLayer(Layer):
    """(conf/layers/ZeroPaddingLayer.java)."""

    padding: tuple = ((1, 1), (1, 1))  # ((top,bottom),(left,right))

    def has_params(self):
        return False

    def apply(self, params, state, x, *, training=False, key=None):
        (pt, pb), (pl, pr) = self.padding
        return jnp.pad(x, ((0, 0), (pt, pb), (pl, pr), (0, 0))), state

    def output_shape(self, input_shape):
        h, w, c = input_shape
        (pt, pb), (pl, pr) = self.padding
        return (h + pt + pb, w + pl + pr, c)


@register_layer
@dataclasses.dataclass(frozen=True)
class Upsampling2D(Layer):
    """(conf/layers/Upsampling2D.java)."""

    size: int = 2

    def has_params(self):
        return False

    def apply(self, params, state, x, *, training=False, key=None):
        return nnops.upsampling2d(x, self.size), state

    def output_shape(self, input_shape):
        h, w, c = input_shape
        return (h * self.size, w * self.size, c)


@register_layer
@dataclasses.dataclass(frozen=True)
class Deconvolution2D(Layer):
    """Transposed convolution (conf/layers/Deconvolution2D.java)."""

    n_in: int = 0
    n_out: int = 0
    kernel_size: tuple = (2, 2)
    stride: tuple = (2, 2)
    padding: Any = "SAME"
    activation: str = "identity"
    weight_init: str = "relu"
    has_bias: bool = True

    def initialize(self, key, input_shape):
        c_in = self.n_in or input_shape[-1]
        kh, kw = self.kernel_size
        params = {"W": winit.init(key, self.weight_init, (kh, kw, c_in, self.n_out))}
        if self.has_bias:
            params["b"] = jnp.zeros((self.n_out,))
        return params, {}

    def apply(self, params, state, x, *, training=False, key=None):
        x = self._maybe_dropout(x, training, key)
        y = nnops.deconv2d(
            x, params["W"], params.get("b"), strides=self.stride, padding=self.padding
        )
        return act.resolve(self.activation)(y), state

    def output_shape(self, input_shape):
        h, w, _ = input_shape
        sh, sw = self.stride
        if self.padding == "SAME":
            return (h * sh, w * sw, self.n_out)
        kh, kw = self.kernel_size
        return ((h - 1) * sh + kh, (w - 1) * sw + kw, self.n_out)


@register_layer
@dataclasses.dataclass(frozen=True)
class SeparableConvolution2D(Layer):
    """Depthwise + pointwise conv (conf/layers/SeparableConvolution2D.java —
    the Xception building block)."""

    n_in: int = 0
    n_out: int = 0
    kernel_size: tuple = (3, 3)
    stride: tuple = (1, 1)
    padding: Any = "SAME"
    depth_multiplier: int = 1
    activation: str = "identity"
    weight_init: str = "relu"
    has_bias: bool = True

    def initialize(self, key, input_shape):
        c_in = self.n_in or input_shape[-1]
        kh, kw = self.kernel_size
        k1, k2 = jax.random.split(key)
        params = {
            "depthW": winit.init(k1, self.weight_init, (kh, kw, c_in, self.depth_multiplier)),
            "pointW": winit.init(k2, self.weight_init, (1, 1, c_in * self.depth_multiplier, self.n_out)),
        }
        if self.has_bias:
            params["b"] = jnp.zeros((self.n_out,))
        return params, {}

    def apply(self, params, state, x, *, training=False, key=None):
        x = self._maybe_dropout(x, training, key)
        y = nnops.separable_conv2d(
            x, params["depthW"], params["pointW"], params.get("b"),
            strides=self.stride, padding=self.padding,
        )
        return act.resolve(self.activation)(y), state

    def output_shape(self, input_shape):
        h, w, _ = input_shape
        sh, sw = self.stride
        if self.padding == "SAME":
            return (-(-h // sh), -(-w // sw), self.n_out)
        kh, kw = self.kernel_size
        return ((h - kh) // sh + 1, (w - kw) // sw + 1, self.n_out)


@register_layer
@dataclasses.dataclass(frozen=True)
class LocalResponseNormalization(Layer):
    """Cross-channel LRN (conf/layers/LocalResponseNormalization.java — the
    AlexNet-era normalization; GPU impl had a cuDNN helper)."""

    n: int = 5  # window (depth radius = n // 2)
    k: float = 2.0
    alpha: float = 1e-4
    beta: float = 0.75

    def has_params(self):
        return False

    def apply(self, params, state, x, *, training=False, key=None):
        y = nnops.lrn(x, depth_radius=self.n // 2, bias=self.k,
                      alpha=self.alpha, beta=self.beta)
        return y, state


@register_layer
@dataclasses.dataclass(frozen=True)
class Cropping2D(Layer):
    """(conf/layers/convolutional/Cropping2D.java)."""

    cropping: tuple = ((0, 0), (0, 0))  # ((top,bottom),(left,right))

    def has_params(self):
        return False

    def apply(self, params, state, x, *, training=False, key=None):
        (ct, cb), (cl, cr) = self.cropping
        return x[:, ct : x.shape[1] - cb, cl : x.shape[2] - cr, :], state

    def output_shape(self, input_shape):
        h, w, c = input_shape
        (ct, cb), (cl, cr) = self.cropping
        return (h - ct - cb, w - cl - cr, c)


@register_layer
@dataclasses.dataclass(frozen=True)
class LayerNormalization(Layer):
    """Layer norm over the last axis (SameDiff layers in the reference;
    first-class here for the transformer configs)."""

    n_out: int = 0

    def initialize(self, key, input_shape):
        c = self.n_out or input_shape[-1]
        return {"gamma": jnp.ones((c,)), "beta": jnp.zeros((c,))}, {}

    def apply(self, params, state, x, *, training=False, key=None):
        return nnops.layernorm(x, params["gamma"], params["beta"]), state


@register_layer
@dataclasses.dataclass(frozen=True)
class SharedLayer(Layer):
    """Weight-sharing reference: applies ``layer``'s computation with the
    params of the graph node named ``source`` (Keras multi-call layers; the
    reference models these as repeated KerasLayer instances over one weight
    set). Owns NO params — ComputationGraph resolves the source's params at
    apply time, and autodiff accumulates both call sites' gradients into the
    source automatically."""

    source: str = ""
    layer: Optional[Layer] = None

    def initialize(self, key, input_shape):
        return {}, {}

    def has_params(self):
        return False

    def output_shape(self, input_shape):
        return self.layer.output_shape(input_shape)

    def apply(self, params, state, x, *, training=False, key=None):
        raise RuntimeError(
            "SharedLayer is resolved by ComputationGraph (needs the source "
            "node's params); it cannot be applied standalone")

    def to_dict(self):
        d = super().to_dict()
        d["layer"] = self.layer.to_dict()
        return d
