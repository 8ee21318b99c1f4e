"""Recurrent layers — LSTM / GravesLSTM / GRU / SimpleRnn + RNN heads.

Reference parity: org/deeplearning4j/nn/conf/layers/{LSTM,GravesLSTM,
GravesBidirectionalLSTM,SimpleRnn,RnnOutputLayer,RnnLossLayer}.java, the
recurrent impls under org/deeplearning4j/nn/layers/recurrent/** (hand-written
activate/backpropGradient with LSTMHelpers.java; cuDNN fast path via
CudnnLSTMHelper — SURVEY.md §2.2 J10, BASELINE config #3), and the wrapper
layers conf/layers/recurrent/{Bidirectional,LastTimeStep}.java — path-cite,
mount empty this round.

TPU-native design:
- Data layout is **[batch, time, features]** (time-major inside the scan);
  the reference's [batch, features, time] is a BLAS-era artifact.
- The recurrence is ONE ``lax.scan`` whose body does a single fused
  [h]·U matmul; the input projection x·W for ALL timesteps is hoisted out of
  the scan into one big (B·T, F)×(F, 4H) matmul that XLA tiles onto the MXU —
  this replaces the cuDNN LSTM kernel (the north star's "cuDNN helpers become
  XLA HLO").
- There is no backpropGradient: JAX differentiates through the scan
  (reverse-mode over scan = the classic BPTT recurrence, with checkpointing
  available via jax.checkpoint at the network level).
- Masks: [batch, time] float/bool; masked steps pass the previous
  hidden/cell state through unchanged (variable-length parity).
- ``apply_seq`` exposes the carry for truncated BPTT and stateful
  ``rnnTimeStep`` inference.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import activations as act
from deeplearning4j_tpu.nn import losses as losses_mod
from deeplearning4j_tpu.nn import weights as winit
from deeplearning4j_tpu.nn.layers import Layer, register_layer
from deeplearning4j_tpu.ops import nn as nnops


def _merge_loss_weights(weights, mask):
    """Per-example loss weights (B,) and a sequence mask (B,T) compose by
    broadcasting the weights over time — both must gate the loss (the
    masters' padding weights must not silently drop the mask)."""
    if weights is None:
        return mask
    if mask is None:
        return weights
    return mask * weights.reshape(
        weights.shape + (1,) * (mask.ndim - weights.ndim))


@dataclasses.dataclass(frozen=True)
class BaseRecurrentLayer(Layer):
    """Common recurrent config: n_in/n_out, activations, weight inits."""

    n_in: int = 0
    n_out: int = 0
    activation: str = "tanh"
    gate_activation: str = "sigmoid"
    weight_init: str = "xavier"
    weight_init_recurrent: Optional[str] = None  # defaults to weight_init

    # -- carry API -----------------------------------------------------------
    def init_carry(self, batch_size: int, dtype=jnp.float32):
        """Zero hidden state (rnnClearPreviousState parity)."""
        raise NotImplementedError

    def apply_seq(self, params, x, carry, *, mask=None, training=False, key=None):
        """(B,T,F) + carry -> ((B,T,H), new_carry)."""
        raise NotImplementedError

    def apply(self, params, state, x, *, training=False, key=None, mask=None):
        x = self._maybe_dropout(x, training, key)
        y, _ = self.apply_seq(
            x=x, params=params, carry=self.init_carry(x.shape[0], x.dtype),
            mask=mask, training=training, key=key,
        )
        return y, state

    def output_shape(self, input_shape):
        t = input_shape[0] if len(input_shape) == 2 else None
        return (t, self.n_out)

    @staticmethod
    def _scan(step, carry, x, mask):
        """Time-major scan with mask-aware state passthrough."""
        xT = jnp.swapaxes(x, 0, 1)  # (T,B,F)
        maskT = None if mask is None else jnp.swapaxes(mask, 0, 1)  # (T,B)

        def body(c, inp):
            if maskT is None:
                xt = inp
                new_c, y = step(c, xt)
                return new_c, y
            xt, mt = inp
            new_c, y = step(c, xt)
            m = mt[:, None].astype(y.dtype)
            new_c = jax.tree_util.tree_map(
                lambda n, o: m * n + (1 - m) * o, new_c, c
            )
            return new_c, m * y

        inputs = xT if maskT is None else (xT, maskT)
        final_c, yT = jax.lax.scan(body, carry, inputs)
        return jnp.swapaxes(yT, 0, 1), final_c


@register_layer
@dataclasses.dataclass(frozen=True)
class LSTM(BaseRecurrentLayer):
    """Standard LSTM, no peepholes (conf/layers/LSTM.java; impl
    layers/recurrent/LSTM.java via LSTMHelpers). Gate order [i,f,o,g];
    forget-gate bias starts at ``forget_gate_bias_init`` (reference default 1)."""

    forget_gate_bias_init: float = 1.0

    def initialize(self, key, input_shape):
        n_in = self.n_in or input_shape[-1]
        h = self.n_out
        k1, k2 = jax.random.split(key)
        rec_init = self.weight_init_recurrent or self.weight_init
        b = jnp.zeros((4 * h,))
        b = b.at[h : 2 * h].set(self.forget_gate_bias_init)
        return {
            "W": winit.init(k1, self.weight_init, (n_in, 4 * h)),
            "U": winit.init(k2, rec_init, (h, 4 * h)),
            "b": b,
        }, {}

    def init_carry(self, batch_size, dtype=jnp.float32):
        h = self.n_out
        return (jnp.zeros((batch_size, h), dtype), jnp.zeros((batch_size, h), dtype))

    def apply_seq(self, params, x, carry, *, mask=None, training=False, key=None):
        h = self.n_out
        f_act = act.resolve(self.activation)
        g_act = act.resolve(self.gate_activation)
        # hoist the input projection out of the scan: one MXU matmul for all T
        xp = x @ params["W"].astype(x.dtype) + params["b"].astype(x.dtype)
        U = params["U"].astype(x.dtype)

        # kernel-engine dispatch (docs/KERNELS.md): the fused Pallas cell
        # replaces the scan body's matmul + gate chain with ONE kernel;
        # mask/TBPTT handling stays in _scan, shared with the exact path
        from deeplearning4j_tpu.ops import kernels as _kern
        from deeplearning4j_tpu.ops.kernels import lstm as _klstm

        xp0 = xp[:, 0] if xp.ndim == 3 else xp
        mode, tuned = _kern.dispatch(
            _klstm.supports(xp0, U, self.gate_activation, self.activation),
            op="lstm_cell",
            sig=_klstm.shape_signature(xp.shape[0], h),
            dtype=str(xp.dtype))
        if mode is not None:
            b_tile = tuned.get("b_tile")

            def step(c, xt):
                h_new, c_new = _klstm.lstm_cell_fused(
                    xt, c[0], c[1], U, _klstm.ORDER_IFOG, mode, b_tile)
                return (h_new, c_new), h_new

            return self._scan(step, carry, xp, mask)

        def step(c, xt):
            h_prev, c_prev = c
            z = xt + h_prev @ U
            i, f, o, g = jnp.split(z, 4, axis=-1)
            c_new = g_act(f) * c_prev + g_act(i) * f_act(g)
            h_new = g_act(o) * f_act(c_new)
            return (h_new, c_new), h_new

        return self._scan(step, carry, xp, mask)


@register_layer
@dataclasses.dataclass(frozen=True)
class GravesLSTM(BaseRecurrentLayer):
    """LSTM with peephole connections (conf/layers/GravesLSTM.java, after
    Graves 2013): i,f peek at c_{t-1}; o peeks at c_t."""

    forget_gate_bias_init: float = 1.0

    def initialize(self, key, input_shape):
        n_in = self.n_in or input_shape[-1]
        h = self.n_out
        k1, k2, k3 = jax.random.split(key, 3)
        rec_init = self.weight_init_recurrent or self.weight_init
        b = jnp.zeros((4 * h,))
        b = b.at[h : 2 * h].set(self.forget_gate_bias_init)
        return {
            "W": winit.init(k1, self.weight_init, (n_in, 4 * h)),
            "U": winit.init(k2, rec_init, (h, 4 * h)),
            "peep": winit.init(k3, "normal", (3, h)) * 0.1,  # [pi, pf, po]
            "b": b,
        }, {}

    def init_carry(self, batch_size, dtype=jnp.float32):
        h = self.n_out
        return (jnp.zeros((batch_size, h), dtype), jnp.zeros((batch_size, h), dtype))

    def apply_seq(self, params, x, carry, *, mask=None, training=False, key=None):
        f_act = act.resolve(self.activation)
        g_act = act.resolve(self.gate_activation)
        xp = x @ params["W"].astype(x.dtype) + params["b"].astype(x.dtype)
        peep = params["peep"]

        def step(c, xt):
            h_prev, c_prev = c
            z = xt + h_prev @ params["U"].astype(xt.dtype)
            i, f, o, g = jnp.split(z, 4, axis=-1)
            i = g_act(i + peep[0].astype(xt.dtype) * c_prev)
            f = g_act(f + peep[1].astype(xt.dtype) * c_prev)
            c_new = f * c_prev + i * f_act(g)
            o = g_act(o + peep[2].astype(xt.dtype) * c_new)
            h_new = o * f_act(c_new)
            return (h_new, c_new), h_new

        return self._scan(step, carry, xp, mask)


@register_layer
@dataclasses.dataclass(frozen=True)
class GRU(BaseRecurrentLayer):
    """Gated recurrent unit (libnd4j gruCell op / SameDiff gru — the DL4J
    layer zoo lacks a GRU config layer; first-class here). Gates [r,z,n];
    the reset gate multiplies the recurrent term AFTER the matmul (one fused
    (H,3H) product per step — the CuDNN/Keras ``reset_after`` formulation,
    which is also the MXU-friendly one). ``recurrent_bias`` adds the separate
    recurrent bias of that formulation (Keras GRU import)."""

    recurrent_bias: bool = False

    def initialize(self, key, input_shape):
        n_in = self.n_in or input_shape[-1]
        h = self.n_out
        k1, k2 = jax.random.split(key)
        rec_init = self.weight_init_recurrent or self.weight_init
        params = {
            "W": winit.init(k1, self.weight_init, (n_in, 3 * h)),
            "U": winit.init(k2, rec_init, (h, 3 * h)),
            "b": jnp.zeros((3 * h,)),
        }
        if self.recurrent_bias:
            params["b_rec"] = jnp.zeros((3 * h,))
        return params, {}

    def init_carry(self, batch_size, dtype=jnp.float32):
        return jnp.zeros((batch_size, self.n_out), dtype)

    def apply_seq(self, params, x, carry, *, mask=None, training=False, key=None):
        h = self.n_out
        f_act = act.resolve(self.activation)
        g_act = act.resolve(self.gate_activation)
        xp = x @ params["W"].astype(x.dtype) + params["b"].astype(x.dtype)
        b_rec = params.get("b_rec")

        def step(h_prev, xt):
            hU = h_prev @ params["U"].astype(xt.dtype)
            if b_rec is not None:
                hU = hU + b_rec.astype(xt.dtype)
            xr, xz, xn = jnp.split(xt, 3, axis=-1)
            hr, hz, hn = jnp.split(hU, 3, axis=-1)
            r = g_act(xr + hr)
            z = g_act(xz + hz)
            n = f_act(xn + r * hn)
            h_new = (1 - z) * n + z * h_prev
            return h_new, h_new

        return self._scan(step, carry, xp, mask)


@register_layer
@dataclasses.dataclass(frozen=True)
class SimpleRnn(BaseRecurrentLayer):
    """Vanilla RNN: h_t = act(x·W + h·U + b) (conf/layers/recurrent/
    SimpleRnn.java)."""

    def initialize(self, key, input_shape):
        n_in = self.n_in or input_shape[-1]
        h = self.n_out
        k1, k2 = jax.random.split(key)
        rec_init = self.weight_init_recurrent or self.weight_init
        return {
            "W": winit.init(k1, self.weight_init, (n_in, h)),
            "U": winit.init(k2, rec_init, (h, h)),
            "b": jnp.zeros((h,)),
        }, {}

    def init_carry(self, batch_size, dtype=jnp.float32):
        return jnp.zeros((batch_size, self.n_out), dtype)

    def apply_seq(self, params, x, carry, *, mask=None, training=False, key=None):
        f_act = act.resolve(self.activation)
        xp = x @ params["W"].astype(x.dtype) + params["b"].astype(x.dtype)

        def step(h_prev, xt):
            h_new = f_act(xt + h_prev @ params["U"].astype(xt.dtype))
            return h_new, h_new

        return self._scan(step, carry, xp, mask)


@register_layer
@dataclasses.dataclass(frozen=True)
class Bidirectional(Layer):
    """Bidirectional wrapper (conf/layers/recurrent/Bidirectional.java):
    runs the wrapped recurrent layer forward and time-reversed, combines via
    ``mode``: concat | add | mul | ave. GravesBidirectionalLSTM parity =
    Bidirectional(GravesLSTM(...))."""

    layer: Any = None  # a BaseRecurrentLayer config
    mode: str = "concat"

    def initialize(self, key, input_shape):
        k1, k2 = jax.random.split(key)
        pf, _ = self.layer.initialize(k1, input_shape)
        pb, _ = self.layer.initialize(k2, input_shape)
        return {"fwd": pf, "bwd": pb}, {}

    def apply(self, params, state, x, *, training=False, key=None, mask=None):
        x = self._maybe_dropout(x, training, key)
        lyr = self.layer
        yf, _ = lyr.apply_seq(
            params["fwd"], x, lyr.init_carry(x.shape[0], x.dtype),
            mask=mask, training=training,
        )
        # time-reverse input (and mask), run, reverse back
        xr = jnp.flip(x, axis=1)
        mr = None if mask is None else jnp.flip(mask, axis=1)
        yb, _ = lyr.apply_seq(
            params["bwd"], xr, lyr.init_carry(x.shape[0], x.dtype),
            mask=mr, training=training,
        )
        yb = jnp.flip(yb, axis=1)
        m = self.mode.lower()
        if m == "concat":
            y = jnp.concatenate([yf, yb], axis=-1)
        elif m == "add":
            y = yf + yb
        elif m == "mul":
            y = yf * yb
        elif m in ("ave", "average"):
            y = (yf + yb) / 2
        else:
            raise ValueError(f"unknown Bidirectional mode {self.mode}")
        return y, state

    def output_shape(self, input_shape):
        t, f = self.layer.output_shape(input_shape)
        return (t, 2 * f) if self.mode.lower() == "concat" else (t, f)

    def to_dict(self):
        d = super().to_dict()
        d["layer"] = self.layer.to_dict()
        return d


@register_layer
@dataclasses.dataclass(frozen=True)
class ConvLSTM2D(Layer):
    """Convolutional LSTM over image sequences (Shi et al. 2015; the
    reference imports Keras ConvLSTM2D via KerasConvLSTM2D.java — path-cite,
    mount empty). Input (B, T, H, W, C) -> (B, T, H', W', filters), or the
    final hidden state (B, H', W', filters) when ``return_sequences=False``.

    TPU-native shape: the input convolution for ALL timesteps is hoisted out
    of the scan into one big (B*T) batched convolution on the MXU; the scan
    body adds only the recurrent convolution (stride 1, SAME — keeps the
    spatial dims, as in Keras). Gate order [i, f, o, g]."""

    n_in: int = 0
    n_out: int = 0               # filters
    kernel_size: Tuple[int, int] = (3, 3)
    stride: Tuple[int, int] = (1, 1)
    padding: Any = "SAME"        # input-conv padding; recurrent conv is SAME
    activation: str = "tanh"
    gate_activation: str = "sigmoid"
    weight_init: str = "xavier"
    return_sequences: bool = True
    forget_gate_bias_init: float = 1.0

    def initialize(self, key, input_shape):
        c_in = self.n_in or input_shape[-1]
        kh, kw = self.kernel_size
        f = self.n_out
        k1, k2 = jax.random.split(key)
        b = jnp.zeros((4 * f,))
        b = b.at[f : 2 * f].set(self.forget_gate_bias_init)
        return {
            "W": winit.init(k1, self.weight_init, (kh, kw, c_in, 4 * f)),
            "U": winit.init(k2, self.weight_init, (kh, kw, f, 4 * f)),
            "b": b,
        }, {}

    def apply(self, params, state, x, *, training=False, key=None, mask=None):
        x = self._maybe_dropout(x, training, key)
        B, T = x.shape[:2]
        f = self.n_out
        f_act = act.resolve(self.activation)
        g_act = act.resolve(self.gate_activation)
        xp = nnops.conv2d(
            x.reshape((B * T,) + x.shape[2:]), params["W"].astype(x.dtype),
            params["b"].astype(x.dtype), strides=self.stride,
            padding=self.padding)
        xp = xp.reshape((B, T) + xp.shape[1:])          # (B,T,H',W',4F)
        h0 = jnp.zeros((B,) + xp.shape[2:4] + (f,), x.dtype)
        carry = (h0, h0)
        xT = jnp.swapaxes(xp, 0, 1)                     # (T,B,H',W',4F)
        maskT = None if mask is None else jnp.swapaxes(mask, 0, 1)
        U = params["U"]

        def body(c, inp):
            xt = inp if maskT is None else inp[0]
            h_prev, c_prev = c
            z = xt + nnops.conv2d(h_prev, U.astype(xt.dtype), None,
                                  strides=(1, 1), padding="SAME")
            i, fg, o, g = jnp.split(z, 4, axis=-1)
            c_new = g_act(fg) * c_prev + g_act(i) * f_act(g)
            h_new = g_act(o) * f_act(c_new)
            if maskT is None:
                return (h_new, c_new), h_new
            m = inp[1].reshape(inp[1].shape + (1,) * 3).astype(h_new.dtype)
            keep = jax.tree_util.tree_map(
                lambda n, old: m * n + (1 - m) * old,
                (h_new, c_new), c)
            return keep, m * h_new

        inputs = xT if maskT is None else (xT, maskT)
        (h_fin, _), yT = jax.lax.scan(body, carry, inputs)
        if not self.return_sequences:
            return h_fin, state
        return jnp.swapaxes(yT, 0, 1), state

    def output_shape(self, input_shape):
        t, h, w, _ = input_shape
        sh, sw = self.stride
        kh, kw = self.kernel_size
        if self.padding == "SAME":
            oh, ow = -(-h // sh), -(-w // sw)
        else:  # VALID
            oh, ow = (h - kh) // sh + 1, (w - kw) // sw + 1
        if not self.return_sequences:
            return (oh, ow, self.n_out)
        return (t, oh, ow, self.n_out)


@register_layer
@dataclasses.dataclass(frozen=True)
class LastTimeStep(Layer):
    """Extract the last (mask-aware) timestep: (B,T,F) -> (B,F)
    (conf/layers/recurrent/LastTimeStep.java wraps a layer; here it is a
    standalone stage — place it after the recurrent layer)."""

    def has_params(self):
        return False

    def apply(self, params, state, x, *, training=False, key=None, mask=None):
        if mask is None:
            return x[:, -1, :], state
        idx = jnp.maximum(jnp.sum(mask, axis=1).astype(jnp.int32) - 1, 0)
        return x[jnp.arange(x.shape[0]), idx, :], state

    def output_shape(self, input_shape):
        return (input_shape[-1],)


@register_layer
@dataclasses.dataclass(frozen=True)
class RnnOutputLayer(Layer):
    """Per-timestep dense + loss head (conf/layers/RnnOutputLayer.java).
    Loss is averaged over (batch, time), honoring the label mask."""

    n_in: int = 0
    n_out: int = 0
    loss: str = "mcxent"
    activation: str = "softmax"
    weight_init: str = "xavier"

    def initialize(self, key, input_shape):
        n_in = self.n_in or input_shape[-1]
        return {
            "W": winit.init(key, self.weight_init, (n_in, self.n_out)),
            "b": jnp.zeros((self.n_out,)),
        }, {}

    def _logits(self, params, x):
        return x @ params["W"].astype(x.dtype) + params["b"].astype(x.dtype)

    def apply(self, params, state, x, *, training=False, key=None, mask=None):
        return act.resolve(self.activation)(self._logits(params, x)), state

    def compute_loss(self, params, state, x, labels, *, training=True, key=None,
                     weights=None, mask=None):
        x = self._maybe_dropout(x, training, key)
        logits = self._logits(params, x)
        logits_fn, act_fn, fused_act = losses_mod.resolve(self.loss)
        w = _merge_loss_weights(weights, mask)
        if logits_fn is not None and fused_act == self.activation.lower():
            return logits_fn(logits, labels, w)
        preds = act.resolve(self.activation)(logits)
        if act_fn is None:
            raise ValueError(f"loss {self.loss} requires activation {fused_act}")
        return act_fn(preds, labels, w)

    def output_shape(self, input_shape):
        return (input_shape[0], self.n_out)


@register_layer
@dataclasses.dataclass(frozen=True)
class RnnLossLayer(Layer):
    """Loss-only RNN head (conf/layers/RnnLossLayer.java)."""

    loss: str = "mcxent"
    activation: str = "softmax"

    def has_params(self):
        return False

    def apply(self, params, state, x, *, training=False, key=None, mask=None):
        return act.resolve(self.activation)(x), state

    def compute_loss(self, params, state, x, labels, *, training=True, key=None,
                     weights=None, mask=None):
        logits_fn, act_fn, fused_act = losses_mod.resolve(self.loss)
        w = _merge_loss_weights(weights, mask)
        if logits_fn is not None and fused_act == self.activation.lower():
            return logits_fn(x, labels, w)
        preds = act.resolve(self.activation)(x)
        if act_fn is None:
            raise ValueError(f"loss {self.loss} requires activation {fused_act}")
        return act_fn(preds, labels, w)

    def output_shape(self, input_shape):
        return tuple(input_shape)


@register_layer
@dataclasses.dataclass(frozen=True)
class GravesBidirectionalLSTM(Layer):
    """conf/layers/GravesBidirectionalLSTM.java parity: a named convenience
    for Bidirectional(GravesLSTM) with separate forward/backward cells and
    concat merging (the reference's fixed behavior)."""

    n_in: int = 0
    n_out: int = 0
    activation: str = "tanh"
    gate_activation: str = "sigmoid"
    weight_init: str = "xavier"

    def _inner(self):
        cell = GravesLSTM(
            n_in=self.n_in, n_out=self.n_out, activation=self.activation,
            gate_activation=self.gate_activation, weight_init=self.weight_init,
            dropout=self.dropout)  # forward the input-dropout rate
        return Bidirectional(layer=cell, mode="concat")

    def initialize(self, key, input_shape):
        return self._inner().initialize(key, input_shape)

    def apply(self, params, state, x, *, training=False, key=None, mask=None):
        return self._inner().apply(params, state, x, training=training,
                                   key=key, mask=mask)

    def output_shape(self, input_shape):
        return self._inner().output_shape(input_shape)
