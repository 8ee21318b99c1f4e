"""Recurrent ops: whole-layer LSTM/GRU/RNN scans (the sd.rnn namespace).

Reference parity: libnd4j declarable ops ops/declarable/generic/recurrent/
(lstmLayer.cpp, gruCell.cpp, sruCell.cpp …) and the cuDNN lstmLayer platform
helper — path-cite, mount empty this round. The reference runs cell kernels
inside a host loop (or hands the whole sequence to cuDNN); the TPU-native
form is ONE ``lax.scan`` over time per direction — XLA unrolls nothing, the
MXU sees one fused (x·W + h·R) per step, and the whole layer is a single
compiled region.

Parameterization follows ONNX (the import path that needs these ops):
stacked per-direction weights, ONNX gate orders (LSTM ``iofc``, GRU ``zrh``),
optional initial states, ``layout`` 0 = seq-major (T,B,C) / 1 = batch-major
(B,T,C). deeplearning4j_tpu.nn.recurrent keeps its own layer classes (DL4J
layer-API parity); these ops serve SameDiff/import/namespace users.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.ops.registry import op
from deeplearning4j_tpu.ops import nn as nnops


def _act(name):
    return {
        "sigmoid": jax.nn.sigmoid, "tanh": jnp.tanh, "relu": jax.nn.relu,
        "identity": (lambda x: x), "softsign": jax.nn.soft_sign,
        "softplus": jax.nn.softplus, "hardsigmoid": jax.nn.hard_sigmoid,
        "elu": jax.nn.elu, "leakyrelu": jax.nn.leaky_relu,
    }[name.lower()]


def _split_b(b, n, h):
    """ONNX B is (2n*h,): input-bias block then recurrent-bias block."""
    if b is None:
        return jnp.zeros((n * h,)), jnp.zeros((n * h,))
    return b[: n * h], b[n * h:]


def _mask_step(new, old, t, seq_lens):
    """Freeze state for finished sequences (ONNX sequence_lens semantics)."""
    if seq_lens is None:
        return new
    alive = (t < seq_lens)[:, None]
    return jnp.where(alive, new, old)


def _scan_dir(step, x_tbc, carry, seq_lens, reverse):
    T = x_tbc.shape[0]
    ts = jnp.arange(T)
    if reverse:
        x_tbc = jnp.flip(x_tbc, axis=0)
        ts = jnp.flip(ts, axis=0)
    carry, ys = lax.scan(step, carry, (x_tbc, ts))
    if reverse:
        ys = jnp.flip(ys, axis=0)
    return carry, ys


def _directions(direction):
    direction = direction.lower()
    if direction == "forward":
        return [False]
    if direction == "reverse":
        return [True]
    if direction == "bidirectional":
        return [False, True]
    raise ValueError(f"unknown direction {direction!r}")


def _seq_major(x, layout):
    return x if int(layout) == 0 else jnp.swapaxes(x, 0, 1)


@op("lstm_layer", "rnn", aliases=("lstmLayer", "lstm"))
def lstm_layer(x, W, R, b=None, seq_lens=None, h0=None, c0=None, *,
               hidden_size, direction="forward", layout=0,
               gate_activation="sigmoid", activation="tanh"):
    """ONNX-semantics LSTM over a full sequence.

    x: (T,B,I) [layout 0] or (B,T,I) [layout 1]; W: (D, 4H, I); R: (D, 4H, H);
    b: (D, 8H); gate order i,o,f,c (ONNX). Returns (Y, Y_h, Y_c) with
    Y (T,D,B,H) [layout 0] / (B,T,D,H) [layout 1], Y_h/Y_c (D,B,H)."""
    h = int(hidden_size)
    x = _seq_major(x, layout)
    if int(layout) == 1:  # ONNX layout=1 states are (B,D,H)
        h0 = None if h0 is None else jnp.swapaxes(h0, 0, 1)
        c0 = None if c0 is None else jnp.swapaxes(c0, 0, 1)
    T, B = x.shape[0], x.shape[1]
    f_g = _act(gate_activation)
    f_c = _act(activation)
    outs, hs, cs = [], [], []
    from deeplearning4j_tpu.ops import kernels as _kern
    from deeplearning4j_tpu.ops.kernels import lstm as _klstm

    for d, reverse in enumerate(_directions(direction)):
        Wd, Rd = W[d].T, R[d].T           # (I,4H), (H,4H)
        bi, br = _split_b(b[d] if b is not None else None, 4, h)
        bias = (bi + br).astype(x.dtype)
        hd = jnp.zeros((B, h), x.dtype) if h0 is None else h0[d].astype(x.dtype)
        cd = jnp.zeros((B, h), x.dtype) if c0 is None else c0[d].astype(x.dtype)

        # kernel-engine dispatch (docs/KERNELS.md): hoist the input
        # projection out of the scan (one MXU matmul for all T) and run the
        # recurrent matmul + gate block as the fused Pallas cell. ONNX gate
        # order i,o,f,c maps to the kernel's static ORDER_IOFG.
        Rd_x = jnp.asarray(Rd, x.dtype)
        xp_probe = jnp.zeros((B, 4 * h), x.dtype)
        mode, tuned = _kern.dispatch(
            _klstm.supports(xp_probe, Rd_x, gate_activation, activation),
            op="lstm_cell", sig=_klstm.shape_signature(B, h),
            dtype=str(x.dtype))
        if mode is not None:
            xp_all = x @ jnp.asarray(Wd, x.dtype) + bias   # (T, B, 4H)
            b_tile = tuned.get("b_tile")

            def step(carry, xp_t, Rd_x=Rd_x):
                hp, cp = carry
                xt, t = xp_t
                h_new, c_new = _klstm.lstm_cell_fused(
                    xt, hp, cp, Rd_x, _klstm.ORDER_IOFG, mode, b_tile)
                c_new = _mask_step(c_new, cp, t, seq_lens)
                h_new = _mask_step(h_new, hp, t, seq_lens)
                return (h_new, c_new), h_new

            (hd, cd), ys = _scan_dir(step, xp_all, (hd, cd), seq_lens,
                                     reverse)
            outs.append(ys)
            hs.append(hd)
            cs.append(cd)
            continue

        def step(carry, xt_t, Wd=Wd, Rd=Rd, bias=bias):
            hp, cp = carry
            xt, t = xt_t
            z = xt @ Wd + hp @ Rd + bias
            i_g, o_g, f_gate, c_in = jnp.split(z, 4, axis=-1)
            i_g, o_g, f_gate = f_g(i_g), f_g(o_g), f_g(f_gate)
            c_new = f_gate * cp + i_g * f_c(c_in)
            h_new = o_g * f_c(c_new)
            c_new = _mask_step(c_new, cp, t, seq_lens)
            h_new = _mask_step(h_new, hp, t, seq_lens)
            return (h_new, c_new), h_new

        (hd, cd), ys = _scan_dir(step, x, (hd, cd), seq_lens, reverse)
        outs.append(ys)
        hs.append(hd)
        cs.append(cd)
    Y = jnp.stack(outs, axis=1)            # (T, D, B, H)
    Yh, Yc = jnp.stack(hs, axis=0), jnp.stack(cs, axis=0)  # (D, B, H)
    if int(layout) == 1:                   # ONNX layout=1: batch-major
        Y = jnp.transpose(Y, (2, 0, 1, 3))        # (B, T, D, H)
        Yh = jnp.swapaxes(Yh, 0, 1)               # (B, D, H)
        Yc = jnp.swapaxes(Yc, 0, 1)
    return Y, Yh, Yc


@op("gru_layer", "rnn", aliases=("gruLayer", "gru"))
def gru_layer(x, W, R, b=None, seq_lens=None, h0=None, *,
              hidden_size, direction="forward", layout=0,
              linear_before_reset=0, gate_activation="sigmoid",
              activation="tanh"):
    """ONNX-semantics GRU. W: (D, 3H, I); R: (D, 3H, H); b: (D, 6H); gate
    order z,r,h (ONNX). ``linear_before_reset=1`` is the CuDNN/Keras
    reset-after form; 0 multiplies r before the recurrent matmul."""
    h = int(hidden_size)
    x = _seq_major(x, layout)
    if int(layout) == 1:
        h0 = None if h0 is None else jnp.swapaxes(h0, 0, 1)
    B = x.shape[1]
    f_g = _act(gate_activation)
    f_c = _act(activation)
    outs, hs = [], []
    for d, reverse in enumerate(_directions(direction)):
        Wd, Rd = W[d].T, R[d].T           # (I,3H), (H,3H)
        bi, br = _split_b(b[d] if b is not None else None, 3, h)
        bi = bi.astype(x.dtype)
        br = br.astype(x.dtype)
        hd = jnp.zeros((B, h), x.dtype) if h0 is None else h0[d].astype(x.dtype)

        def step(carry, xt_t, Wd=Wd, Rd=Rd, bi=bi, br=br):
            hp = carry
            xt, t = xt_t
            xw = xt @ Wd + bi              # (B, 3H): z,r,h blocks
            xz, xr, xh = jnp.split(xw, 3, axis=-1)
            if linear_before_reset:
                hw = hp @ Rd + br
                hz, hr, hh = jnp.split(hw, 3, axis=-1)
                z = f_g(xz + hz)
                r = f_g(xr + hr)
                n = f_c(xh + r * hh)
            else:
                Rz, Rr, Rn = jnp.split(Rd, 3, axis=-1)
                bz, brr, bn = jnp.split(br, 3, axis=-1)
                z = f_g(xz + hp @ Rz + bz)
                r = f_g(xr + hp @ Rr + brr)
                n = f_c(xh + (r * hp) @ Rn + bn)
            h_new = (1.0 - z) * n + z * hp
            h_new = _mask_step(h_new, hp, t, seq_lens)
            return h_new, h_new

        hd, ys = _scan_dir(step, x, hd, seq_lens, reverse)
        outs.append(ys)
        hs.append(hd)
    Y = jnp.stack(outs, axis=1)
    Yh = jnp.stack(hs, axis=0)
    if int(layout) == 1:
        Y = jnp.transpose(Y, (2, 0, 1, 3))
        Yh = jnp.swapaxes(Yh, 0, 1)
    return Y, Yh


@op("rnn_layer", "rnn", aliases=("simple_rnn",))
def rnn_layer(x, W, R, b=None, seq_lens=None, h0=None, *,
              hidden_size, direction="forward", layout=0, activation="tanh"):
    """ONNX-semantics vanilla RNN. W: (D, H, I); R: (D, H, H); b: (D, 2H)."""
    h = int(hidden_size)
    x = _seq_major(x, layout)
    if int(layout) == 1:
        h0 = None if h0 is None else jnp.swapaxes(h0, 0, 1)
    B = x.shape[1]
    f_c = _act(activation)
    outs, hs = [], []
    for d, reverse in enumerate(_directions(direction)):
        Wd, Rd = W[d].T, R[d].T
        bi, br = _split_b(b[d] if b is not None else None, 1, h)
        bias = (bi + br).astype(x.dtype)
        hd = jnp.zeros((B, h), x.dtype) if h0 is None else h0[d].astype(x.dtype)

        def step(carry, xt_t, Wd=Wd, Rd=Rd, bias=bias):
            hp = carry
            xt, t = xt_t
            h_new = f_c(xt @ Wd + hp @ Rd + bias)
            h_new = _mask_step(h_new, hp, t, seq_lens)
            return h_new, h_new

        hd, ys = _scan_dir(step, x, hd, seq_lens, reverse)
        outs.append(ys)
        hs.append(hd)
    Y = jnp.stack(outs, axis=1)
    Yh = jnp.stack(hs, axis=0)
    if int(layout) == 1:
        Y = jnp.transpose(Y, (2, 0, 1, 3))
        Yh = jnp.swapaxes(Yh, 0, 1)
    return Y, Yh


@op("lstm_cell", "rnn", aliases=("lstmCell",))
def lstm_cell(x, h_prev, c_prev, W, R, b=None, *,
              gate_activation="sigmoid", activation="tanh"):
    """One LSTM step (gruCell.cpp/lstmCell parity). x: (B,I); W: (4H,I);
    R: (4H,H); b: (8H,). Gate order i,o,f,c. Returns (h, c)."""
    h = h_prev.shape[-1]
    f_g = _act(gate_activation)
    f_c = _act(activation)
    bi, br = _split_b(b, 4, h)
    z = x @ W.T + h_prev @ R.T + (bi + br).astype(x.dtype)
    i_g, o_g, f_gate, c_in = jnp.split(z, 4, axis=-1)
    c_new = f_g(f_gate) * c_prev + f_g(i_g) * f_c(c_in)
    h_new = f_g(o_g) * f_c(c_new)
    return h_new, c_new


@op("gru_cell", "rnn", aliases=("gruCell",))
def gru_cell(x, h_prev, W, R, b=None, *, linear_before_reset=1,
             gate_activation="sigmoid", activation="tanh"):
    """One GRU step. x: (B,I); W: (3H,I); R: (3H,H); b: (6H,). Order z,r,h."""
    h = h_prev.shape[-1]
    f_g = _act(gate_activation)
    f_c = _act(activation)
    bi, br = _split_b(b, 3, h)
    xw = x @ W.T + bi.astype(x.dtype)
    xz, xr, xh = jnp.split(xw, 3, axis=-1)
    if linear_before_reset:
        hw = h_prev @ R.T + br.astype(x.dtype)
        hz, hr, hh = jnp.split(hw, 3, axis=-1)
        z, r = f_g(xz + hz), f_g(xr + hr)
        n = f_c(xh + r * hh)
    else:
        Rz, Rr, Rn = jnp.split(R, 3, axis=0)
        bz, brr, bn = jnp.split(br.astype(x.dtype), 3)
        z = f_g(xz + h_prev @ Rz.T + bz)
        r = f_g(xr + h_prev @ Rr.T + brr)
        n = f_c(xh + (r * h_prev) @ Rn.T + bn)
    return (1.0 - z) * n + z * h_prev


@op("sequence_mask", "rnn", differentiable=False)
def sequence_mask(lengths, maxlen=None, dtype=jnp.bool_):
    """lengths (B,) -> (B, maxlen) mask (generic/parity_ops/sequence_mask.cpp,
    path-cite). ``maxlen`` must be static (it sets the output shape, an XLA
    requirement); omitting it is only possible with concrete lengths."""
    if maxlen is None:
        if isinstance(lengths, jax.core.Tracer):
            raise ValueError(
                "sequence_mask under jit needs an explicit maxlen — the "
                "output shape cannot depend on traced values (XLA static "
                "shapes)")
        arr = np.asarray(lengths)
        maxlen = int(arr.max()) if arr.size else 0
    r = jnp.arange(maxlen)
    return (r[None, :] < jnp.asarray(lengths)[:, None]).astype(dtype)


@op("sru_cell", "rnn", aliases=("sruCell",))
def sru_cell(x, c_prev, W, b):
    """One Simple Recurrent Unit step (generic/recurrent/sruCell.cpp,
    path-cite; Lei et al. 2017). x: (B, I); c_prev: (B, I); W: (3I, I);
    b: (2I,). Returns (h, c). SRU's highway form requires n_out == n_in."""
    i = x.shape[-1]
    if W.shape != (3 * i, i) or b.shape != (2 * i,):
        raise ValueError(
            f"sru_cell expects W (3I,I)={3 * i, i} and b (2I,)={2 * i,}; "
            f"got W {W.shape}, b {b.shape}")
    z = x @ W.T.astype(x.dtype)                      # (B, 3I)
    zt, f_in, r_in = jnp.split(z, 3, axis=-1)
    bf, br = jnp.split(b.astype(x.dtype), 2)
    f = jax.nn.sigmoid(f_in + bf)
    r = jax.nn.sigmoid(r_in + br)
    c = f * c_prev + (1.0 - f) * zt
    h = r * jnp.tanh(c) + (1.0 - r) * x
    return h, c


@op("sru", "rnn", aliases=("sru_layer",))
def sru(x, W, b, c0=None, mask=None, layout=1):
    """Whole-sequence SRU (generic/recurrent/sru.cpp, path-cite). The
    elementwise recurrence has NO recurrent matmul, so the scan body is
    pure vector math — the big (B*T, I)x(I, 3I) projection is hoisted out
    and hits the MXU once. layout 1 = (B, T, I), 0 = (T, B, I). Returns
    (h_seq, c_final)."""
    if layout == 1:
        x = jnp.swapaxes(x, 0, 1)                    # (T, B, I)
        if mask is not None:
            mask = jnp.swapaxes(mask, 0, 1)
    t, bsz, i = x.shape
    z = (x.reshape(t * bsz, i) @ W.T.astype(x.dtype)).reshape(t, bsz, 3 * i)
    zt, f_in, r_in = jnp.split(z, 3, axis=-1)
    bf, br = jnp.split(b.astype(x.dtype), 2)
    f = jax.nn.sigmoid(f_in + bf)
    r = jax.nn.sigmoid(r_in + br)
    c_init = jnp.zeros((bsz, i), x.dtype) if c0 is None else c0.astype(x.dtype)

    def body(c, inp):
        if mask is None:
            xt, zt_, ft, rt = inp
            c_new = ft * c + (1.0 - ft) * zt_
        else:
            xt, zt_, ft, rt, mt = inp
            c_new = ft * c + (1.0 - ft) * zt_
            m = mt[:, None].astype(c.dtype)
            c_new = m * c_new + (1.0 - m) * c
        h = rt * jnp.tanh(c_new) + (1.0 - rt) * xt
        if mask is not None:
            h = h * mt[:, None].astype(h.dtype)
        return c_new, h

    seq = (x, zt, f, r) if mask is None else (x, zt, f, r, mask)
    c_fin, h = lax.scan(body, c_init, seq)
    if layout == 1:
        h = jnp.swapaxes(h, 0, 1)
    return h, c_fin


@op("conv_lstm_2d", "rnn", aliases=("convLstm2d",))
def conv_lstm_2d(x, W, U, b=None, h0=None, c0=None, *, stride=(1, 1),
                 padding="SAME", gate_activation="sigmoid",
                 activation="tanh"):
    """Convolutional LSTM over (B, T, H, W, C) (Shi et al. 2015; the
    reference ships this capability via Keras import — KerasConvLSTM2D.java,
    path-cite). W: (kh, kw, Cin, 4F) input-conv kernel; U: (kh, kw, F, 4F)
    recurrent kernel (stride 1, SAME). Gate order [i, f, o, g]. Returns
    (y_seq, (h_fin, c_fin)). The input convolution for ALL timesteps runs as
    one batched MXU convolution outside the scan."""
    f_act = _act(activation)
    g_act = _act(gate_activation)
    bsz, t = x.shape[:2]
    nf = W.shape[-1] // 4
    xp = nnops.conv2d(x.reshape((bsz * t,) + x.shape[2:]), W.astype(x.dtype),
                      None if b is None else b.astype(x.dtype),
                      strides=stride, padding=padding)
    xp = xp.reshape((bsz, t) + xp.shape[1:])
    zeros = jnp.zeros((bsz,) + xp.shape[2:4] + (nf,), x.dtype)
    h_init = zeros if h0 is None else h0.astype(x.dtype)
    c_init = zeros if c0 is None else c0.astype(x.dtype)

    def body(carry, xt):
        h_prev, c_prev = carry
        z = xt + nnops.conv2d(h_prev, U.astype(xt.dtype), None,
                              strides=(1, 1), padding="SAME")
        i_g, f_g, o_g, g_g = jnp.split(z, 4, axis=-1)
        c_new = g_act(f_g) * c_prev + g_act(i_g) * f_act(g_g)
        h_new = g_act(o_g) * f_act(c_new)
        return (h_new, c_new), h_new

    (h_fin, c_fin), y = lax.scan(body, (h_init, c_init),
                                 jnp.swapaxes(xp, 0, 1))
    return jnp.swapaxes(y, 0, 1), (h_fin, c_fin)


def _lstm_block_step(xt, cs_prev, h_prev, W, b, wci, wcf, wco, *,
                     forget_bias, cell_clip, use_peephole):
    """One TF-BlockLSTM step. Gate order i, ci(g), f, o; returns the seven
    per-step tensors the TF kernel exposes."""
    z = jnp.concatenate([xt, h_prev], axis=1) @ W + b
    i, ci, f, o = jnp.split(z, 4, axis=-1)
    if use_peephole:
        i = i + cs_prev * wci
        f = f + cs_prev * wcf
    i = jax.nn.sigmoid(i)
    f = jax.nn.sigmoid(f + forget_bias)
    ci = jnp.tanh(ci)
    cs = ci * i + cs_prev * f
    if cell_clip > 0:
        cs = jnp.clip(cs, -cell_clip, cell_clip)
    if use_peephole:
        o = o + cs * wco
    o = jax.nn.sigmoid(o)
    co = jnp.tanh(cs)
    h = co * o
    return i, cs, f, o, ci, co, h


@op("lstm_block_cell", "rnn", aliases=("lstmBlockCell",))
def lstm_block_cell(x, cs_prev, h_prev, W, wci, wcf, wco, b, *,
                    forget_bias=1.0, cell_clip=-1.0, use_peephole=False):
    """Fused single-step LSTM cell, TF LSTMBlockCell / libnd4j lstmBlockCell
    contract (ops/declarable/generic/recurrent/lstmBlockCell.cpp, path-cite
    — mount empty): x (B,I); W ((I+H),4H) with gate order i,c,f,o; optional
    peepholes. Returns (i, cs, f, o, ci, co, h)."""
    return _lstm_block_step(x, cs_prev, h_prev, W, b, wci, wcf, wco,
                            forget_bias=forget_bias, cell_clip=cell_clip,
                            use_peephole=use_peephole)


@op("lstm_block", "rnn", aliases=("lstmBlock", "block_lstm"))
def lstm_block(seq_len_max, x, cs_prev, h_prev, W, wci, wcf, wco, b, *,
               forget_bias=1.0, cell_clip=-1.0, use_peephole=False):
    """Fused whole-sequence LSTM, TF BlockLSTM(V2) / libnd4j lstmBlock
    contract (recurrent/lstmBlock.cpp, path-cite): x (T,B,I); one scan with
    the projection fused per step; steps at or past ``seq_len_max`` emit
    zeros and carry the state through unchanged (the TF kernel's
    sequence-length semantics). Returns seven (T,B,H) stacks
    (i, cs, f, o, ci, co, h)."""
    T = x.shape[0]
    limit = jnp.asarray(seq_len_max, jnp.int32)

    def body(carry, inp):
        cs_p, h_p = carry
        xt, t = inp
        outs = _lstm_block_step(xt, cs_p, h_p, W, b, wci, wcf, wco,
                                forget_bias=forget_bias,
                                cell_clip=cell_clip,
                                use_peephole=use_peephole)
        active = (t < limit)
        zeros = tuple(jnp.where(active, v, jnp.zeros_like(v)) for v in outs)
        cs_new = jnp.where(active, outs[1], cs_p)
        h_new = jnp.where(active, outs[6], h_p)
        return (cs_new, h_new), zeros

    (_, _), ys = lax.scan(body, (cs_prev, h_prev),
                          (x, jnp.arange(T, dtype=jnp.int32)))
    return ys


# ---------------------------------------------------------------------------
# Round-5 tail: libnd4j generic/recurrent static/dynamic RNN ops + sru_bi
# (static_rnn.cpp, dynamic_rnn.cpp, static_bidirectional_rnn.cpp,
#  dynamic_bidirectional_rnn.cpp, sru_bi — path-cites, mount empty).
# Reference signature: simple-RNN cell with Wx (I,H), Wh (H,H), b (H,).
# "static" unrolls the loop in the graph, "dynamic" iterates — under XLA
# both compile to one program; we keep BOTH shapes (unrolled HLO vs scan)
# because compile time and fusion behaviour genuinely differ (r4 LSTM
# A/B, 2026-07: same speed, 3.4x compile-time gap).
# ---------------------------------------------------------------------------

def _simple_rnn_scan(x, Wx, Wh, b, h0, seq_lens, unroll):
    """x: (T,B,I) -> (ys (T,B,H), h_final). tanh cell, zero-padded past
    seq_lens (TF compat: outputs beyond length are zeros, state freezes)."""
    T, B = x.shape[0], x.shape[1]
    H = Wx.shape[1]
    Wx = Wx.astype(x.dtype)
    Wh = Wh.astype(x.dtype)
    bias = jnp.zeros((H,), x.dtype) if b is None else b.astype(x.dtype)
    h = jnp.zeros((B, H), x.dtype) if h0 is None else h0.astype(x.dtype)

    def step(h, xt, t):
        h_new = jnp.tanh(xt @ Wx + h @ Wh + bias)
        if seq_lens is not None:
            alive = (t < jnp.asarray(seq_lens))[:, None]
            h_new = jnp.where(alive, h_new, h)
            y = jnp.where(alive, h_new, jnp.zeros_like(h_new))
        else:
            y = h_new
        return h_new, y

    if unroll:
        ys = []
        for t in range(T):
            h, y = step(h, x[t], t)
            ys.append(y)
        return jnp.stack(ys), h
    h, ys = lax.scan(lambda c, tx: step(c, tx[1], tx[0]),
                     h, (jnp.arange(T), x))
    return ys, h


@op("static_rnn", "rnn", aliases=("staticRNN",))
def static_rnn(x, Wx, Wh, b=None, h0=None, seq_lens=None):
    """Unrolled simple-RNN over (T, B, I). Returns (h_seq, h_final)."""
    return _simple_rnn_scan(x, Wx, Wh, b, h0, seq_lens, unroll=True)


@op("dynamic_rnn", "rnn", aliases=("dynamicRNN",))
def dynamic_rnn(x, Wx, Wh, b=None, h0=None, seq_lens=None, time_major=True):
    """Scan-based simple-RNN; ``time_major=False`` takes (B, T, I)."""
    if not time_major:
        x = jnp.swapaxes(x, 0, 1)
    ys, h = _simple_rnn_scan(x, Wx, Wh, b, h0, seq_lens, unroll=False)
    if not time_major:
        ys = jnp.swapaxes(ys, 0, 1)
    return ys, h


def _bidir_rnn(x, fw, bw, seq_lens, unroll):
    ys_f, h_f = _simple_rnn_scan(x, *fw, seq_lens, unroll)
    if seq_lens is None:
        xr = x[::-1]
        ys_b, h_b = _simple_rnn_scan(xr, *bw, None, unroll)
        ys_b = ys_b[::-1]
    else:
        # reverse each sequence within its own length (TF reverse_sequence)
        T = x.shape[0]
        idx = jnp.arange(T)[:, None]                      # (T, 1)
        lens = jnp.asarray(seq_lens)[None, :]             # (1, B)
        rev = jnp.where(idx < lens, lens - 1 - idx, idx)  # (T, B)
        xr = jnp.take_along_axis(x, rev[:, :, None], axis=0)
        ys_b, h_b = _simple_rnn_scan(xr, *bw, seq_lens, unroll)
        ys_b = jnp.take_along_axis(ys_b, rev[:, :, None], axis=0)
    return jnp.concatenate([ys_f, ys_b], axis=-1), (h_f, h_b)


@op("static_bidirectional_rnn", "rnn", aliases=("staticBidirectionalRNN",))
def static_bidirectional_rnn(x, Wx_f, Wh_f, b_f, Wx_b, Wh_b, b_b,
                             h0_f=None, h0_b=None, seq_lens=None):
    """Bidirectional unrolled simple-RNN: (h_seq (T,B,2H), (h_fw, h_bw))."""
    return _bidir_rnn(x, (Wx_f, Wh_f, b_f, h0_f), (Wx_b, Wh_b, b_b, h0_b),
                      seq_lens, unroll=True)


@op("dynamic_bidirectional_rnn", "rnn", aliases=("dynamicBidirectionalRNN",))
def dynamic_bidirectional_rnn(x, Wx_f, Wh_f, b_f, Wx_b, Wh_b, b_b,
                              h0_f=None, h0_b=None, seq_lens=None,
                              time_major=True):
    if not time_major:
        x = jnp.swapaxes(x, 0, 1)
    ys, hs = _bidir_rnn(x, (Wx_f, Wh_f, b_f, h0_f), (Wx_b, Wh_b, b_b, h0_b),
                        seq_lens, unroll=False)
    if not time_major:
        ys = jnp.swapaxes(ys, 0, 1)
    return ys, hs


@op("sru_bi", "rnn", aliases=("sruBI",))
def sru_bi(x, W, b, c0=None, mask=None):
    """Bidirectional SRU (generic/recurrent/sru.cpp sru_bi, path-cite).
    x: (T, B, 2I) with the feature halves feeding the two directions;
    W: (2*3I, I)-per-direction stacked as (6I, I)... simplified faithful
    form: W (2, 3I, I), b (2, 2I), c0 (2, B, I). Returns
    (h (T, B, 2I), c_final (2, B, I))."""
    W = jnp.asarray(W)
    b = jnp.asarray(b)
    i = W.shape[-1]
    xf, xb = x[..., :i], x[..., i:]
    mask_t = None if mask is None else jnp.asarray(mask)
    c0f = None if c0 is None else c0[0]
    c0b = None if c0 is None else c0[1]
    hf, cf = sru(xf, W[0], b[0], c0f, mask_t, layout=0)
    hb_r, cb = sru(xb[::-1], W[1], b[1], c0b,
                   None if mask_t is None else mask_t[::-1], layout=0)
    return jnp.concatenate([hf, hb_r[::-1]], axis=-1), jnp.stack([cf, cb])
