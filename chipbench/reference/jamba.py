"""Plain float32 reference of Jamba (ai21labs/AI21-Jamba2-3B, ``model_type:
jamba``; Jamba report, arXiv:2403.19887; Mamba, arXiv:2312.00752, section 3).

Full forward over the whole sequence, ``jax.numpy`` at ``highest``: no cache,
no paging, no chunked scan, no kernels, one row at a time. It imports nothing
of ``deeplearning4j_tpu`` and makes its own weights from the seed.

Layout: token embedding ``E``; ``num_hidden_layers`` pre-norm blocks ``h +=
mixer(RMSNorm(h)); h += W_down(silu(W_gate n) * W_up n), n = RMSNorm(h)``;
final RMSNorm; logits ``n E^T`` (``tie_word_embeddings``). No positions
anywhere. Layer ``i``, from 0, is attention where ``i % attn_layer_period ==
attn_layer_offset`` and a Mamba layer elsewhere.

- Mamba mixer: ``[x | z] = n W_in``; ``x = silu(conv(x) + b_conv)``
  (depthwise causal, ``mamba_d_conv`` taps); ``[dt | B | C] = x W_x``;
  Jamba's step: each RMS-normed with a scale of its own; ``delta =
  softplus(dt W_dt + b_dt)``; ``A = -exp(A_log)`` (channels, states); a
  ``lax.scan`` step a token over ``s = exp(delta A) s + delta B x``, ``y = s
  C + D x``; ``out = (y silu(z)) W_out``. The state here is (channels,
  states), as the papers write it.
- Attention mixer: ``num_attention_heads`` query heads of ``head_dim`` over
  ``num_key_value_heads`` key/value heads, each broadcast to its group; a
  full causal softmax of ``q k^T / sqrt(head_dim)``; no rotation; ``W_o``.

Departures, all under ``assumed`` in the configuration's file: ``head_dim``
(hidden / heads: the config gives none), the layer order as read from the
``attn_layer_*`` keys, weights N(0, 0.02) with the Mamba leaves as
:func:`make_weights` says.

With it the model's own counts for the benchmark's readers:
:func:`request_flops`, :func:`prefill_flops`, :func:`decode_step_bytes`,
and the two kernels' least bytes, :func:`ssm_scan_bytes` and
:func:`ssm_step_bytes`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

INIT_STD = 0.02
F32 = jnp.float32
SIZE = {"bfloat16": 2, "float32": 4}


# ------------------------------------------------------------------- shapes
def _dims(cfg: dict) -> dict:
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(
        h=h, layers=cfg["num_hidden_layers"], heads=heads,
        kv=cfg["num_key_value_heads"], dh=cfg.get("head_dim") or h // heads,
        period=cfg["attn_layer_period"], offset=cfg["attn_layer_offset"],
        f=cfg["intermediate_size"], ch=cfg["mamba_expand"] * h,
        n=cfg["mamba_d_state"], conv=cfg["mamba_d_conv"],
        rank=cfg["mamba_dt_rank"], conv_bias=bool(cfg["mamba_conv_bias"]),
        eps=cfg["rms_norm_eps"], vocab=cfg["vocab_size"])


def _is_attention(d: dict, i: int) -> bool:
    return i % d["period"] == d["offset"]


def _layer_shapes(d: dict, i: int) -> dict:
    """Leaf name -> shape of layer ``i`` (from 0)."""
    h, f = d["h"], d["f"]
    s = {"norm1": (h,), "norm2": (h,), "Wgate": (h, f), "Wup": (h, f),
         "Wdown": (f, h)}
    if _is_attention(d, i):
        inner, kv = d["heads"] * d["dh"], d["kv"] * d["dh"]
        s.update(Wq=(h, inner), Wk=(h, kv), Wv=(h, kv), Wo=(inner, h))
    else:
        ch, n, rk = d["ch"], d["n"], d["rank"]
        s.update(Win=(h, 2 * ch), conv_x=(d["conv"], ch),
                 Wx=(ch, rk + 2 * n), dt_norm=(rk,), B_norm=(n,),
                 C_norm=(n,), Wdt=(rk, ch), dt_bias=(ch,), A_log=(ch, n),
                 D=(ch,), Wout=(ch, h))
        if d["conv_bias"]:
            s["conv_bias"] = (ch,)
    return s


#: leaves of two axes that no token is multiplied through
_NOT_MATRICES = ("conv_x", "A_log")


def make_weights(seed: int, cfg: dict):
    """``{"emb": {"word"}, "layers": [...], "head": {"norm"}, "dims"}``,
    leaves of ``param_dtype`` (``dims``: the configuration's sizes as a
    hashable tuple, for :func:`logits_at`); the head has no matrix of its
    own. Matrices N(0, 0.02); norm scales 1 + N(0, 0.02); the convolution
    N(0, 1/2) and its bias N(0, 0.02); ``A_log[c, n] = log(n + 1)`` (Mamba's
    S4D-real start); ``D = 1``; ``dt_bias`` the inverse softplus of a step
    drawn log-uniform in (0.001, 0.1). One jitted call a layer, on the
    device."""
    d = _dims(cfg)
    dt = jnp.dtype(cfg.get("param_dtype", "bfloat16"))

    def leaf(key, name, shape):
        n = lambda std, mean=0.0: (mean + std * jax.random.normal(
            key, shape, F32)).astype(dt)
        if "norm" in name:
            return n(INIT_STD, 1.0)
        if name == "conv_x":
            return n(shape[0] ** -0.5)
        if name == "A_log":
            return jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[1] + 1, dtype=F32)), shape).astype(dt)
        if name == "D":
            return jnp.ones(shape, dt)
        if name == "dt_bias":
            step = jnp.exp(jax.random.uniform(key, shape, F32,
                                              jnp.log(1e-3), jnp.log(1e-1)))
            return (step + jnp.log(-jnp.expm1(-step))).astype(dt)
        return n(INIT_STD)

    @functools.partial(jax.jit, static_argnums=1)
    def build(key, shapes):
        keys = jax.random.split(key, len(shapes))
        return {name: leaf(k, name, shape)
                for k, (name, shape) in zip(keys, shapes)}

    ks = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)),
                          d["layers"] + 2)
    items = lambda s: tuple(sorted(s.items()))
    return {"dims": items(d),
            "emb": build(ks[0], items({"word": (d["vocab"], d["h"])})),
            "layers": [build(ks[i + 1], items(_layer_shapes(d, i)))
                       for i in range(d["layers"])],
            "head": build(ks[-1], items({"norm": (d["h"],)}))}


# ------------------------------------------------------------------ forward
def _lower(x, dtype):
    """``x`` as a matrix unit of ``dtype`` is fed it: float32 as it is; an
    8-bit float rounded about a per-tensor scale and back (the control);
    any other type rounded to it and back."""
    if dtype is None or dtype == F32:
        return x.astype(F32)
    x = x.astype(F32)
    if jnp.dtype(dtype).itemsize == 1:
        top = float(jnp.finfo(dtype).max)
        s = jnp.max(jnp.abs(x)) / top + 1e-30
        return (x / s).astype(dtype).astype(F32) * s
    return x.astype(dtype).astype(F32)


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * w.astype(F32)


def _mamba(p, d, h, mm):
    """One row: normed input (T, H) -> mixer output (T, H)."""
    t = h.shape[0]
    ch, n, rk, kk = d["ch"], d["n"], d["rank"], d["conv"]
    xz = mm(h, p["Win"])
    x, z = xz[:, :ch], xz[:, ch:]
    xp = jnp.concatenate([jnp.zeros((kk - 1, ch), F32), x])
    w = p["conv_x"].astype(F32)
    x = sum(xp[j:j + t] * w[j] for j in range(kk))
    if "conv_bias" in p:
        x = x + p["conv_bias"].astype(F32)
    x = jax.nn.silu(x)
    low = mm(x, p["Wx"])
    dt = _rms(low[:, :rk], p["dt_norm"], d["eps"])
    b = _rms(low[:, rk:rk + n], p["B_norm"], d["eps"])
    c = _rms(low[:, rk + n:], p["C_norm"], d["eps"])
    delta = jax.nn.softplus(mm(dt, p["Wdt"]) + p["dt_bias"].astype(F32))
    a = -jnp.exp(p["A_log"].astype(F32))                     # (ch, n)

    def step(s, inp):
        x_t, d_t, b_t, c_t = inp
        s = jnp.exp(d_t[:, None] * a) * s \
            + (d_t * x_t)[:, None] * b_t[None, :]
        return s, jnp.sum(s * c_t[None, :], axis=1)

    _, y = lax.scan(step, jnp.zeros((ch, n), F32), (x, delta, b, c))
    y = y + p["D"].astype(F32) * x
    return mm(y * jax.nn.silu(z), p["Wout"])


def _attention(p, d, h, mm):
    t = h.shape[0]
    nh, kv, dh = d["heads"], d["kv"], d["dh"]
    q = mm(h, p["Wq"]).reshape(t, nh, dh)
    # every key/value head serves its group of query heads
    k = jnp.repeat(mm(h, p["Wk"]).reshape(t, kv, dh), nh // kv, axis=1)
    v = jnp.repeat(mm(h, p["Wv"]).reshape(t, kv, dh), nh // kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") / dh ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                   precision="highest")
    return mm(o.reshape(t, nh * dh), p["Wo"])


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _layer(p, x, dims, dtype):
    """One block over rows (N, T, H), a row at a time."""
    d = dict(dims)
    mm = lambda a, b: jnp.matmul(_lower(a, dtype), _lower(b, dtype),
                                 precision="highest")

    def row(x):
        h = _rms(x, p["norm1"], d["eps"])
        x = x + (_attention if "Wq" in p else _mamba)(p, d, h, mm)
        h = _rms(x, p["norm2"], d["eps"])
        return x + mm(jax.nn.silu(mm(h, p["Wgate"])) * mm(h, p["Wup"]),
                      p["Wdown"])

    return lax.map(row, x)


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _head(norm, emb, xs, eps, dtype):
    """``n E^T``: the embedding is the head."""
    return jnp.matmul(_lower(_rms(xs, norm, eps), dtype),
                      _lower(emb, dtype).T, precision="highest")


def logits_at(w, tokens, positions, n_heads: int = 0, dtype=None):
    """Next-token logits (B, P, V) float32 at ``positions`` (B, P) of
    ``tokens`` (B, T). ``dtype``: every matrix product's operands rounded to
    that type (``float8_e4m3fn`` is the control); scan, state, norms and
    softmax stay float32. ``n_heads`` is what the harness passes for every
    model; the sizes are read from ``dims``, kept on the weights by
    :func:`make_weights`."""
    dims = w["dims"]
    dtype = None if dtype is None else jnp.dtype(dtype)
    x = w["emb"]["word"].astype(F32)[tokens]
    for p in w["layers"]:
        x = _layer(p, x, dims, dtype)
    xs = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    return _head(w["head"]["norm"], w["emb"]["word"], xs, dict(dims)["eps"],
                 dtype)


# ------------------------------------------------------- the model's counts
def _matmul_params(d: dict, i: int) -> int:
    """Weights of layer ``i`` that a token is multiplied through."""
    return sum(s[0] * s[1] for name, s in _layer_shapes(d, i).items()
               if len(s) == 2 and name not in _NOT_MATRICES)


def _mamba_layers(d: dict) -> int:
    return sum(not _is_attention(d, i) for i in range(d["layers"]))


def mamba_layers(cfg: dict) -> int:
    """How many layers hold the state-space mixer: one ``ssm_scan`` call a
    prefill and one ``ssm_step`` call a decode step each."""
    return _mamba_layers(_dims(cfg))


def _product_flops(d: dict) -> int:
    """One token through every matrix of the layers (a multiply-add counts
    2), the head apart."""
    return 2 * sum(_matmul_params(d, i) for i in range(d["layers"]))


def _attn_flops(d: dict, pairs: int) -> int:
    """``pairs`` (query, key) pairs in every attention layer: a score and a
    weighted value of ``head_dim`` numbers a head."""
    return (d["layers"] - _mamba_layers(d)) * 4 * d["heads"] * d["dh"] * pairs


def request_flops(cfg: dict, prompt: int, new: int) -> float:
    """Operations of one request: ``prompt + new - 1`` tokens pass through
    the layers, the head runs once a served token. A Mamba layer adds, a
    token, 7 operations a state of a channel (``delta A``, its exponential,
    the decay, ``delta x B`` and its add, ``s C`` and its add) and its
    convolution; an attention layer's token i attends i + 1 keys."""
    d = _dims(cfg)
    n = prompt + new - 1
    scan = _mamba_layers(d) * d["ch"] * (7 * d["n"] + 2 * d["conv"])
    return (n * (_product_flops(d) + scan)
            + _attn_flops(d, n * (n + 1) // 2)
            + new * 2 * d["h"] * d["vocab"])


def prefill_flops(cfg: dict, rows: int, seq: int) -> float:
    """Matrix-unit operations of one launched prefill of ``rows`` x ``seq``
    DECLARED positions: every position through every matrix, causal
    attention over seq (seq + 1) / 2 pairs a row, the head once a row. The
    scan's work is the vector unit's and is left out: this is read against
    the bf16 peak of the matrix unit (``chipbench/peaks.py`` has no other)."""
    d = _dims(cfg)
    return (rows * seq * _product_flops(d)
            + rows * _attn_flops(d, seq * (seq + 1) // 2)
            + rows * 2 * d["h"] * d["vocab"])


def _state_bytes(cfg: dict, d: dict) -> int:
    """One stream's state in one Mamba layer: the scan's and the
    convolution's tail."""
    return SIZE[cfg.get("state_dtype", "float32")] * (
        d["ch"] * d["n"] + (d["conv"] - 1) * d["ch"])


def decode_step_bytes(cfg: dict, rows: float, live_tokens: float) -> float:
    """The least one decode step of ``rows`` live streams must move through
    HBM: every matrix once, the embedding once as the head, each Mamba
    layer's state and convolution tail read and written a live row, and the
    key/value rows of the ``live_tokens`` the streams hold, in each
    attention layer."""
    d = _dims(cfg)
    fixed = d["h"] * d["vocab"] + sum(_matmul_params(d, i)
                                      for i in range(d["layers"]))
    kv_row = SIZE[cfg.get("kv_dtype", "bfloat16")] * 2 * d["kv"] * d["dh"]
    return (fixed * SIZE[cfg.get("param_dtype", "bfloat16")]
            + rows * _mamba_layers(d) * 2 * _state_bytes(cfg, d)
            + live_tokens * (d["layers"] - _mamba_layers(d)) * kv_row)


def ssm_scan_bytes(cfg: dict, rows: float, positions: float) -> float:
    """The least ONE layer's prefill scan must move for ``positions`` live
    positions in ``rows`` rows: x, delta and z in and y out (channels each),
    B and C in (states each), once a position; the states in and out, once a
    row. Float32 throughout, as the configuration states the scan."""
    d = _dims(cfg)
    size = SIZE[cfg.get("state_dtype", "float32")]
    return size * (positions * (4 * d["ch"] + 2 * d["n"])
                   + rows * 2 * d["ch"] * d["n"])


def ssm_step_bytes(cfg: dict, rows: float) -> float:
    """The least ONE layer's decode step must move for ``rows`` LIVE rows:
    each row's state read and written once, its x, delta, z, B and C in and
    its y out."""
    d = _dims(cfg)
    size = SIZE[cfg.get("state_dtype", "float32")]
    return size * rows * (2 * d["ch"] * d["n"] + 4 * d["ch"] + 2 * d["n"])
