"""Plain float32 reference of Olmo Hybrid (allenai/Olmo-Hybrid-7B,
``model_type: olmo_hybrid``): Gated DeltaNet layers (Yang et al.,
arXiv:2412.06464, as flash-linear-attention's ``GatedDeltaNet`` lays them
out; ``allow_neg_eigval``: Grazzi et al., arXiv:2411.12537) and softmax
layers with QK-norm in OLMo 2's reordered-norm block (arXiv:2501.00656).

Full forward over the whole sequence, ``jax.numpy`` at ``highest``: no cache,
no paging, no chunks, no kernels, one row at a time. It imports nothing of
``deeplearning4j_tpu`` and makes its own weights from the seed.

Layout: token embedding ``E``; the first ``num_hidden_layers`` entries of
``layer_types``, each a block ``h = x + n1(mixer(x)); out = h +
n2(W_down(silu(h W_gate) * h W_up))`` with ``n`` an RMSNorm of its own
(``rms_norm_eps``); final RMSNorm; logits ``n(h) W_head`` (untied). No
positions anywhere.

- ``linear_attention`` (Gated DeltaNet), a head of ``linear_key_head_dim``
  keys and ``linear_value_head_dim`` values: ``q, k, v = silu(conv(x
  W_q)), silu(conv(x W_k)), silu(conv(x W_v))``, each convolution causal,
  depthwise, ``linear_conv_kernel_dim`` taps, no bias; ``q <- q / |q|
  dk^-1/2``, ``k <- k / |k|`` (the norm as ``sqrt(sum x^2 + 1e-6)``);
  ``beta = 2 sigmoid(x W_b)``; ``g = -exp(A_log) softplus(x W_a +
  dt_bias)``, one a
  head; a ``lax.scan`` step a token over ``S = exp(g) (I - beta k k^T) S +
  beta k v^T`` with ``S`` (dk, dv) float32 a head, ``o = S^T q``; ``out =
  W_o concat_h(n_dv(o_h) * silu(x W_g)_h)``.
- ``full_attention``: ``q = n(x W_q)``, ``k = n(x W_k)`` over the whole
  projections, ``v = x W_v``; heads of ``hidden_size / num_attention_heads``
  over ``num_key_value_heads``; a full causal softmax of ``q k^T /
  sqrt(head_dim)``; ``W_o``.

Departures, all under ``assumed`` in the configuration's file: no rotary
positions (``rope_theta`` is ``null``), the GatedDeltaNet layout and the
block by family convention, the weights as :func:`make_weights` draws them.

With it the model's own counts for the benchmark's readers:
:func:`request_flops`, :func:`prefill_flops`, :func:`decode_step_bytes`,
and the two delta-rule kernels' least work, :func:`gdn_step_bytes` and
:func:`gdn_chunk_work`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

INIT_STD = 0.02
F32 = jnp.float32
SIZE = {"bfloat16": 2, "float32": 4}
CHUNK = 64      # positions of one chunk of the delta rule's prefill


# ------------------------------------------------------------------- shapes
def _dims(cfg: dict) -> dict:
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    layers = cfg["num_hidden_layers"]
    assert cfg["linear_num_key_heads"] == cfg["linear_num_value_heads"]
    return dict(
        h=h, layers=layers, types=tuple(cfg["layer_types"][:layers]),
        heads=heads, kv=cfg["num_key_value_heads"], dh=h // heads,
        lin=cfg["linear_num_key_heads"], dk=cfg["linear_key_head_dim"],
        dv=cfg["linear_value_head_dim"], conv=cfg["linear_conv_kernel_dim"],
        neg=bool(cfg["linear_allow_neg_eigval"]),
        f=cfg["intermediate_size"], eps=cfg["rms_norm_eps"],
        vocab=cfg["vocab_size"])


def _is_linear(d: dict, i: int) -> bool:
    return d["types"][i] == "linear_attention"


def _layer_shapes(d: dict, i: int) -> dict:
    """Leaf name -> shape of layer ``i`` (from 0)."""
    h, f = d["h"], d["f"]
    s = {"norm1": (h,), "norm2": (h,), "Wgate": (h, f), "Wup": (h, f),
         "Wdown": (f, h)}
    if _is_linear(d, i):
        n, keys, vals = d["lin"], d["lin"] * d["dk"], d["lin"] * d["dv"]
        s.update(Wq=(h, keys), Wk=(h, keys), Wv=(h, vals),
                 conv_q=(d["conv"], keys), conv_k=(d["conv"], keys),
                 conv_v=(d["conv"], vals), Wa=(h, n), Wb=(h, n), A_log=(n,),
                 dt_bias=(n,), Wg=(h, vals), o_norm=(d["dv"],),
                 Wo=(vals, h))
    else:
        inner, kv = d["heads"] * d["dh"], d["kv"] * d["dh"]
        s.update(Wq=(h, inner), Wk=(h, kv), Wv=(h, kv), Wo=(inner, h),
                 q_norm=(inner,), k_norm=(kv,))
    return s


#: leaves of two axes that no token is multiplied through
_NOT_MATRICES = ("conv_q", "conv_k", "conv_v")


def make_weights(seed: int, cfg: dict):
    """``{"emb": {"word"}, "layers": [...], "head": {"norm", "W"},
    "dims"}``, leaves of ``param_dtype`` (``dims``: the configuration's
    sizes as a hashable tuple, for :func:`logits_at`). Matrices N(0, 0.02);
    norm scales 1 + N(0, 0.02); the convolutions N(0, 1/taps); ``A_log``
    the log of a rate drawn uniform in (1, 16) a head; ``dt_bias`` the
    inverse softplus of a step drawn log-uniform in (0.001, 0.1) a head.
    One jitted call a layer, on the device."""
    d = _dims(cfg)
    dt = jnp.dtype(cfg.get("param_dtype", "bfloat16"))

    def leaf(key, name, shape):
        n = lambda std, mean=0.0: (mean + std * jax.random.normal(
            key, shape, F32)).astype(dt)
        if "norm" in name:
            return n(INIT_STD, 1.0)
        if name.startswith("conv_"):
            return n(shape[0] ** -0.5)
        if name == "A_log":
            return jnp.log(jax.random.uniform(key, shape, F32, 1.0,
                                              16.0)).astype(dt)
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
            "head": build(ks[-1], items({"norm": (d["h"],),
                                         "W": (d["h"], d["vocab"])}))}


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


def _gated_deltanet(p, d, x, mm):
    """One row: the block's input (T, H) -> mixer output (T, H)."""
    t = x.shape[0]
    n, dk, dv, kk = d["lin"], d["dk"], d["dv"], d["conv"]

    def conv(a, w):         # causal, depthwise, zeros before the first
        ap = jnp.concatenate([jnp.zeros((kk - 1, a.shape[1]), F32), a])
        w = w.astype(F32)
        return sum(ap[j:j + t] * w[j] for j in range(kk))

    unit = lambda a: a / jnp.sqrt(jnp.sum(jnp.square(a), -1, keepdims=True)
                                  + 1e-6)
    q = jax.nn.silu(conv(mm(x, p["Wq"]), p["conv_q"])).reshape(t, n, dk)
    k = jax.nn.silu(conv(mm(x, p["Wk"]), p["conv_k"])).reshape(t, n, dk)
    v = jax.nn.silu(conv(mm(x, p["Wv"]), p["conv_v"])).reshape(t, n, dv)
    q, k = unit(q) * dk ** -0.5, unit(k)
    beta = jax.nn.sigmoid(mm(x, p["Wb"])) * (2.0 if d["neg"] else 1.0)
    g = -jnp.exp(p["A_log"].astype(F32)) * jax.nn.softplus(
        mm(x, p["Wa"]) + p["dt_bias"].astype(F32))            # (T, heads)

    def step(s, inp):
        q_t, k_t, v_t, b_t, g_t = inp
        ks = jnp.einsum("hk,hkv->hv", k_t, s, precision="highest")
        s = jnp.exp(g_t)[:, None, None] * (
            s - b_t[:, None, None] * k_t[:, :, None] * ks[:, None, :]) \
            + b_t[:, None, None] * k_t[:, :, None] * v_t[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision="highest")

    _, o = lax.scan(step, jnp.zeros((n, dk, dv), F32), (q, k, v, beta, g))
    gate = jax.nn.silu(mm(x, p["Wg"])).reshape(t, n, dv)
    o = _rms(o, p["o_norm"], d["eps"]) * gate
    return mm(o.reshape(t, n * dv), p["Wo"])


def _attention(p, d, x, mm):
    t = x.shape[0]
    nh, kv, dh = d["heads"], d["kv"], d["dh"]
    q = _rms(mm(x, p["Wq"]), p["q_norm"], d["eps"]).reshape(t, nh, dh)
    k = _rms(mm(x, p["Wk"]), p["k_norm"], d["eps"]).reshape(t, kv, dh)
    v = mm(x, p["Wv"]).reshape(t, kv, dh)
    # every key/value head serves its group of query heads
    k, v = (jnp.repeat(a, nh // kv, axis=1) for a in (k, v))
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
        mixer = _gated_deltanet if "Wa" in p else _attention
        h = x + _rms(mixer(p, d, x, mm), p["norm1"], d["eps"])
        y = mm(jax.nn.silu(mm(h, p["Wgate"])) * mm(h, p["Wup"]), p["Wdown"])
        return h + _rms(y, p["norm2"], d["eps"])

    return lax.map(row, x)


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _head(norm, w, xs, eps, dtype):
    return jnp.matmul(_lower(_rms(xs, norm, eps), dtype), _lower(w, dtype),
                      precision="highest")


def logits_at(w, tokens, positions, n_heads: int = 0, dtype=None):
    """Next-token logits (B, P, V) float32 at ``positions`` (B, P) of
    ``tokens`` (B, T). ``dtype``: every matrix product's operands rounded to
    that type (``float8_e4m3fn`` is the control); the recurrence, its
    state, norms and softmax stay float32. ``n_heads`` is what the harness
    passes for every model; the sizes are read from ``dims``, kept on the
    weights by :func:`make_weights`."""
    dims = w["dims"]
    dtype = None if dtype is None else jnp.dtype(dtype)
    x = w["emb"]["word"].astype(F32)[tokens]
    for p in w["layers"]:
        x = _layer(p, x, dims, dtype)
    xs = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    return _head(w["head"]["norm"], w["head"]["W"], xs, dict(dims)["eps"],
                 dtype)


# ------------------------------------------------------- the model's counts
def _matmul_params(d: dict, i: int) -> int:
    """Weights of layer ``i`` that a token is multiplied through."""
    return sum(s[0] * s[1] for name, s in _layer_shapes(d, i).items()
               if len(s) == 2 and name not in _NOT_MATRICES)


def _linear_layers(d: dict) -> int:
    return sum(_is_linear(d, i) for i in range(d["layers"]))


def gdn_layers(cfg: dict) -> int:
    """How many layers hold the Gated DeltaNet: one chunked prefill call a
    prefill and one decode-step call a decode step each."""
    return _linear_layers(_dims(cfg))


def _product_flops(d: dict) -> int:
    """One token through every matrix of the layers (a multiply-add counts
    2), the head apart."""
    return 2 * sum(_matmul_params(d, i) for i in range(d["layers"]))


def _attn_flops(d: dict, pairs: int) -> int:
    """``pairs`` (query, key) pairs in every full layer: a score and a
    weighted value of ``head_dim`` numbers a head."""
    return (d["layers"] - _linear_layers(d)) * 4 * d["heads"] * d["dh"] \
        * pairs


def _recurrence_flops(d: dict) -> int:
    """The delta rule's work a token, over the linear layers: ``k^T S``,
    the rank-one correction and its scale, the decay, the write ``beta k
    v^T`` and ``S^T q`` (9 operations a number of a head's state), and
    the three convolutions (2 a tap a channel)."""
    per = 9 * d["lin"] * d["dk"] * d["dv"] \
        + 2 * d["conv"] * d["lin"] * (2 * d["dk"] + d["dv"])
    return _linear_layers(d) * per


def request_flops(cfg: dict, prompt: int, new: int) -> float:
    """Operations of one request: ``prompt + new - 1`` tokens pass through
    the layers (products, the recurrence), a full layer's token i attends
    i + 1 keys, and the head runs once a served token."""
    d = _dims(cfg)
    n = prompt + new - 1
    return (n * (_product_flops(d) + _recurrence_flops(d))
            + _attn_flops(d, n * (n + 1) // 2)
            + new * 2 * d["h"] * d["vocab"])


def _chunk_flops(d: dict) -> int:
    """Matrix-unit operations of one chunk of one head in the WY form:
    ``K K^T`` and ``Q K^T`` below the diagonal (C^2 dk each), the unit
    lower-triangular solve against ``[K~ | V]`` (C^2 (dk + dv)), ``W S``,
    ``Q S`` and ``K^T U`` (2 C dk dv each), ``(Q K^T) U`` (C^2 dv)."""
    c, dk, dv = CHUNK, d["dk"], d["dv"]
    return 2 * c * c * dk + c * c * (dk + dv) + 6 * c * dk * dv \
        + c * c * dv


def prefill_flops(cfg: dict, rows: int, seq: int) -> float:
    """Matrix-unit operations of one launched prefill of ``rows`` x ``seq``
    DECLARED positions: every position through every matrix, the delta
    rule's chunks in every linear layer, causal attention over seq (seq +
    1) / 2 pairs a row in every full layer, the head once a row."""
    d = _dims(cfg)
    chunks = -(-seq // CHUNK)
    return (rows * seq * _product_flops(d)
            + rows * chunks * _linear_layers(d) * d["lin"] * _chunk_flops(d)
            + rows * _attn_flops(d, seq * (seq + 1) // 2)
            + rows * 2 * d["h"] * d["vocab"])


def _state_bytes(cfg: dict, d: dict) -> int:
    """One stream's state in one linear layer: every head's (dk, dv) and
    the three convolutions' tail."""
    return SIZE[cfg.get("state_dtype", "float32")] * (
        d["lin"] * d["dk"] * d["dv"]
        + (d["conv"] - 1) * d["lin"] * (2 * d["dk"] + d["dv"]))


def decode_step_bytes(cfg: dict, rows: float, live_tokens: float) -> float:
    """The least one decode step of ``rows`` live streams must move through
    HBM: every matrix once (the head's among them), each linear layer's
    state and convolution tail read and written a live row, and the
    key/value rows of the ``live_tokens`` the streams hold, in each full
    layer."""
    d = _dims(cfg)
    fixed = d["h"] * d["vocab"] + sum(_matmul_params(d, i)
                                      for i in range(d["layers"]))
    kv_row = SIZE[cfg.get("kv_dtype", "bfloat16")] * 2 * d["kv"] * d["dh"]
    return (fixed * SIZE[cfg.get("param_dtype", "bfloat16")]
            + rows * _linear_layers(d) * 2 * _state_bytes(cfg, d)
            + live_tokens * (d["layers"] - _linear_layers(d)) * kv_row)


def gdn_step_bytes(cfg: dict, rows: float) -> float:
    """The least ONE linear layer's decode kernel must move for ``rows``
    LIVE rows: each row's state read and written once, its q and k, decay
    and beta in, its v in and o out."""
    d = _dims(cfg)
    size = SIZE[cfg.get("state_dtype", "float32")]
    n, dk, dv = d["lin"], d["dk"], d["dv"]
    return size * rows * n * (2 * dk * dv + 2 * dk + 2 + 2 * dv)


def gdn_chunk_work(cfg: dict, rows: float, chunks: float):
    """(operations, bytes) the least ONE linear layer's chunked prefill
    kernel must do for ``chunks`` live chunks of ``CHUNK`` positions in
    ``rows`` rows: every head's chunk products (:func:`_chunk_flops`); q,
    k, v, the decay and beta in and o out, once a position of a live
    chunk; the final state out once a row. Float32 throughout, as the
    configuration states the recurrence."""
    d = _dims(cfg)
    size = SIZE[cfg.get("state_dtype", "float32")]
    n, dk, dv = d["lin"], d["dk"], d["dv"]
    flops = chunks * n * _chunk_flops(d)
    moved = size * (chunks * CHUNK * n * (2 * dk + 2 * dv + 2)
                    + rows * n * dk * dv)
    return flops, moved
