"""Plain float32 reference of one chip's share of Kimi Linear
(moonshotai/Kimi-Linear-48B-A3B-Instruct; Kimi Linear report,
arXiv:2510.26692; flash-linear-attention ``KimiDeltaAttention``).

Full forward over the whole sequence, ``jax.numpy`` at ``highest``: no cache,
no paging, no chunking, no grouped products, one row at a time. It imports
nothing of ``deeplearning4j_tpu`` and makes its own weights from the seed.

Layout: token embedding; ``num_hidden_layers`` pre-norm blocks ``x +=
mixer(RMSNorm(x)); x += ffn(RMSNorm(x))``; final RMSNorm; untied head.

- KDA mixer (layers of ``linear_attn_config.kda_layers``): ``q, k, v =
  SiLU(conv(x W))`` (depthwise causal, kernel 4), ``q`` and ``k``
  L2-normalised per head, ``q`` scaled by d^-0.5; per-channel log decay
  ``g = -exp(A_log) softplus(W_f2 W_f1 x + dt_bias)``, ``beta = sigmoid(x
  W_b)``; per head ``S = (I - beta k k^T) Diag(exp g) S + beta k v^T``, ``o
  = S^T q``, a ``lax.scan`` step a token; ``y = W_o [RMSNorm_head(o) *
  sigmoid(W_g2 W_g1 x)]``.
- MLA mixer without positions (``full_attn_layers``): ``[c | kr] = x W_dkv``,
  ``c = RMSNorm(c)``, ``[kc_h | v_h] = c W_ukv``, ``q_h = x W_q``; scores
  ``(qc.kc + qr.kr) / sqrt(192)``, causal softmax, expanded form.
- Feed-forward: layer 1 dense gated SiLU; after it ``s = sigmoid(x W_r)``
  over ALL ``published_num_experts``, the ``num_experts_per_token`` largest
  of ``s + bias``, weights ``s_i / sum s * routed_scaling_factor``, the
  shared expert, and of the chosen experts only the ``num_experts`` HELD
  HERE (from ``expert_offset``), each applied in a loop to every token and
  weighted by 0 where it was not chosen. What the absent experts would add
  is left out: this is one chip's share under 16-way expert parallelism.

Departures, all under ``assumed`` in the configuration's file: the low-rank
gate widths (the head size), ``A_log`` a head and ``dt_bias`` a channel, no
bias on the output gate, weights N(0, 0.02).

With it the model's own counts for the benchmark's readers:
:func:`request_flops` and :func:`decode_step_bytes`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

INIT_STD = 0.02
F32 = jnp.float32


# ------------------------------------------------------------------- shapes
def _dims(cfg: dict) -> dict:
    lin = cfg["linear_attn_config"]
    return dict(
        h=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
        kda_heads=lin["num_heads"], kda_d=lin["head_dim"],
        conv=lin["short_conv_kernel_size"],
        rank=cfg.get("gate_low_rank", lin["head_dim"]),
        mla=set(lin["full_attn_layers"]), heads=cfg["num_attention_heads"],
        r=cfg["kv_lora_rank"], dn=cfg["qk_nope_head_dim"],
        dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        dense=cfg["first_k_dense_replace"], f=cfg["intermediate_size"],
        fe=cfg["moe_intermediate_size"], held=cfg["num_experts"],
        routed=cfg.get("published_num_experts", cfg["num_experts"]),
        offset=cfg.get("expert_offset", 0), k=cfg["num_experts_per_token"],
        shared=cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
        scale=cfg["routed_scaling_factor"], eps=cfg["rms_norm_eps"],
        vocab=cfg["vocab_size"])


def _layer_shapes(d: dict, i: int) -> dict:
    """Leaf name -> shape of layer ``i`` (from 1, as the published lists)."""
    h = d["h"]
    s = {"norm1": (h,), "norm2": (h,)}
    if i in d["mla"]:
        nh = d["heads"]
        s.update(Wdkv=(h, d["r"] + d["dr"]), kv_norm=(d["r"],),
                 Wukv=(d["r"], nh * (d["dn"] + d["dv"])),
                 Wq=(h, nh * (d["dn"] + d["dr"])), Wo=(nh * d["dv"], h))
    else:
        inner = d["kda_heads"] * d["kda_d"]
        s.update(Wq=(h, inner), Wk=(h, inner), Wv=(h, inner),
                 conv_q=(d["conv"], inner), conv_k=(d["conv"], inner),
                 conv_v=(d["conv"], inner), Wf1=(h, d["rank"]),
                 Wf2=(d["rank"], inner), A_log=(d["kda_heads"],),
                 dt_bias=(inner,), Wb=(h, d["kda_heads"]),
                 Wg1=(h, d["rank"]), Wg2=(d["rank"], inner),
                 o_norm=(d["kda_d"],), Wo=(inner, h))
    if i <= d["dense"]:
        s.update(Wgate=(h, d["f"]), Wup=(h, d["f"]), Wdown=(d["f"], h))
    else:
        e, fe = d["held"], d["fe"]
        s.update(router=(h, d["routed"]), router_bias=(d["routed"],),
                 Egate=(e, h, fe), Eup=(e, h, fe), Edown=(e, fe, h))
        if d["shared"]:
            s.update(Sgate=(h, d["shared"]), Sup=(h, d["shared"]),
                     Sdown=(d["shared"], h))
    return s


def make_weights(seed: int, cfg: dict):
    """``{"emb": {"word"}, "layers": [...], "head": {"norm", "W"}, "dims"}``,
    bfloat16 leaves, this chip's share only (``dims``: the configuration's
    sizes as a hashable tuple, for :func:`logits_at`). Matrices N(0, 0.02); norm
    scales 1 + N(0, 0.02); the convolutions N(0, 1/2); the selection bias
    N(0, 0.05); ``A_log = log U(1, 16)`` a head; ``dt_bias`` the inverse
    softplus of a step drawn log-uniform in (0.001, 0.1): nothing is left at
    a value that would hide a term the program dropped. One jitted call a
    layer, on the device."""
    d = _dims(cfg)
    dt = jnp.dtype(cfg.get("param_dtype", "bfloat16"))

    def leaf(key, name, shape):
        n = lambda std, mean=0.0: (mean + std * jax.random.normal(
            key, shape, F32)).astype(dt)
        if "norm" in name:
            return n(INIT_STD, 1.0)
        if name.startswith("conv_"):
            return n(shape[0] ** -0.5)
        if name == "router_bias":
            return n(0.05)
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
    return {"dims": items({k: v for k, v in d.items() if k != "mla"}),
            "emb": build(ks[0], items({"word": (d["vocab"], d["h"])})),
            "layers": [build(ks[i], items(_layer_shapes(d, i)))
                       for i in range(1, d["layers"] + 1)],
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


def _kda(p, d, h, mm):
    """One row: normed input (T, H) -> mixer output (T, H)."""
    t = h.shape[0]
    nh, dk, kk = d["kda_heads"], d["kda_d"], d["conv"]

    def conv(name):
        x = mm(h, p["W" + name])
        xp = jnp.concatenate([jnp.zeros((kk - 1, x.shape[1]), F32), x])
        w = p["conv_" + name].astype(F32)
        y = sum(xp[j:j + t] * w[j] for j in range(kk))
        return jax.nn.silu(y).reshape(t, nh, dk)

    unit = lambda a: a * lax.rsqrt(jnp.sum(jnp.square(a), -1, keepdims=True)
                                   + 1e-6)
    q, k, v = unit(conv("q")) * dk ** -0.5, unit(conv("k")), conv("v")
    f = mm(mm(h, p["Wf1"]), p["Wf2"]) + p["dt_bias"].astype(F32)
    g = -jnp.exp(p["A_log"].astype(F32))[:, None] \
        * jax.nn.softplus(f).reshape(t, nh, dk)
    beta = jax.nn.sigmoid(mm(h, p["Wb"]))                        # (T, nh)

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., None]
        u = b_t[:, None] * (v_t - jnp.einsum("hk,hkv->hv", k_t, s,
                                             precision="highest"))
        s = s + k_t[..., None] * u[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", q_t, s, precision="highest")

    _, o = lax.scan(step, jnp.zeros((nh, dk, dk), F32), (q, k, v, g, beta))
    gate = jax.nn.sigmoid(mm(mm(h, p["Wg1"]), p["Wg2"])).reshape(t, nh, dk)
    o = _rms(o, p["o_norm"], d["eps"]) * gate
    return mm(o.reshape(t, nh * dk), p["Wo"])


def _mla(p, d, h, mm):
    t = h.shape[0]
    nh, dn, dr, dv, r = d["heads"], d["dn"], d["dr"], d["dv"], d["r"]
    ckr = mm(h, p["Wdkv"])
    c, kr = _rms(ckr[:, :r], p["kv_norm"], d["eps"]), ckr[:, r:]
    kv = mm(c, p["Wukv"]).reshape(t, nh, dn + dv)
    q = mm(h, p["Wq"]).reshape(t, nh, dn + dr)
    s = (jnp.einsum("qhd,khd->hqk", q[..., :dn], kv[..., :dn],
                    precision="highest")
         + jnp.einsum("qhd,kd->hqk", q[..., dn:], kr, precision="highest")) \
        / (dn + dr) ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), kv[..., dn:],
                   precision="highest")
    return mm(o.reshape(t, nh * dv), p["Wo"])


def _ffn(p, d, h, mm):
    gated = lambda g, u, w: mm(jax.nn.silu(mm(h, g)) * mm(h, u), w)
    if "Wgate" in p:
        return gated(p["Wgate"], p["Wup"], p["Wdown"])
    # the router is never lowered: a pick is discrete, and the control is
    # about the precision of the arithmetic, not about other experts
    s = jax.nn.sigmoid(jnp.matmul(h, p["router"].astype(F32),
                                  precision="highest"))
    _, idx = lax.top_k(s + p["router_bias"].astype(F32), d["k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * d["scale"]
    y = gated(p["Sgate"], p["Sup"], p["Sdown"]) if "Sgate" in p \
        else jnp.zeros_like(h)
    for e in range(d["held"]):       # the experts held here, one at a time
        w_e = jnp.sum(jnp.where(idx == d["offset"] + e, w, 0.0), axis=-1)
        y = y + w_e[:, None] * gated(p["Egate"][e], p["Eup"][e],
                                     p["Edown"][e])
    return y


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _layer(p, x, dims, dtype):
    """One block over rows (N, T, H), a row at a time."""
    d = dict(dims)
    mm = lambda a, b: jnp.matmul(_lower(a, dtype), _lower(b, dtype),
                                 precision="highest")

    def row(x):
        h = _rms(x, p["norm1"], d["eps"])
        x = x + (_mla if "Wdkv" in p else _kda)(p, d, h, mm)
        return x + _ffn(p, d, _rms(x, p["norm2"], d["eps"]), mm)

    return lax.map(row, x)


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _head(p, xs, eps, dtype):
    return jnp.matmul(_lower(_rms(xs, p["norm"], eps), dtype),
                      _lower(p["W"], dtype), precision="highest")


def logits_at(w, tokens, positions, n_heads: int = 0, dtype=None):
    """Next-token logits (B, P, V) float32 at ``positions`` (B, P) of
    ``tokens`` (B, T). ``dtype``: every matrix product's operands rounded to
    that type (``float8_e4m3fn`` is the control); state, norms, softmax and
    the router stay float32. ``n_heads`` is what the harness passes for
    every model; the head counts are read from the weights' shapes through
    ``dims``, kept on the weights by :func:`make_weights`' caller."""
    dims = w.get("dims") or _dims_of(w, n_heads)
    x = w["emb"]["word"].astype(F32)[tokens]
    for p in w["layers"]:
        x = _layer(p, x, dims, None if dtype is None else jnp.dtype(dtype))
    xs = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    return _head(w["head"], xs, dict(dims)["eps"],
                 None if dtype is None else jnp.dtype(dtype))


# ------------------------------------------------------- the model's counts
def _matmul_params(d: dict, i: int, experts: float) -> float:
    """Weights of layer ``i`` that a token is multiplied through, with
    ``experts`` routed experts a token."""
    n = 0
    for name, shape in _layer_shapes(d, i).items():
        if len(shape) == 2 and not name.startswith("conv_"):
            n += shape[0] * shape[1]
        elif len(shape) == 3:
            n += experts * shape[1] * shape[2]
    return n


def request_flops(cfg: dict, prompt: int, new: int) -> float:
    """Operations of one request on this chip (a multiply-add counts 2):
    ``prompt + new - 1`` tokens pass through the layers, the head runs once
    a served token. A KDA layer adds 7 dk dv a head a token for its state
    (decay, ``k^T S``, the rank-1 write, ``S^T q``) and its convolutions; an
    MLA layer's token i attends i + 1 keys of 192 + 128 numbers a head. The
    routed experts count by the expected picks that name an expert held
    here: ``num_experts_per_token x num_experts / published_num_experts``."""
    d = _dims(cfg)
    n = prompt + new - 1
    here = d["k"] * d["held"] / d["routed"]
    total = 0.0
    for i in range(1, d["layers"] + 1):
        total += 2 * n * _matmul_params(d, i, here)
        if i in d["mla"]:
            total += 2 * d["heads"] * (d["dn"] + d["dr"] + d["dv"]) \
                * (n * (n + 1) // 2)
        else:
            inner = d["kda_heads"] * d["kda_d"]
            total += n * (7 * inner * d["kda_d"] + 2 * 3 * inner * d["conv"])
    return total + new * 2 * d["h"] * d["vocab"]


def decode_step_bytes(cfg: dict, rows: float, live_tokens: float,
                      experts_touched: float) -> float:
    """The least one decode step of ``rows`` streams must move through HBM
    on this chip: every matrix outside the routed experts once (mixers,
    dense and shared feed-forwards, routers, head), ``experts_touched``
    routed experts (summed over the layers: held experts with at least one
    pick), each KDA layer's state and convolution tail read and written a
    row, and the latent rows of the ``live_tokens`` the streams hold, in
    each MLA layer."""
    d = _dims(cfg)
    size = {"bfloat16": 2, "float32": 4}
    wb = size[cfg.get("param_dtype", "bfloat16")]
    fixed = d["h"] * d["vocab"]
    n_kda = 0
    for i in range(1, d["layers"] + 1):
        fixed += _matmul_params(d, i, 0)
        n_kda += i not in d["mla"]
    inner = d["kda_heads"] * d["kda_d"]
    state = size[cfg.get("state_dtype", "float32")] * (
        inner * d["kda_d"] + (d["conv"] - 1) * 3 * inner)
    latent = size[cfg.get("kv_dtype", "bfloat16")] * (d["r"] + d["dr"])
    return (fixed * wb + experts_touched * 3 * d["h"] * d["fe"] * wb
            + rows * n_kda * 2 * state
            + live_tokens * len(d["mla"]) * latent)
