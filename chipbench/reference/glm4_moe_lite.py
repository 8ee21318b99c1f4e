"""Plain float32 reference of GLM-4.7-Flash (zai-org/GLM-4.7-Flash
``config.json``, ``model_type: glm4_moe_lite``; the attention and the MTP
module as the DeepSeek-V3 report, arXiv:2412.19437, sections 2.1 and 2.2,
writes them).

Full forward over the whole sequence, ``jax.numpy`` at ``highest``: no cache,
no paging, no absorbed form, no grouped products; attention expanded, one
block of queries at a time; the 64 experts in a loop; one row at a time. It
imports nothing of ``deeplearning4j_tpu`` and makes its own weights from the
seed.

Layout: token embedding; ``num_hidden_layers`` pre-norm blocks ``x +=
attn(RMSNorm(x)); x += ffn(RMSNorm(x))``; final RMSNorm; untied head. With
``h = RMSNorm(x)``:

- Attention (every layer): ``c_q = RMSNorm(h W_dq)`` (``q_lora_rank``),
  ``[q_n | q_r] = c_q W_uq`` a head (``qk_nope_head_dim | qk_rope_head_dim``);
  ``[c_kv | k_r] = h W_dkv`` (one key row for all heads), ``c = RMSNorm(c_kv)``,
  ``[k_n | v] = c W_ukv`` a head; ``q_r`` and ``k_r`` rotated by the token's
  position (``rope_theta``, all ``qk_rope_head_dim`` dims); scores ``(q_n .
  k_n + q_r' . k_r') / sqrt(qk_nope + qk_rope)``, causal softmax; ``W_o``.
- Feed-forward: the first ``first_k_dense_replace`` layers dense gated SiLU;
  after them ``s = sigmoid(h W_r)`` over ``n_routed_experts``, the
  ``num_experts_per_tok`` largest of ``s + bias`` (the bias selects only;
  ``n_group`` = ``topk_group`` = 1: plain top-k), weights ``s_i / sum s *
  routed_scaling_factor``, each chosen expert HELD HERE (``num_experts`` from
  ``expert_offset``; all 64 in the benchmark's cut) applied in a loop to
  every token and weighted by 0 where it was not chosen, plus the shared
  expert.
- MTP module (:func:`mtp_logits_at`): ``x'_i = W_eh [RMSNorm_e(Emb(t_{i+1}))
  ; RMSNorm_h(h_i)]`` with ``h_i`` the main stack's last hidden state before
  its final norm; one block of the same attention and expert layer; RMSNorm;
  the main model's embedding and head -> logits for ``t_{i+2}``.

Departures from the published description, all under ``assumed`` in the
configuration's file:
- the rotary pairing: dim i of the 64 turns with dim i + 32 (halves), as the
  DeepSeek-V3 code permutes its interleaved checkpoint to; with random
  weights any pairing is a permutation of ``W_uq``'s and ``W_dkv``'s columns;
- the order inside ``W_eh``'s input: the embedding first, then the hidden
  state;
- weights: matrices N(0, 0.02), norm scales 1 + N(0, 0.02), selection bias
  N(0, 0.05), bfloat16 leaves; the routed experts of a layer are
  ``sqrt(rho) B + sqrt(1 - rho) D_e`` with ``B`` drawn once a layer and
  ``D_e`` an expert, each N(0, 0.02), ``rho`` the file's
  ``expert_common_share`` (:func:`make_weights` says why);
- the served window (``max_position_embeddings`` of the file) bounds the
  positions; no ``rope_scaling`` (null as published).

With it the model's own counts for the benchmark's readers:
:func:`request_flops`, :func:`prefill_flops` and :func:`decode_step_bytes`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

INIT_STD = 0.02
F32 = jnp.float32
Q_BLOCK = 256


# ------------------------------------------------------------------- shapes
def _dims(cfg: dict) -> dict:
    routed = cfg["n_routed_experts"]
    return dict(
        h=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
        heads=cfg["num_attention_heads"], rq=cfg["q_lora_rank"],
        r=cfg["kv_lora_rank"], dn=cfg["qk_nope_head_dim"],
        dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        theta=float(cfg["rope_theta"]), dense=cfg["first_k_dense_replace"],
        f=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"],
        routed=routed, held=cfg.get("num_experts", routed),
        offset=cfg.get("expert_offset", 0), k=cfg["num_experts_per_tok"],
        shared=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        scale=cfg["routed_scaling_factor"], eps=cfg["rms_norm_eps"],
        vocab=cfg["vocab_size"],
        mtp=cfg.get("num_nextn_predict_layers", 0))


def _layer_shapes(d: dict, i: int) -> dict:
    """Leaf name -> shape of layer ``i`` (from 0); an expert layer for any
    ``i`` past the dense ones (the MTP block is one)."""
    h, nh = d["h"], d["heads"]
    s = {"norm1": (h,), "norm2": (h,), "Wdq": (h, d["rq"]),
         "q_norm": (d["rq"],), "Wuq": (d["rq"], nh * (d["dn"] + d["dr"])),
         "Wdkv": (h, d["r"] + d["dr"]), "kv_norm": (d["r"],),
         "Wukv": (d["r"], nh * (d["dn"] + d["dv"])),
         "Wo": (nh * d["dv"], h)}
    if i < d["dense"]:
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
    """``{"emb": {"word"}, "layers": [...], "head": {"norm", "W"}, "dims"``
    and, where ``num_nextn_predict_layers`` is 1, ``"mtp": {"enorm",
    "hnorm", "Weh", "block": {...}, "norm"}}``: bfloat16 leaves (the file's
    ``param_dtype``), shared with the program and not copied. Matrices
    N(0, 0.02); norm scales 1 + N(0, 0.02); the selection bias N(0, 0.05):
    nothing is left at a value that would hide a term the program dropped.
    One jitted call a layer, on the device.

    The routed experts of a layer share a part: each of ``Egate``, ``Eup``,
    ``Edown`` is ``sqrt(rho) B + sqrt(1 - rho) D_e``, ``B`` one matrix a
    layer and ``D_e`` one an expert, both N(0, 0.02) (so is every element of
    the sum), ``rho`` = ``expert_common_share`` (0: independent experts).
    With independent experts and all 64 held, one pick that rounding moves
    from the 4th to the 5th score swaps a quarter of a token's routed output
    for an unrelated one and moves its logits as far as fp8 arithmetic does
    (PERF.md section 2: the two readings did not part). A trained router's
    near-tied experts are near neighbours; a shared part says that, and
    hides no term: every expert still differs, and a dropped, doubled or
    mis-weighted pick changes the whole of that expert's output."""
    d = _dims(cfg)
    dt = jnp.dtype(cfg.get("param_dtype", "bfloat16"))
    rho = float(cfg.get("expert_common_share", 0.0))

    def leaf(key, name, shape):
        n = lambda std, mean=0.0, shape=shape, key=key: (
            mean + std * jax.random.normal(key, shape, F32))
        if "norm" in name:
            return n(INIT_STD, 1.0).astype(dt)
        if name == "router_bias":
            return n(0.05).astype(dt)
        if len(shape) == 3 and rho:
            kb, kd = jax.random.split(key)
            return (rho ** 0.5 * n(INIT_STD, shape=(1,) + shape[1:], key=kb)
                    + (1 - rho) ** 0.5 * n(INIT_STD, key=kd)).astype(dt)
        return n(INIT_STD).astype(dt)

    @functools.partial(jax.jit, static_argnums=1)
    def build(key, shapes):
        keys = jax.random.split(key, len(shapes))
        return {name: leaf(k, name, shape)
                for k, (name, shape) in zip(keys, shapes)}

    ks = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)),
                          d["layers"] + 4)
    items = lambda s: tuple(sorted(s.items()))
    h = d["h"]
    w = {"dims": items(d),
         "emb": build(ks[0], items({"word": (d["vocab"], h)})),
         "layers": [build(ks[i + 1], items(_layer_shapes(d, i)))
                    for i in range(d["layers"])],
         "head": build(ks[d["layers"] + 1],
                       items({"norm": (h,), "W": (h, d["vocab"])}))}
    if d["mtp"]:
        w["mtp"] = build(ks[d["layers"] + 2],
                         items({"enorm": (h,), "hnorm": (h,),
                                "Weh": (2 * h, h), "norm": (h,)}))
        w["mtp"]["block"] = build(ks[d["layers"] + 3],
                                  items(_layer_shapes(d, d["dense"])))
    return w


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


def _rope(x, theta):
    """``x`` (T, ..., d) at positions 0..T-1: dim i turns with dim i + d/2
    by ``t * theta^(-2i/d)``."""
    t, half = x.shape[0], x.shape[-1] // 2
    ang = jnp.arange(t, dtype=F32)[:, None] \
        * theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (half,))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def _attention(p, d, h, mm):
    """One row: normed input (T, H) -> attention output (T, H), expanded,
    a block of queries at a time."""
    t = h.shape[0]
    nh, dn, dr, dv, r = d["heads"], d["dn"], d["dr"], d["dv"], d["r"]
    cq = _rms(mm(h, p["Wdq"]), p["q_norm"], d["eps"])
    q = mm(cq, p["Wuq"]).reshape(t, nh, dn + dr)
    ckr = mm(h, p["Wdkv"])
    c = _rms(ckr[:, :r], p["kv_norm"], d["eps"])
    kr = _rope(ckr[:, r:], d["theta"])
    kv = mm(c, p["Wukv"]).reshape(t, nh, dn + dv)
    qn, qr = q[..., :dn], _rope(q[..., dn:], d["theta"])
    blk = min(Q_BLOCK, t)
    pad = -t % blk
    qn, qr = (jnp.pad(a, ((0, pad), (0, 0), (0, 0))) for a in (qn, qr))

    def block(q0):
        sl = lambda a: lax.dynamic_slice_in_dim(a, q0, blk, axis=0)
        s = (jnp.einsum("qhd,khd->hqk", sl(qn), kv[..., :dn],
                        precision="highest")
             + jnp.einsum("qhd,kd->hqk", sl(qr), kr, precision="highest")) \
            / (dn + dr) ** 0.5
        ok = jnp.arange(t)[None, :] <= (q0 + jnp.arange(blk))[:, None]
        return jnp.einsum("hqk,khd->qhd",
                          jax.nn.softmax(jnp.where(ok, s, -jnp.inf), -1),
                          kv[..., dn:], precision="highest")

    o = lax.map(block, jnp.arange(0, t + pad, blk))
    return mm(o.reshape(t + pad, nh * dv)[:t], p["Wo"])


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

    def expert(e, y):                # the experts held here, one at a time
        w_e = jnp.sum(jnp.where(idx == d["offset"] + e, w, 0.0), axis=-1)
        at = lambda a: lax.dynamic_index_in_dim(a, e, 0, keepdims=False)
        return y + w_e[:, None] * gated(at(p["Egate"]), at(p["Eup"]),
                                        at(p["Edown"]))

    return lax.fori_loop(0, p["Egate"].shape[0], expert, y)


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _layer(p, x, dims, dtype):
    """One block over rows (N, T, H), a row at a time."""
    d = dict(dims)
    mm = lambda a, b: jnp.matmul(_lower(a, dtype), _lower(b, dtype),
                                 precision="highest")

    def row(x):
        x = x + _attention(p, d, _rms(x, p["norm1"], d["eps"]), mm)
        return x + _ffn(p, d, _rms(x, p["norm2"], d["eps"]), mm)

    return lax.map(row, x)


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _head(p, xs, eps, dtype):
    return jnp.matmul(_lower(_rms(xs, p["norm"], eps), dtype),
                      _lower(p["W"], dtype), precision="highest")


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _join(p, e, h, eps, dtype):
    x = jnp.concatenate([_rms(e, p["enorm"], eps), _rms(h, p["hnorm"], eps)],
                        -1)
    return jnp.matmul(_lower(x, dtype), _lower(p["Weh"], dtype),
                      precision="highest")


def _dtype(dtype):
    return None if dtype is None else jnp.dtype(dtype)


def _hidden(w, tokens, dtype):
    """The main stack's last hidden state (B, T, H), before the final
    norm."""
    x = w["emb"]["word"][tokens].astype(F32)
    for p in w["layers"]:
        x = _layer(p, x, w["dims"], dtype)
    return x


def logits_at(w, tokens, positions, n_heads: int = 0, dtype=None):
    """Next-token logits (B, P, V) float32 at ``positions`` (B, P) of
    ``tokens`` (B, T). ``dtype``: every matrix product's operands rounded to
    that type (``float8_e4m3fn`` is the control); norms, softmax, rotation
    and the router stay float32. ``n_heads`` is what the harness passes for
    every model; the sizes are read from ``dims``, kept on the weights by
    :func:`make_weights`."""
    x = _hidden(w, tokens, _dtype(dtype))
    xs = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    return _head(w["head"], xs, dict(w["dims"])["eps"], _dtype(dtype))


def mtp_logits_at(w, tokens, positions, dtype=None):
    """The MTP module's logits (B, P, V) at ``positions``: at position i,
    for the token at i + 2, from ``tokens[i + 1]`` and the main stack's
    hidden state at i. ``tokens`` (B, T): the last column's next token does
    not exist, so ask only positions below T - 1."""
    eps, m = dict(w["dims"])["eps"], w["mtp"]
    dtype = _dtype(dtype)
    emb = w["emb"]["word"][jnp.roll(tokens, -1, axis=1)].astype(F32)
    x = _join({k: m[k] for k in ("enorm", "hnorm", "Weh")}, emb,
              _hidden(w, tokens, dtype), eps, dtype)
    x = _layer(m["block"], x, w["dims"], dtype)
    xs = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    return _head({"norm": m["norm"], "W": w["head"]["W"]}, xs, eps, dtype)


# ------------------------------------------------------- the model's counts
def _matmul_params(d: dict, i: int, experts: float) -> float:
    """Weights of layer ``i`` that a token is multiplied through, with
    ``experts`` routed experts a token."""
    n = 0
    for shape in _layer_shapes(d, i).values():
        if len(shape) == 2:
            n += shape[0] * shape[1]
        elif len(shape) == 3:
            n += experts * shape[1] * shape[2]
    return n


def _token_flops(d: dict) -> float:
    """Matrix-product operations a token costs in the layers (a
    multiply-add counts 2): every matrix outside the routed experts and the
    expected picks that name an expert held here, ``num_experts_per_tok x
    num_experts / n_routed_experts`` (all four in the benchmark's cut)."""
    here = d["k"] * d["held"] / d["routed"]
    return 2 * sum(_matmul_params(d, i, here) for i in range(d["layers"]))


def _attn_flops(d: dict, keys: float) -> float:
    """Scores and values of one layer over ``keys`` (query, key) pairs in
    the expanded form: ``qk_nope + qk_rope + v_head`` numbers a head."""
    return 2 * d["heads"] * (d["dn"] + d["dr"] + d["dv"]) * keys


def request_flops(cfg: dict, prompt: int, new: int) -> float:
    """Operations of one request on this chip: ``prompt + new - 1`` tokens
    pass through the layers, token i attends i + 1 keys in every layer, the
    head runs once a served token."""
    d = _dims(cfg)
    n = prompt + new - 1
    return (n * _token_flops(d)
            + d["layers"] * _attn_flops(d, n * (n + 1) // 2)
            + new * 2 * d["h"] * d["vocab"])


def prefill_flops(cfg: dict, rows: int, seq: int) -> float:
    """Operations of one launched prefill of ``rows`` x ``seq`` DECLARED
    positions: what the program computes whatever the prompts hold (padding
    is multiplied through every matrix but routed to no expert, which this
    count overstates by the padding's share of the experts' part); causal
    attention over seq (seq + 1) / 2 pairs a row; the head once a row."""
    d = _dims(cfg)
    return (rows * seq * _token_flops(d)
            + rows * d["layers"] * _attn_flops(d, seq * (seq + 1) // 2)
            + rows * 2 * d["h"] * d["vocab"])


def decode_step_bytes(cfg: dict, rows: float, live_tokens: float,
                      experts_touched: float) -> float:
    """The least one decode step of ``rows`` streams must move through HBM
    on this chip: every matrix outside the routed experts once (attention,
    dense and shared feed-forwards, routers, head), ``experts_touched``
    routed experts (summed over the layers: held experts with at least one
    pick; 3 x hidden x moe_intermediate numbers each, 18.87 MB in
    bfloat16), and the latent rows of the ``live_tokens`` the streams hold,
    in every layer."""
    d = _dims(cfg)
    size = {"bfloat16": 2, "float32": 4}
    wb = size[cfg.get("param_dtype", "bfloat16")]
    fixed = d["h"] * d["vocab"] + sum(_matmul_params(d, i, 0)
                                      for i in range(d["layers"]))
    latent = size[cfg.get("kv_dtype", "bfloat16")] * (d["r"] + d["dr"])
    return (fixed * wb + experts_touched * 3 * d["h"] * d["fe"] * wb
            + live_tokens * d["layers"] * latent)
