"""Decoder layers for served language models: a token embedding without
position tables, the residual block with a choice of mixers and
feed-forwards, and a normed logits head (its own matrix, or the
embedding's: ``tied``). The block is pre-norm, ``x += mixer(RMSNorm(x)); x
+= ffn(RMSNorm(x))``, or, with ``norm="post"``, OLMo 2's reordered norm
(arXiv:2501.00656): ``x += RMSNorm(mixer(x)); x += RMSNorm(ffn(x))``.

Mixers (``mixer=``):

- ``"kda"`` — Kimi Delta Attention (ops/kda.py): gated delta-rule linear
  attention. Its cache is a STATE, one slot a stream: the (heads, dk, dv)
  float32 matrix and the last ``conv_size - 1`` inputs of the short
  convolutions. Whatever the context length, the state has one size.
- ``"gdn"`` — the Gated DeltaNet (arXiv:2412.06464) as flash-linear-
  attention's ``GatedDeltaNet`` lays it out: the same delta rule and cache
  as ``"kda"`` through the same code, with keys of ``head_dim`` and values
  of ``value_dim``, a decay of one value a head (``-exp(A_log)
  softplus(x W_a + dt_bias)``), a full-rank output gate ``silu(x W_g)``,
  and ``beta = 2 sigmoid(x W_b)`` in (0, 2): negative eigenvalues
  (arXiv:2411.12537). What differs is a :class:`DeltaRule`. A slot holds
  its states in whole lane tiles, two 192-wide heads side by side
  (ops/kda.py ``state_pack``).
- ``"mla"`` — multi-head latent attention: the cache is one row ``[c_t |
  kr_t]`` a token for all heads (``kv_lora_rank + qk_rope_dim`` numbers, in
  the parameters' type), behind the same page tables as a per-head K/V
  cache. A row is STORED at that width rounded up to whole 128-lane tiles
  (576 -> 640, ``[c | kr | zeros]``; a width that is whole tiles already is
  stored as it is): a TPU lays an (S, R) array whose R is not whole tiles
  out with S on the lanes, and every program that scatters or gathers slots
  then copies the whole pool to a slot-major layout and back (the rule of
  nn/transformer.py's ``init_pool``; PERF.md, PR 28 and PR 36). The extra
  lanes are exactly zero and meet zeros of the query, so every product is
  the configuration's; ``Wdkv`` and every byte count keep the latent width.
  Prefill attends in the expanded form, decode in the absorbed form over
  the paged rows (ops/attention.latent_paged_attention). Two things
  are a configuration's: ``rope`` rotates the ``qk_rope_dim`` dims of every
  head's query and of the shared key row by each token's own position
  (the key BEFORE it is written, so the cache holds ``[c | RoPE(kr)]`` and
  the absorbed decode stays one pass; without it the dims are carried
  unrotated and the layer sees no positions), and ``q_lora_rank`` makes the
  query low-rank (``RMSNorm(h W_dq) W_uq``; 0 = one full-rank ``Wq``).
- ``"mamba"`` — the selective state-space mixer of Mamba as Jamba stacks it
  (ops/ssm.py): ``[x | z] = h W_in``, ``x = silu(causal_conv(x) + b)``,
  ``[dt | B | C] = x W_x``, each RMS-normed with a scale of its own (Jamba's
  step), ``dt = softplus(dt W_dt + b_dt)``, the scan over ``d_state`` states
  a channel of ``expand x hidden`` channels, ``(y silu(z)) W_out``. Its
  cache is a STATE like KDA's, one slot a stream: the (d_state, channels)
  float32 states, channels on the lanes, and the convolution's last
  ``conv_size - 1`` inputs.
- ``"gqa"`` — softmax attention with ``n_heads`` query heads over
  ``n_kv_heads`` key/value heads and no positions. The cache is one row a
  token, ``n_kv_heads x [k | v]`` in the parameters' type (256 numbers at
  one head of 128: whole lane tiles, the rule above). Prefill attends in the
  expanded causal form, decode through the same block-chunked pass as the
  latent rows (ops/attention.grouped_paged_attention). ``qk_norm``
  RMS-norms the whole query and key projections before the heads are split
  (OLMo 2), the key before it is cached.

Feed-forwards (``ffn=``): ``"dense"`` gated SiLU, or ``"moe"``: a sigmoid
router over ``n_experts`` with a selection bias, the weights of the chosen
``top_k`` renormalised and scaled, one shared expert, and the routed experts
HELD HERE (``n_local_experts`` from ``expert_offset``) through the grouped
dispatch of nn/moe.py.

Every block meets the serving block protocol of serving/generate.py
(``cache_kind``, ``init_pool``, ``prefill_paged``, ``decode_window_paged``,
``prefill_resume_paged``); ``apply`` is the same mathematics without a
cache. :class:`NextTokenModule` is a model's own next-token-prediction
(MTP) head: one more block fed the main stack's last hidden state, which
serving/generate.py takes as a self-draft. Numbers: parameters in
their own type (bfloat16 when served), matrix products accumulate in
float32, the residual stream, norms, softmax, router and delta-rule state
are float32.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import moe
from deeplearning4j_tpu.nn.layers import Layer, register_layer
from deeplearning4j_tpu.ops import attention as attn_ops
from deeplearning4j_tpu.ops import kda, ssm

F32 = jnp.float32
#: the minor width of a TPU's (8, 128) tile: a cache row is stored in whole ones
LANES = 128


def rms_norm(x, weight, eps: float):
    """RMSNorm over the last axis in float32: x / rms(x) * weight."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * weight.astype(F32)


def _mm(x, w):
    """x @ w in the weights' type, accumulated and returned in float32."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=F32)


def _normal(key, shape, std, dtype, mean=0.0):
    return (mean + std * jax.random.normal(key, shape, F32)).astype(dtype)


def _step_bias(key, n: int, dtype):
    """The inverse softplus of ``n`` step sizes drawn log-uniform in
    (0.001, 0.1): the bias a state mixer's softplus turns back into them."""
    step = jnp.exp(jax.random.uniform(key, (n,), F32, jnp.log(1e-3),
                                      jnp.log(1e-1)))
    return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)


def rope(x, positions, theta: float):
    """Rotary positions over the whole last axis of ``x`` (B, T, ..., d) by
    ``positions`` (B, T), in float32: dim i is paired with dim i + d/2 (the
    "halves" layout) and the pair turned by ``positions * theta^(-2i/d)``."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[..., None] * freq            # (B, T, d/2)
    # taken before the head axis is put in: a query's and the key's, in
    # every layer of a program, are then one computation to the compiler
    over_heads = ang.shape[:2] + (1,) * (x.ndim - 3) + (half,)
    cos, sin = jnp.cos(ang).reshape(over_heads), \
        jnp.sin(ang).reshape(over_heads)
    x = x.astype(F32)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


@register_layer
@dataclasses.dataclass(frozen=True)
class TokenEmbeddingLayer(Layer):
    """Token ids (B, T) -> float32 hidden states (B, T, H): a lookup, no
    position or type tables (the mixers carry order)."""

    vocab_size: int = 0
    hidden_size: int = 0
    init_range: float = 0.02
    param_dtype: str = "float32"
    #: what a served model may hold at most; 0 = no bound of its own
    max_position: int = 0

    def initialize(self, key, input_shape):
        return {"word": _normal(key, (self.vocab_size, self.hidden_size),
                                self.init_range, self.param_dtype)}, {}

    def apply(self, params, state, x, *, training=False, key=None):
        return self.embed_window(params, x, None), state

    def embed_step(self, params, tokens, positions):
        return self.embed_window(params, tokens, positions)

    def embed_window(self, params, tokens, positions):
        return jnp.take(params["word"], tokens.astype(jnp.int32),
                        axis=0).astype(F32)

    def output_shape(self, input_shape):
        return (input_shape[0], self.hidden_size)


@register_layer
@dataclasses.dataclass(frozen=True)
class NormedLogitsLayer(Layer):
    """Final RMSNorm and a hidden x vocab head without bias; float32 logits
    (a bfloat16 logit near 8 has steps of 0.03). ``tied``: the head is the
    embedding itself. The layer then owns the norm alone and reads the
    (vocab, hidden) matrix under ``"E"``, which :meth:`tie` puts there BY
    REFERENCE: one array on the device, contracted over its own second axis
    (``n E^T``), no second copy and no transposed one."""

    n_in: int = 0
    n_out: int = 0
    eps: float = 1e-5
    init_range: float = 0.02
    param_dtype: str = "float32"
    tied: bool = False

    def initialize(self, key, input_shape):
        p = {"norm": jnp.ones((self.n_in,), self.param_dtype)}
        if not self.tied:
            p["W"] = _normal(key, (self.n_in, self.n_out), self.init_range,
                             self.param_dtype)
        return p, {}

    @staticmethod
    def tie(params, emb_params):
        """The head's parameters with the embedding's matrix beside the
        norm: the same array, not a copy."""
        return dict(params, E=emb_params["word"])

    def _logits(self, params, x):
        n = rms_norm(x, params["norm"], self.eps)
        if not self.tied:
            return _mm(n, params["W"])
        e = params["E"]
        return jax.lax.dot_general(
            n.astype(e.dtype), e, (((n.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=F32)

    def apply(self, params, state, x, *, training=False, key=None, mask=None):
        return self._logits(params, x), state

    def output_shape(self, input_shape):
        return (input_shape[0], self.n_out)


class StateWalk(NamedTuple):
    """What a ``"state"`` mixer says of itself to the serving path
    (serving/generate.py counts by it and imports no op module)."""

    #: names the counters: ``serving.<name>_prefill_<unit>_live_total``, ...
    name: str
    #: what its prefill walks, and how many positions one is
    unit: str
    size: int
    #: whether the prefill's counts are summed over the layers
    per_layer: bool


_STATE_WALKS = {"kda": StateWalk("kda", "chunks", kda.CHUNK, False),
                "gdn": StateWalk("gdn", "chunks", kda.CHUNK, False),
                "mamba": StateWalk("ssm", "positions", 1, True)}


class DeltaRule(NamedTuple):
    """What a delta-rule mixer makes of its input beside q, k and v."""

    #: one decay a head (``W_a``, (hidden, heads)), or one a channel
    #: through the low-rank ``W_f1 W_f2``
    decay_per_head: bool
    #: the output gate: full-rank ``W_g``, or low-rank ``W_g1 W_g2``
    full_rank_gate: bool
    #: the gate's activation
    gate: object
    #: the top of ``beta``'s range: 2 lets an eigenvalue of the
    #: transition go negative
    beta_top: float


_DELTA_RULES = {"kda": DeltaRule(False, False, jax.nn.sigmoid, 1.0),
                "gdn": DeltaRule(True, True, jax.nn.silu, 2.0)}


@register_layer
@dataclasses.dataclass(frozen=True)
class HybridDecoderBlock(Layer):
    """One residual block (module doc)."""

    hidden_size: int = 0
    mixer: str = "kda"            # "kda" | "gdn" | "mla" | "mamba" | "gqa"
    ffn: str = "dense"            # "dense" | "moe"
    norm: str = "pre"             # "pre" | "post" (OLMo 2's reordered norm)
    n_heads: int = 1
    eps: float = 1e-5
    init_range: float = 0.02
    param_dtype: str = "float32"
    # a delta rule's keys; gqa's heads have the size too, mamba's
    # convolution the length
    head_dim: int = 128
    value_dim: int = 0            # a delta rule's values (0: head_dim)
    conv_size: int = 4
    gate_rank: int = 128          # the low-rank width of kda's two gates
    # mla
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64         # rotated only where ``rope`` says so
    v_head_dim: int = 128
    q_lora_rank: int = 0          # 0 = one full-rank Wq
    rope: bool = False            # rotate the qk_rope_dim dims by position
    rope_theta: float = 10000.0
    # mamba
    d_state: int = 16
    dt_rank: int = 0
    expand: int = 2
    conv_bias: bool = True
    # gqa
    n_kv_heads: int = 1
    qk_norm: bool = False         # RMSNorm of the whole q and k projections
    # ffn
    ffn_size: int = 0             # dense width, or one expert's
    n_experts: int = 0            # the router's width
    n_local_experts: int = 0      # held here (0 = all)
    expert_offset: int = 0        # the first one held here
    top_k: int = 1
    routed_scale: float = 1.0
    shared_size: int = 0          # the shared expert's width (0 = none)

    causal = True

    # ------------------------------------------------------------- protocol
    @property
    def cache_kind(self) -> str:
        """``"state"``: one slot a stream; ``"tokens"``: one row a token
        behind the page tables."""
        return "state" if self.mixer in _STATE_WALKS else "tokens"

    @property
    def state_walk(self) -> StateWalk:
        """Of a ``"state"`` layer: what its prefill walks and what its
        counters are called (:class:`StateWalk`)."""
        return _STATE_WALKS[self.mixer]

    @property
    def _held(self) -> int:
        return self.n_local_experts or self.n_experts

    @property
    def _inner(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def _dv(self) -> int:
        """A delta-rule head's value width."""
        return self.value_dim or self.head_dim

    @property
    def _pack(self) -> int:
        """Delta-rule heads a state slot lays side by side on its lanes
        (ops/kda.py ``state_pack``)."""
        return kda.state_pack(self.n_heads, self._dv)

    @property
    def _row(self) -> int:
        """The latent width: what ``Wdkv`` makes and every byte count reads."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def _stored(self) -> int:
        """The width a latent row is stored at: whole lane tiles."""
        return -(-self._row // LANES) * LANES

    @property
    def _state_prefill(self):
        """A ``"state"`` mixer's whole-prompt pass: (params, x, mask) ->
        (mixer output, final state, convolution tail as its slot holds
        it)."""
        return self._ssm_prefill if self.mixer == "mamba" \
            else self._kda_prefill

    @property
    def _channels(self) -> int:
        """The state-space mixer's inner width."""
        return self.expand * self.hidden_size

    @property
    def _pool_row(self) -> int:
        """The width of a ``"tokens"`` row as stored: a grouped-query
        layer's ``n_kv_heads x [k | v]``, a latent layer's whole tiles."""
        if self.mixer == "gqa":
            return self.n_kv_heads * 2 * self.head_dim
        return self._stored

    # ----------------------------------------------------------- parameters
    def initialize(self, key, input_shape):
        hs, r, dt = self.hidden_size, self.init_range, self.param_dtype
        ks = iter(jax.random.split(key, 32))
        mat = lambda *shape: _normal(next(ks), shape, r, dt)
        one = lambda n: _normal(next(ks), (n,), r, dt, mean=1.0)
        p = {"norm1": one(hs), "norm2": one(hs)}
        if self.mixer in _DELTA_RULES:
            rule, h = _DELTA_RULES[self.mixer], self.n_heads
            inner, values = self._inner, h * self._dv
            for n, width in (("q", inner), ("k", inner), ("v", values)):
                p["W" + n] = mat(hs, width)
                p["conv_" + n] = _normal(next(ks), (self.conv_size, width),
                                         self.conv_size ** -0.5, dt)
            if rule.decay_per_head:
                p["Wa"] = mat(hs, h)
            else:
                p.update(Wf1=mat(hs, self.gate_rank),
                         Wf2=mat(self.gate_rank, inner))
            p["Wb"] = mat(hs, h)
            if rule.full_rank_gate:
                p["Wg"] = mat(hs, values)
            else:
                p.update(Wg1=mat(hs, self.gate_rank),
                         Wg2=mat(self.gate_rank, values))
            p.update(o_norm=one(self._dv), Wo=mat(values, hs))
            # decay rates 1..16 a head, step sizes 0.001..0.1 a channel (a
            # head, where the decay is one a head)
            p["A_log"] = jnp.log(jax.random.uniform(
                next(ks), (h,), F32, 1.0, 16.0)).astype(dt)
            p["dt_bias"] = _step_bias(
                next(ks), h if rule.decay_per_head else inner, dt)
        elif self.mixer == "mla":
            h = self.n_heads
            q_out = h * (self.qk_nope_dim + self.qk_rope_dim)
            p.update(Wdkv=mat(hs, self._row), kv_norm=one(self.kv_lora_rank),
                     Wukv=mat(self.kv_lora_rank,
                              h * (self.qk_nope_dim + self.v_head_dim)),
                     Wo=mat(h * self.v_head_dim, hs))
            if self.q_lora_rank:
                p.update(Wdq=mat(hs, self.q_lora_rank),
                         q_norm=one(self.q_lora_rank),
                         Wuq=mat(self.q_lora_rank, q_out))
            else:
                p["Wq"] = mat(hs, q_out)
        elif self.mixer == "mamba":
            ch, n, rk = self._channels, self.d_state, self.dt_rank
            p.update(Win=mat(hs, 2 * ch),
                     conv_x=_normal(next(ks), (self.conv_size, ch),
                                    self.conv_size ** -0.5, dt),
                     Wx=mat(ch, rk + 2 * n), dt_norm=one(rk), B_norm=one(n),
                     C_norm=one(n), Wdt=mat(rk, ch), Wout=mat(ch, hs),
                     D=jnp.ones((ch,), dt))
            if self.conv_bias:
                p["conv_bias"] = mat(ch)
            # Mamba's S4D-real start; step sizes 0.001..0.1 a channel
            p["A_log"] = jnp.broadcast_to(jnp.log(
                jnp.arange(1, n + 1, dtype=F32)), (ch, n)).astype(dt)
            p["dt_bias"] = _step_bias(next(ks), ch, dt)
        elif self.mixer == "gqa":
            inner, kv = self._inner, self.n_kv_heads * self.head_dim
            p.update(Wq=mat(hs, inner), Wk=mat(hs, kv), Wv=mat(hs, kv),
                     Wo=mat(inner, hs))
            if self.qk_norm:
                p.update(q_norm=one(inner), k_norm=one(kv))
        else:
            raise ValueError(f"unknown mixer {self.mixer!r}")
        f = self.ffn_size
        if self.ffn == "dense":
            p.update(Wgate=mat(hs, f), Wup=mat(hs, f), Wdown=mat(f, hs))
        elif self.ffn == "moe":
            e = self._held
            p.update(router=mat(hs, self.n_experts),
                     router_bias=_normal(next(ks), (self.n_experts,), 0.05,
                                         dt),
                     Egate=mat(e, hs, f), Eup=mat(e, hs, f),
                     Edown=mat(e, f, hs))
            if self.shared_size:
                s = self.shared_size
                p.update(Sgate=mat(hs, s), Sup=mat(hs, s), Sdown=mat(s, hs))
        else:
            raise ValueError(f"unknown ffn {self.ffn!r}")
        return p, {}

    # -------------------------------------------------------- feed-forwards
    def _normed_in(self, params, x, name: str):
        """What a sublayer reads of the residual stream: ``x`` RMS-normed
        by ``name`` (pre-norm), or ``x`` itself (post-norm)."""
        if self.norm == "post":
            return x.astype(F32)
        return rms_norm(x, params[name], self.eps)

    def _normed_out(self, params, y, name: str):
        """What a sublayer adds to the residual stream: ``y`` (pre-norm),
        or ``y`` RMS-normed by ``name`` (post-norm)."""
        if self.norm == "post":
            return rms_norm(y, params[name], self.eps)
        return y

    def _ffn(self, params, x, live=None):
        """x (B, T, H) float32 -> (what the feed-forward adds, stats or
        None); ``live`` (B, T) bool marks the tokens a router may count and
        send."""
        h = self._normed_in(params, x, "norm2")
        gated = lambda g, u, d: _mm(jax.nn.silu(_mm(h, params[g]))
                                    * _mm(h, params[u]), params[d])
        if self.ffn == "dense":
            return self._normed_out(params, gated("Wgate", "Wup", "Wdown"),
                                    "norm2"), None
        h2 = h.reshape(-1, self.hidden_size)
        with jax.named_scope("moe.route"):
            idx, w = moe.route_sigmoid_topk(
                h2, params["router"].astype(F32), params["router_bias"],
                self.top_k, self.routed_scale)
        with jax.named_scope("moe.grouped"):
            y, stats = moe.grouped_experts(
                h2.astype(params["Egate"].dtype), idx, w, params["Egate"],
                params["Eup"], params["Edown"], e_offset=self.expert_offset,
                n_experts=self.n_experts,
                live=None if live is None else live.reshape(-1))
        y = y.reshape(x.shape)
        if self.shared_size:
            y = y + gated("Sgate", "Sup", "Sdown")
        return self._normed_out(params, y, "norm2"), stats

    def _finish(self, params, x, a, pool, live=None, phase: int = 0):
        """Both residual adds; a router's counts, and 1 for the call, go
        onto row ``phase`` (0 prefill, 1 decode) of the pool's ``moe``
        accumulator (wrapping int32: the host reads differences)."""
        x = x + self._normed_out(params, a, "norm1")
        y, stats = self._ffn(params, x, live)
        if stats is not None and "moe" in pool:
            row = jnp.concatenate([stats, jnp.ones((1,), jnp.int32)])
            pool = dict(pool, moe=pool["moe"].at[phase].add(row))
        return x + y, pool

    # ---------------------------------------------- delta-rule mixers (KDA)
    def _kda_inputs(self, params, h, conv):
        """The mixer's input (B, T, H) and the short convolution to run,
        ``conv(raw, w)``: ``kda.causal_conv`` over whole prompts, the decode
        step's in the streams' slots -> q, k (B, T, heads, dk), v (B, T,
        heads, dv), g (B, T, heads, dk or 1), beta (B, T, heads), the output
        gate, and the projections before the convolution (the next tail).
        What the mixer's :class:`DeltaRule` says decides the decay, the gate
        and beta's range."""
        rule = _DELTA_RULES[self.mixer]
        b, t, _ = h.shape
        nh, dk, dv = self.n_heads, self.head_dim, self._dv
        with jax.named_scope(self.mixer + ".inputs"):
            raw = jnp.concatenate([_mm(h, params["W" + n]) for n in "qkv"],
                                  -1)
            w = jnp.concatenate([params["conv_" + n] for n in "qkv"],
                                -1).astype(F32)
            q, k, v = jnp.split(jax.nn.silu(conv(raw, w)),
                                [nh * dk, 2 * nh * dk], -1)
            heads = lambda a, d: a.reshape(b, t, nh, d)
            unit = lambda a: a * jax.lax.rsqrt(
                jnp.sum(jnp.square(a), -1, keepdims=True) + 1e-6)
            q, k = unit(heads(q, dk)) * dk ** -0.5, unit(heads(k, dk))
            v = heads(v, dv)
            rates = -jnp.exp(params["A_log"].astype(F32))
            if rule.decay_per_head:
                f = _mm(h, params["Wa"]) + params["dt_bias"].astype(F32)
                g = (rates * jax.nn.softplus(f))[..., None]
                gate = _mm(h, params["Wg"])
            else:
                f = _mm(_mm(h, params["Wf1"]), params["Wf2"]) \
                    + params["dt_bias"].astype(F32)
                g = rates[:, None] * heads(jax.nn.softplus(f), dk)
                gate = _mm(_mm(h, params["Wg1"]), params["Wg2"])
            beta = jax.nn.sigmoid(_mm(h, params["Wb"]))
            if rule.beta_top != 1.0:
                beta = rule.beta_top * beta
            return q, k, v, g, beta, heads(rule.gate(gate), dv), raw

    def _kda_out(self, params, o, gate):
        with jax.named_scope(self.mixer + ".out"):
            o = rms_norm(o, params["o_norm"], self.eps) * gate
            return _mm(o.reshape(*o.shape[:2], self.n_heads * self._dv),
                       params["Wo"])

    def _kda_prefill(self, params, x, mask):
        """Whole prompts from an empty state -> (mixer output, final state
        as a slot holds it, the convolutions' tail at each row's length)."""
        b, t, _ = x.shape
        h = self._normed_in(params, x, "norm1")
        q, k, v, g, beta, gate, raw = self._kda_inputs(params, h,
                                                       kda.causal_conv)
        lengths = jnp.sum(mask.astype(jnp.int32), axis=1)
        s0 = jnp.zeros((b, self.n_heads, self.head_dim, self._dv), F32)
        # padding moves no state, and chunks behind a length are not visited
        with jax.named_scope(self.mixer + ".chunked"):
            o, s = kda.kda_chunked(q, k, v, g, beta, s0, lengths)
        return (self._kda_out(params, o, gate), kda.pack_states(s, self._pack),
                kda.conv_tail(raw, lengths, self.conv_size - 1))

    # ------------------------------------------------------------ MLA mixer
    def _rope(self, x, positions):
        """The rotated dims of a query or key at ``positions`` (B, T); as
        they came where this configuration carries them unrotated."""
        if not self.rope:
            return x
        with jax.named_scope("mla.rope"):
            return rope(x, positions, self.rope_theta)

    def _mla_rows(self, params, h, positions):
        """Normed input -> the cache rows [RMSNorm(c) | kr | 0] (B, T,
        stored width), the key dims rotated by ``positions`` before they are
        cached, the lanes past the latent width exactly zero."""
        ckr = _mm(h, params["Wdkv"])
        c = rms_norm(ckr[..., :self.kv_lora_rank], params["kv_norm"],
                     self.eps)
        kr = self._rope(ckr[..., self.kv_lora_rank:], positions)
        fill = jnp.zeros(c.shape[:-1] + (self._stored - self._row,), F32)
        return jnp.concatenate([c, kr, fill], -1)

    def _mla_q(self, params, h, positions):
        b, t, _ = h.shape
        if self.q_lora_rank:
            with jax.named_scope("mla.q_lowrank"):
                q = _mm(rms_norm(_mm(h, params["Wdq"]), params["q_norm"],
                                 self.eps), params["Wuq"])
        else:
            q = _mm(h, params["Wq"])
        q = q.reshape(b, t, self.n_heads,
                      self.qk_nope_dim + self.qk_rope_dim)
        if not self.rope:
            return q
        dn = self.qk_nope_dim
        return jnp.concatenate(
            [q[..., :dn], self._rope(q[..., dn:], positions)], -1)

    @property
    def _mla_scale(self) -> float:
        return (self.qk_nope_dim + self.qk_rope_dim) ** -0.5

    def _mla_expanded(self, params, h, mask, q_block: int = 256):
        """Causal attention over a whole prompt in the expanded form, a
        block of queries at a time (the score matrix of 16 x 1,024 x 32
        heads would be 2 GB) -> (mixer output, cache rows)."""
        b, t, _ = h.shape
        nh, dn, dv = self.n_heads, self.qk_nope_dim, self.v_head_dim
        # whole prompts are right-padded: every row's positions are 0..T-1
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))
        rows = self._mla_rows(params, h, positions)
        c = rows[..., :self.kv_lora_rank]
        kr = rows[..., self.kv_lora_rank:self._row]
        kv = _mm(c, params["Wukv"]).reshape(b, t, nh, dn + dv)
        dt = params["Wukv"].dtype
        kc, v = kv[..., :dn].astype(dt), kv[..., dn:].astype(dt)
        q = self._mla_q(params, h, positions).astype(dt)
        kr = kr.astype(dt)
        k_pos = jnp.arange(t)
        keep = mask.astype(bool)[:, None, None, :]

        def block(q0):
            qb = jax.lax.dynamic_slice_in_dim(q, q0, q_block, axis=1)
            s = jnp.einsum("bqhd,bkhd->bhqk", qb[..., :dn], kc,
                           preferred_element_type=F32) \
                + jnp.einsum("bqhd,bkd->bhqk", qb[..., dn:], kr,
                             preferred_element_type=F32)
            ok = (k_pos[None, :] <= (q0 + jnp.arange(q_block))[:, None]) & keep
            p = jax.nn.softmax(jnp.where(ok, s * self._mla_scale, -1e30), -1)
            return jnp.einsum("bhqk,bkhd->bqhd", p.astype(dt), v,
                              preferred_element_type=F32)

        q_block = min(q_block, t)
        pad = -t % q_block
        if pad:
            q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        o = jax.lax.map(block, jnp.arange(0, t + pad, q_block))
        o = jnp.moveaxis(o, 0, 1).reshape(b, t + pad, nh * dv)[:, :t]
        return _mm(o, params["Wo"]), rows

    def _mla_absorbed(self, params, h, pool, tables, positions, block_size):
        """Window queries against the paged rows in absorbed form: ``qc
        W_uk^T`` meets ``c`` directly, and ``sum p c`` is expanded through
        ``W_uv`` after the softmax."""
        b, w, _ = h.shape
        nh, dn, dv = self.n_heads, self.qk_nope_dim, self.v_head_dim
        wukv = params["Wukv"].reshape(self.kv_lora_rank, nh, dn + dv)
        q = self._mla_q(params, h, positions)
        # zeros against the stored row's zero lanes: they add 0 to a score
        fill = jnp.zeros(q.shape[:-1] + (self._stored - self._row,), F32)
        q_abs = jnp.concatenate(
            [jnp.einsum("bwhd,rhd->bwhr", q[..., :dn].astype(wukv.dtype),
                        wukv[..., :dn], preferred_element_type=F32),
             q[..., dn:], fill], axis=-1)            # (B, W, heads, stored)
        ctx = attn_ops.latent_paged_attention(
            jnp.moveaxis(q_abs, 1, 2), pool, tables, positions, block_size,
            self.kv_lora_rank, self._mla_scale)           # (B, heads, W, r)
        o = jnp.einsum("bhwr,rhd->bwhd", ctx.astype(wukv.dtype),
                       wukv[..., dn:], preferred_element_type=F32)
        return _mm(o.reshape(b, w, nh * dv), params["Wo"])

    # ---------------------------------------------------------- Mamba mixer
    def _ssm_inputs(self, params, h, conv):
        """Normed input (B, T, H) and the short convolution to run, ``conv(raw,
        w)``: ``kda.causal_conv`` over whole prompts, the decode step's in
        the streams' slots -> the scan's x, dt (B, T, channels), B, C (B, T,
        d_state), the output gate z, and the projection before the
        convolution (the next tail)."""
        ch, n, rk = self._channels, self.d_state, self.dt_rank
        xz = _mm(h, params["Win"])
        raw, z = xz[..., :ch], xz[..., ch:]
        with jax.named_scope("ssm.conv"):
            x = conv(raw, params["conv_x"].astype(F32))
            if self.conv_bias:
                x = x + params["conv_bias"].astype(F32)
            x = jax.nn.silu(x)
        with jax.named_scope("ssm.proj"):
            low = _mm(x, params["Wx"])
            norm = lambda a, name: rms_norm(a, params[name], self.eps)
            dt = jax.nn.softplus(
                _mm(norm(low[..., :rk], "dt_norm"), params["Wdt"])
                + params["dt_bias"].astype(F32))
            bm = norm(low[..., rk:rk + n], "B_norm")
            cm = norm(low[..., rk + n:], "C_norm")
        return x, dt, bm, cm, z, raw

    def _ssm_rates(self, params):
        """The decay rates A (channels, d_state) and the skip D."""
        return (-jnp.exp(params["A_log"].astype(F32)),
                params["D"].astype(F32))

    def _ssm_prefill(self, params, x, mask):
        """Whole prompts from an empty state -> (mixer output, final state,
        the convolution's tail at each row's length, flat as a slot holds
        it)."""
        h = self._normed_in(params, x, "norm1")
        xc, dt, bm, cm, z, raw = self._ssm_inputs(params, h, kda.causal_conv)
        lengths = jnp.sum(mask.astype(jnp.int32), axis=1)
        s0 = jnp.zeros((x.shape[0], self.d_state, self._channels), F32)
        rates, skip = self._ssm_rates(params)
        with jax.named_scope("ssm.scan"):
            # padding moves no state, and a walk ends with its row's length
            y, s = ssm.selective_scan(xc, dt, rates, bm, cm, skip, s0,
                                      lengths, z)
        tail = kda.conv_tail(raw, lengths, self.conv_size - 1)
        return _mm(y, params["Wout"]), s, tail.reshape(x.shape[0], -1)

    # -------------------------------------------------- grouped-query mixer
    def _gqa_qkv(self, params, h):
        """The mixer's input -> q (B, T, heads, d), k, v (B, T, kv heads,
        d) and the cache rows ``n_kv_heads x [k | v]`` (B, T, row); the
        whole q and k projections RMS-normed first where ``qk_norm``
        says."""
        b, t, _ = h.shape
        heads = lambda a, n: a.reshape(b, t, n, self.head_dim)
        q, k = _mm(h, params["Wq"]), _mm(h, params["Wk"])
        if self.qk_norm:
            with jax.named_scope("gqa.qk_norm"):
                q = rms_norm(q, params["q_norm"], self.eps)
                k = rms_norm(k, params["k_norm"], self.eps)
        q, k = heads(q, self.n_heads), heads(k, self.n_kv_heads)
        v = heads(_mm(h, params["Wv"]), self.n_kv_heads)
        rows = jnp.concatenate([k, v], -1).reshape(b, t, self._pool_row)
        return q, k, v, rows

    def _gqa_expanded(self, params, h, mask, q_block: int = 256):
        """Causal softmax over a whole prompt, every query head of a group
        against the group's one key/value head, a block of queries at a time
        -> (mixer output, cache rows)."""
        b, t, _ = h.shape
        g, d = self.n_kv_heads, self.head_dim
        q, k, v, rows = self._gqa_qkv(params, h)
        dt = params["Wq"].dtype
        q = q.reshape(b, t, g, self.n_heads // g, d).astype(dt)
        k, v = k.astype(dt), v.astype(dt)
        k_pos = jnp.arange(t)
        keep = mask.astype(bool)[:, None, None, None, :]

        def block(q0):
            qb = jax.lax.dynamic_slice_in_dim(q, q0, q_block, axis=1)
            s = jnp.einsum("bqgrd,bkgd->bgrqk", qb, k,
                           preferred_element_type=F32)
            ok = (k_pos[None, :] <= (q0 + jnp.arange(q_block))[:, None]) & keep
            p = jax.nn.softmax(jnp.where(ok, s * d ** -0.5, -1e30), -1)
            return jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(dt), v,
                              preferred_element_type=F32)

        q_block = min(q_block, t)
        pad = -t % q_block
        if pad:
            q = jnp.pad(q, ((0, 0), (0, pad)) + ((0, 0),) * 3)
        with jax.named_scope("gqa.attend"):
            o = jax.lax.map(block, jnp.arange(0, t + pad, q_block))
        o = jnp.moveaxis(o, 0, 1).reshape(b, t + pad, self._inner)[:, :t]
        return _mm(o, params["Wo"]), rows

    # ------------------------------------------------------------- no cache
    def apply(self, params, state, x, *, training=False, key=None, mask=None):
        if mask is None:
            mask = jnp.ones(x.shape[:2], F32)
        if self.cache_kind == "state":
            a, _, _ = self._state_prefill(params, x, mask)
        else:
            attend = (self._gqa_expanded if self.mixer == "gqa"
                      else self._mla_expanded)
            a, _ = attend(params, self._normed_in(params, x, "norm1"), mask)
        out, _ = self._finish(params, x.astype(F32), a, {},
                              mask.astype(bool))
        return out, state

    # ---------------------------------------------------------- paged cache
    def init_pool(self, num_slots: int):
        """``cache_kind`` ``"state"``: ``num_slots`` stream slots of the
        delta-rule or state-space state (float32; a delta rule's heads
        side by side in whole lane tiles) and the convolutions' tail;
        ``"tokens"``: ``num_slots`` rows in the parameters' type, a
        grouped-query layer's ``n_kv_heads x [k | v]`` or a latent layer's
        of the
        STORED width (the latent width rounded up to whole 128-lane tiles,
        the extra lanes zero), because a TPU lays an (S, R) array whose R is
        not whole tiles out with S on the lanes, and every program that
        scatters or gathers slots then copies the whole pool to a slot-major
        layout and back (the rule of nn/transformer.py's ``init_pool``,
        PERF.md PR 28; here PR 36). A routed feed-forward adds its
        counters."""
        if self.mixer in _DELTA_RULES:
            # a slot's heads ``pack`` to a row of whole lane tiles
            pk = self._pack
            pool = {"state": jnp.zeros((num_slots, self.n_heads // pk,
                                        self.head_dim, pk * self._dv), F32),
                    "conv": jnp.zeros((num_slots, self.conv_size - 1,
                                       2 * self._inner
                                       + self.n_heads * self._dv), F32)}
        elif self.mixer == "mamba":
            # the tail flat, a slot a row of whole lane tiles: with the 3
            # inputs as an axis of their own the device re-lays the whole
            # pool around every gather and scatter (the rule above)
            pool = {"state": jnp.zeros((num_slots, self.d_state,
                                        self._channels), F32),
                    "conv": jnp.zeros((num_slots, (self.conv_size - 1)
                                       * self._channels), F32)}
        else:
            pool = {"rows": jnp.zeros((num_slots, self._pool_row),
                                      self.param_dtype)}
        if self.ffn == "moe":
            pool["moe"] = jnp.zeros((2, len(moe.MOE_STATS) + 1), jnp.int32)
        return pool

    def prefill_paged(self, params, x, pool, where, mask=None):
        """Whole prompts (B, T, H). ``where`` is each stream's address in
        this block's cache: flat token slots (B, T) for ``"tokens"``, the
        stream's state slot (B,) for ``"state"`` (0 = the trash slot)."""
        if self.cache_kind == "state":
            a, s, tail = self._state_prefill(params, x, mask)
            pool = dict(pool, state=pool["state"].at[where].set(s),
                        conv=pool["conv"].at[where].set(tail))
        else:
            attend = (self._gqa_expanded if self.mixer == "gqa"
                      else self._mla_expanded)
            a, rows = attend(params, self._normed_in(params, x, "norm1"),
                             mask)
            pool = dict(pool, rows=pool["rows"].at[where.reshape(-1)].set(
                rows.reshape(-1, self._pool_row).astype(pool["rows"].dtype)))
        return self._finish(params, x, a, pool, mask.astype(bool))

    def decode_window_paged(self, params, x_w, pool, where, positions,
                            block_size, limits=None, phase: int = 1):
        """Window tokens (B, W, H) at ``positions`` (B, W), each row's own.
        ``where``: the page tables (B, max_blocks) for ``"tokens"``, the
        state slots (B,) for ``"state"``. A token past its stream's
        ``limits`` writes no cache row and moves no state. A router's counts
        go onto row ``phase`` (:meth:`_finish`)."""
        live = jnp.ones(positions.shape, bool) if limits is None \
            else positions <= limits[:, None]
        h = self._normed_in(params, x_w, "norm1")
        stepped = {}    # a "state" block's two pools, each stepped in place

        def conv(raw, w):
            # each live stream's tail, read and written in its slot
            y, stepped["conv"] = kda.conv_step_paged(raw, w, pool["conv"],
                                                     where, live)
            return y

        if self.mixer in _DELTA_RULES:
            q, k, v, g, beta, gate, _ = self._kda_inputs(params, h, conv)
            # and its state, in the same slot of the state pool
            with jax.named_scope(self.mixer + ".step"):
                o, stepped["state"] = kda.kda_step_paged(
                    q, k, v, g, beta, pool["state"], where, live)
            pool = dict(pool, **stepped)
            a = self._kda_out(params, o, gate)
        elif self.mixer == "mamba":
            xc, dt, bm, cm, z, _ = self._ssm_inputs(params, h, conv)
            rates, skip = self._ssm_rates(params)
            with jax.named_scope("ssm.step"):
                y, stepped["state"] = ssm.selective_step_paged(
                    xc, dt, rates, bm, cm, skip, pool["state"], where, live,
                    z)
            pool = dict(pool, **stepped)
            a = _mm(y, params["Wout"])
        elif self.mixer == "gqa":
            slots = attn_ops.paged_slots(where, positions, block_size)
            slots = jnp.where(live, slots, 0)
            q, _, _, rows = self._gqa_qkv(params, h)
            pool = dict(pool, rows=pool["rows"].at[slots.reshape(-1)].set(
                rows.reshape(-1, self._pool_row).astype(pool["rows"].dtype)))
            with jax.named_scope("gqa.attend"):
                o = attn_ops.grouped_paged_attention(
                    jnp.moveaxis(q, 1, 2), pool["rows"], where, positions,
                    block_size, self.n_kv_heads)
            a = _mm(jnp.moveaxis(o, 1, 2).reshape(*h.shape[:2], self._inner),
                    params["Wo"])
        else:
            slots = attn_ops.paged_slots(where, positions, block_size)
            slots = jnp.where(live, slots, 0)
            rows = self._mla_rows(params, h, positions)
            pool = dict(pool, rows=pool["rows"].at[slots.reshape(-1)].set(
                rows.reshape(-1, self._stored).astype(pool["rows"].dtype)))
            a = self._mla_absorbed(params, h, pool["rows"], where, positions,
                                   block_size)
        return self._finish(params, x_w, a, pool, live, phase=phase)

    def prefill_resume_paged(self, params, x_w, pool, where, positions,
                             block_size, limits=None):
        """A chunk of prompt tokens from each row's own resume point: the
        window's write-then-attend over the paged rows, counted as prefill.
        (A net with ``"state"`` layers is refused before it gets here:
        serving/generate.py.)"""
        return self.decode_window_paged(params, x_w, pool, where, positions,
                                        block_size, limits=limits, phase=0)

    def output_shape(self, input_shape):
        return (input_shape[0], self.hidden_size)


@dataclasses.dataclass(frozen=True)
class NextTokenModule:
    """A model's own next-token-prediction (MTP) head (DeepSeek-V3 report,
    arXiv:2412.19437, section 2.2): ``x'_i = W_eh [RMSNorm_e(Emb(t_{i+1})) ;
    RMSNorm_h(h_i)]`` with ``h_i`` the main stack's last hidden state before
    its final norm, one ``block`` with latent rows of its own, a final
    RMSNorm, and the MAIN model's embedding and head (shared by reference,
    never copied) -> logits for ``t_{i+2}``. No layer of a net: the serving
    path takes it, with its parameters, as a self-draft
    (:class:`SelfDraft`)."""

    block: HybridDecoderBlock
    eps: float = 1e-5
    init_range: float = 0.02
    param_dtype: str = "float32"

    def initialize(self, key):
        hs, r, dt = self.block.hidden_size, self.init_range, self.param_dtype
        ks = jax.random.split(key, 5)
        one = lambda k: _normal(k, (hs,), r, dt, mean=1.0)
        return {"enorm": one(ks[0]), "hnorm": one(ks[1]),
                "Weh": _normal(ks[2], (2 * hs, hs), r, dt),
                "block": self.block.initialize(ks[3], None)[0],
                "norm": one(ks[4])}

    def join(self, params, emb_x, h):
        """The block's input: the next token's embedding (first) and the
        main stack's hidden state, each normed, through ``W_eh``."""
        return _mm(jnp.concatenate(
            [rms_norm(emb_x, params["enorm"], self.eps),
             rms_norm(h, params["hnorm"], self.eps)], -1), params["Weh"])

    def logits(self, params, head, head_params, x):
        """This module's final norm, then the main model's head."""
        return head._logits(dict(head_params, norm=params["norm"]), x)


class SelfDraft:
    """A model's own next-token-prediction module as its draft
    (``Generator(self_draft=)``): ``module`` (:class:`NextTokenModule`:
    ``block``, ``join``, ``logits``) and its ``params`` (``enorm``,
    ``hnorm``, ``Weh``, ``block``, ``norm``). The embedding and the head are
    the served net's, by reference."""

    def __init__(self, module, params=None):
        self.module, self.params = module, params
