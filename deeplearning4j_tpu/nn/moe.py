"""Mixture-of-Experts feed-forward layers with expert parallelism.

The reference has no MoE and no expert parallelism (SURVEY.md §2.3: EP —
"not required"); this is a TPU-native extension in the same spirit as ring
attention: the strategies large models actually need.

Two dispatches live here, for two uses.

**Grouped dispatch** (:func:`route_sigmoid_topk`, :func:`grouped_experts`) is
the design for a layer of many experts, and what a served model runs: each
token's (token, expert) picks are sorted by expert, the picks of the
experts HELD HERE pass through one grouped matrix product per projection
(``lax.ragged_dot``: on a TPU a native grouped kernel whose work is the
rows it is given, not rows x experts), and the weighted results are added
back to their tokens. The layer is told which slice of the experts it
holds (``e_offset`` and the leading size of its expert matrices), routes
over ALL of them, and adds nothing for the absent ones: that partial sum is
one chip's share under expert parallelism, and the shares of all the chips
add up to the uncut layer (tests/test_kimi_linear.py). The rows of a pass
are bounded by twice the picks a uniform router would send here; a router
that sends more is served by further passes of the same loop (its trip
count is data), never by dropping a pick.

**Dense dispatch** (:class:`MixtureOfExperts`): every expert computes every
token and the gate zeroes what was not picked. Static shapes and no sort,
which is what lets :func:`expert_parallel` hand the same layer to GSPMD with
the expert axis sharded over a mesh; it costs experts/top_k times the work,
so it is for the few experts (E <= ~32) of a trainable layer, not for a
router of hundreds.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.nn import activations as act
from deeplearning4j_tpu.nn import weights as winit
from deeplearning4j_tpu.nn.layers import Layer, register_layer


@register_layer
@dataclasses.dataclass(frozen=True)
class MixtureOfExperts(Layer):
    """Top-k routed MoE FFN over [B, T, H] (or [B, H]) inputs.

    Params: router (H, E); per-expert W1 (E, H, F), b1 (E, F), W2 (E, F, H),
    b2 (E, H). Output has the input's shape; aux load-balancing loss
    (Switch-Transformer style) is exposed via ``aux_loss`` on the state.
    """

    n_in: int = 0
    n_experts: int = 4
    ffn_size: int = 0          # default 4*n_in
    top_k: int = 2
    activation: str = "gelu"
    weight_init: str = "xavier"
    router_noise: float = 0.0  # jitter std during training
    aux_loss_weight: float = 0.01

    @property
    def _ffn(self):
        return self.ffn_size or 4 * self.n_in

    def initialize(self, key, input_shape):
        kr, k1, k2 = jax.random.split(key, 3)
        e, h, f = self.n_experts, self.n_in, self._ffn
        init_each = lambda k, shape: jnp.stack([
            winit.init(kk, self.weight_init, shape)
            for kk in jax.random.split(k, e)
        ])
        return {
            "router": winit.init(kr, self.weight_init, (h, e)),
            "W1": init_each(k1, (h, f)),
            "b1": jnp.zeros((e, f)),
            "W2": init_each(k2, (f, h)),
            "b2": jnp.zeros((e, h)),
        }, {}

    # -- routing ------------------------------------------------------------
    def _gates(self, params, x2d, training, key):
        logits = x2d @ params["router"]  # (N, E)
        if training and self.router_noise > 0.0 and key is not None:
            logits = logits + self.router_noise * jax.random.normal(
                key, logits.shape, logits.dtype)
        if self.top_k < self.n_experts:
            # top_k indices + one-hot mask guarantees EXACTLY top_k experts
            # even under tied logits (e.g. a zero-init router)
            _, idx = lax.top_k(logits, self.top_k)  # (N, k)
            keep = jax.nn.one_hot(idx, self.n_experts,
                                  dtype=jnp.bool_).any(axis=-2)  # (N, E)
            logits = jnp.where(keep, logits, -jnp.inf)
        gates = jax.nn.softmax(logits, axis=-1)  # zero where masked
        return gates, logits

    def _expert_partial(self, params, x2d, gates, e_offset=0, constrain=None):
        """Weighted sum over THIS param shard's experts (EP body).
        ``constrain``: optional hook applied to the expert-leading
        intermediates — ``expert_parallel`` passes a sharding constraint so
        the partitioner keeps the expert axis distributed."""
        fn = act.resolve(self.activation)
        hidden = fn(jnp.einsum("nh,ehf->enf", x2d, params["W1"])
                    + params["b1"][:, None])
        if constrain is not None:
            hidden = constrain(hidden)
        out = jnp.einsum("enf,efh->enh", hidden, params["W2"]) \
            + params["b2"][:, None]
        if constrain is not None:
            out = constrain(out)
        local_e = params["W1"].shape[0]
        g = lax.dynamic_slice_in_dim(gates, e_offset, local_e, axis=1)
        return jnp.einsum("ne,enh->nh", g.astype(out.dtype), out)

    def apply(self, params, state, x, *, training=False, key=None, mask=None):
        kd = kr = None
        if key is not None:
            kd, kr = jax.random.split(key)  # independent dropout/router noise
        x = self._maybe_dropout(x, training, kd)
        shape = x.shape
        x2d = x.reshape(-1, shape[-1])
        gates, _ = self._gates(params, x2d, training, kr)
        y = self._expert_partial(params, x2d, gates)
        return y.reshape(shape), state

    def aux_loss(self, params, x, training=False, key=None):
        """Switch-style load-balancing loss: E * sum_e f_e * p_e, where f_e is
        the fraction of tokens whose top choice is e and p_e the mean gate."""
        x2d = x.reshape(-1, x.shape[-1])
        gates, logits = self._gates(params, x2d, training, key)
        probs = jax.nn.softmax(x2d @ params["router"], axis=-1)
        top1 = jax.nn.one_hot(jnp.argmax(logits, -1), self.n_experts)
        f = jnp.mean(top1, axis=0)
        p = jnp.mean(probs, axis=0)
        return self.aux_loss_weight * self.n_experts * jnp.sum(f * p)

    def output_shape(self, input_shape):
        return tuple(input_shape)


def expert_parallel(layer: MixtureOfExperts, params, x, mesh: Mesh,
                    axis_name: str = "model"):
    """Run the MoE layer with experts sharded over ``axis_name``, expressed
    as GSPMD (no per-device mapped functions — ROADMAP item 1): the expert-stacked param
    leaves are annotated ``PartitionSpec(axis_name)`` on their expert axis,
    the router stays replicated (tiny), and sharding constraints keep the
    ``enf``/``enh`` intermediates distributed — the final gate-weighted sum
    over the expert axis is where the partitioner inserts the EP
    all-reduce. Numerically identical to ``layer.apply``."""
    m = mesh.shape[axis_name]
    if layer.n_experts % m:
        raise ValueError(f"n_experts={layer.n_experts} not divisible by "
                         f"mesh axis {axis_name}={m}")
    return _expert_parallel_program(layer, mesh, axis_name)(params, x)


@functools.lru_cache(maxsize=64)
def _expert_parallel_program(layer: MixtureOfExperts, mesh: Mesh,
                             axis_name: str):
    from jax.sharding import NamedSharding

    espec = NamedSharding(mesh, P(axis_name))  # expert axis leads each leaf
    rep = NamedSharding(mesh, P())
    pspec = {
        "router": rep, "W1": espec, "b1": espec, "W2": espec, "b2": espec,
    }

    def constrain(t):
        # intermediates are [e, n, ...]: keep the expert axis distributed
        return jax.lax.with_sharding_constraint(
            t, NamedSharding(mesh, P(axis_name)))

    def run(params, x):
        x2d = x.reshape(-1, x.shape[-1])
        gates, _ = layer._gates(params, x2d, False, None)  # router replicated
        y = layer._expert_partial(params, x2d, gates, constrain=constrain)
        return y.reshape(x.shape)

    return jax.jit(run, in_shardings=(pspec, rep))


# ---------------------------------------------------------------------------
# Grouped dispatch: many experts, a slice of them held here
# ---------------------------------------------------------------------------

#: what :func:`grouped_experts` counts, in this order
MOE_STATS = ("picks", "picks_local", "experts_touched", "expert_load_max")


def route_sigmoid_topk(x2d, router, bias, top_k: int, scale: float):
    """Sigmoid router with a selection bias: ``s = sigmoid(x W_r)`` over ALL
    experts in float32 at full precision (a pick is discrete: rounding the
    product would move picks that the input does not); the ``top_k`` are the largest of ``s + bias`` (the
    bias only selects); weights ``s_i / sum_k s * scale``. Returns (idx
    (N, k) int32, weights (N, k) float32)."""
    s = jax.nn.sigmoid(jnp.dot(x2d, router, precision=lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32))
    _, idx = lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    return idx, w / jnp.sum(w, axis=-1, keepdims=True) * scale


def _pass_rows(n_pairs: int, n_local: int, n_experts: int) -> int:
    """Rows of one grouped pass: twice what a uniform router sends to the
    experts held here, in whole 128s, at most every pick."""
    fair = -(-n_pairs * n_local // n_experts)
    return min(n_pairs, max(128, -(-2 * fair // 128) * 128))


def grouped_experts(x2d, idx, weights, w_gate, w_up, w_down, *,
                    e_offset: int, n_experts: int, live=None):
    """The gated-SiLU experts held here, applied to the picks that name
    them: ``y_n = sum over picks (n, e) with e_offset <= e < e_offset + E_l
    of w * W_down,e (SiLU(x W_gate,e) * x W_up,e)``.

    ``x2d`` (N, H); ``idx``/``weights`` (N, k) from the router over all
    ``n_experts``; ``w_gate``/``w_up`` (E_l, H, F), ``w_down`` (E_l, F, H).
    ``live`` (N,) bool: tokens that count (padding and finished rows are
    routed nowhere). Returns (y (N, H) float32, stats (4,) int32 as
    :data:`MOE_STATS`: picks of live tokens, those that name an expert held
    here, experts here with at least one pick, the most picks one expert
    here got)."""
    n, k = idx.shape
    e_l = w_gate.shape[0]
    local = (idx >= e_offset) & (idx < e_offset + e_l)
    if live is not None:
        local = local & live[:, None]
    key = jnp.where(local, idx - e_offset, e_l).reshape(-1)      # (N*k,)
    order = jnp.argsort(key).astype(jnp.int32)     # picks here first, by expert
    counts = jnp.sum(jax.nn.one_hot(key, e_l + 1, dtype=jnp.int32),
                     axis=0)[:e_l]                               # (E_l,)
    ends = jnp.cumsum(counts)
    n_here = ends[-1]
    rows = _pass_rows(n * k, e_l, n_experts)
    order = jnp.pad(order, (0, rows))              # the last pass may overhang
    w_flat = weights.reshape(-1)

    def one_pass(c, y):
        lo = c * rows
        pick = lax.dynamic_slice_in_dim(order, lo, rows)
        tok = pick // k
        sizes = jnp.clip(ends, lo, lo + rows) \
            - jnp.clip(ends - counts, lo, lo + rows)
        xs = x2d[tok]
        dot = lambda a, b: lax.ragged_dot(
            a, b, sizes, preferred_element_type=jnp.float32)
        hid = (jax.nn.silu(dot(xs, w_gate)) * dot(xs, w_up)).astype(
            x2d.dtype)
        out = dot(hid, w_down)
        valid = lo + jnp.arange(rows) < n_here
        out = jnp.where(valid[:, None], out * w_flat[pick][:, None], 0.0)
        return y.at[tok].add(out)

    y = lax.fori_loop(0, -(-n_here // rows), one_pass,
                      jnp.zeros(x2d.shape, jnp.float32))
    n_live = n if live is None else jnp.sum(live)
    stats = jnp.stack([n_live * k, n_here, jnp.sum(counts > 0),
                       jnp.max(counts)]).astype(jnp.int32)
    return y, stats
