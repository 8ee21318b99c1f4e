"""Search-space registry: existing seams declare their tunable knobs as
typed candidate sets.

Five eras of perf work each ended with "CPU proves equivalence but cannot
rank" (docs/KERNELS.md, FUSION_TUNING.md, DISTRIBUTED.md): the repo has
accumulated deferred perf decisions with no machinery to close them. This
module is the declaration side of that machinery (TVM's schedule space,
arXiv:1802.04799 §4): each :class:`SearchSpace` names one seam, emits its
typed candidates for a concrete workload context, guards each candidate
with the seam's own validity checks (tile divides shape, VMEM fit), and
builds the measurable case (reference outputs + candidate outputs + a
timed runner) the driver in ``tuning/measure.py`` sweeps.

Registered spaces:

- ``conv2d_tiles`` / ``lstm_tiles`` — MEASURABLE. The Pallas kernel tile
  shapes (``row_tile`` / ``b_tile``, ops/kernels/) *plus* the exact path
  as candidate ``exact``: the winner record's ``impl`` field IS the
  per-(op, shape, dtype) ``kernel_impl`` decision the cuDNN paper frames
  as algorithm selection (arXiv:1410.0759 §3), subsumed by tile search.
- ``remat_policy`` — MEASURABLE (conf scope). Rides
  ``util/xla_tuning.register_policy``: every registered policy name is a
  candidate, measured on a small conv net's jitted train step; the winner
  lands under the reserved ``conf-default`` signature consulted by the
  conf builders.
- ``prefill_chunk`` — MEASURABLE (conf scope). Chunked-prefill window
  width for the paged decode engine (serving/generate.py); equivalence
  gate is generated-token identity (tolerance 0) so a chunk width that
  perturbs decode output can never win; the latency trade it ranks is
  decode-lane HOL blocking vs whole-prompt dispatch amortization.
- ``xla_flags`` — DECLARED. Candidates from
  ``xla_tuning.XLA_FLAG_CANDIDATES``; flags are process-global and abort
  XLA when unknown, so measurement belongs to the subprocess harness
  (``benchmarks/fusion_sweep.py``), not the in-process driver.
- ``bucket_sets`` — DECLARED. Candidate bucket specs for ragged
  workloads; ranking needs the workload's real length distribution
  (``benchmarks/autotune.py --space bucket_sets`` on a recorded stream).
- ``compression_hosts`` — DECLARED. Hierarchical-compression host counts
  (parallel/compression.py); unrankable without real DCN, the standing
  first-TPU-session harvest (docs/DISTRIBUTED.md honesty note).

Declared spaces still enumerate and key — the database schema covers
them, the first real-chip session measures them.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

from deeplearning4j_tpu.tuning.database import TuningKey


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One point in a search space: a dispatch choice (``impl``) plus its
    typed parameters. ``label`` is the stable human/database name."""

    label: str
    impl: str = "exact"            # "exact" | "pallas" | knob-specific
    params: Dict[str, Any] = dataclasses.field(default_factory=dict)
    is_default: bool = False

    def as_dict(self) -> dict:
        return {"label": self.label, "impl": self.impl,
                "params": dict(self.params),
                "is_default": self.is_default}


class MeasureCase:
    """One concrete workload built by a space: the reference outputs, a
    per-candidate output function (for the equivalence gate), and a
    per-candidate timed runner (one call = one measured execution,
    blocked to completion)."""

    def __init__(self, *, reference: Callable[[], Any],
                 outputs: Callable[[Candidate], Any],
                 timer: Callable[[Candidate], Callable[[], None]],
                 tolerance: float):
        self.reference = reference
        self.outputs = outputs
        self.timer = timer
        self.tolerance = tolerance


class SearchSpace:
    """Base declaration. Subclasses override the class attributes and the
    four methods; ``measurable=False`` spaces only declare (enumerate +
    key) and state what measuring them ``requires``."""

    name: str = ""
    op: str = ""                   # database key op
    scope: str = "op"              # "op" (shape-keyed) | "conf"
    measurable: bool = True
    requires: str = ""             # why a declared space cannot measure here
    tolerance: float = 1e-5        # per-seam equivalence bound (abs, fp32)

    def signature(self, ctx: dict) -> str:
        raise NotImplementedError

    def dtype(self, ctx: dict) -> str:
        return str(ctx.get("dtype", "float32"))

    def key(self, ctx: dict) -> TuningKey:
        return TuningKey.for_op(self.op, self.signature(ctx),
                                self.dtype(ctx))

    def enumerate(self, ctx: dict) -> List[Candidate]:
        raise NotImplementedError

    def validate(self, cand: Candidate, ctx: dict) -> Tuple[bool, str]:
        """Validated-shape guard: (ok, reason). Invalid candidates are
        recorded as skipped, never measured."""
        return True, ""

    def neighbors(self, cand: Candidate, ctx: dict) -> List[Candidate]:
        """Adjacent candidates for greedy refinement (random search mode);
        default none."""
        return []

    def build(self, ctx: dict) -> MeasureCase:
        raise NotImplementedError(
            f"space {self.name!r} is declared, not measurable here"
            + (f" (requires {self.requires})" if self.requires else ""))

    def default_contexts(self) -> List[dict]:
        """The workload contexts ``benchmarks/autotune.py`` sweeps when
        the user names no explicit shapes — the repo's hot-path
        geometries, kept tiny on CPU (the machinery proof) and meaningful
        on the chip."""
        return []


# ---------------------------------------------------------------- registry
_REGISTRY: Dict[str, SearchSpace] = {}


def register_space(space: SearchSpace) -> SearchSpace:
    """Declare a knob space (idempotent by name; re-registering replaces,
    the ``xla_tuning.register_policy`` convention)."""
    if not space.name:
        raise ValueError("search space needs a name")
    _REGISTRY[space.name] = space
    return space


def get_space(name: str) -> SearchSpace:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown search space {name!r}; known: {space_names()}"
        ) from None


def space_names() -> List[str]:
    return sorted(_REGISTRY)


def measurable_spaces() -> List[str]:
    return sorted(n for n, s in _REGISTRY.items() if s.measurable)


# ----------------------------------------------------- conv2d tile space
class ConvTileSpace(SearchSpace):
    """Pallas conv2d forward row tiles + the exact path, per conv
    geometry — the first registrable space (ISSUE 11; parameterized in
    ops/kernels/conv.py). Context: ``{"x_shape", "w_shape", "strides",
    "padding", "dilation", "groups", "dtype"}``."""

    name = "conv2d_tiles"
    op = "conv2d"
    tolerance = 2e-4   # docs/KERNELS.md conv fwd/grad bound (fp32, CPU)

    def _geom(self, ctx):
        from deeplearning4j_tpu.ops.kernels import conv as kconv

        x_shape = tuple(ctx["x_shape"])
        w_shape = tuple(ctx["w_shape"])
        strides = tuple(ctx.get("strides", (1, 1)))
        dilation = tuple(ctx.get("dilation", (1, 1)))
        groups = int(ctx.get("groups", 1))
        pads = kconv.resolve_padding(
            ctx.get("padding", "SAME"), x_shape[1:3], w_shape[:2], strides,
            dilation)
        # kconv._out_size is the ONE output-size formula (shared with the
        # kernels) — no second inline copy to drift
        oh = kconv._out_size(x_shape[1], pads[0], w_shape[0], strides[0],
                             dilation[0])
        return x_shape, w_shape, strides, dilation, groups, pads, oh

    def signature(self, ctx: dict) -> str:
        from deeplearning4j_tpu.ops.kernels import conv as kconv

        x_shape, w_shape, strides, dilation, groups, _, _ = self._geom(ctx)
        # ONE signature builder shared with the dispatch site (ops/nn.py)
        return kconv.shape_signature(x_shape, w_shape, strides,
                                     ctx.get("padding", "SAME"), dilation,
                                     groups)

    def enumerate(self, ctx: dict) -> List[Candidate]:
        from deeplearning4j_tpu.ops.kernels import conv as kconv

        _, _, _, _, _, _, oh = self._geom(ctx)
        out = [Candidate("exact", impl="exact", is_default=True)]
        for rt in kconv.valid_row_tiles(oh):
            label = "pallas:rt=whole" if rt is None else f"pallas:rt={rt}"
            out.append(Candidate(label, impl="pallas",
                                 params={"row_tile": rt}))
        return out

    def validate(self, cand: Candidate, ctx: dict) -> Tuple[bool, str]:
        from deeplearning4j_tpu.ops.kernels import conv as kconv

        if cand.impl == "exact":
            return True, ""
        oh = self._geom(ctx)[-1]
        rt = cand.params.get("row_tile")
        if not kconv.valid_row_tile(oh, rt):
            return False, f"row_tile {rt} does not divide OH={oh}"
        return True, ""

    def neighbors(self, cand: Candidate, ctx: dict) -> List[Candidate]:
        if cand.impl != "pallas":
            return []
        all_c = [c for c in self.enumerate(ctx) if c.impl == "pallas"]
        tiles = [c.params.get("row_tile") for c in all_c]
        try:
            i = tiles.index(cand.params.get("row_tile"))
        except ValueError:
            return []
        return [all_c[j] for j in (i - 1, i + 1) if 0 <= j < len(all_c)]

    def build(self, ctx: dict) -> MeasureCase:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from deeplearning4j_tpu.ops.kernels import conv as kconv

        x_shape, w_shape, strides, dilation, groups, pads, _ = \
            self._geom(ctx)
        dtype = jnp.dtype(self.dtype(ctx))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=x_shape), dtype)
        w = jnp.asarray(rng.normal(size=w_shape) * 0.1, dtype)
        interpret = jax.default_backend() != "tpu"

        def loss_of(conv_fn):
            def loss(x, w):
                return jnp.sum(jnp.sin(conv_fn(x, w)))
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))

        def exact_conv(x, w):
            from jax import lax

            dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                            ("NHWC", "HWIO", "NHWC"))
            return lax.conv_general_dilated(
                x, w, window_strides=strides,
                padding=[tuple(p) for p in pads], rhs_dilation=dilation,
                dimension_numbers=dn,
                feature_group_count=groups).astype(x.dtype)

        def fn_for(cand: Candidate):
            if cand.impl == "exact":
                return loss_of(exact_conv)
            rt = cand.params.get("row_tile")
            return loss_of(lambda x, w: kconv.conv2d_pallas(
                x, w, strides, pads, dilation, groups, interpret, rt))

        def outputs(cand: Candidate):
            v, (gx, gw) = fn_for(cand)(x, w)
            return (v, gx, gw)

        def timer(cand: Candidate):
            f = fn_for(cand)

            def run_once():
                v, (gx, gw) = f(x, w)
                jax.block_until_ready((v, gx, gw))

            return run_once

        return MeasureCase(
            reference=lambda: outputs(Candidate("exact", impl="exact")),
            outputs=outputs, timer=timer, tolerance=self.tolerance)

    def default_contexts(self) -> List[dict]:
        import jax

        tiny = jax.default_backend() != "tpu"
        if tiny:  # machinery proof: small enough for the CPU interpreter
            return [
                {"x_shape": (2, 16, 16, 8), "w_shape": (3, 3, 8, 16),
                 "strides": (1, 1), "padding": "SAME",
                 "dilation": (1, 1), "groups": 1, "dtype": "float32"},
                {"x_shape": (2, 16, 16, 8), "w_shape": (3, 3, 8, 16),
                 "strides": (2, 2), "padding": "SAME",
                 "dilation": (1, 1), "groups": 1, "dtype": "float32"},
            ]
        # the flagship hot shapes (zoo ResNet-50 stem + res3) — the first
        # real-chip harvest measures what training actually runs
        return [
            {"x_shape": (32, 56, 56, 64), "w_shape": (3, 3, 64, 64),
             "strides": (1, 1), "padding": "SAME", "dilation": (1, 1),
             "groups": 1, "dtype": "bfloat16"},
            {"x_shape": (32, 28, 28, 128), "w_shape": (3, 3, 128, 128),
             "strides": (1, 1), "padding": "SAME", "dilation": (1, 1),
             "groups": 1, "dtype": "bfloat16"},
        ]


# ------------------------------------------------------ lstm tile space
class LstmTileSpace(SearchSpace):
    """Fused LSTM cell batch tiles + the exact scan, per (B, H, T)
    geometry (ops/kernels/lstm.py). Context: ``{"batch", "hidden",
    "timesteps", "dtype"}``."""

    name = "lstm_tiles"
    op = "lstm_cell"
    tolerance = 1e-4   # docs/KERNELS.md LSTM trajectory bound (fp32)

    def signature(self, ctx: dict) -> str:
        from deeplearning4j_tpu.ops.kernels import lstm as klstm

        # (B, H) only: the per-step kernel is T-independent, so a winner
        # measured at one sequence length serves every scan (ONE builder
        # shared with the dispatch sites in nn/recurrent.py + ops/rnn.py)
        return klstm.shape_signature(int(ctx["batch"]), int(ctx["hidden"]))

    def enumerate(self, ctx: dict) -> List[Candidate]:
        from deeplearning4j_tpu.ops.kernels import lstm as klstm

        out = [Candidate("exact", impl="exact", is_default=True)]
        for bt in klstm.valid_b_tiles(int(ctx["batch"])):
            label = "pallas:bt=whole" if bt is None else f"pallas:bt={bt}"
            out.append(Candidate(label, impl="pallas",
                                 params={"b_tile": bt}))
        return out

    def validate(self, cand: Candidate, ctx: dict) -> Tuple[bool, str]:
        from deeplearning4j_tpu.ops.kernels import lstm as klstm

        if cand.impl == "exact":
            return True, ""
        b = int(ctx["batch"])
        bt = cand.params.get("b_tile")
        if not klstm.valid_b_tile(b, bt):
            return False, f"b_tile {bt} does not divide B={b}"
        return True, ""

    def neighbors(self, cand: Candidate, ctx: dict) -> List[Candidate]:
        if cand.impl != "pallas":
            return []
        all_c = [c for c in self.enumerate(ctx) if c.impl == "pallas"]
        tiles = [c.params.get("b_tile") for c in all_c]
        try:
            i = tiles.index(cand.params.get("b_tile"))
        except ValueError:
            return []
        return [all_c[j] for j in (i - 1, i + 1) if 0 <= j < len(all_c)]

    def build(self, ctx: dict) -> MeasureCase:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from deeplearning4j_tpu.ops.kernels import lstm as klstm

        b, h = int(ctx["batch"]), int(ctx["hidden"])
        t = int(ctx.get("timesteps", 8))
        dtype = jnp.dtype(self.dtype(ctx))
        rng = np.random.default_rng(0)
        xp = jnp.asarray(rng.normal(size=(t, b, 4 * h)) * 0.3, dtype)
        h0 = jnp.zeros((b, h), dtype)
        c0 = jnp.zeros((b, h), dtype)
        u = jnp.asarray(rng.normal(size=(h, 4 * h)) * 0.1, dtype)
        mode = "pallas" if jax.default_backend() == "tpu" else "interpret"

        def seq_for(cand: Candidate):
            if cand.impl == "exact":
                def seq(xp, u):
                    return klstm.lstm_sequence_exact(xp, h0, c0, u)
            else:
                bt = cand.params.get("b_tile")

                def seq(xp, u, bt=bt):
                    ys, _ = klstm.lstm_sequence_fused(
                        xp, h0, c0, u, klstm.ORDER_IFOG, mode, bt)
                    return ys

            def loss(xp, u):
                return jnp.sum(jnp.cos(seq(xp, u)))

            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))

        def outputs(cand: Candidate):
            v, (gx, gu) = seq_for(cand)(xp, u)
            return (v, gx, gu)

        def timer(cand: Candidate):
            f = seq_for(cand)

            def run_once():
                out = f(xp, u)
                jax.block_until_ready(out)

            return run_once

        return MeasureCase(
            reference=lambda: outputs(Candidate("exact", impl="exact")),
            outputs=outputs, timer=timer, tolerance=self.tolerance)

    def default_contexts(self) -> List[dict]:
        import jax

        if jax.default_backend() != "tpu":
            return [{"batch": 8, "hidden": 16, "timesteps": 6,
                     "dtype": "float32"}]
        return [{"batch": 128, "hidden": 512, "timesteps": 64,
                 "dtype": "float32"}]


# --------------------------------------------------- remat policy space
class RematPolicySpace(SearchSpace):
    """Selective-remat policy for the jitted train step (conf scope,
    riding ``util/xla_tuning.register_policy`` — every registered name is
    a candidate, including user-registered ones). Measured on a small
    conv net's whole ``_fit_batch``; equivalence = k-step loss trajectory
    within the fp32 reassociation bound (remat recomputes, it must not
    change math). Winner lands under the reserved ``conf-default``
    signature consulted by the conf builders at build() time."""

    name = "remat_policy"
    op = "remat_policy"
    scope = "conf"
    tolerance = 5e-4   # fp32 trajectory wobble over k steps (FMA folds)

    def signature(self, ctx: dict) -> str:
        return "conf-default"

    def dtype(self, ctx: dict) -> str:
        return "any"

    def enumerate(self, ctx: dict) -> List[Candidate]:
        from deeplearning4j_tpu.util import xla_tuning

        out = []
        for name in xla_tuning.policy_names():
            out.append(Candidate(
                f"policy:{name}", impl="conf",
                params={"remat_policy": None if name == "none" else name},
                is_default=(name == "none")))
        return out

    def _make_net(self, seed: int = 7):
        from deeplearning4j_tpu.nn import layers as L
        from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        from deeplearning4j_tpu.nn.updaters import Sgd

        def build(policy):
            conf = (NeuralNetConfiguration.builder()
                    .seed(seed).updater(Sgd(0.05))
                    .list()
                    .layer(L.ConvolutionLayer(n_out=8, kernel_size=(3, 3)))
                    .stage_boundary()
                    .layer(L.ConvolutionLayer(n_out=8, kernel_size=(3, 3)))
                    .stage_boundary()
                    .layer(L.DenseLayer(n_out=16))
                    .layer(L.OutputLayer(n_out=4, loss="mcxent",
                                         activation="softmax"))
                    .set_input_type((12, 12, 3))
                    .build())
            conf.remat_policy = policy
            net = MultiLayerNetwork(conf)
            net.init()
            return net

        return build

    def build(self, ctx: dict) -> MeasureCase:
        import jax
        import numpy as np

        steps = int(ctx.get("steps", 3))
        rng = np.random.default_rng(3)
        x = np.asarray(rng.normal(size=(8, 12, 12, 3)), np.float32)
        y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, size=8)]
        build = self._make_net()

        def trajectory(cand: Candidate):
            net = build(cand.params.get("remat_policy"))
            for _ in range(steps):
                net._fit_batch(x, y)
            return float(net.score_value)

        nets = {}

        def net_for(cand: Candidate):
            if cand.label not in nets:
                net = build(cand.params.get("remat_policy"))
                for _ in range(2):          # warm past the trace
                    net._fit_batch(x, y)
                float(net.score_value)
                nets[cand.label] = net
            return nets[cand.label]

        def timer(cand: Candidate):
            net = net_for(cand)

            def run_once():
                net._fit_batch(x, y)
                float(net.score_value)

            return run_once

        def outputs(cand: Candidate):
            return (trajectory(cand),)

        return MeasureCase(
            reference=lambda: outputs(
                Candidate("policy:none", impl="conf",
                          params={"remat_policy": None})),
            outputs=outputs, timer=timer, tolerance=self.tolerance)

    def default_contexts(self) -> List[dict]:
        return [{"steps": 3}]


# ------------------------------------------------- declared-only spaces
class XlaFlagsSpace(SearchSpace):
    """XLA flag candidates (util/xla_tuning.XLA_FLAG_CANDIDATES). Flags
    are process-global and unknown flags ABORT XLA at client init, so the
    in-process driver must not measure them — ``benchmarks/
    fusion_sweep.py`` is the subprocess harness; commit its winner by
    hand as a ``TuningDatabase.commit`` entry under this space's key
    (op=xla_flags, sig=conf-default — see docs/AUTOTUNE.md), the schema
    a future importer flag would also write."""

    name = "xla_flags"
    op = "xla_flags"
    scope = "conf"
    measurable = False
    requires = "subprocess isolation (benchmarks/fusion_sweep.py)"

    def signature(self, ctx: dict) -> str:
        return "conf-default"

    def dtype(self, ctx: dict) -> str:
        return "any"

    def enumerate(self, ctx: dict) -> List[Candidate]:
        from deeplearning4j_tpu.util import xla_tuning

        out = [Candidate("flags:none", impl="conf",
                         params={"xla_flags": ""}, is_default=True)]
        for name, flag in xla_tuning.XLA_FLAG_CANDIDATES:
            out.append(Candidate(name, impl="conf",
                                 params={"xla_flags": flag}))
        return out


class BucketSetSpace(SearchSpace):
    """Shape-bucket candidate sets (data/bucketing.py). Ranking needs the
    workload's real length distribution — pad-waste vs recompile-count is
    a property of the data, not the op — so this space declares the
    candidates and the key shape; ``benchmarks/autotune.py`` measures it
    against a recorded stream when one is provided."""

    name = "bucket_sets"
    op = "bucket_sets"
    scope = "conf"
    measurable = False
    requires = "a recorded ragged-length distribution (autotune.py --help)"

    def signature(self, ctx: dict) -> str:
        dist = ctx.get("length_histogram")
        if dist:
            return "hist=" + ",".join(f"{k}:{v}"
                                      for k, v in sorted(dist.items()))
        return "conf-default"

    def dtype(self, ctx: dict) -> str:
        return "any"

    def enumerate(self, ctx: dict) -> List[Candidate]:
        cands = [Candidate("buckets:pow2", impl="conf",
                           params={"batch_buckets": "pow2"},
                           is_default=True),
                 Candidate("buckets:8-16-32", impl="conf",
                           params={"batch_buckets": [8, 16, 32]}),
                 Candidate("buckets:16-64", impl="conf",
                           params={"batch_buckets": [16, 64]})]
        return cands


class CompressionHostsSpace(SearchSpace):
    """Hierarchical gradient-compression host counts
    (parallel/compression.py ``compression_hosts``): full-precision
    intra-host combines, encoded cross-host axis. Wire math is
    deterministic but wall-clock ranking needs real DCN — the standing
    first-TPU-session harvest (docs/DISTRIBUTED.md)."""

    name = "compression_hosts"
    op = "compression_hosts"
    scope = "conf"
    measurable = False
    requires = "real multi-host DCN (CPU cannot rank wire vs encode cost)"

    def signature(self, ctx: dict) -> str:
        return "conf-default"

    def dtype(self, ctx: dict) -> str:
        return "any"

    def enumerate(self, ctx: dict) -> List[Candidate]:
        out = [Candidate("hosts:auto", impl="conf",
                         params={"compression_hosts": "auto"},
                         is_default=True)]
        for n in (1, 2, 4):
            out.append(Candidate(f"hosts:{n}", impl="conf",
                                 params={"compression_hosts": n}))
        return out


class PipeScheduleSpace(SearchSpace):
    """Pipeline-schedule candidates for the 3D-parallel trainer
    (parallel/pipelined.py, docs/DISTRIBUTED.md#pipeline-parallelism):
    microbatch counts (the bubble-vs-activation-memory dial — bubble
    fraction (S-1)/(n_micro+S-1) shrinks as n_micro grows while live
    activations grow) × the schedule family (the implemented GPipe
    fill-drain scan vs a 1F1B interleave candidate). On this CPU the
    bubble is arithmetic, not wall-clock — CPU proves the schedules
    EQUIVALENT (trajectory tests) and computes their bubble fractions,
    but cannot rank bubble cost against per-microbatch dispatch overhead
    or remat recompute; and 1F1B's payoff is live-activation memory that
    only a real HBM budget prices. The first chip session measures steps
    of the real pipelined fit per candidate (1f1b additionally needs the
    interleaved variant implemented behind the same gpipe_scan seam)."""

    name = "pipe_schedule"
    op = "pipe_schedule"
    scope = "conf"
    measurable = False
    requires = ("real TPU wall-clock + HBM budget (CPU proves schedule "
                "equivalence and computes bubble fractions, cannot rank "
                "bubble vs dispatch/remat cost; 1f1b candidates also need "
                "the interleaved scan variant on chip)")

    def signature(self, ctx: dict) -> str:
        s = int(ctx.get("pipe_stages", 2))
        return f"stages={s}"

    def dtype(self, ctx: dict) -> str:
        return "any"

    def enumerate(self, ctx: dict) -> List[Candidate]:
        from deeplearning4j_tpu.parallel.pipeline import bubble_fraction

        s = int(ctx.get("pipe_stages", 2))
        out = []
        for sched in ("gpipe", "1f1b"):
            for mult in (1, 2, 4, 8):
                n_micro = s * mult
                out.append(Candidate(
                    f"{sched}:m{n_micro}", impl="conf",
                    params={"pipe_schedule": sched, "n_micro": n_micro,
                            "bubble_fraction": bubble_fraction(s, n_micro)},
                    is_default=(sched == "gpipe" and mult == 1)))
        return out


# --------------------------------------------------- prefill chunk space
class PrefillChunkSpace(SearchSpace):
    """Chunked-prefill window width for the paged decode engine
    (serving/generate.py ``prefill_chunk``, docs/SERVING.md#prefix-cache
    --chunked-prefill): how long prompts are sliced into fixed windows
    interleaved with decode batches. Small chunks bound decode-lane HOL
    blocking (Sarathi-style stall control); the whole-prompt prefill
    amortizes dispatch best. The equivalence gate is the serving
    contract itself — **generated-token identity** (tolerance 0: the
    chunked path must reproduce the whole-prompt path bit-for-bit), so a
    chunk width that perturbs decode can never win. Context:
    ``{"max_length", "prompt_len", "batch", "max_new"}``."""

    name = "prefill_chunk"
    op = "prefill_chunk"
    scope = "conf"
    tolerance = 0.0    # token IDs are integers: identity or rejection

    def signature(self, ctx: dict) -> str:
        return (f"maxlen={int(ctx.get('max_length', 64))}"
                f",prompt={int(ctx.get('prompt_len', 24))}")

    def dtype(self, ctx: dict) -> str:
        return "int32"

    def enumerate(self, ctx: dict) -> List[Candidate]:
        max_length = int(ctx.get("max_length", 64))
        out = [Candidate("chunk:whole", impl="conf",
                         params={"prefill_chunk": None}, is_default=True)]
        w = 4
        while w < max_length:
            out.append(Candidate(f"chunk:{w}", impl="conf",
                                 params={"prefill_chunk": w}))
            w *= 2
        return out

    def validate(self, cand: Candidate, ctx: dict) -> Tuple[bool, str]:
        w = cand.params.get("prefill_chunk")
        if w is None:
            return True, ""
        max_length = int(ctx.get("max_length", 64))
        if not 1 <= int(w) <= max_length:
            return False, f"chunk {w} outside [1, max_length={max_length}]"
        return True, ""

    def neighbors(self, cand: Candidate, ctx: dict) -> List[Candidate]:
        if cand.params.get("prefill_chunk") is None:
            return []
        all_c = [c for c in self.enumerate(ctx)
                 if c.params.get("prefill_chunk") is not None]
        widths = [c.params.get("prefill_chunk") for c in all_c]
        try:
            i = widths.index(cand.params.get("prefill_chunk"))
        except ValueError:
            return []
        return [all_c[j] for j in (i - 1, i + 1) if 0 <= j < len(all_c)]

    def build(self, ctx: dict) -> MeasureCase:
        import jax.numpy as jnp
        import numpy as np

        from deeplearning4j_tpu.serving.generate import Generator
        from deeplearning4j_tpu.zoo.bert import Bert

        max_length = int(ctx.get("max_length", 64))
        prompt_len = int(ctx.get("prompt_len", 24))
        batch = int(ctx.get("batch", 2))
        max_new = int(ctx.get("max_new", 8))
        vocab = 61
        net = Bert.tiny(causal=True, task="mlm", vocab_size=vocab,
                        max_length=max_length, hidden_dropout=0.0).init()
        rng = np.random.default_rng(11)
        prompts = [[int(t) for t in rng.integers(1, vocab, prompt_len)]
                   for _ in range(batch)]

        gens: Dict[str, Generator] = {}

        def gen_for(cand: Candidate) -> Generator:
            if cand.label not in gens:
                g = Generator(net, paged=True, block_size=4,
                              batch_buckets=(batch,),
                              prefill_buckets=(max_length,),
                              prefill_chunk=cand.params.get("prefill_chunk"))
                g.generate(prompts, max_new_tokens=max_new)  # warm the trace
                gens[cand.label] = g
            return gens[cand.label]

        def outputs(cand: Candidate):
            toks = gen_for(cand).generate(prompts, max_new_tokens=max_new)
            # pad ragged eos-exits to a fixed shape for the pytree diff
            arr = np.full((batch, max_new), -1, np.int32)
            for i, row in enumerate(toks):
                arr[i, :len(row)] = row
            return (jnp.asarray(arr),)

        def timer(cand: Candidate):
            g = gen_for(cand)

            def run_once():
                g.generate(prompts, max_new_tokens=max_new)

            return run_once

        return MeasureCase(
            reference=lambda: outputs(
                Candidate("chunk:whole", impl="conf",
                          params={"prefill_chunk": None})),
            outputs=outputs, timer=timer, tolerance=self.tolerance)

    def default_contexts(self) -> List[dict]:
        import jax

        if jax.default_backend() != "tpu":
            return [{"max_length": 32, "prompt_len": 20, "batch": 2,
                     "max_new": 4}]
        return [{"max_length": 2048, "prompt_len": 1536, "batch": 8,
                 "max_new": 32}]


# ------------------------------------------------- affinity head space
class AffinityHeadSpace(SearchSpace):
    """Prompt-head length the fleet router hashes for prefix-affinity
    routing (serving/fleet.py ``affinity_head``, env
    ``DL4J_TPU_AFFINITY_HEAD`` — docs/SERVING.md#fleet). The TVM framing
    (arXiv:1802.04799): a routing policy's free parameter is a search
    dimension, not a constant. The trade-off is real on both ends —
    head:0 disables affinity (pure least-loaded: best load spread, every
    worker cold-starts every prefix), a short head collapses distinct
    system prompts onto one worker (hot-spot risk), a long head splits
    requests that DO share a radix-cache prefix across workers (hit-rate
    loss). Ranking candidates needs a live multi-worker fleet under a
    representative shared-prefix traffic trace: the objective (fleet QPS
    at a latency bound, or aggregate ``prefix_cache_hit_rate`` ×
    load-stddev penalty) only exists at fleet scope, so the space is
    declared, not measurable in this process."""

    name = "affinity_head"
    op = "affinity_head"
    scope = "conf"
    measurable = False
    requires = ("a live multi-process fleet + representative shared-"
                "prefix traffic trace (the objective — fleet QPS / "
                "aggregate prefix hit rate vs load skew — only exists "
                "at fleet scope)")

    def signature(self, ctx: dict) -> str:
        n = int(ctx.get("n_workers", 2))
        return f"workers={n}"

    def dtype(self, ctx: dict) -> str:
        return "any"

    def enumerate(self, ctx: dict) -> List[Candidate]:
        from deeplearning4j_tpu.serving.fleet import DEFAULT_AFFINITY_HEAD

        out = [Candidate("head:0", impl="conf",
                         params={"affinity_head": 0})]  # no affinity
        for head in (4, 8, 16, 32, 64):
            out.append(Candidate(
                f"head:{head}", impl="conf",
                params={"affinity_head": head},
                is_default=head == DEFAULT_AFFINITY_HEAD))
        return out


# ------------------------------------------------------- default wiring
register_space(ConvTileSpace())
register_space(LstmTileSpace())
register_space(RematPolicySpace())
register_space(XlaFlagsSpace())
register_space(BucketSetSpace())
register_space(CompressionHostsSpace())
register_space(PipeScheduleSpace())
register_space(PrefillChunkSpace())
register_space(AffinityHeadSpace())
