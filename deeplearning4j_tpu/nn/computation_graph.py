"""ComputationGraph — arbitrary-DAG network with multiple inputs/outputs.

Reference parity: org/deeplearning4j/nn/graph/ComputationGraph.java plus its
config twin org/deeplearning4j/nn/conf/ComputationGraphConfiguration.java and
the GraphBuilder DSL (addInputs / addLayer / addVertex / setOutputs) —
path-cite, mount empty this round (SURVEY.md §2.2 J9).

TPU-native collapse: the reference walks `GraphVertex[] topologicalOrder`
twice per iteration (doForward, then doBackward with hand-written epsilons per
vertex) with a JNI crossing per op. Here the whole DAG — every branch, merge,
residual add, loss, reverse-mode gradient, and updater — traces into ONE jitted
XLA program per step; topological order exists only at Python trace time.

Parity notes:
- A layer node with several declared inputs gets an implicit feature-axis
  merge, exactly like the reference (ComputationGraphConfiguration auto-adds a
  MergeVertex).
- Training requires every configured output to be an OutputLayer/LossLayer
  (IOutputLayer in the reference); labels align with setOutputs order.
- fit accepts DataSet (single in/out) or MultiDataSet (lists).
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.data.bucketing import BucketingPolicy
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn import updaters as upd
from deeplearning4j_tpu.nn import vertices as V
from deeplearning4j_tpu.nn.conf import (_buckets_from_json, _buckets_to_json,
                                        _detuple)
from deeplearning4j_tpu.nn.multilayer import _dispatch_sig, _struct_of
from deeplearning4j_tpu.util import cost_model as cmod
from deeplearning4j_tpu.util import telemetry as tm
from deeplearning4j_tpu.util.compile_watcher import note_trace


@dataclasses.dataclass
class GraphNode:
    name: str
    node: Any  # Layer | GraphVertex
    inputs: List[str]

    @property
    def is_layer(self) -> bool:
        return isinstance(self.node, L.Layer)


@dataclasses.dataclass
class ComputationGraphConfiguration:
    """DAG description (ComputationGraphConfiguration.java parity)."""

    inputs: List[str]
    nodes: List[GraphNode]
    outputs: List[str]
    seed: int = 12345
    updater: Any = None
    input_shapes: Optional[List[Tuple[int, ...]]] = None  # excl. batch, per input
    compute_dtype: str = "float32"
    tbptt_length: int = 0  # >0: truncated-BPTT segment length (tBPTTLength)
    # Fusion-boundary engineering (util/xla_tuning.py): named selective-remat
    # policy, stage boundaries as node names (each named node ENDS a stage),
    # optional optimization barriers at the boundaries.
    remat_policy: Optional[str] = None
    remat_stages: Optional[Tuple[str, ...]] = None
    stage_barriers: bool = False
    # Sync-free step orchestration (docs/HOST_PIPELINE.md): coalesce the loss
    # fetch + TrainingListener dispatch into one host round-trip per window.
    sync_every: int = 1
    # Shape bucketing (docs/COMPILE_CACHE.md, data/bucketing.py): pad ragged
    # batches (and optionally the time axis) to a fixed bucket set so the
    # jitted step compiles once per bucket. None | "pow2" | explicit tuple.
    batch_buckets: Any = None
    seq_buckets: Any = None
    # Hot-path kernel engine + fused optimizer apply (docs/KERNELS.md):
    # same knobs as MultiLayerConfiguration.
    kernel_impl: Optional[str] = None
    fused_update: bool = False
    loss_scale: str = "none"
    loss_scale_value: float = 2.0 ** 15
    loss_scale_growth: int = 2000
    # Encoded gradient collectives (parallel/compression.py): same knobs as
    # MultiLayerConfiguration.
    grad_compression: str = "none"
    grad_compression_threshold: float = 1e-3
    grad_compression_target: float = 1e-3
    # Pipeline parallelism (parallel/pipelined.py): same knobs as
    # MultiLayerConfiguration — stage boundaries come from the graph
    # builder's stage_boundary() node names.
    pipe_stages: int = 0
    n_micro: int = 0

    # -- serialization (JSON round-trip is a tested invariant) ---------------
    def to_json(self) -> str:
        return json.dumps(
            {
                "inputs": self.inputs,
                "outputs": self.outputs,
                "seed": self.seed,
                "updater": self.updater.to_dict() if self.updater else None,
                "input_shapes": [list(s) for s in self.input_shapes]
                if self.input_shapes
                else None,
                "compute_dtype": self.compute_dtype,
                "tbptt_length": self.tbptt_length,
                "remat_policy": self.remat_policy,
                "remat_stages": list(self.remat_stages)
                if self.remat_stages else None,
                "stage_barriers": self.stage_barriers,
                "sync_every": self.sync_every,
                "batch_buckets": _buckets_to_json(self.batch_buckets),
                "seq_buckets": _buckets_to_json(self.seq_buckets),
                "kernel_impl": self.kernel_impl,
                "fused_update": self.fused_update,
                "loss_scale": self.loss_scale,
                "loss_scale_value": self.loss_scale_value,
                "loss_scale_growth": self.loss_scale_growth,
                "grad_compression": self.grad_compression,
                "grad_compression_threshold": self.grad_compression_threshold,
                "grad_compression_target": self.grad_compression_target,
                "pipe_stages": self.pipe_stages,
                "n_micro": self.n_micro,
                "nodes": [
                    {
                        "name": n.name,
                        "inputs": n.inputs,
                        "node": n.node.to_dict(),
                    }
                    for n in self.nodes
                ],
            },
            indent=2,
        )

    @staticmethod
    def from_json(s: str) -> "ComputationGraphConfiguration":
        d = json.loads(s)

        def denode(nd):
            if "@layer" in nd:
                nd = dict(nd)
                for k, v in list(nd.items()):
                    if isinstance(v, list):
                        nd[k] = _detuple(v)
                    if k == "updater" and isinstance(v, dict):
                        nd[k] = upd.updater_from_dict(v)
                return L.layer_from_dict(nd)
            return V.vertex_from_dict(nd)

        return ComputationGraphConfiguration(
            inputs=list(d["inputs"]),
            outputs=list(d["outputs"]),
            seed=d["seed"],
            updater=upd.updater_from_dict(d["updater"]) if d["updater"] else None,
            input_shapes=[tuple(s) for s in d["input_shapes"]]
            if d["input_shapes"]
            else None,
            compute_dtype=d.get("compute_dtype", "float32"),
            tbptt_length=d.get("tbptt_length", 0),
            remat_policy=d.get("remat_policy"),
            remat_stages=tuple(d["remat_stages"])
            if d.get("remat_stages") else None,
            stage_barriers=d.get("stage_barriers", False),
            sync_every=d.get("sync_every", 1),
            batch_buckets=_buckets_from_json(d.get("batch_buckets")),
            seq_buckets=_buckets_from_json(d.get("seq_buckets")),
            kernel_impl=d.get("kernel_impl"),
            fused_update=d.get("fused_update", False),
            loss_scale=d.get("loss_scale", "none"),
            loss_scale_value=d.get("loss_scale_value", 2.0 ** 15),
            loss_scale_growth=d.get("loss_scale_growth", 2000),
            grad_compression=d.get("grad_compression", "none"),
            grad_compression_threshold=d.get("grad_compression_threshold",
                                             1e-3),
            grad_compression_target=d.get("grad_compression_target", 1e-3),
            pipe_stages=d.get("pipe_stages", 0),
            n_micro=d.get("n_micro", 0),
            nodes=[
                GraphNode(n["name"], denode(n["node"]), list(n["inputs"]))
                for n in d["nodes"]
            ],
        )

    def topological_order(self) -> List[GraphNode]:
        """Kahn's algorithm over the node list (GraphIndices parity)."""
        by_name = {n.name: n for n in self.nodes}
        indeg = {
            n.name: sum(1 for i in n.inputs if i in by_name) for n in self.nodes
        }
        for n in self.nodes:
            for i in n.inputs:
                if i not in by_name and i not in self.inputs:
                    raise ValueError(f"node {n.name!r} consumes unknown input {i!r}")
        ready = [n for n in self.nodes if indeg[n.name] == 0]
        order: List[GraphNode] = []
        consumers: Dict[str, List[str]] = {}
        for n in self.nodes:
            for i in n.inputs:
                consumers.setdefault(i, []).append(n.name)
        while ready:
            n = ready.pop(0)
            order.append(n)
            for cname in consumers.get(n.name, ()):  # noqa: B905
                indeg[cname] -= 1
                if indeg[cname] == 0:
                    ready.append(by_name[cname])
        if len(order) != len(self.nodes):
            raise ValueError("graph has a cycle")
        return order


class GraphBuilder:
    """Fluent DSL (ComputationGraphConfiguration.GraphBuilder parity)."""

    def __init__(self, parent=None):
        self._p = parent  # nn.conf.Builder carrying global settings
        self._inputs: List[str] = []
        self._nodes: List[GraphNode] = []
        self._outputs: List[str] = []
        self._input_shapes: Optional[List[tuple]] = None
        self._tbptt: Optional[int] = None
        self._stage_ends: List[str] = []

    def add_inputs(self, *names: str) -> "GraphBuilder":
        self._inputs.extend(names)
        return self

    def add_layer(self, name: str, layer: L.Layer, *inputs: str) -> "GraphBuilder":
        self._nodes.append(GraphNode(name, layer, list(inputs)))
        return self

    def add_vertex(self, name: str, vertex: V.GraphVertex, *inputs: str) -> "GraphBuilder":
        self._nodes.append(GraphNode(name, vertex, list(inputs)))
        return self

    def set_outputs(self, *names: str) -> "GraphBuilder":
        self._outputs = list(names)
        return self

    def set_input_types(self, *shapes) -> "GraphBuilder":
        self._input_shapes = [tuple(s) for s in shapes]
        return self

    def tbptt_length(self, k: int) -> "GraphBuilder":
        """Truncated-BPTT segment length (backpropType(TruncatedBPTT) +
        tBPTT{Forward,Backward}Length parity; one k, like MLN)."""
        self._tbptt = k
        return self

    def stage_boundary(self, *node_names: str) -> "GraphBuilder":
        """Mark remat/fusion stage boundaries: each named node ENDS a stage
        (util/xla_tuning.py). With no names, the last added node ends the
        stage. Boundaries are inert until a remat policy or stage barriers
        are configured on the parent builder."""
        if not node_names:
            if not self._nodes:
                raise ValueError("stage_boundary() before any node")
            node_names = (self._nodes[-1].name,)
        for n in node_names:
            if n not in self._stage_ends:
                self._stage_ends.append(n)
        return self

    def build(self) -> ComputationGraphConfiguration:
        if not self._inputs:
            raise ValueError("add_inputs required")
        if not self._outputs:
            raise ValueError("set_outputs required")
        nodes = self._nodes
        if self._p is not None:
            stamped = []
            for n in nodes:
                node = n.node
                if isinstance(node, L.Layer):
                    node = self._p._stamp_layer(node)
                stamped.append(GraphNode(n.name, node, n.inputs))
            nodes = stamped
        return ComputationGraphConfiguration(
            inputs=list(self._inputs),
            nodes=nodes,
            outputs=list(self._outputs),
            seed=self._p._seed if self._p else 12345,
            updater=self._p._updater if self._p else None,
            input_shapes=self._input_shapes,
            compute_dtype=self._p._compute_dtype if self._p else "float32",
            tbptt_length=self._tbptt if self._tbptt is not None
            else (self._p._tbptt_length if self._p else 0),
            remat_policy=getattr(self._p, "_remat_policy", None),
            remat_stages=tuple(self._stage_ends) or None,
            stage_barriers=getattr(self._p, "_stage_barriers", False),
            sync_every=getattr(self._p, "_sync_every", 1),
            batch_buckets=getattr(self._p, "_batch_buckets", None),
            seq_buckets=getattr(self._p, "_seq_buckets", None),
            kernel_impl=getattr(self._p, "_kernel_impl", None),
            fused_update=getattr(self._p, "_fused_update", False),
            loss_scale=getattr(self._p, "_loss_scale", "none"),
            loss_scale_value=getattr(self._p, "_loss_scale_value", 2.0 ** 15),
            loss_scale_growth=getattr(self._p, "_loss_scale_growth", 2000),
            grad_compression=getattr(self._p, "_grad_compression", "none"),
            grad_compression_threshold=getattr(
                self._p, "_grad_compression_threshold", 1e-3),
            grad_compression_target=getattr(
                self._p, "_grad_compression_target", 1e-3),
            pipe_stages=getattr(self._p, "_pipe_stages", 0),
            n_micro=getattr(self._p, "_n_micro", 0),
        )


def _first_mask(ds, singular: str, plural: str):
    """DataSet carries one mask; MultiDataSet a list (the shared-mask case —
    one sequence mask across inputs — takes the first)."""
    m = getattr(ds, singular, None)
    if m is not None:
        return m
    ms = getattr(ds, plural, None)
    return ms[0] if ms else None


def _as_mask(m):
    """Coerce a mask argument (array | dict name->array | None) to jnp."""
    if m is None:
        return None
    if isinstance(m, dict):
        return {k: (None if v is None else jnp.asarray(v))
                for k, v in m.items()}
    return jnp.asarray(m)


def _mask_dict(ds, names, singular: str, plural: str):
    """Masks for a CG batch: a DataSet's single mask stays a shared array;
    a MultiDataSet's mask LIST becomes a dict keyed by input/output name so
    each stream keeps its own mask (per-input TBPTT masks, VERDICT r2 #3)."""
    m = getattr(ds, singular, None)
    if m is not None:
        return m
    ms = getattr(ds, plural, None)
    if not ms:
        return None
    return dict(zip(names, ms))


class ComputationGraph:
    """DAG network runtime (ComputationGraph.java parity). The whole
    forward+backward+updater step is one jitted XLA program."""

    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self.topo = conf.topological_order()
        self.params: Dict[str, dict] = {}
        self.states: Dict[str, dict] = {}
        self.opt_states: Dict[str, Any] = {}
        self.iteration = 0
        self.epoch = 0
        self.listeners: list = []
        self.score_value: float = float("nan")
        self.last_iteration_wall_ns = None  # set during coalesced dispatch
        self._train_step = None
        self._it_dev = None   # device-resident iteration counter
        self._it_sync = -1    # host iteration the device counter mirrors
        from deeplearning4j_tpu.nn.listeners import CoalescingListenerDispatcher

        self._dispatcher = CoalescingListenerDispatcher(
            self, getattr(conf, "sync_every", 1))
        self._updaters: Dict[str, Any] = {}
        for n in self.topo:
            if n.is_layer:
                self._updaters[n.name] = (
                    n.node.updater or conf.updater or upd.Sgd(0.1)
                )
        self._rng_key = jax.random.PRNGKey(conf.seed)
        # fused donated optimizer apply (docs/KERNELS.md): built in init()
        self._fused = None
        if (getattr(conf, "loss_scale", "none") != "none"
                and not getattr(conf, "fused_update", False)):
            raise ValueError(
                "loss_scale requires fused_update=True — the scale "
                "automaton lives in the fused optimizer state")
        node_names = {n.name for n in self.topo}
        for name in conf.outputs:
            if name not in node_names:
                raise ValueError(f"unknown output {name!r}")
        consumed = {i for n in self.topo for i in n.inputs}
        for name in conf.outputs:
            if name in consumed:
                raise ValueError(
                    f"output {name!r} is consumed by another node — outputs "
                    "must be terminal (IOutputLayer semantics)"
                )
        layer_names = {n.name for n in self.topo if n.is_layer}
        for n in self.topo:
            if isinstance(n.node, L.SharedLayer) \
                    and n.node.source not in layer_names:
                raise ValueError(
                    f"SharedLayer {n.name!r} references unknown source "
                    f"{n.node.source!r}")
        self._segments = self._build_segments()
        # Cost attribution (util/cost_model.py): one scope tag per node,
        # threaded through every trace as named_scope("layer:<tag>"). A
        # SharedLayer node computes under its OWN tag with the source's
        # params — weight-shared layers legitimately appear in two rows.
        self._node_tags = {n.name: cmod.sanitize_tag(n.name)
                           for n in self.topo}
        self._cost_flops_per_example = None  # set by cost_report()
        self._peak_flops = None
        # Shape bucketing (data/bucketing.py) + AOT-warmed executables
        self._bucketing = BucketingPolicy.from_conf(conf)
        self._aot_steps: dict = {}
        self._aot_forward: dict = {}
        # device-resident 0/1 weights cache — fit always threads weights so
        # bucketed == unbucketed program (data/bucketing.py dev_weights)
        self._w_cache: dict = {}
        self._last_fit_ns = None  # step-cadence stamp (telemetry histogram)

    def _dev_weights(self, size: int, real: int):
        from deeplearning4j_tpu.data.bucketing import dev_weights

        return dev_weights(self._w_cache, size, real)

    # ------------------------------------------- fusion-boundary segmentation
    def _build_segments(self):
        """Partition the topo order into remat/fusion stages
        (util/xla_tuning.py). Returns (stages, keep_after, tail) or None when
        no policy/barrier is configured: ``stages`` is a list of node lists
        (each wrapped in jax.checkpoint per the policy), ``keep_after[k]``
        the activation names still consumed after stage k (everything else
        is dropped at the boundary — that IS the remat saving), ``tail`` the
        unwrapped remainder containing the loss heads."""
        conf = self.conf
        active = (conf.remat_policy not in (None, "none")) or conf.stage_barriers
        if not active:
            return None
        names = {n.name for n in self.topo}
        out_names = set(conf.outputs)
        bounds = [s for s in (conf.remat_stages or ())]
        for s in bounds:
            if s not in names:
                raise ValueError(f"remat stage boundary {s!r} is not a node")
            if s in out_names:
                raise ValueError(
                    f"remat stage boundary {s!r} is an output layer — the "
                    "loss head always runs in the unwrapped tail")
        bound_set = set(bounds)
        stages, cur = [], []
        if not bound_set:
            # no markers: the whole body before the first output node is
            # one stage (whole-graph remat — the measured-rejected r5
            # candidate, kept available for A/B harness runs)
            for n in self.topo:
                if n.name in out_names:
                    break
                cur.append(n)
            stages, tail = [cur], self.topo[len(cur):]
        else:
            for n in self.topo:
                cur.append(n)
                if n.name in bound_set:
                    stages.append(cur)
                    cur = []
            tail = cur
        if not tail:
            raise ValueError("remat stages consume every node — the loss "
                             "head must stay outside the last boundary")
        for k, stage in enumerate(stages):
            swallowed = [n.name for n in stage if n.name in out_names]
            if swallowed:
                # an output inside a checkpointed stage would run plain
                # .apply() instead of compute_loss(), silently dropping its
                # loss (and gradients) from training — refuse loudly
                raise ValueError(
                    f"output node(s) {swallowed} fall inside remat stage "
                    f"{k} (boundary {stage[-1].name!r}): every output/loss "
                    "head must stay in the unwrapped tail — move or remove "
                    "the boundaries that precede auxiliary heads")
        # liveness at each boundary: names consumed by any later stage/tail
        groups = stages + [tail]
        keep_after = [set() for _ in stages]
        consumed: set = set()
        for k in range(len(groups) - 1, 0, -1):
            for n in groups[k]:
                consumed.update(n.inputs)
            keep_after[k - 1] = set(consumed)
        return stages, keep_after, tail

    # ------------------------------------------------------------------ init
    def init(self, input_shapes=None) -> "ComputationGraph":
        shapes = input_shapes or self.conf.input_shapes
        if shapes is None:
            raise ValueError("input_shapes required (set_input_types on the builder)")
        shape_of: Dict[str, tuple] = {
            name: tuple(s) for name, s in zip(self.conf.inputs, shapes)
        }
        key = jax.random.PRNGKey(self.conf.seed)
        self.params, self.states = {}, {}
        for n in self.topo:
            in_shapes = [shape_of[i] for i in n.inputs]
            if n.is_layer:
                ishape = self._merged_shape(in_shapes)
                key, sub = jax.random.split(key)
                p, s = n.node.initialize(sub, ishape)
                self.params[n.name] = p
                self.states[n.name] = s
                shape_of[n.name] = tuple(n.node.output_shape(ishape))
            else:
                self.params[n.name] = {}
                self.states[n.name] = {}
                shape_of[n.name] = tuple(n.node.output_shape(*in_shapes))
        if getattr(self.conf, "fused_update", False):
            self._fused = upd.FusedUpdateEngine(
                self._updaters,
                {k: self.params[k] for k in self._updaters},
                loss_scale=getattr(self.conf, "loss_scale", "none"),
                loss_scale_value=getattr(self.conf, "loss_scale_value",
                                         2.0 ** 15),
                growth_interval=getattr(self.conf, "loss_scale_growth", 2000))
            self.opt_states = self._fused.init_state(
                {k: self.params[k] for k in self._updaters})
        else:
            self.opt_states = {
                name: self._updaters[name].init_state(self.params[name])
                for name in self._updaters
            }
        self._shape_of = shape_of
        self._train_step = self._jit_train_step()
        # output() programs return the OUTPUT vertices only: a jit that
        # hands back every vertex keeps every activation alive at once, and
        # ResNet-50 at B=256 224x224 then exhausts a 16 GB chip (measured,
        # PR 21). feed_forward() is the call that wants them all.
        self._forward_jit = jax.jit(functools.partial(self._forward_outputs, training=False))
        self._forward_train_jit = jax.jit(functools.partial(self._forward_outputs, training=True))
        self._feed_forward_jit = jax.jit(functools.partial(self._forward, training=False))
        return self

    @staticmethod
    def _merged_shape(in_shapes):
        if len(in_shapes) == 1:
            return in_shapes[0]
        base = list(in_shapes[0])
        base[-1] = sum(s[-1] for s in in_shapes)
        return tuple(base)

    def num_params(self) -> int:
        return sum(
            int(np.prod(x.shape))
            for p in self.params.values()
            for x in jax.tree_util.tree_leaves(p)
        )

    # --------------------------------------------------------------- forward
    def _cast(self, x):
        if self.conf.compute_dtype == "bfloat16" and jnp.issubdtype(
            x.dtype, jnp.floating
        ):
            return x.astype(jnp.bfloat16)
        return x

    def _cast_params(self, params):
        if self.conf.compute_dtype != "bfloat16":
            return params
        return jax.tree_util.tree_map(
            lambda p: p.astype(jnp.bfloat16)
            if jnp.issubdtype(p.dtype, jnp.floating)
            else p,
            params,
        )

    def _gather_input(self, acts, node):
        xs = [acts[i] for i in node.inputs]
        if node.is_layer:
            return xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=-1)
        return xs

    @staticmethod
    def _arriving_mask(produced, n, mask):
        """Mask arriving at node ``n``: per-input dict masks propagate
        through the DAG (feedForwardMaskArrays parity — each node inherits
        the first non-None mask among its inputs, pass-through vertices keep
        it); a single shared mask applies everywhere, as before."""
        if produced is None:
            return mask
        return next((produced.get(i) for i in n.inputs
                     if produced.get(i) is not None), None)

    def _loss_mask_kw(self, node, mask, label_mask, x):
        """compute_loss mask gate: label mask falls back to the feature mask;
        same shape/signature rule as :meth:`_mask_kw`."""
        lm = label_mask if label_mask is not None else mask
        if (
            lm is not None
            and getattr(x, "ndim", 0) == 3
            and lm.shape[:2] == x.shape[:2]
            and "mask" in inspect.signature(node.compute_loss).parameters
        ):
            return {"mask": lm}
        return {}

    def _mask_kw(self, node, mask, x):
        """Mask threading rule (feedForwardMaskArrays parity, same shape gate
        as MultiLayerNetwork): a (B,T) mask reaches layers that accept one
        while activations keep a matching (B,T,...) leading shape."""
        if (
            mask is not None
            and getattr(x, "ndim", 0) == 3
            and mask.shape[:2] == x.shape[:2]
            and "mask" in inspect.signature(node.apply).parameters
        ):
            return {"mask": mask}
        return {}

    @staticmethod
    def _resolve_shared(node, name):
        """(layer-to-apply, params/state key): SharedLayer nodes compute with
        their source node's params (weight sharing)."""
        if isinstance(node, L.SharedLayer):
            return node.layer, node.source
        return node, name

    def _kscope(self):
        """Kernel-dispatch scope for every trace of this graph's layers
        (ops/kernels — docs/KERNELS.md)."""
        from deeplearning4j_tpu.ops import kernels as _kern

        return _kern.impl_scope(getattr(self.conf, "kernel_impl", None))

    def _forward(self, params, states, inputs, *, training, keys=None,
                 mask=None):
        """inputs: dict name->array. Returns (dict name->activation, states)."""
        note_trace("ComputationGraph.forward", inputs, mask)  # trace-time only
        with self._kscope():
            return self._forward_body(params, states, inputs,
                                      training=training, keys=keys, mask=mask)

    def _forward_outputs(self, params, states, inputs, *, training,
                         mask=None):
        """:meth:`_forward` cut down to the graph's declared outputs."""
        acts, new_states = self._forward(params, states, inputs,
                                         training=training, mask=mask)
        return {name: acts[name] for name in self.conf.outputs}, new_states

    def _forward_body(self, params, states, inputs, *, training, keys=None,
                      mask=None):
        acts = {k: self._cast(v) for k, v in inputs.items()}
        cparams = self._cast_params(params)
        new_states = dict(states)
        for n in self.topo:
            if n.is_layer:
                k = keys[n.name] if keys is not None else None
                x = self._gather_input(acts, n)
                lyr, pkey = self._resolve_shared(n.node, n.name)
                with cmod.layer_scope(self._node_tags[n.name]):
                    h, ns = lyr.apply(
                        cparams[pkey], states[pkey], x,
                        training=training, key=k,
                        **self._mask_kw(lyr, mask, x),
                    )
                acts[n.name] = h
                new_states[pkey] = ns
            else:
                acts[n.name] = n.node.apply(*self._gather_input(acts, n))
        return acts, new_states

    def _loss(self, params, states, inputs, labels, keys, weights=None,
              mask=None, label_mask=None):
        """Sum of output-layer losses + regularization. labels: dict
        output-name -> labels array. ``mask``/``label_mask``: (B,T) feature/
        label masks for sequence graphs (single shared mask, like MLN)."""
        with self._kscope():
            return self._loss_body(params, states, inputs, labels, keys,
                                   weights, mask, label_mask)

    def _loss_body(self, params, states, inputs, labels, keys, weights=None,
                   mask=None, label_mask=None):
        if self._segments is not None and mask is None and label_mask is None:
            # fusion-boundary path: stage-segmented remat/barriers (masked
            # sequence graphs keep the plain path — masks thread through the
            # flat loop, and the conv stages remat targets carry no masks)
            return self._loss_remat(params, states, inputs, labels, keys,
                                    weights)
        acts = {k: self._cast(v) for k, v in inputs.items()}
        cparams = self._cast_params(params)
        new_states = dict(states)
        out_names = set(self.conf.outputs)
        produced = dict(mask) if isinstance(mask, dict) else None
        loss = 0.0  # weak-typed: stays fp64 under the gradcheck's enable_x64
        for n in self.topo:
            mk = self._arriving_mask(produced, n, mask)
            if produced is not None:
                produced[n.name] = mk
            if not n.is_layer:
                acts[n.name] = n.node.apply(*self._gather_input(acts, n))
                continue
            x = self._gather_input(acts, n)
            if n.name in out_names:
                if not hasattr(n.node, "compute_loss"):
                    raise ValueError(
                        f"output {n.name!r} must be an OutputLayer/LossLayer"
                    )
                lm = (label_mask.get(n.name)
                      if isinstance(label_mask, dict) else label_mask)
                with cmod.layer_scope(self._node_tags[n.name]):
                    out_loss = n.node.compute_loss(
                        cparams[n.name], states[n.name], x, labels[n.name],
                        training=True, key=keys[n.name], weights=weights,
                        **self._loss_mask_kw(n.node, mk, lm, x),
                    )
                loss = loss + out_loss.astype(
                    jnp.promote_types(out_loss.dtype, jnp.float32)
                )
                acts[n.name] = x  # terminal; activation unused downstream
            else:
                lyr, pkey = self._resolve_shared(n.node, n.name)
                with cmod.layer_scope(self._node_tags[n.name]):
                    h, ns = lyr.apply(
                        cparams[pkey], states[pkey], x, training=True,
                        key=keys[n.name], **self._mask_kw(lyr, mk, x),
                    )
                acts[n.name] = h
                new_states[pkey] = ns
        reg = sum(
            (
                n.node.regularization(params[n.name])
                for n in self.topo
                if n.is_layer
            ),
            start=0.0,
        )
        return loss + reg, new_states

    def _loss_remat(self, params, states, inputs, labels, keys, weights=None):
        """_loss with the topo order split into remat/fusion stages
        (``_build_segments``): each stage runs inside ``jax.checkpoint``
        under the configured policy (save conv/dot outputs, recompute cheap
        elementwise/BN — util/xla_tuning.py), activations dead past a
        boundary are dropped there, and ``stage_barriers`` fences fusion at
        each boundary. Values and gradients are exactly those of the plain
        path — remat changes only what XLA keeps live across fwd/bwd."""
        from deeplearning4j_tpu.util import xla_tuning

        stages, keep_after, tail = self._segments
        wrap, policy = xla_tuning.resolve_policy(self.conf.remat_policy)
        acts = {k: self._cast(v) for k, v in inputs.items()}
        cparams = self._cast_params(params)
        new_states = dict(states)

        def stage_runner(nodes):
            def run(seg_params, seg_states, seg_keys, acts_in):
                a = dict(acts_in)
                st = {}
                for n in nodes:
                    if n.is_layer:
                        x = self._gather_input(a, n)
                        lyr, pkey = self._resolve_shared(n.node, n.name)
                        with cmod.layer_scope(self._node_tags[n.name]):
                            h, ns = lyr.apply(
                                seg_params[pkey], seg_states[pkey], x,
                                training=True, key=seg_keys[n.name],
                            )
                        a[n.name] = h
                        st[pkey] = ns
                    else:
                        a[n.name] = n.node.apply(*self._gather_input(a, n))
                return a, st
            return run

        for k, nodes in enumerate(stages):
            run = stage_runner(nodes)
            if wrap:
                run = jax.checkpoint(run, policy=policy)
            pkeys = {self._resolve_shared(n.node, n.name)[1]
                     for n in nodes if n.is_layer}
            acts_out, st = run(
                {p: cparams[p] for p in pkeys},
                {p: states[p] for p in pkeys},
                {n.name: keys[n.name] for n in nodes if n.is_layer},
                acts,
            )
            new_states.update(st)
            acts = {name: v for name, v in acts_out.items()
                    if name in keep_after[k]}
            if self.conf.stage_barriers:
                acts = xla_tuning.barrier(acts)
        # unwrapped tail: remaining nodes + the loss heads (same arithmetic
        # as the plain _loss loop, maskless)
        out_names = set(self.conf.outputs)
        loss = 0.0  # weak-typed: stays fp64 under the gradcheck's enable_x64
        for n in tail:
            if not n.is_layer:
                acts[n.name] = n.node.apply(*self._gather_input(acts, n))
                continue
            x = self._gather_input(acts, n)
            if n.name in out_names:
                if not hasattr(n.node, "compute_loss"):
                    raise ValueError(
                        f"output {n.name!r} must be an OutputLayer/LossLayer"
                    )
                with cmod.layer_scope(self._node_tags[n.name]):
                    out_loss = n.node.compute_loss(
                        cparams[n.name], states[n.name], x, labels[n.name],
                        training=True, key=keys[n.name], weights=weights,
                    )
                loss = loss + out_loss.astype(
                    jnp.promote_types(out_loss.dtype, jnp.float32)
                )
                acts[n.name] = x
            else:
                lyr, pkey = self._resolve_shared(n.node, n.name)
                with cmod.layer_scope(self._node_tags[n.name]):
                    h, ns = lyr.apply(
                        cparams[pkey], states[pkey], x, training=True,
                        key=keys[n.name],
                    )
                acts[n.name] = h
                new_states[pkey] = ns
        reg = sum(
            (
                n.node.regularization(params[n.name])
                for n in self.topo
                if n.is_layer
            ),
            start=0.0,
        )
        return loss + reg, new_states

    # -------------------------------------------------------- truncated BPTT
    @staticmethod
    def _is_recurrent(lyr) -> bool:
        return hasattr(lyr, "apply_seq") and hasattr(lyr, "init_carry")

    def _init_carries(self, batch_size, dtype):
        """Per-node carry dict for recurrent layer nodes (ComputationGraph's
        tbpttStateMap parity)."""
        return {
            n.name: n.node.init_carry(batch_size, dtype)
            for n in self.topo
            if n.is_layer and self._is_recurrent(n.node)
        }

    def _loss_tbptt(self, params, states, carries, inputs, labels, keys,
                    mask=None, label_mask=None, weights=None):
        """_loss variant for one TBPTT segment: recurrent nodes take carries
        in and hand carries out; gradients truncate at the segment boundary
        because the incoming carry is a plain argument."""
        with self._kscope():
            return self._loss_tbptt_body(params, states, carries, inputs,
                                         labels, keys, mask, label_mask,
                                         weights)

    def _loss_tbptt_body(self, params, states, carries, inputs, labels, keys,
                         mask=None, label_mask=None, weights=None):
        acts = {k: self._cast(v) for k, v in inputs.items()}
        cparams = self._cast_params(params)
        new_states = dict(states)
        new_carries = dict(carries)
        out_names = set(self.conf.outputs)
        produced = dict(mask) if isinstance(mask, dict) else None
        loss = 0.0
        for n in self.topo:
            mk = self._arriving_mask(produced, n, mask)
            if produced is not None:
                produced[n.name] = mk
            if not n.is_layer:
                acts[n.name] = n.node.apply(*self._gather_input(acts, n))
                continue
            x = self._gather_input(acts, n)
            if n.name in out_names:
                lm = (label_mask.get(n.name)
                      if isinstance(label_mask, dict) else label_mask)
                with cmod.layer_scope(self._node_tags[n.name]):
                    out_loss = n.node.compute_loss(
                        cparams[n.name], states[n.name], x, labels[n.name],
                        training=True, key=keys[n.name], weights=weights,
                        **self._loss_mask_kw(n.node, mk, lm, x),
                    )
                loss = loss + out_loss.astype(
                    jnp.promote_types(out_loss.dtype, jnp.float32))
                acts[n.name] = x
            elif n.name in carries:
                seg_mask = (mk if (mk is not None and x.ndim == 3
                                   and mk.shape[:2] == x.shape[:2])
                            else None)
                with cmod.layer_scope(self._node_tags[n.name]):
                    xx = n.node._maybe_dropout(x, True, keys[n.name])
                    h, c = n.node.apply_seq(
                        cparams[n.name], xx, carries[n.name], mask=seg_mask,
                        training=True, key=keys[n.name])
                acts[n.name] = h
                new_carries[n.name] = c
            else:
                lyr, pkey = self._resolve_shared(n.node, n.name)
                with cmod.layer_scope(self._node_tags[n.name]):
                    h, ns = lyr.apply(
                        cparams[pkey], states[pkey], x, training=True,
                        key=keys[n.name], **self._mask_kw(lyr, mk, x),
                    )
                acts[n.name] = h
                new_states[pkey] = ns
        reg = sum((n.node.regularization(params[n.name])
                   for n in self.topo if n.is_layer), start=0.0)
        return loss + reg, (new_states, new_carries)

    @functools.cached_property
    def _tbptt_step(self):
        """One jitted train step per TBPTT segment (the reference's
        doTruncatedBPTT inside ComputationGraph.java)."""
        updaters = self._updaters
        layer_names = [n.name for n in self.topo if n.is_layer]

        def step(params, states, opts, carries, iteration, inputs, labels,
                 key, mask, label_mask, weights=None):
            note_trace("ComputationGraph.tbptt_step", inputs, labels, weights,
                       mask, label_mask)
            subkeys = jax.random.split(key, len(layer_names))
            keys = dict(zip(layer_names, subkeys))
            engine = self._fused
            scale = engine.current_scale(opts) if engine is not None else None
            (_, ((new_states, new_carries), loss)), grads = \
                jax.value_and_grad(
                    upd.FusedUpdateEngine.wrap_scaled(self._loss_tbptt,
                                                      scale),
                    has_aux=True)(
                    params, states, carries, inputs, labels, keys, mask,
                    label_mask, weights)
            with cmod.optimizer_scope():  # cost attribution: (optimizer) row
                if engine is not None:
                    new_params, new_opts = engine.apply(
                        params, grads, opts, iteration)
                else:
                    new_params, new_opts = dict(params), dict(opts)
                    for name in layer_names:
                        if not grads[name]:
                            continue
                        p, s = upd.apply_updater(
                            updaters[name], params[name], grads[name],
                            opts[name], iteration)
                        new_params[name] = p
                        new_opts[name] = s
            return new_params, new_states, new_opts, new_carries, loss

        return jax.jit(step, donate_argnums=(0, 1, 2))

    def _fit_batch_tbptt(self, inputs, labs, mask=None, label_mask=None):
        """Segment loop: carries flow forward across segments, gradients are
        truncated; every segment is one updater step (update-per-segment, as
        in the reference)."""
        k = self.conf.tbptt_length
        real_n = next(iter(inputs.values())).shape[0]
        if self._bucketing is not None:
            # batch axis: pad rows + 0/1 weights (segments pad individually
            # below — no whole-sequence time padding here). Keep the whole
            # segment loop in HOST numpy: pad_segment would otherwise sync
            # device->host for every segment slice.
            inputs = {kk: np.asarray(v) for kk, v in inputs.items()}
            labs = {kk: np.asarray(v) for kk, v in labs.items()}
            to_np = lambda m: (m if m is None else  # noqa: E731
                               ({kk: (None if v is None else np.asarray(v))
                                 for kk, v in m.items()}
                                if isinstance(m, dict) else np.asarray(m)))
            mask, label_mask = to_np(mask), to_np(label_mask)
            npad = self._bucketing.bucket_batch(real_n)
            if npad != real_n:
                bpad = lambda a: (None if a is None else  # noqa: E731
                                  np.pad(a, [(0, npad - real_n)] +
                                         [(0, 0)] * (np.ndim(a) - 1)))
                inputs = {kk: bpad(v) for kk, v in inputs.items()}
                labs = {kk: bpad(v) for kk, v in labs.items()}
                pad_m = lambda m: (m if m is None else  # noqa: E731
                                   ({kk: bpad(v) for kk, v in m.items()}
                                    if isinstance(m, dict) else bpad(m)))
                mask, label_mask = pad_m(mask), pad_m(label_mask)
        weights = self._dev_weights(
            next(iter(inputs.values())).shape[0], real_n)
        T = next(v.shape[1] for v in inputs.values() if v.ndim == 3)
        ref = next(iter(inputs.values()))
        carries = self._init_carries(ref.shape[0], self._cast(ref).dtype)
        losses = []

        def seg(d, s):
            return {kk: (v[:, s:s + k] if v.ndim == 3 else v)
                    for kk, v in d.items()}

        def seg_mask(mm, s):
            if mm is None:
                return None
            if isinstance(mm, dict):  # per-input masks sliced independently
                return {kk: (None if v is None else v[:, s:s + k])
                        for kk, v in mm.items()}
            return mm[:, s:s + k]

        for s in range(0, T, k):
            ms = seg_mask(mask, s)
            lms = seg_mask(label_mask, s)
            seg_in, seg_lab = seg(inputs, s), seg(labs, s)
            if self._bucketing is not None:
                # tail remainder pads to k; full segments get all-ones masks
                # — one jit signature for every segment (data/bucketing.py)
                seg_in, ms, lms = self._bucketing.pad_segment(
                    seg_in, ms, lms, k)
                seg_lab, _, _ = self._bucketing.pad_segment(
                    seg_lab, None, None, k)
            self._rng_key, sub = jax.random.split(self._rng_key)
            with tm.step_span("cg.tbptt_step", iteration=self.iteration,
                              segment_start=s):
                (self.params, self.states, self.opt_states, carries, loss) = (
                    self._tbptt_step(self.params, self.states,
                                     self.opt_states, carries,
                                     jnp.asarray(self.iteration),
                                     seg_in, seg_lab, sub, ms, lms, weights))
            self.iteration += 1
            losses.append(loss)
        self._dispatcher.flush()  # keep cross-path dispatch ordering intact
        self.score_value = float(jnp.mean(jnp.stack(losses)))
        for lst in self.listeners:
            lst.iteration_done(self, self.iteration, self.epoch)

    # -------------------------------------------------------------- pretrain
    def pretrain(self, data, epochs: int = 1):
        """ComputationGraph.pretrain(DataSetIterator) parity: layerwise
        unsupervised training of every pretrain-capable layer node, in
        topological order."""
        for n in self.topo:
            if n.is_layer and getattr(n.node, "is_pretrain_layer",
                                      lambda: False)():
                self.pretrain_layer(n.name, data, epochs=epochs)
        return self

    def pretrain_layer(self, name: str, data, epochs: int = 1):
        """pretrainLayer(String, DataSetIterator) parity: one node trained on
        its unsupervised objective; its input comes from an inference-mode
        forward pass (XLA dead-code-eliminates the rest of the graph)."""
        from deeplearning4j_tpu.data.dataset import DataSet

        node = next(n for n in self.topo if n.name == name)
        if not getattr(node.node, "is_pretrain_layer", lambda: False)():
            raise ValueError(
                f"node {name!r} ({type(node.node).__name__}) is not a "
                "pretrain layer")
        updater = self._updaters[name]
        opt = updater.init_state(self.params[name])
        base_params = dict(self.params)
        states = self.states

        @jax.jit
        def step(p, opt_state, iteration, inputs, key):
            params = dict(base_params)
            params[name] = p

            def loss_fn(p_):
                params[name] = p_
                acts, _ = self._forward(params, states, inputs,
                                        training=False)
                x = self._gather_input(acts, node)
                return node.node.pretrain_loss(p_, x, key)

            loss, g = jax.value_and_grad(loss_fn)(p)
            new_p, new_opt = upd.apply_updater(updater, p, g, opt_state,
                                               iteration)
            return new_p, new_opt, loss

        if isinstance(data, (np.ndarray, jnp.ndarray)):
            data = [DataSet(np.asarray(data), None)]
        elif isinstance(data, (DataSet,)):
            data = [data]
        loss = None
        it_count = 0
        for _ in range(epochs):
            if hasattr(data, "reset"):
                data.reset()
            for ds in data:
                feats = ds.features if hasattr(ds, "features") else ds
                feats = feats if isinstance(feats, (list, tuple)) else [feats]
                inputs = dict(zip(self.conf.inputs,
                                  [jnp.asarray(f) for f in feats]))
                self._rng_key, sub = jax.random.split(self._rng_key)
                self.params[name], opt, loss = step(
                    self.params[name], opt, jnp.asarray(it_count), inputs, sub)
                it_count += 1
        if loss is not None:
            self.score_value = loss
        return self

    # ------------------------------------------------ stateful rnn inference
    def rnn_time_step(self, *inputs):
        """Stateful step-by-step inference over the DAG (ComputationGraph.
        rnnTimeStep parity): recurrent-node carries persist across calls."""
        from deeplearning4j_tpu.nn.recurrent import Bidirectional

        for n in self.topo:
            if n.is_layer and isinstance(n.node, Bidirectional):
                raise ValueError(
                    "rnn_time_step does not support Bidirectional layers")
        ins = {}
        squeeze = False
        for name, x in zip(self.conf.inputs, inputs):
            x = self._cast(jnp.asarray(x))
            if x.ndim == 2:
                squeeze = True
                x = x[:, None]
            ins[name] = x
        B = next(iter(ins.values())).shape[0]
        carries = getattr(self, "_rnn_carries", None)
        if carries is None:
            carries = self._init_carries(B, next(iter(ins.values())).dtype)
        cparams = self._cast_params(self.params)
        acts = dict(ins)
        new_carries = dict(carries)
        for n in self.topo:
            if not n.is_layer:
                acts[n.name] = n.node.apply(*self._gather_input(acts, n))
                continue
            x = self._gather_input(acts, n)
            if n.name in carries:
                h, c = n.node.apply_seq(cparams[n.name], x, carries[n.name],
                                        training=False)
                new_carries[n.name] = c
            else:
                h, _ = n.node.apply(cparams[n.name], self.states[n.name], x,
                                    training=False)
            acts[n.name] = h
        self._rnn_carries = new_carries
        outs = [acts[o] for o in self.conf.outputs]
        if squeeze:
            outs = [o[:, -1] if o.ndim == 3 else o for o in outs]
        return outs[0] if len(outs) == 1 else outs

    def rnn_clear_previous_state(self):
        """rnnClearPreviousState parity."""
        self._rnn_carries = None

    # ------------------------------------------------------------ train step
    def _jit_train_step(self):
        """Iteration counter + RNG-key evolution live INSIDE the jitted step
        (see MultiLayerNetwork._build_train_step: avoids two extra
        dispatches per step)."""
        base = self.make_step_fn(weighted=True)

        def step(params, states, opt_states, iteration, key, inputs, labels,
                 weights=None, mask=None, label_mask=None):
            # trace-time only: one retrace == one CompileWatcher line
            note_trace("ComputationGraph.train_step", inputs, labels, weights,
                       mask, label_mask)
            new_key, sub = jax.random.split(key)
            p, s, o, loss = base(params, states, opt_states, iteration,
                                 inputs, labels, sub, weights=weights,
                                 mask=mask, label_mask=label_mask)
            return p, s, o, loss, iteration + 1, new_key

        return jax.jit(step, donate_argnums=(0, 1, 2, 3, 4))

    def make_step_fn(self, weighted: bool = False):
        updaters = self._updaters
        layer_names = [n.name for n in self.topo if n.is_layer]
        in_name = self.conf.inputs[0]
        out_name = self.conf.outputs[0]

        def step(params, states, opt_states, iteration, inputs, labels, key,
                 weights=None, mask=None, label_mask=None):
            # Raw arrays (e.g. from ParallelWrapper) → dict form: a bare
            # array feeds the single input; a list/tuple zips with the
            # graph's input/output order (multi-input graphs).
            if not isinstance(inputs, dict):
                inputs = (dict(zip(self.conf.inputs, inputs))
                          if isinstance(inputs, (list, tuple))
                          else {in_name: inputs})
            if not isinstance(labels, dict):
                labels = (dict(zip(self.conf.outputs, labels))
                          if isinstance(labels, (list, tuple))
                          else {out_name: labels})
            subkeys = jax.random.split(key, len(layer_names))
            keys = dict(zip(layer_names, subkeys))
            engine = self._fused
            scale = engine.current_scale(opt_states) if engine is not None \
                else None
            (_, (new_states, loss)), grads = jax.value_and_grad(
                upd.FusedUpdateEngine.wrap_scaled(self._loss, scale),
                has_aux=True
            )(params, states, inputs, labels, keys, weights, mask,
              label_mask)
            with cmod.optimizer_scope():  # cost attribution: (optimizer) row
                if engine is not None:
                    new_params, new_opts = engine.apply(
                        params, grads, opt_states, iteration)
                else:
                    new_params, new_opts = dict(params), dict(opt_states)
                    for name in layer_names:
                        if not grads[name]:
                            continue
                        p, s = upd.apply_updater(
                            updaters[name], params[name], grads[name],
                            opt_states[name], iteration,
                        )
                        new_params[name] = p
                        new_opts[name] = s
            return new_params, new_states, new_opts, loss

        if weighted:
            return step
        return lambda params, states, opt_states, iteration, inputs, labels, \
            key, mask=None, label_mask=None: step(
            params, states, opt_states, iteration, inputs, labels, key,
            mask=mask, label_mask=label_mask,
        )

    # ------------------------------------------------------------------- fit
    def fit(self, data, labels=None, epochs: int = 1):
        """fit(x, y) | fit([x1, x2], [y1, ...]) | fit(DataSet) | fit(iterator)."""
        from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet

        if labels is not None:
            for _ in range(epochs):
                self._fit_batch(data, labels)
                self._end_epoch()
            return self
        if isinstance(data, (DataSet, MultiDataSet)):  # fit(DataSet) parity
            data = [data]
        for _ in range(epochs):
            if hasattr(data, "reset"):
                data.reset()
            for ds in data:
                feats = ds.features if isinstance(ds.features, (list, tuple)) else [ds.features]
                labs = ds.labels if isinstance(ds.labels, (list, tuple)) else [ds.labels]
                # raw arrays through: _fit_batch pads (bucketing) on the
                # host before the one host->device transfer
                self._fit_batch(
                    list(feats), list(labs),
                    mask=_mask_dict(ds, self.conf.inputs,
                                    "features_mask", "features_masks"),
                    label_mask=_mask_dict(ds, self.conf.outputs,
                                          "labels_mask", "labels_masks"),
                )
            self._end_epoch()
        return self

    def _end_epoch(self):
        self._dispatcher.flush()  # epoch-end callbacks see a complete epoch
        self.epoch += 1
        for lst in self.listeners:
            if hasattr(lst, "on_epoch_end"):
                lst.on_epoch_end(self)

    def _fit_batch(self, features, labels, mask=None, label_mask=None):
        if not isinstance(features, (list, tuple)):
            features = [features]
        if not isinstance(labels, (list, tuple)):
            labels = [labels]
        if (self.conf.tbptt_length
                and any(np.ndim(v) == 3 for v in features)
                and all(np.ndim(v) == 3 for v in labels)
                and next(v.shape[1] for v in features
                         if np.ndim(v) == 3) > self.conf.tbptt_length):
            # per-sequence (2-D) labels cannot be segmented: whole-sequence
            # BPTT instead, as the reference's doTruncatedBPTT does
            inputs = dict(zip(self.conf.inputs,
                              [jnp.asarray(f) for f in features]))
            labs = dict(zip(self.conf.outputs,
                            [jnp.asarray(l) for l in labels]))
            return self._fit_batch_tbptt(
                inputs, labs, mask=_as_mask(mask),
                label_mask=_as_mask(label_mask))
        real_n = np.shape(features[0])[0]
        if self._bucketing is not None:
            # host-side padding: every batch carries the 0/1 weights vector
            # so the epoch keeps one jit signature per bucket
            features, labels, mask, label_mask, _ = (
                self._bucketing.pad_graph_batch(features, labels, mask,
                                                label_mask))
        # always-weighted: ones over real rows, zeros over padding
        weights = self._dev_weights(np.shape(features[0])[0], real_n)
        inputs = dict(zip(self.conf.inputs, [jnp.asarray(f) for f in features]))
        labs = dict(zip(self.conf.outputs, [jnp.asarray(l) for l in labels]))
        if self._train_step is None:  # cleared by external training masters
            self._train_step = self._jit_train_step()
        if self._it_dev is None or self._it_sync != self.iteration:
            self._it_dev = jax.device_put(jnp.asarray(self.iteration, jnp.int32))
        mk, lmk = _as_mask(mask), _as_mask(label_mask)
        step = self._aot_steps.get(
            _dispatch_sig(inputs, labs, weights, mk, lmk), self._train_step)
        if tm.enabled():
            import time as _time

            now = _time.time_ns()
            if self._last_fit_ns is not None:
                dt = (now - self._last_fit_ns) / 1e9
                tm.observe("train.step_seconds", dt, model="cg")
                if dt > 0:
                    # cost attribution gauges (docs/OBSERVABILITY.md)
                    tm.gauge("train.examples_per_sec", real_n / dt,
                             model="cg")
                    if self._cost_flops_per_example and self._peak_flops:
                        tm.gauge(
                            "train.model_flops_utilization",
                            self._cost_flops_per_example
                            * np.shape(features[0])[0] / dt
                            / self._peak_flops, model="cg")
            self._last_fit_ns = now
            tm.counter("train.steps_total", model="cg")
        # dispatch span with XLA trace/compile sub-spans when this shape
        # retraced (CompileWatcher markers — docs/OBSERVABILITY.md)
        with tm.step_span("cg.train_step", iteration=self.iteration):
            (self.params, self.states, self.opt_states, loss,
             self._it_dev, self._rng_key) = step(
                self.params, self.states, self.opt_states, self._it_dev,
                self._rng_key, inputs, labs, weights, mk, lmk,
            )
        self.score_value = loss
        # activation-stats listeners must never see fabricated padding rows
        self.last_features = tuple(
            f if real_n == np.shape(f)[0] else f[:real_n] for f in features)
        self.iteration += 1
        self._it_sync = self.iteration
        # sync_every=1: immediate dispatch (legacy cadence); >1: coalesced
        # windows — one host round-trip per window (docs/HOST_PIPELINE.md)
        self._dispatcher.iteration_done(loss, self.iteration, self.epoch)

    # ------------------------------------------------------------ AOT warmup
    def warmup(self, shapes=None, *, train=True, inference=True,
               dtype=jnp.float32, export_dir=None):
        """Ahead-of-time compile the train step / inference forward for every
        bucket (``jit(...).lower().compile()``) — the ComputationGraph twin
        of :meth:`MultiLayerNetwork.warmup`. ``shapes``: iterable of batch
        signatures; each entry is one shape per graph input INCLUDING the
        batch dim (a bare tuple is accepted for single-input graphs, e.g.
        ``[(8, 32), (16, 32)]``). Defaults to the explicit ``batch_buckets``
        list x ``conf.input_shapes``. ``export_dir``: on-disk AOT lowering
        store (util/aot_store.py) — a later process deserializes the
        lowered module and skips the Python trace; see
        :meth:`MultiLayerNetwork.warmup` for the donation trade-off.
        Returns the number of executables built/loaded."""
        if not self.params:
            raise ValueError("init() the graph before warmup()")
        store = None
        if export_dir is not None:
            from deeplearning4j_tpu.util.aot_store import AotStore

            store = AotStore(export_dir)
        if shapes is None:
            if self.conf.input_shapes is None:
                raise ValueError(
                    "warmup() needs shapes= or conf.input_shapes")
            if (self._bucketing is None
                    or not isinstance(self._bucketing.batch_buckets, tuple)):
                raise ValueError(
                    "warmup() without shapes= needs explicit batch_buckets "
                    "on the conf (pow2 has no finite bucket list)")
            shapes = [
                [(b,) + tuple(s) for s in self.conf.input_shapes]
                for b in self._bucketing.batch_buckets
            ]
        built = 0
        p_s, s_s, o_s = (_struct_of(self.params), _struct_of(self.states),
                         _struct_of(self.opt_states))
        it_s = jax.ShapeDtypeStruct((), jnp.int32)
        key_s = _struct_of(self._rng_key)
        for entry in shapes:
            if entry and not isinstance(entry[0], (list, tuple)):
                entry = [entry]  # single-input graph, bare shape
            if len(entry) != len(self.conf.inputs):
                raise ValueError(
                    f"warmup entry has {len(entry)} shapes for "
                    f"{len(self.conf.inputs)} graph inputs")
            b = int(entry[0][0])
            ins_s = {
                name: jax.ShapeDtypeStruct(tuple(int(d) for d in shape),
                                           dtype)
                for name, shape in zip(self.conf.inputs, entry)
            }
            labs_s = {
                name: jax.ShapeDtypeStruct((b,) + tuple(self._shape_of[name]),
                                           jnp.float32)
                for name in self.conf.outputs
            }
            # fit always threads a weights vector (ones when unbucketed)
            w_s = jax.ShapeDtypeStruct((b,), jnp.float32)
            if train:
                if self._train_step is None:
                    self._train_step = self._jit_train_step()
                sig = _dispatch_sig(ins_s, labs_s, w_s, None, None)
                if sig not in self._aot_steps:
                    self._aot_steps[sig] = self._aot_build(
                        store, "cg_train_step", sig, self._train_step,
                        (p_s, s_s, o_s, it_s, key_s, ins_s, labs_s, w_s,
                         None, None), {})
                    built += 1
            if inference:
                fsig = (False, _dispatch_sig(ins_s, None))
                if fsig not in self._aot_forward:
                    self._aot_forward[fsig] = self._aot_build(
                        store, "cg_forward", fsig, self._forward_jit,
                        (p_s, s_s, ins_s), {"mask": None})
                    built += 1
        return built

    def _aot_build(self, store, tag, sig, jit_fn, args, kwargs):
        from deeplearning4j_tpu.util.aot_store import aot_build

        return aot_build(store, tag, self.conf.to_json(), sig, jit_fn,
                         args, kwargs)

    # -------------------------------------------------------- cost reporting
    def cost_report(self, batch_size=None, *, shapes=None,
                    dtype=jnp.float32, profile: bool = False, steps: int = 3,
                    peak_flops=None, name: str = "cg",
                    publish: bool = True):
        """Per-node FLOPs / bytes / device-time cost table for ONE train
        step — the ComputationGraph twin of
        :meth:`MultiLayerNetwork.cost_report` (same artifact-extraction
        pipeline: lower().compile() -> cost_analysis() totals + HLO
        op-metadata attribution over the ``layer:<node>`` scopes; analytic
        conf-keyed fallback tagged ``source: analytic``). A SharedLayer node
        shows up as its OWN row (zero params — the source row owns them):
        weight sharing means one layer legitimately appears in two scopes.

        ``shapes``: one full input shape per graph input (incl. batch dim);
        defaults to ``batch_size`` x ``conf.input_shapes``."""
        from deeplearning4j_tpu.util import cost_model as _cm

        if not self.params:
            raise ValueError("init() the graph before cost_report()")
        if shapes is None:
            if self.conf.input_shapes is None:
                raise ValueError(
                    "cost_report() needs shapes= or conf.input_shapes")
            b = int(batch_size or 8)
            shapes = [(b,) + tuple(s) for s in self.conf.input_shapes]
        if shapes and not isinstance(shapes[0], (list, tuple)):
            shapes = [shapes]  # single-input graph, bare shape
        shapes = [tuple(int(d) for d in s) for s in shapes]
        if len(shapes) != len(self.conf.inputs):
            raise ValueError(
                f"cost_report got {len(shapes)} shapes for "
                f"{len(self.conf.inputs)} graph inputs")
        b = shapes[0][0]
        params_by_tag = {
            self._node_tags[n.name]: int(sum(
                int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(self.params[n.name])))
            for n in self.topo if n.is_layer}
        if self._train_step is None:
            self._train_step = self._jit_train_step()
        p_s, s_s, o_s = (_struct_of(self.params), _struct_of(self.states),
                         _struct_of(self.opt_states))
        it_s = jax.ShapeDtypeStruct((), jnp.int32)
        key_s = _struct_of(self._rng_key)
        ins_s = {nm: jax.ShapeDtypeStruct(s, dtype)
                 for nm, s in zip(self.conf.inputs, shapes)}
        labs_s = {nm: jax.ShapeDtypeStruct((b,) + tuple(self._shape_of[nm]),
                                           jnp.float32)
                  for nm in self.conf.outputs}
        w_s = jax.ShapeDtypeStruct((b,), jnp.float32)
        compiled = self._train_step.lower(
            p_s, s_s, o_s, it_s, key_s, ins_s, labs_s, w_s, None,
            None).compile()
        totals: dict = {}
        attrib = None
        source = "analytic"
        try:
            totals = _cm.compiled_totals(compiled)
            attrib = _cm.attribute_hlo(_cm.compiled_text(compiled))
            source = "xla"
        except _cm.CostAnalysisUnavailable:
            pass
        step_time = layer_times = device_time = None
        if profile:
            rng = np.random.default_rng(0)
            ins = {}
            for nm, s in zip(self.conf.inputs, shapes):
                if jnp.issubdtype(dtype, jnp.floating):
                    ins[nm] = jnp.asarray(rng.normal(size=s), dtype=dtype)
                else:
                    ins[nm] = jnp.zeros(s, dtype)
            labs = {nm: jnp.zeros((b,) + tuple(self._shape_of[nm]),
                                  jnp.float32)
                    for nm in self.conf.outputs}
            w = jnp.ones((b,), jnp.float32)
            step_time, layer_times, device_time = _cm.profile_compiled_step(
                compiled,
                (self.params, self.states, self.opt_states,
                 jnp.asarray(0, jnp.int32), self._rng_key),
                (ins, labs, w, None, None), steps=steps,
                inst_map=attrib.inst_map if attrib else None)
        if attrib is not None:
            rows = _cm.rows_from_attribution(attrib, params_by_tag,
                                             layer_times)
        else:
            entries = []
            for n in self.topo:
                if not n.is_layer:
                    continue
                in_shape = self._merged_shape(
                    [tuple(self._shape_of[i]) for i in n.inputs])
                lyr, _pkey = self._resolve_shared(n.node, n.name)
                entries.append((self._node_tags[n.name], lyr, in_shape,
                                params_by_tag.get(
                                    self._node_tags[n.name], 0)))
            rows = _cm.analytic_rows(entries, b)
            totals = {"flops": sum(r.flops for r in rows)}
        report = _cm.CostReport(
            rows=rows, totals=totals, batch=b,
            params_total=self.num_params(), source=source, model=str(name),
            step_time_s=step_time, device_time_s=device_time,
            peak_flops=(peak_flops if peak_flops is not None
                        else _cm.peak_flops_from_env(
                            self.conf.compute_dtype)))
        self._cost_flops_per_example = report.flops_per_step / b
        self._peak_flops = report.peak_flops
        if publish:
            _cm.publish_report(str(name), report)
        return report

    # ---------------------------------------------------------------- output
    def make_forward_fn(self):
        """fn(params, states, x) -> first-output activations, for serving
        wrappers (ParallelInference) — single-input graphs."""
        in_name = self.conf.inputs[0]
        out_name = self.conf.outputs[0]

        def fwd(params, states, x):
            acts, _ = self._forward(params, states, {in_name: x}, training=False)
            return acts[out_name]

        return fwd

    def output(self, *inputs, train: bool = False, mask=None):
        """Forward pass; returns a list of output activations (or a single
        array when the graph has one output — DL4J returns INDArray[]).
        ``train=True`` uses training-mode statistics but no dropout (no RNG
        threaded, matching the reference's output(train)). ``mask``: (B,T)
        feature mask for sequence graphs."""
        real_n = None
        if self._bucketing is not None and mask is None:
            padded = [self._bucketing.pad_inference_batch(x) for x in inputs]
            if any(p.shape[0] != n for p, n in padded):
                real_n = padded[0][1]
            inputs = [p for p, _ in padded]
        ins = dict(zip(self.conf.inputs, [jnp.asarray(x) for x in inputs]))
        mk = None if mask is None else jnp.asarray(mask)
        fwd = self._forward_train_jit if train else self._forward_jit
        aot = self._aot_forward.get((bool(train), _dispatch_sig(ins, mk)))
        acts, _ = (aot or fwd)(self.params, self.states, ins, mask=mk)
        outs = [acts[name] for name in self.conf.outputs]
        if real_n is not None:
            outs = [o[:real_n] for o in outs]
        return outs[0] if len(outs) == 1 else outs

    def feed_forward(self, *inputs):
        """All vertex activations by name (ComputationGraph.feedForward)."""
        ins = dict(zip(self.conf.inputs, [jnp.asarray(x) for x in inputs]))
        acts, _ = self._feed_forward_jit(self.params, self.states, ins)
        return acts

    def score(self, dataset=None, x=None, y=None, mask=None,
              label_mask=None) -> float:
        if dataset is not None:
            x, y = dataset.features, dataset.labels
            if mask is None:
                mask = _mask_dict(dataset, self.conf.inputs,
                                  "features_mask", "features_masks")
            if label_mask is None:
                label_mask = _mask_dict(dataset, self.conf.outputs,
                                        "labels_mask", "labels_masks")
        feats = x if isinstance(x, (list, tuple)) else [x]
        labs = y if isinstance(y, (list, tuple)) else [y]
        real_n = np.shape(feats[0])[0]
        if self._bucketing is not None:
            feats, labs, mask, label_mask, _ = (
                self._bucketing.pad_graph_batch(feats, labs, mask,
                                                label_mask))
        weights = self._dev_weights(np.shape(feats[0])[0], real_n)
        inputs = dict(zip(self.conf.inputs, [jnp.asarray(f) for f in feats]))
        labels = dict(zip(self.conf.outputs, [jnp.asarray(l) for l in labs]))
        loss = self._loss_eval(
            self.params, self.states, inputs, labels,
            _as_mask(mask), _as_mask(label_mask), weights)
        return float(loss)

    @functools.cached_property
    def _loss_eval(self):
        """Inference-mode loss (no dropout, running batchnorm stats) —
        MultiLayerNetwork.score parity."""
        out_names = set(self.conf.outputs)

        def eval_loss(params, states, inputs, labels, mask, label_mask,
                      weights=None):
            note_trace("ComputationGraph.loss_eval", inputs, labels, mask,
                       label_mask, weights)
            acts = {k: self._cast(v) for k, v in inputs.items()}
            cparams = self._cast_params(params)
            produced = dict(mask) if isinstance(mask, dict) else None
            loss = 0.0
            for n in self.topo:
                mk = self._arriving_mask(produced, n, mask)
                if produced is not None:
                    produced[n.name] = mk
                if not n.is_layer:
                    acts[n.name] = n.node.apply(*self._gather_input(acts, n))
                    continue
                x = self._gather_input(acts, n)
                if n.name in out_names:
                    lm = (label_mask.get(n.name)
                          if isinstance(label_mask, dict) else label_mask)
                    with cmod.layer_scope(self._node_tags[n.name]):
                        loss = loss + n.node.compute_loss(
                            cparams[n.name], states[n.name], x,
                            labels[n.name], training=False, weights=weights,
                            **self._loss_mask_kw(n.node, mk, lm, x),
                        )
                    acts[n.name] = x
                else:
                    with cmod.layer_scope(self._node_tags[n.name]):
                        h, _ = n.node.apply(
                            cparams[n.name], states[n.name], x,
                            training=False, **self._mask_kw(n.node, mk, x)
                        )
                    acts[n.name] = h
            return loss

        return jax.jit(eval_loss)

    # -------------------------------------------------------------- evaluate
    def evaluate(self, iterator):
        from deeplearning4j_tpu.eval import Evaluation

        ev = Evaluation()
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            feats = ds.features if isinstance(ds.features, (list, tuple)) else [ds.features]
            preds = self.output(*feats,
                                mask=getattr(ds, "features_mask", None))
            p0 = preds[0] if isinstance(preds, list) else preds
            l0 = ds.labels[0] if isinstance(ds.labels, (list, tuple)) else ds.labels
            ev.eval(np.asarray(l0), np.asarray(p0))
        return ev

    # -------------------------------------------------------------- plumbing
    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def get_score(self) -> float:
        return float(self.score_value)

    @property
    def score_(self):
        return float(self.score_value)
