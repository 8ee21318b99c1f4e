"""MultiLayerNetwork — the linear-stack network with fit/output/score/evaluate.

Reference parity: org/deeplearning4j/nn/multilayer/MultiLayerNetwork.java
(~4k LoC: fitHelper → Solver → StochasticGradientDescent →
computeGradientAndScore → per-layer activate/backpropGradient → updater →
step; SURVEY.md §3.1) — path-cite, mount empty this round.

TPU-native collapse: the entire minibatch iteration — forward, loss, reverse
AD, updater, parameter step — is ONE jitted function, compiled once per input
shape and executed as a single XLA program on device. The reference crosses
JNI per op and keeps params/gradients as flattened off-heap views; here
params/optimizer state live on device as pytrees and are donated
(buffer-aliased) across steps, the PJRT-era equivalent of workspaces.

Listeners fire on the host with the scalar loss (fetching only the scalar —
one small transfer per iteration, matching the reference's
TrainingListener.iterationDone cadence).
"""

from __future__ import annotations

import functools
import inspect
import time
from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.data.bucketing import BucketingPolicy
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn import updaters as upd
from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
from deeplearning4j_tpu.util import cost_model as cmod
from deeplearning4j_tpu.util import telemetry as tm
from deeplearning4j_tpu.util.compile_watcher import note_trace


def _struct_of(tree):
    """Pytree → matching ShapeDtypeStruct tree (AOT warmup operands)."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def _dispatch_sig(*args):
    """Shape/dtype signature of the data operands of one step/forward call —
    the key for the AOT-compiled executable table (warmup). Handles arrays,
    ShapeDtypeStructs, None, and (for ComputationGraph) dicts/lists of them."""
    from deeplearning4j_tpu.util.compile_watcher import _shape_of

    return tuple(_shape_of(a) for a in args)


class MultiLayerNetwork:
    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers = conf.layers
        self.params: List[dict] = []
        self.states: List[dict] = []
        self.opt_states: List[Any] = []
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
        self._updaters = [
            (lyr.updater or conf.updater or upd.Sgd(0.1)) for lyr in conf.layers
        ]
        # fused donated optimizer apply (docs/KERNELS.md#fused-optimizer-
        # apply): built in init() once params exist; None = per-leaf walk
        self._fused = None
        if (getattr(conf, "loss_scale", "none") != "none"
                and not getattr(conf, "fused_update", False)):
            raise ValueError(
                "loss_scale requires fused_update=True — the scale "
                "automaton lives in the fused optimizer state")
        self._rng_key = jax.random.PRNGKey(conf.seed)
        # Mask plumbing (setLayerMaskArrays/feedForwardMaskArray parity):
        # which layers' apply()/compute_loss() accept a mask kwarg.
        self._mask_aware = [
            "mask" in inspect.signature(lyr.apply).parameters for lyr in self.layers
        ]
        self._loss_mask_aware = hasattr(self.layers[-1], "compute_loss") and (
            "mask" in inspect.signature(self.layers[-1].compute_loss).parameters
        )
        self._segments = self._build_segments()
        # Shape bucketing (data/bucketing.py): ragged batches pad to a fixed
        # bucket set with 0-weighted rows; None when both knobs are off.
        self._bucketing = BucketingPolicy.from_conf(conf)
        # AOT-warmed executables (warmup()): dispatch signature → compiled.
        self._aot_steps: dict = {}
        self._aot_forward: dict = {}
        # Cost attribution (util/cost_model.py): one stable scope tag per
        # layer, threaded through every trace as named_scope("layer:<tag>")
        # so the compiled HLO (and the profiler's device events) attribute
        # per layer. Index prefix keeps tags unique under repeated names.
        self._layer_tags = [
            cmod.sanitize_tag(f"{i}_{lyr.name or type(lyr).__name__}")
            for i, lyr in enumerate(self.layers)
        ]
        self._cost_flops_per_example = None  # set by cost_report()
        self._peak_flops = None
        # Device-resident 0/1 weight vectors keyed by (size, real-count):
        # fit ALWAYS threads per-example weights (ones when unbucketed), so
        # bucketed and unbucketed batches execute the SAME weighted-loss
        # program — the bit-identity invariant (data/bucketing.py
        # dev_weights).
        self._w_cache: dict = {}
        self._last_fit_ns = None  # step-cadence stamp (telemetry histogram)

    def _dev_weights(self, size: int, real: int):
        from deeplearning4j_tpu.data.bucketing import dev_weights

        return dev_weights(self._w_cache, size, real)

    # ------------------------------------------- fusion-boundary segmentation
    def _build_segments(self):
        """Partition the layer stack into remat/fusion stages
        (util/xla_tuning.py). Returns (list of (start, end) index pairs,
        tail_start) or None when no policy/barrier is configured. The loss
        head (and anything after the last boundary) always runs unwrapped."""
        conf = self.conf
        active = (getattr(conf, "remat_policy", None) not in (None, "none")
                  or getattr(conf, "stage_barriers", False))
        if not active:
            return None
        n = len(self.layers)
        bounds = sorted(set(conf.remat_stages or ()))
        for b in bounds:
            if not 0 < b < n:
                raise ValueError(
                    f"remat stage boundary {b} out of range (1..{n - 1}); "
                    "the loss head always runs in the unwrapped tail")
        if not bounds:
            bounds = [n - 1]  # whole body before the loss head = one stage
        spans, start = [], 0
        for b in bounds:
            spans.append((start, b))
            start = b
        return spans, start

    # ------------------------------------------------------------------ init
    def init(self, input_shape=None) -> "MultiLayerNetwork":
        """Initialize params/state (MultiLayerNetwork.init parity)."""
        shape = tuple(input_shape or self.conf.input_shape or ())
        if not shape:
            raise ValueError("input_shape required (set_input_type on the builder)")
        key = jax.random.PRNGKey(self.conf.seed)
        self.params, self.states = [], []
        cur = shape
        for lyr in self.layers:
            key, sub = jax.random.split(key)
            p, s = lyr.initialize(sub, cur)
            self.params.append(p)
            self.states.append(s)
            cur = lyr.output_shape(cur)
        if getattr(self.conf, "fused_update", False):
            self._fused = upd.FusedUpdateEngine(
                self._updaters, self.params,
                loss_scale=getattr(self.conf, "loss_scale", "none"),
                loss_scale_value=getattr(self.conf, "loss_scale_value",
                                         2.0 ** 15),
                growth_interval=getattr(self.conf, "loss_scale_growth", 2000))
            self.opt_states = self._fused.init_state(self.params)
        else:
            self.opt_states = [
                u.init_state(p) for u, p in zip(self._updaters, self.params)
            ]
        self._output_shape = cur
        self._train_step = self._build_train_step()
        self._forward_jit = jax.jit(functools.partial(self._forward, training=False))
        self._forward_train_jit = jax.jit(functools.partial(self._forward, training=True))
        return self

    def num_params(self) -> int:
        return sum(int(np.prod(x.shape)) for p in self.params for x in jax.tree_util.tree_leaves(p))

    # --------------------------------------------------------------- forward
    def _kscope(self):
        """Kernel-dispatch scope for every trace of this net's layers
        (ops/kernels — docs/KERNELS.md). conf.kernel_impl None leaves the
        ambient DL4J_TPU_KERNEL_IMPL / auto resolution in place."""
        from deeplearning4j_tpu.ops import kernels as _kern

        return _kern.impl_scope(getattr(self.conf, "kernel_impl", None))

    def _cast(self, x):
        if self.conf.compute_dtype == "bfloat16" and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(jnp.bfloat16)
        return x

    def _cast_params(self, params):
        if self.conf.compute_dtype != "bfloat16":
            return params
        return jax.tree_util.tree_map(
            lambda p: p.astype(jnp.bfloat16) if jnp.issubdtype(p.dtype, jnp.floating) else p,
            params,
        )

    def _forward(self, params, states, x, *, training, keys=None, mask=None):
        note_trace("MultiLayerNetwork.forward", x, mask)  # trace-time only
        with self._kscope():
            return self._forward_body(params, states, x, training=training,
                                      keys=keys, mask=mask)

    def _forward_body(self, params, states, x, *, training, keys=None,
                      mask=None):
        h = self._cast(x)
        cparams = self._cast_params(params)
        new_states = []
        for i, lyr in enumerate(self.layers):
            k = keys[i] if keys is not None else None
            kw = {}
            if (
                mask is not None
                and self._mask_aware[i]
                and h.ndim == 3
                and mask.shape[:2] == h.shape[:2]
            ):
                kw["mask"] = mask
            with cmod.layer_scope(self._layer_tags[i]):
                h, ns = lyr.apply(cparams[i], states[i], h,
                                  training=training, key=k, **kw)
            new_states.append(ns)
            if h.ndim < 3:
                mask = None  # time axis consumed (LastTimeStep/GlobalPooling)
        return h, new_states

    def _loss_body(self, params, states, carries, x, y, keys, weights, mask,
                   label_mask, training=True):
        """The ONE forward+loss body shared by training (_loss), evaluation
        (_loss_eval), and truncated BPTT (_tbptt_step). ``carries`` is None
        for whole-sequence paths; a per-layer carry list routes recurrent
        layers through ``apply_seq`` (TBPTT segments). ``weights``: optional
        per-example loss weights (ParallelWrapper uses zeros to mask padded
        examples exactly). ``mask``/``label_mask``: (B,T) masks."""
        with self._kscope():
            return self._loss_body_impl(params, states, carries, x, y, keys,
                                        weights, mask, label_mask, training)

    def _loss_body_impl(self, params, states, carries, x, y, keys, weights,
                        mask, label_mask, training=True):
        h = self._cast(x)
        cparams = self._cast_params(params)
        new_states, new_carries = [], []
        fmask = mask
        for i, lyr in enumerate(self.layers[:-1]):
            seg_mask = (
                fmask
                if (fmask is not None and h.ndim == 3
                    and fmask.shape[:2] == h.shape[:2])
                else None
            )
            if carries is not None and self._is_recurrent(lyr):
                with cmod.layer_scope(self._layer_tags[i]):
                    h = lyr._maybe_dropout(h, training, keys[i])
                    h, c = lyr.apply_seq(cparams[i], h, carries[i],
                                         mask=seg_mask, training=training,
                                         key=keys[i])
                new_carries.append(c)
                new_states.append(states[i])
            else:
                kw = {}
                if seg_mask is not None and self._mask_aware[i]:
                    kw["mask"] = seg_mask
                with cmod.layer_scope(self._layer_tags[i]):
                    h, ns = lyr.apply(cparams[i], states[i], h,
                                      training=training, key=keys[i], **kw)
                new_states.append(ns)
                new_carries.append(None if carries is None else carries[i])
            if h.ndim < 3:
                fmask = None
        out = self.layers[-1]
        if not hasattr(out, "compute_loss"):
            raise ValueError("last layer must be an OutputLayer/LossLayer")
        loss_kw = {}
        lm = label_mask if label_mask is not None else fmask
        if lm is not None and self._loss_mask_aware:
            loss_kw["mask"] = lm
        if weights is not None:
            loss_kw["weights"] = weights
        with cmod.layer_scope(self._layer_tags[-1]):
            loss = out.compute_loss(
                cparams[-1], states[-1], h, y, training=training,
                key=keys[-1], **loss_kw,
            )
        new_states.append(states[-1])
        new_carries.append(None if carries is None else carries[-1])
        reg = sum(
            (lyr.regularization(params[i]) for i, lyr in enumerate(self.layers)),
            start=jnp.asarray(0.0),
        )
        return loss.astype(jnp.float32) + reg, (new_states, new_carries)

    def _loss(self, params, states, x, y, keys, weights=None, mask=None,
              label_mask=None):
        if self._segments is not None and mask is None and label_mask is None:
            # fusion-boundary path (util/xla_tuning.py): masked sequence
            # nets keep the plain path — remat targets the conv stacks
            return self._loss_remat(params, states, x, y, keys, weights)
        loss, (new_states, _) = self._loss_body(
            params, states, None, x, y, keys, weights, mask, label_mask)
        return loss, new_states

    def _loss_remat(self, params, states, x, y, keys, weights=None):
        """_loss with the layer stack split into remat/fusion stages: each
        stage runs inside ``jax.checkpoint`` under the configured policy,
        ``stage_barriers`` fences fusion at the boundaries. Exact same values
        and gradients as the plain path (remat only changes what XLA keeps
        live across fwd/bwd)."""
        with self._kscope():
            return self._loss_remat_impl(params, states, x, y, keys, weights)

    def _loss_remat_impl(self, params, states, x, y, keys, weights=None):
        from deeplearning4j_tpu.util import xla_tuning

        spans, tail_start = self._segments
        wrap, policy = xla_tuning.resolve_policy(self.conf.remat_policy)
        h = self._cast(x)
        cparams = self._cast_params(params)
        new_states = [None] * len(self.layers)

        def stage_runner(a, b):
            def run(seg_params, seg_states, seg_keys, h):
                st = []
                for j, i in enumerate(range(a, b)):
                    with cmod.layer_scope(self._layer_tags[i]):
                        h, ns = self.layers[i].apply(
                            seg_params[j], seg_states[j], h, training=True,
                            key=seg_keys[j])
                    st.append(ns)
                return h, st
            return run

        for a, b in spans:
            run = stage_runner(a, b)
            if wrap:
                run = jax.checkpoint(run, policy=policy)
            h, st = run([cparams[i] for i in range(a, b)],
                        [states[i] for i in range(a, b)],
                        [keys[i] for i in range(a, b)], h)
            new_states[a:b] = st
            if self.conf.stage_barriers:
                h = xla_tuning.barrier(h)
        for i in range(tail_start, len(self.layers) - 1):
            with cmod.layer_scope(self._layer_tags[i]):
                h, ns = self.layers[i].apply(cparams[i], states[i], h,
                                             training=True, key=keys[i])
            new_states[i] = ns
        out = self.layers[-1]
        if not hasattr(out, "compute_loss"):
            raise ValueError("last layer must be an OutputLayer/LossLayer")
        loss_kw = {} if weights is None else {"weights": weights}
        with cmod.layer_scope(self._layer_tags[-1]):
            loss = out.compute_loss(
                cparams[-1], states[-1], h, y, training=True, key=keys[-1],
                **loss_kw,
            )
        new_states[-1] = states[-1]
        reg = sum(
            (lyr.regularization(params[i]) for i, lyr in enumerate(self.layers)),
            start=jnp.asarray(0.0),
        )
        return loss.astype(jnp.float32) + reg, new_states

    # ------------------------------------------------------------ train step
    def make_step_fn(self, weighted: bool = False):
        """The un-jitted train step (forward+AD+updaters). ParallelWrapper
        reuses this under mesh shardings; ``weighted`` adds a per-example
        loss-weight argument."""
        updaters = self._updaters
        n_layers = len(self.layers)
        engine = self._fused

        def step(params, states, opt_states, iteration, x, y, key, weights=None,
                 mask=None, label_mask=None):
            keys = list(jax.random.split(key, n_layers))
            scale = engine.current_scale(opt_states) if engine is not None \
                else None
            # loss scaling (arXiv:1710.03740): gradients come out scale x
            # true (the fused apply unscales them); the aux threads the
            # UNSCALED loss for reporting. One trace shape with/without.
            (_, (new_states, loss)), grads = jax.value_and_grad(
                upd.FusedUpdateEngine.wrap_scaled(self._loss, scale),
                has_aux=True
            )(params, states, x, y, keys, weights, mask, label_mask)
            with cmod.optimizer_scope():  # cost attribution: (optimizer) row
                if engine is not None:
                    new_params, new_opts = engine.apply(
                        params, grads, opt_states, iteration)
                else:
                    new_params, new_opts = [], []
                    for i in range(n_layers):
                        if not grads[i]:
                            new_params.append(params[i])
                            new_opts.append(opt_states[i])
                            continue
                        p, s = upd.apply_updater(
                            updaters[i], params[i], grads[i], opt_states[i],
                            iteration
                        )
                        new_params.append(p)
                        new_opts.append(s)
            return new_params, new_states, new_opts, loss

        if weighted:
            return step
        return lambda params, states, opt_states, iteration, x, y, key, \
            mask=None, label_mask=None: step(
            params, states, opt_states, iteration, x, y, key,
            mask=mask, label_mask=label_mask,
        )

    def _build_train_step(self):
        """Jit the step with iteration and RNG-key evolution INSIDE the
        program: per-step host work is then a single enqueue (no scalar
        host->device transfer for the iteration counter, no tiny device
        program for jax.random.split — each is a dispatch of its own
        between steps)."""
        base = self.make_step_fn(weighted=True)

        def step(params, states, opt_states, iteration, key, x, y,
                 weights=None, mask=None, label_mask=None):
            # trace-time only: one retrace == one line in the CompileWatcher
            note_trace("MultiLayerNetwork.train_step", x, y, weights, mask,
                       label_mask)
            new_key, sub = jax.random.split(key)
            p, s, o, loss = base(params, states, opt_states, iteration, x, y,
                                 sub, weights=weights, mask=mask,
                                 label_mask=label_mask)
            return p, s, o, loss, iteration + 1, new_key

        return jax.jit(step, donate_argnums=(0, 1, 2, 3, 4))

    # ------------------------------------------------------------------- fit
    def fit(self, data, labels=None, epochs: int = 1):
        """fit(x, y) | fit(DataSet) | fit(iterator) | fit(iterator, epochs=N)."""
        if labels is not None:
            for _ in range(epochs):
                self._fit_batch(jnp.asarray(data), jnp.asarray(labels))
                self._end_epoch()
            return self
        from deeplearning4j_tpu.data.dataset import DataSet

        if isinstance(data, DataSet):  # fit(DataSet) parity: one-batch iterator
            data = [data]
        for _ in range(epochs):
            if hasattr(data, "reset"):
                data.reset()
            for ds in data:
                # arrays pass through untouched: _fit_batch pads (bucketing)
                # on the HOST before the one host->device transfer
                self._fit_batch(
                    ds.features, ds.labels,
                    mask=getattr(ds, "features_mask", None),
                    label_mask=getattr(ds, "labels_mask", None),
                )
            self._end_epoch()
        return self

    def _end_epoch(self):
        self._dispatcher.flush()  # epoch-end callbacks see a complete epoch
        self.epoch += 1
        for lst in self.listeners:
            if hasattr(lst, "on_epoch_end"):
                lst.on_epoch_end(self)

    # -------------------------------------------------------- truncated BPTT
    def _is_recurrent(self, lyr) -> bool:
        return hasattr(lyr, "apply_seq") and hasattr(lyr, "init_carry")

    @functools.cached_property
    def _tbptt_step(self):
        """One jitted train step over a TBPTT segment: recurrent layers take
        carries in and hand carries out; gradients stop at segment boundaries
        because the incoming carry is a plain (non-differentiated) argument.
        (MultiLayerNetwork.doTruncatedBPTT parity — SURVEY.md §5.7.)"""
        updaters = self._updaters
        n_layers = len(self.layers)
        engine = self._fused

        def step(params, states, opt_states, carries, iteration, x, y, key,
                 mask, label_mask, weights=None):
            note_trace("MultiLayerNetwork.tbptt_step", x, y, weights, mask,
                       label_mask)
            keys = list(jax.random.split(key, n_layers))
            scale = engine.current_scale(opt_states) if engine is not None \
                else None
            (_, ((new_states, new_carries), loss)), grads = \
                jax.value_and_grad(
                    upd.FusedUpdateEngine.wrap_scaled(self._loss_body, scale),
                    has_aux=True)(
                    params, states, carries, x, y, keys, weights, mask,
                    label_mask)
            with cmod.optimizer_scope():  # cost attribution: (optimizer) row
                if engine is not None:
                    new_params, new_opts = engine.apply(
                        params, grads, opt_states, iteration)
                else:
                    new_params, new_opts = [], []
                    for i in range(n_layers):
                        if not grads[i]:
                            new_params.append(params[i])
                            new_opts.append(opt_states[i])
                            continue
                        p, s = upd.apply_updater(
                            updaters[i], params[i], grads[i], opt_states[i],
                            iteration)
                        new_params.append(p)
                        new_opts.append(s)
            return new_params, new_states, new_opts, new_carries, loss

        return jax.jit(step, donate_argnums=(0, 1, 2))

    def _init_carries(self, batch_size, dtype):
        return [
            lyr.init_carry(batch_size, dtype) if self._is_recurrent(lyr) else None
            for lyr in self.layers
        ]

    def _fit_batch_tbptt(self, x, y, mask=None, label_mask=None):
        """Segment loop: carries flow forward, gradients are truncated at
        segment boundaries; each segment applies the updater and counts as an
        iteration (update-per-segment semantics — Adam bias correction and
        LR schedules advance per update, as in the reference)."""
        k = self.conf.tbptt_length
        real_n = np.shape(x)[0]
        if self._bucketing is not None:
            # batch axis: pad rows + 0/1 weights (bit-identical, like the
            # non-TBPTT path). Time axis is NOT whole-sequence padded here —
            # each segment pads individually below, so every tail remainder
            # lands on the same (B, k) signature. The whole segment loop
            # stays in HOST numpy (slice/pad on host, ONE upload per step) —
            # slicing a device array per segment would sync device->host
            # for every pad_segment call.
            x = np.asarray(x)
            y = np.asarray(y)
            npad = self._bucketing.bucket_batch(real_n)
            if npad != real_n:
                pad = lambda a: (None if a is None else  # noqa: E731
                                 np.pad(np.asarray(a),
                                        [(0, npad - real_n)] +
                                        [(0, 0)] * (np.ndim(a) - 1)))
                x, y, mask, label_mask = pad(x), pad(y), pad(mask), pad(label_mask)
        else:
            # unbucketed: device-resident slicing (no host round trips)
            x = jnp.asarray(x)
            y = jnp.asarray(y)
        weights = self._dev_weights(np.shape(x)[0], real_n)
        T = x.shape[1]
        # carries live in the compute dtype: an fp32 carry would promote the
        # recurrent matmuls and silently drop the bf16/MXU policy
        carries = self._init_carries(x.shape[0], self._cast(x).dtype)
        losses = []
        for s in range(0, T, k):
            xs = x[:, s:s + k]
            ys = y[:, s:s + k] if y.ndim == 3 else y
            ms = None if mask is None else mask[:, s:s + k]
            lms = None if label_mask is None else label_mask[:, s:s + k]
            if self._bucketing is not None:
                # pad the tail remainder up to k (masks zero over the pad)
                # AND attach all-ones masks to full segments, so every
                # segment — tail or not — shares ONE jit signature
                (xs, ys), ms, lms = self._bucketing.pad_segment(
                    (xs, ys), ms, lms, k)
            self._rng_key, sub = jax.random.split(self._rng_key)
            with tm.step_span("mln.tbptt_step", iteration=self.iteration,
                              segment_start=s):
                (self.params, self.states, self.opt_states, carries, loss) = (
                    self._tbptt_step(self.params, self.states,
                                     self.opt_states, carries,
                                     jnp.asarray(self.iteration), xs, ys,
                                     sub, ms, lms, weights))
            self.iteration += 1
            losses.append(loss)
        self._dispatcher.flush()  # keep cross-path dispatch ordering intact
        self.score_value = float(jnp.mean(jnp.stack(losses)))
        self.last_features = x  # full sequence, not the last TBPTT segment
        for lst in self.listeners:
            lst.iteration_done(self, self.iteration, self.epoch)

    # ------------------------------------------------- stateful rnn inference
    def rnn_time_step(self, x):
        """Stateful step-by-step inference (rnnTimeStep parity): carries
        persist across calls. ``x``: (B, T, F) or (B, F) for one step."""
        from deeplearning4j_tpu.nn.recurrent import Bidirectional

        for lyr in self.layers:
            if isinstance(lyr, Bidirectional):
                # the backward direction needs the FUTURE sequence — stepping
                # is ill-defined (the reference's rnnTimeStep throws too)
                raise ValueError("rnn_time_step does not support Bidirectional layers")
        x = self._cast(jnp.asarray(x))
        cparams = self._cast_params(self.params)
        squeeze = x.ndim == 2
        if squeeze:
            x = x[:, None]
        carries = getattr(self, "_rnn_carries", None)
        if carries is not None:
            for c in carries:
                for leaf in jax.tree_util.tree_leaves(c):
                    if leaf.shape[0] != x.shape[0]:
                        raise ValueError(
                            f"rnn_time_step batch size changed ({leaf.shape[0]}"
                            f" -> {x.shape[0]}); call rnn_clear_previous_state()")
        else:
            carries = self._init_carries(x.shape[0], x.dtype)
        h = x
        new_carries = []
        for i, lyr in enumerate(self.layers):
            if self._is_recurrent(lyr):
                h, c = lyr.apply_seq(cparams[i], h, carries[i], training=False)
                new_carries.append(c)
            else:
                h, _ = lyr.apply(cparams[i], self.states[i], h, training=False)
                new_carries.append(None)
        self._rnn_carries = new_carries
        return h[:, -1] if (squeeze and h.ndim == 3) else h

    def rnn_clear_previous_state(self):
        """rnnClearPreviousState parity."""
        self._rnn_carries = None

    def _fit_batch(self, x, y, mask=None, label_mask=None):
        # fit() passes DataSet arrays through raw (bucketing pads on the
        # host); coerce list-typed inputs here without touching arrays that
        # are already on device (np.asarray on a jnp array would sync)
        if not hasattr(x, "ndim"):
            x = np.asarray(x)
        if not hasattr(y, "ndim"):
            y = np.asarray(y)
        if mask is not None and not hasattr(mask, "ndim"):
            mask = np.asarray(mask)
        if label_mask is not None and not hasattr(label_mask, "ndim"):
            label_mask = np.asarray(label_mask)
        if (self.conf.tbptt_length and x.ndim == 3 and y.ndim == 3
                and x.shape[1] > self.conf.tbptt_length):
            # per-sequence (2-D) labels cannot be segmented: fall back to
            # whole-sequence BPTT, as the reference's doTruncatedBPTT does
            return self._fit_batch_tbptt(x, y, mask=mask, label_mask=label_mask)
        real_n = np.shape(x)[0]
        if self._bucketing is not None:
            # host-side padding (numpy): no pad-program compiles, and the
            # weights vector is attached to EVERY batch so the epoch keeps
            # one jit signature per bucket (ragged tail => 0 extra traces)
            x, y, mask, label_mask, _ = self._bucketing.pad_batch(
                x, y, mask, label_mask)
        if self._train_step is None:  # cleared by external training masters
            self._train_step = self._build_train_step()
        if self._it_dev is None or self._it_sync != self.iteration:
            self._it_dev = jax.device_put(jnp.asarray(self.iteration, jnp.int32))
        x = jnp.asarray(x)
        y = jnp.asarray(y)
        # always-weighted: ones over the real rows, zeros over padding
        weights = self._dev_weights(x.shape[0], real_n)
        mask = None if mask is None else jnp.asarray(mask)
        label_mask = None if label_mask is None else jnp.asarray(label_mask)
        # AOT-warmed executable for this signature if warmup() built one
        # (zero retrace/compile risk on the serving path), else the jit path
        step = self._aot_steps.get(
            _dispatch_sig(x, y, weights, mask, label_mask), self._train_step)
        if tm.enabled():
            now = time.time_ns()
            if self._last_fit_ns is not None:
                dt = (now - self._last_fit_ns) / 1e9
                tm.observe("train.step_seconds", dt, model="mln")
                if dt > 0:
                    # cost attribution gauges (docs/OBSERVABILITY.md): real
                    # throughput each step; MFU once cost_report() measured
                    # the program's FLOPs and a peak is configured
                    tm.gauge("train.examples_per_sec", real_n / dt,
                             model="mln")
                    if self._cost_flops_per_example and self._peak_flops:
                        tm.gauge(
                            "train.model_flops_utilization",
                            self._cost_flops_per_example * x.shape[0]
                            / dt / self._peak_flops, model="mln")
            self._last_fit_ns = now
            tm.counter("train.steps_total", model="mln")
        # dispatch span with XLA trace/compile sub-spans when this shape
        # retraced (CompileWatcher markers — docs/OBSERVABILITY.md)
        with tm.step_span("mln.train_step", iteration=self.iteration):
            (self.params, self.states, self.opt_states, loss,
             self._it_dev, self._rng_key) = step(
                self.params, self.states, self.opt_states, self._it_dev,
                self._rng_key, x, y, weights, mask, label_mask,
            )
        self.score_value = loss  # fetched lazily; float() forces transfer
        # activation-stats listeners must never see fabricated padding rows
        self.last_features = x if real_n == x.shape[0] else x[:real_n]
        self.iteration += 1
        self._it_sync = self.iteration
        # sync_every=1: immediate dispatch (legacy cadence); >1: the device
        # loss is queued and listeners fire in coalesced windows — one host
        # round-trip per window instead of a sync point every iteration
        self._dispatcher.iteration_done(loss, self.iteration, self.epoch)

    # -------------------------------------------------------------- pretrain
    def pretrain(self, data, epochs: int = 1):
        """MultiLayerNetwork.pretrain(DataSetIterator) parity: layerwise
        unsupervised training of every pretrain-capable layer (AutoEncoder,
        VariationalAutoencoder), in order. Labels are ignored."""
        for i, lyr in enumerate(self.layers):
            if getattr(lyr, "is_pretrain_layer", lambda: False)():
                self.pretrain_layer(i, data, epochs=epochs)
        return self

    def pretrain_layer(self, i: int, data, epochs: int = 1):
        """pretrainLayer(int, DataSetIterator) parity: train ONE layer on its
        unsupervised objective, inputs fed forward (inference mode) through
        the layers below. One jitted loss+grad+update program per layer."""
        from deeplearning4j_tpu.data.dataset import DataSet

        lyr = self.layers[i]
        if not getattr(lyr, "is_pretrain_layer", lambda: False)():
            raise ValueError(
                f"layer {i} ({type(lyr).__name__}) is not a pretrain layer")
        updater = self._updaters[i]
        opt = updater.init_state(self.params[i])
        layers = self.layers
        below_p = [self.params[j] for j in range(i)]
        below_s = [self.states[j] for j in range(i)]

        @jax.jit
        def step(p, opt_state, iteration, x, key):
            for j in range(i):
                x, _ = layers[j].apply(below_p[j], below_s[j], x,
                                       training=False)
            loss, g = jax.value_and_grad(lyr.pretrain_loss)(p, x, key)
            new_p, new_opt = upd.apply_updater(updater, p, g, opt_state,
                                               iteration)
            return new_p, new_opt, loss

        if isinstance(data, (np.ndarray, jnp.ndarray)):
            data = [DataSet(np.asarray(data), None)]
        elif isinstance(data, DataSet):
            data = [data]
        loss = None
        it_count = 0
        for _ in range(epochs):
            if hasattr(data, "reset"):
                data.reset()
            for ds in data:
                x = jnp.asarray(ds.features if hasattr(ds, "features") else ds)
                self._rng_key, sub = jax.random.split(self._rng_key)
                self.params[i], opt, loss = step(
                    self.params[i], opt, jnp.asarray(it_count), x, sub)
                it_count += 1
        if loss is not None:
            self.score_value = loss
        return self

    # ------------------------------------------------------------ AOT warmup
    def warmup(self, shapes=None, *, train=True, inference=True,
               dtype=jnp.float32, export_dir=None):
        """Ahead-of-time compile the train step and/or inference forward for
        every bucket BEFORE traffic arrives (``jit(...).lower().compile()``),
        so the first real batch executes a pre-built binary instead of
        paying trace+compile in the serving path (docs/COMPILE_CACHE.md).

        ``shapes``: iterable of full input shapes INCLUDING the batch dim
        (e.g. ``[(8, 28, 28, 1), (16, 28, 28, 1)]``). Defaults to the
        explicit ``batch_buckets`` list x ``conf.input_shape``. The compiled
        executables are kept per signature and dispatched directly by
        fit()/output(); with a persistent compilation cache enabled the
        lowering also lands on disk for the NEXT process.

        ``export_dir``: directory for the on-disk AOT LOWERING store
        (util/aot_store.py): the first process serializes the lowered
        module, a later process deserializes it and skips the Python
        trace + MLIR build entirely — combined with the persistent
        compilation cache, a restarted server's warmup is deserialize-only.
        Trade-off: the loaded path does not donate buffers (an extra
        params/opt-state copy per step) — right for serving and short
        fine-tunes. Returns the number of executables built/loaded."""
        if not self.params:
            raise ValueError("init() the network before warmup()")
        if shapes is None:
            if self.conf.input_shape is None:
                raise ValueError("warmup() needs shapes= or conf.input_shape")
            if (self._bucketing is None
                    or not isinstance(self._bucketing.batch_buckets, tuple)):
                raise ValueError(
                    "warmup() without shapes= needs explicit batch_buckets "
                    "on the conf (pow2 has no finite bucket list)")
            shapes = [(b,) + tuple(self.conf.input_shape)
                      for b in self._bucketing.batch_buckets]
        store = None
        if export_dir is not None:
            from deeplearning4j_tpu.util.aot_store import AotStore

            store = AotStore(export_dir)
        built = 0
        p_s, s_s, o_s = (_struct_of(self.params), _struct_of(self.states),
                         _struct_of(self.opt_states))
        it_s = jax.ShapeDtypeStruct((), jnp.int32)
        key_s = _struct_of(self._rng_key)
        for shape in shapes:
            shape = tuple(int(d) for d in shape)
            b = shape[0]
            x_s = jax.ShapeDtypeStruct(shape, dtype)
            y_s = jax.ShapeDtypeStruct((b,) + tuple(self._output_shape),
                                       jnp.float32)
            # fit always threads a weights vector (ones when unbucketed)
            w_s = jax.ShapeDtypeStruct((b,), jnp.float32)
            if train:
                if self._train_step is None:
                    self._train_step = self._build_train_step()
                sig = _dispatch_sig(x_s, y_s, w_s, None, None)
                if sig not in self._aot_steps:
                    self._aot_steps[sig] = self._aot_build(
                        store, "mln_train_step", sig, self._train_step,
                        (p_s, s_s, o_s, it_s, key_s, x_s, y_s, w_s, None,
                         None), {})
                    built += 1
            if inference:
                # inference path pads rows but carries no weights; both
                # train=False and train=True forwards share one lowering rule
                fsig = (False, _dispatch_sig(x_s, None))
                if fsig not in self._aot_forward:
                    self._aot_forward[fsig] = self._aot_build(
                        store, "mln_forward", fsig, self._forward_jit,
                        (p_s, s_s, x_s), {"mask": None})
                    built += 1
        return built

    def _aot_build(self, store, tag, sig, jit_fn, args, kwargs):
        from deeplearning4j_tpu.util.aot_store import aot_build

        return aot_build(store, tag, self.conf.to_json(), sig, jit_fn,
                         args, kwargs)

    # -------------------------------------------------------- cost reporting
    def cost_report(self, batch_size=None, *, shape=None, dtype=jnp.float32,
                    profile: bool = False, steps: int = 3, peak_flops=None,
                    name: str = "mln", publish: bool = True):
        """Per-layer FLOPs / bytes / device-time cost table for ONE train
        step (docs/OBSERVABILITY.md#cost-attribution--mfu). Static costs
        come from the compiled executable itself — ``lower().compile()``
        then ``cost_analysis()`` totals + HLO op-metadata attribution over
        the ``layer:`` named scopes (util/cost_model.py); backends without
        XLA cost analysis fall back to analytic conf-keyed formulas, tagged
        ``source: analytic``.

        ``profile=True`` additionally executes the compiled step on COPIES
        of the live state (donation-safe — the model does not advance),
        measuring wall step time and a per-layer fwd/bwd device-time table
        from the JAX profiler's XPlane events. MFU is reported against
        ``peak_flops`` (default: the ``DL4J_TPU_PEAK_FLOPS`` env knob).
        The report publishes to the UI server's ``/costs`` route and primes
        the ``train.model_flops_utilization`` gauge for subsequent fits."""
        from deeplearning4j_tpu.util import cost_model as _cm

        if not self.params:
            raise ValueError("init() the network before cost_report()")
        if shape is None:
            if self.conf.input_shape is None:
                raise ValueError(
                    "cost_report() needs shape= or conf.input_shape")
            shape = (int(batch_size or 8),) + tuple(self.conf.input_shape)
        shape = tuple(int(d) for d in shape)
        b = shape[0]
        params_by_tag = {
            t: int(sum(int(np.prod(l.shape))
                       for l in jax.tree_util.tree_leaves(p)))
            for t, p in zip(self._layer_tags, self.params)}
        if self._train_step is None:
            self._train_step = self._build_train_step()
        p_s, s_s, o_s = (_struct_of(self.params), _struct_of(self.states),
                         _struct_of(self.opt_states))
        it_s = jax.ShapeDtypeStruct((), jnp.int32)
        key_s = _struct_of(self._rng_key)
        x_s = jax.ShapeDtypeStruct(shape, dtype)
        y_s = jax.ShapeDtypeStruct((b,) + tuple(self._output_shape),
                                   jnp.float32)
        w_s = jax.ShapeDtypeStruct((b,), jnp.float32)
        compiled = self._train_step.lower(
            p_s, s_s, o_s, it_s, key_s, x_s, y_s, w_s, None, None).compile()
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
            if jnp.issubdtype(dtype, jnp.floating):
                x = jnp.asarray(rng.normal(size=shape), dtype=dtype)
            else:
                x = jnp.zeros(shape, dtype)
            y = jnp.zeros((b,) + tuple(self._output_shape), jnp.float32)
            w = jnp.ones((b,), jnp.float32)
            step_time, layer_times, device_time = _cm.profile_compiled_step(
                compiled,
                (self.params, self.states, self.opt_states,
                 jnp.asarray(0, jnp.int32), self._rng_key),
                (x, y, w, None, None), steps=steps,
                inst_map=attrib.inst_map if attrib else None)
        if attrib is not None:
            rows = _cm.rows_from_attribution(attrib, params_by_tag,
                                             layer_times)
        else:
            entries, cur = [], tuple(self.conf.input_shape or shape[1:])
            for tag, lyr in zip(self._layer_tags, self.layers):
                entries.append((tag, lyr, cur, params_by_tag.get(tag, 0)))
                cur = tuple(lyr.output_shape(cur))
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
        """fn(params, states, x) -> output activations (serving wrappers)."""

        def fwd(params, states, x):
            out, _ = self._forward(params, states, x, training=False)
            return out

        return fwd

    def output(self, x, train: bool = False, mask=None):
        """Forward pass (MultiLayerNetwork.output parity). The OutputLayer's
        apply() gives dense+activation, i.e. probabilities. ``train=True``
        uses training-mode statistics (e.g. batchnorm batch stats) but no
        dropout (no RNG is threaded, matching the reference's output(train)).
        ``mask``: (B,T) feature mask (output(x, fMask) parity).

        Under shape bucketing, a ragged batch pads up to its bucket and the
        padded rows are sliced off the result — row-independent layers leave
        the real rows bit-identical while eval keeps one compile per bucket."""
        real_n = None
        if self._bucketing is not None and mask is None:
            x, real_n = self._bucketing.pad_inference_batch(x)
            if real_n == x.shape[0]:
                real_n = None
        mk = None if mask is None else jnp.asarray(mask)
        x = jnp.asarray(x)
        fn = self._forward_train_jit if train else self._forward_jit
        aot = self._aot_forward.get((bool(train), _dispatch_sig(x, mk)))
        out, _ = (aot or fn)(self.params, self.states, x, mask=mk)
        return out if real_n is None else out[:real_n]

    def feed_forward(self, x):
        """Per-layer activations (MultiLayerNetwork.feedForward parity)."""
        h = self._cast(jnp.asarray(x))
        acts = [h]
        for i, lyr in enumerate(self.layers):
            h, _ = lyr.apply(self._cast_params(self.params)[i], self.states[i], h, training=False)
            acts.append(h)
        return acts

    def score(self, dataset=None, x=None, y=None, mask=None, label_mask=None) -> float:
        """Loss on a dataset (MultiLayerNetwork.score parity). Honors the
        DataSet's feature/label masks, like training does."""
        if dataset is not None:
            x, y = dataset.features, dataset.labels
            mask = getattr(dataset, "features_mask", None)
            label_mask = getattr(dataset, "labels_mask", None)
        real_n = np.shape(x)[0]
        if self._bucketing is not None:
            x, y, mask, label_mask, _ = self._bucketing.pad_batch(
                x, y, mask, label_mask)
        mk = None if mask is None else jnp.asarray(mask)
        lmk = None if label_mask is None else jnp.asarray(label_mask)
        x = jnp.asarray(x)
        loss, _ = self._loss_eval(
            self.params, self.states, x, jnp.asarray(y), mk, lmk,
            self._dev_weights(x.shape[0], real_n))
        return float(loss)

    @functools.cached_property
    def _loss_eval(self):
        def eval_loss(params, states, x, y, mask, label_mask, weights=None):
            note_trace("MultiLayerNetwork.loss_eval", x, y, mask, label_mask,
                       weights)
            keys = [None] * len(self.layers)
            loss, _ = self._loss_body(params, states, None, x, y, keys,
                                      weights, mask, label_mask,
                                      training=False)
            return loss, None

        return jax.jit(eval_loss)

    # -------------------------------------------------------------- evaluate
    def evaluate(self, iterator):
        """Classification evaluation over an iterator → Evaluation."""
        from deeplearning4j_tpu.eval import Evaluation

        ev = Evaluation()
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            preds = self.output(ds.features,
                                mask=getattr(ds, "features_mask", None))
            ev.eval(np.asarray(ds.labels), np.asarray(preds))
        return ev

    def evaluate_regression(self, iterator):
        from deeplearning4j_tpu.eval import RegressionEvaluation

        ev = RegressionEvaluation()
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            preds = self.output(ds.features,
                                mask=getattr(ds, "features_mask", None))
            ev.eval(np.asarray(ds.labels), np.asarray(preds))
        return ev

    # -------------------------------------------------------------- plumbing
    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def add_listener(self, listener):
        self.listeners.append(listener)
        return self

    @property
    def score_(self):
        return float(self.score_value)

    def get_score(self) -> float:
        return float(self.score_value)
