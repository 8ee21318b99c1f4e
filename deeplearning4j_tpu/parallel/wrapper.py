"""ParallelWrapper + ParallelInference — multi-device training/serving parity.

Reference: org/deeplearning4j/parallelism/{ParallelWrapper,ParallelInference}
.java (SURVEY.md §3.5: thread-per-GPU replicas, gradient averaging or
threshold-encoded sharing through EncodedGradientsAccumulator, round-robin
inference replicas) — path-cite, mount empty this round.

TPU-native collapse: there are no replicas, no trainer threads, no
accumulator. The SAME jitted train step as single-device, compiled with the
batch sharded over the mesh 'data' axis and params replicated — GSPMD inserts
one fused gradient ``all-reduce`` over ICI per step. Synchronous averaging
every iteration (the reference's averaging mode with frequency=1) is exact
here and costs one collective; the async/compressed machinery existed to hide
slow interconnects that ICI does not have. The encoded-gradient machinery
survives as the ``grad_compression`` knob (parallel/compression.py,
docs/DISTRIBUTED.md#gradient-compression): per-worker error-feedback
encode → all-reduce(quantized) → decode inside the lane-decomposed step,
for the DCN-bound regimes where wire bytes are the scarce resource.
"""

from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import Future
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.parallel import gspmd
from deeplearning4j_tpu.parallel.mesh import TrainingMesh
from deeplearning4j_tpu.util import telemetry as tm


class ParallelWrapper:
    """Data-parallel fit over a device mesh (ParallelWrapper.fit parity).

    Usage:
        pw = ParallelWrapper(net)            # all local devices
        pw.fit(iterator, epochs=2)
        # net.params are updated in place (replicated arrays)

    Two execution modes, both ONE ``jit``-compiled GSPMD program per step
    (docs/DISTRIBUTED.md):

    - default: the model's own step with the batch sharded over 'data' and
      params replicated; the partitioner inserts the fused gradient
      all-reduce. With ``zero_optimizer=True`` (default) the optimizer
      moments are additionally ZeRO-sharded over 'data'
      (``with_sharding_constraint`` — arXiv:2004.13336): the weight update
      becomes reduce-scatter → 1/N-sharded update → all-gather, cutting
      per-chip optimizer memory and update compute ~Nx.
    - ``deterministic=True``: the batch is decomposed into a fixed number of
      ``replicas`` lanes (vmapped, lane axis sharded) and cross-lane
      combines use explicit pairwise-tree adds (parallel/gspmd.py), making
      the fit BIT-identical across mesh sizes — an 8-device sharded fit
      reproduces the single-device fit exactly (params, Adam moments, RNG
      key), proven in tests/test_gspmd_identity.py. TBPTT segments are
      supported on MultiLayerNetworks.

    Telemetry: every step records a ``parallel.step`` dispatch span; every
    ``skew_every`` steps a completion probe watches each replica's loss
    shard become ready, emits one ``parallel.replica_step`` span per replica
    row on the merged trace, and publishes the max−min completion spread as the
    ``parallel.straggler_skew_seconds`` gauge (per-replica timing/skew
    visibility — arxiv 2004.13336's prerequisite for scaling the
    distributed path). The probe is a deliberate sync point, which is why
    it runs at window cadence, not per step; ``skew_every=0`` disables it.
    On a single-host CPU mesh the compiled all-reduce has already
    synchronized the replicas, so the skew reads ≈0 there — the gauge is
    meaningful on real multi-chip ICI. ``_build`` additionally publishes
    the mesh axis sizes, the ZeRO sharded fraction, and the per-device
    optimizer-state bytes as gauges, and keeps the full per-leaf layout
    table on ``self.layout``.
    """

    def __init__(self, model, workers: Optional[int] = None,
                 mesh: Optional[TrainingMesh] = None, prefetch: int = 2,
                 skew_every: int = 10, zero_optimizer: bool = True,
                 deterministic: bool = False, replicas: Optional[int] = None,
                 grad_compression=None,
                 compression_threshold: Optional[float] = None,
                 compression_target_sparsity: Optional[float] = None,
                 compression_hosts: Optional[int] = None):
        from deeplearning4j_tpu.parallel import compression as _comp

        self.model = model
        if mesh is None:
            devices = jax.devices()[: workers or len(jax.devices())]
            mesh = TrainingMesh(data=len(devices), devices=devices)
        self.mesh = mesh
        self.prefetch = prefetch
        self.skew_every = skew_every
        self.zero_optimizer = zero_optimizer
        self.deterministic = deterministic
        if deterministic and (mesh.model != 1 or mesh.seq != 1
                              or mesh.pipe != 1):
            raise ValueError(
                "deterministic lane mode is a data-parallel contract; use a "
                "data-only mesh (model=seq=pipe=1). PipelinedTrainer is "
                "deterministic by construction — its pipe contract is "
                "documented separately (docs/DISTRIBUTED.md)")
        # lane count: fixed at construction so a fit is reproducible across
        # device counts (pass the same replicas on every topology)
        self.replicas = int(replicas if replicas is not None else mesh.data)
        # Encoded gradient collectives (docs/DISTRIBUTED.md#gradient-
        # compression): grad_compression is a scheme name
        # (none|threshold|bitmap|onebit), a prebuilt GradCompressor, or
        # None (defer to conf.grad_compression, which folds in the
        # DL4J_TPU_GRAD_COMPRESSION env default). An active scheme routes
        # the step through the lane decomposition — per-worker gradients
        # are what the error-feedback encode needs, and the lane path's
        # deterministic combine is what makes the t→0 bit-identity and the
        # wire-ratio tests exact.
        if isinstance(grad_compression, _comp.GradCompressor):
            self._compressor = grad_compression
        else:
            scheme = _comp.resolve_scheme(grad_compression, model.conf)
            if scheme == "none":
                self._compressor = None
            else:
                conf = model.conf
                hosts = compression_hosts
                if hosts in (None, "auto"):
                    hosts = self.mesh.dcn_hosts() \
                        if hosts == "auto" else 1
                self._compressor = _comp.GradCompressor(
                    scheme=scheme,
                    initial_threshold=(
                        compression_threshold
                        if compression_threshold is not None
                        else getattr(conf, "grad_compression_threshold",
                                     1e-3)),
                    target_sparsity=(
                        compression_target_sparsity
                        if compression_target_sparsity is not None
                        else getattr(conf, "grad_compression_target", 1e-3)),
                    hosts=int(hosts))
        if self._compressor is not None:
            self._compressor.exchange_axis(self.replicas)  # fail fast
            engine = getattr(model, "_fused", None)
            if engine is not None and engine.loss_scale == "dynamic":
                raise ValueError(
                    "grad_compression with loss_scale='dynamic' is not "
                    "supported: the residual accumulates in scaled units, "
                    "so a scale change mid-run would silently re-weight "
                    "the carried error — use loss_scale='static' (the "
                    "residual then lives consistently in scaled units) or "
                    "compression 'none'")
        #: compression forces the lane-decomposed step (per-worker grads)
        self._uses_lanes = bool(deterministic or self._compressor)
        self._sharded_step = None
        self._tbptt_step = None
        self._zero_specs = None
        self._param_specs = self._state_specs = self._opt_specs = None
        self._comp_state = None
        self._comp_specs = None
        self._comp_stats = None
        self._stage_jits = None
        self.layout: dict = {}

    def _build(self):
        model = self.model
        if model._train_step is None and not self._uses_lanes:
            raise ValueError("model must be init()ed first")
        if not model.params:
            raise ValueError("model must be init()ed first")
        if self.zero_optimizer and self.mesh.n_devices > 1:
            self._zero_specs = gspmd.zero_shardings(
                self.mesh.mesh, model.opt_states)
        # replicate current model state across the mesh (TP-sharded leaves
        # placed on this mesh keep their sharding); ZeRO places the
        # optimizer state sharded over 'data'
        model.params = self.mesh.replicate(model.params)
        model.states = self.mesh.replicate(model.states)
        if self._zero_specs is not None:
            model.opt_states = gspmd.place_tree(
                model.opt_states, self._zero_specs)
        else:
            model.opt_states = self.mesh.replicate(model.opt_states)
        # pin each step's OUTPUT layouts to the placement just made:
        # without this the partitioner propagates the ZeRO-sharded moments
        # into the updated params, the next step's inputs arrive with a
        # different (partially sharded) layout, and the program silently
        # re-partitions — layout must be a fixed point across steps
        if self.mesh.n_devices > 1:
            from jax.sharding import NamedSharding

            def spec_of(leaf):
                s = getattr(leaf, "sharding", None)
                return s if isinstance(s, NamedSharding) \
                    else self.mesh.replicated()

            self._param_specs = jax.tree_util.tree_map(
                spec_of, model.params)
            self._state_specs = jax.tree_util.tree_map(
                spec_of, model.states)
            self._opt_specs = (self._zero_specs
                               if self._zero_specs is not None
                               else jax.tree_util.tree_map(
                                   spec_of, model.opt_states))
        else:
            self._param_specs = self._state_specs = self._opt_specs = None
        if self._compressor is not None:
            self._place_compression_state()
        self._sharded_step = (self._build_lane_step() if self._uses_lanes
                              else self._build_fast_step())
        self._publish_layout()

    # ------------------------------------------------- compression state
    def _comp_template(self):
        """ONE worker's gradient template: the fused engine's flat group
        buffers when the model fuses its update (the encode then runs on
        exactly what ZeRO reduce-scatters), the param-shaped tree
        otherwise."""
        model = self.model
        engine = getattr(model, "_fused", None)
        if engine is not None:
            return [np.zeros((g.total,), np.float32) for g in engine.groups]
        f32 = lambda p: np.zeros(np.shape(p), np.float32)  # noqa: E731
        if isinstance(model._updaters, dict):
            return {k: jax.tree_util.tree_map(f32, v)
                    for k, v in model.params.items()}
        return [jax.tree_util.tree_map(f32, p) for p in model.params]

    def _place_compression_state(self):
        """Adopt (checkpoint-restored / reshard-migrated) or initialize the
        residual + threshold, place them on the mesh (residual sharded over
        'data' when the exchange axis divides it — worker-sharded RESIDENT
        state, the fused-master invariant), and pin the layout specs the
        step re-asserts every iteration."""
        comp = self._compressor
        template = self._comp_template()
        prior = getattr(self.model, "_grad_comp_state", None)
        if prior is not None and not comp.state_matches(
                prior, template, self.replicas):
            raise ValueError(
                "restored grad-compression state does not match this "
                "wrapper's layout (scheme/replicas/hosts changed between "
                "runs?) — clear model._grad_comp_state to reinitialize, "
                "losing the carried residual")
        state = prior if prior is not None \
            else comp.init_state(template, self.replicas)
        if self.mesh.n_devices > 1:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            d = self.mesh.data

            def spec_of(leaf):
                shape = np.shape(leaf)
                if shape and shape[0] % d == 0:
                    return NamedSharding(
                        self.mesh.mesh,
                        P("data", *([None] * (len(shape) - 1))))
                return self.mesh.replicated()

            self._comp_specs = jax.tree_util.tree_map(spec_of, state)
            state = gspmd.place_tree(state, self._comp_specs)
        else:
            self._comp_specs = None
            state = jax.tree_util.tree_map(jnp.asarray, state)
        self._comp_state = state
        self.model._grad_comp_state = state

    def _adopt_compression_state(self):
        """Re-place the model-side compression state when someone swapped
        it from outside the step loop — a checkpoint restore
        (util/checkpoint.py sets ``model._grad_comp_state``) or a rollback.
        Identity-checked per step: free when nothing changed."""
        if self._compressor is None:
            return
        if getattr(self.model, "_grad_comp_state", None) is self._comp_state:
            return
        self._place_compression_state()

    def _build_fast_step(self):
        # The model's own step function (weighted variant for exact ragged-
        # batch masking), jitted over sharded operands: params replicated,
        # batch split over 'data'. jit infers the SPMD partition from operand
        # shardings (set by device_put in fit); the gradient all-reduce is
        # emitted by the partitioner, not written here.
        base = self.model.make_step_fn(weighted=True)
        zspecs = self._zero_specs
        if self._param_specs is None:
            return jax.jit(base, donate_argnums=(0, 1, 2))
        pspecs, sspecs, ospecs = (self._param_specs, self._state_specs,
                                  self._opt_specs)

        def step(params, states, opts, iteration, x, y, key, w):
            # assert the ZeRO layout on entry and every layout on exit: the
            # partitioner then emits reduce-scatter(grads) -> sharded
            # update -> all-gather(params) instead of N redundant full
            # updates, and the step's output layout equals its input
            # layout (donation-exact, stable across steps)
            if zspecs is not None:
                opts = gspmd.constrain_tree(opts, zspecs)
            p, s, o, loss = base(params, states, opts, iteration, x, y,
                                 key, w)
            return (gspmd.constrain_tree(p, pspecs),
                    gspmd.constrain_tree(s, sspecs),
                    gspmd.constrain_tree(o, ospecs), loss)

        return jax.jit(step, donate_argnums=(0, 1, 2))

    # Determinism note (pinned by tests/test_gspmd_identity.py): the lane
    # step is THREE jit programs, not one. LLVM's FMA contraction fuses a
    # multiply into a following add WITHIN one compiled kernel (and
    # ``optimization_barrier`` does not reach that level), so a lane-weight
    # multiply living in the same kernel as the cross-lane add tree rounds
    # differently on 1 device (fused mul+add) than on 8 (the adds cross
    # device boundaries and cannot contract). Splitting at jit boundaries
    # forces materialization: stage A ends in multiplies (no consumer
    # adds), stage B is slices+adds with post-multiplies only (no
    # contractible mul→add), stage C is the elementwise updater — each
    # stage is topology-invariant, so the composition is bit-identical on
    # every mesh size.
    def _lane_combine_fns(self):
        sspecs = self._state_specs
        comp = self._compressor
        cspecs = self._comp_specs
        model = self.model
        engine = getattr(model, "_fused", None)
        comp_flat = comp is not None and engine is not None

        def combine(loss_s, s_l, states_l, scaled_g):
            total = gspmd.pairwise_sum(s_l)
            inv = 1.0 / jnp.where(total == 0.0, 1.0, total)
            grads = jax.tree_util.tree_map(
                lambda t: gspmd.pairwise_sum(t) * inv.astype(t.dtype),
                scaled_g)
            loss = gspmd.pairwise_sum(loss_s) * inv
            new_states = gspmd.combine_states(states_l)
            if sspecs is not None:
                new_states = gspmd.constrain_tree(new_states, sspecs)
            return loss, grads, new_states

        def combine_compressed(loss_s, s_l, states_l, scaled_g, comp_state):
            """The combine stage with the encoded exchange spliced in
            where the cross-lane gradient sum used to be: per-worker
            error-feedback encode → deterministic pairwise all-reduce of
            the quantized payloads → dense decode → weighted-mean
            normalization. With the fused engine, the per-lane gradients
            flatten FIRST (vmapped) so the encode runs on the flat
            per-(rule, dtype) buffers ZeRO reduce-scatters."""
            total = gspmd.pairwise_sum(s_l)
            inv = 1.0 / jnp.where(total == 0.0, 1.0, total)
            payload = (jax.vmap(engine.flatten_grads)(scaled_g)
                       if comp_flat else scaled_g)
            grads, new_comp, stats = comp.encode_combine(
                payload, comp_state, inv)
            loss = gspmd.pairwise_sum(loss_s) * inv
            new_states = gspmd.combine_states(states_l)
            if sspecs is not None:
                new_states = gspmd.constrain_tree(new_states, sspecs)
            if cspecs is not None:
                new_comp = gspmd.constrain_tree(new_comp, cspecs)
            return loss, grads, new_states, new_comp, stats

        zspecs = self._zero_specs
        pspecs = self._param_specs

        def update(params, opts, grads, iteration):
            if zspecs is not None:
                opts = gspmd.constrain_tree(opts, zspecs)
            if comp_flat:
                # decode output IS the flat buffer list — feed the fused
                # update directly, no per-leaf round trip
                new_params, new_opts = gspmd.apply_updaters_flat(
                    model, params, grads, opts, iteration)
            else:
                new_params, new_opts = gspmd.apply_updaters(
                    model, params, grads, opts, iteration,
                    scaled_grads=True)
            # pin the output layout to the input layout (see _build): the
            # updated params must come back replicated even though the
            # ZeRO-sharded moments fed the update
            if pspecs is not None:
                new_params = gspmd.constrain_tree(new_params, pspecs)
            if zspecs is not None:
                new_opts = gspmd.constrain_tree(new_opts, zspecs)
            return new_params, new_opts

        j_combine = (jax.jit(combine_compressed, donate_argnums=(4,))
                     if comp is not None else jax.jit(combine))
        return j_combine, jax.jit(update, donate_argnums=(0, 1))

    @staticmethod
    def _lane_scale(loss_l, s_l, grads_l):
        """Lane-side weighting — multiplies whose only consumers are jit
        outputs (the cross-lane adds live in the next jit)."""
        scale = jax.tree_util.tree_map(
            lambda t: t * s_l.reshape(
                s_l.shape + (1,) * (t.ndim - 1)).astype(t.dtype), grads_l)
        return loss_l * s_l, scale

    def _loss_scale_arg(self):
        """The loss-scale multiplier the lane stage multiplies into the
        loss this step (None when the model has no scaling policy): read
        from the CURRENT opt state so the dynamic automaton's value is the
        one this step's gradients are scaled by — the fused apply unscales
        with the same state."""
        engine = getattr(self.model, "_fused", None)
        if engine is None or engine.loss_scale == "none":
            return None
        return engine.current_scale(self.model.opt_states)

    def _run_compressed_combine(self, j_combine, combine_args):
        """Thread the resident compression state through the combine jit
        and keep both wrapper- and model-side references current (the
        model-side one is what checkpoints carry — util/checkpoint.py)."""
        loss, grads, new_states, self._comp_state, self._comp_stats = \
            j_combine(*combine_args, self._comp_state)
        self.model._grad_comp_state = self._comp_state
        return loss, grads, new_states

    def _build_lane_step(self):
        model = self.model
        lane_vg = gspmd.make_lane_value_and_grad(model)
        compressed = self._compressor is not None

        def lanes(params, states, x, y, keys, w, scale):
            # the SAME vmapped program on every topology: on one device it
            # executes unpartitioned, on N the lane axis is sharded — the
            # per-lane values are identical either way (pinned exceptions:
            # conv filter grads and >=1024-wide gemm contractions, whose
            # XLA:CPU lowering is fold-dependent; docs/DISTRIBUTED.md)
            (loss_l, s_l), (states_l, grads_l) = jax.vmap(
                lane_vg, in_axes=(None, None, 0, 0, 0, 0, None, None, None)
            )(params, states, x, y, keys, w, None, None, scale)
            loss_s, scaled = self._lane_scale(loss_l, s_l, grads_l)
            return loss_s, s_l, states_l, scaled

        j_lanes = jax.jit(lanes)
        j_combine, j_update = self._lane_combine_fns()
        self._stage_jits = (j_lanes, j_combine, j_update)

        def step(params, states, opts, iteration, x, y, keys, w):
            loss_s, s_l, states_l, scaled = j_lanes(
                params, states, x, y, keys, w, self._loss_scale_arg())
            if compressed:
                loss, grads, new_states = self._run_compressed_combine(
                    j_combine, (loss_s, s_l, states_l, scaled))
            else:
                loss, grads, new_states = j_combine(loss_s, s_l, states_l,
                                                    scaled)
            new_params, new_opts = j_update(params, opts, grads, iteration)
            return new_params, new_states, new_opts, loss

        return step

    def _build_tbptt_step(self):
        model = self.model
        lane_vg = gspmd.make_lane_tbptt_value_and_grad(model)
        compressed = self._compressor is not None

        def lanes(params, states, carries, x, y, keys, w, fm, lm, scale):
            (loss_l, s_l), (states_l, carries_l, grads_l) = jax.vmap(
                lane_vg, in_axes=(None, None, 0, 0, 0, 0, 0, 0, 0, None)
            )(params, states, carries, x, y, keys, w, fm, lm, scale)
            loss_s, scaled = self._lane_scale(loss_l, s_l, grads_l)
            return loss_s, s_l, states_l, carries_l, scaled

        j_lanes = jax.jit(lanes)
        j_combine, j_update = self._lane_combine_fns()

        def step(params, states, opts, carries, iteration, x, y, keys, w,
                 fm, lm):
            loss_s, s_l, states_l, carries_l, scaled = j_lanes(
                params, states, carries, x, y, keys, w, fm, lm,
                self._loss_scale_arg())
            if compressed:
                loss, grads, new_states = self._run_compressed_combine(
                    j_combine, (loss_s, s_l, states_l, scaled))
            else:
                loss, grads, new_states = j_combine(loss_s, s_l, states_l,
                                                    scaled)
            new_params, new_opts = j_update(params, opts, grads, iteration)
            return new_params, new_states, new_opts, carries_l, loss

        return step

    def _lane_keys(self, sub):
        keys = jax.random.split(sub, self.replicas)
        if self.mesh.n_devices > 1:
            keys = jax.device_put(keys, self.mesh.spec("data"))
        return keys

    def step_batch(self, ds):
        """Run ONE sharded train step on a DataSet (listeners included) —
        the unit the elastic supervisor (parallel/elastic.py) wraps with
        checkpoint/drain/rollback handling. Returns the device loss."""
        import time as _time

        if self._sharded_step is None:
            self._build()
        self._adopt_compression_state()
        model = self.model
        if (self._uses_lanes
                and getattr(model.conf, "tbptt_length", None)
                and not isinstance(model._updaters, dict)
                and np.ndim(ds.features) == 3 and np.ndim(ds.labels) == 3
                and np.shape(ds.features)[1] > model.conf.tbptt_length):
            return self._step_batch_tbptt(ds)
        x, y, w = self._shard(ds.features, ds.labels)
        model._rng_key, sub = jax.random.split(model._rng_key)
        key_arg = self._lane_keys(sub) if self._uses_lanes else sub
        t0 = _time.time_ns()
        with tm.span("parallel.step", iteration=model.iteration,
                     replicas=self.mesh.data):
            model.params, model.states, model.opt_states, loss = (
                self._sharded_step(
                    model.params, model.states, model.opt_states,
                    jnp.asarray(model.iteration), x, y, key_arg, w,
                )
            )
        model.score_value = loss
        model.iteration += 1
        tm.counter("train.steps_total", model="parallel")
        if (self.skew_every and tm.enabled()
                and model.iteration % self.skew_every == 0):
            self._probe_replica_skew(loss, t0)
            self._publish_compression_stats()
        for lst in model.listeners:
            lst.iteration_done(model, model.iteration, model.epoch)
        return loss

    def _step_batch_tbptt(self, ds):
        """Deterministic sharded TBPTT (MultiLayerNetwork): the segment
        loop of ``doTruncatedBPTT`` with every segment one lane-decomposed
        SPMD step — carries stay lane-stacked across segments, gradients
        truncate at segment boundaries, one update per segment."""
        model = self.model
        k = model.conf.tbptt_length
        R = self.replicas
        fm = getattr(ds, "features_mask", None)
        lm = getattr(ds, "labels_mask", None)
        x, y, w, (fm, lm) = self.mesh.pad_lane_batch(
            ds.features, ds.labels, R, extras=(fm, lm))
        if self._tbptt_step is None:
            self._tbptt_step = self._build_tbptt_step()
        b = x.shape[1]
        dtype = model._cast(x).dtype
        carries = jax.tree_util.tree_map(
            lambda c: jnp.broadcast_to(c[None], (R,) + c.shape),
            model._init_carries(b, dtype))
        T = x.shape[2]
        losses = []
        for s in range(0, T, k):
            xs = x[:, :, s:s + k]
            ys = y[:, :, s:s + k] if y.ndim == 4 else y
            ms = None if fm is None else fm[:, :, s:s + k]
            lms = None if lm is None else lm[:, :, s:s + k]
            model._rng_key, sub = jax.random.split(model._rng_key)
            keys = self._lane_keys(sub)
            with tm.span("parallel.tbptt_step", iteration=model.iteration,
                         segment_start=s):
                (model.params, model.states, model.opt_states, carries,
                 loss) = self._tbptt_step(
                    model.params, model.states, model.opt_states, carries,
                    jnp.asarray(model.iteration), xs, ys, keys, w, ms, lms)
            model.iteration += 1
            losses.append(loss)
        model.score_value = float(jnp.mean(jnp.stack(losses)))
        tm.counter("train.steps_total", model="parallel")
        for lst in model.listeners:
            lst.iteration_done(model, model.iteration, model.epoch)
        return model.score_value

    def end_epoch(self):
        """Advance the epoch counter + epoch-end callbacks (the tail of one
        fit() epoch, split out for the elastic supervisor)."""
        model = self.model
        model.epoch += 1
        for lst in model.listeners:
            if hasattr(lst, "on_epoch_end"):
                lst.on_epoch_end(model)

    def fit(self, iterator, epochs: int = 1):
        if self._sharded_step is None:
            self._build()
        for _ in range(epochs):
            if hasattr(iterator, "reset"):
                iterator.reset()
            for ds in iterator:
                self.step_batch(ds)
            self.end_epoch()
        return self.model

    def _shard(self, x, y):
        if self._uses_lanes:
            return self.mesh.pad_lane_batch(x, y, self.replicas)
        return self.mesh.pad_shard_batch(x, y)

    # --------------------------------------------------- compression stats
    def compression_stats(self) -> Optional[dict]:
        """Latest step's deterministic wire accounting as plain floats
        (one host sync — window-cadence material, not per-step), also
        pushed to the ``parallel.allreduce_*`` telemetry gauges. None when
        compression is off or no compressed step ran yet."""
        if self._comp_stats is None:
            return None
        stats = {k: float(v) for k, v in self._comp_stats.items()}
        thr = self._comp_state.get("threshold") \
            if self._comp_state is not None else None
        if thr is not None:
            stats["threshold"] = float(jax.device_get(thr))
        if tm.enabled():
            tm.gauge("parallel.allreduce_wire_bytes", stats["wire_bytes"])
            tm.gauge("parallel.allreduce_dense_bytes", stats["dense_bytes"])
            tm.gauge("parallel.allreduce_compression_ratio", stats["ratio"])
            tm.counter("parallel.allreduce_wire_bytes_total",
                       value=stats["wire_bytes"])
            tm.counter("parallel.allreduce_exchanges_total")
        return stats

    def _publish_compression_stats(self):
        if self._comp_stats is not None and tm.enabled():
            self.compression_stats()

    # ------------------------------------------------------- layout plumbing
    def _publish_mesh_gauges(self):
        """One gauge per canonical mesh axis — the ONE loop shared with the
        pipelined trainer's layout publisher, so a future axis cannot be
        threaded into one and silently missed in the other."""
        mesh = self.mesh
        for axis in TrainingMesh.AXES:
            tm.gauge("parallel.mesh_axis_size", getattr(mesh, axis),
                     axis=axis)

    def _publish_layout(self):
        """Telemetry gauges + the per-leaf layout table (satellite:
        telemetry reports per-device layouts; docs/OBSERVABILITY.md)."""
        mesh = self.mesh
        self._publish_mesh_gauges()
        frac = (gspmd.sharded_fraction(self._zero_specs)
                if self._zero_specs is not None else 0.0)
        tm.gauge("parallel.zero_state_sharded_fraction", frac)
        tm.gauge("parallel.opt_state_bytes_per_device",
                 self.opt_state_bytes_per_device())
        comp = self._compressor
        self.layout = {
            "signature": mesh.layout_signature(
                extra=(self.zero_optimizer, self.deterministic,
                       self.replicas,
                       (comp.scheme, comp.hosts) if comp else None)),
            "params": gspmd.describe_shardings(self.model.params),
            "opt_states": gspmd.describe_shardings(self.model.opt_states),
        }
        if comp is not None:
            tm.gauge("parallel.grad_compression_hosts", comp.hosts)
            self.layout["grad_compression"] = {
                "scheme": comp.scheme, "hosts": comp.hosts,
                "residual": gspmd.describe_shardings(
                    self._comp_state["residual"]),
            }

    def opt_state_bytes_per_device(self) -> int:
        """Bytes of optimizer state ONE device holds — the ZeRO memory
        number (~1/N of the replicated total when sharded;
        tests/test_gspmd_identity.py holds it under a quarter at N=8)."""
        return gspmd.tree_bytes_per_device(self.model.opt_states)

    def reshard(self, mesh: Optional[TrainingMesh] = None):
        """Re-place model state and re-build the compiled step on a NEW
        mesh — the elastic regroup hook (parallel/elastic.py): after worker
        loss the survivors form a shrunken mesh and the same program
        recompiles onto it (the sharding layout is part of the compile
        key). Deterministic mode keeps its lane count across the re-shard,
        so the fit trajectory is preserved up to lane-fold fp association
        (docs/DISTRIBUTED.md)."""
        model = self.model
        # pull state off the old placement (host round trip — regroup-rare)
        model.params = jax.tree_util.tree_map(np.asarray, model.params)
        model.states = jax.tree_util.tree_map(np.asarray, model.states)
        model.opt_states = jax.tree_util.tree_map(np.asarray,
                                                  model.opt_states)
        if self._comp_state is not None:
            # residual/threshold migrate with the regroup: the lane count
            # is fixed at construction, so the worker-stacked shapes are
            # mesh-independent and the re-placed fit continues the SAME
            # error-feedback trajectory (trajectory-exact regroup —
            # tests/test_compression.py)
            model._grad_comp_state = jax.tree_util.tree_map(
                np.asarray, self._comp_state)
            self._comp_state = None
        if mesh is None:
            # re-derive from the CURRENT device view (after worker loss the
            # survivors), keeping the model/seq factors when they still fit
            devices = jax.devices()
            model_ax, seq_ax, pipe_ax = (self.mesh.model, self.mesh.seq,
                                         self.mesh.pipe)
            if len(devices) % (model_ax * seq_ax * pipe_ax):
                model_ax = seq_ax = pipe_ax = 1
            mesh = TrainingMesh(
                data=len(devices) // (model_ax * seq_ax * pipe_ax),
                model=model_ax, seq=seq_ax, pipe=pipe_ax, devices=devices)
        if self.deterministic and (mesh.model != 1 or mesh.seq != 1
                                   or mesh.pipe != 1):
            raise ValueError("deterministic lane mode needs a data-only mesh")
        self.mesh = mesh
        self._sharded_step = None
        self._tbptt_step = None
        self._zero_specs = None
        self._comp_specs = None
        self._build()
        tm.counter("parallel.reshards_total")
        return self

    # --------------------------------------------------------- cost report
    def cost_report(self, batch_size=None, *, shape=None, dtype=jnp.float32,
                    name: str = "parallel", publish: bool = True):
        """Per-layer cost table for ONE GSPMD-sharded train step.
        ``cost_analysis()`` totals of a partitioned executable are
        PER-DEVICE — the report carries ``devices`` and exposes both
        per-device and global FLOPs/bytes (``totals_global``), keeping the
        reconciliation semantics honest under sharding
        (docs/OBSERVABILITY.md#cost-attribution--mfu)."""
        from deeplearning4j_tpu.util import cost_model as _cm

        model = self.model
        if self._sharded_step is None:
            self._build()
        if self._uses_lanes:
            return self._cost_report_lanes(
                batch_size=batch_size, shape=shape, dtype=dtype, name=name,
                publish=publish)
        conf = model.conf
        if shape is None:
            if getattr(conf, "input_shape", None) is None:
                raise ValueError("cost_report() needs shape= or "
                                 "conf.input_shape")
            shape = ((int(batch_size or 8 * self.mesh.data),)
                     + tuple(conf.input_shape))
        shape = tuple(int(d) for d in shape)
        b = shape[0]
        if b % self.mesh.data:
            raise ValueError(f"global batch {b} must divide the data axis "
                             f"({self.mesh.data})")

        def struct(t):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=getattr(a, "sharding", None)),
                t)

        p_s, s_s, o_s = (struct(model.params), struct(model.states),
                         struct(model.opt_states))
        it_s = jax.ShapeDtypeStruct((), jnp.int32)
        key_s = struct(model._rng_key)
        bsh = self.mesh.batch_sharding(len(shape))
        x_s = jax.ShapeDtypeStruct(shape, dtype, sharding=bsh)
        y_s = jax.ShapeDtypeStruct((b,) + tuple(model._output_shape),
                                   jnp.float32,
                                   sharding=self.mesh.batch_sharding(
                                       1 + len(model._output_shape)))
        w_s = jax.ShapeDtypeStruct((b,), jnp.float32,
                                   sharding=self.mesh.batch_sharding(1))
        compiled = self._sharded_step.lower(
            p_s, s_s, o_s, it_s, x_s, y_s, key_s, w_s).compile()
        params_by_tag = {}
        if hasattr(model, "_layer_tags"):
            params_by_tag = {
                t: int(sum(int(np.prod(l.shape))
                           for l in jax.tree_util.tree_leaves(p)))
                for t, p in zip(model._layer_tags, model.params)}
        totals, attrib, source = {}, None, "analytic"
        try:
            totals = _cm.compiled_totals(compiled)
            attrib = _cm.attribute_hlo(_cm.compiled_text(compiled))
            source = "xla"
        except _cm.CostAnalysisUnavailable:
            pass
        if attrib is not None:
            rows = _cm.rows_from_attribution(attrib, params_by_tag, None)
        else:
            rows = []
        report = _cm.CostReport(
            rows=rows, totals=totals, batch=b,
            params_total=model.num_params(), source=source, model=str(name),
            peak_flops=_cm.peak_flops_from_env(
                getattr(self.model.conf, "compute_dtype", None)),
            devices=self.mesh.n_devices)
        if publish:
            _cm.publish_report(str(name), report)
        return report

    def _cost_report_lanes(self, batch_size=None, *, shape=None,
                           dtype=jnp.float32, name: str = "parallel",
                           publish: bool = True):
        """Cost report for the LANE-DECOMPOSED step (deterministic mode and
        the compressed-DP path): the step is deliberately staged as three
        jit programs (lanes / combine / update — the FMA-contraction
        determinism note above), so the report lowers ALL THREE with the
        fit-time shapes/shardings, sums their per-device totals, and merges
        their per-layer attributions — the lanes program carries the
        ``layer:*`` scopes, the update program the ``(optimizer)`` row, the
        combine (and encode, when compressing) lands in ``(untagged)``."""
        from deeplearning4j_tpu.util import cost_model as _cm

        model = self.model
        conf = model.conf
        if shape is None:
            if getattr(conf, "input_shape", None) is None:
                raise ValueError("cost_report() needs shape= or "
                                 "conf.input_shape")
            shape = ((int(batch_size or 8 * self.mesh.data),)
                     + tuple(conf.input_shape))
        shape = tuple(int(d) for d in shape)
        b, R = shape[0], self.replicas
        if b % R:
            raise ValueError(f"global batch {b} must divide the lane count "
                             f"({R})")

        def struct(t):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    jnp.shape(a), jnp.asarray(a).dtype,
                    sharding=getattr(a, "sharding", None)), t)

        lane_shape = (R, b // R) + tuple(shape[1:])
        lsh = (self.mesh.spec("data", *([None] * (len(lane_shape) - 1)))
               if self.mesh.n_devices > 1 else None)
        x_s = jax.ShapeDtypeStruct(lane_shape, dtype, sharding=lsh)
        y_shape = (R, b // R) + tuple(model._output_shape)
        y_s = jax.ShapeDtypeStruct(
            y_shape, jnp.float32,
            sharding=(self.mesh.spec("data", *([None] * (len(y_shape) - 1)))
                      if self.mesh.n_devices > 1 else None))
        w_s = jax.ShapeDtypeStruct(
            (R, b // R), jnp.float32,
            sharding=(self.mesh.spec("data", None)
                      if self.mesh.n_devices > 1 else None))
        keys_s = struct(self._lane_keys(jax.random.PRNGKey(0)))
        scale = self._loss_scale_arg()
        scale_s = None if scale is None else struct(scale)
        p_s, s_s, o_s = (struct(model.params), struct(model.states),
                         struct(model.opt_states))
        it_s = jax.ShapeDtypeStruct((), jnp.int32)

        j_lanes, j_combine, j_update = self._stage_jits
        lanes_args = (p_s, s_s, x_s, y_s, keys_s, w_s, scale_s)
        lanes_out = jax.eval_shape(j_lanes, *lanes_args)
        if self._compressor is not None:
            comb_args = tuple(lanes_out) + (struct(self._comp_state),)
            _loss, grads_s = jax.eval_shape(j_combine, *comb_args)[:2]
        else:
            comb_args = tuple(lanes_out)
            _loss, grads_s, _st = jax.eval_shape(j_combine, *comb_args)
        upd_args = (p_s, o_s, grads_s, it_s)

        params_by_tag = {}
        if hasattr(model, "_layer_tags"):
            params_by_tag = {
                t: int(sum(int(np.prod(l.shape))
                           for l in jax.tree_util.tree_leaves(p)))
                for t, p in zip(model._layer_tags, model.params)}
        totals: dict = {}
        merged: Optional[_cm.HloAttribution] = None
        source = "analytic"
        try:
            for fn, args in ((j_lanes, lanes_args), (j_combine, comb_args),
                             (j_update, upd_args)):
                compiled = fn.lower(*args).compile()
                for k, v in _cm.compiled_totals(compiled).items():
                    totals[k] = totals.get(k, 0.0) + v
                att = _cm.attribute_hlo(_cm.compiled_text(compiled))
                if merged is None:
                    merged = att
                else:
                    for key, costs in att.by_layer.items():
                        dst = merged.by_layer.setdefault(key, {})
                        for ck, cv in costs.items():
                            dst[ck] = dst.get(ck, 0.0) + cv
                    merged.flops_total += att.flops_total
                    merged.transcendentals_total += att.transcendentals_total
                    merged.bytes_total += att.bytes_total
                    merged.inst_map.update(att.inst_map)
            source = "xla"
        except _cm.CostAnalysisUnavailable:
            totals, merged = {}, None
        rows = (_cm.rows_from_attribution(merged, params_by_tag, None)
                if merged is not None else [])
        report = _cm.CostReport(
            rows=rows, totals=totals, batch=b,
            params_total=model.num_params(), source=source, model=str(name),
            peak_flops=_cm.peak_flops_from_env(
                getattr(conf, "compute_dtype", None)),
            devices=self.mesh.n_devices)
        if publish:
            _cm.publish_report(str(name), report)
        return report

    def _probe_replica_skew(self, loss, dispatch_t0_ns: int):
        """Record when each replica's loss shard became ready: one
        ``parallel.replica_step`` span per replica (from dispatch to that
        replica's completion, on a synthetic per-replica trace row) and the
        max−min spread as the straggler-skew gauge. Completion is observed
        by POLLING ``is_ready()`` across all shards so arrival order is
        captured regardless of index — blocking shard-by-shard would charge
        a low-index straggler's wait to every later replica and read ~0
        skew exactly when the straggler exists."""
        import time as _time

        shards = getattr(loss, "addressable_shards", None)
        if not shards:
            return
        done_ns = [0] * len(shards)
        pending = set(range(len(shards)))
        deadline = _time.monotonic() + 60.0
        while pending and _time.monotonic() < deadline:
            for i in list(pending):
                if shards[i].data.is_ready():
                    done_ns[i] = _time.time_ns()
                    pending.discard(i)
            if pending:
                _time.sleep(5e-5)
        for i in pending:  # deadline hit: block out the stragglers
            jax.block_until_ready(shards[i].data)
            done_ns[i] = _time.time_ns()
        tele = tm.get_telemetry()
        for i, (sh, t1) in enumerate(zip(shards, done_ns)):
            tele.event("parallel.replica_step", dispatch_t0_ns, t1,
                       tid=10_000 + i,
                       tname=f"replica {i} ({sh.device})",
                       replica=i)
        skew = (max(done_ns) - min(done_ns)) / 1e9
        tm.gauge("parallel.straggler_skew_seconds", skew)
        tm.gauge("parallel.replicas", len(shards))

    def average_model(self):
        """No-op for API parity: params are kept consistent every step by the
        compiled all-reduce (averaging mode with frequency=1, exact)."""
        return self.model

    def warmup(self, batch_sizes, input_shape=None, label_shape=None):
        """AOT warmup of the sharded train step for each GLOBAL batch size
        (docs/COMPILE_CACHE.md): runs one throwaway step per size on
        zero-valued shadow state (params are donated — the real model state
        is never touched), so the first real fit() batch executes a warm
        executable. Shapes default to the model conf. Returns the number of
        signatures primed."""
        import numpy as np_

        if self._sharded_step is None:
            self._build()
        model = self.model
        conf = model.conf
        in_shape = tuple(input_shape or conf.input_shape or ())
        if not in_shape:
            raise ValueError("warmup() needs input_shape (or conf.input_shape)")
        out_shape = tuple(label_shape or getattr(model, "_output_shape", ()))
        if not out_shape:
            raise ValueError("warmup() needs label_shape")
        zeros = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda a: jnp.zeros(a.shape, a.dtype), t)
        # the compressed step donates (and advances) the resident
        # residual/threshold through self._comp_state: park the REAL state
        # and run warmup on a shadow copy, so priming executables never
        # perturbs the error-feedback trajectory
        real_comp = self._comp_state
        real_stats = self._comp_stats
        primed = 0
        try:
            for b in batch_sizes:
                x = np_.zeros((int(b),) + in_shape, np_.float32)
                y = np_.zeros((int(b),) + out_shape, np_.float32)
                xs, ys, w = self._shard(x, y)
                # shadow state, same shardings as the real one (params/
                # states replicated, optimizer state ZeRO-sharded when
                # enabled — the warm executable must match the fit-time
                # layout, which is part of jit's dispatch key and the
                # persistent compile-cache key)
                p = self.mesh.replicate(zeros(model.params),
                                        keep_existing=False)
                s = self.mesh.replicate(zeros(model.states),
                                        keep_existing=False)
                o = zeros(model.opt_states)
                o = (gspmd.place_tree(o, self._zero_specs)
                     if self._zero_specs is not None
                     else self.mesh.replicate(o, keep_existing=False))
                if real_comp is not None:
                    shadow = zeros(real_comp)
                    if self._comp_specs is not None:
                        shadow = gspmd.place_tree(shadow, self._comp_specs)
                    self._comp_state = shadow
                key = (self._lane_keys(jax.random.PRNGKey(0))
                       if self._uses_lanes else jax.random.PRNGKey(0))
                self._sharded_step(p, s, o, jnp.asarray(0), xs, ys, key, w)
                primed += 1
        finally:
            self._comp_state = real_comp
            self._comp_stats = real_stats
            if real_comp is not None:
                self.model._grad_comp_state = real_comp
        return primed


class ParallelInference:
    """Throughput serving over the mesh (ParallelInference parity).

    The reference round-robins requests over model replicas and coalesces
    batches on a queue; here a replicated-params, batch-sharded jitted forward
    serves the full mesh in one call. ``output`` accepts any batch size and
    pads to mesh divisibility.
    """

    def __init__(self, model, mesh: Optional[TrainingMesh] = None,
                 batch_limit: int = 1024, batch_timeout_ms: float = 3.0,
                 queue_limit: int = 256, bucketing=None):
        from deeplearning4j_tpu.data.bucketing import BucketingPolicy

        self.model = model
        self.mesh = mesh or TrainingMesh(data=len(jax.devices()))
        self.batch_limit = batch_limit
        self.batch_timeout_ms = batch_timeout_ms
        # Shape bucketing for serving (docs/COMPILE_CACHE.md): request
        # batches round up to a bucket BEFORE mesh padding, bounding the
        # number of compiled forward signatures under arbitrary traffic.
        # Defaults to the model conf's policy; pass a BucketingPolicy, a
        # spec string ("pow2" / "batch=8,16,32"), or False to disable.
        if bucketing is None:
            bucketing = BucketingPolicy.from_conf(
                getattr(model, "conf", None))
        elif bucketing is False:
            bucketing = None
        elif isinstance(bucketing, str):
            bucketing = BucketingPolicy.from_spec(bucketing)
        self.bucketing = bucketing
        self._params = self.mesh.replicate(model.params)
        self._states = self.mesh.replicate(model.states)
        self._fwd = jax.jit(model.make_forward_fn())
        self._queue: "queue.Queue[Tuple[np.ndarray, Future]]" = queue.Queue(
            maxsize=queue_limit)
        self._worker: Optional[threading.Thread] = None
        self._worker_lock = threading.Lock()
        self._stop = threading.Event()
        self._shut_down = False

    def output(self, x):
        x = np.asarray(x)
        n = len(x)
        if self.bucketing is not None:
            # ONE bucket plan for every request size (data/bucketing.py
            # plan_serving_batch, shared with the serving scheduler —
            # docs/SERVING.md): sizes between buckets pad up to the next
            # bucket, sizes above the largest bucket chunk into
            # largest-bucket pieces — a novel request size NEVER traces a
            # new program once warmup() has primed the buckets
            plan = self.bucketing.plan_serving_batch(n, cap=self.batch_limit)
            if len(plan) > 1:
                chunks, off = [], 0
                for take, padded in plan:
                    chunks.append(self._output_one(x[off:off + take],
                                                   padded))
                    off += take
                return np.concatenate(chunks, axis=0)
            return self._output_one(x, plan[0][1])
        if n > self.batch_limit:
            # chunk to bound per-call device memory (the reference's queue
            # coalescing bounds batches the same way)
            chunks = [
                self._output_one(x[i : i + self.batch_limit])
                for i in range(0, n, self.batch_limit)
            ]
            return np.concatenate(chunks, axis=0)
        return self._output_one(x)

    def _output_one(self, x, target=None):
        """One device call, padded to ``target`` rows (the plan's padded
        size — which the plan may deliberately leave UNPADDED when
        batch_limit excludes every bucket, honoring the memory bound) then
        to mesh divisibility. Without a plan, buckets first then
        mesh-pads."""
        n = len(x)
        d = self.mesh.data
        if target is None:
            # bucket first, then mesh-divisibility: one compiled forward per
            # bucket instead of one per distinct (padded) request size
            target = (n if self.bucketing is None
                      else self.bucketing.bucket_batch(n))
        target += (d - target % d) % d
        pad = target - n
        if pad:
            x = np.concatenate([x, np.repeat(x[-1:], pad, axis=0)], axis=0)
        xs = self.mesh.shard_batch(x)
        out = self._fwd(self._params, self._states, xs)
        return np.asarray(out)[:n]

    def warmup(self, batch_sizes=None, input_shape=None):
        """Pre-compile the serving forward for every bucket before traffic
        (ParallelInference.warmup — docs/COMPILE_CACHE.md): one zero-batch
        call per size primes the dispatch cache, so first-request latency is
        execution-only. ``batch_sizes`` defaults to the explicit
        ``batch_buckets`` list of the bucketing policy; ``input_shape``
        (excl. batch) defaults to the model conf. Returns the number of
        signatures primed."""
        if batch_sizes is None:
            if (self.bucketing is None
                    or not isinstance(self.bucketing.batch_buckets, tuple)):
                raise ValueError(
                    "warmup() without batch_sizes needs an explicit "
                    "batch_buckets bucketing policy")
            batch_sizes = self.bucketing.batch_buckets
        conf = getattr(self.model, "conf", None)
        shape = tuple(input_shape
                      or getattr(conf, "input_shape", None)
                      or (getattr(conf, "input_shapes", None) or [()])[0])
        if not shape:
            raise ValueError("warmup() needs input_shape (or conf.input_shape)")
        primed = 0
        for b in batch_sizes:
            self.output(np.zeros((int(b),) + shape, np.float32))
            primed += 1
        return primed

    # ----------------------------------------------------- dynamic batching
    def output_async(self, x) -> "Future":
        """Queue a request; a background thread coalesces pending requests
        into one device batch (the reference's observable-queue batching in
        ParallelInference.java). Returns a Future of the predictions."""
        with self._worker_lock:
            if self._shut_down:
                raise RuntimeError("ParallelInference shut down")
            if self._worker is None:
                self._start_worker()
            fut: Future = Future()
            self._queue.put((np.asarray(x), fut))
        return fut

    @staticmethod
    def _resolve(fut: Future, value=None, exc=None):
        """Set a future's outcome, tolerating caller-side cancel()."""
        if not fut.set_running_or_notify_cancel():
            return  # cancelled before we got to it
        if exc is not None:
            fut.set_exception(exc)
        else:
            fut.set_result(value)

    def _start_worker(self):
        self._stop.clear()

        def run():
            while not self._stop.is_set():
                try:
                    first = self._queue.get(timeout=0.1)
                except queue.Empty:
                    continue
                batch: List[Tuple[np.ndarray, Future]] = [first]
                total = len(first[0])
                deadline = self.batch_timeout_ms / 1e3
                t0 = time.monotonic()
                while total < self.batch_limit:
                    remaining = deadline - (time.monotonic() - t0)
                    if remaining <= 0:
                        break
                    try:
                        item = self._queue.get(timeout=remaining)
                    except queue.Empty:
                        break
                    batch.append(item)
                    total += len(item[0])
                # the WHOLE batch body is guarded: a bad request (wrong
                # rank/width) must fail its batch, never kill the worker
                try:
                    xs = np.concatenate([b[0] for b in batch], axis=0)
                    preds = self.output(xs)
                    off = 0
                    for arr, fut in batch:
                        self._resolve(fut, value=preds[off:off + len(arr)])
                        off += len(arr)
                except Exception as e:
                    for _, fut in batch:
                        if not fut.done():
                            self._resolve(fut, exc=e)

        self._worker = threading.Thread(target=run, daemon=True)
        self._worker.start()

    def shutdown(self):
        """Stop the batching worker (failing any queued requests); later
        output_async calls raise instead of hanging."""
        with self._worker_lock:
            self._shut_down = True
            self._stop.set()
            worker = self._worker
            self._worker = None
        if worker is not None:
            worker.join(timeout=2.0)
        while True:
            try:
                _, fut = self._queue.get_nowait()
            except queue.Empty:
                break
            if not fut.done():
                self._resolve(fut, exc=RuntimeError("ParallelInference shut down"))
