"""ServingModel — one loaded model behind the batching scheduler.

Binds a trained ``MultiLayerNetwork``/``ComputationGraph`` (or a mesh-backed
``ParallelInference``) to the serving tier with ONE
:class:`~deeplearning4j_tpu.data.bucketing.BucketingPolicy` as the shared
source of truth for every shape decision — warmup, coalescing limit,
request padding, and prefill/decode buckets all read the same policy, so a
request size that "falls between buckets" pads up to the next bucket
instead of tracing a new program (docs/SERVING.md).

Two kinds:

- ``kind="classify"``: forward inference. Requests are (n, …feature) row
  batches; the scheduler's coalesced rows are chunk-planned
  (``plan_serving_batch``) and executed through the AOT-warmed
  ``net.output`` path (or ``ParallelInference.output`` when ``use_mesh``),
  then split back per request. Row independence makes the batched result
  bit-identical to per-request results.
- ``kind="generate"``: paged-KV-cache autoregressive decode
  (serving/generate.py — paged block pool, optional speculative decoding
  via ``draft_net``/``spec_tokens`` or the model's own MTP module as
  ``self_draft``, optional ``quantize="int8"``).
  Requests are token prompts; coalesced prompts decode as one batch,
  per-request ``max_new_tokens`` honored by trimming (rows are
  attention-independent, so batching never changes a row's tokens). A
  batch the block pool cannot hold sheds ``PoolExhaustedError`` (429).

``quantize="int8"`` on either kind serves resident int8 weights +
per-channel scales with the dequantize inside the forward
(serving/quantize.py); the fp32 path is bit-unchanged.

``execute`` counts the XLA traces it causes via the CompileWatcher — the
scheduler publishes them as ``serving.recompiles_total``, the steady-state-
zero contract tests/test_serving.py asserts.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from deeplearning4j_tpu.data.bucketing import BucketingPolicy
from deeplearning4j_tpu.util import faults as fl
from deeplearning4j_tpu.util import telemetry as tm
from deeplearning4j_tpu.util.compile_watcher import get_watcher

_DEFAULT_BUCKETS = (1, 2, 4, 8, 16, 32)


class ServingModel:
    """One model-id's executor (see module doc)."""

    def __init__(self, net, model_id: str, *, kind: str = "classify",
                 bucketing=None, use_mesh: bool = False,
                 export_dir: Optional[str] = None,
                 max_length: Optional[int] = None,
                 prefill_buckets=None,
                 paged: bool = True, block_size: int = 16,
                 pool_blocks: Optional[int] = None,
                 prefix_cache: bool = False,
                 prefill_chunk: Optional[int] = None,
                 draft_net=None, spec_tokens: int = 4, self_draft=None,
                 quantize: Optional[str] = None):
        if kind not in ("classify", "generate"):
            raise ValueError(f"unknown serving kind {kind!r}")
        self.net = net
        self.model_id = str(model_id)
        self.kind = kind
        self.export_dir = export_dir
        self._max_length = max_length
        self._use_mesh = bool(use_mesh)
        # decode-engine knobs (docs/SERVING.md#paged-kv--speculative-decode)
        self._paged = bool(paged)
        self._block_size = int(block_size)
        self._pool_blocks = pool_blocks
        self._prefix_cache = bool(prefix_cache)
        self._prefill_chunk = prefill_chunk
        self._draft_net = draft_net
        self._spec_tokens = int(spec_tokens)
        self._self_draft = self_draft
        self.quantize = quantize
        self._qp = None       # classify-kind int8 residents
        self._qforward = None
        #: rolling-reload version surface (docs/SERVING.md#resilience):
        #: starts at 1, bumps on every successful swap_from()
        self.version = 1
        self.reload_time: Optional[float] = None
        # execute() holds this for each batch; a rolling reload's swap takes
        # it too, so the swap lands BETWEEN batch cycles — the in-flight
        # batch finishes on the old weights, the next one runs the new.
        # REENTRANT: a chunked prefill's yield hook re-enters execute()
        # from the same worker thread to run queued decode batches
        # between prompt chunks (serving/scheduler.py).
        self._swap_lock = threading.RLock()
        if isinstance(bucketing, str):
            bucketing = BucketingPolicy.from_spec(bucketing)
        if bucketing is None:
            bucketing = BucketingPolicy.from_conf(getattr(net, "conf", None))
        if bucketing is None or not isinstance(
                bucketing.batch_buckets, tuple):
            # serving needs a FINITE bucket list (warmup must enumerate it);
            # keep any seq buckets the conf declared
            seq = getattr(bucketing, "seq_buckets", None)
            bucketing = BucketingPolicy(batch_buckets=_DEFAULT_BUCKETS,
                                        seq_buckets=seq)
        self.policy = bucketing
        self.inference = None
        self.generator = None
        if kind == "generate":
            from deeplearning4j_tpu.serving.generate import Generator

            self.generator = Generator(
                net, max_length=max_length,
                batch_buckets=self.policy.batch_buckets,
                prefill_buckets=(prefill_buckets
                                 or self.policy.seq_buckets),
                paged=self._paged, block_size=self._block_size,
                pool_blocks=self._pool_blocks,
                prefix_cache=self._prefix_cache,
                prefill_chunk=self._prefill_chunk,
                draft_net=self._draft_net, spec_tokens=self._spec_tokens,
                self_draft=self._self_draft,
                quantize=quantize, model_id=self.model_id)
            self.policy = self.generator.policy
            self._qp = self.generator._qp
        elif quantize is not None:
            if use_mesh:
                raise ValueError("quantize + use_mesh is not supported — "
                                 "the mesh path shards fp32 params")
            from deeplearning4j_tpu.serving.quantize import maybe_quantize
            from deeplearning4j_tpu.util.compile_watcher import note_trace

            self._qp = maybe_quantize(net, quantize,
                                      model_id=self.model_id)
            fwd, qp = net.make_forward_fn(), self._qp

            def _qfwd(raw, states, x):
                # the int8 classify executable: dequantize-in-forward over
                # the resident (int8, scales) leaves (serving/quantize.py)
                note_trace("serving.classify_int8", x)
                return fwd(qp.rebuild(raw), states, x)

            import jax

            self._qforward = jax.jit(_qfwd)
        if use_mesh and kind != "generate":
            from deeplearning4j_tpu.parallel.wrapper import ParallelInference

            # the SAME policy object the scheduler plans with — one bucket
            # source of truth for warmup() and coalescing
            self.inference = ParallelInference(net, bucketing=self.policy)
        self.warmed = False

    @property
    def supports_chunked_prefill(self) -> bool:
        """Whether this model's batches can yield mid-prefill — the
        scheduler only wires its interleave hook into models that chunk
        (one whole-prompt prefill has no yield points)."""
        return (self.generator is not None
                and self.generator.prefill_chunk is not None)

    # -------------------------------------------------------------- shapes
    def coalesce_limit(self) -> int:
        """Largest batch the scheduler should coalesce to — the largest
        bucket (a bigger batch would just be split again)."""
        top = self.policy.largest_batch_bucket()
        return int(top) if top else 64

    def payload_rows(self, payload) -> int:
        if self.kind == "generate":
            return 1  # one prompt row per request
        return int(np.shape(payload)[0])

    # -------------------------------------------------------------- warmup
    def warmup(self) -> int:
        """Compile every bucket signature before traffic: the classify
        forward per batch bucket (through the r8 AOT path — with
        ``export_dir`` a warm process deserializes the stored lowering
        instead of re-tracing), or every prefill/decode executable for
        generate. Returns the number of signatures primed."""
        if self.kind == "generate":
            primed = self.generator.warmup()
        elif self._qforward is not None:
            conf = getattr(self.net, "conf", None)
            shape = tuple(getattr(conf, "input_shape", None) or ())
            if not shape:
                raise ValueError(
                    f"{self.model_id}: warmup() needs conf.input_shape")
            raw = self._qp.args()
            primed = 0
            for b in self.policy.batch_buckets:
                self._qforward(raw, self.net.states,
                               np.zeros((int(b),) + shape, np.float32))
                primed += 1
        elif self.inference is not None:
            primed = self.inference.warmup(
                batch_sizes=self.policy.batch_buckets)
        else:
            conf = getattr(self.net, "conf", None)
            shape = tuple(getattr(conf, "input_shape", None) or ())
            if not shape:
                raise ValueError(
                    f"{self.model_id}: warmup() needs conf.input_shape")
            primed = self.net.warmup(
                shapes=[(int(b),) + shape
                        for b in self.policy.batch_buckets],
                train=False, inference=True, export_dir=self.export_dir)
            # prime the jit dispatch too (output() prefers AOT executables,
            # but a signature miss must still find a warm jit cache)
            for b in self.policy.batch_buckets:
                self.net.output(np.zeros((int(b),) + shape, np.float32))
        self.warmed = True
        return primed

    # ------------------------------------------------------------- execute
    def execute(self, payloads: List[Any], _trace: bool = False,
                _step: Optional[int] = None, _yield=None, **opts
                ) -> Tuple[List[Any], Dict[str, Any]]:
        """Run one coalesced batch; returns (per-payload results, stats).
        stats: real/padded row counts and the number of XLA traces this
        batch caused (0 in steady state); generate batches add
        ``decode_tokens``/``decode_seconds`` for per-request tokens/sec.
        ``_trace`` (set by the scheduler for head-sampled batches) emits
        the batch-level pad/device/decode phase spans; ``_step`` is the
        scheduler's batch-cycle number — the serving faults' ``@nth``
        concept (util/faults.py). The faults fire ONLY on scheduler
        batches (``_step`` set): a reload's canary and direct execute()
        calls run with ``_step=None``, and letting them consume a stepless
        armed fault would reject a good reload while the fault's
        documented target — the live worker — never saw it."""
        if _step is not None:
            injector = fl.get_injector()
            fault = injector.fire(fl.SERVING_SLOW_BATCH, step=_step)
            if fault is not None:
                # a real stall on the real worker thread: queued requests
                # behind it age toward their deadlines exactly as they
                # would behind a wedged device
                time.sleep((fault.arg or 50.0) / 1e3)
            if injector.fire(fl.SERVING_COMPUTE_ERROR,
                             step=_step) is not None:
                raise RuntimeError(
                    f"{self.model_id}: injected serving compute error "
                    f"(batch {_step})")
        watcher = get_watcher()
        with self._swap_lock:
            # traces are counted per-THREAD: a concurrent reload warming
            # its shadow model on another thread must not read as this
            # batch having recompiled (docs/SERVING.md#resilience)
            traces_before = watcher.thread_traces()
            stats: Dict[str, Any] = {}
            if self.kind == "generate":
                results, real, padded = self._execute_generate(
                    payloads, _trace=_trace, _stats=stats, _yield=_yield,
                    **opts)
            else:
                results, real, padded = self._execute_classify(
                    payloads, _trace=_trace, **opts)
            stats.update({
                "real_rows": real,
                "padded_rows": padded,
                "recompiles": watcher.thread_traces() - traces_before,
            })
        return results, stats

    def _emit(self, name: str, t0_ns: int, **args):
        # deferred (no registry lock): this runs on the scheduler worker
        # while other models' workers hold the GIL — see event_deferred
        tm.get_telemetry().event_deferred(name, t0_ns, time.time_ns(),
                                          model=self.model_id, **args)

    def _execute_classify(self, payloads, _trace=False, **opts):
        # on a serving worker: launch from the first forward, drain once
        # every output is on the host (each chunk is fetched as it is run,
        # so launch holds the device's time and wait is empty)
        if opts:
            raise ValueError(f"classify takes no options, got {opts}")
        n = sum(int(np.shape(p)[0]) for p in payloads)
        # the SAME cap-aware plan the mesh path executes, so the occupancy
        # stat reflects the padding that actually ran (mesh-divisibility
        # rounding of the 'data' axis is not included — on a 1-device
        # serving mesh it is zero)
        cap = (self.inference.batch_limit if self.inference is not None
               else None)
        plan = self.policy.plan_serving_batch(n, cap=cap)
        padded = sum(p for _, p in plan)
        if self.inference is not None:
            t0 = time.time_ns() if _trace else 0
            xs = np.concatenate([np.asarray(p) for p in payloads], axis=0)
            if _trace:
                self._emit("serving.exec.pad", t0, rows=n)
            t1 = time.time_ns() if _trace else 0
            tm.phase("serving.generate.launch")
            out = self.inference.output(xs)  # plans the chunks inside
            tm.phase("serving.generate.drain")
            if _trace:
                self._emit("serving.exec.device", t1, rows=n, padded=padded)
        else:
            # bucket-padding phase (host work) separated from the device
            # phase so a sampled trace shows where the milliseconds went
            t0 = time.time_ns() if _trace else 0
            xs = np.concatenate([np.asarray(p) for p in payloads], axis=0)
            padded_chunks, off = [], 0
            for take, bucket in plan:
                chunk = xs[off:off + take]
                if bucket != take:
                    pad = np.zeros((bucket - take,) + xs.shape[1:],
                                   xs.dtype)
                    chunk = np.concatenate([chunk, pad], axis=0)
                padded_chunks.append((chunk, take))
                off += take
            if _trace:
                self._emit("serving.exec.pad", t0, rows=n, padded=padded)
            t1 = time.time_ns() if _trace else 0
            tm.phase("serving.generate.launch")
            if self._qforward is not None:
                raw = self._qp.args()
                chunks = [np.asarray(self._qforward(
                    raw, self.net.states, chunk))[:take]
                          for chunk, take in padded_chunks]
            else:
                chunks = [np.asarray(self.net.output(chunk))[:take]
                          for chunk, take in padded_chunks]
            out = np.concatenate(chunks, axis=0)
            tm.phase("serving.generate.drain")
            if _trace:
                self._emit("serving.exec.device", t1, rows=n,
                           padded=padded, chunks=len(plan))
        results, off = [], 0
        for p in payloads:
            k = int(np.shape(p)[0])
            results.append(out[off:off + k])
            off += k
        return results, n, padded

    def _execute_generate(self, payloads, _trace=False, _stats=None,
                          _yield=None, **opts):
        prompts = [list(np.asarray(p).ravel().astype(np.int64)) for p in
                   payloads]
        max_new = int(opts.get("max_new_tokens", 16))
        t0 = time.perf_counter()
        tokens = self.generator.generate(
            prompts, max_new_tokens=max_new,
            temperature=float(opts.get("temperature", 0.0)),
            eos_id=opts.get("eos_id"), trace=_trace,
            stats=_stats,  # speculation: draft_accept_rate per rider
            yield_hook=_yield)  # chunked prefill: scheduler interleave
        if _stats is not None:
            # decode wall (incl. prefill) — the scheduler turns this into
            # per-request serving.decode_tokens_per_sec observations
            _stats["decode_seconds"] = time.perf_counter() - t0
            _stats["decode_tokens"] = sum(len(t) for t in tokens)
        real = len(prompts)
        padded = self.policy.bucket_batch(real)
        return tokens, real, padded

    # ----------------------------------------------------- rolling reload
    def clone_with_net(self, net) -> "ServingModel":
        """A SHADOW ServingModel around ``net`` with this model's exact
        serving configuration (kind, bucket policy, export dir, mesh) —
        the reload pipeline warms and canary-validates it without touching
        the live model's caches (docs/SERVING.md#resilience)."""
        return ServingModel(net, self.model_id, kind=self.kind,
                            bucketing=self.policy,
                            use_mesh=self._use_mesh,
                            export_dir=self.export_dir,
                            max_length=self._max_length,
                            paged=self._paged,
                            block_size=self._block_size,
                            pool_blocks=self._pool_blocks,
                            prefix_cache=self._prefix_cache,
                            prefill_chunk=self._prefill_chunk,
                            draft_net=self._draft_net,
                            spec_tokens=self._spec_tokens,
                            self_draft=self._self_draft,
                            quantize=self.quantize)

    def structure_matches(self, net) -> bool:
        """Whether ``net``'s parameter tree is swap-compatible with the
        live one (same treedef, same leaf shapes) — a reload that changes
        topology must go through a fresh ``register()``, not a swap."""
        import jax

        live = jax.tree_util.tree_leaves(self.net.params)
        new = jax.tree_util.tree_leaves(net.params)
        if (jax.tree_util.tree_structure(self.net.params)
                != jax.tree_util.tree_structure(net.params)):
            return False
        return all(np.shape(a) == np.shape(b) for a, b in zip(live, new))

    def canary_check(self, payload=None) -> Tuple[bool, str]:
        """Run one canary batch through THIS model (a warmed shadow during
        reload) and decide whether the weights are servable: the forward
        must complete and produce finite values. Corrupt or NaN-producing
        weights fail here and never reach traffic. Returns (ok, detail)."""
        try:
            if self.kind == "generate":
                if not self.generator.health_probe():
                    return False, "non-finite prefill logits"
                toks, _ = self.execute(
                    [np.asarray([1, 2, 3], np.int32)]
                    if payload is None else [payload], max_new_tokens=2)
                if not toks or not toks[0]:
                    return False, "canary decode produced no tokens"
            else:
                if payload is None:
                    conf = getattr(self.net, "conf", None)
                    shape = tuple(getattr(conf, "input_shape", None) or ())
                    if not shape:
                        return False, "no canary payload and no input_shape"
                    payload = np.zeros((1,) + shape, np.float32)
                out, _ = self.execute([payload])
                arr = np.asarray(out[0])
                if not np.all(np.isfinite(arr)):
                    bad = int(arr.size - np.isfinite(arr).sum())
                    return False, (f"canary output has {bad} non-finite "
                                   f"value(s) of {arr.size}")
        except Exception as e:  # noqa: BLE001 — canary verdict, not a crash
            return False, f"canary raised {type(e).__name__}: {e}"
        return True, ""

    def swap_from(self, shadow: "ServingModel") -> int:
        """Atomically adopt the shadow's (warmed, canary-validated) net and
        executors. Taken under the same lock ``execute`` holds, so the swap
        lands between batch cycles: zero shed requests, and — because the
        shadow warmed every bucket signature on its own thread — zero
        steady-state recompiles after it. Returns the new version."""
        with self._swap_lock:
            self.net = shadow.net
            self.generator = shadow.generator
            self.inference = shadow.inference
            # int8 residents swap WITH the net: the classify executable
            # branches on _qforward (whose closure holds the quantized
            # leaves) — leaving the old pair here would silently keep
            # serving the PRE-reload weights while version advances
            self._qp = shadow._qp
            self._qforward = shadow._qforward
            self.policy = shadow.policy
            self.warmed = shadow.warmed
            self.version += 1
            self.reload_time = time.time()
        tm.gauge("serving.model_version", self.version, model=self.model_id)
        return self.version

    def describe(self) -> dict:
        out = {
            "kind": self.kind,
            "buckets": self.policy.to_spec(),
            "coalesce_limit": self.coalesce_limit(),
            "warmed": self.warmed,
            "version": self.version,
            "reload_time": self.reload_time,
            "iteration": int(getattr(self.net, "iteration", 0) or 0),
            "mesh": self.inference is not None,
            "params": int(self.net.num_params())
            if hasattr(self.net, "num_params") else None,
        }
        if self.quantize:
            out["quantize"] = self.quantize
            if self._qp is not None:
                out["weight_bytes_resident"] = self._qp.resident_bytes()
                out["weight_bytes_fp32"] = self._qp.fp32_bytes()
        if self.generator is not None:
            pool = self.generator.pool_stats()
            if pool is not None:
                out["kv_pool"] = pool
            hit = self.generator.prefix_hit_rate()
            if hit is not None:
                # top-level so the fleet router's /v1/models poll reads it
                # without unpacking kv_pool (docs/SERVING.md#fleet)
                out["prefix_hit_rate"] = hit
            if self.generator.draft is not None:
                out["speculative"] = {
                    "spec_tokens": self.generator.spec_tokens,
                    "draft_params": int(
                        self.generator.draft.net.num_params())
                    if hasattr(self.generator.draft.net, "num_params")
                    else None,
                }
            elif self.generator.mtp is not None:
                out["speculative"] = {
                    "spec_tokens": self.generator.spec_tokens,
                    "self_draft": True}
        return out
