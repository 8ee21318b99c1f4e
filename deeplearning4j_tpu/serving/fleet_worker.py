"""Fleet worker entry point (serving/fleet.py spawns this):

    python -m deeplearning4j_tpu.serving.fleet_worker \\
        --spec spec.json --worker-id w0 --ready-file w0.ready.json

Replays the fleet spec (:func:`~deeplearning4j_tpu.serving.fleet.
fleet_spec`): restores each model's ModelSerializer archive, registers it
on a fresh :class:`ModelRouter`, starts a warmed :class:`ModelServer` on
an ephemeral port, and publishes ``{"port", "pid", "worker_id"}`` to the
ready file (atomic tmp + rename — the supervisor never reads a torn
handshake). The process then serves until SIGTERM, which runs the
server's graceful drain (finish queued work, 503 new admissions) before
exiting 0 — the same finish-in-flight contract the single-process tier
gives a preemption notice. A respawned worker with ``export_dir`` in its
``model_kw`` warms from the AOT export store instead of re-tracing
(docs/SERVING.md#fleet).

Spec ``env`` entries are applied before jax imports, so XLA thread
pinning (``XLA_FLAGS``) and ``DL4J_TPU_*`` knobs take effect in every
worker uniformly.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def build_router(spec: dict):
    """A ModelRouter loaded per the fleet spec. Imported lazily so the
    ``--help`` path and the spec/env plumbing stay jax-free."""
    from deeplearning4j_tpu.data.bucketing import BucketingPolicy
    from deeplearning4j_tpu.serving.model import ServingModel
    from deeplearning4j_tpu.serving.router import ModelRouter
    from deeplearning4j_tpu.util.model_serializer import ModelSerializer

    router = ModelRouter(name=spec.get("name", "fleet-worker"))
    for m in spec.get("models", []):
        net = ModelSerializer.restore_model(m["path"], load_updater=False)
        kw = dict(m.get("model_kw") or {})
        b = kw.get("bucketing")
        if isinstance(b, dict):
            kw["bucketing"] = BucketingPolicy(
                batch_buckets=tuple(b["batch_buckets"])
                if b.get("batch_buckets") else None,
                seq_buckets=tuple(b["seq_buckets"])
                if b.get("seq_buckets") else None)
        elif isinstance(b, str):
            kw["bucketing"] = BucketingPolicy.from_spec(b)
        if m.get("draft_path"):
            kw["draft_net"] = ModelSerializer.restore_model(
                m["draft_path"], load_updater=False)
        model = ServingModel(net, m["id"], kind=m.get("kind", "classify"),
                             quantize=m.get("quantize"), **kw)
        router.register(model, **(m.get("register") or {}))
    return router


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--spec", required=True, help="fleet spec JSON path")
    ap.add_argument("--worker-id", required=True)
    ap.add_argument("--ready-file", required=True,
                    help="where to publish {port,pid} once warmed")
    args = ap.parse_args(argv)
    with open(args.spec) as f:
        spec = json.load(f)
    for k, v in (spec.get("env") or {}).items():
        # the supervisor already put these in our environment; honoring
        # them here too makes the module runnable by hand with the same
        # spec (setdefault: an explicit operator override wins)
        os.environ.setdefault(str(k), str(v))
    # before the first compile: a respawned worker deserializes its
    # warm-up executables instead of recompiling them
    from deeplearning4j_tpu.util.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    router = build_router(spec)
    from deeplearning4j_tpu.serving.server import ModelServer

    server = ModelServer(router, port=int(spec.get("port", 0)),
                         worker_id=args.worker_id).start(warmup=True)
    tmp = args.ready_file + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"port": server.port, "pid": os.getpid(),
                   "worker_id": args.worker_id, "host": server.host}, f)
    os.replace(tmp, args.ready_file)
    try:
        # serve until SIGTERM flips the drain flag (ModelServer installed
        # the handler — this IS the main thread) or the server dies
        while server._thread is not None and server._thread.is_alive():
            if server.draining:
                server.wait_drained(timeout=60.0)
                break
            time.sleep(0.2)
    finally:
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
