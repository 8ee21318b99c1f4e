"""On-disk AOT lowering store — the cross-process HALF the persistent
compilation cache cannot cover.

``jax_compilation_cache_dir`` (util/compile_cache.py) removes the BACKEND
compile from a warm process, but the warm process still pays the full
Python trace + jaxpr→MLIR lowering (several seconds for the flagship
topology — the dominant term once the backend compile is cached). This
store serializes the LOWERED module (``jax.export``) keyed by everything
the trace depends on; a warm process deserializes StableHLO instead of
re-tracing, and its backend compile then hits the persistent cache — the
full compile-once chain across processes.

Key = sha256 of (function tag, model conf JSON, call signature,
jax/jaxlib versions, a content digest of the deeplearning4j_tpu package
sources, and the tracing-relevant Environment flags). Any code or config
change misses cleanly and re-exports — a stale entry can never be loaded.

Trade-off: ``Exported.call`` does NOT preserve buffer donation, so a
loaded train step keeps an extra copy of params/opt-state alive per step.
Right for serving cold starts and short fine-tunes; for long training runs
on memory-tight chips, prefer plain ``warmup()`` (in-process AOT keeps
donation) and let only the backend cache work across processes.
"""

from __future__ import annotations

import hashlib
import os
from typing import Callable, Optional

_pkg_digest_cache: Optional[str] = None


def aot_build(store: Optional["AotStore"], tag: str, conf_json: str, sig,
              jit_fn, args, kwargs):
    """One AOT executable for a warmup signature, shared by
    MultiLayerNetwork and ComputationGraph: from the lowering store when
    available (deserialize, NO re-trace), else trace+lower+compile —
    exporting to the store along the way so the next process skips the
    trace."""
    if store is None:
        return jit_fn.lower(*args, **kwargs).compile()
    key = store.key(tag, conf_json, sig)
    fn = store.load(key)
    if fn is None:
        from jax import export as jexport

        exported = jexport.export(jit_fn)(*args, **kwargs)
        store.save(key, exported)
        fn = exported.call
    return fn


def _tm():
    from deeplearning4j_tpu.util import telemetry

    return telemetry


def package_digest() -> str:
    """Content digest of every .py file in the deeplearning4j_tpu package —
    part of the store key, so ANY code change invalidates (the traced
    program can depend on any module). ~2 MB of source, computed once per
    process."""
    global _pkg_digest_cache
    if _pkg_digest_cache is None:
        import deeplearning4j_tpu

        root = os.path.dirname(os.path.abspath(deeplearning4j_tpu.__file__))
        h = hashlib.sha256()
        for dirpath, dirnames, filenames in sorted(os.walk(root)):
            dirnames.sort()
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                h.update(os.path.relpath(path, root).encode())
                with open(path, "rb") as fh:
                    h.update(fh.read())
        _pkg_digest_cache = h.hexdigest()
    return _pkg_digest_cache


class AotStore:
    """Directory of serialized ``jax.export`` modules, loaded by exact key."""

    def __init__(self, directory: str):
        self.dir = os.path.abspath(directory)
        os.makedirs(self.dir, exist_ok=True)

    def key(self, tag: str, conf_json: str, sig) -> str:
        import jax

        h = hashlib.sha256()
        for part in (tag, conf_json, repr(sig), jax.__version__,
                     package_digest(), self._env_bits()):
            h.update(repr(part).encode())
        return h.hexdigest()

    @staticmethod
    def _env_bits() -> str:
        """Environment flags that can alter the traced program."""
        from deeplearning4j_tpu.config import get_environment

        env = get_environment()
        return repr((env.debug, env.profiling, env.nan_panic,
                     env.default_compute_dtype))

    def _path(self, key: str) -> str:
        return os.path.join(self.dir, f"{key}.jaxexport")

    def load(self, key: str) -> Optional[Callable]:
        """Deserialize the lowered module for ``key`` -> callable, or None.
        The callable re-compiles the stored StableHLO on first use (a
        persistent-cache hit when that is enabled) — no Python re-trace.
        Hits/misses feed the telemetry registry (``aot_store.hits_total`` /
        ``aot_store.misses_total`` on /metrics)."""
        path = self._path(key)
        if not os.path.exists(path):
            _tm().counter("aot_store.misses_total")
            return None
        from jax import export as jexport

        try:
            with open(path, "rb") as fh:
                exported = jexport.deserialize(fh.read())
        except Exception:
            _tm().counter("aot_store.misses_total")
            return None  # truncated/incompatible blob: treat as a miss
        _tm().counter("aot_store.hits_total")
        return exported.call

    def save(self, key: str, exported) -> str:
        path = self._path(key)
        tmp = path + ".tmp"
        with open(tmp, "wb") as fh:
            fh.write(exported.serialize())
        os.replace(tmp, path)  # atomic: concurrent processes race safely
        _tm().counter("aot_store.saves_total")
        return path

    def entries(self) -> int:
        return sum(1 for f in os.listdir(self.dir)
                   if f.endswith(".jaxexport"))
