"""Persistent (on-disk) XLA compilation cache wiring.

A fresh process pays a full trace+compile for every jitted program even when
an identical binary was built seconds earlier by the previous run. JAX ships
a content-addressed on-disk executable cache; this module decides WHERE it
lives, by one rule:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and this module
  sets no directory in code — whoever launched the process (an operator, a
  benchmark harness, a parent that wants a throwaway cache for a cold/warm
  experiment) placed the cache from outside.
- unset: the cache goes to :data:`FIXED_CACHE_DIR`, ``<checkout>/.jax_cache``
  (git-ignored). The directory is part of every cache key, so it never
  depends on a pid, a clock or ``mkdtemp`` — a path that moves never hits.

Nothing is enabled on import: entry points (``chip_smoke.py``,
``chipbench/run.py``, ``serving/fleet_worker.py``) call :func:`enable_persistent_cache` before
their first compile.

Cache keys include the XLA/jaxlib version, backend, and the full HLO — a
jaxlib upgrade or code change misses cleanly (stale entries are harmless;
``clear_persistent_cache`` prunes). Thresholds default to cache-everything
(min compile time 0s, no min entry size): JAX's defaults skip programs that
compile in under a second, and a serving warm-up is dozens of those. See
docs/COMPILE_CACHE.md for layout/invalidation caveats.
"""

from __future__ import annotations

import os
import shutil
from typing import Optional

FIXED_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_persistent_cache(
    *,
    min_compile_time_secs: float = 0.0,
    min_entry_size_bytes: int = -1,
) -> str:
    """Turn on the on-disk executable cache (module doc: the directory is
    ``JAX_COMPILATION_CACHE_DIR`` when set, else :data:`FIXED_CACHE_DIR`)
    with cache-everything thresholds, so a later process deserializes
    instead of recompiling. Idempotent; returns the directory in use."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(FIXED_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", FIXED_CACHE_DIR)
    from deeplearning4j_tpu.util import telemetry as tm

    tm.counter("compile_cache.enables_total")
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", min_compile_time_secs)
    jax.config.update(
        "jax_persistent_cache_min_entry_size_bytes", min_entry_size_bytes)
    # the config updates alone do not take effect once a first compile has
    # latched a no-dir cache object
    compilation_cache.reset_cache()
    return jax.config.jax_compilation_cache_dir


def disable_persistent_cache() -> None:
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_compilation_cache_dir", None)
    compilation_cache.reset_cache()


def cache_dir() -> Optional[str]:
    """The directory JAX persists executables to, or None when off."""
    import jax

    return jax.config.jax_compilation_cache_dir


def cache_entries(path: Optional[str] = None) -> int:
    """Number of persisted executables in the cache dir (0 if absent)."""
    path = path or cache_dir()
    if not path or not os.path.isdir(path):
        return 0
    return sum(1 for f in os.listdir(path) if f.endswith("-cache"))


def clear_persistent_cache(path: Optional[str] = None) -> None:
    """Remove every entry under the cache dir (the dir itself stays)."""
    path = path or cache_dir()
    if not path or not os.path.isdir(path):
        return
    for name in os.listdir(path):
        full = os.path.join(path, name)
        if os.path.isdir(full):
            shutil.rmtree(full, ignore_errors=True)
        else:
            try:
                os.remove(full)
            except OSError:
                pass
