"""Fusion-boundary engineering: selective rematerialization + XLA tuning.

Why this module exists (r5 trace, 2026-07): the flagship ResNet-50 step's
device floor decomposes into ≈35.5 ms irreducible conv compute + ≈35.2 ms
bandwidth-floor non-conv work + **≈36 ms fusion-context cost** — convs inside
the fused train step run at roughly half their isolated efficiency. Whole-loss
remat was measured and REJECTED (+32%, r5): recomputing the convs costs full
price. The open lever is *finer-grained* control of what XLA keeps live
across the forward/backward boundary and where fusion regions end:

- **Selective remat** (`jax.checkpoint` + `checkpoint_policies`): per-stage
  policies that SAVE the expensive conv/dot outputs and recompute only the
  cheap elementwise/BN epilogue in the backward pass. The conv ops in
  ``ops/nn.py`` tag their outputs with ``checkpoint_name(..., 'conv_out')``
  (dense matmuls tag ``'dot_out'``) so name-based policies can target them.
- **Optimization barriers** (`lax.optimization_barrier`) at residual-stage
  boundaries: forbids XLA from fusing across stages, bounding the live-range
  and memory pressure each fusion region sees.
- **XLA flag candidates** for the sweep harness (`benchmarks/fusion_sweep.py`):
  process-global scheduling/fusion knobs, validated per-build in a subprocess
  (unknown flags abort XLA, so candidates never run in-process).

This is the schedule/fusion search space TVM explores automatically
(PAPERS.md: arXiv 1802.04799) applied to the path the reference delegated to
cuDNN's hand-tuned primitives (arXiv 1410.0759).

Usage: ``NeuralNetConfiguration.builder().remat_policy('save_conv')`` plus
``stage_boundary()`` markers (the zoo ResNet-50 marks its residual stages);
the config JSON round-trips. ``DL4J_TPU_REMAT_POLICY`` sets the default.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
from jax import lax
from jax.ad_checkpoint import checkpoint_name

# Names used by ops/nn.py to tag rematerialization-relevant outputs.
CONV_OUT = "conv_out"
DOT_OUT = "dot_out"

_cp = jax.checkpoint_policies

# name -> factory returning a jax checkpoint policy, or None for "recompute
# everything inside the stage" (jax.checkpoint's default behaviour).
_POLICIES: Dict[str, Optional[Callable[[], Any]]] = {
    # per-stage full remat: save only the stage-boundary activations
    "full": None,
    # save conv outputs, recompute the cheap BN/elementwise epilogue
    "save_conv": lambda: _cp.save_only_these_names(CONV_OUT),
    # save conv AND dense-matmul outputs
    "save_conv_dots": lambda: _cp.save_from_both_policies(
        _cp.save_only_these_names(CONV_OUT, DOT_OUT),
        _cp.dots_with_no_batch_dims_saveable,
    ),
    # save every non-batched dot (transformer-style policy; convs recompute)
    "save_dots": lambda: _cp.dots_with_no_batch_dims_saveable,
    # save everything: remat-free, but the checkpoint stages still scope
    # XLA's fusion regions (A/B candidate for boundary effects alone)
    "save_all": lambda: _cp.everything_saveable,
}


def policy_names() -> List[str]:
    """Registered policy names ('none' disables wrapping)."""
    return ["none"] + sorted(_POLICIES)


def register_policy(name: str, factory: Optional[Callable[[], Any]]):
    """Register a custom policy (factory -> jax checkpoint policy, or None
    for full per-stage remat)."""
    _POLICIES[name] = factory
    return factory


def resolve_policy(name: Optional[str]) -> Tuple[bool, Optional[Any]]:
    """(wrap_stages, checkpoint_policy) for a configured policy name.

    ``None``/'none' -> (False, None): stages run unwrapped.
    'full'          -> (True, None): jax.checkpoint default (recompute all).
    otherwise       -> (True, policy) from the registry.
    """
    if name is None or name == "none":
        return False, None
    try:
        factory = _POLICIES[name]
    except KeyError:
        raise ValueError(
            f"unknown remat policy {name!r}; known: {policy_names()}"
        ) from None
    return True, (factory() if factory is not None else None)


def checkpoint_stage(fn: Callable, policy_name: Optional[str]) -> Callable:
    """Wrap one stage function in jax.checkpoint per the named policy
    (identity for 'none')."""
    wrap, policy = resolve_policy(policy_name)
    if not wrap:
        return fn
    return jax.checkpoint(fn, policy=policy)


def tag(x, name: str):
    """Tag an intermediate for name-based checkpoint policies. Transparent
    (identity) outside a jax.checkpoint region."""
    return checkpoint_name(x, name)


@jax.custom_vjp
def barrier(tree):
    """Fusion fence: forbids XLA from fusing/scheduling across this point.
    Accepts any pytree of arrays and returns it unchanged in value.
    Differentiable (``lax.optimization_barrier`` has no autodiff rule): the
    cotangents pass through a barrier too, fencing the backward stage
    boundaries symmetrically with the forward ones."""
    return lax.optimization_barrier(tree)


def _barrier_fwd(tree):
    return lax.optimization_barrier(tree), None


def _barrier_bwd(_, ct):
    return (lax.optimization_barrier(ct),)


barrier.defvjp(_barrier_fwd, _barrier_bwd)


# --------------------------------------------------------------------------
# XLA flag-sweep candidates (benchmarks/fusion_sweep.py)
# --------------------------------------------------------------------------
# Each candidate is (name, flag-string appended to XLA_FLAGS). Flags are
# process-global and unknown flags ABORT XLA at client init, so the harness
# applies them only in a fresh subprocess and reports per-build validity
# instead of assuming it. TPU-prefixed flags are expected to be rejected on
# the CPU backend — that rejection is itself recorded in the sweep table.
XLA_FLAG_CANDIDATES: List[Tuple[str, str]] = [
    ("flags:opt_level_2", "--xla_backend_optimization_level=2"),
    ("flags:no_xla_remat", "--xla_disable_hlo_passes=rematerialization"),
    ("flags:tpu_vmem_64M", "--xla_tpu_scoped_vmem_limit_kib=65536"),
    ("flags:tpu_no_latency_sched",
     "--xla_tpu_enable_latency_hiding_scheduler=false"),
]
