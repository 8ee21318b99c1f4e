"""Hot-path Pallas kernel engine: dispatch seam + conv/LSTM kernels.

ROADMAP item 2 ("custom Pallas/Mosaic kernels for the conv + LSTM +
attention hot paths") following the cuDNN (arXiv:1410.0759) / TVM
(arXiv:1802.04799) playbook: hand-tiled primitives behind a framework-level
dispatch seam, so the framework code never hard-codes a vendor path. The
seam is the SAME exact-or-kernel pattern ``ops/attention.py`` established
for flash attention, generalized:

- ``kernel_impl``: ``"auto" | "exact" | "pallas"``. ``exact`` always takes
  the XLA-HLO reference path. ``pallas`` forces the kernel: compiled by
  Mosaic on the TPU backend (a geometry the compiler refuses raises the
  compiler's own error — nothing falls back), the Pallas INTERPRETER on any
  other backend, which is how tests/test_kernels.py proves kernel==exact
  without a chip. ``auto`` takes the exact path unless the tuning database
  holds a measured ``pallas`` winner for this (op, shape, dtype, backend):
  a winner can only come from ``benchmarks/autotune.py`` run on that
  backend, so ``auto`` never reaches a kernel the backend's compiler has
  not already compiled and timed.
- Resolution order: explicit ``impl_scope(...)`` context (the nets stamp
  their conf's ``kernel_impl`` here around every trace) > the
  ``DL4J_TPU_KERNEL_IMPL`` env knob > ``"auto"``.

Every kernel is gated by equivalence proofs against the exact path
(docs/KERNELS.md lists the tolerances and what the v5e compiler said about
each kernel). A CPU run proves value/grad equivalence; only a chip run
ranks a kernel against XLA:TPU.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
from typing import Optional

import jax

_VALID = ("auto", "exact", "pallas")

# trace-time override (MultiLayerNetwork/ComputationGraph stamp their conf
# knob here around every forward/loss trace); None = fall through to env
_impl_override: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "dl4j_kernel_impl", default=None)


def validate_impl(impl: Optional[str]) -> Optional[str]:
    if impl is not None and impl not in _VALID:
        raise ValueError(
            f"kernel_impl must be one of {_VALID}, got {impl!r}")
    return impl


@contextlib.contextmanager
def impl_scope(impl: Optional[str]):
    """Pin the kernel dispatch for the dynamic extent (trace time). ``None``
    leaves the ambient resolution (env knob / auto) in place."""
    validate_impl(impl)
    tok = _impl_override.set(impl) if impl is not None else None
    try:
        yield
    finally:
        if tok is not None:
            _impl_override.reset(tok)


def resolve_impl() -> str:
    """Effective kernel_impl: scope override > DL4J_TPU_KERNEL_IMPL > auto."""
    impl = _impl_override.get()
    if impl is None:
        impl = os.environ.get("DL4J_TPU_KERNEL_IMPL") or "auto"
    if impl not in _VALID:
        raise ValueError(
            f"DL4J_TPU_KERNEL_IMPL must be one of {_VALID}, got {impl!r}")
    return impl


def dispatch(supported: bool, op: Optional[str] = None,
             sig: Optional[str] = None, dtype: Optional[str] = None):
    """The one dispatch rule. Returns ``(mode, params)``: ``mode`` is
    ``None`` (take the exact path), ``"pallas"`` (Mosaic-compiled kernel,
    TPU backend), or ``"interpret"`` (Pallas interpreter, any other
    backend); ``params`` carries the tuned kernel parameters (e.g. conv
    ``row_tile``) or ``{}``.

    ``supported``: whether the call site's geometry/dtype has a kernel
    (callers compute this — e.g. conv requires NHWC + HWIO + f32/bf16).

    ``auto`` engages a kernel only on evidence: the call site passes its
    (op, shape-signature, dtype), and a ``pallas`` winner in the tuning
    database (tuning/database.py, ``DL4J_TPU_TUNING_DB``) for the current
    backend/topology decides impl AND parameters. No database, no entry
    or an ``exact`` winner all mean the exact path."""
    if not supported:
        return None, {}
    impl = resolve_impl()
    if impl == "exact":
        return None, {}
    mode = "pallas" if jax.default_backend() == "tpu" else "interpret"
    if impl == "pallas":
        return mode, {}
    winner = _tuned_winner(op, sig, dtype)
    if winner is None or winner.get("impl") != "pallas":
        return None, {}
    return mode, dict(winner.get("params") or {})


def _tuned_winner(op, sig, dtype):
    """Tuning-database consultation for ``auto`` dispatch: the winner
    record or None. Cheap on the trace path — ``database_dir()`` is one
    env/global read when no database is armed, and lookups are cached in
    memory (positive and negative) once one is."""
    if op is None or sig is None:
        return None
    from deeplearning4j_tpu.tuning import database as _tdb

    if _tdb.database_dir() is None:
        return None
    return _tdb.resolve(op, sig, dtype or "float32")


from deeplearning4j_tpu.ops.kernels import conv, lstm  # noqa: E402,F401
