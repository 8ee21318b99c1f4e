"""Gradient checking — the correctness backbone.

Reference parity: DL4J's ``GradientCheckUtil``
(org/deeplearning4j/gradientcheck/GradientCheckUtil.java) and the nd4j op
validation framework (org/nd4j/autodiff/validation/{OpValidation,GradCheckUtil}
.java) — path-cite, mount empty this round. Same method: exact central finite
differences in float64, per-parameter comparison of relative error.

TPU-native twist: analytic gradients come from ``jax.grad`` over the op table
(no per-op doDiff code to check — but the lowerings themselves can still be
wrong, e.g. a custom VJP or a non-differentiable reformulation, which is what
this harness catches). Checks run in a local x64 context.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np


DEFAULT_EPS = 1e-6
DEFAULT_MAX_REL_ERROR = 1e-5
DEFAULT_MIN_ABS_ERROR = 1e-8


class GradCheckResult:
    def __init__(self):
        self.failures: list[str] = []
        self.n_params = 0
        self.max_rel_error = 0.0

    @property
    def passed(self) -> bool:
        return not self.failures

    def __repr__(self):
        status = "PASS" if self.passed else "FAIL"
        msg = f"GradCheck {status}: {self.n_params} params, max_rel_error={self.max_rel_error:.3e}"
        if self.failures:
            msg += "\n" + "\n".join(self.failures[:20])
        return msg


def _compare_array(
    result: GradCheckResult,
    label: str,
    array: np.ndarray,
    analytic: np.ndarray,
    eval_at: Callable[[np.ndarray], float],
    *,
    eps: float,
    max_rel_error: float,
    min_abs_error: float,
    max_params_per_array: int,
    rng: np.random.Generator,
) -> None:
    """Shared central-difference loop: perturb entries of ``array``, compare
    (f(x+eps)-f(x-eps))/2eps against ``analytic``; record failures."""
    flat = array.reshape(-1)
    idxs = np.arange(flat.size)
    if flat.size > max_params_per_array:
        idxs = rng.choice(flat.size, size=max_params_per_array, replace=False)
    for j in idxs:
        plus = flat.copy()
        plus[j] += eps
        minus = flat.copy()
        minus[j] -= eps
        numeric = (
            eval_at(plus.reshape(array.shape)) - eval_at(minus.reshape(array.shape))
        ) / (2 * eps)
        ana = analytic.reshape(-1)[j]
        abs_err = abs(numeric - ana)
        denom = max(abs(numeric), abs(ana))
        rel_err = abs_err / denom if denom > 0 else 0.0
        result.n_params += 1
        result.max_rel_error = max(result.max_rel_error, rel_err)
        if rel_err > max_rel_error and abs_err > min_abs_error:
            result.failures.append(
                f"  {label}[{j}]: analytic={ana:.8e} numeric={numeric:.8e} "
                f"rel_err={rel_err:.3e}"
            )


def check_gradients(
    fn: Callable,
    args: Sequence,
    *,
    argnums=None,
    eps: float = DEFAULT_EPS,
    max_rel_error: float = DEFAULT_MAX_REL_ERROR,
    min_abs_error: float = DEFAULT_MIN_ABS_ERROR,
    max_params_per_array: int = 64,
    seed: int = 0,
) -> GradCheckResult:
    """Compare jax.grad of scalar ``fn(*args)`` against fp64 central differences.

    Like GradientCheckUtil.checkGradients: perturb each parameter ±eps; relative
    error must stay below ``max_rel_error`` unless the absolute error is below
    ``min_abs_error``. For large arrays a seeded random subset of
    ``max_params_per_array`` entries is checked (the reference checks all —
    subset keeps CI fast)."""
    if argnums is None:
        argnums = tuple(
            i for i, a in enumerate(args)
            if isinstance(a, (jnp.ndarray, np.ndarray))
            and jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
        )
    elif isinstance(argnums, int):
        argnums = (argnums,)

    with jax.enable_x64():
        args64 = [
            jnp.asarray(a, dtype=jnp.float64) if i in argnums else a
            for i, a in enumerate(args)
        ]
        if jnp.ndim(fn(*args64)) != 0:
            raise ValueError("gradcheck requires a scalar-valued function")
        analytic = jax.grad(fn, argnums=argnums)(*args64)
        result = GradCheckResult()
        rng = np.random.default_rng(seed)

        for gi, ai in enumerate(argnums):
            a = np.asarray(args64[ai], dtype=np.float64)

            def eval_at(v, ai=ai):
                new_args = list(args64)
                new_args[ai] = jnp.asarray(v)
                return float(fn(*new_args))

            _compare_array(
                result, f"arg{ai}", a,
                np.asarray(analytic[gi], dtype=np.float64), eval_at,
                eps=eps, max_rel_error=max_rel_error,
                min_abs_error=min_abs_error,
                max_params_per_array=max_params_per_array, rng=rng,
            )
        return result


def check_model_gradients(
    loss_fn: Callable,
    params,
    *,
    eps: float = DEFAULT_EPS,
    max_rel_error: float = 1e-4,
    min_abs_error: float = 1e-7,
    max_params_per_array: int = 32,
    seed: int = 0,
) -> GradCheckResult:
    """Gradcheck over a parameter pytree: loss_fn(params) -> scalar.

    This is the shape DL4J's layer gradchecks take (flattened param vector vs
    per-param finite difference); here the pytree stays structured. Defaults
    are looser than :func:`check_gradients` (deep compositions accumulate more
    truncation error)."""
    with jax.enable_x64():
        params64 = jax.tree_util.tree_map(
            lambda p: jnp.asarray(p, dtype=jnp.float64), params
        )
        analytic = jax.grad(loss_fn)(params64)
        leaves, treedef = jax.tree_util.tree_flatten(params64)
        grad_leaves = jax.tree_util.tree_leaves(analytic)
        result = GradCheckResult()
        rng = np.random.default_rng(seed)

        for li, (leaf, gleaf) in enumerate(zip(leaves, grad_leaves)):

            def eval_at(v, li=li):
                new_leaves = list(leaves)
                new_leaves[li] = jnp.asarray(v)
                return float(loss_fn(jax.tree_util.tree_unflatten(treedef, new_leaves)))

            _compare_array(
                result, f"leaf{li}", np.asarray(leaf, dtype=np.float64),
                np.asarray(gleaf, dtype=np.float64), eval_at,
                eps=eps, max_rel_error=max_rel_error,
                min_abs_error=min_abs_error,
                max_params_per_array=max_params_per_array, rng=rng,
            )
        return result
