"""The comparison that decides ``correct``: the program's numbers against
the plain reference's, each beside a limit of its own. The limits are in the
configuration's file under ``limits`` and PERF.md gives the readings each
was set from."""

from __future__ import annotations

import statistics


def leaf_gaps(got: dict, want: dict, skip=(), only=None) -> list:
    """Per leaf, the gap between the program's norm and the reference's (not
    the norm of a difference), against the reference's norm of that leaf or
    of the median leaf, whichever is larger. ``skip`` leaves leaves out,
    ``only`` keeps those it names; the median is of all the leaves."""
    median = statistics.median(want.values())
    return [abs(got[k] - want[k]) / max(want[k], median)
            for k in want if k not in skip and (only is None or k in only)]


def worst_leaf_gap(got: dict, want: dict, skip=(), only=None) -> float:
    return max(leaf_gaps(got, want, skip, only))


def median_leaf_gap(got: dict, want: dict, skip=()) -> float:
    return statistics.median(leaf_gaps(got, want, skip))


def angle(a, b) -> float:
    """1 - cosine between two arrays: 0 where they point the same way,
    whatever their lengths; 1 where one of them is all zeros."""
    import numpy as np

    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    scale = np.linalg.norm(a) * np.linalg.norm(b)
    return float(1.0 - a @ b / scale) if scale > 0 else 1.0


def training_numbers(got: dict, want: dict, limits: dict) -> list:
    """``got`` and ``want``: ``losses`` of the first steps, ``grad_norms``
    of the first gradient and ``change_norms`` of the parameters over those
    steps, per leaf, and ``first_grads``, the first gradient itself of the
    leaves that ``limits["grad_angle"]`` names; ``want`` also ``sizes``, the
    count of numbers in each leaf.

    - ``lossN_gap``: each step's loss against the reference's.
    - ``grad_norm_gap``: the median leaf's gap of gradient norms. (The worst
      leaf's is a batch-norm vector of 64 numbers in the first stages, where
      a deep batch-normed net at initialisation is chaotic: it reads 0.2-0.5
      for any arithmetic but the reference's own; PERF.md section 2.)
    - ``big_grad_norm_gap``: the worst gap of gradient norms among the
      leaves of ``limits["big_leaf_size"]`` numbers or more (the matrices
      and kernels: nearly all the parameters). The norm of many numbers
      holds where that of a vector of 64 does not, so here the worst leaf
      is judged, and one leaf whose gradient has the wrong size fails.
    - ``grad_angle.<leaf>``: 1 - cosine between that leaf's gradient and the
      reference's, each leaf with a limit of its own: what rounding or a
      wrong backward pass moves, and norms do not see.
    - ``change_norm_gap``: the worst leaf's gap of the parameters' change.
      Leaves whose reference gradient is under a thousandth of the median
      leaf's move by round-off alone under Adam and are left out.
    """
    med = statistics.median(want["grad_norms"].values())
    still = [k for k, v in want["grad_norms"].items() if v < 1e-3 * med]
    out = [{"name": f"loss{i + 1}_gap",
            "value": abs(g - w) / abs(w), "limit": limits["loss_gap"]}
           for i, (g, w) in enumerate(zip(got["losses"], want["losses"]))]
    out.append({"name": "grad_norm_gap",
                "value": median_leaf_gap(got["grad_norms"],
                                         want["grad_norms"]),
                "limit": limits["grad_norm_gap"]})
    big = {k for k, n in want["sizes"].items()
           if n >= limits["big_leaf_size"]}
    out.append({"name": "big_grad_norm_gap",
                "value": worst_leaf_gap(got["grad_norms"],
                                        want["grad_norms"], only=big),
                "limit": limits["big_grad_norm_gap"]})
    out += [{"name": f"grad_angle.{leaf}",
             "value": angle(got["first_grads"][leaf],
                            want["first_grads"][leaf]), "limit": limit}
            for leaf, limit in limits["grad_angle"].items()]
    out.append({"name": "change_norm_gap",
                "value": worst_leaf_gap(got["change_norms"],
                                        want["change_norms"], skip=still),
                "limit": limits["change_norm_gap"]})
    return out


def serving_numbers(gaps, n_bad: int, limits: dict) -> list:
    """``gaps``: for every served token compared, how far its reference
    logit lies below the reference's best. ``n_bad``: answers of the wrong
    length or with ids outside the vocabulary (exact: limit 0)."""
    return [{"name": "logit_gap_max", "value": max(gaps) if len(gaps) else
             float("inf"), "limit": limits["logit_gap_max"]},
            {"name": "malformed_answers", "value": n_bad, "limit": 0}]


def verdict(numbers: list) -> bool:
    return bool(numbers) and all(
        n["value"] == n["value"] and n["value"] <= n["limit"]
        for n in numbers)
