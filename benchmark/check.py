"""The comparison that decides ``correct``: every number compared is
printed beside its limit (PERF.md section 2 says what each limit was
set from).  A number that is missing, or not finite, fails."""
from __future__ import annotations

import math
import statistics
import sys
from typing import Dict, List

import numpy as np


def _flat(tree) -> Dict[str, float]:
    from benchmark.weights import leaf_paths
    import jax

    return dict(zip(leaf_paths(tree),
                    (float(x) for x in jax.tree_util.tree_leaves(tree))))


def worst_leaf_gap(got, want, skip=()) -> dict:
    """Gap between the program's norm and the reference's, by the worst
    leaf, against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    got, want = _flat(got), _flat(want)
    median = statistics.median(want.values())
    worst, where = 0.0, ""
    for path, ref in want.items():
        if path in skip:
            continue
        gap = abs(got[path] - ref) / max(ref, median, 1e-30)
        if not math.isfinite(gap):
            gap = float("inf")
        if gap >= worst:
            worst, where = gap, path
    return {"value": worst, "leaf": where}


def worst_leaf_difference(got, want) -> float:
    """Norm of the difference of two trees' leaves, by the worst leaf,
    against the reference's norm of that leaf or of the median leaf."""
    import jax

    got = jax.tree_util.tree_leaves(got)
    want = jax.tree_util.tree_leaves(want)
    norms = [float(np.linalg.norm(np.asarray(w, np.float64))) for w in want]
    median = statistics.median(norms)
    return max(
        float(np.linalg.norm(np.asarray(g, np.float64)
                             - np.asarray(w, np.float64)))
        / max(n, median, 1e-30) for g, w, n in zip(got, want, norms))


def nought_gradient_leaves(ref_grad_norms, share: float = 1e-3) -> List[str]:
    """Leaves whose reference gradient is nought to rounding (under a
    thousandth of the median leaf's): Adam moves them by round-off
    alone, so they are left out of the change."""
    flat = _flat(ref_grad_norms)
    median = statistics.median(flat.values())
    return [p for p, v in flat.items() if v < share * median]


def training_numbers(program: dict, reference: dict) -> Dict[str, float]:
    """``program`` and ``reference``: ``losses``, ``grad1_norms``,
    ``change_norms`` over the same first steps."""
    pl, rl = program["losses"], reference["losses"]
    if len(pl) < len(rl):
        return {"loss_gap": float("inf"), "grad1_gap": float("inf"),
                "change_gap": float("inf")}
    loss_gap = max(abs(p - r) / abs(r) for p, r in zip(pl, rl))
    skip = nought_gradient_leaves(reference["grad1_norms"])
    extra = {}
    if reference.get("update1") is not None:
        extra["update1_gap"] = worst_leaf_difference(
            program["update1"], reference["update1"]) \
            if program.get("update1") is not None else float("inf")
    return {
        **extra,
        "loss_gap": loss_gap,
        "grad1_gap": worst_leaf_gap(program["grad1_norms"],
                                    reference["grad1_norms"])["value"],
        "change_gap": worst_leaf_gap(program["change_norms"],
                                     reference["change_norms"],
                                     skip)["value"],
    }


def judge(numbers: Dict[str, float], limits: Dict[str, float],
          flags: Dict[str, bool]) -> dict:
    """-> ``{"correct": bool, "compared": {name: [number, limit]}}``;
    ``flags`` are the yes/no conditions (no compile in the window, every
    request answered, ...), each with the limit 0 faults."""
    compared, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name, float("nan"))
        value = float(value)
        good = math.isfinite(value) and value <= limit
        ok = ok and good
        compared[name] = [value if math.isfinite(value) else str(value),
                          limit]
    for name, passed in flags.items():
        ok = ok and bool(passed)
        compared[name] = [0 if passed else 1, 0]
    return {"correct": ok, "compared": compared}


def print_compared(verdict: dict) -> None:
    """The run's last lines on standard error."""
    for name, (value, limit) in verdict["compared"].items():
        print(f"[correct] {name} = {value} (limit {limit})",
              file=sys.stderr)
    print(f"[correct] correct = {verdict['correct']}", file=sys.stderr,
          flush=True)


def percentile(values, q: float) -> float:
    """q in [0, 100], linear interpolation (numpy's default); of no
    values, not a number."""
    if not len(values):
        return float("nan")
    return float(np.percentile(np.asarray(values, np.float64), q))
