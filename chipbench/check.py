"""The comparison that decides `correct` for a training cell.

Both sides hand over the same structure, from their first steps on the
same weights and rows:

    {"loss":   [loss of step 1, 2, ...],
     "grad1":  {leaf: norm of the first gradient as the optimizer got it},
     "dparam": {leaf: norm of the parameters' change after the steps},
     "stats":  {leaf: norm of the change of a running statistic},  (optional)
     "rank":   {leaf: number of dimensions}}                  (reference only)

Losses are compared by their relative gap. The per-leaf norms are
compared by the worst leaf: the gap between the two norms (not the norm
of a difference), against the reference's norm of that leaf or of the
median leaf, whichever is larger, since some gradients are all but zero.
A leaf whose reference gradient is under a thousandth of the median
leaf's moves under Adam by round-off alone and is left out of `dparam`.
Beside the worst leaf, the median leaf's gap and the worst gap over
matrices and kernels (leaves of rank 2 or more) are worked out: a cell
compares those where `PERF.md` shows that the worst leaf overall is
noise by the nature of the leaf.
A number is compared only where the cell's limits file gives a limit.
"""

from __future__ import annotations

import math
import statistics

#: reference gradient under this share of the median leaf's: the leaf's
#: change is rounding, and is not compared
DEAD_GRADIENT = 1e-3


def leaf_gaps(program: dict, reference: dict, leaves=None) -> dict:
    median = statistics.median(reference.values())
    return {leaf: abs(program[leaf] - reference[leaf])
            / max(reference[leaf], median, 1e-30)
            for leaf in (leaves if leaves is not None else reference)}


def worst_leaf_gap(program: dict, reference: dict, leaves=None):
    """(gap, leaf) of the worst leaf."""
    worst, where = 0.0, None
    for leaf, gap in leaf_gaps(program, reference, leaves).items():
        if not gap <= worst:        # also catches NaN
            worst, where = gap, leaf
    return worst, where


def median_leaf_gap(program: dict, reference: dict, leaves=None):
    return statistics.median(
        leaf_gaps(program, reference, leaves).values()), None


def numbers(program: dict, reference: dict) -> dict:
    """Every number of the comparison, with the leaf it was read on."""
    out = {}
    for i, (a, b) in enumerate(zip(program["loss"], reference["loss"]), 1):
        out[f"loss_{i}"] = (abs(a - b) / abs(b), None)
    g_med = statistics.median(reference["grad1"].values())
    alive = [k for k, g in reference["grad1"].items()
             if g >= DEAD_GRADIENT * g_med]
    # matrices and kernels: leaves of rank 2 or more
    wide = [k for k, r in reference["rank"].items() if r >= 2]
    for key, leaves in (("grad1", None), ("dparam", alive)):
        p, r = program[key], reference[key]
        out[f"{key}_worst_leaf_gap"] = worst_leaf_gap(p, r, leaves)
        out[f"{key}_median_leaf_gap"] = median_leaf_gap(p, r, leaves)
        out[f"{key}_worst_matrix_gap"] = worst_leaf_gap(
            p, r, [k for k in wide if leaves is None or k in leaves])
    if "stats" in reference:
        out["stats_worst_leaf_gap"] = worst_leaf_gap(program["stats"],
                                                     reference["stats"])
    return out


def compare(program: dict, reference: dict, limits: dict) -> dict:
    """{name: {"value", "limit", "ok", "leaf"}} for each number that
    has a limit."""
    out = {}
    for name, (value, leaf) in numbers(program, reference).items():
        if name not in limits:
            continue
        ok = math.isfinite(value) and value <= limits[name]
        out[name] = {"value": value, "limit": limits[name], "ok": ok,
                     "leaf": leaf}
    return out
