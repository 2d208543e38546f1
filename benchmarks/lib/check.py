"""The comparison that decides `correct`: the numbers, each beside its
limit. Limits live in the cell's traffic file under `limits`, with the
readings they were set from in PERF.md; a number without a limit there
is an error, never a pass.
"""
from __future__ import annotations

import statistics

#: leaves whose reference gradient is under this share of the median
#: leaf's are nought to rounding; under Adam they move by round-off
#: alone, so their change is not compared (rule on the reference's
#: gradient, never on a leaf's name)
IDLE_GRADIENT = 1e-3


def leaf_gaps(prog, ref, skip=()):
    """`{leaf: |prog - ref|}` measured against the reference's norm of
    that leaf or of the median leaf, whichever is larger (some
    gradients are all but zero)."""
    floor = statistics.median(ref.values())
    return {name: abs(prog[name] - r) / max(r, floor, 1e-30)
            for name, r in ref.items() if name not in skip}


def worst_leaf_gap(prog, ref, skip=()):
    """-> (largest gap, its leaf)."""
    gaps = leaf_gaps(prog, ref, skip)
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def column_gap(prog_cols, ref_cols):
    """Worst leaf's distance between the program's and the reference's
    vectors of column norms, against the reference's norm of that leaf
    or of the median leaf. -> (gap, leaf)."""
    import numpy as np

    size = {n: float(np.linalg.norm(c)) for n, c in ref_cols.items()}
    floor = statistics.median(size.values())
    gaps = {n: float(np.linalg.norm(prog_cols[n] - c))
            / max(size[n], floor, 1e-30) for n, c in ref_cols.items()}
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def idle_leaves(ref_grad_norm):
    floor = IDLE_GRADIENT * statistics.median(ref_grad_norm.values())
    return {n for n, g in ref_grad_norm.items() if g < floor}


def train_numbers(prog, ref):
    """`prog` and `ref` are `{"loss": [...], "grad_norm": {...},
    "change_norm": {...}}`; -> `{check name: value}`, `{name: leaf}`."""
    out, where = {}, {}
    for k, (a, b) in enumerate(zip(prog["loss"], ref["loss"]), 1):
        out[f"loss{k}_gap"] = abs(a - b) / abs(b)
    out["grad_norm_gap"], where["grad_norm_gap"] = worst_leaf_gap(
        prog["grad_norm"], ref["grad_norm"])
    out["grad_colnorm_gap"], where["grad_colnorm_gap"] = column_gap(
        prog["grad_cols"], ref["grad_cols"])
    idle = idle_leaves(ref["grad_norm"])
    out["change_norm_gap"], where["change_norm_gap"] = worst_leaf_gap(
        prog["change_norm"], ref["change_norm"], skip=idle)
    where["idle_leaves"] = len(idle)
    return out, where


def judge(numbers, limits):
    """-> (`correct`, `{name: {"value": v, "limit": l}}`). The cell's
    traffic file lists the numbers that are compared, each with its
    limit; a listed number the run did not produce is an error. A
    number passes when it is finite and at most its limit; an exact
    comparison has the limit 0. (A number read but not listed is not
    compared: PERF.md says which and why.)"""
    checks, ok = {}, True
    for name, limit in limits.items():
        if name not in numbers:
            raise KeyError(f"the cell's traffic file sets a limit for "
                           f"{name!r}, which the run did not produce")
        value = numbers[name]
        checks[name] = {"value": value, "limit": limit}
        if not (value == value and value <= limit):     # NaN fails
            ok = False
    return ok, checks
