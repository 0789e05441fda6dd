"""The comparison that decides `correct`: the timed path's first steps
against the plain reference's, number by number, each with its own limit.

Five numbers per cell: the loss of each followed step; the norm of the
first gradient as the optimizer got it; seeded projections of that
gradient (`sketch.py`), over all leaves and over the head's alone; and the
norm of the parameters' change after the last followed step.  The two norms are taken leaf by leaf and judged by
the worst leaf: the gap between the program's norm and the reference's
(not the norm of their difference), over the reference's norm of that leaf
or of the median leaf, whichever is larger, since some gradients are all
but zero.  The root mean square of a leaf's projections' gaps, over the same
norm, estimates that leaf's error as a share of its gradient from a few
draws; the leaves are judged together by the root mean square over them,
which is steady from seed to seed.  The head's leaves (the reference names
them) are judged once more by themselves: their gradient is the forward
pass's result times the loss's derivative, so a forward pass in a lower
precision shows there by itself, where the backward pass through
normalisation layers has not yet amplified every rounding noise alike.
"""

from __future__ import annotations

import math
import statistics


def worst_leaf_gap(program: dict[str, float], reference: dict[str, float]) -> tuple[float, str]:
    if set(program) != set(reference):
        missing = sorted(set(program) ^ set(reference))
        raise ValueError(f"leaves differ between program and reference: {missing[:5]}")
    floor = statistics.median(reference.values())
    worst, where = 0.0, ""
    for leaf, ref in reference.items():
        gap = abs(program[leaf] - ref) / max(ref, floor)
        if not math.isfinite(gap):
            return math.inf, leaf
        if gap >= worst:
            worst, where = gap, leaf
    return worst, where


def sketch_gap(
    program: dict[str, list[float]],
    reference: dict[str, list[float]],
    norms: dict[str, float],
    leaves: list[str] | None = None,
) -> tuple[float, str]:
    """Root mean square, over `leaves` (all, if None) and each leaf's
    projections, of (program's projection - reference's) over the
    reference's norm of the leaf (floored as above), and the leaf where it
    is largest."""
    if set(program) != set(reference):
        missing = sorted(set(program) ^ set(reference))
        raise ValueError(f"leaves differ between program and reference: {missing[:5]}")
    floor = statistics.median(norms.values())
    gaps = {
        k: math.sqrt(
            statistics.fmean((p - r) ** 2 for p, r in zip(program[k], reference[k], strict=True))
        ) / max(norms[k], floor)
        for k in (reference if leaves is None else leaves)
    }
    if not all(math.isfinite(g) for g in gaps.values()):
        return math.inf, next(k for k, g in gaps.items() if not math.isfinite(g))
    return math.sqrt(statistics.fmean(g * g for g in gaps.values())), max(gaps, key=gaps.get)


def compare(program: dict, reference: dict, limits: dict) -> list[dict]:
    """One row per number compared: its value, its limit, whether it
    holds, and for a norm the leaf that was worst."""
    steps = len(reference["loss"])
    loss_gap = max(
        abs(p - r) / abs(r) for p, r in zip(program["loss"][:steps], reference["loss"])
    )
    rows = [{"name": "loss_gap", "value": loss_gap, "leaf": None}]
    gap, leaf = worst_leaf_gap(program["grad_norm"], reference["grad_norm"])
    rows.append({"name": "grad_norm_gap", "value": gap, "leaf": leaf})
    sketched = (program["grad_sketch"], reference["grad_sketch"], reference["grad_norm"])
    gap, leaf = sketch_gap(*sketched)
    rows.append({"name": "grad_sketch_gap", "value": gap, "leaf": leaf})
    gap, leaf = sketch_gap(*sketched, leaves=reference["head_leaves"])
    rows.append({"name": "head_sketch_gap", "value": gap, "leaf": leaf})
    gap, leaf = worst_leaf_gap(program["update_norm"], reference["update_norm"])
    rows.append({"name": "update_norm_gap", "value": gap, "leaf": leaf})
    for row in rows:
        row["limit"] = float(limits[row["name"]])
        row["ok"] = bool(math.isfinite(row["value"]) and row["value"] <= row["limit"])
    return rows
