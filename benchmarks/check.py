"""The comparison that decides ``correct`` for a training cell.

The program's first steps (the same compiled step and state that the
window then drives) against the plain reference following them from
the same weights and batches.  Numbers compared, each a relative gap
with a limit of its own in ``benchmarks/limits/<workload>.json``:

- ``loss<k>_gap``: |program - reference| / |reference| of step k's loss;
- ``grad1_gap``: the first gradient as the optimizer got it, read from
  the optimizer's state after one step, worst leaf;
- ``change<n>_gap``: the parameters' change after the n steps, worst
  leaf, over the leaves whose reference gradient is not nought.

A leaf's gap is the distance between the program's NORM and the
reference's (not the norm of their difference), measured against the
reference's norm of that leaf or of the median leaf, whichever is
larger: some gradients are all but zero.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, Iterable, Tuple

# leaves whose reference gradient is under this share of the median
# leaf's move, under an adaptive optimizer, by round-off alone: they
# are left out of the change (by this rule, never by name)
NEGLIGIBLE_GRADIENT = 1e-3


def relative_gap(program: float, reference: float) -> float:
    if not (math.isfinite(program) and math.isfinite(reference)):
        return math.inf
    return abs(program - reference) / max(abs(reference), 1e-30)


def worst_leaf_gap(program: Dict[str, float], reference: Dict[str, float],
                   skip: Iterable[str] = ()) -> Tuple[float, str]:
    """(gap, leaf) of the leaf whose norms lie farthest apart."""
    if set(program) != set(reference):
        raise ValueError(
            "program and reference disagree on the leaves: "
            f"{sorted(set(program) ^ set(reference))[:6]}")
    skip = set(skip)
    floor = statistics.median(reference.values())
    worst, where = 0.0, ""
    for leaf, ref in reference.items():
        if leaf in skip:
            continue
        got = program[leaf]
        gap = (abs(got - ref) / max(ref, floor, 1e-30)
               if math.isfinite(got) and math.isfinite(ref) else math.inf)
        if gap >= worst:
            worst, where = gap, leaf
    return worst, where


def median_leaf_gap(program: Dict[str, float], reference: Dict[str, float],
                    skip: Iterable[str] = ()) -> float:
    """The median over leaves of the same gap: steady from seed to
    seed where the worst leaf is one small leaf's noise."""
    skip = set(skip)
    floor = statistics.median(reference.values())
    return statistics.median(
        abs(program[leaf] - ref) / max(ref, floor, 1e-30)
        for leaf, ref in reference.items() if leaf not in skip)


def negligible_leaves(reference_grad: Dict[str, float]) -> set:
    cut = NEGLIGIBLE_GRADIENT * statistics.median(reference_grad.values())
    return {leaf for leaf, n in reference_grad.items() if n < cut}


def compare(program: dict, reference: dict) -> Dict[str, dict]:
    """``program``/``reference``: ``{"losses": [...], "grad1": {leaf:
    norm}, "change": {leaf: norm}}``.  Returns name -> {"value": gap,
    "leaf": worst leaf or None}; the reference may follow fewer steps
    than the program ran, the comparison is over the reference's."""
    out = {}
    n = len(reference["losses"])
    for k in range(n):
        out[f"loss{k + 1}_gap"] = {
            "value": relative_gap(program["losses"][k],
                                  reference["losses"][k]), "leaf": None}
    gap, leaf = worst_leaf_gap(program["grad1"], reference["grad1"])
    out["grad1_gap"] = {"value": gap, "leaf": leaf}
    out["grad1_median_gap"] = {"value": median_leaf_gap(
        program["grad1"], reference["grad1"]), "leaf": None}
    skip = negligible_leaves(reference["grad1"])
    gap, leaf = worst_leaf_gap(program["change"], reference["change"], skip)
    out[f"change{n}_gap"] = {"value": gap, "leaf": leaf}
    out[f"change{n}_median_gap"] = {"value": median_leaf_gap(
        program["change"], reference["change"], skip), "leaf": None}
    return out


def decide(numbers: Dict[str, dict], limits: Dict[str, float]):
    """(correct, rows): every number that has a limit is held to it; a
    limit without its number is a failure (the check did not run)."""
    rows, correct = [], True
    for name, limit in limits.items():
        got = numbers.get(name)
        value = got["value"] if got else math.nan
        ok = got is not None and value <= limit
        correct = correct and ok
        rows.append({"name": name, "value": value, "limit": limit,
                     "leaf": got["leaf"] if got else None, "ok": ok})
    return correct, rows
