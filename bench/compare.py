"""The numbers that decide ``correct``: each is a gap between what the timed
path produced and the plain reference, read against a limit.

Training readings (``train_gaps``) follow the program's first steps:

* ``loss_gap``   — the widest relative gap of a step's meta-objective;
* ``gnorm_gap``  — the widest relative gap of a step's pre-clip norm of
  the Eq.-7 gradient;
* ``grad_gap``   — the first clipped gradient as the state keeps it (the
  stored change after one step), by the worst leaf;
* ``change_gap`` — the stored change after all the followed steps, by the
  worst leaf;
* ``major_grad_gap`` and ``major_change_gap`` — the same two, by the worst
  of the leaves that carry the update: those whose share of the
  reference's first clipped gradient norm is at least ``MAJOR`` (``major``).

A leaf's gap is |‖program‖ − ‖reference‖| over the larger of the
reference's norm of that leaf and of the median leaf.  Leaves whose
reference gradient is under a thousandth of the median leaf's move by
round-off alone and are left out (``counted``).
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, Iterable, List


def worst(gaps: Iterable[float]) -> float:
    """The largest gap; NaN if any gap is NaN (Python's ``max`` can skip
    a NaN, and a reading that is not a number fails its limit)."""
    gaps = list(gaps)
    if not gaps or any(math.isnan(g) for g in gaps):
        return float("nan")
    return max(gaps)


# the least share of the reference's first clipped gradient norm that a
# leaf of the update carries
MAJOR = 0.01


def counted(grad_leaf: Dict[str, float]) -> List[str]:
    med = statistics.median(grad_leaf.values())
    return sorted(p for p, v in grad_leaf.items() if v >= 1e-3 * med)


def major(grad_leaf: Dict[str, float]) -> List[str]:
    total = math.sqrt(sum(v * v for v in grad_leaf.values()))
    return sorted(p for p, v in grad_leaf.items() if v >= MAJOR * total)


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              leaves: List[str]) -> Dict[str, float]:
    """Each counted leaf's gap of norms."""
    med = statistics.median(ref[p] for p in leaves)
    return {p: abs(prog[p] - ref[p]) / max(ref[p], med, 1e-30)
            for p in leaves}


def train_gaps(prog: dict, ref: dict) -> Dict[str, float]:
    """Gaps between two sets of training readings (program vs reference,
    or control vs reference)."""
    leaves = counted(ref["grad_leaf"])
    first = leaf_gaps(prog["change_first"], ref["change_first"], leaves)
    last = leaf_gaps(prog["change_last"], ref["change_last"], leaves)
    big = [p for p in major(ref["grad_leaf"]) if p in first]
    n = min(len(prog["loss"]), len(ref["loss"]))
    return {
        "loss_gap": worst(abs(prog["loss"][i] - ref["loss"][i])
                          / abs(ref["loss"][i]) for i in range(n)),
        "gnorm_gap": worst(abs(prog["grad_norm"][i] - ref["grad_norm"][i])
                           / ref["grad_norm"][i] for i in range(n)),
        "grad_gap": worst(first.values()),
        "change_gap": worst(last.values()),
        "major_grad_gap": worst(first[p] for p in big),
        "major_change_gap": worst(last[p] for p in big),
    }
