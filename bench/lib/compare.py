"""The comparisons that decide ``correct``: each reduces what the timed path
produced, and what the reference computed, to one number."""
from __future__ import annotations

import numpy as np


def loss_gap(program: list[float], reference: list[float]) -> float:
    """Largest relative gap of a step's loss."""
    if len(program) != len(reference):
        return float("inf")
    return max(abs(p - r) / abs(r) for p, r in zip(program, reference))


def leaf_gap(program: dict, reference: dict,
             skip: frozenset = frozenset()) -> float:
    """Worst leaf's gap between the program's norm and the reference's,
    against the larger of that leaf's reference norm and the median
    leaf's."""
    keys = [k for k in reference if k not in skip]
    if set(keys) - set(program):
        return float("inf")
    median = float(np.median([reference[k] for k in keys]))
    return max(abs(program[k] - reference[k]) / max(reference[k], median)
               for k in keys)


def still_leaves(raw_grad: dict, share: float = 1e-3) -> frozenset:
    """Leaves whose reference gradient is nought to rounding: under
    ``share`` of the median leaf's.  Under Adam they move by round-off
    alone, so their change is not compared."""
    median = float(np.median(list(raw_grad.values())))
    return frozenset(k for k, v in raw_grad.items() if v < share * median)
