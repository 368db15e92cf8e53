"""Measures that compare a program's readings with the reference's."""

from __future__ import annotations

import math
from typing import Dict

import numpy as np

# A leaf whose reference gradient is under this share of the median leaf's
# is nought to rounding (a key bias under softmax is one): under Adam such a
# leaf moves by round-off alone, so it is left out of the comparison.
NOUGHT = 1e-3


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             ref_grads: Dict[str, float]) -> float:
    """The worst leaf's |prog norm - ref norm|, over the larger of that
    leaf's reference norm and the median leaf's.  Leaves whose reference
    gradient is nought to rounding are left out; a leaf the program lacks
    reads infinite."""
    med_g = float(np.median(list(ref_grads.values())))
    kept = [k for k in ref if ref_grads.get(k, 0.0) >= NOUGHT * med_g]
    if not kept:
        return math.inf
    med = float(np.median([ref[k] for k in kept]))
    worst = 0.0
    for k in kept:
        if k not in prog or not math.isfinite(prog[k]):
            return math.inf
        worst = max(worst, abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30))
    return worst
