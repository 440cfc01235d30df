"""Numbers compared with the plain reference, each by its worst case."""

from __future__ import annotations

import statistics
from typing import Dict, Optional, Tuple

#: a leaf whose reference gradient is under this share of the median
#: leaf's is nought to rounding (a key's bias under softmax): under Adam
#: it moves by round-off alone, so its change is not compared
NEGLIGIBLE_GRAD = 1e-3


def rel_gap(got: float, want: float) -> float:
    return abs(got - want) / max(abs(want), 1e-30)


def worst_leaf_gap(got: Dict[str, float], want: Dict[str, float],
                   ref_grad: Optional[Dict[str, float]] = None
                   ) -> Tuple[float, str]:
    """Largest ``|got - want|`` over the leaves, each against the larger of
    the reference's norm of that leaf and of the median leaf."""
    names = sorted(want)
    if ref_grad is not None:
        med_g = statistics.median(ref_grad[n] for n in names)
        names = [n for n in names if ref_grad[n] >= NEGLIGIBLE_GRAD * med_g]
    med = statistics.median(want[n] for n in names)
    worst, leaf = 0.0, ""
    for n in names:
        if n not in got:
            return float("inf"), n
        gap = abs(got[n] - want[n]) / max(want[n], med, 1e-30)
        if gap > worst:
            worst, leaf = gap, n
    return worst, leaf
