"""The arithmetic from samples to metrics.  No jax, no numpy: the parent
process of a run uses it."""

from __future__ import annotations

import math
from typing import List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (0-100) by linear interpolation between the
    two closest ranks (numpy's default); None of no samples."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    rank = (len(xs) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (rank - lo))


def tpot_s(first_s: float, last_s: float, out_tokens: int
           ) -> Optional[float]:
    """Time per output token as a reader of the stream sees it: from the
    first item received to the last, over the tokens that came after the
    first.  The stream flushes several tokens an item, so the gap between
    items is not a token's time; the whole span over the whole count is.
    None of a one-token answer."""
    if out_tokens < 2:
        return None
    return (last_s - first_s) / (out_tokens - 1)


def mean(values: Sequence[float]) -> Optional[float]:
    xs: List[float] = list(values)
    return sum(xs) / len(xs) if xs else None
