"""Wall-clock arithmetic of the end-to-end metrics. Every tail is taken
over all samples and every rate over the whole window: nothing here
takes a median of chunks or of steps."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) of all ``values``, by linear
    interpolation between the closest ranks (numpy's default rule)."""
    if not values:
        raise ValueError("percentile of no samples")
    xs = sorted(values)
    rank = (len(xs) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def rate(count: float, seconds: float) -> float:
    """``count`` over the whole window of ``seconds``."""
    if seconds <= 0:
        raise ValueError(f"window of {seconds} s")
    return count / seconds

