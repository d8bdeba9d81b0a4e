"""Percentiles and rates over a whole window."""

from __future__ import annotations

import math


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile (q in (0, 100]) over every value."""
    if not values:
        raise ValueError("percentile of no values")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def tokens_in(times: list[float], t0: float, t1: float) -> int:
    return sum(1 for t in times if t0 <= t < t1)
