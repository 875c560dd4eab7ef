"""Arithmetic from stamps to the numbers reported. Pure Python and numpy:
no JAX, nothing of the program."""

from __future__ import annotations

import math


def percentile(values, p: float) -> float:
    """Nearest-rank percentile (p in 0..100) of a non-empty sequence: the
    smallest value with at least p% of the sample at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(p / 100.0 * len(xs)))
    return float(xs[min(rank, len(xs)) - 1])


def token_gaps(stamps, lo: float, hi: float) -> list[float]:
    """Gaps between consecutive token stamps of one request, both stamps
    inside the window [lo, hi)."""
    return [b - a for a, b in zip(stamps, stamps[1:]) if lo <= a and b < hi]


def tokens_in(stamps, lo: float, hi: float) -> int:
    return sum(1 for t in stamps if lo <= t < hi)
