"""Percentiles, counts and spreads: the benchmark's own arithmetic."""

from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence

PERCENTILES = (50.0, 90.0, 95.0, 99.0, 99.9)
BEYOND = 10     # samples that must lie beyond a reported percentile


def percentile(values: Sequence[float], p: float) -> float:
    """The p-th percentile (0..100) by linear interpolation between the
    order statistics at rank p/100 * (n-1); raises on no samples."""
    if not values:
        raise ValueError("percentile of no samples")
    xs = sorted(values)
    rank = (p / 100.0) * (len(xs) - 1)
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def samples_beyond(n: int, p: float) -> float:
    """How many of n samples lie beyond the p-th percentile (rounded at
    the ninth decimal, so that 100 samples have 10 beyond the 90th)."""
    return round(n * (100.0 - p) / 100.0, 9)


def highest_supported_percentile(n: int) -> Optional[float]:
    """The highest of PERCENTILES with at least BEYOND samples past it
    (choosing-metrics guide, section 1); None under 2 * BEYOND samples."""
    ok = [p for p in PERCENTILES if samples_beyond(n, p) >= BEYOND]
    return max(ok) if ok else None


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of `statistics.quantiles(values, n=4)`:
    the bound rule's spread."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
