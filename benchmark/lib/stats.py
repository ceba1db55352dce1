"""Percentile arithmetic of the benchmark (no dependency but the list)."""

from __future__ import annotations

import math
from typing import Sequence

# A percentile is reported only with this many samples beyond it
# (choosing-metrics guide, section 1).
SAMPLES_BEYOND = 10


def percentile(samples: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between the
    two nearest order statistics: ``percentile(x, 50)`` is the median."""
    if not samples:
        raise ValueError("no samples")
    if not 0 <= q <= 100:
        raise ValueError(f"percentile {q} outside 0..100")
    xs = sorted(samples)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(samples: Sequence[float]) -> float:
    return percentile(samples, 50)


def samples_beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie strictly beyond the ``q``-th
    percentile's upper order statistic."""
    if n <= 0:
        return 0
    return n - 1 - math.ceil((n - 1) * q / 100.0)


def enough_beyond(n: int, q: float) -> bool:
    return samples_beyond(n, q) >= SAMPLES_BEYOND


def merged_intervals(intervals, lo: float = -math.inf, hi: float = math.inf) -> list:
    """The union of ``(start, end)`` intervals, clipped to ``[lo, hi]``, as
    a sorted list of disjoint ``(start, end)``."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def union_seconds(intervals, lo: float = -math.inf, hi: float = math.inf) -> float:
    """Total length of the union of ``(start, end)`` intervals, clipped to
    ``[lo, hi]``."""
    return sum(e - s for s, e in merged_intervals(intervals, lo, hi))
