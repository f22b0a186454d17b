"""Percentile, per-request and window arithmetic on host-clock records.

Times are seconds on one ``time.perf_counter`` clock. A request that
failed or never finished is ``math.inf`` in a latency list, so it counts
as missing every limit and lands in the tail.
"""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default), with ``inf`` entries sorting last.
    An empty list has no percentile."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    frac = pos - lo
    if frac == 0.0 or xs[lo] == xs[hi]:
        return float(xs[lo])
    if math.isinf(xs[hi]):
        return math.inf
    return float(xs[lo] + (xs[hi] - xs[lo]) * frac)


def tpot(first_s: float, last_s: float, n_tokens: int) -> float | None:
    """Time per output token after the first: (last - first) / (n - 1).
    None for a request with fewer than two tokens."""
    if n_tokens < 2:
        return None
    return (last_s - first_s) / (n_tokens - 1)


def due_in(records, start: float, end: float):
    """The records whose due time lies in [start, end)."""
    return [r for r in records if start <= r.due < end]


def rate(count: float, start: float, end: float) -> float:
    """Events per second over [start, end]."""
    if end <= start:
        raise ValueError(f"empty window [{start}, {end}]")
    return count / (end - start)
