"""Percentile and failure arithmetic, kept apart so that it can be tested."""

from __future__ import annotations

import math


def percentile(values: list, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    order statistics, as numpy's default."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("no samples")
    rank = (len(ordered) - 1) * q / 100.0
    low = math.floor(rank)
    high = min(low + 1, len(ordered) - 1)
    return ordered[low] + (ordered[high] - ordered[low]) * (rank - low)


def with_misses(latencies: list, miss: float) -> list:
    """A request that failed (None) stays in the denominator: it is given
    the time at which the run gave up on it, which no served request
    can exceed, so it misses every limit and weighs on every tail."""
    return [miss if value is None else value for value in latencies]


def share_within(latencies: list, limit: float) -> float:
    """Share of requests (failed ones counted, as misses) at or under
    `limit`."""
    if not latencies:
        raise ValueError("no samples")
    return sum(value is not None and value <= limit
               for value in latencies) / len(latencies)


def halves_ratio(due: list, latencies: list, seconds: float) -> float:
    """Median latency of the window's second half over that of its first:
    the sign of a backlog that grows (see the knee in PERF.md)."""
    first = [l for d, l in zip(due, latencies) if d < seconds / 2]
    second = [l for d, l in zip(due, latencies) if d >= seconds / 2]
    return percentile(second, 50) / percentile(first, 50)
