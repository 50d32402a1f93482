"""Window arithmetic: the end-to-end numbers from what a window recorded.

Kept apart from the drivers so that the tests check the arithmetic alone.
"""
from __future__ import annotations

import math


def per_item(window_s: float, count: int) -> float:
    """The whole window's time over all the items it finished: a rate
    taken over all the work and all the time, never a median of pieces."""
    if count < 1:
        raise ValueError("a window that finished nothing has no time per item")
    return window_s / count


def latencies(requests) -> list[float]:
    """Client-side latency of each request, from the time it was due to
    the time its response came: ``requests`` holds ``(due, done)`` pairs,
    ``done`` None for a request that failed, was refused or never came,
    which counts as infinite."""
    return [math.inf if done is None else done - due
            for due, done in requests]


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value that at least ``q``
    percent of ``values`` do not exceed.  Infinite values sort last, so
    failures count against the tail and never break the arithmetic."""
    vals = sorted(values)
    if not vals:
        raise ValueError("no values")
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[rank - 1]


def arrival_times(n: int, seconds: float, rng) -> list[float]:
    """``n`` open-loop arrivals in [0, seconds): the gaps are the n
    quantiles of one exponential distribution, the same set for every
    seed, in an order drawn from ``rng``, scaled so that the mean rate is
    n / seconds and the last arrival is due before the window closes."""
    if n < 1:
        return []
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    order = rng.permutation(n)
    gaps = [gaps[i] for i in order]
    scale = seconds * n / (n + 1) / sum(gaps)
    out, t = [], 0.0
    for gap in gaps:
        t += gap * scale
        out.append(t)
    return out


def apportion(n: int, weights) -> list[int]:
    """Split ``n`` into whole counts proportional to ``weights`` by largest
    remainder, so every seed sends the same mix."""
    total = float(sum(weights))
    raw = [n * w / total for w in weights]
    counts = [math.floor(r) for r in raw]
    left = n - sum(counts)
    by_rem = sorted(range(len(raw)), key=lambda i: counts[i] - raw[i])
    for i in by_rem[:left]:
        counts[i] += 1
    return counts
