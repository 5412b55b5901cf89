"""The arithmetic of the yardstick: percentiles, rates, spreads.

A failed or refused request misses every limit: it enters each latency list
as ``inf``, so it pushes the percentiles up instead of vanishing.
"""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float | None:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics; ``inf`` entries sort last. None of an empty list."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if math.isinf(xs[hi]):
        return math.inf if hi != lo or math.isinf(xs[lo]) else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def tpot_ms(first_s: float, last_s: float, n_tokens: int) -> float | None:
    """Time per output token of one stream: (last - first) / (n - 1)."""
    if n_tokens < 2:
        return None
    return (last_s - first_s) * 1000.0 / (n_tokens - 1)


def rate(count: float, seconds: float) -> float:
    return count / seconds


def spread(values) -> float:
    """Run-to-run spread as the builder's contract defines it: the distance
    between the first and third quartile, by ``statistics.quantiles(n=4)``,
    as a share of the median."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def histogram_quantile(buckets: list[tuple[float, float]], q: float) -> float | None:
    """Quantile ``q`` (0..1) of a Prometheus histogram given as cumulative
    ``(upper_bound, count)`` pairs (the delta of two scrapes), interpolated
    linearly inside the bucket it falls in."""
    buckets = sorted(buckets)
    total = buckets[-1][1] if buckets else 0
    if total <= 0:
        return None
    want = q * total
    prev_le, prev_n = 0.0, 0.0
    for le, n in buckets:
        if n >= want:
            if math.isinf(le):
                return prev_le
            share = (want - prev_n) / (n - prev_n) if n > prev_n else 1.0
            return prev_le + (le - prev_le) * share
        prev_le, prev_n = le, n
    return prev_le
