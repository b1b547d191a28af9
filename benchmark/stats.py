"""Arithmetic over a window's requests: percentiles, rates, spreads.

Every statistic is taken over all the requests of the window — a stalled
or failed request is in the sample, never trimmed from it.
"""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by the nearest-rank rule: the smallest
    value with at least q% of the sample at or below it."""
    if not values:
        raise ValueError("percentile of an empty sample")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def rate_per_s(done_times, t0: float, seconds: float) -> float:
    """Completions inside [t0, t0 + seconds] over the whole of it."""
    if seconds <= 0:
        raise ValueError("a window has a positive length")
    t1 = t0 + seconds
    return sum(1 for t in done_times if t0 <= t <= t1) / seconds


def iqr_share(values) -> float:
    """The spread the bounds are set from: the distance between the first
    and third quartile as a share of the median."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
