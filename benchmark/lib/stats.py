"""Arithmetic the benchmark's numbers rest on: percentiles (with +inf for
failures), quartile spread, histogram-delta reductions. Nothing here reads
the program."""

from __future__ import annotations

import math
import statistics

# JSON has no infinity: a percentile that lands on a failed request prints
# as this many milliseconds (an hour), far outside any bound
INF_MS = 3.6e6


def percentile(values, q: float) -> float:
    """The q-quantile by the index rule ``sorted[min(n-1, int(n*q))]``
    (copied from bench.py:457 ``_pctl``); ``math.inf`` entries sort last, so
    a failed request pushes the tail out instead of vanishing. NaN for no
    samples."""
    vals = sorted(values)
    if not vals:
        return math.nan
    return vals[min(len(vals) - 1, int(len(vals) * q))]


def finite_ms(seconds: float) -> float:
    """Seconds -> milliseconds for the result line; +inf -> INF_MS."""
    return INF_MS if math.isinf(seconds) else seconds * 1e3


def spread(values) -> float:
    """Distance between first and third quartile as a share of the median,
    with ``statistics.quantiles(values, n=4)`` — the rule the bounds follow."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def hist_delta(before, after) -> dict:
    """Delta of two of the program's ``HistSnapshot``s, taken apart into
    plain numbers: bucket upper edges, per-bucket counts, count and sum."""
    if tuple(before.bounds) != tuple(after.bounds):
        raise ValueError("histogram bucket ladders differ")
    return {
        "bounds": tuple(after.bounds),
        "counts": tuple(a - b for a, b in zip(after.counts, before.counts)),
        "count": after.count - before.count,
        "total": after.total - before.total,
    }


def hist_mean(h: dict) -> float | None:
    return h["total"] / h["count"] if h["count"] > 0 else None


def hist_percentile(h: dict, q: float) -> float | None:
    """Quantile of a bucketed delta by linear interpolation inside the
    containing bucket. The ladder is geometric (x1.25 a bucket), so the
    estimate is good to about a tenth of its value; the mean is exact."""
    n = h["count"]
    if n <= 0:
        return None
    rank = min(n - 1, int(n * q))
    cum = 0
    bounds = h["bounds"]
    for i, c in enumerate(h["counts"]):
        if c <= 0:
            continue
        if cum + c > rank:
            lo = 0.0 if i == 0 else bounds[i - 1]
            hi = bounds[i] if i < len(bounds) else bounds[-1]
            return lo + (hi - lo) * (rank - cum + 1) / c
        cum += c
    return bounds[-1]
