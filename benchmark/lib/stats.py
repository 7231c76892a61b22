"""Percentiles and spreads, the benchmark's own arithmetic."""

from __future__ import annotations

import statistics


def percentile(samples, q):
    """Linear-interpolated percentile of `samples`, q in [0, 1]; None for
    an empty list (a metric with nothing to read is left out, never 0)."""
    if not samples:
        return None
    ordered = sorted(samples)
    if len(ordered) == 1:
        return float(ordered[0])
    pos = q * (len(ordered) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(ordered) - 1)
    return float(ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo))


def quartile_spread(values):
    """Distance between the first and third quartile as a share of the
    median, by `statistics.quantiles(values, n=4)`: the spread a bound is
    set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
