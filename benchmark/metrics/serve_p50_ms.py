"""Median, over every request offered in the window, of the time from its
scheduled arrival to its image in host memory (ms, harness clock)."""

from benchmark.lib.stats import percentile


def read(observed):
    return percentile(observed.get("latencies_ms") or [], 0.50)
