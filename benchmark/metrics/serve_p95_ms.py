"""95th percentile of the same set as `serve_p50_ms`."""

from benchmark.lib.stats import percentile


def read(observed):
    return percentile(observed.get("latencies_ms") or [], 0.95)
