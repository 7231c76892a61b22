"""Median of the engine's `queue_wait` span over the window's requests:
admission to the start of the request's own chunk."""

from benchmark.lib.stats import percentile


def read(observed):
    waits = [s["dur_ms"] for t in observed.get("request_traces") or []
             for s in t["spans"] if s["name"] == "queue_wait"]
    return percentile(waits, 0.50)
