"""Median, over the window's requests, of the time its chunk took from
staging to the response: the engine's `bucket/pad`, `h2d_transfer`,
`execute` and `d2h/slice` spans together (their split is unsound singly:
`execute` closes when the asynchronous call returns)."""

from benchmark.lib.stats import percentile

STAGES = ("bucket/pad", "h2d_transfer", "execute", "d2h/slice")


def read(observed):
    totals = [sum(s["dur_ms"] for s in t["spans"] if s["name"] in STAGES)
              for t in observed.get("request_traces") or []]
    return percentile(totals, 0.50)
