"""Median of the program's `data_wait` span: the loop thread blocked in
`next(feed)`, and nothing else, per iteration."""

from benchmark.lib import program_spans


def read(observed):
    return program_spans.median_ms("data_wait")
