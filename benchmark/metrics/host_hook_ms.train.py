"""Median of the program's `prefetch_preprocess` span: the trainer's
host hook on one batch, in the prefetcher's producer thread."""

from benchmark.lib import program_spans


def read(observed):
    return program_spans.median_ms("prefetch_preprocess")
