"""Median of the program's `prefetch_host` span: the prefetcher's
producer thread waiting for the loader's next batch."""

from benchmark.lib import program_spans


def read(observed):
    return program_spans.median_ms("prefetch_host")
