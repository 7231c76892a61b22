"""Median of the program's `prefetch_transfer` span: placing one batch,
to its being on the device, in the prefetcher's producer thread."""

from benchmark.lib import program_spans


def read(observed):
    return program_spans.median_ms("prefetch_transfer")
