"""Roofline share of `lm/mamba2/ssd_scan`: the reference's `scan_work` (operations
and bytes, every layer of the kind, forward and backward) against the
device time under the scope (`benchmark/lib/roofline.py`)."""

from benchmark.lib import roofline


def read(observed):
    return roofline.share(observed, "ssd_scan", "lm/mamba2/ssd_scan")
