"""Roofline share of `lm/attn/kda_scan`: the reference's `kda_scan_work`
(operations and bytes of the chunked form at the configuration's chunk,
every layer of the kind, forward and backward) against the device time
under the scope (`benchmark/lib/roofline.py`)."""

from benchmark.lib import roofline


def read(observed):
    return roofline.share(observed, "kda_scan", "lm/attn/kda_scan")
