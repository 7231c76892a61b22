"""Device milliseconds a step under the `lm/mamba2/ssd_scan` scope (the chunked state-space scan of the Mamba-2 mixers),
all its layers, forward, recompute and backward together."""

from benchmark.lib import scope_times


def read(observed):
    return scope_times.under(observed, "lm/mamba2/ssd_scan")
