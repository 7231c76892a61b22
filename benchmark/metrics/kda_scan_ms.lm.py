"""Device milliseconds a step under the `lm/attn/kda_scan` scope (the
log-decays from the gate and the chunked delta rule of the Kimi Delta
Attention mixers), all its layers, forward, recompute and backward
together."""

from benchmark.lib import scope_times


def read(observed):
    return scope_times.under(observed, "lm/attn/kda_scan")
