"""Device milliseconds a step under the `lm/attn/scores` scope (the causal attention scores and their product with the values),
all its layers, forward, recompute and backward together."""

from benchmark.lib import scope_times


def read(observed):
    return scope_times.under(observed, "lm/attn/scores")
