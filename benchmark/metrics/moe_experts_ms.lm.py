"""Device milliseconds a step under the `lm/moe/experts` scope (the held experts' two grouped products),
all its layers, forward, recompute and backward together."""

from benchmark.lib import scope_times


def read(observed):
    return scope_times.under(observed, "lm/moe/experts")
