"""Device milliseconds a step under `lm/moe/dispatch` and
`lm/moe/combine`: the sort of the assignments, the gather into the held
experts' buffer and the weighted scatter back, all expert layers, forward,
recompute and backward together."""

from benchmark.lib import scope_times


def read(observed):
    there = scope_times.under(observed, "lm/moe/dispatch")
    back = scope_times.under(observed, "lm/moe/combine")
    if there is None and back is None:
        return None
    return (there or 0.0) + (back or 0.0)
