"""Device milliseconds a step under the `lm/head_loss` scope (the final norm, the head's logits by token chunks and the cross-entropy),
all its layers, forward, recompute and backward together."""

from benchmark.lib import scope_times


def read(observed):
    return scope_times.under(observed, "lm/head_loss")
