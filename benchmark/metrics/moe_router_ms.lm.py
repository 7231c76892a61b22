"""Device milliseconds a step under the `lm/moe/router` scope (every expert
layer's router: the logits' product, the top-k and the chosen weights),
forward, the block's recompute and backward together. In a model with the
early router the scope's operations read the attention layer's normed input;
the name is the same. None where the step has no such scope."""

from benchmark.lib import scope_times


def read(observed):
    return scope_times.under(observed, "lm/moe/router")
