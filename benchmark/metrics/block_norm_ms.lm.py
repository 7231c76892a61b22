"""Device milliseconds a step under `lm/block/...` (each block's norm
before its mixer, and its residual sum) and `lm/final_norm` (the norms
before the head's and the multi-token-prediction module's losses);
forward, recompute and backward together."""

from benchmark.lib import step_scopes


def read(observed):
    return step_scopes.under(observed, ("lm/block/", "lm/final_norm"))
