"""Device milliseconds a step under the `lm/attn/window_scores` scope (the
scores of the sliding-window attention layers and their product with the
values), all its layers, forward, recompute and backward together; the
full layers' stand under `lm/attn/scores` (`attn_scores_ms.lm`)."""

from benchmark.lib import scope_times


def read(observed):
    return scope_times.under(observed, "lm/attn/window_scores")
