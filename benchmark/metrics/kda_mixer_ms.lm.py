"""Device milliseconds a step under every scope that starts with
`lm/attn/kda_`: what a Kimi Delta Attention mixer does but its output
product (`lm/attn/out`, which the attention layer shares): projections,
convolutions and l2 norms, the decays and the delta rule, the gated head
norm; all its layers, forward, recompute and backward together."""

from benchmark.lib import scope_times


def read(observed):
    return scope_times.under(observed, "lm/attn/kda_")
