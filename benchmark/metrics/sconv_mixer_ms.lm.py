"""Device milliseconds a step under every scope that starts with
`lm/attn/sconv_`: what a gated short convolution mixer does but its
output product (`lm/attn/out`, which the attention layer shares): the
product into the two gates and the convolution's input, the gates and the
taps; all its layers, forward, recompute and backward together."""

from benchmark.lib import scope_times


def read(observed):
    return scope_times.under(observed, "lm/attn/sconv_")
