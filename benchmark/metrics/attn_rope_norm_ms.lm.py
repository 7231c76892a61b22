"""Device milliseconds a step under `lm/attn/qk_norm` and `lm/attn/rope`:
what a grouped-query attention layer does to its queries and keys between
their products and the scores (each head RMS-normed, then the rotary
turn), every layer of the kind, forward, recompute and backward
together."""

from benchmark.lib import scope_times

SCOPES = ("lm/attn/qk_norm", "lm/attn/rope")


def read(observed):
    found = [ms for ms in (scope_times.under(observed, scope)
                           for scope in SCOPES) if ms is not None]
    return sum(found) if found else None
