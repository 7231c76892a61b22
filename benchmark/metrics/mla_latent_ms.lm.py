"""Device milliseconds a step under `lm/attn/q_latent`, `lm/attn/kv_latent`
and `lm/attn/rope`: what latent attention does around its scores (the
projections into and out of the two latents with their norms, the rotary
turn, the per-head keys built from the shared rotary key), every layer of
the kind, forward, recompute and backward together."""

from benchmark.lib import scope_times

SCOPES = ("lm/attn/q_latent", "lm/attn/kv_latent", "lm/attn/rope")


def read(observed):
    found = [ms for ms in (scope_times.under(observed, scope)
                           for scope in SCOPES) if ms is not None]
    return sum(found) if found else None
