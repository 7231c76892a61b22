"""fp32-island registry: one source of truth for the numerics that must
stay in float32 regardless of the compute dtype policy.

PR 9 protected these spots with hand-written trace asserts scattered
through the layers (weight_norm power iteration, instance/layer-norm
statistics, the health-audit accumulators). This module replaces them
with a declared registry:

- ``scope(name)`` wraps the island's compute in a
  ``jax.named_scope("fp32_island[<name>]")`` marker. The marker lands on
  every equation's ``source_info.name_stack`` in the traced jaxpr, which
  is what lets the graph auditor (jaxpr_audit.py) statically reject any
  ``convert_element_type`` to bf16/f16 *inside* the island — the exit
  cast back to the compute dtype belongs OUTSIDE the scope.
- ``guard(name, **values)`` keeps the PR-9 trace-time check: it raises
  at trace time when a value entering the island is not fp32, so the
  bug is caught even when the program never reaches the auditor.

Register islands here (or via ``register``) so the rule set and the
docs enumerate the same list.
"""

import contextlib

import jax
import jax.numpy as jnp

# the literal marker prefix the jaxpr auditor greps for in name stacks
SCOPE_PREFIX = "fp32_island["

_REGISTRY = {}


class IslandViolation(TypeError):
    """A value entered a declared fp32 island with the wrong dtype."""


def register(name, description, where=""):
    """Declare an fp32 island. ``where`` is the home module (or the
    two that share it, each with its part), for docs and reports."""
    _REGISTRY[str(name)] = {"description": str(description),
                            "where": str(where)}
    return str(name)


def registered():
    """name -> {description, where} for every declared island."""
    return {k: dict(v) for k, v in _REGISTRY.items()}


@contextlib.contextmanager
def scope(name):
    """Mark the enclosed (traced) compute as belonging to the fp32
    island ``name``. Down-casts to bf16/f16 inside this scope are graph
    violations; cast back to the compute dtype after leaving it."""
    if name not in _REGISTRY:
        raise KeyError(
            f"fp32 island {name!r} is not registered — declare it with "
            f"analysis.islands.register() so the audit rule set and the "
            f"docs stay in sync")
    with jax.named_scope(f"{SCOPE_PREFIX}{name}]"):
        yield


def guard(name, **values):
    """Trace-time dtype check at an island entry: every named value
    must already be float32 (the caller up-casts explicitly so the
    reader can see where precision changes)."""
    island = _REGISTRY.get(name, {})
    for label, value in values.items():
        dtype = jnp.result_type(value)
        if dtype != jnp.float32:
            raise IslandViolation(
                f"fp32_island[{name}]: {label} entered as {dtype}, "
                f"expected float32"
                + (f" ({island['description']})" if island else ""))


def island_of(name_stack):
    """Island name embedded in a stringified jaxpr name stack, or None.

    ``str(eqn.source_info.name_stack)`` carries named scopes verbatim,
    e.g. ``"loss_fn/fp32_island[norm_stats]/mean"``.
    """
    text = str(name_stack)
    start = text.find(SCOPE_PREFIX)
    if start < 0:
        return None
    start += len(SCOPE_PREFIX)
    end = text.find("]", start)
    return text[start:end] if end >= 0 else None


# ----------------------------------------------------------- declarations
# The repo's declared islands. Keep this list in lockstep with the
# README rule table.

register("norm_stats",
         "instance/layer-norm statistics (mean/var/rsqrt) accumulate in "
         "fp32; bf16 stats destabilize small spatial grids",
         where="imaginaire_tpu/layers/activation_norm.py")
register("sn_power_iteration",
         "spectral-norm power iteration and sigma estimate run in fp32; "
         "bf16 u-vectors drift and under-estimate sigma",
         where="imaginaire_tpu/layers/weight_norm.py")
register("loss_accumulation",
         "loss totals and grad/param health norms accumulate in fp32 "
         "(tree_norm, audit guard) so the finite-check is trustworthy",
         where="imaginaire_tpu/diagnostics/audit.py")
register("router_scores",
         "an expert router's logits, its scores by either scoring (sigmoid "
         "under a bias, or the softmax over the chosen logits), its top-k "
         "choice and the selected weights' normalization run in fp32; a "
         "bf16 score flips choices between near-tied experts",
         where="imaginaire_tpu/models/generators/hybrid_lm.py")
register("ssm_scan",
         "a state-space scan's step sizes (softplus), decays exp(dt A), "
         "their cumulative sums and the state carried across chunks stay "
         "fp32; bf16 decays compound over thousands of steps",
         where="imaginaire_tpu/ops/state_space.py (the scan), "
               "imaginaire_tpu/models/generators/hybrid_lm.py (the mixer's "
               "step sizes and decays)")
register("rotary_angles",
         "a rotary position embedding's angles (position times frequency, "
         "thousands of radians at long contexts), their cosines and sines "
         "and the turn itself run in fp32; a bf16 angle is off by whole "
         "radians past a few hundred positions",
         where="imaginaire_tpu/models/generators/hybrid_lm.py")
register("delta_rule",
         "a delta-rule linear attention's log-decays (softplus, exp(A_log)) "
         "and their cumulative sums, beta, the decays exp(c_i - c_j), the "
         "unit-lower-triangular inverse of each chunk's WY form and the "
         "state carried across chunks stay fp32; a bf16 decay compounds "
         "over thousands of steps and a bf16 solve loses the rank-one "
         "corrections it sums",
         where="imaginaire_tpu/ops/delta_rule.py (the rule), "
               "imaginaire_tpu/models/generators/hybrid_lm.py (the mixer's "
               "log-decays and beta)")
