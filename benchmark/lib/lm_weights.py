"""A token model's weights from the seed, on the device, in one jitted
call: `benchmark/lib/weights.py` for the parameter kinds a hybrid
language model has (that file raises on a kind it does not know, and is
the SPADE cells' to keep).

The reference owns the list ({name: (shape, kind)}); this file fills it.
The same arrays go to the program and to the reference.
"""

from __future__ import annotations

import math

from benchmark.lib.weights import seed_key

# softplus(dt_bias) is drawn log-uniform over the config's time_step_min
# to time_step_max and floored at time_step_floor
TIME_STEP = (1e-3, 1e-1, 1e-4)


def _leaf(key, shape, kind):
    import jax
    import jax.numpy as jnp

    if kind == "kernel":      # (..., fan_in, out)
        return (jax.random.normal(key, shape, jnp.float32)
                / math.sqrt(shape[-2]))
    if kind == "embedding":
        return jax.random.normal(key, shape, jnp.float32)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "bias":
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    if kind == "score_bias":
        return 0.01 * jax.random.normal(key, shape, jnp.float32)
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if kind == "dt_bias":
        lo, hi, floor = TIME_STEP
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(lo), math.log(hi)))
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))      # softplus^-1
    raise ValueError(f"unknown parameter kind {kind!r}")


def make(spec, seed):
    """{name: float32 array on the default device} for every name of
    `spec`, a pure function of the seed."""
    import jax

    names = sorted(spec)

    def build(key):
        return {name: _leaf(jax.random.fold_in(key, i), *spec[name])
                for i, name in enumerate(names)}

    # lint: allow(bare-jit) -- the benchmark's own one-shot program
    return jax.jit(build)(seed_key(seed))
