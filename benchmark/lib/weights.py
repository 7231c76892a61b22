"""Weights from the seed, on the device, in one jitted call.

The reference owns the list of parameters ({name: (shape, kind)}); this
file fills it. The same arrays go to the program (through the driver's
adapter) and to the reference, so neither takes anything the other made.

JAX is imported inside the functions: a tool that imports this module
before `harness.place_caches()` must not start JAX without the cache's
place set.
"""

from __future__ import annotations

import math


def seed_key(seed):
    """A key from any whole number a little over 2**31 and beyond: the
    low 31 bits seed it, the rest are folded in."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _leaf(key, shape, kind):
    import jax
    import jax.numpy as jnp

    if kind == "kernel":
        fan_in = math.prod(shape[:-1])
        return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
    if kind == "bias":
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    if kind == "u":
        v = jax.random.normal(key, shape, jnp.float32)
        return v / jnp.linalg.norm(v)
    if kind == "bn_mean":
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    if kind == "bn_var":
        return jax.random.uniform(key, shape, jnp.float32, 0.25, 1.0)
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
