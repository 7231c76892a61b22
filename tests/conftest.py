"""Test harness: force an 8-device virtual CPU mesh BEFORE jax import.

The reference has no multi-device tests at all (SURVEY.md section 4); we
test sharding logic for real by faking 8 host devices, which exercises
exactly the SPMD partitioning and collectives that run on a TPU slice.
"""

import os

# Tests always run on the virtual CPU mesh, whatever the environment
# says: set before jax is imported.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert jax.devices()[0].platform == "cpu", (
    "tests must run on the virtual CPU mesh, got " + str(jax.devices()))

jax.config.update("jax_threefry_partitionable", True)
# Persistent compilation cache: model-level tests compile big graphs;
# repeat runs hit the cache instead of recompiling. Placed by the same
# helper, under the same rule, as every entry point.
from imaginaire_tpu.utils import compile_cache  # noqa: E402

compile_cache.configure()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.RandomState(0)


@pytest.fixture
def key():
    return jax.random.PRNGKey(0)
