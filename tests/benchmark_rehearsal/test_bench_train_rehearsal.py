"""The training cell's driver end to end on the CPU at a tiny width, with
the control and a broken step. Minutes on the CPU, so marked slow: run it
by hand after a change to `drivers/train_fed.py` or `reference/`:

    JAX_PLATFORMS=cpu python -m pytest -m slow tests/benchmark_rehearsal/test_bench_train_rehearsal.py
"""

import os

import pytest

from bench_rehearsal_util import ROOT, tiny_cell

CELL = "spade_cocostuff_256.train_fed"
pytestmark = pytest.mark.slow


def _run(cache_dir, seed, seconds=1.0):
    import jax

    from benchmark.drivers import train_fed
    from benchmark.lib import harness

    loaded = tiny_cell(CELL, cache_dir, fixture_samples=32)
    peaks = harness.read_json(os.path.join(ROOT, "benchmark", "peaks.json"))
    return loaded, train_fed.run(
        loaded, seed=seed, seconds=seconds, trace=False,
        devices=jax.devices()[:1], peaks=peaks, clock=harness.Clock(),
        shrunk=True)


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    return _run(tmp_path_factory.mktemp("bench_cache"), 2 ** 31 + 17)


def test_a_sound_run_follows_the_reference(sound):
    loaded, run = sound
    assert set(run["metrics"]) == {"train_imgs_per_s", "setup_s"}
    c = run["compared"]
    assert c["compiles_in_window"]["value"] == 0
    # bfloat16 compute against the float32 reference, at a tiny width
    assert c["loss_D_first_rel"]["value"] < 2e-2
    assert c["loss_G_first_rel"]["value"] < 2e-2
    # every limit of the cell's file is compared, and nothing without one
    assert set(c) == set(loaded["workload"]["limits"]) | {
        "compiles_in_window"}
    # the later steps' losses are reported beside the compared numbers
    assert max(run["extra"]["loss_gaps_by_step"]["G"]) < 0.1
    assert run["attempted"] >= 1 and run["failed"] == 0


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        tmp_path, monkeypatch):
    """The rest of a run with the timed path broken underneath: the
    generator's update hands back the state it was given."""
    from imaginaire_tpu.trainers.base import BaseTrainer

    def frozen(self, data):
        import jax.numpy as jnp

        return {"total": jnp.zeros(())}

    monkeypatch.setattr(BaseTrainer, "gen_update", frozen)
    loaded, run = _run(tmp_path, 29)
    assert run["correct"] is False
    change = run["compared"]["param_change_norm_worst_leaf"]
    assert change["value"] > change["limit"]


def test_control_in_float8_products_fails_a_limit():
    """The reference with float8 products, put in the program's place,
    has to come out as not correct: no trainer, seeded batches."""
    import types

    import jax.numpy as jnp
    import numpy as np

    from benchmark.drivers import train_fed
    from benchmark.lib import harness, labels, program, weights
    from bench_rehearsal_util import TINY

    loaded = harness.load_cell(CELL)
    sizes = dict(loaded["config"]["sizes"], **TINY)
    limits = loaded["workload"]["limits"]
    reference = program.load_reference(loaded["config"], "train")
    spec = reference.spec(sizes)
    for seed in (3, 4, 2 ** 31 + 5):
        rng = np.random.default_rng(seed)
        pool = labels.label_pool(seed, 4, sizes["image_size"],
                                 sizes["num_labels"])
        batches = [{"images": rng.uniform(-1, 1, (4, 256, 256, 3)).astype(
                        np.float32),
                    "label": np.concatenate(pool, axis=0)}
                   for _ in range(2)]
        recorded = types.SimpleNamespace(batches=batches)
        runs = {precision: train_fed.reference_steps(
            reference, weights.make(spec, seed), sizes, recorded, seed,
            precision, jnp.bfloat16) for precision in ("float32", "float8")}
        numbers, _ = train_fed.compare(runs["float8"], runs["float32"])
        failed = [k for k, v in numbers.items() if v > limits[k]]
        assert "first_gradient_apart_median_leaf" in failed, (seed, numbers)
