"""The serving cell's driver end to end on the CPU at a tiny width: the
shape of the result line, `correct` from the plain reference, the control
that has to fail, the timed path broken underneath, and the command
refusing to measure without a TPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench_rehearsal_util import ROOT, tiny_cell

CELL = "spade_cocostuff_256.serve_open_steady"
TRAFFIC = dict(rate_rps=4.0, label_pool=3, checked_requests=3)
SECONDS = 2.0


def _run(cache_dir, seed):
    import jax

    from benchmark.drivers import serve_open
    from benchmark.lib import harness

    loaded = tiny_cell(CELL, cache_dir, **TRAFFIC)
    peaks = harness.read_json(os.path.join(ROOT, "benchmark", "peaks.json"))
    return loaded, serve_open.run(
        loaded, seed=seed, seconds=SECONDS, trace=False,
        devices=jax.devices()[:1], peaks=peaks, clock=harness.Clock(),
        shrunk=True)


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    return _run(tmp_path_factory.mktemp("bench_cache"), 2 ** 31 + 17)


def test_result_line_has_the_contracts_keys(sound, capsys):
    from benchmark.lib import harness

    _, run = sound
    harness.emit(**run)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert set(line["metrics"]) == {"serve_p50_ms", "serve_p95_ms", "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert line["attempted"] == int(TRAFFIC["rate_rps"] * SECONDS)
    assert line["failed"] == 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for name, c in line["compared"].items():
        assert f"compared {name} = " in err and "limit" in err


def test_sound_run_is_correct_and_compiles_nothing_in_the_window(sound):
    _, run = sound
    assert run["correct"] is True
    compared = run["compared"]
    assert compared["compiles_in_window"]["value"] == 0
    assert compared["requests_unanswered"]["value"] == 0
    # float32 on the CPU: the program and the reference agree closely
    assert compared["image_rel_err_max"]["value"] < 1e-4


def test_control_in_float8_products_fails_the_limit(sound):
    """The reference in the precision the limit stands against (float8
    e4m3 products: PERF.md says why not bfloat16), put in the program's
    place, has to come out as not correct."""
    from benchmark.drivers import serve_open
    from benchmark.lib import labels, program, weights

    loaded, _ = sound
    config, sizes = loaded["config"], loaded["config"]["sizes"]
    reference = program.load_reference(config, "serve")
    limits = loaded["workload"]["limits"]
    for seed in (5, 6, 2 ** 31 + 7):
        values = weights.make(reference.spec(sizes), seed)
        pool = labels.label_pool(seed, 2, sizes["image_size"],
                                 sizes["num_labels"])
        seeds = [seed + 1, seed + 2]
        ref = serve_open.reference_images(reference, values, sizes, pool,
                                          seeds, [0, 1], "float32")
        low = serve_open.reference_images(reference, values, sizes, pool,
                                          seeds, [0, 1], "float8")
        worst = max(serve_open.rel_err(low[i], ref[i]) for i in (0, 1))
        assert worst > limits["image_rel_err_max"], (seed, worst)


def test_an_answer_altered_where_it_is_produced_is_not_correct(
        tmp_path, monkeypatch):
    """The rest of a run with the timed path broken underneath: every
    image comes back shifted by one pixel."""
    from imaginaire_tpu.serving.engine import ServingEngine

    run_lanes = ServingEngine._run

    def shifted(self, key, data, rng):
        import jax.numpy as jnp

        return jnp.roll(run_lanes(self, key, data, rng), 1, axis=2)

    monkeypatch.setattr(ServingEngine, "_run", shifted)
    _, run = _run(tmp_path, 23)
    assert run["correct"] is False
    assert (run["compared"]["image_rel_err_max"]["value"]
            > run["compared"]["image_rel_err_max"]["limit"])


def test_the_reference_names_every_weight_serving_reads(sound):
    """Every parameter of the reference lands in the program's state (the
    driver raises otherwise), and the style noise is the program's draw."""
    from benchmark.reference import spade_generator

    a = np.asarray(spade_generator.style_noise(2 ** 31 - 5, 16))
    b = np.asarray(spade_generator.style_noise(2 ** 31 - 5, 16))
    np.testing.assert_array_equal(a, b)
    assert a.shape == (1, 16) and abs(float(a.mean())) < 1.0


def test_the_command_refuses_to_measure_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", HOME=str(tmp_path))
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode == 3
    assert done.stdout.strip() == ""
    assert "not a TPU" in done.stderr


def test_the_command_refuses_a_directory_without_the_program(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
