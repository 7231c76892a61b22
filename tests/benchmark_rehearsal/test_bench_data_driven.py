"""A later PR adds a configuration, a cell and a per-layer metric by
adding files, never by editing one: shown with a dummy of each in a
temporary checkout."""

import json
import os
import shutil

import pytest

from bench_rehearsal_util import ROOT

from benchmark.lib import harness


@pytest.fixture
def checkout(tmp_path):
    """A copy of BENCHMARK.json and of the benchmark's data files."""
    root = tmp_path / "checkout"
    bench = root / "benchmark"
    for sub in ("configs", "workloads", "metrics"):
        shutil.copytree(os.path.join(ROOT, "benchmark", sub), bench / sub)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copy(os.path.join(ROOT, "benchmark", "candidates.json"), bench)
    return root


def _add(root, config=None, cell=None, metric=None):
    path = root / "BENCHMARK.json"
    spec = json.loads(path.read_text())
    if config:
        spec["configs"].append(config)
    if cell:
        spec["workloads"].append(cell)
    if metric:
        spec["per_layer"].append(metric)
    path.write_text(json.dumps(spec))


def test_a_new_cell_and_configuration_need_only_files(checkout):
    bench = checkout / "benchmark"
    (bench / "configs" / "dummy_512.json").write_text(json.dumps(
        {"name": "dummy_512", "source": "paper", "reduced": [],
         "assumed": [], "sizes": {"image_size": 512}}))
    (bench / "workloads" / "dummy_512.serve_open_bursty.json").write_text(
        json.dumps({"driver": "serve_open", "limits": {},
                    "traffic": {"process": "onoff", "rate_rps": 3.0,
                                "on_share": 0.2, "burst_requests": 4}}))
    _add(checkout,
         config={"name": "dummy_512", "source": "paper", "reduced": [],
                 "file": "benchmark/configs/dummy_512.json", "why": "test"},
         cell={"name": "dummy_512.serve_open_bursty", "config": "dummy_512",
               "traffic": "serve_open_bursty", "chips": 1, "why": "test"})
    loaded = harness.load_cell("dummy_512.serve_open_bursty",
                               root=str(checkout))
    assert loaded["config"]["sizes"]["image_size"] == 512
    assert loaded["workload"]["traffic"]["process"] == "onoff"
    assert harness.load_driver(loaded["workload"]["driver"]).run
    # the cells that were there still load, untouched
    for cell in ("spade_cocostuff_256.train_fed",
                 "spade_cocostuff_256.serve_open_steady"):  # a candidate
        assert harness.load_cell(cell, root=str(checkout))["cell"][
            "chips"] == 1


def test_a_new_per_layer_metric_needs_only_its_reader(checkout):
    bench = checkout / "benchmark"
    (bench / "metrics" / "answers_per_batch.serve.py").write_text(
        "def read(observed):\n"
        "    run = observed.get('lanes_run')\n"
        "    return observed['answers'] / run if run else None\n")
    cell = "spade_cocostuff_256.serve_open_steady"
    _add(checkout, metric={
        "name": "answers_per_batch.serve", "unit": "1", "better": "higher",
        "source": "program_counter", "layer": "serving engine",
        "moves": "serve_p50_ms", "workloads": [cell]})
    spec = harness.load_spec(str(checkout))
    mine = harness.metrics_of(spec, cell, "per_layer")
    assert "answers_per_batch.serve" in [m["name"] for m in mine]
    observed = {"answers": 30, "lanes_run": 40, "lanes_padded": 10,
                "memory_peak_bytes": 9e9}
    got = harness.read_metrics(mine, observed, str(bench))
    assert got["answers_per_batch.serve"] == {"value": 0.75, "unit": "1"}
    assert got["lane_pad_share.serve"]["value"] == 25.0
    # a reader that finds nothing is left out of the line, never 0
    assert "device_idle.serve" not in got and "queue_wait_ms.serve" not in got
    assert "answers_per_batch.serve" not in harness.read_metrics(
        mine, {"answers": 3}, str(bench))


def test_an_unknown_cell_is_refused(checkout):
    with pytest.raises(harness.BenchmarkError):
        harness.load_cell("no_such.cell", root=str(checkout))
