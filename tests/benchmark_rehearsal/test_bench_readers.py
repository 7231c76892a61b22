"""Each metric's reader on observations a run would hand it."""

import pytest

from bench_rehearsal_util import ROOT  # noqa: F401

from benchmark.lib import harness


def _read(name, observed):
    spec = harness.load_spec()
    metric = [m for m in spec["end_to_end"] + spec["per_layer"]
              if m["name"] == name]
    return harness.read_metrics(metric, observed).get(name, {}).get("value")


TRACES = [{"spans": [{"name": "admit", "dur_ms": 0.1},
                     {"name": "queue_wait", "dur_ms": w},
                     {"name": "bucket/pad", "dur_ms": 5.0},
                     {"name": "h2d_transfer", "dur_ms": 20.0},
                     {"name": "execute", "dur_ms": 1.0},
                     {"name": "d2h/slice", "dur_ms": 30.0},
                     {"name": "respond", "dur_ms": 0.2}], "fields": {}}
          for w in (2.0, 4.0, 9.0)]


@pytest.mark.parametrize("name,observed,want", [
    ("serve_p50_ms", {"latencies_ms": [10, 30, 20]}, 20.0),
    ("serve_p95_ms", {"latencies_ms": list(range(101))}, 95.0),
    ("setup_s", {"setup_s": 91.5}, 91.5),
    ("queue_wait_ms.serve", {"request_traces": TRACES}, 4.0),
    ("batch_exec_ms.serve", {"request_traces": TRACES}, 56.0),
    ("lane_pad_share.serve", {"lanes_run": 50, "lanes_padded": 5}, 10.0),
    ("loadgen_late_ms.serve", {"late_ms": [0.0, None, 1.0, 2.0]}, 1.9),
    ("device_idle.serve", {"trace": {"busy_s": 1.0, "window_s": 4.0}}, 75.0),
    ("device_idle.train", {"trace": {"busy_s": 3.0, "window_s": 4.0}}, 25.0),
    ("hbm_peak_gb.serve", {"memory_peak_bytes": 9.5e9}, 9.5),
    ("hbm_peak_gb.train", {"memory_peak_bytes": 15e9}, 15.0),
    ("train_imgs_per_s", {"images": 240, "window_s": 20.0, "chips": 1}, 12.0),
    ("data_wait_share.train", {"feed_wait_s": 0.5, "window_s": 20.0}, 2.5),
    ("dis_step_ms", {"trace": {"modules": {
        "jit_dis_step(1)": [0.09, 0.1, 0.11], "jit_gen_step(2)": [0.2]}}},
     100.0),
    ("gen_step_ms", {"trace": {"modules": {
        "jit_dis_step(1)": [0.09], "jit_gen_step(2)": [0.2, 0.22]}}}, 210.0),
    ("mfu.train", {"step_flops": {"iteration": 197e11}, "iterations": 20,
                   "window_s": 10.0, "chips": 1,
                   "peaks": {"bf16_flops_per_s": 197e12}}, 20.0),
])
def test_reader(name, observed, want):
    assert _read(name, observed) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "serve_p50_ms", "queue_wait_ms.serve", "batch_exec_ms.serve",
    "lane_pad_share.serve", "device_idle.serve", "device_idle.train",
    "hbm_peak_gb.train", "train_imgs_per_s", "dis_step_ms", "gen_step_ms",
    "mfu.train", "data_wait_share.train", "loadgen_late_ms.serve"])
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    assert _read(name, {}) is None
