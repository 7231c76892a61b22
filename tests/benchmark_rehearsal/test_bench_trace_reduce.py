"""The trace reduction on a small recorded trace: 280 ms cut from the
training cell's first traced run on the TPU v5e (PR 23), the tail of a
generator step, the wait for the next batch, the head of a discriminator
step."""

import gzip
import os

import pytest

from bench_rehearsal_util import ROOT  # noqa: F401

from benchmark.lib import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "train_280ms.xplane.pb.gz")


@pytest.fixture(scope="module")
def profile(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "cut.xplane.pb"
    with gzip.open(DATA, "rb") as src:
        path.write_bytes(src.read())
    return trace_reduce.load(str(path))


def test_device_planes_and_lines_are_found(profile):
    planes = trace_reduce.device_planes(profile)
    assert [p.name for p in planes] == ["/device:TPU:0"]
    assert "XLA Ops" in [line.name for line in planes[0].lines]
    assert "plane /device:TPU:0" in trace_reduce.describe(profile, limit=1)


def test_busy_idle_and_modules(profile):
    reduced = trace_reduce.reduce(profile)
    assert reduced["window_s"] == pytest.approx(0.278400898, rel=1e-6)
    assert reduced["busy_s"] == pytest.approx(0.061925325, rel=1e-6)
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    assert reduced["idle_gaps"] == [["unattributed",
                                     pytest.approx(0.216475573, rel=1e-6)]]
    assert list(reduced["modules"]) == [
        "jit__dis_step_fn(625203755969180693)"]
    assert reduced["modules"]["jit__dis_step_fn(625203755969180693)"] == [
        pytest.approx(0.084103393)]
    assert len(reduced["device_ops"]) == 10
    name, seconds = reduced["device_ops"][0]
    assert name == "fusion.68 (f32[512]" and seconds == pytest.approx(
        0.001841063)
    assert all(a[1] >= b[1] for a, b in zip(reduced["device_ops"],
                                            reduced["device_ops"][1:]))


def test_idle_gaps_go_to_what_the_host_was_doing(profile):
    lo = min(ev.start_ns for p in trace_reduce.device_planes(profile)
             for line in p.lines if line.name == "XLA Ops"
             for ev in line.events)
    marks = [(lo, lo + 100e6, "dispatch_steps"),
             (lo + 100e6, lo + 300e6, "next_feed")]
    reduced = trace_reduce.reduce(profile, marks)
    gaps = dict((k, v) for k, v in reduced["idle_gaps"])
    assert max(gaps, key=gaps.get) == "next_feed"
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)


def test_a_trace_without_device_operations_reduces_to_nothing(tmp_path):
    class Empty:
        planes = []

    assert trace_reduce.reduce(Empty()) is None
    with pytest.raises(FileNotFoundError):
        trace_reduce.newest_xplane(str(tmp_path))
