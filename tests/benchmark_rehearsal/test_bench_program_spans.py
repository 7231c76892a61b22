"""The readers of the program's own spans (PR 24): each on a hand-made
phase table, compile ledger or profile, the two idle shares against
`device_idle.train`, and what a program without the spans gives."""

import gzip
import json
import os
import types

import pytest

from bench_rehearsal_util import ROOT

from benchmark.lib import harness, program_spans, trace_reduce

CELL = "spade_cocostuff_256.train_fed"
NEW = ["feed_wait_ms.train", "loader_batch_ms.train", "host_hook_ms.train",
       "h2d_ms.train", "dispatch_ms.train", "health_poll_ms.train",
       "idle_feed_starved.train", "idle_host_busy.train", "init_state_s",
       "step_build_s"]

PHASES = {
    "data_wait": {"count": 50, "total_ms": 11000.0, "p50_ms": 231.5},
    "prefetch_host": {"count": 52, "total_ms": 20000.0, "p50_ms": 402.0},
    "prefetch_preprocess": {"count": 52, "total_ms": 900.0, "p50_ms": 17.25},
    "prefetch_transfer": {"count": 52, "total_ms": 1500.0, "p50_ms": 29.0},
    "dis_step": {"count": 50, "total_ms": 30100.0, "p50_ms": 2.5},
    "gen_step": {"count": 50, "total_ms": 45150.0, "p50_ms": 3.25},
    "health_poll": {"count": 99, "total_ms": 9000.0, "p50_ms": 80.0},
    "init_state": {"count": 1, "total_ms": 81250.0, "p50_ms": 81250.0},
}
LEDGER = [
    {"label": "dis_step", "lower_ms": 23000.0, "compile_ms": 4500.0},
    {"label": "gen_step", "lower_ms": 31000.0, "compile_ms": 9000.0},
    {"label": "patch_eval_extractor", "lower_ms": 10.0, "compile_ms": 5.0},
]


def _read(name, observed):
    spec = harness.load_spec()
    metric = [m for m in spec["per_layer"] if m["name"] == name]
    return harness.read_metrics(metric, observed).get(name, {}).get("value")


def _with_program(monkeypatch, phases, records):
    from imaginaire_tpu.telemetry import xla_obs

    monkeypatch.setattr(program_spans, "phase_table", lambda: phases)
    monkeypatch.setattr(xla_obs, "ledger",
                        lambda: types.SimpleNamespace(records=records))


@pytest.mark.parametrize("name,want", [
    ("feed_wait_ms.train", 231.5),
    ("loader_batch_ms.train", 402.0),
    ("host_hook_ms.train", 17.25),
    ("h2d_ms.train", 29.0),
    ("dispatch_ms.train", 5.75),
    ("health_poll_ms.train", 9000.0 / 50),
    ("init_state_s", 81.25),
    ("step_build_s", 67.5),
])
def test_reader_on_a_hand_made_program(monkeypatch, name, want):
    _with_program(monkeypatch, PHASES, LEDGER)
    assert _read(name, {}) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_span_gives_nothing(monkeypatch, tmp_path,
                                                  name):
    """The parent of PR 24: a phase table and a ledger without the names,
    and no trace, or a traced run whose file is not there."""
    _with_program(monkeypatch, {}, [])
    monkeypatch.setattr(harness, "CACHE_DIR", str(tmp_path))
    assert _read(name, {}) is None
    assert _read(name, {"trace": {"busy_s": 1.0, "window_s": 2.0}}) is None


def test_the_phase_table_is_the_programs_own_and_outlives_shutdown():
    from imaginaire_tpu.telemetry import core

    old = core._TELEMETRY
    try:
        tm = core._TELEMETRY = core.Telemetry(enabled=True, mfu=False)
        for _ in range(3):
            with tm.span("data_wait"):
                pass
        tm.shutdown()
        assert program_spans.count("data_wait") == 3
        assert program_spans.median_ms("data_wait") >= 0.0
        assert program_spans.total_s("init_state") is None
    finally:
        core._TELEMETRY = old


# ------------------------------------------------------- a hand-made profile

MS = 1_000_000


def _line(name, events):
    return types.SimpleNamespace(name=name, events=[
        types.SimpleNamespace(name=n, start_ns=s * MS, duration_ns=d * MS)
        for n, s, d in events])


def _profile(host_lines):
    """One device busy in [0, 100), [300, 400), [450, 1000) ms: idle 200
    ms and 50 ms of a 1000 ms window."""
    device = types.SimpleNamespace(name="/device:TPU:0", lines=[
        _line("XLA Ops", [("%fusion.1 = f32[8]{0} fusion()", 0, 100),
                          ("%fusion.2 = f32[8]{0} fusion()", 300, 100),
                          ("%fusion.3 = f32[8]{0} fusion()", 450, 550)]),
        _line("XLA Modules", [("jit__dis_step_fn(1)", 0, 100)])])
    host = types.SimpleNamespace(name="/host:CPU", lines=host_lines)
    return types.SimpleNamespace(planes=[host, device])


LOOP = _line("python", [
    ("imaginaire/data_wait", 50, 200),      # covers [100, 250) of gap one
    ("imaginaire/start_of_iteration", 250, 1),
    ("imaginaire/dis_step", 251, 40),
    ("imaginaire/data_wait", 440, 5),       # covers [440, 445) of gap two
    ("bench/next_feed", 50, 200)])
PRODUCER = _line("python", [("imaginaire/prefetch_host", 0, 240),
                            ("imaginaire/prefetch_put", 500, 400)])


def test_idle_split_of_a_hand_made_profile():
    split = program_spans.idle_split(_profile([LOOP, PRODUCER]))
    assert split == {"window_s": pytest.approx(1.0),
                     "idle_s": pytest.approx(0.25),
                     "covered_s": pytest.approx(0.155)}
    assert program_spans.idle_split(_profile([PRODUCER])) is None
    host_alone = _profile([LOOP])
    del host_alone.planes[1]
    assert program_spans.idle_split(host_alone) is None


@pytest.fixture
def traced_run(tmp_path, monkeypatch):
    """A traced run's `observed` and trace directory, the profile's file
    standing for the hand-made profile; counts how often it is parsed."""
    monkeypatch.setattr(harness, "CACHE_DIR", str(tmp_path))
    where = tmp_path / "trace" / "plugins" / "profile" / "2026_09_28"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(b"hand-made")
    profile = _profile([LOOP, PRODUCER])
    loads = []

    def load(path):
        loads.append(path)
        return profile

    monkeypatch.setattr(trace_reduce, "load", load)
    return {"trace": trace_reduce.reduce(profile)}, loads


def test_the_two_idle_shares_add_up_to_the_devices_idle_share(traced_run):
    observed, loads = traced_run
    starved = _read("idle_feed_starved.train", observed)
    busy = _read("idle_host_busy.train", observed)
    assert starved == pytest.approx(15.5)
    assert busy == pytest.approx(9.5)
    assert starved + busy == pytest.approx(
        _read("device_idle.train", observed), abs=1e-9)
    # one parse of the file serves both readers
    assert len(loads) == 1


def test_the_breakdown_tool_names_what_each_thread_was_in():
    from benchmark.tools import span_breakdown

    out = span_breakdown.breakdown(_profile([LOOP, PRODUCER]))
    assert out["threads"] == [
        {"data_wait": 2, "start_of_iteration": 1, "dis_step": 1},
        {"prefetch_host": 1, "prefetch_put": 1}]
    starved, busy = out["feed_starved_s"], out["host_busy_s"]
    assert starved["_total"] == pytest.approx(0.155)
    assert starved["prefetch_host"] == pytest.approx(0.14)
    assert starved["prefetch_put"] == 0.0
    assert busy["_total"] == pytest.approx(0.095)
    assert busy["dis_step"] == pytest.approx(0.04)
    assert busy["start_of_iteration"] == pytest.approx(0.001)
    assert busy["data_wait"] == 0.0
    assert out["long_gaps"] == [
        {"at_ms": 100.0, "ms": 200.0, "in": {
            "data_wait": 150.0, "dis_step": 40.0, "prefetch_host": 140.0,
            "start_of_iteration": 1.0}},
        {"at_ms": 400.0, "ms": 50.0, "in": {"data_wait": 5.0}}]


def test_a_recorded_trace_of_the_parent_has_no_program_events(tmp_path):
    """PR 23's recorded cut: device operations, no `imaginaire/` event."""
    data = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "train_280ms.xplane.pb.gz")
    path = tmp_path / "cut.xplane.pb"
    with gzip.open(data, "rb") as src:
        path.write_bytes(src.read())
    assert program_spans.idle_split(trace_reduce.load(str(path))) is None


# --------------------------------------------------------------- the contract

def test_the_ten_entries_are_appended_and_whole():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entries = spec["per_layer"][-len(NEW):]
    assert [m["name"] for m in entries] == NEW
    layers = {m["layer"] for m in spec["per_layer"][:-len(NEW)]}
    for m in entries:
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["workloads"] == [CELL]
        assert m["layer"] in layers | {"set-up"}
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                           m["name"] + ".py"))
    assert {m["moves"] for m in entries} == {"train_imgs_per_s", "setup_s"}
    # the traced run of the cell reads all of them, after the six it had
    traced = [m["name"] for m in harness.metrics_of(
        harness.load_spec(), CELL, "per_layer")]
    assert traced[-len(NEW):] == NEW and len(traced) == 16
