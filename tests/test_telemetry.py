"""Telemetry stack coverage (ISSUE 2 satellite): JSONL sink round-trip,
span nesting/monotonicity, watchdog stack dumps, MFU math, Meter->sink
fan-out with TensorBoard parity, torch-free degradation, trace knob,
and the report renderer."""

import json
import logging
import os
import sys
import threading
import time

import numpy as np
import pytest

from imaginaire_tpu import telemetry
from imaginaire_tpu.telemetry import core as tcore
from imaginaire_tpu.telemetry.report import (
    load_events,
    render_report,
    summarize,
)
from imaginaire_tpu.telemetry.sinks import JsonlSink, Sink


class CaptureSink(Sink):
    def __init__(self):
        self.events = []
        self.flushes = 0

    def emit(self, event):
        self.events.append(event)

    def flush(self):
        self.flushes += 1

    def of_kind(self, kind):
        return [e for e in self.events if e["kind"] == kind]


@pytest.fixture
def tm_sandbox():
    """Isolate the module singleton: each test configures its own
    Telemetry and the previous one is restored afterwards."""
    old = tcore._TELEMETRY
    yield
    tcore._TELEMETRY.shutdown()
    tcore._TELEMETRY = old


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def test_jsonl_sink_roundtrip(tm_sandbox, tmp_path):
    tm = telemetry.configure(logdir=str(tmp_path), enabled=True,
                             sinks=["jsonl"], flush_every_n_steps=0)
    with tm.span("gen_step", step=7):
        pass
    tm.counter("loss/total", 1.25, step=7)
    tm.meta("run_info", config="x.yaml")
    tm.shutdown()

    events = _read_jsonl(str(tmp_path / "telemetry.jsonl"))
    kinds = {e["kind"] for e in events}
    assert {"span", "counter", "meta"} <= kinds
    # by name: `configure` replays the process-wide compile ledger into a
    # new instance's sinks first, so whatever an earlier test of this
    # worker compiled (xla/compile/* counters, xla_compile/* metas) leads
    span, = [e for e in events
             if e["kind"] == "span" and e["name"] == "gen_step"]
    assert span["step"] == 7
    assert span["dur_ms"] >= 0 and span["thread"]
    counter, = [e for e in events
                if e["kind"] == "counter" and e["name"] == "loss/total"]
    assert counter["value"] == 1.25 and counter["step"] == 7


def test_span_nesting_and_timing_monotonicity(tm_sandbox):
    sink = CaptureSink()
    tm = telemetry.configure(enabled=True, sinks=[sink],
                             flush_every_n_steps=0)
    with tm.span("outer", step=1):
        time.sleep(0.002)
        with tm.span("inner", step=1):
            time.sleep(0.002)
        time.sleep(0.002)
    tm.flush()

    spans = {e["name"]: e for e in sink.of_kind("span")}
    assert spans["inner"]["parent"] == "outer"
    assert spans["outer"]["parent"] is None
    # the child closed first but started later; both clocks monotone
    assert spans["inner"]["t"] >= spans["outer"]["t"]
    assert spans["inner"]["dur_ms"] <= spans["outer"]["dur_ms"]
    assert spans["outer"]["dur_ms"] >= 6.0 - 1.0  # 3 sleeps, coarse clock


def test_same_name_nested_span_not_double_counted(tm_sandbox):
    tm = telemetry.configure(enabled=True, sinks=[],
                             flush_every_n_steps=0)
    with tm.span("data_wait"):
        with tm.span("data_wait"):
            time.sleep(0.001)
    phases = tm.window_summary()["phases"]
    assert phases["data_wait"]["count"] == 1


class _FakeAnnotation:
    """Stands in for `jax.profiler.TraceAnnotation`: records, in order,
    what was opened and closed on which thread."""

    log = []

    def __init__(self, name, **kwargs):
        self.name = name

    def __enter__(self):
        self.log.append(("open", self.name,
                         threading.current_thread().name))
        return self

    def __exit__(self, *exc):
        self.log.append(("close", self.name,
                         threading.current_thread().name))
        return False


@pytest.fixture
def annotations(monkeypatch):
    import jax

    monkeypatch.setattr(_FakeAnnotation, "log", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    return _FakeAnnotation.log


def _nested(tm):
    with tm.span("outer", step=1):
        with tm.span("inner", step=1):
            pass
    me = threading.current_thread().name
    return [("open", "imaginaire/outer", me), ("open", "imaginaire/inner", me),
            ("close", "imaginaire/inner", me),
            ("close", "imaginaire/outer", me)]


def _second_thread(tm):
    def work():
        with tm.span("prefetch_host"):
            pass

    worker = threading.Thread(target=work, name="device-prefetch")
    worker.start()
    worker.join(timeout=10)
    assert not worker.is_alive()
    return [("open", "imaginaire/prefetch_host", "device-prefetch"),
            ("close", "imaginaire/prefetch_host", "device-prefetch")]


@pytest.mark.parametrize("drive", [_nested, _second_thread])
def test_span_is_one_profiler_annotation_on_its_thread(
        tm_sandbox, annotations, drive):
    tm = telemetry.configure(enabled=True, sinks=[],
                             flush_every_n_steps=0)
    assert drive(tm) == annotations


def test_disabled_telemetry_opens_no_annotation(annotations):
    tm = tcore.Telemetry(enabled=False)
    with tm.span("gen_step", step=1):
        pass
    assert annotations == []


def test_disabled_singleton_is_noop(tmp_path):
    tm = tcore.Telemetry(enabled=False)
    with tm.span("x"):
        pass
    tm.counter("y", 1.0)
    tm.step_complete(0, items=4)
    tm.flush()
    assert tm.window_summary()["phases"] == {}


def test_watchdog_dumps_producer_thread_stack(tm_sandbox, tmp_path):
    release = threading.Event()

    def stalled_producer():
        release.wait(timeout=30)  # parked, like a blocked queue.get

    producer = threading.Thread(target=stalled_producer, daemon=True,
                                name="device-prefetch")
    producer.start()
    tm = telemetry.configure(logdir=str(tmp_path), enabled=True,
                             sinks=["jsonl"], flush_every_n_steps=0,
                             hang_timeout_s=0.15)
    tm.step_complete(1, items=1)  # arm the heartbeat
    deadline = time.time() + 10
    path = str(tmp_path / "telemetry.jsonl")
    hangs = []
    while time.time() < deadline and not hangs:
        time.sleep(0.05)
        if os.path.exists(path):
            hangs = [e for e in _read_jsonl(path) if e["kind"] == "hang"]
    release.set()
    producer.join(timeout=5)
    assert hangs, "watchdog never fired on a stalled step"
    hang = hangs[0]
    assert hang["step"] == 1
    assert "no step completed" in hang["reason"]
    assert "device-prefetch" in hang["stacks"], sorted(hang["stacks"])
    assert any("stalled_producer" in frame
               for frame in hang["stacks"]["device-prefetch"])
    # one dump per stall, not one per poll tick
    time.sleep(0.4)
    hangs = [e for e in _read_jsonl(path) if e["kind"] == "hang"]
    assert len(hangs) == 1


def test_watchdog_suspended_during_eval_span(tm_sandbox, tmp_path):
    """ISSUE 3 satellite: a long FID/KID sweep (an open ``eval`` span)
    must not read as a hang — and the stall clock re-arms when the span
    exits, so the watchdog stays live for real post-eval stalls."""
    tm = telemetry.configure(logdir=str(tmp_path), enabled=True,
                             sinks=["jsonl"], flush_every_n_steps=0,
                             hang_timeout_s=0.15)
    tm.step_complete(1, items=1)
    path = str(tmp_path / "telemetry.jsonl")
    with tm.span("eval", step=1):
        assert tm.watchdog_suspended()
        time.sleep(0.6)  # 4x the timeout, all inside the eval span
    assert not tm.watchdog_suspended()
    time.sleep(0.05)
    tm._push_to_sinks()
    hangs = [e for e in _read_jsonl(path)] if os.path.exists(path) else []
    assert not [e for e in hangs if e["kind"] == "hang"], \
        "watchdog fired during an eval span"
    # exiting the span re-armed the clock from NOW: a real stall after
    # eval still fires
    deadline = time.time() + 10
    fired = []
    while time.time() < deadline and not fired:
        time.sleep(0.05)
        if os.path.exists(path):
            fired = [e for e in _read_jsonl(path) if e["kind"] == "hang"]
    assert fired, "watchdog armed-after-eval never fired on a real stall"


def test_mfu_counter_matches_hand_computed_value(tm_sandbox):
    sink = CaptureSink()
    tm = telemetry.configure(enabled=True, sinks=[sink],
                             flush_every_n_steps=0, peak_flops=1e12)
    tm.set_step_flops(2e9)

    fake_now = [100.0]
    tm._clock = lambda: fake_now[0]
    tm.reset_window()
    for i in range(5):
        fake_now[0] += 0.01
        tm.step_complete(i, items=4, dur_s=0.01)
    tm.flush(step=4)

    counters = {e["name"]: e["value"] for e in sink.of_kind("counter")}
    # 5 steps of 2 GFLOP in 0.05 s against a 1 TFLOP/s peak => 20% MFU
    assert counters["perf/mfu"] == pytest.approx(0.2)
    assert counters["perf/imgs_per_sec"] == pytest.approx(400.0)
    assert counters["perf/step_time_ms_p50"] == pytest.approx(10.0)
    assert counters["perf/step_time_ms_p99"] == pytest.approx(10.0)
    meta = next(e for e in sink.of_kind("meta")
                if e["name"] == "step_flops")
    assert meta["flops"] == 2e9
    assert meta["peak_source"] == "config:telemetry.peak_flops"


def test_meter_fanout_keeps_tensorboard_parity(tm_sandbox, tmp_path,
                                               monkeypatch):
    from imaginaire_tpu.utils import meters

    class StubWriter:
        def __init__(self):
            self.scalars = []

        def add_scalar(self, name, value, step):
            self.scalars.append((name, float(value), step))

        def flush(self):
            pass

    stub = StubWriter()
    monkeypatch.setattr(meters, "_WRITER", stub)
    telemetry.configure(logdir=str(tmp_path), enabled=True,
                        sinks=["jsonl", "tensorboard"],
                        flush_every_n_steps=0)

    meter = meters.Meter("data/host_wait_ms")
    meter.write(2.0)
    meter.write(4.0)
    meter.flush(step=11)
    telemetry.get().shutdown()

    # TB got the averaged scalar exactly once (via the sink, not the
    # direct writer path on top of it). The xla_obs ledger may add its
    # own xla/* / mem/* counters on the flush cadence — those are not
    # meter fanout and are filtered from the parity check.
    meter_scalars = [s for s in stub.scalars
                     if not s[0].startswith(("xla/", "mem/"))]
    assert meter_scalars == [("data/host_wait_ms", 3.0, 11)]
    events = _read_jsonl(str(tmp_path / "telemetry.jsonl"))
    counter = next(e for e in events if e["kind"] == "counter"
                   and e["name"] == "data/host_wait_ms")
    assert counter["value"] == 3.0 and counter["step"] == 11


def test_meter_nonfinite_warns_and_counts(tm_sandbox, tmp_path, caplog):
    from imaginaire_tpu.utils import meters

    telemetry.configure(logdir=str(tmp_path), enabled=True,
                        sinks=["jsonl"], flush_every_n_steps=0)
    meter = meters.Meter("gen_update/total")
    meter.write(1.0)
    meter.write(float("nan"))
    meter.write(float("inf"))
    with caplog.at_level(logging.WARNING,
                         logger="imaginaire_tpu.utils.meters"):
        meter.flush(step=3)
    telemetry.get().shutdown()

    assert any("non-finite" in rec.message for rec in caplog.records)
    events = _read_jsonl(str(tmp_path / "telemetry.jsonl"))
    counters = {e["name"]: e["value"] for e in events
                if e["kind"] == "counter"}
    assert counters["gen_update/total/nonfinite_count"] == 2.0
    assert counters["gen_update/total"] == 1.0  # finite mean still lands


def test_set_summary_writer_degrades_without_torch(tmp_path, monkeypatch):
    from imaginaire_tpu.utils import meters

    monkeypatch.setattr(meters, "_WRITER", None)
    # None in sys.modules makes `import torch.utils.tensorboard` raise
    # ImportError — the torch-free-host simulation
    monkeypatch.setitem(sys.modules, "torch.utils.tensorboard", None)
    meters.set_summary_writer(str(tmp_path))  # must not raise
    assert meters.get_summary_writer() is None
    # and the writer-less write path stays a no-op, not a crash
    meters.write_summary("x", 1.0, 0)


def test_trace_at_step_knob(tm_sandbox, monkeypatch):
    import jax

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda path: calls.append(("start", path)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", None)))
    tm = telemetry.configure(enabled=True, sinks=[], logdir="/tmp/x",
                             flush_every_n_steps=0, trace_at_step=3,
                             trace_num_steps=2)
    for step in range(1, 7):
        tm.step_complete(step)
    assert [c[0] for c in calls] == ["start", "stop"]
    assert calls[0][1].endswith("/trace")
    # started exactly at step 3, stopped once step 3+2 was reached
    # by name: the replayed compile ledger's metas carry no step
    steps = {e["name"]: e["step"] for e in tm._events
             if e["kind"] == "meta" and e["name"].startswith("trace_")}
    assert steps == {"trace_started": 3, "trace_stopped": 5}


def test_window_summary_data_wait_share(tm_sandbox):
    tm = telemetry.configure(enabled=True, sinks=[],
                             flush_every_n_steps=0)
    fake_now = [10.0]
    tm._clock = lambda: fake_now[0]
    tm.reset_window()
    with tm.span("data_wait"):
        time.sleep(0.01)
    fake_now[0] += 0.1
    tm.step_complete(0, items=2)
    s = tm.window_summary()
    assert s["duration_s"] == pytest.approx(0.1)
    assert 5.0 < s["data_wait_share_pct"] < 50.0
    assert s["imgs_per_sec"] == pytest.approx(20.0)


def test_report_renders_phase_table(tm_sandbox, tmp_path):
    tm = telemetry.configure(logdir=str(tmp_path), enabled=True,
                             sinks=["jsonl"], flush_every_n_steps=0)
    for step in range(3):
        with tm.span("dis_step", step=step):
            time.sleep(0.001)
        with tm.span("gen_step", step=step):
            time.sleep(0.002)
        tm.step_complete(step, items=2, dur_s=0.003)
    tm.flush(step=2)
    tm.shutdown()

    path = str(tmp_path / "telemetry.jsonl")
    report = render_report(path)
    assert "| gen_step | 3 |" in report
    assert "| dis_step | 3 |" in report
    assert "perf/imgs_per_sec" in report
    summary = summarize(load_events(path))
    assert summary["phases"]["gen_step"]["count"] == 3
    assert not summary["hangs"]


def _expert_counters(flushes, extra):
    """`moe/<layer>/*` counter events of layers 10 and 3 over `flushes`
    flushes; `extra(layer, flush, held)` adds to a layer's stats."""
    events = []
    for flush in range(flushes):
        for layer, held in (("10", 5000 + flush), ("3", 900 + flush)):
            stats = {"held_assignments": held, "load_max_over_mean": 2.5,
                     "buffer_occupancy": held / 49152}
            stats.update(extra(layer, flush, held))
            events += [{"kind": "counter", "name": f"moe/{layer}/{k}",
                        "value": v, "step": flush, "t": float(flush)}
                       for k, v in stats.items()]
    return events


@pytest.mark.parametrize("compact,column", [
    ([1.0, 1.0, 0.0, 1.0], "| 75% |"), (None, "| n/a |")],
    ids=["with_compact", "from_before_the_counter"])
def test_report_renders_the_experts_table(compact, column):
    """The latest `moe/<layer>/*` counters a layer, layers in numeric
    order, and the share of the flushes' steps on the filled prefix (the
    whole series of `moe/<layer>/compact`, where a run has it)."""
    events = _expert_counters(4, lambda layer, flush, held: {} if compact
                              is None else {"compact": compact[flush]
                                            if layer == "3" else 1.0})
    lines = render_report(events).splitlines()
    start = lines.index("## experts")
    assert lines[start + 1].endswith(
        "| buffer occupancy | moved over held | on the prefix |")
    rows = lines[start + 3:start + 5]
    assert rows[0].startswith("| 3 | 903 | 2.50 | 1.8% ")
    assert rows[0].endswith(column)
    assert rows[1].startswith("| 10 | 5003 | 2.50 | 10.2% ")
    assert rows[1].endswith("| 100% |" if compact else "| n/a |")
    assert "## experts" not in render_report(
        [e for e in events if not e["name"].startswith("moe/")])


@pytest.mark.parametrize("moved,columns", [
    ({"10": 5120.0, "3": 8192.0}, ("| 1.02 |", "| 9.09 |")),
    ({"10": 0.0, "3": 1024.0}, ("| 0.00 |", "| 1.14 |")),
    (None, ("| n/a |", "| n/a |"))],
    ids=["with_moved_rows", "a_layer_that_moved_nothing",
         "from_before_the_counter"])
def test_report_renders_moved_over_held_rows(moved, columns):
    """ISSUE 40: the newest flush's `moe/<layer>/moved_rows` over its
    `held_assignments`, a layer: near 1 where the movement ends with the
    held rows, the tier over them where it does not."""
    events = _expert_counters(2, lambda layer, flush, held: {} if moved
                              is None else {"moved_rows": moved[layer]})
    lines = render_report(events).splitlines()
    rows = lines[lines.index("## experts") + 3:][:2]
    assert rows[0].startswith("| 3 | 901 | 2.50 | 1.8% " + columns[1])
    assert rows[1].startswith("| 10 | 5001 | 2.50 | 10.2% " + columns[0])
    assert rows[0].endswith("| n/a |")


def test_a_layer_that_holds_nothing_has_no_moved_share():
    events = [{"kind": "counter", "name": f"moe/2/{k}", "value": 0.0,
               "step": 0, "t": 0.0}
              for k in ("held_assignments", "moved_rows")]
    assert "| 2 | 0 | nan | nan% | n/a | n/a |" in render_report(events)


def test_telemetry_report_cli(tm_sandbox, tmp_path):
    import subprocess

    tm = telemetry.configure(logdir=str(tmp_path), enabled=True,
                             sinks=["jsonl"], flush_every_n_steps=0)
    with tm.span("ckpt", step=1):
        pass
    tm.shutdown()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(root, "scripts",
                                      "telemetry_report.py"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "ckpt" in r.stdout


def _tiny_trainer(logdir):
    """Smallest real BaseTrainer loop (two Dense-net step programs):
    fast to compile, exercises the full instrumented iteration surface
    including the one-time cost-analysis MFU registration."""
    import jax.numpy as jnp
    from flax import linen as nn

    from imaginaire_tpu.config import Config
    from imaginaire_tpu.trainers.base import BaseTrainer

    class TinyG(nn.Module):
        @nn.compact
        def __call__(self, data, training=False):
            return {"fake_images": nn.Dense(3)(data["images"])}

    class TinyD(nn.Module):
        @nn.compact
        def __call__(self, data, net_G_output, training=False):
            dense = nn.Dense(1)
            return {"real_outputs": [dense(data["images"])],
                    "fake_outputs": [dense(net_G_output["fake_images"])]}

    class TinyTrainer(BaseTrainer):
        def _init_loss(self, cfg):
            self.weights = {"l2": 1.0}

        def gen_forward(self, vars_G, vars_D, loss_params, data, rng,
                        training=True):
            out = self.net_G.apply(vars_G, data, training=training)
            return {"l2": jnp.mean(out["fake_images"] ** 2)}, {}

        def dis_forward(self, vars_G, vars_D, loss_params, data, rng,
                        training=True):
            out = self.net_G.apply(vars_G, data, training=training)
            d_out = self.net_D.apply(vars_D, data, out,
                                     training=training)
            return {"l2": jnp.mean(d_out["real_outputs"][0] ** 2)
                    + jnp.mean(d_out["fake_outputs"][0] ** 2)}, {}

    cfg = Config()
    cfg.logdir = logdir
    return TinyTrainer(cfg, net_G=TinyG(), net_D=TinyD())


def test_trainer_step_emits_spans_counters_and_mfu(tm_sandbox, tmp_path):
    """End-to-end: a real BaseTrainer loop emits data_wait/dis_step/
    gen_step spans, throughput counters, and the cost-analysis MFU."""
    import jax
    import numpy as np

    trainer = _tiny_trainer(str(tmp_path))
    rng = np.random.RandomState(0)
    batch = {"images": rng.rand(2, 8, 3).astype(np.float32)}

    tm = telemetry.configure(logdir=str(tmp_path), enabled=True,
                             sinks=["jsonl"], flush_every_n_steps=2)
    trainer.init_state(jax.random.PRNGKey(0), batch)
    for i, data in enumerate(tm.timed_iter([batch] * 3, "data_wait")):
        data = trainer.start_of_iteration(data, i)
        trainer.dis_update(data)
        trainer.gen_update(data)
        trainer.end_of_iteration(data, 0, i + 1)
    # the feed wait alone is `data_wait`: once for each batch and once
    # for the end of the feed, not a second time in start_of_iteration
    phases = tm.window_summary()["phases"]
    assert phases["data_wait"]["count"] == 4
    assert phases["start_of_iteration"]["count"] == 3
    tm.shutdown()

    events = _read_jsonl(str(tmp_path / "telemetry.jsonl"))
    names = {e["name"] for e in events if e["kind"] == "span"}
    # no cost_analysis span anymore: the compile ledger (xla_obs)
    # records FLOPs from the same compile that runs the step
    assert {"data_wait", "dis_step", "gen_step"} <= names
    counters = {e["name"] for e in events if e["kind"] == "counter"}
    assert "perf/imgs_per_sec" in counters
    # the CPU has no row in the peak table: no peak is assumed for it,
    # so no MFU is computed, and the step_flops meta says why
    assert "perf/mfu" not in counters
    assert any(c.startswith("xla/compile/gen_step/") for c in counters)
    spans = [e for e in events if e["kind"] == "span"
             and e["name"] == "gen_step"]
    assert len(spans) == 3
    meta = next(e for e in events if e["kind"] == "meta"
                and e["name"] == "step_flops")
    assert meta["flops"] > 0
    assert meta["peak_flops"] is None
    assert "device_kind=cpu" in meta["peak_source"]


class _ArrayDataset:
    def __len__(self):
        return 8

    def __getitem__(self, idx):
        rng = np.random.RandomState(idx)
        return {"images": rng.rand(8, 3).astype(np.float32)}


@pytest.fixture(scope="module")
def fed_loop_spans(tmp_path_factory):
    """The span events of `train.py`'s loop body at a tiny size: a
    worker-threaded loader behind the trainer's device prefetcher, three
    iterations. One run for all the cases below."""
    import jax

    from imaginaire_tpu.data.loader import DataLoader

    old = tcore._TELEMETRY
    sink = CaptureSink()
    logdir = str(tmp_path_factory.mktemp("fed_loop"))
    try:
        tm = telemetry.configure(logdir=logdir, enabled=True, sinks=[sink],
                                 flush_every_n_steps=0)
        trainer = _tiny_trainer(logdir)
        dataset = _ArrayDataset()
        loader = DataLoader(dataset, 2, shuffle=False, num_workers=2)
        trainer.init_state(jax.random.PRNGKey(0),
                           loader._collate([dataset[0], dataset[1]]))
        feed = trainer.data_prefetcher(loader, iteration_of=lambda i: i)
        iteration = 0
        for data in tm.timed_iter(feed, "data_wait", step_of=lambda i: i):
            data = trainer.start_of_iteration(data, iteration)
            trainer.dis_update(data)
            trainer.gen_update(data)
            iteration += 1
            trainer.end_of_iteration(data, 0, iteration)
        trainer.diag.drain(trainer)
        tm.flush()
    finally:
        tcore._TELEMETRY.shutdown()
        tcore._TELEMETRY = old
    assert iteration == 4
    return {"loop_thread": threading.current_thread().name,
            "spans": sink.of_kind("span")}


@pytest.mark.parametrize("name,thread,count", [
    ("init_state", "loop", 1),
    ("health_poll", "loop", 8),        # after each program but the first,
                                       # and the drain
    ("end_of_iteration", "loop", 4),
    ("prefetch_put", "device-prefetch", 4),
    ("loader_fetch", "loader-worker", 8),
    ("loader_collate", "loader-producer", 4),
])
def test_layer_boundary_spans_of_a_fed_loop(fed_loop_spans, name, thread,
                                            count):
    spans = [e for e in fed_loop_spans["spans"] if e["name"] == name]
    assert len(spans) == count
    if thread == "loop":
        thread = fed_loop_spans["loop_thread"]
    assert all(e["thread"].startswith(thread) for e in spans)
    # each opens at a layer boundary, under no other span
    assert all(e["parent"] is None for e in spans)


def test_one_iterations_loop_spans_share_its_step(fed_loop_spans):
    by_step = {}
    for e in fed_loop_spans["spans"]:
        if e["thread"] == fed_loop_spans["loop_thread"] \
                and e["name"] != "init_state":
            by_step.setdefault(e["step"], []).append(e["name"])
    assert by_step[1] == ["data_wait", "start_of_iteration", "dis_step",
                          "health_poll", "gen_step", "health_poll",
                          "end_of_iteration"]


def test_prefetch_transfer_ends_when_the_batch_is_on_the_device(
        tm_sandbox, monkeypatch):
    import jax

    from imaginaire_tpu.data.device_prefetch import DevicePrefetcher

    ready_at = []
    wait_for = jax.block_until_ready

    def slow_ready(tree):
        time.sleep(0.03)
        out = wait_for(tree)
        ready_at.append(time.time())
        return out

    monkeypatch.setattr(jax, "block_until_ready", slow_ready)
    sink = CaptureSink()
    tm = telemetry.configure(enabled=True, sinks=[sink],
                             flush_every_n_steps=0)
    batches = [{"images": np.ones((2, 4), np.float32), "key": ["a", "b"]}]
    out = list(DevicePrefetcher(batches, depth=1))
    tm.flush()
    assert isinstance(out[0]["images"], jax.Array)
    span, = [e for e in sink.of_kind("span")
             if e["name"] == "prefetch_transfer"]
    assert len(ready_at) == 1
    assert span["t"] + span["dur_ms"] / 1e3 >= ready_at[0] - 1e-3
    assert span["dur_ms"] >= 30.0


@pytest.mark.parametrize("kind,peak", [("TPU v5 lite", 197e12),
                                       ("TPU v5e", 197e12),
                                       ("TPU v4", 275e12),
                                       ("cpu", None),
                                       ("TPU v9x", None)])
def test_peak_flops_come_from_the_device_kinds_own_row(monkeypatch, kind,
                                                       peak):
    import jax
    from types import SimpleNamespace

    monkeypatch.setattr(jax, "devices",
                        lambda *a: [SimpleNamespace(device_kind=kind)])
    got, source = telemetry.resolve_peak_flops()
    assert got == peak
    assert kind in source
    if peak is None:
        assert "not computed" in source
    # an explicit override still wins, on any device
    assert telemetry.resolve_peak_flops(3e12)[0] == 3e12


def test_span_overhead_stays_negligible(tm_sandbox):
    """The per-span cost (enabled, buffering) must stay micro-scale —
    the <1% step-overhead acceptance budget at ms-scale steps."""
    tm = telemetry.configure(enabled=True, sinks=[],
                             flush_every_n_steps=0, ring_size=64)
    n = 2000
    t0 = time.perf_counter()
    for i in range(n):
        with tm.span("gen_step", step=i):
            pass
    per_span = (time.perf_counter() - t0) / n
    assert per_span < 200e-6, f"span overhead {per_span * 1e6:.1f}us"
