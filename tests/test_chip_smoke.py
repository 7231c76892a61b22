"""CPU rehearsal of chip_smoke.py: the same train-then-serve flow and the
same four-device comparison, imported as functions and driven at
``configs/unit_test/spade.yaml`` width on the virtual CPU mesh. The device
check is stubbed here; the script itself has no CPU mode."""

import importlib
import json
import math
import os
import signal

import jax
import pytest

import chip_smoke
from imaginaire_tpu.parallel import mesh as mesh_mod
from imaginaire_tpu.telemetry import core as tcore
from imaginaire_tpu.telemetry import xla_obs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
UNIT_CONFIG = os.path.join(ROOT, "configs", "unit_test", "spade.yaml")


@pytest.fixture
def entry_point_sandbox():
    """train.main()/inference.main() install process-wide state (the
    telemetry singleton, the compile ledger, the mesh, signal handlers);
    put back what later tests of this worker expect."""
    old_tm, old_mesh = tcore._TELEMETRY, mesh_mod._GLOBAL_MESH
    handlers = {s: signal.getsignal(s)
                for s in (signal.SIGTERM, signal.SIGINT)}
    xla_obs._reset_for_tests()
    yield
    tcore._TELEMETRY.shutdown()
    tcore._TELEMETRY = old_tm
    mesh_mod._GLOBAL_MESH = old_mesh
    for s, h in handlers.items():
        signal.signal(s, h)
    xla_obs._reset_for_tests()


@pytest.fixture
def at_unit_width(monkeypatch):
    """What the test stubs: the device check (CPU devices stand in for
    chips), the device-evidence check (recorded, and shown below to fail
    on a CPU run), and the width."""
    seen = {}
    monkeypatch.setattr(chip_smoke, "ZOO_CONFIG", UNIT_CONFIG)
    monkeypatch.setattr(chip_smoke, "DP_GLOBAL_BATCH", 4)
    monkeypatch.setattr(chip_smoke, "require_tpu",
                        lambda n: jax.devices()[:max(n, 1)])
    monkeypatch.setattr(
        chip_smoke, "check_device_evidence",
        lambda logdir, kind: seen.setdefault("evidence", (logdir, kind)))
    return seen


def test_no_tpu_exits_nonzero_and_prints_no_result(capsys, tmp_path):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main(["--out", str(tmp_path)])
    assert "needs a TPU" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out
    assert os.listdir(tmp_path) == []


def test_train_then_serve_flow(entry_point_sandbox, at_unit_width,
                               tmp_path, capsys):
    out = str(tmp_path / "out")
    chip_smoke.main(["--out", out, "--seed", "0"])
    lines = capsys.readouterr().out.strip().splitlines()
    dev = jax.devices()[0]
    # the exact shape of the last line
    assert lines[-1] == ('{"ok": true, "device": {"platform": "%s", '
                         '"kind": "%s", "count": 1}}'
                         % (dev.platform, dev.device_kind))
    logdir = os.path.join(out, "run")
    assert at_unit_width["evidence"] == (logdir, dev.device_kind)
    # the hand-over: inference restored what train wrote, verified
    with open(os.path.join(logdir, "latest_checkpoint.txt")) as f:
        name = f.read().strip()
    assert name == "epoch_00000_iteration_000000006_checkpoint"
    verified = [e for e in chip_smoke._events(logdir)
                if e.get("name") == "ckpt/verified"]
    assert verified and verified[-1]["verified"]
    assert os.path.basename(verified[-1]["checkpoint"]) == name
    assert not any(".corrupt" in n for n in os.listdir(logdir))
    # finite losses for each of the six iterations, no counted recompile
    # (the checks ran on the train telemetry before the serve phase
    # replayed the ledger into the same logdir)
    train = next(ln for ln in lines if "] train:" in ln)
    assert "recompiles 0" in train and "'gen_step': ['first']" in train
    losses = [ln.split("]")[1].split() for ln in lines
              if "]   iteration" in ln]
    assert [int(t[1].rstrip(":")) for t in losses] == [1, 2, 3, 4, 5, 6]
    assert all(math.isfinite(float(t[3])) and math.isfinite(float(t[5]))
               for t in losses)
    served = [ln for ln in lines if "] serve:" in ln]
    assert served and int(served[0].split("serve: ")[1].split()[0]) >= 8
    # a mesh of exactly one device, though eight are visible
    assert [ln for ln in lines if "x1, compile cache" in ln]
    # the real device-evidence check refuses this run: the CPU has no
    # row in the peak table and no memory_stats()
    real = importlib.reload(chip_smoke).check_device_evidence
    with pytest.raises(SystemExit) as exc:
        real(logdir, dev.device_kind)
    assert "no perf/mfu" in str(exc.value.code)


def test_four_device_comparison(entry_point_sandbox, at_unit_width,
                                monkeypatch, tmp_path, capsys):
    # the zoo file's norms at unit width: sync-batch statistics are what
    # the data axis has to reduce besides the gradients
    import yaml

    with open(UNIT_CONFIG) as f:
        cfg = yaml.safe_load(f)
    cfg["gen"]["global_adaptive_norm_type"] = "sync_batch"
    cfg["gen"]["activation_norm_params"]["activation_norm_type"] = \
        "sync_batch"
    sync_cfg = str(tmp_path / "unit_sync_batch.yaml")
    with open(sync_cfg, "w") as f:
        yaml.safe_dump(cfg, f)
    monkeypatch.setattr(chip_smoke, "ZOO_CONFIG", sync_cfg)
    out = str(tmp_path / "out")
    chip_smoke.main(["--out", out, "--seed", "0", "--chips", "4"])
    lines = capsys.readouterr().out.strip().splitlines()
    dev = jax.devices()[0]
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": dev.platform,
                               "kind": dev.device_kind, "count": 4}}
    # only the comparison ran: no serve phase, two training runs
    assert not os.path.exists(os.path.join(out, "served"))
    assert not any("serve:" in ln for ln in lines)
    layout = [ln for ln in lines if "data_parallel layout:" in ln]
    assert layout and "as 4 x (1, " in layout[0]
    coll = next(ln for ln in lines if "data_parallel collectives:" in ln)
    coll = json.loads(coll.split("collectives: ")[1])
    for label in ("dis_step", "gen_step"):
        assert coll[label]["of_gradients"] > 0
        assert coll[label]["of_sync_batch_statistics"] > 0
    # gated: what the first step computes before any optimiser step —
    # the D loss, the D gradient's norms, the G loss's perceptual term
    gated = [ln.split("gated: ")[1].split()[0] for ln in lines
             if "first step, gated:" in ln]
    assert {"dis_update/total", "health/D/grad_norm/_total",
            "gen_update/Perceptual"} <= set(gated)
    assert len([ln for ln in lines if ", printed:" in ln]) == 6


def _evidence(**first_step):
    return {"first_step": first_step,
            "losses": {it: {"dis_update/total": 2.0,
                            "gen_update/total": -20.0 - it}
                       for it in range(1, chip_smoke.TRAIN_ITERS + 1)}}


def test_layout_gate_passes_on_reduction_order_noise(capsys):
    one = _evidence(**{"dis_update/total": 2.0,
                       "health/D/grad_norm/_total": 3.2174,
                       "gen_update/Perceptual": 0.046445})
    dp = _evidence(**{"dis_update/total": 2.0,
                      "health/D/grad_norm/_total": 3.2150,
                      "gen_update/Perceptual": 0.046453})
    # later losses are printed whatever they are
    dp["losses"][1]["gen_update/total"] = -23.5
    chip_smoke.compare_layouts(dp, one)
    assert "iteration 6, printed" in capsys.readouterr().out


@pytest.mark.parametrize("name, value, why", [
    # a shard of the batch seen twice, or a reduction that left one out
    ("health/D/grad_norm/_total", 3.2174 * 1.03, "grad_norm/_total"),
    ("gen_update/Perceptual", 0.046445 * 0.97, "gen_update/Perceptual"),
    ("dis_update/total", 2.05, "dis_update/total"),
    ("health/D/grad_norm/_total", float("nan"), "grad_norm/_total"),
    # logged by one layout only
    ("gen_update/Perceptual", None, "one layout only"),
])
def test_layout_gate_fails_beyond_tolerance(name, value, why):
    first = {"dis_update/total": 2.0, "health/D/grad_norm/_total": 3.2174,
             "gen_update/Perceptual": 0.046445}
    one, moved = _evidence(**first), dict(first)
    if value is None:
        del moved[name]
    else:
        moved[name] = value
    with pytest.raises(SystemExit) as exc:
        chip_smoke.compare_layouts(_evidence(**moved), one)
    assert why in str(exc.value.code)


def test_layout_gate_needs_the_gradient_norms():
    only_loss = _evidence(**{"dis_update/total": 2.0})
    with pytest.raises(SystemExit) as exc:
        chip_smoke.compare_layouts(only_loss, only_loss)
    assert "no D gradient norms" in str(exc.value.code)


def test_device_evidence_needs_the_devices_own_row(tmp_path):
    def write(events):
        with open(tmp_path / "telemetry.jsonl", "w") as f:
            for e in events:
                f.write(json.dumps(e) + "\n")

    flops = {"kind": "meta", "name": "step_flops", "flops": 1e12,
             "peak_flops": 197e12,
             "peak_source": "device_kind:TPU v5 lite (docs)"}
    mfu = {"kind": "counter", "name": "perf/mfu", "value": 0.3, "step": 6}
    mem = {"kind": "counter", "name": "mem/TPU_0/peak_bytes_in_use",
           "value": 1 << 30, "step": 6}
    write([flops, mfu, mem])
    got = chip_smoke.check_device_evidence(str(tmp_path), "TPU v5 lite")
    assert got["peak_flops"] == 197e12
    for events, why in (([flops, mfu], "no mem/"),
                        ([flops, mem], "no perf/mfu"),
                        ([dict(flops, peak_source="config:telemetry"
                                                  ".peak_flops"),
                          mfu, mem], "own row")):
        write(events)
        with pytest.raises(SystemExit) as exc:
            chip_smoke.check_device_evidence(str(tmp_path), "TPU v5 lite")
        assert why in str(exc.value.code)


def test_first_step_evidence_stops_at_the_first_optimiser_step():
    def counter(name, step, value):
        return {"kind": "counter", "name": name, "step": step,
                "value": value}

    got = chip_smoke.first_step_evidence([
        counter("health/D/grad_norm/_total", 0, 3.2),
        counter("health/D/grad_norm/fpse", 0, 0.35),
        counter("health/D/grad_norm/_total", 100, 9.9),  # a later audit
        counter("health/G/grad_norm/_total", 0, 14600.0),  # through the new D
        counter("health/D/update_ratio/_total", 0, 0.8),
        counter("dis_update/total", 1, 2.0),
        counter("dis_update/total", 2, 2.5),
        counter("gen_update/Perceptual", 1, 0.046),
        counter("gen_update/GAN", 1, -21.4),  # through the new D
        counter("gen_update/total", 1, -9.4),
        {"kind": "meta", "name": "dis_update/total", "step": 1},
    ])
    assert got == {"health/D/grad_norm/_total": 3.2,
                   "health/D/grad_norm/fpse": 0.35,
                   "dis_update/total": 2.0,
                   "gen_update/Perceptual": 0.046}
