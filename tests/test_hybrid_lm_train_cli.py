"""`train.py` on the tiny token-model preset (ISSUE 27 (e)): two
iterations, a checkpoint and a resume, through `train.main()` itself in
this process; the spans and the new counters in the jsonl and in the
report, no recompile, a clean graph audit."""

import json
import os
import signal
import sys

import pytest

from hybrid_lm_util import (DELTA_YAML, EARLY_YAML, LATENT_YAML, ROOT,
                            SCONV_YAML, TINY_YAML)

from imaginaire_tpu.parallel import mesh as mesh_mod
from imaginaire_tpu.telemetry import core as tcore
from imaginaire_tpu.telemetry import xla_obs


@pytest.fixture
def entry_point_sandbox():
    """train.main() installs process-wide state (the telemetry
    singleton, the compile ledger, the mesh, signal handlers); put back
    what later tests of this worker expect."""
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


def _train(monkeypatch, logdir, max_iter, config=TINY_YAML):
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import train

    monkeypatch.setattr(sys, "argv", [
        "train.py", "--config", config, "--logdir", logdir,
        "--max_iter", str(max_iter), "--seed", "0"])
    return train.main()


def _events(logdir):
    with open(os.path.join(logdir, "telemetry.jsonl")) as f:
        return [json.loads(line) for line in f if line.strip()]


def test_train_py_trains_checkpoints_and_resumes(entry_point_sandbox,
                                                 monkeypatch, tmp_path,
                                                 capsys):
    logdir = str(tmp_path / "log")
    trainer = _train(monkeypatch, logdir, 2)
    assert trainer.current_iteration == 2
    assert os.path.exists(os.path.join(logdir, "latest_checkpoint.txt"))
    events = _events(logdir)
    spans = {e["name"] for e in events if e["kind"] == "span"}
    assert {"data_wait", "gen_step", "health_poll", "init_state",
            "start_of_iteration", "end_of_iteration", "prefetch_host",
            "prefetch_transfer", "prefetch_put"} <= spans
    assert "dis_step" not in spans
    counters = {e["name"]: e["value"] for e in events
                if e["kind"] == "counter"}
    assert counters["perf/tokens_per_sec"] > 0
    assert counters["perf/imgs_per_sec"] > 0
    for layer in (1, 4):
        assert counters[f"moe/{layer}/held_assignments"] > 0
        assert counters[f"moe/{layer}/load_max_over_mean"] >= 1
        assert 0 < counters[f"moe/{layer}/buffer_occupancy"] <= 1
        assert 0 <= counters[f"moe/{layer}/compact"] <= 1
        # a tier of the unit-test sizes is one segment (ISSUE 40)
        assert counters[f"moe/{layer}/moved_rows"] == (
            128 if counters[f"moe/{layer}/compact"] else 256)
    assert counters["xla/recompiles"] == 0
    assert counters["xla/graph_violations"] == 0
    assert "expand_labels" not in {
        e.get("label") for e in events if e["kind"] == "meta"}
    metas = {e["name"] for e in events if e["kind"] == "meta"}
    assert "step_flops" in metas and "xla_compile/gen_step" in metas
    assert "kda_impl" not in metas      # no delta-rule layer, no meta
    # the two Mamba-2 layers of 'MEM*E', on the CPU: the ``chunks`` arm,
    # which has no kernel whose residuals a block could keep (ISSUE 46)
    ssd = [e for e in events
           if e["kind"] == "meta" and e["name"] == "ssd_impl"]
    assert len(ssd) == 1
    assert ssd[0]["layers"] == [0, 2]
    assert ssd[0]["arm"] == {"0": "chunks", "2": "chunks"}
    assert ssd[0]["kept_bytes"] == {"0": 0, "2": 0}
    assert (ssd[0]["heads"], ssd[0]["head_dim"], ssd[0]["groups"],
            ssd[0]["state"], ssd[0]["chunk"]) == (8, 16, 2, 16, 16)
    # the one attention layer of 'MEM*E', on the CPU: the plain arm
    attn = [e for e in events
            if e["kind"] == "meta" and e["name"] == "attn_impl"]
    assert len(attn) == 1
    assert attn[0]["layers"] == {"3": "blocks"} and attn[0]["length"] == 64
    assert attn[0]["head_dim"] == 16
    assert set(attn[0]["tiles"]) == {"fwd", "bwd"}
    # the plain arm has no kernel whose residuals a block could keep
    assert attn[0]["kept_bytes"] == {"3": 0}
    # the two expert layers' grouped products, on the CPU: the plain arm
    # (ISSUE 38), on the tiers a step of 128 tokens picks from
    moe = [e for e in events
           if e["kind"] == "meta" and e["name"] == "moe_impl"]
    assert len(moe) == 1
    assert moe[0]["layers"] == {"1": "ragged_dot", "4": "ragged_dot"}
    assert (moe[0]["hidden"], moe[0]["width"], moe[0]["held"]) == (64, 48, 4)
    assert moe[0]["tiers"] == [128, 256]
    assert set(moe[0]["tiles"]) == {"up", "down"}

    from imaginaire_tpu.telemetry.report import render_report

    report = render_report(os.path.join(logdir, "telemetry.jsonl"))
    assert "## experts" in report and "perf/tokens_per_sec" in report
    assert ("- ssd_impl: layers 0, 2; 8 heads of 16 in 2 groups, state 16, "
            "chunks of 16 steps; layer 0 chunks, layer 2 chunks; ") in report
    assert "| moved over held |" in report
    assert "gen_step: 0 violation(s)" in report
    assert ("attn_impl at length 64, head size 16: layer 3 blocks; fused "
            "tiles") in report
    assert "; the blocks keep 0 bytes of the kernel's forward" in report
    assert ("- moe_impl: layer 1 ragged_dot, layer 4 ragged_dot; 4 held "
            "experts of 64 x 48 on tiers of 128, 256 rows; kernel tiles "
            "(rows x width) up fwd 128x128, dlhs 128x128; down fwd") in report
    assert ("; routers read layer 1 own_norm, layer 4 own_norm, scored by "
            "sigmoid; experts relu2, a buffer of 256 rows") in report

    # the resume leg: restores iteration 2 and trains on to 3
    capsys.readouterr()
    xla_obs._reset_for_tests()
    resumed = _train(monkeypatch, logdir, 3)
    out = capsys.readouterr().out
    assert "Done with loading the checkpoint (resume=True)" in out
    assert "Done with training!!!" in out
    assert resumed.current_iteration == 3
    steps = [e["step"] for e in _events(logdir)
             if e["kind"] == "span" and e["name"] == "gen_step"]
    assert steps == [0, 1, 2]


def test_train_py_trains_the_latent_attention_preset(entry_point_sandbox,
                                                     monkeypatch, tmp_path):
    """ISSUE 31: `configs/unit_test/glm4_moe_lite.yaml` through
    `train.main()`: both losses reported, the module's expert layer under
    its own index, every attention layer (the module's too) in the
    `attn_impl` meta, no recompile, a clean graph audit (the rotary turn
    is a float32 island)."""
    logdir = str(tmp_path / "log")
    trainer = _train(monkeypatch, logdir, 2, config=LATENT_YAML)
    assert trainer.current_iteration == 2
    assert trainer.weights == {"lm": 1.0, "mtp": 0.3}
    events = _events(logdir)
    counters = {e["name"]: e["value"] for e in events
                if e["kind"] == "counter"}
    assert counters["lm/main"] > 0 and counters["lm/mtp"] > 0
    assert counters["perf/tokens_per_sec"] > 0
    for layer in (3, 5, 7):
        assert counters[f"moe/{layer}/held_assignments"] > 0
    assert counters["xla/recompiles"] == 0
    assert counters["xla/graph_violations"] == 0
    attn = [e for e in events
            if e["kind"] == "meta" and e["name"] == "attn_impl"]
    assert len(attn) == 1 and attn[0]["head_dim"] == 32
    assert attn[0]["layers"] == {str(i): "blocks" for i in (0, 2, 4, 6)}
    assert attn[0]["kept_bytes"] == {str(i): 0 for i in (0, 2, 4, 6)}

    from imaginaire_tpu.telemetry.report import render_report

    report = render_report(os.path.join(logdir, "telemetry.jsonl"))
    assert "| 7 |" in report.split("## experts")[1]
    assert "- lm/main: " in report and "- lm/mtp: " in report
    assert "head size 32: layer 0 blocks, layer 2 blocks" in report


def test_train_py_trains_the_delta_rule_preset(entry_point_sandbox,
                                               monkeypatch, tmp_path):
    """ISSUE 34: `configs/unit_test/solar_open2.yaml` through
    `train.main()`: one loss, the three expert layers' counters, the one
    softmax layer in the `attn_impl` meta and the two delta-rule layers
    in a `kda_impl` meta, no recompile, a clean graph audit (the delta
    rule is a float32 island: nothing inside it is cast down)."""
    logdir = str(tmp_path / "log")
    trainer = _train(monkeypatch, logdir, 2, config=DELTA_YAML)
    assert trainer.current_iteration == 2
    assert trainer.weights == {"lm": 1.0}
    events = _events(logdir)
    counters = {e["name"]: e["value"] for e in events
                if e["kind"] == "counter"}
    assert counters["lm/main"] > 0 and "lm/mtp" not in counters
    assert counters["perf/tokens_per_sec"] > 0
    for layer in (1, 3, 5):
        assert counters[f"moe/{layer}/held_assignments"] > 0
    assert counters["xla/recompiles"] == 0
    assert counters["xla/graph_violations"] == 0
    metas = {e["name"]: e for e in events if e["kind"] == "meta"}
    assert metas["attn_impl"]["layers"] == {"0": "blocks"}
    assert metas["attn_impl"]["head_dim"] == 16
    kda = metas["kda_impl"]
    assert (kda["layers"], kda["heads"], kda["head_dim"], kda["chunk"],
            kda["sub_block"]) == ([2, 4], 4, 16, 16, 16)
    assert sum(1 for e in events if e["kind"] == "meta"
               and e["name"] == "kda_impl") == 1

    from imaginaire_tpu.telemetry.report import render_report

    report = render_report(os.path.join(logdir, "telemetry.jsonl"))
    assert "head size 16: layer 0 blocks" in report
    assert ("- kda_impl: layers 2, 4; 4 heads of 16 held; chunks of 16 "
            "steps in sub-blocks of 16, 8 at once") in report
    assert "| 5 |" in report.split("## experts")[1]


def test_train_py_trains_the_short_convolution_preset(entry_point_sandbox,
                                                      monkeypatch, tmp_path):
    """ISSUE 39: `configs/unit_test/lfm2_moe.yaml` through `train.main()`
    at its batch of 2: one loss, the two expert layers' counters (no
    shared expert), the one rotary attention layer with normed queries and
    keys in the `attn_impl` meta with the head size the kernel would run
    it at, no `kda_impl`, no recompile, a clean graph audit (the head
    norm's statistics and the turn are float32 islands), and every new
    scope named by an instruction of the compiled step."""
    logdir = str(tmp_path / "log")
    trainer = _train(monkeypatch, logdir, 2, config=SCONV_YAML)
    assert trainer.current_iteration == 2
    assert trainer.weights == {"lm": 1.0}
    events = _events(logdir)
    counters = {e["name"]: e["value"] for e in events
                if e["kind"] == "counter"}
    assert counters["lm/main"] > 0 and "lm/mtp" not in counters
    assert counters["perf/tokens_per_sec"] > 0
    for layer in (3, 5):
        assert counters[f"moe/{layer}/held_assignments"] > 0
        assert 0 < counters[f"moe/{layer}/buffer_occupancy"] <= 1
    assert counters["xla/recompiles"] == 0
    assert counters["xla/graph_violations"] == 0
    metas = {e["name"]: e for e in events if e["kind"] == "meta"}
    assert "kda_impl" not in metas and "ssd_impl" not in metas
    attn = metas["attn_impl"]
    assert attn["layers"] == {"2": "blocks"} and attn["head_dim"] == 16
    assert attn["kernel_head_dim"] == 128 and attn["kept_bytes"] == {"2": 0}
    moe = metas["moe_impl"]
    assert moe["layers"] == {"3": "ragged_dot", "5": "ragged_dot"}
    assert (moe["hidden"], moe["width"], moe["held"]) == (64, 48, 4)
    names = set(xla_obs.ledger().label_op_names["gen_step"].values())
    for scope in ("lm/attn/sconv_proj", "lm/attn/sconv_conv",
                  "lm/attn/qk_norm", "lm/attn/rope", "lm/attn/out",
                  "lm/mlp/dense", "lm/moe/experts"):
        assert any(scope in name for name in names), scope
    assert not any("lm/moe/shared" in name for name in names)

    from imaginaire_tpu.telemetry.report import render_report

    report = render_report(os.path.join(logdir, "telemetry.jsonl"))
    assert ("attn_impl at length 64, head size 16: layer 2 blocks; fused "
            "tiles") in report
    assert "| 5 |" in report.split("## experts")[1]


def test_train_py_trains_the_early_router_preset(entry_point_sandbox,
                                                 monkeypatch, tmp_path):
    """ISSUE 45: `configs/unit_test/smallthinker.yaml` through
    `train.main()`: one loss, the four expert layers' counters, the full
    layer and the three window layers in the `attn_impl` meta, the
    `moe_impl` meta saying what each router reads, how it scores and what
    gates the experts, the report printing it, no recompile, a clean
    graph audit (the softmax over the chosen logits is inside the
    router's float32 island), and the router's scope named by
    instructions of the compiled step."""
    logdir = str(tmp_path / "log")
    trainer = _train(monkeypatch, logdir, 2, config=EARLY_YAML)
    assert trainer.current_iteration == 2
    assert trainer.weights == {"lm": 1.0}
    # the model has no buffer: no score-correction bias
    assert set(trainer.state["vars_G"]) == {"params"}
    events = _events(logdir)
    counters = {e["name"]: e["value"] for e in events
                if e["kind"] == "counter"}
    assert counters["lm/main"] > 0 and "lm/mtp" not in counters
    for layer in (1, 3, 5, 7):
        assert counters[f"moe/{layer}/held_assignments"] > 0
        assert 0 < counters[f"moe/{layer}/buffer_occupancy"] <= 1
    assert counters["xla/recompiles"] == 0
    assert counters["xla/graph_violations"] == 0
    metas = {e["name"]: e for e in events if e["kind"] == "meta"}
    attn = metas["attn_impl"]
    assert attn["layers"] == dict.fromkeys("0246", "blocks")
    assert attn["windows"] == dict.fromkeys("246", 24)
    moe = metas["moe_impl"]
    assert moe["layers"] == dict.fromkeys("1357", "ragged_dot")
    assert moe["router_input"] == dict.fromkeys("1357", "attention_input")
    assert (moe["scoring"], moe["activation"], moe["held"],
            moe["buffer_rows"]) == ("softmax_of_chosen", "relu", 4, 256)
    names = set(xla_obs.ledger().label_op_names["gen_step"].values())
    for scope in ("lm/moe/router", "lm/attn/qkv", "lm/attn/window_scores",
                  "lm/attn/scores", "lm/attn/rope", "lm/moe/experts"):
        assert any(scope in name for name in names), scope
    assert not any("lm/moe/shared" in name for name in names)

    from imaginaire_tpu.telemetry.report import render_report

    report = render_report(os.path.join(logdir, "telemetry.jsonl"))
    assert ("; routers read layer 1 attention_input, layer 3 "
            "attention_input, layer 5 attention_input, layer 7 "
            "attention_input, scored by softmax_of_chosen; experts relu, a "
            "buffer of 256 rows") in report
    assert ("layer 0 blocks, layer 2 blocks (window 24), layer 4 blocks "
            "(window 24), layer 6 blocks (window 24)") in report
