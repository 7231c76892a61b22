"""The token-model cell's files on the CPU (ISSUE 27 (g)): the driver end
to end at a tiny size, every new reader on a synthetic `observed` (a
number, and `None` when its input is missing), the reduction by scope on a
synthetic trace, the control, and the configuration against the catalog's
row."""

import copy
import json
import os
import types

import pytest

from bench_rehearsal_util import ROOT

from benchmark.lib import harness, scope_times

CELL = "nemotron3_nano_30b_a3b.train_packed_8k"
TINY = dict(pattern="MEM*E", hidden_size=64, vocab_size=256,
            vocab_slice=256, mamba_num_heads=8, mamba_head_dim=16,
            n_groups=2, ssm_state_size=16, chunk_size=16,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            n_routed_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=48, moe_shared_expert_intermediate_size=96,
            experts_held={"first": 0, "count": 4, "of": 8},
            expert_buffer_rows=256, seq_len=64, batch_seqs=2)
NEW_READERS = [
    "train_tokens_per_s.lm", "dispatch_ms.lm", "ssd_scan_ms.lm",
    "moe_experts_ms.lm", "moe_dispatch_ms.lm", "attn_scores_ms.lm",
    "head_loss_ms.lm", "ssd_scan_roofline.lm", "moe_experts_roofline.lm",
    "attn_scores_roofline.lm", "moe_load_max_over_mean.lm",
    "moe_held_assignments.lm"]


def tiny_cell(cache_dir):
    harness.CACHE_DIR = str(cache_dir)
    loaded = harness.load_cell(CELL)
    loaded["config"] = copy.deepcopy(loaded["config"])
    loaded["config"]["sizes"].update(TINY)
    loaded["workload"] = copy.deepcopy(loaded["workload"])
    loaded["workload"]["traffic"].update(fixture_sequences=16)
    return loaded


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    import jax

    from benchmark.drivers import train_lm

    cache = harness.CACHE_DIR
    loaded = tiny_cell(tmp_path_factory.mktemp("bench_cache"))
    peaks = harness.read_json(os.path.join(ROOT, "benchmark", "peaks.json"))
    try:
        run = train_lm.run(loaded, seed=2 ** 31 + 17, seconds=0.3,
                           trace=False, devices=jax.devices()[:1],
                           peaks=peaks, clock=harness.Clock(), shrunk=True)
    finally:
        harness.CACHE_DIR = cache
    return loaded, run


def test_a_sound_run_follows_the_reference(sound):
    loaded, run = sound
    assert set(run["metrics"]) == {"train_imgs_per_s", "setup_s"}
    c = run["compared"]
    assert set(c) == set(loaded["workload"]["limits"]) | {
        "compiles_in_window"}
    assert c["compiles_in_window"]["value"] == 0
    # bfloat16 compute against the float32 reference, at a tiny width
    assert c["loss_first_rel"]["value"] < 1e-2
    assert c["first_gradient_norm_worst_leaf"]["value"] < 0.1
    assert c["param_change_norm_worst_leaf"]["value"] < 0.2
    assert run["attempted"] >= 1 and run["failed"] == 0
    held = run["extra"]["held_assignments"]
    assert sorted(held["program"][0]) == sorted(held["reference"][0]) == [1, 4]
    json.dumps(run["extra"])    # the result line takes it


def test_the_seam_refuses_a_yaml_whose_sizes_differ():
    from benchmark.lib import lm_program

    config = copy.deepcopy(harness.load_cell(CELL)["config"])
    lm_program.load_config(config)       # the shipped YAML agrees
    config["sizes"]["moe_intermediate_size"] = 1024
    with pytest.raises(harness.BenchmarkError, match="moe_intermediate_size"):
        lm_program.load_config(config)
    config = copy.deepcopy(harness.load_cell(CELL)["config"])
    config["sizes"]["experts_held"]["count"] = 16
    with pytest.raises(harness.BenchmarkError, match="experts_held"):
        lm_program.load_config(config)


def test_configuration_holds_the_catalog_row():
    """Every number of the catalog's `config` under its own key, but for
    the three keys in `reduced`; no width among those."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    config = harness.load_cell(CELL)["config"]
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == config["source"])
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    sizes = config["sizes"]
    assert sizes["pattern"] == row["config"]["hybrid_override_pattern"][
        :config["num_hidden_layers"]]
    assert sizes["experts_held"]["count"] == config["n_routed_experts"] >= 8
    assert sizes["vocab_slice"] == config["vocab_size"] \
        >= row["config"]["vocab_size"] // 8
    for key in ("hidden_size", "mamba_num_heads", "mamba_head_dim",
                "n_groups", "ssm_state_size", "moe_intermediate_size",
                "moe_shared_expert_intermediate_size", "head_dim",
                "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "n_routed_experts", "chunk_size"):
        assert sizes[key] == row["config"][key], key

    from benchmark.reference import nemotron_h_train as reference

    assert 660e6 < reference.parameter_count(sizes) < 670e6


def test_step_flops_follow_the_issues_count():
    """359 M multiply-adds a token forward at even routing (ISSUE 27), so
    17.6 TFLOP a step; the routed share follows the assignments."""
    from benchmark.reference import nemotron_h_train as reference

    sizes = harness.load_cell(CELL)["config"]["sizes"]
    even = {k: 3072 for k in (1, 3, 6, 8)}
    flops = reference.step_flops(sizes, 1, 8192, even)
    assert flops["iteration"] == pytest.approx(17.6e12, rel=0.02)
    shares = {k: v / sum(flops["forward"].values())
              for k, v in flops["forward"].items()}
    assert shares["M"] == pytest.approx(0.45, abs=0.02)
    assert shares["E"] == pytest.approx(0.27, abs=0.02)
    more = reference.step_flops(sizes, 1, 8192, {k: 6144 for k in even})
    assert more["iteration"] - flops["iteration"] == pytest.approx(
        3 * 4 * 2 * 2 * 3072 * 2688 * 1856)
    for work in (reference.scan_work(sizes, 1, 8192),
                 reference.attn_work(sizes, 1, 8192),
                 reference.expert_work(sizes, 3072)):
        assert work[0] > 0 and work[1] > 0


# ------------------------------------------------------------- the readers


def _observed():
    seconds = {"lm/mamba2/ssd_scan": 0.060, "lm/moe/experts": 0.010,
               "lm/moe/dispatch": 0.004, "lm/moe/combine": 0.003,
               "lm/attn/scores": 0.020, "lm/head_loss": 0.015}
    return {"tokens": 80 * 8192, "images": 80, "window_s": 20.0, "chips": 1,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "scopes": {"steps": 14, "seconds": seconds},
            "work": {"ssd_scan": [1.0e12, 8.19e9],
                     "moe_experts": [0.394e12, 1e9],
                     "attn_scores": [1.97e12, 1e9]},
            "held_assignments": {1: 3000.0, 3: 3100.0, 6: 2900.0, 8: 3200.0},
            "load_max_over_mean": [1.5, 2.0, 2.5]}


def _read(name, observed):
    reader = harness.load_by_path(
        os.path.join(ROOT, "benchmark", "metrics", name + ".py"),
        "lm_metric_" + name.replace(".", "_"))
    return reader.read(observed)


def test_every_new_reader_is_declared_for_the_cell_alone():
    spec = harness.load_spec()
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW_READERS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_imgs_per_s"
    listed = {m["name"] for m in harness.metrics_of(spec, CELL, "per_layer")}
    assert "dis_step_ms" not in listed and "dispatch_ms.train" not in listed
    assert {"mfu.train", "gen_step_ms", "device_idle.train",
            "hbm_peak_gb.train"} <= listed


def test_pr24_span_entries_are_still_whole():
    """What `test_bench_program_spans.py::
    test_the_ten_entries_are_appended_and_whole` holds beyond PR 24 (it
    asserts that the ten are the list's LAST entries and name one cell,
    which no later append can keep; `tests/conftest.py` says so): the ten
    stand together and in order, whole, each with its reader, and SPADE's
    cell still reads all of them after the six it had."""
    import test_bench_program_spans as pr24

    spec = harness.load_spec()
    names = [m["name"] for m in spec["per_layer"]]
    first = names.index(pr24.NEW[0])
    entries = spec["per_layer"][first:first + len(pr24.NEW)]
    assert [m["name"] for m in entries] == pr24.NEW
    layers = {m["layer"] for m in spec["per_layer"][:first]}
    for m in entries:
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["workloads"][0] == pr24.CELL
        assert m["layer"] in layers | {"set-up"}
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                           m["name"] + ".py"))
    assert {m["moves"] for m in entries} == {"train_imgs_per_s", "setup_s"}
    traced = [m["name"] for m in harness.metrics_of(spec, pr24.CELL,
                                                    "per_layer")]
    assert traced[-len(pr24.NEW):] == pr24.NEW and len(traced) == 16


@pytest.mark.parametrize("name,value", [
    ("train_tokens_per_s.lm", 32768.0), ("ssd_scan_ms.lm", 60.0),
    ("moe_experts_ms.lm", 10.0), ("moe_dispatch_ms.lm", 7.0),
    ("attn_scores_ms.lm", 20.0), ("head_loss_ms.lm", 15.0),
    # 10 ms of bytes against 60: memory-bound; 2 ms of operations against
    # 10; 10 ms of operations against 20
    ("ssd_scan_roofline.lm", 100.0 / 6), ("moe_experts_roofline.lm", 20.0),
    ("attn_scores_roofline.lm", 50.0),
    ("moe_load_max_over_mean.lm", 2.0), ("moe_held_assignments.lm", 12200.0)])
def test_reader_gives_its_number_or_nothing(name, value):
    assert _read(name, _observed()) == pytest.approx(value)
    assert _read(name, {}) is None
    # a parent without the scopes: the traced run's line leaves it out
    assert _read(name, dict(_observed(), scopes=None, tokens=None,
                            held_assignments=None,
                            load_max_over_mean=None)) is None


def test_dispatch_reader_takes_the_gen_step_span(monkeypatch):
    from benchmark.lib import program_spans

    monkeypatch.setattr(program_spans, "phase_table",
                        lambda: {"gen_step": {"p50_ms": 4.5, "count": 80}})
    assert _read("dispatch_ms.lm", {}) == 4.5
    monkeypatch.setattr(program_spans, "phase_table", lambda: {})
    assert _read("dispatch_ms.lm", {}) is None


# ------------------------------------------------------- the scopes' times

HLO = """
HloModule jit__gen_step_fn
  %fusion.7 = bf16[8192,64]{1,0} fusion(%p0), kind=kLoop, metadata={op_name="jit(_gen_step_fn)/jvp(Generator)/layer_0/mixer/lm/mamba2/ssd_scan/fp32_island[ssm_scan]/mul"}
  %while.3 = (s32[], f32[64]) while(%t), metadata={op_name="jit(_gen_step_fn)/transpose(jvp(Generator))/layer_0/mixer/lm/mamba2/ssd_scan/while"}
  ROOT %custom-call.2 = bf16[16384,1856]{1,0} custom-call(%a, %b), metadata={op_name="jit(_gen_step_fn)/jvp(Generator)/checkpoint/layer_1/mixer/lm/moe/experts/ragged_dot"}
  %copy.9 = f32[8]{0} copy(%c), metadata={op_name="jit(_gen_step_fn)/adam/mul"}
"""


def _event(name, start, duration, stats=()):
    return types.SimpleNamespace(name=name, start_ns=start,
                                 duration_ns=duration, stats=list(stats))


def _profile(events, steps=2):
    ops = types.SimpleNamespace(name="XLA Ops", events=events)
    modules = types.SimpleNamespace(name="XLA Modules", events=[
        _event(f"jit__gen_step_fn({i})", 0, 10) for i in range(steps)])
    plane = types.SimpleNamespace(name="/device:TPU:0", lines=[ops, modules])
    host = types.SimpleNamespace(name="/host:CPU", lines=[])
    return types.SimpleNamespace(planes=[plane, host])


def test_scopes_by_the_hlo_text():
    assert scope_times.instruction_scopes(HLO) == {
        "fusion.7": "lm/mamba2/ssd_scan", "while.3": "lm/mamba2/ssd_scan",
        "custom-call.2": "lm/moe/experts"}
    events = [
        _event("%fusion.7 = bf16[8192,64]{1,0} fusion(%p0)", 0, 4_000_000),
        # a loop and an operation of its body: counted once
        _event("%while.3 = (s32[], f32[64]) while(%t)", 5_000_000,
               6_000_000),
        _event("%fusion.7 = bf16[8192,64]{1,0} fusion(%p0)", 6_000_000,
               1_000_000),
        _event("%custom-call.2 = bf16[16384,1856]{1,0} custom-call(%a)",
               12_000_000, 2_000_000),
        _event("%copy.9 = f32[8]{0} copy(%c)", 15_000_000, 1_000_000)]
    reduced = scope_times.reduce(_profile(events), HLO)
    assert reduced["steps"] == 2
    assert reduced["seconds"] == {
        "lm/mamba2/ssd_scan": pytest.approx(0.005),
        "lm/moe/experts": pytest.approx(0.001)}
    assert reduced["matched_s"] == pytest.approx(0.012)
    assert reduced["busy_s"] == pytest.approx(0.013)
    observed = {"scopes": reduced}
    assert scope_times.under(observed, "lm/mamba2/") == pytest.approx(5.0)
    assert scope_times.under(observed, "lm/attn/scores") is None


def test_scopes_by_the_events_own_stats_and_none_without_either():
    stat = ("tf_op", "jit(_gen_step_fn)/jvp(Generator)/layer_5/mixer/"
                     "lm/attn/scores/checkpoint/dot_general")
    events = [_event("%fusion.1 = f32[2]{0} fusion(%x)", 0, 2_000_000,
                     [stat])]
    reduced = scope_times.reduce(_profile(events, steps=1), None)
    assert reduced["seconds"] == {"lm/attn/scores": pytest.approx(0.002)}
    bare = scope_times.reduce(
        _profile([_event("%fusion.1 = f32[2]{0} fusion(%x)", 0, 10)]), None)
    assert bare["seconds"] == {}
    assert scope_times.reduce(_profile([]), HLO) is None
    assert scope_times.reduce(_profile(events, steps=0), HLO) is None
    assert scope_times.scope_of("jit(f)/lm/moe/router/top_k") \
        == "lm/moe/router"
    assert scope_times.scope_of("jit(f)/adam/mul") is None


# -------------------------------------------------------------- the control


def test_control_in_float8_products_is_told_from_float32():
    """The reference with float8 products, put in the program's place,
    reads further from float32 than bfloat16 products do, on the gradients'
    distance (the limits themselves are set from chip readings)."""
    import numpy as np

    from benchmark.drivers import train_lm
    from benchmark.lib import lm_weights
    from benchmark.reference import nemotron_h_train as reference

    sizes = dict(harness.load_cell(CELL)["config"]["sizes"], **TINY)
    rng = np.random.default_rng(5)
    batches = [rng.integers(0, 256, (2, 64)).astype(np.int32)
               for _ in range(2)]
    runs = {precision: train_lm.reference_steps(
        reference, lm_weights.make(reference.spec(sizes), 5), sizes,
        batches, precision, 0.002)
        for precision in ("float32", "bfloat16", "float8")}
    apart = {p: train_lm.compare(runs[p], runs["float32"])[0][
        "first_gradient_apart_median_leaf"] for p in ("bfloat16", "float8")}
    assert apart["float8"] > 4 * apart["bfloat16"] > 0
