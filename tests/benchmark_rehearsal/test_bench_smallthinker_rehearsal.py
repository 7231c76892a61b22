"""The early-router token cell's files on the CPU (ISSUE 45): the driver
end to end at a tiny size, the new reader on a synthetic `observed` (a
number, and `None` without its input), the configuration against the
catalog's row and the program's own parameter count, the reference's
work, the `moe_impl` meta, the control, and the entries of
`BENCHMARK.json` found by name: no cell's count of metrics and no entry's
position is asserted, and a list of cells is held to its beginning, so a
later cell appended to it breaks nothing here."""

import copy
import json
import math
import os

import pytest

from bench_rehearsal_util import ROOT

from benchmark.lib import harness

CELL = "smallthinker_21b_a3b.train_packed_16k"
SPADE = "spade_cocostuff_256.train_fed"
NEMOTRON = "nemotron3_nano_30b_a3b.train_packed_8k"
GLM = "glm4_7_flash.train_packed_8k"
SOLAR = "solar_open2_250b.train_packed_8k"
LFM2 = "lfm2_8b_a1b.train_packed_8k_b2"
TRINITY = "trinity_mini.train_packed_16k"
TINY = dict(pattern="*EWEWEWE", hidden_size=64, vocab_size=256,
            vocab_slice=256, num_attention_heads=14,
            num_key_value_heads=2, head_dim=16, sliding_window=24,
            n_routed_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=48,
            experts_held={"first": 0, "count": 4, "of": 8},
            expert_buffer_rows=128, seq_len=64, batch_seqs=1)
NEW_READERS = ["moe_router_ms.lm"]
# the accepted metrics the cell joins: every one a token cell reports but
# another model's own, and the window layers' two
SHARED = ["data_wait_share.train", "gen_step_ms", "mfu.train",
          "device_idle.train", "hbm_peak_gb.train", "feed_wait_ms.train",
          "loader_batch_ms.train", "host_hook_ms.train", "h2d_ms.train",
          "health_poll_ms.train", "idle_feed_starved.train",
          "idle_host_busy.train", "init_state_s", "step_build_s",
          "train_tokens_per_s.lm", "dispatch_ms.lm", "moe_experts_ms.lm",
          "moe_dispatch_ms.lm", "attn_scores_ms.lm", "head_loss_ms.lm",
          "moe_experts_roofline.lm", "attn_scores_roofline.lm",
          "moe_load_max_over_mean.lm", "moe_held_assignments.lm",
          "attn_rope_norm_ms.lm", "attn_window_ms.lm",
          "attn_window_roofline.lm"]
# three more: ``test_bench_step_scopes.py`` holds their lists of cells
# whole, so joining them takes an edit of that file, which is a
# ``benchmark`` PR's (PERF.md section 7)
HELD_WHOLE = ["step_tail_ms.train", "unscoped_ms.train", "block_norm_ms.lm"]


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

    from benchmark.drivers import train_lm_work

    cache = harness.CACHE_DIR
    loaded = tiny_cell(tmp_path_factory.mktemp("bench_cache"))
    assert loaded["workload"]["driver"] == "train_lm_work"
    peaks = harness.read_json(os.path.join(ROOT, "benchmark", "peaks.json"))
    try:
        run = train_lm_work.run(loaded, seed=2 ** 31 + 45, seconds=0.3,
                                trace=False, devices=jax.devices()[:1],
                                peaks=peaks, clock=harness.Clock(),
                                shrunk=True)
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
    assert sorted(held["program"][0]) == sorted(held["reference"][0]) \
        == [1, 3, 5, 7]
    assert loaded["config"]["sizes"]["nextn_loss_weight"] == 0.0
    assert run["extra"]["mtp_loss_share"] is None
    # a sample is a sequence, one a step
    assert run["metrics"]["train_imgs_per_s"]["value"] == pytest.approx(
        run["attempted"] / run["extra"]["window_s"])
    json.dumps(run["extra"])    # the result line takes it


def test_the_cells_traffic_is_the_issues():
    loaded = harness.load_cell(CELL)
    traffic, sizes = loaded["workload"]["traffic"], loaded["config"]["sizes"]
    assert (traffic["seq_len"], traffic["batch_seqs"]) == (16384, 1)
    assert traffic["token_ids"] == {"distribution": "zipf", "exponent": 1.1,
                                    "ids": 37984}
    # Trinity's traffic to the letter but for the ids' range
    other = harness.load_cell(TRINITY)["workload"]["traffic"]
    assert {k for k in traffic if traffic[k] != other[k]} == {"token_ids"}
    assert set(traffic) == set(other)
    assert (sizes["seq_len"], sizes["batch_seqs"], sizes["vocab_slice"]) \
        == (16384, 1, 37984)
    assert loaded["workload"]["driver"] == "train_lm_work"
    assert set(loaded["workload"]["why_each_limit"]) == set(
        loaded["workload"]["limits"]) | {"tie_margin"}


def test_the_seam_refuses_a_yaml_whose_sizes_differ():
    from benchmark.lib import lm_program

    config = copy.deepcopy(harness.load_cell(CELL)["config"])
    lm_program.load_config(config)       # the shipped YAML agrees
    for key, value in (("sliding_window", 2048), ("use_early_router", False),
                       ("moe_primary_router_apply_softmax", False),
                       ("hidden_act", "silu"), ("seq_len", 8192),
                       ("num_attention_heads", 32),
                       ("moe_intermediate_size", 1024)):
        changed = copy.deepcopy(config)
        changed["sizes"][key] = value
        with pytest.raises(harness.BenchmarkError):
            lm_program.load_config(changed)


def test_configuration_holds_the_catalog_row():
    """Every number of the catalog's `config` under its own key, but for
    the three keys in `reduced`; no width among those; the cut keeps to
    the floors."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    config = harness.load_cell(CELL)["config"]
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == config["source"])
    assert row["name"] == "SmallThinker-21BA3B-Instruct"
    published = row["config"]
    assert config["reduced"] == ["num_hidden_layers",
                                 "moe_num_primary_experts", "vocab_size"]
    for key, value in published.items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    sizes = config["sizes"]
    # layers 0 to 3: one whole period of both layouts
    held = config["num_hidden_layers"]
    assert held == 4
    assert published["sliding_window_layout"][:held] == published[
        "rope_layout"][:held] == [0, 1, 1, 1]
    assert published["sliding_window_layout"] == [0, 1, 1, 1] * 13
    assert sizes["pattern"] == "".join(
        ("W" if layout else "*") + "E"
        for layout in published["sliding_window_layout"][:held])
    assert sizes["use_rope_on_full_attention"] is False
    assert sizes["use_early_router"] is True
    assert sizes["experts_held"]["count"] == config[
        "moe_num_primary_experts"] >= 8
    assert sizes["experts_held"]["of"] == published[
        "moe_num_primary_experts"] == sizes["n_routed_experts"]
    assert sizes["vocab_slice"] == config["vocab_size"] \
        == published["vocab_size"] // 4
    assert sizes["norm_eps"] == published["rms_norm_eps"]
    assert "routed_scaling_factor" not in sizes
    assert "moe_shared_expert_intermediate_size" not in sizes
    assert sizes["seq_len"] == published["max_position_embeddings"]
    for ours, theirs in (("moe_intermediate_size", "moe_ffn_hidden_size"),
                         ("num_experts_per_tok",
                          "moe_num_active_primary_experts"),
                         ("sliding_window", "sliding_window_size")):
        assert sizes[ours] == published[theirs], ours
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "rope_theta",
                "moe_primary_router_apply_softmax"):
        assert sizes[key] == published[key], key
    assert sizes["vocab_size"] == published["vocab_size"]
    spec = harness.load_spec()
    entry = {c["name"]: c for c in spec["configs"]}["smallthinker_21b_a3b"]
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    assert entry["file"] == "benchmark/configs/smallthinker_21b_a3b.json"
    assert len(entry["why"]) <= 200


def test_the_programs_parameters_are_the_references():
    """The program's own tree at the published widths (shapes only)
    against `reference.spec`, name by name: 656,529,920 (ISSUE 45's
    count), and no buffer."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import lm_program
    from benchmark.lib.program import flatten
    from benchmark.reference import smallthinker_train as reference
    from imaginaire_tpu.models.generators import hybrid_lm

    config = harness.load_cell(CELL)["config"]
    cfg = lm_program.load_config(config)
    net = hybrid_lm.Generator(cfg.gen, cfg.data)
    shapes = jax.eval_shape(lambda: net.init(
        jax.random.PRNGKey(0), {"tokens": jnp.zeros((1, 64), jnp.int32)}))
    assert set(shapes) == {"params"}
    ours = {name: tuple(leaf.shape) for tree in shapes.values()
            for name, leaf in flatten(dict(tree)).items()}
    spec = reference.spec(config["sizes"])
    assert ours == {name: tuple(shape) for name, (shape, _) in spec.items()}
    count = sum(leaf.size for leaf in jax.tree_util.tree_leaves(
        shapes["params"]))
    assert count == 656_529_920 == reference.parameter_count(
        config["sizes"])
    by_layer = {}
    for name, (shape, _) in spec.items():
        layer = name.split("/")[0]
        by_layer[layer] = by_layer.get(layer, 0) + math.prod(shape)
    # each with its block's norm scale of 2560
    for layer in (0, 2, 4, 6):
        assert by_layer[f"layer_{layer}"] == 20_971_520 + 2560
    for layer in (1, 3, 5, 7):
        assert by_layer[f"layer_{layer}"] == (163_840 + 16 * 5_898_240
                                              + 2560)
    assert by_layer["embedding"] == by_layer["head"] == 37_984 * 2560
    assert spec["embedding"] == ((37984, 2560), "embedding")
    assert reference.split({"a/router": 1}) == ({"a/router": 1}, {})


def test_work_counts_the_band_the_triangle_and_the_landed_rows():
    """`attn_scores` is the full layer's triangle, `attn_window` the
    three window layers' band and nothing else, `moe_experts` three
    products of 2560 x 768 over the rows that landed; forward some
    11.6e12 operations a step (ISSUE 45, part 7)."""
    from benchmark.reference import smallthinker_train as reference

    sizes = harness.load_cell(CELL)["config"]["sizes"]
    layers = (1, 3, 5, 7)
    even = {k: 24576 for k in layers}
    length, window = 16384, 4096
    pairs = sum(min(i + 1, window) for i in range(length))
    assert pairs == 58_722_304
    operations, nbytes = reference.window_work(sizes, 1, length)
    assert operations == 3 * 4 * 28 * 128 * pairs
    assert nbytes == 3 * 2 * length * 128 * (2 * 28 + 2 * 4)
    work = reference.work(sizes, 1, length, even)
    assert work["attn_window"] == [3 * operations, 3 * nbytes]
    full = reference.attn_work(sizes, 1, length)
    assert work["attn_scores"] == list(full)
    assert full[0] == 3 * 4 * 28 * 128 * length * (length + 1) // 2
    # a window as long as the sequence is the triangle
    assert reference.window_work(dict(sizes, sliding_window=length), 1,
                                 length) == tuple(full)
    one = reference.expert_work(sizes, 24576)
    assert one[0] == 3 * 3 * 2 * 24576 * 2560 * 768
    assert work["moe_experts"] == [4 * n for n in one]
    assert reference.work(sizes, 1, length, {})["moe_experts"] is None
    forward = reference.step_flops(sizes, 1, length, even)["forward"]
    assert 3 * operations / 3 == pytest.approx(2.53e12, rel=0.01)
    assert full[0] / 3 == pytest.approx(1.92e12, rel=0.01)
    assert forward["head"] == pytest.approx(3.19e12, rel=0.01)
    assert 4 * one[0] / 3 == pytest.approx(1.16e12, rel=0.01)
    assert sum(forward.values()) == pytest.approx(11.6e12, rel=0.01)
    assert reference.step_flops(sizes, 1, length, even)[
        "iteration"] == 3 * sum(forward.values())


def test_the_impl_metas_say_what_the_step_runs():
    """The trainer's metas at the cell's shape: the three window layers
    with their window and 70 tiles of the 136 on or below the diagonal
    in every pass of the kernel; every expert layer's router on the
    attention layer's input, scored by the softmax over the chosen,
    relu-gated experts, 16 held on tiers of 49,152 and 98,304 rows; the
    report prints both."""
    from benchmark.lib import lm_program
    from imaginaire_tpu.telemetry.report import render_report
    from imaginaire_tpu.trainers import lm

    cfg = lm_program.load_config(harness.load_cell(CELL)["config"])
    cfg.gen["compute_dtype"] = "bfloat16"
    meta = lm.attn_impl(cfg.gen, (1, 16384))
    assert sorted(meta["layers"], key=int) == ["0", "2", "4", "6"]
    assert meta["windows"] == dict.fromkeys(["2", "4", "6"], 4096)
    assert meta["visited_tiles"] == dict.fromkeys(
        ["2", "4", "6"], dict.fromkeys(["fwd", "dq", "dkv"], [70, 136]))
    moe = lm.moe_impl(cfg.gen, (1, 16384))
    assert moe["router_input"] == dict.fromkeys("1357", "attention_input")
    assert (moe["scoring"], moe["activation"], moe["held"], moe["tiers"],
            moe["buffer_rows"]) == ("softmax_of_chosen", "relu", 16,
                                    [49152, 98304], 98304)
    assert (moe["hidden"], moe["width"]) == (2560, 768)
    assert moe["tiles"]["up"]["fwd"] == (128, 768)
    assert moe["tiles"]["down"]["fwd"] == (128, 640)
    report = render_report([{"kind": "meta", "name": "attn_impl", **meta},
                            {"kind": "meta", "name": "moe_impl", **moe}])
    assert ("layer 0 blocks, layer 2 blocks (window 4096: fwd 70 of 136, "
            "dq 70 of 136, dkv 70 of 136 tiles a head), ") in report
    assert ("layer 7 attention_input, scored by softmax_of_chosen; experts "
            "relu, a buffer of 98304 rows") in report
    # an accepted model's routers read their own norm
    late = lm.moe_impl(lm_program.load_config(
        harness.load_cell(TRINITY)["config"]).gen, (1, 16384))
    assert late["router_input"] == dict.fromkeys("3579", "own_norm")
    assert (late["scoring"], late["activation"]) == ("sigmoid", "silu")


# -------------------------------------------------------------- the reader


def _observed():
    seconds = {"lm/moe/router": 0.004, "lm/moe/dispatch": 0.010,
               "lm/moe/experts": 0.030, "lm/attn/window_scores": 0.050,
               "lm/attn/qkv": 0.006}
    return {"peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "scopes": {"steps": 7, "seconds": seconds}}


def _read(name, observed):
    reader = harness.load_by_path(
        os.path.join(ROOT, "benchmark", "metrics", name + ".py"),
        "smallthinker_metric_" + name.replace(".", "_"))
    return reader.read(observed)


def test_reader_gives_its_number_or_nothing():
    name = "moe_router_ms.lm"
    assert _read(name, _observed()) == pytest.approx(4.0)
    assert _read(name, {}) is None
    # a program without the scope (a model without experts): the traced
    # run's line leaves the metric out
    assert _read(name, dict(_observed(), scopes={
        "steps": 7, "seconds": {"lm/attn/scores": 0.1,
                                "lm/attn/qkv": 0.01}})) is None
    assert _read(name, dict(_observed(), scopes=None)) is None


def test_the_routers_scope_is_found_whole_by_both_reductions():
    from benchmark.lib import scope_times, step_scopes

    stack = ("jit(_gen_step_fn)/jvp(Generator)/layer_1/mixer/lm/moe/router/"
             "fp32_island[router_scores]/dot_general")
    assert scope_times.scope_of(stack) == "lm/moe/router"
    assert step_scopes.scope_of(stack) == "lm/moe/router"
    for taken in ("lm/moe/dispatch", "lm/moe/experts", "lm/moe/combine",
                  "lm/moe/shared"):
        assert not "lm/moe/router".startswith(taken)
        assert not taken.startswith("lm/moe/router")


# ------------------------------------------------ the entries, by name


def _by_name(spec):
    return {m["name"]: m for m in spec["per_layer"]}


def _listed(spec, cell):
    return {m["name"] for m in harness.metrics_of(spec, cell, "per_layer")}


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_is_declared_for_the_cell(name):
    spec = harness.load_spec()
    entry = _by_name(spec)[name]
    assert entry["workloads"][0] == CELL
    assert entry["moves"] == "train_imgs_per_s"
    assert entry["layer"] == "ops" and entry["source"] == "device_trace"
    assert (entry["unit"], entry["better"]) == ("ms", "lower")
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                       name + ".py"))
    for other in (SPADE, NEMOTRON, GLM, SOLAR, LFM2, TRINITY):
        assert name not in _listed(spec, other)


def test_the_cell_reads_the_shared_metrics_and_no_other_models():
    spec = harness.load_spec()
    by_name = _by_name(spec)
    listed = _listed(spec, CELL)
    assert set(SHARED) | set(NEW_READERS) <= listed
    assert not set(HELD_WHOLE) & listed
    for name in SHARED:
        assert by_name[name]["workloads"].index(CELL) > by_name[name][
            "workloads"].index(TRINITY)
    assert not {"dis_step_ms", "dispatch_ms.train", "ssd_scan_ms.lm",
                "ssd_scan_roofline.lm", "mla_latent_ms.lm",
                "mla_latent_roofline.lm", "mtp_loss_share.lm",
                "kda_scan_ms.lm", "kda_scan_roofline.lm", "kda_mixer_ms.lm",
                "gen_net_ms.train", "dis_net_ms.train", "vgg_loss_ms.train",
                "sconv_mixer_ms.lm", "sconv_conv_roofline.lm"} & listed
    # every share of a peak or of a roofline Trinity's cell reports
    assert {m["name"] for m in spec["per_layer"]
            if ("roofline" in m["name"] or "mfu" in m["name"])
            and TRINITY in m["workloads"]} <= listed
    assert [m["name"] for m in harness.metrics_of(
        spec, CELL, "end_to_end")] == ["train_imgs_per_s", "setup_s"]
    cell = {c["name"]: c for c in spec["workloads"]}[CELL]
    assert cell == {"name": CELL, "config": "smallthinker_21b_a3b",
                    "traffic": "train_packed_16k", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "16,384" in cell["why"]


# -------------------------------------------------------------- the control


def test_control_in_float8_products_is_told_from_float32():
    """The reference with float8 products, put in the program's place,
    reads further from float32 than bfloat16 products do, on the
    gradients' distance (the limits themselves are set from chip
    readings)."""
    import numpy as np

    from benchmark.drivers import train_lm
    from benchmark.lib import lm_weights
    from benchmark.reference import smallthinker_train as reference

    sizes = dict(harness.load_cell(CELL)["config"]["sizes"], **TINY)
    rng = np.random.default_rng(5)
    batches = [rng.integers(0, 256, (1, 64)).astype(np.int32)
               for _ in range(2)]
    runs = {precision: train_lm.reference_steps(
        reference, lm_weights.make(reference.spec(sizes), 5), sizes,
        batches, precision, 0.002)
        for precision in ("float32", "bfloat16", "float8")}
    apart = {p: train_lm.compare(runs[p], runs["float32"])[0][
        "first_gradient_apart_median_leaf"] for p in ("bfloat16", "float8")}
    assert apart["float8"] > 3 * apart["bfloat16"] > 0
