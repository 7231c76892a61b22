"""The sliding-window token cell's files on the CPU (ISSUE 41): the
driver end to end at a tiny size, the two new readers on a synthetic
`observed` (a number, and `None` without its input), the configuration
against the catalog's row and the program's own parameter count, the
reference's two score families, the window's count in the `attn_impl`
meta, the control, and the entries of `BENCHMARK.json` found by name: no
cell's count of metrics and no entry's position is asserted, and a list
of cells is held to its beginning, so a later cell appended to it breaks
nothing here."""

import copy
import json
import math
import os

import pytest

from bench_rehearsal_util import ROOT

from benchmark.lib import harness

CELL = "trinity_mini.train_packed_16k"
SPADE = "spade_cocostuff_256.train_fed"
NEMOTRON = "nemotron3_nano_30b_a3b.train_packed_8k"
GLM = "glm4_7_flash.train_packed_8k"
SOLAR = "solar_open2_250b.train_packed_8k"
LFM2 = "lfm2_8b_a1b.train_packed_8k_b2"
TINY = dict(pattern="W-WE*EWE", hidden_size=64, vocab_size=256,
            vocab_slice=256, embed_scale=8.0, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, sliding_window=24,
            intermediate_size=160, n_routed_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=48,
            moe_shared_expert_intermediate_size=48,
            experts_held={"first": 0, "count": 4, "of": 8},
            expert_buffer_rows=128, seq_len=64, batch_seqs=1)
NEW_READERS = ["attn_window_ms.lm", "attn_window_roofline.lm"]
# the accepted metrics the cell joins: every one a token cell reports but
# another model's own
SHARED = ["data_wait_share.train", "gen_step_ms", "mfu.train",
          "device_idle.train", "hbm_peak_gb.train", "feed_wait_ms.train",
          "loader_batch_ms.train", "host_hook_ms.train", "h2d_ms.train",
          "health_poll_ms.train", "idle_feed_starved.train",
          "idle_host_busy.train", "init_state_s", "step_build_s",
          "train_tokens_per_s.lm", "dispatch_ms.lm", "moe_experts_ms.lm",
          "moe_dispatch_ms.lm", "attn_scores_ms.lm", "head_loss_ms.lm",
          "moe_experts_roofline.lm", "attn_scores_roofline.lm",
          "moe_load_max_over_mean.lm", "moe_held_assignments.lm",
          "attn_rope_norm_ms.lm"]
# three more that ISSUE 41 named: ``test_bench_step_scopes.py`` holds
# their lists of cells whole, so joining them takes an edit of that file,
# which is a ``benchmark`` PR's (PERF.md section 7)
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
        run = train_lm_work.run(loaded, seed=2 ** 31 + 41, seconds=0.3,
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
        == [3, 5, 7]
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
    assert traffic["document_tokens"] == {
        "distribution": "lognormal", "median": 700, "sigma": 1.2,
        "clip": 16384}
    assert traffic["token_ids"] == {"distribution": "zipf", "exponent": 1.1,
                                    "ids": 25024}
    assert (traffic["fixture_sequences"], traffic["content_seed"],
            traffic["end_of_document_id"]) == (512, 0, 0)
    # the other token cells' traffic to the letter but for the length
    # (the documents' clip with it) and the ids' range
    other = harness.load_cell(SOLAR)["workload"]["traffic"]
    assert {k for k in traffic if traffic[k] != other[k]} == {
        "seq_len", "document_tokens", "token_ids"}
    assert (sizes["seq_len"], sizes["batch_seqs"], sizes["vocab_slice"]) \
        == (16384, 1, 25024)
    assert loaded["workload"]["driver"] == "train_lm_work"
    assert set(loaded["workload"]["why_each_limit"]) == set(
        loaded["workload"]["limits"]) | {"tie_margin"}


def test_the_seam_refuses_a_yaml_whose_sizes_differ():
    from benchmark.lib import lm_program

    config = copy.deepcopy(harness.load_cell(CELL)["config"])
    lm_program.load_config(config)       # the shipped YAML agrees
    for key, value in (("sliding_window", 4096), ("use_post_norm", False),
                       ("use_rope_on_full_attention", True),
                       ("embed_scale", 1.0), ("seq_len", 8192),
                       ("moe_intermediate_size", 1856)):
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
    assert row["name"] == "Trinity-Mini"
    published = row["config"]
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    for key, value in published.items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    sizes = config["sizes"]
    # layer 1, the second leading dense one, then one whole period
    kinds = published["layer_types"]
    first = published["num_dense_layers"] - 1
    held = kinds[first:first + config["num_hidden_layers"]]
    assert held == ["sliding_attention", "sliding_attention",
                    "full_attention", "sliding_attention",
                    "sliding_attention"]
    letters = {"sliding_attention": "W", "full_attention": "*"}
    assert sizes["pattern"] == "".join(
        letters[kind] + ("-" if index < published["num_dense_layers"]
                         else "E")
        for index, kind in enumerate(held, first))
    assert config["num_hidden_layers"] - 1 >= 4
    assert sizes["experts_held"]["count"] == config["num_experts"] >= 8
    assert sizes["experts_held"]["of"] == published["num_experts"] \
        == sizes["n_routed_experts"]
    assert sizes["vocab_slice"] == config["vocab_size"] \
        == published["vocab_size"] // 8
    assert sizes["embed_scale"] == math.sqrt(published["hidden_size"])
    assert sizes["norm_eps"] == published["rms_norm_eps"]
    assert sizes["routed_scaling_factor"] == published["route_scale"]
    assert sizes["moe_shared_expert_intermediate_size"] == published[
        "num_shared_experts"] * published["moe_intermediate_size"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_experts_per_tok", "num_attention_heads",
                "num_key_value_heads", "head_dim", "sliding_window",
                "rope_theta", "hidden_act"):
        assert sizes[key] == published[key], key
    assert sizes["vocab_size"] == published["vocab_size"]
    spec = harness.load_spec()
    entry = {c["name"]: c for c in spec["configs"]}["trinity_mini"]
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    assert entry["file"] == "benchmark/configs/trinity_mini.json"


def test_the_programs_parameters_are_the_references():
    """The program's own tree at the published widths (shapes only)
    against `reference.spec`, name by name: 705,473,792 (ISSUE 41's
    count)."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import lm_program
    from benchmark.lib.program import flatten
    from benchmark.reference import afmoe_train as reference
    from imaginaire_tpu.models.generators import hybrid_lm

    config = harness.load_cell(CELL)["config"]
    cfg = lm_program.load_config(config)
    net = hybrid_lm.Generator(cfg.gen, cfg.data)
    shapes = jax.eval_shape(lambda: net.init(
        jax.random.PRNGKey(0), {"tokens": jnp.zeros((1, 64), jnp.int32)}))
    ours = {name: tuple(leaf.shape) for tree in shapes.values()
            for name, leaf in flatten(dict(tree)).items()}
    spec = reference.spec(config["sizes"])
    assert ours == {name: tuple(shape) for name, (shape, _) in spec.items()}
    count = sum(leaf.size for leaf in jax.tree_util.tree_leaves(
        shapes["params"]))
    assert count == 705_473_792
    # the routers' biases are buffers: 4 x 128 more in the reference's list
    assert reference.parameter_count(config["sizes"]) == count + 4 * 128
    by_layer = {}
    for name, (shape, _) in spec.items():
        if name.endswith("score_bias"):
            continue
        layer = name.split("/")[0]
        by_layer[layer] = by_layer.get(layer, 0) + math.prod(shape)
    # each with its block's two norm scales of 2048
    for layer in (0, 2, 4, 6, 8):
        assert by_layer[f"layer_{layer}"] == 27_263_232 + 2 * 2048
    assert by_layer["layer_1"] == 37_748_736 + 2 * 2048
    for layer in (3, 5, 7, 9):
        assert by_layer[f"layer_{layer}"] == 107_216_896 + 2 * 2048
    # the embedding is drawn as a kernel (the configuration's `assumed`)
    assert spec["embedding"] == ((25024, 2048), "kernel")


def test_work_counts_both_score_families():
    """`attn_scores` is the full layer's triangle, `attn_window` the four
    window layers' band and nothing else; a step's products outside the
    scores are 9.1e12 operations forward (ISSUE 41, part 7)."""
    from benchmark.reference import afmoe_train as reference

    sizes = harness.load_cell(CELL)["config"]["sizes"]
    layers = (3, 5, 7, 9)
    even = {k: 16384 for k in layers}
    length, window = 16384, 2048
    pairs = sum(min(i + 1, window) for i in range(length))
    operations, nbytes = reference.window_work(sizes, 1, length)
    assert operations == 3 * 4 * 32 * 128 * pairs
    assert nbytes == 3 * 2 * length * 128 * (2 * 32 + 2 * 4)
    work = reference.work(sizes, 1, length, even)
    assert work["attn_window"] == [4 * operations, 4 * nbytes]
    full = reference.attn_work(sizes, 1, length)
    assert work["attn_scores"] == list(full)
    assert full[0] == 3 * 4 * 32 * 128 * length * (length + 1) // 2
    # the band of four layers costs about what the one triangle does
    assert 4 * operations / full[0] == pytest.approx(0.94, abs=0.01)
    # a window as long as the sequence is the triangle
    assert reference.window_work(dict(sizes, sliding_window=length), 1,
                                 length) == tuple(full)
    assert work["moe_experts"] == [4 * n for n in
                                   reference.expert_work(sizes, 16384)]
    assert reference.work(sizes, 1, length, {})["moe_experts"] is None
    forward = reference.step_flops(sizes, 1, length, even)["forward"]
    scores = (operations * 4 + full[0]) / 3
    assert sum(forward.values()) - scores == pytest.approx(9.1e12, rel=0.01)
    assert full[0] / 3 == pytest.approx(2.2e12, rel=0.01)
    assert 4 * operations / 3 == pytest.approx(2.1e12, rel=0.02)
    assert forward["W"] + forward["*"] > 0.3 * sum(forward.values())


def test_attn_impl_counts_the_bands_tiles():
    """The trainer's `attn_impl` meta at the cell's shape: each window
    layer with its window and, for every pass of the kernel, 45 tiles of
    the 136 on or below the diagonal; the report prints them."""
    from benchmark.lib import lm_program
    from imaginaire_tpu.telemetry.report import render_report
    from imaginaire_tpu.trainers import lm

    cfg = lm_program.load_config(harness.load_cell(CELL)["config"])
    cfg.gen["compute_dtype"] = "bfloat16"
    meta = lm.attn_impl(cfg.gen, (1, 16384))
    assert sorted(meta["layers"], key=int) == ["0", "2", "4", "6", "8"]
    assert meta["windows"] == dict.fromkeys(["0", "2", "6", "8"], 2048)
    assert meta["visited_tiles"] == dict.fromkeys(
        ["0", "2", "6", "8"], dict.fromkeys(["fwd", "dq", "dkv"], [45, 136]))
    report = render_report([{"kind": "meta", "name": "attn_impl", **meta}])
    assert ("layer 2 blocks (window 2048: fwd 45 of 136, dq 45 of 136, "
            "dkv 45 of 136 tiles a head), layer 4 blocks, ") in report


# ------------------------------------------------------------- the readers


def _observed():
    seconds = {"lm/attn/window_scores": 0.050, "lm/attn/scores": 0.040,
               "lm/attn/qk_norm": 0.002, "lm/attn/rope": 0.003,
               "lm/attn/qkv": 0.006, "lm/attn/out": 0.015}
    return {"peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "scopes": {"steps": 7, "seconds": seconds},
            "work": {"attn_window": [4.925e12, 0.5e9],
                     "attn_scores": [6.6e12, 0.1e9]}}


def _read(name, observed):
    reader = harness.load_by_path(
        os.path.join(ROOT, "benchmark", "metrics", name + ".py"),
        "afmoe_metric_" + name.replace(".", "_"))
    return reader.read(observed)


@pytest.mark.parametrize("name,value", [
    ("attn_window_ms.lm", 50.0),
    # 25 ms of operations (0.6 ms of bytes) against 50
    ("attn_window_roofline.lm", 50.0)])
def test_reader_gives_its_number_or_nothing(name, value):
    assert _read(name, _observed()) == pytest.approx(value)
    assert _read(name, {}) is None
    # a program without the scope (another model's, or a parent's): the
    # traced run's line leaves the metric out
    assert _read(name, dict(_observed(), scopes={
        "steps": 7, "seconds": {"lm/attn/scores": 0.1,
                                "lm/attn/qkv": 0.01}})) is None
    assert _read(name, dict(_observed(), work=None, scopes=None)) is None


def test_the_full_layers_readers_leave_the_band_out():
    """`attn_scores_ms.lm` and its roofline read `lm/attn/scores` by
    prefix: the window layers' scope does not start with it, and both
    readers' patterns find the new scopes whole."""
    from benchmark.lib import scope_times, step_scopes

    assert _read("attn_scores_ms.lm", _observed()) == pytest.approx(40.0)
    for scope in ("lm/attn/window_scores", "lm/block/post_norm"):
        stack = (f"jit(step)/jvp(Generator)/layer_2/{scope}/"
                 "fp32_island[norm_stats]/mul")
        assert step_scopes.scope_of(stack) == scope
        for taken in ("lm/attn/scores", "lm/attn/q_latent",
                      "lm/attn/kv_latent", "lm/attn/kda_",
                      "lm/attn/sconv_", "lm/block/norm"):
            assert not scope.startswith(taken)
    assert scope_times.scope_of(
        "jit(step)/jvp(Generator)/layer_2/mixer/lm/attn/window_scores/dot"
    ) == "lm/attn/window_scores"


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
    assert entry["unit"] == ("%" if "roofline" in name else "ms")
    assert entry["better"] == ("higher" if "roofline" in name else "lower")
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                       name + ".py"))
    for other in (SPADE, NEMOTRON, GLM, SOLAR, LFM2):
        assert name not in _listed(spec, other)


def test_the_cell_reads_the_shared_metrics_and_no_other_models():
    spec = harness.load_spec()
    by_name = _by_name(spec)
    listed = _listed(spec, CELL)
    assert set(SHARED) | set(NEW_READERS) <= listed
    assert not set(HELD_WHOLE) & listed
    for name in SHARED:
        assert by_name[name]["workloads"].index(CELL) > by_name[name][
            "workloads"].index(LFM2)
    assert not {"dis_step_ms", "dispatch_ms.train", "ssd_scan_ms.lm",
                "ssd_scan_roofline.lm", "mla_latent_ms.lm",
                "mla_latent_roofline.lm", "mtp_loss_share.lm",
                "kda_scan_ms.lm", "kda_scan_roofline.lm", "kda_mixer_ms.lm",
                "gen_net_ms.train", "dis_net_ms.train", "vgg_loss_ms.train",
                "sconv_mixer_ms.lm", "sconv_conv_roofline.lm"} & listed
    # every share of a peak or of a roofline the token cells report
    assert {m["name"] for m in spec["per_layer"]
            if ("roofline" in m["name"] or "mfu" in m["name"])
            and LFM2 in m["workloads"]
            and not m["name"].startswith("sconv_")} <= listed
    assert [m["name"] for m in harness.metrics_of(
        spec, CELL, "end_to_end")] == ["train_imgs_per_s", "setup_s"]
    cell = {c["name"]: c for c in spec["workloads"]}[CELL]
    assert cell == {"name": CELL, "config": "trinity_mini",
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
    from benchmark.reference import afmoe_train as reference

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
