"""The delta-rule token cell's files on the CPU (ISSUE 34): the driver end
to end at a tiny size, each new reader on a synthetic `observed` (a
number, and `None` without its input), the configuration against the
catalog's row and the program's own parameter count, the reference's work
counts, the control, and what outlives `test_bench_glm_rehearsal.py::
test_pr27_reader_entries_are_still_whole`."""

import copy
import json
import math
import os

import pytest

from bench_rehearsal_util import ROOT

from benchmark.lib import harness

CELL = "solar_open2_250b.train_packed_8k"
NEMOTRON = "nemotron3_nano_30b_a3b.train_packed_8k"
GLM = "glm4_7_flash.train_packed_8k"
TINY = dict(pattern="*EKEKE", hidden_size=64, vocab_size=256,
            vocab_slice=256, num_attention_heads=4, num_key_value_heads=1,
            head_dim=16,
            linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16,
                                "num_heads": 4},
            kda_chunk_size=16, n_routed_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=48, moe_shared_expert_intermediate_size=48,
            experts_held={"first": 0, "count": 4, "of": 8},
            expert_buffer_rows=256, seq_len=64, batch_seqs=2)
NEW_READERS = ["kda_scan_ms.lm", "kda_scan_roofline.lm", "kda_mixer_ms.lm"]
# the drivers of a token model's training cell
TOKEN_DRIVERS = ("train_lm", "train_lm_work")


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
        run = train_lm_work.run(loaded, seed=2 ** 31 + 34, seconds=0.3,
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
        == [1, 3, 5]
    # one loss: the weight the driver reads is of a module that is not
    # there, and the step returns no `mtp`
    assert loaded["config"]["sizes"]["nextn_loss_weight"] == 0.0
    assert run["extra"]["mtp_loss_share"] is None
    json.dumps(run["extra"])    # the result line takes it


def test_the_seam_refuses_a_yaml_whose_sizes_differ():
    from benchmark.lib import lm_program

    config = copy.deepcopy(harness.load_cell(CELL)["config"])
    lm_program.load_config(config)       # the shipped YAML agrees
    for key, value in (
            ("kda_chunk_size", 32), ("use_gqa_gate", False),
            ("num_attention_heads", 64),
            ("linear_attn_config", {"short_conv_kernel_size": 4,
                                    "head_dim": 128, "num_heads": 64})):
        changed = copy.deepcopy(config)
        changed["sizes"][key] = value
        with pytest.raises(harness.BenchmarkError, match=key):
            lm_program.load_config(changed)


def test_configuration_holds_the_catalog_row():
    """Every number of the catalog's `config` under its own key, but for
    the six keys in `reduced` (the linear-attention group is named by its
    top-level key and differs in its head count alone); no width among
    those; the cut keeps to the floors."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    config = harness.load_cell(CELL)["config"]
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == config["source"])
    published = row["config"]
    assert config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_attention_heads", "num_key_value_heads", "linear_attn_config"]
    for key, value in published.items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    linear = config["linear_attn_config"]
    assert {k for k in linear if linear[k] != published[
        "linear_attn_config"][k]} == {"num_heads"}
    sizes = config["sizes"]
    # one whole period: the softmax layer, then gqa_interval linear ones
    period = published["gqa_interval"] + 1
    assert published["gqa_layers"][:2] == [0, period]
    assert sizes["pattern"] == "*E" + "KE" * published["gqa_interval"]
    assert config["num_hidden_layers"] == period >= 4
    assert published["first_k_dense_replace"] == 0
    assert sizes["experts_held"]["count"] == config["n_routed_experts"] >= 8
    assert sizes["experts_held"]["of"] == published["n_routed_experts"]
    assert sizes["vocab_slice"] == config["vocab_size"] \
        >= published["vocab_size"] // 8
    assert sizes["norm_eps"] == published["rms_norm_eps"]
    assert sizes["moe_shared_expert_intermediate_size"] == published[
        "n_shared_experts"] * published["moe_intermediate_size"]
    # the heads held are an eighth of each mixer's, one key-value head
    assert sizes["num_attention_heads"] * 8 == published[
        "num_attention_heads"]
    assert sizes["num_key_value_heads"] * 8 == published[
        "num_key_value_heads"]
    assert sizes["linear_attn_config"]["num_heads"] * 8 == published[
        "linear_attn_config"]["num_heads"]
    for key in ("hidden_size", "head_dim", "moe_intermediate_size",
                "num_experts_per_tok", "n_routed_experts",
                "routed_scaling_factor", "use_gqa_gate"):
        assert sizes[key] == published[key], key
    for key in ("short_conv_kernel_size", "head_dim"):
        assert sizes["linear_attn_config"][key] == published[
            "linear_attn_config"][key]


def test_the_programs_parameters_are_the_references():
    """The program's own tree at the published widths (shapes only)
    against `reference.spec`, name by name: 840.9 M (ISSUE 34's table)."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import lm_program
    from benchmark.lib.program import flatten
    from benchmark.reference import solar_open2_train as reference
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
    assert count == 840_871_320
    assert count == pytest.approx(840.9e6, rel=0.01)
    # the routers' biases are buffers: 4 x 320 more in the reference's list
    assert reference.parameter_count(config["sizes"]) == count + 4 * 320
    # a mixer's share: 13.63 M the softmax layer, 18.13 M a linear one
    by_layer = {}
    for name, (shape, _) in spec.items():
        layer = name.split("/")[0]
        by_layer[layer] = by_layer.get(layer, 0) + math.prod(shape)
    assert by_layer["layer_0"] == pytest.approx(13.63e6, rel=0.001)
    assert by_layer["layer_2"] == pytest.approx(18.13e6, rel=0.001)


def test_work_counts_follow_the_issues():
    """The chunked delta rule's count at chunk 64 and head 128: a token
    and head 10 C d + 6 d^2 products' operations forward, three passes;
    every layer under a scope is counted; the routed share follows the
    assignments."""
    from benchmark.reference import solar_open2_train as reference

    sizes = harness.load_cell(CELL)["config"]["sizes"]
    layers = (1, 3, 5, 7)
    even = {k: 1638 for k in layers}
    operations, nbytes = reference.kda_scan_work(sizes, 1, 8192)
    assert operations == 3 * 8192 * 8 * (10 * 64 * 128 + 6 * 128 * 128)
    # q, k, v, o in bfloat16, the log-decays in float32, beta; a state a
    # chunk and head, written and read
    assert nbytes == 3 * (8192 * 8 * (8 * 128 + 4 * 128 + 4)
                          + 2 * 4 * 128 * 8 * 128 * 128)
    work = reference.work(sizes, 1, 8192, even)
    assert work["kda_scan"] == [3 * operations, 3 * nbytes]
    assert work["attn_scores"] == list(reference.attn_work(sizes, 1, 8192))
    assert work["moe_experts"] == [4 * n for n in
                                   reference.expert_work(sizes, 1638)]
    assert all(n > 0 for pair in work.values() for n in pair)
    assert reference.work(sizes, 1, 8192, {})["moe_experts"] is None
    flops = reference.step_flops(sizes, 1, 8192, even)
    assert flops["iteration"] == pytest.approx(12.78e12, rel=0.01)
    more = reference.step_flops(sizes, 1, 8192, {k: 8192 for k in layers})
    assert more["iteration"] - flops["iteration"] == pytest.approx(
        3 * 4 * 3 * 2 * (8192 - 1638) * 4096 * 1280)
    # a larger chunk costs more within chunks and as much across them
    wider = reference.kda_scan_work(dict(sizes, kda_chunk_size=128), 1, 8192)
    assert wider[0] - operations == 3 * 8192 * 8 * 10 * 64 * 128


# ------------------------------------------------------------- the readers


def _observed():
    seconds = {"lm/attn/kda_proj": 0.012, "lm/attn/kda_conv": 0.006,
               "lm/attn/kda_scan": 0.040, "lm/attn/kda_gate_norm": 0.002,
               "lm/attn/scores": 0.020, "lm/attn/out": 0.010,
               "lm/attn/gate": 0.001}
    return {"peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "scopes": {"steps": 7, "seconds": seconds},
            "work": {"kda_scan": [0.1e12, 1.638e9]}}


def _read(name, observed):
    reader = harness.load_by_path(
        os.path.join(ROOT, "benchmark", "metrics", name + ".py"),
        "solar_metric_" + name.replace(".", "_"))
    return reader.read(observed)


@pytest.mark.parametrize("name,value", [
    ("kda_scan_ms.lm", 40.0),
    # 2 ms of bytes (0.5 ms of operations) against 40
    ("kda_scan_roofline.lm", 5.0),
    ("kda_mixer_ms.lm", 60.0)])
def test_reader_gives_its_number_or_nothing(name, value):
    assert _read(name, _observed()) == pytest.approx(value)
    assert _read(name, {}) is None
    # a program without the scopes (Nemotron's, GLM's, or a parent's): the
    # traced run's line leaves the metric out
    assert _read(name, dict(_observed(), scopes={
        "steps": 7, "seconds": {"lm/attn/scores": 0.1,
                                "lm/attn/qkv": 0.01}})) is None
    assert _read(name, dict(_observed(), work=None, scopes=None)) is None


def test_the_new_scopes_are_the_reduction_s_to_read():
    """`scope_times.SCOPE` reads `lm/attn/\\w+`: each scope the mixers
    name is found whole, and none starts with a name another metric's
    prefix would catch."""
    from benchmark.lib import scope_times

    for scope in ("lm/attn/kda_proj", "lm/attn/kda_conv", "lm/attn/kda_scan",
                  "lm/attn/kda_gate_norm", "lm/attn/gate", "lm/attn/out"):
        stack = f"jit(step)/layer_2/mixer/{scope}/fp32_island[delta_rule]/dot"
        assert scope_times.scope_of(stack) == scope
        for taken in ("lm/attn/scores", "lm/attn/q_latent",
                      "lm/attn/kv_latent", "lm/attn/rope"):
            assert not scope.startswith(taken)


def test_the_new_readers_are_declared_for_the_new_cell_alone():
    spec = harness.load_spec()
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW_READERS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_imgs_per_s"
        assert by_name[name]["layer"] == "ops"
        assert by_name[name]["source"] == "device_trace"
    listed = {m["name"] for m in harness.metrics_of(spec, CELL, "per_layer")}
    assert not {"dis_step_ms", "dispatch_ms.train", "ssd_scan_ms.lm",
                "ssd_scan_roofline.lm", "mla_latent_ms.lm",
                "mla_latent_roofline.lm", "mtp_loss_share.lm"} & listed
    assert {"mfu.train", "gen_step_ms", "device_idle.train",
            "hbm_peak_gb.train", "attn_scores_roofline.lm",
            "moe_experts_roofline.lm", "moe_held_assignments.lm"} <= listed
    assert len(listed) == 27
    assert [m["name"] for m in harness.metrics_of(
        spec, CELL, "end_to_end")] == ["train_imgs_per_s", "setup_s"]
    cell = {c["name"]: c for c in spec["workloads"]}[CELL]
    assert cell["chips"] == 1 and cell["config"] == "solar_open2_250b"


def test_pr27_and_pr31_reader_entries_are_still_whole():
    """What `test_bench_glm_rehearsal.py::
    test_pr27_reader_entries_are_still_whole` holds beyond PR 31 (it
    asserts that the twelve `*.lm` entries list no cell but Nemotron's and
    GLM's, which no third token cell can keep; `tests/conftest.py` says
    so). The token cells are found by their driver, so the next one needs
    no skip: each of the twelve lists Nemotron's cell first and token
    cells only, moves `train_imgs_per_s` and has its reader; Nemotron's
    cell reads the twelve and nothing a later token model brought; GLM's
    reads its 27."""
    import test_bench_glm_rehearsal as pr31
    import test_bench_lm_rehearsal as pr27

    spec = harness.load_spec()
    token_cells = [c["name"] for c in spec["workloads"] if harness.read_json(
        os.path.join(ROOT, "benchmark", "workloads", c["name"] + ".json"))[
            "driver"] in TOKEN_DRIVERS]
    assert token_cells[:3] == [NEMOTRON, GLM, CELL]
    by_name = {m["name"]: m for m in spec["per_layer"]}
    assert len(pr27.NEW_READERS) == 12
    for name in pr27.NEW_READERS:
        listed = by_name[name]["workloads"]
        assert listed[0] == NEMOTRON
        # in the cells' own order, and token cells only
        assert listed == [c for c in token_cells if c in listed]
        assert by_name[name]["moves"] == "train_imgs_per_s"
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                           name + ".py"))
    for name in pr31.NEW_READERS:
        assert by_name[name]["workloads"] == [GLM]
    nemotron = {m["name"] for m in harness.metrics_of(spec, NEMOTRON,
                                                      "per_layer")}
    assert set(pr27.NEW_READERS) <= nemotron and len(nemotron) == 26
    assert "dis_step_ms" not in nemotron
    assert "dispatch_ms.train" not in nemotron
    later = {m["name"] for m in spec["per_layer"]
             if m.get("workloads", [NEMOTRON])[0] in token_cells[1:]}
    assert later == set(pr31.NEW_READERS) | set(NEW_READERS)
    assert not later & nemotron
    glm = {m["name"] for m in harness.metrics_of(spec, GLM, "per_layer")}
    assert len(glm) == 27 and set(pr31.NEW_READERS) <= glm
    assert not set(NEW_READERS) & glm
    assert {"mfu.train", "gen_step_ms", "device_idle.train",
            "hbm_peak_gb.train"} <= nemotron & glm


# -------------------------------------------------------------- the control


def test_control_in_float8_products_is_told_from_float32():
    """The reference with float8 products, put in the program's place,
    reads further from float32 than bfloat16 products do, on the
    gradients' distance (the limits themselves are set from chip
    readings); the recurrence itself is float32 in all three."""
    import numpy as np

    from benchmark.drivers import train_lm
    from benchmark.lib import lm_weights
    from benchmark.reference import solar_open2_train as reference

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
    assert apart["float8"] > 3 * apart["bfloat16"] > 0
