"""The latent-attention token cell's files on the CPU (ISSUE 31): the new
driver end to end at a tiny size, each new reader on a synthetic
`observed` (a number, and `None` without its input), the configuration
against the catalog's row and the program's own parameter count, the
reference's work counts, the control, and what outlives
`test_bench_lm_rehearsal.py::
test_every_new_reader_is_declared_for_the_cell_alone`."""

import copy
import json
import os

import pytest

from bench_rehearsal_util import ROOT

from benchmark.lib import harness

CELL = "glm4_7_flash.train_packed_8k"
NEMOTRON = "nemotron3_nano_30b_a3b.train_packed_8k"
TINY = dict(pattern="*-*E*E", hidden_size=64, vocab_size=256,
            vocab_slice=256, num_attention_heads=4, q_lora_rank=24,
            kv_lora_rank=16, qk_nope_head_dim=24, qk_rope_head_dim=8,
            v_head_dim=32, intermediate_size=160, n_routed_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=48,
            moe_shared_expert_intermediate_size=48,
            experts_held={"first": 0, "count": 4, "of": 8},
            expert_buffer_rows=256, seq_len=64, batch_seqs=2)
NEW_READERS = ["mla_latent_ms.lm", "mla_latent_roofline.lm",
               "mtp_loss_share.lm"]


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
        run = train_lm_work.run(loaded, seed=2 ** 31 + 31, seconds=0.3,
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
    # the module's expert layer under its own index, after the pattern's
    assert sorted(held["program"][0]) == sorted(held["reference"][0]) \
        == [3, 5, 7]
    # both losses ran and the module's was weighed: lambda CE_mtp over
    # CE_main + lambda CE_mtp, two near-equal cross-entropies at 0.3
    assert run["extra"]["mtp_loss_share"] == pytest.approx(0.3 / 1.3,
                                                           abs=0.03)
    json.dumps(run["extra"])    # the result line takes it


def test_a_program_without_the_yaml_cannot_load_the_cell(tmp_path, capsys):
    """The benchmark's files laid over a commit older than the
    configuration: the driver says so and exits 2 before it builds
    anything, as `run.py` does for a cell it cannot find."""
    import jax

    from benchmark.drivers import train_lm_work

    cache = harness.CACHE_DIR
    loaded = tiny_cell(tmp_path)
    harness.CACHE_DIR = cache
    loaded["config"]["program_yaml"] = "configs/projects/none/absent.yaml"
    with pytest.raises(SystemExit) as halted:
        train_lm_work.run(loaded, seed=1, seconds=0.1, trace=False,
                          devices=jax.devices()[:1], peaks={},
                          clock=harness.Clock())
    assert halted.value.code == 2
    assert "cannot load cell 'glm4_7_flash.train_packed_8k'" \
        in capsys.readouterr().err


def test_the_seam_refuses_a_yaml_whose_sizes_differ():
    from benchmark.lib import lm_program

    config = copy.deepcopy(harness.load_cell(CELL)["config"])
    lm_program.load_config(config)       # the shipped YAML agrees
    for key, value in (("kv_lora_rank", 256), ("nextn_loss_weight", 0.1),
                       ("hidden_act", "relu2")):
        changed = copy.deepcopy(config)
        changed["sizes"][key] = value
        with pytest.raises(harness.BenchmarkError, match=key):
            lm_program.load_config(changed)


def test_configuration_holds_the_catalog_row():
    """Every number of the catalog's `config` under its own key, but for
    the three keys in `reduced`; no width among those; the program's own
    parameter count at these sizes is the reference's, 706.5 M."""
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
    # a block is two letters; the leading dense one, then expert blocks
    dense = row["config"]["first_k_dense_replace"]
    assert sizes["pattern"] == "*-" * dense + "*E" * (
        config["num_hidden_layers"] - dense)
    assert config["num_hidden_layers"] - dense >= 4
    assert sizes["nextn_pattern"] == "*E" * row["config"][
        "num_nextn_predict_layers"]
    assert sizes["experts_held"]["count"] == config["n_routed_experts"] >= 8
    assert sizes["experts_held"]["of"] == row["config"]["n_routed_experts"]
    assert sizes["vocab_slice"] == config["vocab_size"] \
        >= row["config"]["vocab_size"] // 8
    assert sizes["norm_eps"] == row["config"]["rms_norm_eps"]
    assert sizes["moe_shared_expert_intermediate_size"] == row["config"][
        "n_shared_experts"] * row["config"]["moe_intermediate_size"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "q_lora_rank", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "rope_theta", "num_experts_per_tok", "n_routed_experts",
                "routed_scaling_factor", "hidden_act"):
        assert sizes[key] == row["config"][key], key


def test_the_programs_parameters_are_the_references():
    """The program's own tree at the published widths (shapes only)
    against `reference.spec`, name by name: 706.5 M."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import lm_program
    from benchmark.lib.program import flatten
    from benchmark.reference import glm4_moe_lite_train as reference
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
    assert count == pytest.approx(706.5e6, rel=0.01)
    # the routers' biases are buffers: 5 x 64 more in the reference's list
    assert reference.parameter_count(config["sizes"]) == count + 5 * 64


def test_work_counts_follow_the_issues():
    """ISSUE 31's reckoning: 17.3 TFLOP of products and 12.4 of causal
    scores a step at even routing; every layer under a scope is counted,
    the module's block included; the routed share follows the
    assignments."""
    from benchmark.reference import glm4_moe_lite_train as reference

    sizes = harness.load_cell(CELL)["config"]["sizes"]
    layers = (3, 5, 7, 9, 11)
    even = {k: 4096 for k in layers}
    flops = reference.step_flops(sizes, 1, 8192, even)
    scores = 6 * reference.attn_work(sizes, 1, 8192)[0]
    assert scores == pytest.approx(12.4e12, rel=0.01)
    assert flops["iteration"] - scores == pytest.approx(17.3e12, rel=0.02)
    more = reference.step_flops(sizes, 1, 8192, {k: 8192 for k in layers})
    assert more["iteration"] - flops["iteration"] == pytest.approx(
        3 * 5 * 3 * 2 * 4096 * 2048 * 1536)
    work = reference.work(sizes, 1, 8192, even)
    assert work["attn_scores"][0] == scores
    assert work["mla_latent"] == [6 * n for n in
                                  reference.latent_work(sizes, 1, 8192)]
    assert work["moe_experts"] == [5 * n for n in
                                   reference.expert_work(sizes, 4096)]
    # the four projections around the latents: 11.27 M parameters a layer
    assert reference.latent_work(sizes, 1, 8192)[0] == 3 * 2 * 8192 * (
        2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960)
    assert all(n > 0 for pair in work.values() for n in pair)
    assert reference.work(sizes, 1, 8192, {})["moe_experts"] is None


# ------------------------------------------------------------- the readers


def _observed():
    seconds = {"lm/attn/q_latent": 0.004, "lm/attn/kv_latent": 0.005,
               "lm/attn/rope": 0.003, "lm/attn/scores": 0.150,
               "lm/attn/out": 0.010}
    return {"peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "scopes": {"steps": 7, "seconds": seconds},
            "work": {"mla_latent": [0.591e12, 1e9]},
            "mtp_loss_shares": [0.22, 0.24]}


def _read(name, observed):
    reader = harness.load_by_path(
        os.path.join(ROOT, "benchmark", "metrics", name + ".py"),
        "glm_metric_" + name.replace(".", "_"))
    return reader.read(observed)


@pytest.mark.parametrize("name,value", [
    ("mla_latent_ms.lm", 12.0),
    # 3 ms of operations against 12
    ("mla_latent_roofline.lm", 25.0),
    ("mtp_loss_share.lm", 23.0)])
def test_reader_gives_its_number_or_nothing(name, value):
    assert _read(name, _observed()) == pytest.approx(value)
    assert _read(name, {}) is None
    # a program without the scopes or the second loss (Nemotron's, or a
    # parent's): the traced run's line leaves the metric out
    assert _read(name, dict(_observed(), mtp_loss_shares=[], scopes={
        "steps": 7, "seconds": {"lm/attn/scores": 0.1,
                                "lm/attn/qkv": 0.01}})) is None
    assert _read(name, dict(_observed(), work=None, scopes=None,
                            mtp_loss_shares=None)) is None


def test_one_latent_scope_alone_still_reads():
    observed = _observed()
    observed["scopes"]["seconds"] = {"lm/attn/rope": 0.003}
    assert _read("mla_latent_ms.lm", observed) == pytest.approx(3.0)
    assert _read("mla_latent_roofline.lm", observed) == pytest.approx(100.0)


def test_the_new_readers_are_declared_for_the_new_cell_alone():
    spec = harness.load_spec()
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW_READERS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_imgs_per_s"
        assert by_name[name]["layer"] == "ops"
    listed = {m["name"] for m in harness.metrics_of(spec, CELL, "per_layer")}
    assert not {"dis_step_ms", "dispatch_ms.train", "ssd_scan_ms.lm",
                "ssd_scan_roofline.lm"} & listed
    assert {"mfu.train", "gen_step_ms", "device_idle.train",
            "hbm_peak_gb.train", "attn_scores_roofline.lm",
            "moe_experts_roofline.lm", "moe_held_assignments.lm"} <= listed
    assert len(listed) == 27
    assert [m["name"] for m in harness.metrics_of(
        spec, CELL, "end_to_end")] == ["train_imgs_per_s", "setup_s"]
    cell = {c["name"]: c for c in spec["workloads"]}[CELL]
    assert cell["chips"] == 1 and cell["config"] == "glm4_7_flash"


def test_pr27_reader_entries_are_still_whole():
    """What `test_bench_lm_rehearsal.py::
    test_every_new_reader_is_declared_for_the_cell_alone` holds beyond PR
    27 (it asserts that each `*.lm` metric names Nemotron's cell and no
    other, which no later token cell can keep; `tests/conftest.py` says
    so): each of the twelve lists Nemotron's cell first, moves
    `train_imgs_per_s`, has its reader, and Nemotron's cell still reads
    all twelve and none of SPADE's alone."""
    import test_bench_lm_rehearsal as pr27

    spec = harness.load_spec()
    by_name = {m["name"]: m for m in spec["per_layer"]}
    assert len(pr27.NEW_READERS) == 12
    for name in pr27.NEW_READERS:
        assert by_name[name]["workloads"][0] == NEMOTRON
        assert set(by_name[name]["workloads"]) <= {NEMOTRON, CELL}
        assert by_name[name]["moves"] == "train_imgs_per_s"
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                           name + ".py"))
    listed = {m["name"] for m in harness.metrics_of(spec, NEMOTRON,
                                                    "per_layer")}
    assert set(pr27.NEW_READERS) <= listed
    assert "dis_step_ms" not in listed and "dispatch_ms.train" not in listed
    assert not set(NEW_READERS) & listed
    assert {"mfu.train", "gen_step_ms", "device_idle.train",
            "hbm_peak_gb.train"} <= listed


# -------------------------------------------------------------- the control


def test_control_in_float8_products_is_told_from_float32():
    """The reference with float8 products, put in the program's place,
    reads further from float32 than bfloat16 products do, on the gradients'
    distance (3.9 times at this width; the limits themselves are set from
    chip readings)."""
    import numpy as np

    from benchmark.drivers import train_lm
    from benchmark.lib import lm_weights
    from benchmark.reference import glm4_moe_lite_train as reference

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
