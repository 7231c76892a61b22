"""The reader of the step programs' named scopes (PR 36) on hand-made
profiles, its six metric files on a hand-made result, and the successors
of the four rehearsals that froze a cell's count of per-layer metrics
(`tests/conftest.py` skips those): these find every entry BY NAME and
assert no cell's count and no entry's position, so the next PR that
appends a metric needs no skip."""

import gzip
import json
import os
import types

import pytest

from bench_rehearsal_util import ROOT

from benchmark.lib import harness, step_scopes, trace_reduce

SPADE = "spade_cocostuff_256.train_fed"
NEMOTRON = "nemotron3_nano_30b_a3b.train_packed_8k"
GLM = "glm4_7_flash.train_packed_8k"
SOLAR = "solar_open2_250b.train_packed_8k"
# this PR's entries: (name, layer, the cells that report it, in order)
NEW = [("step_tail_ms.train", "step programs", [SPADE, NEMOTRON, GLM, SOLAR]),
       ("unscoped_ms.train", "ops", [SPADE, NEMOTRON, GLM, SOLAR]),
       ("block_norm_ms.lm", "ops", [NEMOTRON, GLM, SOLAR]),
       ("gen_net_ms.train", "ops", [SPADE]),
       ("dis_net_ms.train", "ops", [SPADE]),
       ("vgg_loss_ms.train", "ops", [SPADE])]

MS = 1_000_000      # ns

# two programs whose instruction names collide: `fusion.1` is the
# generator's forward in the G step and Adam's update in the D step
OP_NAMES = {
    "gen_step": {
        "fusion.1": "jit(_gen_step_fn)/jvp(gan/G)/Generator/conv",
        "fusion.2": "jit(_gen_step_fn)/transpose(jvp(gan/G))/Generator/conv",
        "fusion.3": "jit(_gen_step_fn)/transpose(jvp(gan/G))/checkpoint/"
                    "rematted_computation/Generator/conv",
        "while.4": "jit(_gen_step_fn)/jvp(gan/loss/perceptual)/vgg/while",
        "fusion.5": "jit(_gen_step_fn)/jvp(gan/loss/perceptual)/vgg/relu",
        "fusion.6": "jit(_gen_step_fn)/step/optim/mul",
        "fusion.7": "jit(_gen_step_fn)/jvp(lm/head_loss)/lm/final_norm/mul",
        "ragged-dot-none.8": "ragged-dot-none",
        "copy.9": "copy",
    },
    "dis_step": {
        "fusion.1": "jit(_dis_step_fn)/step/optim/mul",
        "fusion.2": "jit(_dis_step_fn)/jvp(gan/G)/Generator/conv",
        "fusion.3": "jit(_dis_step_fn)/transpose(jvp(gan/D))/D/conv",
    },
}


def _event(name, start_ms, ms):
    return types.SimpleNamespace(name=name, start_ns=int(start_ms * MS),
                                 duration_ns=int(ms * MS), stats=[])


def _op(name, start_ms, ms):
    return _event(f"%{name} = f32[8]{{0}} fusion(%p)", start_ms, ms)


def _profile(ops, modules):
    plane = types.SimpleNamespace(name="/device:TPU:0", lines=[
        types.SimpleNamespace(name="XLA Ops", events=ops),
        types.SimpleNamespace(name="XLA Modules", events=modules),
        # not a line of single operations: never read
        types.SimpleNamespace(name="Async XLA Ops", events=[
            _op("fusion.1", 100, 50)])])
    host = types.SimpleNamespace(name="/host:CPU", lines=[])
    return types.SimpleNamespace(planes=[plane, host])


def _hand_made():
    """A G step that the trace's start cut (from 0, where the plane's
    events begin), a whole D step, a whole G step, a whole D step."""
    modules = [_event("jit__gen_step_fn(11)", 0, 20),
               _event("jit__dis_step_fn(22)", 30, 10),
               _event("jit__gen_step_fn(11)", 50, 40),
               _event("jit__dis_step_fn(22)", 100, 10),
               _event("jit_expand_labels(33)", 120, 5)]
    ops = [
        # the cut step: its time counts nowhere
        _op("fusion.2", 0, 20),
        # D step 1: G's forward 4, D's backward 3, Adam 2
        _op("fusion.2", 30, 4), _op("fusion.3", 34, 3), _op("fusion.1", 37, 2),
        # the whole G step, 38 ms busy of its 40
        _op("fusion.1", 50, 5),                 # gan/G forward
        _op("while.4", 55, 10),                 # perceptual: a loop ...
        _op("fusion.5", 56, 2),                 # ... and its body, once
        _op("copy.9", 59, 1),                   # no scope: the loop's
        _op("fusion.7", 65, 1),                 # the LAST scope: final_norm
        _op("fusion.3", 66, 6),                 # gan/G recompute
        _op("fusion.2", 72, 8),                 # gan/G backward
        _op("ragged-dot-none.8", 80, 3),        # by its prefix; the pass
                                                # of the event before it
        _op("copy.9", 83, 2),                   # under no scope
        _op("fusion.6", 87, 3),                 # after a 2 ms gap
        # D step 2: the same instructions, other times
        _op("fusion.2", 100, 6), _op("fusion.3", 106, 1),
        _op("fusion.1", 107, 2),
        # another program's operation: in no step
        _op("fusion.1", 120, 5)]
    return _profile(ops, modules)


def test_reader_on_a_hand_made_profile_of_two_programs():
    reduced = step_scopes.reduce(_hand_made(), OP_NAMES)
    assert reduced["executions"] == {"gen_step": 1, "dis_step": 2}
    gen, dis = (reduced["seconds"][p] for p in ("gen_step", "dis_step"))
    assert gen == {
        "gan/G": {"forward": pytest.approx(0.005),
                  "recompute": pytest.approx(0.006),
                  "backward": pytest.approx(0.008)},
        "gan/loss/perceptual": {"forward": pytest.approx(0.010)},
        "lm/final_norm": {"forward": pytest.approx(0.001)},
        "lm/moe/experts": {"backward": pytest.approx(0.003)},
        "step/optim": {"forward": pytest.approx(0.003)}}
    # the colliding names read by the D step's own map, a mean of two
    assert dis == {
        "gan/G": {"forward": pytest.approx(0.005)},
        "gan/D": {"backward": pytest.approx(0.002)},
        "step/optim": {"forward": pytest.approx(0.002)}}
    assert reduced["busy_s"] == {"gen_step": pytest.approx(0.038),
                                 "dis_step": pytest.approx(0.009)}
    assert reduced["matched_s"] == {"gen_step": pytest.approx(0.036),
                                    "dis_step": pytest.approx(0.009)}


def test_scopes_and_the_unscoped_part_add_up_to_the_busy_time(tmp_path):
    reduced = step_scopes.reduce(_hand_made(), OP_NAMES)
    for program, scopes in reduced["seconds"].items():
        under = sum(s for passes in scopes.values() for s in passes.values())
        assert under == pytest.approx(reduced["matched_s"][program])
    rows = step_scopes.table(reduced)
    assert sum(r[-1] for r in rows) == pytest.approx(
        1e3 * sum(reduced["busy_s"].values()))
    assert [r[:2] for r in rows if r[0] == "dis_step"] == [
        ("dis_step", "gan/G"), ("dis_step", "gan/D"),
        ("dis_step", "step/optim"), ("dis_step", "(no scope)")]
    # on a recorded trace too (PR 23's 280 ms: the head of one D step,
    # begun after the plane's first event), whatever the map says
    data = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "train_280ms.xplane.pb.gz")
    path = tmp_path / "cut.xplane.pb"
    with gzip.open(data, "rb") as src:
        path.write_bytes(src.read())
    recorded = step_scopes.reduce(trace_reduce.load(str(path)),
                                  {"dis_step": {}})
    assert recorded["executions"] == {"dis_step": 1}
    assert recorded["seconds"] == {"dis_step": {}}
    assert recorded["matched_s"] == {"dis_step": 0.0}
    assert 0 < recorded["busy_s"]["dis_step"] <= 0.0842


def test_a_program_without_a_map_gives_none():
    profile = _hand_made()
    assert step_scopes.reduce(profile, None) is None
    assert step_scopes.reduce(profile, {}) is None
    # a map of a program the trace does not run
    assert step_scopes.reduce(profile, {"inception": {}}) is None
    # one program's map alone: the other program is left out, not guessed
    only = step_scopes.reduce(profile, {"dis_step": OP_NAMES["dis_step"]})
    assert list(only["seconds"]) == ["dis_step"]
    # a trace whose only execution was cut
    cut = _profile([_op("fusion.1", 0, 5)],
                   [_event("jit__gen_step_fn(11)", 0, 5)])
    assert step_scopes.reduce(cut, OP_NAMES) is None


def test_the_last_scope_and_the_pass_of_a_name_stack():
    assert step_scopes.scope_of(
        "jit(f)/jvp(G)/layer_3/lm/block/norm/fp32_island[norm_stats]/mul") \
        == "lm/block/norm"
    assert step_scopes.scope_of("jit(f)/jvp(G)/lm/mlp/dense/dot") \
        == "lm/mlp/dense"
    assert step_scopes.scope_of("jit(f)/jvp(G)/lm/mtp/merge/dot") \
        == "lm/mtp/merge"
    assert step_scopes.scope_of("jit(f)/jvp(gan/loss/adversarial)/mean") \
        == "gan/loss/adversarial"
    assert step_scopes.scope_of("jit(f)/step/guard/jit(_where)/select_n") \
        == "step/guard"
    # a module named like a scope's beginning is not the scope
    assert step_scopes.scope_of("jit(f)/jvp(gan/GAN_head)/conv") is None
    assert step_scopes.scope_of("jit(f)/adam/mul") is None
    assert step_scopes.scope_of(None) is None
    # the compiled text's own stacks: a block's recompute stands under
    # the backward's transpose( too
    assert step_scopes.pass_of(
        "jit(f)/transpose(jvp(G))/jvp(G)/checkpoint/rematted_computation/"
        "layer_0/mixer/lm/attn/qkv/dot") == "recompute"
    assert step_scopes.pass_of(
        "jit(f)/transpose(jvp(G))/jvp(G)/checkpoint/layer_0/mixer/"
        "lm/attn/qkv/dot") == "backward"
    assert step_scopes.pass_of("jit(f)/jvp(G)/layer_0/lm/embed/gather") \
        == "forward"
    assert step_scopes.pass_of("ragged-dot-none") is None


# -------------------------------------------------------- the metric files

REDUCED = {
    "executions": {"gen_step": 14, "dis_step": 14},
    "seconds": {
        "gen_step": {
            "gan/G": {"forward": 0.030, "recompute": 0.028, "backward": 0.062},
            "gan/D": {"forward": 0.010, "backward": 0.015},
            "gan/loss/perceptual": {"forward": 0.012, "backward": 0.018},
            "gan/loss/adversarial": {"forward": 0.001},
            "lm/block/norm": {"forward": 0.002, "backward": 0.004},
            "lm/block/residual": {"forward": 0.001},
            "lm/final_norm": {"forward": 0.0005, "backward": 0.0005},
            "step/optim": {"forward": 0.020}, "step/ema": {"forward": 0.008},
            "step/cast": {"forward": 0.002, "backward": 0.001}},
        "dis_step": {
            "gan/G": {"forward": 0.031},
            "gan/D": {"forward": 0.012, "recompute": 0.011,
                      "backward": 0.020},
            "step/optim": {"forward": 0.003},
            "step/guard": {"forward": 0.001}}},
    "busy_s": {"gen_step": 0.260, "dis_step": 0.084},
    "matched_s": {"gen_step": 0.2141, "dis_step": 0.078}}


def _read(name, observed):
    spec = harness.load_spec()
    metric = [m for m in spec["per_layer"] if m["name"] == name]
    return harness.read_metrics(metric, observed).get(name, {}).get("value")


@pytest.mark.parametrize("name,want", [
    ("step_tail_ms.train", 20 + 8 + 3 + 3 + 1),
    ("unscoped_ms.train", 260 - 214.1 + 84 - 78),
    ("block_norm_ms.lm", 6 + 1 + 1),
    ("gen_net_ms.train", 120 + 31),
    ("dis_net_ms.train", 25 + 43),
    ("vgg_loss_ms.train", 30),
])
def test_metric_file_on_a_hand_made_result(monkeypatch, name, want):
    monkeypatch.setattr(step_scopes, "traced", lambda observed: REDUCED)
    assert _read(name, {"trace": {}}) == pytest.approx(want)
    # the parent of PR 36: a ledger without the maps
    monkeypatch.setattr(step_scopes, "traced", lambda observed: None)
    assert _read(name, {"trace": {}}) is None


def test_a_scope_no_program_has_gives_nothing(monkeypatch):
    monkeypatch.setattr(step_scopes, "traced", lambda observed: {
        **REDUCED, "seconds": {"gen_step": {"lm/embed": {"forward": 0.1}}}})
    assert _read("vgg_loss_ms.train", {"trace": {}}) is None
    assert _read("step_tail_ms.train", {"trace": {}}) is None


def test_the_runs_own_trace_is_read_by_the_programs_own_ledger(
        monkeypatch, tmp_path):
    """`traced` finds the newest `.xplane.pb` under the harness's trace
    directory, asks the program's ledger for the maps, parses once, and
    leaves `scopes.json` beside the trace; a program whose ledger has no
    such attribute (the parent) gives None."""
    from imaginaire_tpu.telemetry import xla_obs

    monkeypatch.setattr(harness, "CACHE_DIR", str(tmp_path))
    observed = {"trace": {"busy_s": 1.0}}
    assert step_scopes.traced({}) is None            # not a traced run
    assert step_scopes.traced(observed) is None      # no file
    run = tmp_path / "trace" / "plugins" / "profile" / "2026_10_01"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(b"")
    loads = []
    monkeypatch.setattr(trace_reduce, "load",
                        lambda path: loads.append(path) or _hand_made())
    step_scopes._reduced_file.cache_clear()
    monkeypatch.setattr(xla_obs, "ledger",
                        lambda: types.SimpleNamespace(records=[]))
    assert step_scopes.traced(observed) is None
    assert step_scopes.unscoped_ms(observed) is None
    step_scopes._reduced_file.cache_clear()
    monkeypatch.setattr(xla_obs, "ledger", lambda: types.SimpleNamespace(
        records=[], label_op_names=OP_NAMES))
    first = step_scopes.traced(observed)
    assert first["executions"] == {"gen_step": 1, "dis_step": 2}
    assert step_scopes.traced(observed) is first and len(loads) == 1
    assert step_scopes.under(observed, ("gan/G",)) == pytest.approx(24.0)
    assert step_scopes.under(observed, ("lm/attn/",)) is None
    assert step_scopes.unscoped_ms(observed) == pytest.approx(2.0)
    with open(tmp_path / "trace" / "scopes.json") as f:
        assert isinstance(json.load(f), dict)
    step_scopes._reduced_file.cache_clear()


# ------------------------------------------------ the entries, by name


def _by_name(spec):
    return {m["name"]: m for m in spec["per_layer"]}


def _listed(spec, cell):
    return [m["name"] for m in harness.metrics_of(spec, cell, "per_layer")]


def _has_reader(name):
    return os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                       name + ".py"))


@pytest.mark.parametrize("name,layer,cells", NEW)
def test_the_six_entries_are_declared(name, layer, cells):
    spec = harness.load_spec()
    entry = _by_name(spec)[name]
    assert entry == {"name": name, "unit": "ms", "better": "lower",
                     "source": "device_trace", "layer": layer,
                     "moves": "train_imgs_per_s", "workloads": cells}
    assert _has_reader(name)
    moved = {m["name"]: m for m in spec["end_to_end"]}["train_imgs_per_s"]
    for cell in cells:
        assert cell in moved["workloads"] and name in _listed(spec, cell)
    assert layer in {m["layer"] for m in spec["per_layer"]
                     if m["name"] not in [n for n, _, _ in NEW]}


def test_pr24_span_entries_by_name():
    """What `test_bench_lm_rehearsal.py::
    test_pr24_span_entries_are_still_whole` holds beyond PR 36 (it asserts
    that SPADE's cell reads 16 metrics, PR 24's ten the last): each of
    the ten whole, with its reader, SPADE's cell first, and SPADE's cell
    reads all ten in PR 24's order."""
    import test_bench_program_spans as pr24

    spec = harness.load_spec()
    by_name = _by_name(spec)
    layers = {m["layer"] for m in spec["per_layer"]
              if m["name"] not in pr24.NEW}
    for name in pr24.NEW:
        m = by_name[name]
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["workloads"][0] == pr24.CELL
        assert m["layer"] in layers | {"set-up"}
        assert _has_reader(name)
    assert {by_name[n]["moves"] for n in pr24.NEW} == {"train_imgs_per_s",
                                                       "setup_s"}
    assert [n for n in _listed(spec, pr24.CELL)
            if n in pr24.NEW] == pr24.NEW
    assert [m["name"] for m in spec["per_layer"]
            if m["name"] in pr24.NEW] == pr24.NEW


@pytest.mark.parametrize("which", ["glm", "solar"])
def test_a_models_own_readers_by_name(which):
    """What the two `test_the_new_readers_are_declared_for_the_new_cell_
    alone` hold beyond PR 36 (each asserts that its cell reads 27
    metrics): a model's own readers name its cell alone, its cell reads
    the shared ones and none of another model's."""
    import test_bench_glm_rehearsal as pr31
    import test_bench_solar_rehearsal as pr34

    module, config, others = {
        "glm": (pr31, "glm4_7_flash", pr34.NEW_READERS),
        "solar": (pr34, "solar_open2_250b", pr31.NEW_READERS)}[which]
    spec = harness.load_spec()
    by_name = _by_name(spec)
    for name in module.NEW_READERS:
        assert by_name[name]["workloads"] == [module.CELL]
        assert by_name[name]["moves"] == "train_imgs_per_s"
        assert by_name[name]["layer"] == "ops"
        assert _has_reader(name)
    for name in pr34.NEW_READERS:
        assert by_name[name]["source"] == "device_trace"
    listed = set(_listed(spec, module.CELL))
    assert set(module.NEW_READERS) <= listed
    assert not {"dis_step_ms", "dispatch_ms.train", "ssd_scan_ms.lm",
                "ssd_scan_roofline.lm", *others} & listed
    assert {"mfu.train", "gen_step_ms", "device_idle.train",
            "hbm_peak_gb.train", "attn_scores_roofline.lm",
            "moe_experts_roofline.lm", "moe_held_assignments.lm"} <= listed
    assert [m["name"] for m in harness.metrics_of(
        spec, module.CELL, "end_to_end")] == ["train_imgs_per_s", "setup_s"]
    cell = {c["name"]: c for c in spec["workloads"]}[module.CELL]
    assert cell["chips"] == 1 and cell["config"] == config


def test_pr27_and_pr31_reader_entries_by_name():
    """What `test_bench_solar_rehearsal.py::
    test_pr27_and_pr31_reader_entries_are_still_whole` holds beyond PR 36
    (it asserts that Nemotron's cell reads 26 metrics and GLM's 27): the
    token cells found by their driver; each of PR 27's twelve lists
    Nemotron's cell first and token cells only, in the cells' order;
    Nemotron's cell reads the twelve and nothing a later token model
    brought for itself."""
    import test_bench_glm_rehearsal as pr31
    import test_bench_lm_rehearsal as pr27
    import test_bench_solar_rehearsal as pr34

    spec = harness.load_spec()
    token_cells = [c["name"] for c in spec["workloads"] if harness.read_json(
        os.path.join(ROOT, "benchmark", "workloads", c["name"] + ".json"))[
            "driver"] in pr34.TOKEN_DRIVERS]
    assert token_cells[:3] == [NEMOTRON, GLM, SOLAR]
    by_name = _by_name(spec)
    assert len(pr27.NEW_READERS) == 12
    for name in pr27.NEW_READERS:
        listed = by_name[name]["workloads"]
        assert listed[0] == NEMOTRON
        assert listed == [c for c in token_cells if c in listed]
        assert by_name[name]["moves"] == "train_imgs_per_s"
        assert _has_reader(name)
    for name in pr31.NEW_READERS:
        assert by_name[name]["workloads"] == [GLM]
    nemotron = set(_listed(spec, NEMOTRON))
    assert set(pr27.NEW_READERS) <= nemotron
    assert "dis_step_ms" not in nemotron
    assert "dispatch_ms.train" not in nemotron
    # what lists a later token cell FIRST is that model's own
    later = {m["name"] for m in spec["per_layer"]
             if m.get("workloads", [NEMOTRON])[0] in token_cells[1:]}
    assert set(pr31.NEW_READERS) | set(pr34.NEW_READERS) <= later
    assert not later & nemotron
    glm = set(_listed(spec, GLM))
    assert set(pr31.NEW_READERS) <= glm
    assert not set(pr34.NEW_READERS) & glm
    assert {"mfu.train", "gen_step_ms", "device_idle.train",
            "hbm_peak_gb.train"} <= nemotron & glm
