"""BENCHMARK.json against the rules a driver checks before any run, and
against the files the harness finds by name."""

import json
import os
import re

import pytest

from bench_rehearsal_util import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_command_stays_inside_paths(spec):
    assert len(spec["command"]) <= 32
    for word in spec["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p + "/") for p in spec["paths"])


def test_names_and_units(spec):
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    names += [c["name"] for c in spec["configs"]]
    names += [w["name"] for w in spec["workloads"]]
    assert all(NAME.match(n) for n in names), names
    for group in ("end_to_end", "per_layer"):
        seen = [m["name"] for m in spec[group]]
        assert len(seen) == len(set(seen))
        for m in spec[group]:
            assert UNIT.match(m["unit"]) and m["better"] in ("lower",
                                                             "higher")
            assert m["source"] in SOURCES


def test_metric_entries_have_just_their_keys(spec):
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_setup_s_is_everywhere(spec):
    setup = [m for m in spec["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]


def test_cells(spec):
    configs = {c["name"] for c in spec["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    assert {w["config"] for w in spec["workloads"]} == configs
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 4)


def test_every_cell_reports_enough(spec):
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    for w in spec["workloads"]:
        mine = [m for m in spec["end_to_end"] if m["name"] != "setup_s"
                and w["name"] in m.get("workloads", [w["name"]])]
        assert mine, w["name"]
        layers = [m for m in spec["per_layer"]
                  if w["name"] in m.get("workloads", [w["name"]])]
        assert layers, w["name"]
        for m in layers:
            moved = e2e[m["moves"]]
            assert w["name"] in moved.get("workloads", [w["name"]])


def test_candidates_are_whole_and_apart(spec):
    """Cells measured but not admitted: complete entries, none of them in
    BENCHMARK.json, each with its files."""
    bench = os.path.join(ROOT, "benchmark")
    with open(os.path.join(bench, "candidates.json")) as f:
        extra = json.load(f)
    for group in ("workloads", "end_to_end", "per_layer"):
        assert not ({e["name"] for e in extra[group]}
                    & {e["name"] for e in spec[group]})
    for w in extra["workloads"]:
        assert os.path.exists(os.path.join(bench, "workloads",
                                           w["name"] + ".json"))
    for m in extra["end_to_end"] + extra["per_layer"]:
        assert m["workloads"] and os.path.exists(
            os.path.join(bench, "metrics", m["name"] + ".py"))


def test_files_the_harness_finds_by_name(spec):
    bench = os.path.join(ROOT, "benchmark")
    for c in spec["configs"]:
        assert any(c["file"].startswith(p + "/") for p in spec["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        assert config["source"] == c["source"]
        assert config["reduced"] == c["reduced"] and "assumed" in config
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
    for w in spec["workloads"]:
        with open(os.path.join(bench, "workloads", w["name"] + ".json")) as f:
            workload = json.load(f)
        assert os.path.exists(os.path.join(
            bench, "drivers", workload["driver"] + ".py"))
        assert "limits" in workload
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert os.path.exists(os.path.join(bench, "metrics",
                                           m["name"] + ".py")), m["name"]
