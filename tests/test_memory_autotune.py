"""memory_autotune pure core against a fake ledger (ISSUE 10): candidate
enumeration, pareto filtering, tie-breaking, and the budget refusal —
none of which should need an XLA compile to be trusted."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "scripts"))

import memory_autotune as ma  # noqa: E402


def _row(name, bs=1, temp=100, flops=10.0, footprint=None, **kw):
    return dict({"name": name, "batch_size": bs, "temp_bytes": temp,
                 "flops": flops,
                 "footprint_bytes": (footprint if footprint is not None
                                     else (temp or 0) + 50)}, **kw)


class TestEnumeration:
    def test_full_grid(self):
        cands = ma.enumerate_candidates(
            ["none", "blocks"], ["float32", "bfloat16"], [1, 4])
        assert len(cands) == 8
        assert cands[0] == {"name": "none/float32/bs1",
                            "remat_policy": "none",
                            "compute_dtype": "float32", "batch_size": 1}
        assert {c["name"] for c in cands} >= {"blocks/bfloat16/bs4",
                                              "none/bfloat16/bs1"}

    def test_policy_validated_by_shared_resolver(self):
        # same registry, same error message as the model-side knob
        with pytest.raises(ValueError, match="remat"):
            ma.enumerate_candidates(["block"], ["float32"], [1])

    def test_bad_dtype_and_bs_loud(self):
        with pytest.raises(ValueError, match="compute dtype"):
            ma.enumerate_candidates(["none"], ["float16"], [1])
        with pytest.raises(ValueError, match="batch size"):
            ma.enumerate_candidates(["none"], ["float32"], [0])

    def test_modulation_axis_opt_in(self):
        # ISSUE 16: the fused-SPADE axis doubles the grid and suffixes
        # candidate names; omitting it keeps the PR-9 name shape
        plain = ma.enumerate_candidates(["none"], ["float32"], [4])
        assert [c["name"] for c in plain] == ["none/float32/bs4"]
        assert "spade_modulation" not in plain[0]
        both = ma.enumerate_candidates(["none"], ["float32"], [4],
                                       modulations=["fused", "unfused"])
        assert [c["name"] for c in both] \
            == ["none/float32/bs4/fused", "none/float32/bs4/unfused"]
        assert [c["spade_modulation"] for c in both] \
            == ["fused", "unfused"]

    def test_bad_modulation_loud(self):
        with pytest.raises(ValueError, match="modulation"):
            ma.enumerate_candidates(["none"], ["float32"], [1],
                                    modulations=["kernel"])


class TestFakeLedgerRows:
    def test_row_from_ledger_reduces_executables(self):
        cand = {"name": "blocks/bfloat16/bs4", "remat_policy": "blocks",
                "compute_dtype": "bfloat16", "batch_size": 4}
        row = ma.row_from_ledger(
            cand, "spade", (512, 512),
            {"gen_step": {"temp_bytes": 900, "total_bytes": 1500},
             "dis_step": {"temp_bytes": 400, "total_bytes": 700}},
            {"gen_step": 2e12, "dis_step": 1e12},
            state_bytes=300)
        assert row["temp_bytes"] == 900      # worst executable, not sum
        assert row["flops"] == 3e12          # dis + gen both run
        assert row["footprint_bytes"] == 1800  # worst total + state
        assert row["error"] is None
        assert row["family"] == "spade" and row["batch_size"] == 4

    def test_failed_compile_stays_unmeasured(self):
        cand = {"name": "none/float32/bs1", "remat_policy": "none",
                "compute_dtype": "float32", "batch_size": 1}
        row = ma.row_from_ledger(cand, "spade", (512, 512),
                                 {"gen_step": {}}, {}, state_bytes=0)
        assert row["temp_bytes"] is None and row["flops"] is None
        assert "failed" in row["error"]
        assert ma.pareto_frontier([row]) == []


class TestPareto:
    def test_dominated_rows_drop(self):
        rows = [_row("a", temp=100, flops=10.0),
                _row("b", temp=50, flops=20.0),
                _row("c", temp=120, flops=30.0),   # dominated by a
                _row("d", temp=80, flops=15.0)]
        assert [r["name"] for r in ma.pareto_frontier(rows)] \
            == ["b", "d", "a"]

    def test_exact_ties_both_survive(self):
        rows = [_row("a", temp=50, flops=10.0),
                _row("b", temp=50, flops=10.0)]
        assert [r["name"] for r in ma.pareto_frontier(rows)] == ["a", "b"]

    def test_unmeasured_never_on_frontier(self):
        rows = [_row("a", temp=None, flops=None),
                _row("b", temp=50, flops=10.0)]
        assert [r["name"] for r in ma.pareto_frontier(rows)] == ["b"]

    def test_legalized_rows_never_on_frontier(self):
        # ISSUE 16: a CPU-legalized bf16 row may look pareto-optimal but
        # measured a different program than the dtype it claims
        rows = [_row("bf16", temp=10, flops=1.0, legalized=True),
                _row("f32", temp=50, flops=10.0)]
        assert [r["name"] for r in ma.pareto_frontier(rows)] == ["f32"]


class TestRecommend:
    def test_bigger_batch_wins_over_smaller_temp(self):
        # the point of the autotuner: spend the savings as batch size
        rows = [_row("small-temp", bs=1, temp=10, flops=1.0),
                _row("big-batch", bs=4, temp=90, flops=9.0)]
        assert ma.recommend(rows)["name"] == "big-batch"

    def test_tie_breaks_temp_then_flops_then_name(self):
        rows = [_row("b", bs=2, temp=50, flops=5.0),
                _row("a", bs=2, temp=50, flops=5.0),
                _row("c", bs=2, temp=50, flops=4.0),
                _row("d", bs=2, temp=60, flops=1.0)]
        assert ma.recommend(rows)["name"] == "c"      # min flops at min temp
        rows = rows[:2]
        assert ma.recommend(rows)["name"] == "a"      # name order last

    def test_budget_filters_feasible_set(self):
        rows = [_row("fits", bs=1, temp=40, flops=9.0, footprint=80),
                _row("oom", bs=8, temp=10, flops=1.0, footprint=200)]
        # the bigger batch would win, but it doesn't fit the budget
        got = ma.recommend(rows, bytes_limit=100, mem_budget_frac=0.9)
        assert got["name"] == "fits"

    def test_refusal_when_nothing_fits(self):
        rows = [_row("a", footprint=200), _row("b", footprint=300)]
        with pytest.raises(ma.MemoryBudgetError, match="no candidate"):
            ma.recommend(rows, bytes_limit=100, mem_budget_frac=0.9)

    def test_refusal_when_nothing_measured(self):
        with pytest.raises(ma.MemoryBudgetError):
            ma.recommend([_row("a", temp=None, flops=None)])

    def test_no_limit_means_all_feasible(self):
        rows = [_row("huge", bs=4, footprint=10**15)]
        assert ma.recommend(rows, bytes_limit=None)["name"] == "huge"

    def test_legalized_rows_excluded_from_recommendation(self):
        rows = [_row("bf16-legal", bs=8, temp=10, flops=1.0,
                     legalized=True),
                _row("f32-real", bs=4, temp=90, flops=9.0)]
        assert ma.recommend(rows)["name"] == "f32-real"
        with pytest.raises(ma.MemoryBudgetError):
            ma.recommend([rows[0]])


class TestProfileRows:
    def test_winner_and_pareto_marked(self):
        rows = [_row("blocks/bfloat16/bs4", bs=4, temp=2**30, flops=1e12,
                     remat_policy="blocks", compute_dtype="bfloat16"),
                _row("none/float32/bs4", bs=4, temp=3 * 2**30, flops=9e11,
                     remat_policy="none", compute_dtype="float32")]
        lines = ma.profile_rows("spade", (512, 512), rows,
                                ["blocks/bfloat16/bs4", "none/float32/bs4"],
                                "blocks/bfloat16/bs4")
        assert any("**winner**" in ln and "blocks" in ln for ln in lines)
        assert all(ln.startswith("| spade 512x512 |") for ln in lines)

    def test_legalized_rows_marked_in_table(self):
        rows = [_row("none/bfloat16/bs1", bs=1, temp=2**30, flops=1e12,
                     remat_policy="none", compute_dtype="bfloat16",
                     legalized=True)]
        lines = ma.profile_rows("spade", (512, 512), rows, [], None)
        assert len(lines) == 1 and "legalized" in lines[0]
