"""Graph auditor (ISSUE 12): jaxpr/HLO static analysis + AST lint.

Three layers:

- deliberately-bad toy programs, one per audit rule — each violation
  must NAME its jaxpr path (or donated-arg path), because an
  unlocatable verdict is useless to the person fixing it;
- AST-rule toys incl. the allowlist contract (reasoned allow
  suppresses; a reasonless allow is itself a violation);
- clean passes: every trainer family's real step programs audit to
  zero violations (video families are slow-marked), and the repo's own
  sources pass the lint — the same gates CI runs.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from imaginaire_tpu import analysis
from imaginaire_tpu.analysis import (
    ast_rules,
    collectives,
    donation,
    hlo_audit,
    islands,
    jaxpr_audit,
)


def _trace(fn, *args):
    return jax.jit(fn).trace(*args)


def _rules(violations):
    return [v.rule for v in violations]


# ------------------------------------------------------------ jaxpr rules


class TestJaxprRules:
    def test_host_callback_named(self):
        def bad(x):
            jax.debug.print("x={x}", x=x)
            return x * 2

        tr = _trace(bad, jnp.ones((4,)))
        viols, stats = jaxpr_audit.audit_jaxpr("toy", tr.jaxpr)
        assert "host_callback" in _rules(viols)
        v = next(v for v in viols if v.rule == "host_callback")
        assert "eqns[" in v.path, v.path  # names the offending equation
        assert stats["callback_eqns"] >= 1

    def test_pure_callback_named(self):
        def bad(x):
            return jax.pure_callback(
                lambda a: np.asarray(a) * 2,
                jax.ShapeDtypeStruct((4,), np.float32), x)

        tr = _trace(bad, jnp.ones((4,), jnp.float32))
        viols, _ = jaxpr_audit.audit_jaxpr("toy", tr.jaxpr)
        v = next(v for v in viols if v.rule == "host_callback")
        assert "eqns[" in v.path

    def test_f64_leak_named(self):
        with jax.enable_x64():
            def bad(x):
                return jnp.sum(x.astype(jnp.float64))

            tr = _trace(bad, jnp.ones((4,), jnp.float32))
            viols, stats = jaxpr_audit.audit_jaxpr("toy", tr.jaxpr)
        assert "f64_leak" in _rules(viols)
        v = next(v for v in viols if v.rule == "f64_leak")
        assert "eqns[" in v.path
        assert stats["f64_eqns"] >= 1

    def test_island_cast_named(self):
        def bad(x):
            with islands.scope("norm_stats"):
                m = jnp.mean(x.astype(jnp.float32))
                return m.astype(jnp.bfloat16)  # cast INSIDE the island

        tr = _trace(bad, jnp.ones((4, 4), jnp.bfloat16))
        viols, _ = jaxpr_audit.audit_jaxpr("toy", tr.jaxpr)
        assert "island_cast" in _rules(viols)
        v = next(v for v in viols if v.rule == "island_cast")
        assert "eqns[" in v.path
        assert "norm_stats" in v.message

    def test_island_exit_cast_outside_is_clean(self):
        def good(x):
            with islands.scope("norm_stats"):
                m = jnp.mean(x.astype(jnp.float32))
            return m.astype(jnp.bfloat16)  # exit cast OUTSIDE

        tr = _trace(good, jnp.ones((4, 4), jnp.bfloat16))
        viols, _ = jaxpr_audit.audit_jaxpr("toy", tr.jaxpr)
        assert "island_cast" not in _rules(viols)

    def test_unregistered_island_scope_raises(self):
        with pytest.raises(KeyError, match="not registered"):
            with islands.scope("no_such_island"):
                pass

    def test_island_guard(self):
        islands.guard("norm_stats", ok=jnp.ones((2,), jnp.float32))
        with pytest.raises(islands.IslandViolation, match="float32"):
            islands.guard("norm_stats",
                          bad=jnp.ones((2,), jnp.bfloat16))

    def test_baked_constant_named(self):
        big = jnp.asarray(np.ones((256, 256), np.float32))  # 256 KiB

        def bad(x):
            return x + big

        tr = _trace(bad, jnp.ones((256, 256), jnp.float32))
        viols, stats = jaxpr_audit.audit_jaxpr(
            "toy", tr.jaxpr, const_bytes_limit=64 << 10)
        assert "baked_constant" in _rules(viols)
        v = next(v for v in viols if v.rule == "baked_constant")
        assert "f32" in v.message or "float32" in v.message
        assert stats["const_bytes"] >= 256 * 1024

    def test_small_constants_pass(self):
        small = jnp.ones((8,), jnp.float32)

        def good(x):
            return x + small

        tr = _trace(good, jnp.ones((8,), jnp.float32))
        viols, _ = jaxpr_audit.audit_jaxpr("toy", tr.jaxpr,
                                           const_bytes_limit=64 << 10)
        assert not viols


# ---------------------------------------------------- donation + HLO view


class TestDonation:
    def test_dead_donation_named(self):
        def f(a, b, c):
            return a + c  # b is donated but unused

        jitted = jax.jit(f, donate_argnums=(0, 1))
        args = (jnp.ones((8,)), jnp.ones((8,)), jnp.ones((8,)))
        traced = jitted.trace(*args)
        lowered = traced.lower()
        compiled = lowered.compile()
        hlo = compiled.as_text()
        viols, summary = donation.audit_donation(
            "toy", compiled, traced.jaxpr, lowered, hlo)
        assert summary["declared"] == 2
        assert summary["dead_count"] == 1
        v = next(v for v in viols if v.rule == "dead_donation")
        assert "[0][1]" in v.path  # names WHICH donated arg is dead
        assert summary["aliased"] >= 1  # arg a still aliases

    def test_live_donations_clean(self):
        def f(a, b):
            return a + b, a * b

        jitted = jax.jit(f, donate_argnums=(0, 1))
        args = (jnp.ones((8,)), jnp.ones((8,)))
        traced = jitted.trace(*args)
        lowered = traced.lower()
        compiled = lowered.compile()
        viols, summary = donation.audit_donation(
            "toy", compiled, traced.jaxpr, lowered, compiled.as_text())
        assert summary["dead_count"] == 0
        assert not viols

    def test_alias_map_parse(self):
        hlo = ("HloModule jit_f, input_output_alias={ {0}: (0, {}, "
               "may-alias), {1}: (2, {}, must-alias) }\n")
        assert hlo_audit.aliased_param_indices(hlo) == {0, 2}

    def test_collective_stats(self):
        hlo = ("  ar = f32[1024]{0} all-reduce(p), replica_groups={}\n"
               "  ag.1 = bf16[2,64]{1,0} all-gather(x), dimensions={0}\n")
        stats = hlo_audit.collective_stats(hlo)
        assert stats["all-reduce"]["count"] == 1
        assert stats["all-reduce"]["bytes"] == 4096
        assert stats["all-gather"]["bytes"] == 256

    def test_jaxpr_collectives(self):
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:8]), ("d",))
        from imaginaire_tpu.parallel import shard_map
        from jax.sharding import PartitionSpec

        fn = shard_map(lambda x: jax.lax.psum(x, "d"), mesh=mesh,
                       in_specs=PartitionSpec("d"),
                       out_specs=PartitionSpec())
        tr = _trace(fn, jnp.ones((8, 4)))
        found = collectives.jaxpr_collectives(tr.jaxpr)
        assert "psum" in found


# ------------------------------------------------------------- audit_program


class TestAuditProgram:
    def test_full_report_shape(self):
        def f(a, b):
            return a + 1.0  # b donated-dead

        jitted = jax.jit(f, donate_argnums=(0, 1))
        args = (jnp.ones((4,)), jnp.ones((4,)))
        traced = jitted.trace(*args)
        lowered = traced.lower()
        compiled = lowered.compile()
        audit = analysis.audit_program("toy", traced=traced,
                                       lowered=lowered,
                                       compiled=compiled)
        assert audit["violation_count"] == 1
        assert audit["violations"][0]["rule"] == "dead_donation"
        assert audit["donation"]["dead_count"] == 1
        assert "collectives" in audit
        assert "errors" not in audit or not audit["errors"]

    def test_trace_only(self):
        tr = _trace(lambda x: x * 2, jnp.ones((4,)))
        audit = analysis.audit_program("toy", traced=tr,
                                       include_hlo=False)
        assert audit["violation_count"] == 0


# ---------------------------------------------------------------- AST rules


def _lint(src, rel="imaginaire_tpu/models/toy.py"):
    viols, sups = ast_rules.lint_source(src, rel)
    return [v.rule for v in viols], sups


class TestAstRules:
    def test_bare_jit(self):
        rules, _ = _lint("import jax\nf = jax.jit(lambda x: x)\n")
        assert "bare-jit" in rules

    def test_bare_jit_allowed_in_ledger_home(self):
        rules, _ = _lint("import jax\nf = jax.jit(lambda x: x)\n",
                         rel="imaginaire_tpu/telemetry/xla_obs.py")
        assert "bare-jit" not in rules

    def test_host_sync(self):
        rules, _ = _lint(
            "import jax\n\ndef f(x):\n    return jax.device_get(x)\n",
            rel="imaginaire_tpu/trainers/toy.py")
        assert "host-sync" in rules

    def test_untimed_barrier(self):
        rules, _ = _lint(
            "from jax.experimental import multihost_utils\n"
            "multihost_utils.sync_global_devices('x')\n",
            rel="imaginaire_tpu/trainers/toy.py")
        assert "untimed-barrier" in rules

    def test_numpy_random_in_traced_code(self):
        rules, _ = _lint(
            "import numpy as np\n\ndef f(x):\n"
            "    return x + np.random.rand(4)\n")
        assert "numpy-random" in rules

    def test_mutable_default_pytree(self):
        rules, _ = _lint(
            "from flax import linen as nn\n\n"
            "class M(nn.Module):\n    scales: list = []\n")
        assert "mutable-default-pytree" in rules

    def test_allow_with_reason_suppresses(self):
        rules, sups = _lint(
            "import jax\n"
            "# lint: allow(bare-jit) -- toy reason\n"
            "f = jax.jit(lambda x: x)\n")
        assert "bare-jit" not in rules
        assert sups and sups[0].reason == "toy reason"

    def test_allow_without_reason_is_a_violation(self):
        rules, _ = _lint(
            "import jax\n"
            "# lint: allow(bare-jit)\n"
            "f = jax.jit(lambda x: x)\n")
        assert "allowlist-reason" in rules

    def test_repo_is_lint_clean(self):
        import os

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        viols, sups = ast_rules.lint_repo(root)
        assert not viols, [v.as_dict() for v in viols]
        # zero silent suppressions: every allow carries its reason
        assert all(s.reason for s in sups)


# ------------------------------------------------- real-program clean pass


IMAGE_FAMILIES = ("spade", "pix2pixHD", "unit", "munit", "funit",
                  "coco_funit")
VIDEO_FAMILIES = ("vid2vid", "fs_vid2vid", "wc_vid2vid")


def _assert_family_clean(family):
    from imaginaire_tpu.analysis import programs

    audits = programs.audit_family(family)
    assert audits, f"no programs traced for {family}"
    for label, audit in audits.items():
        assert audit.get("violation_count", 0) == 0, \
            f"{family}/{label}: {audit['violations']}"
        assert not audit.get("errors"), \
            f"{family}/{label} audit errored: {audit['errors']}"


@pytest.mark.parametrize("family", IMAGE_FAMILIES)
def test_family_step_programs_clean(family):
    _assert_family_clean(family)


@pytest.mark.slow
@pytest.mark.parametrize("family", VIDEO_FAMILIES)
def test_video_family_step_programs_clean(family):
    _assert_family_clean(family)


def test_aux_programs_clean():
    from imaginaire_tpu.analysis import programs

    for label, traced in programs.trace_aux_programs():
        audit = analysis.audit_program(label, traced=traced,
                                       include_hlo=False)
        assert audit["violation_count"] == 0, \
            f"{label}: {audit['violations']}"
