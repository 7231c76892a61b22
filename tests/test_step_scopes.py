"""The step programs' named scopes and the compile ledger's instruction
names (ISSUE 36): the unit-test SPADE YAML's two step programs and the
token unit-test YAML's one, compiled once each, hold every scope in their
optimized text's `op_name`s; the ledger answers their maps after the
trainer is gone and counts the scoped instructions in its entry; an
instruction printed over several lines keeps its `op_name`; with
telemetry off nothing is kept."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from imaginaire_tpu import telemetry
from imaginaire_tpu.config import Config
from imaginaire_tpu.registry import resolve
from imaginaire_tpu.telemetry import core as tcore
from imaginaire_tpu.telemetry import xla_obs

UNIT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                    "configs", "unit_test")
SDS = jax.ShapeDtypeStruct
STEP = {"step/cast", "step/clip", "step/optim", "step/guard", "step/health"}
GAN = {"gan/G", "gan/D", "gan/loss/adversarial"}
LM = {"lm/block/norm", "lm/block/residual", "lm/final_norm"}


@pytest.fixture(scope="module")
def compiled(tmp_path_factory):
    """{label-of-this-test: (ledger entry, {instruction: op_name})} of the
    three step programs, compiled once for shapes alone, with telemetry
    on and the persistent cache keyed WITH names (by its default JAX keys
    a program without them, and an entry another build of this repo left
    would serve that build's names)."""
    old_tm, old_key = tcore._TELEMETRY, \
        jax.config.jax_compilation_cache_include_metadata_in_key
    xla_obs._reset_for_tests()
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    logdir = str(tmp_path_factory.mktemp("step_scopes"))
    telemetry.configure(None, logdir=logdir, enabled=True)
    out = {}
    try:
        for name, data in (
                ("spade", {"images": SDS((1, 256, 256, 3), jnp.float32),
                           "label": SDS((1, 256, 256, 14), jnp.float32)}),
                ("hybrid_lm", {"tokens": SDS((2, 64), jnp.int32)})):
            cfg = Config(os.path.join(UNIT, name + ".yaml"))
            cfg.logdir = logdir
            # every scope of the step's tail in one compile: a cast to
            # bfloat16, a clip and an averaged generator
            cfg.trainer.mixed_precision = {"enabled": True,
                                           "compute_dtype": "bfloat16"}
            cfg.gen_opt.clip_grad_norm = 10.0
            if name == "spade":
                cfg.trainer.model_average = True
                cfg.dis_opt.clip_grad_norm = 10.0
            else:
                cfg.data.seq_len = 64
            trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
            trainer._place_state = lambda state, data=None: state
            state = jax.eval_shape(trainer._init_state,
                                   jax.random.PRNGKey(0), data)
            programs = [trainer._jit_gen_step]
            if trainer.net_D is not None:
                programs.append(trainer._jit_dis_step)
            for program in programs:
                program.aot_compile(state, data)
                out[f"{name}/{program.label}"] = (
                    xla_obs.ledger().records[-1],
                    # the ledger's by label; the next trainer's program of
                    # the same label replaces it
                    dict(xla_obs.ledger().label_op_names[program.label]))
            del trainer, programs, program, state
        out["labels after the trainers are gone"] = sorted(
            xla_obs.ledger().label_op_names)
        yield out
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          old_key)
        tcore._TELEMETRY.shutdown()
        tcore._TELEMETRY = old_tm
        xla_obs._reset_for_tests()


def _scopes(op_names):
    from benchmark.lib import step_scopes

    return {scope for op_name in op_names.values()
            for scope in step_scopes.SCOPE.findall(op_name)}


@pytest.mark.parametrize("program,want", [
    ("spade/gen_step", STEP | GAN | {"step/ema", "gan/loss/perceptual"}),
    ("spade/dis_step", STEP | GAN),
    # the token trainer casts nothing at the step's top: the model casts
    # each kernel where it uses it
    ("hybrid_lm/gen_step", (STEP - {"step/cast"}) | LM | {
        "lm/embed", "lm/head_loss", "lm/moe/experts"}),
])
def test_every_scope_is_in_the_optimized_text(compiled, program, want):
    entry, op_names = compiled[program]
    found = _scopes(op_names)
    assert want <= found, sorted(want - found)
    # D's step runs no perceptual loss and averages nothing
    if program == "spade/dis_step":
        assert not {"gan/loss/perceptual", "step/ema"} & found
    assert entry["scoped_instructions"] == xla_obs.scoped_instructions(
        op_names) > 0
    assert "op_names" not in entry     # the map is the ledger's, not the line's
    json.dumps(entry)


def test_the_ledger_answers_after_the_trainer_is_gone(compiled):
    assert compiled["labels after the trainers are gone"] == [
        "dis_step", "gen_step"]
    entry, op_names = compiled["spade/dis_step"]
    # the D step's whole G forward stands under gan/G of THAT program
    assert any("gan/G" in v and "transpose(" not in v
               for v in op_names.values())
    assert not any("gan/G" in v and "transpose(" in v
                   for v in op_names.values())


def test_the_meta_and_the_report_show_the_count(compiled):
    from imaginaire_tpu.telemetry.report import render_report

    entry, _ = compiled["spade/gen_step"]
    events = [{"kind": "counter", "name": "xla/compile/gen_step/count",
               "value": 1, "t": 0.0},
              {"kind": "meta", "name": "xla_compile/gen_step", "t": 0.0,
               **{k: v for k, v in entry.items() if k != "kind"}}]
    assert (f"{entry['scoped_instructions']} instructions under named "
            "scopes") in render_report(events)


THREE_LINES = """HloModule jit_f
ENTRY %main.1 (x.1: f32[8,8]) -> f32[8,8] {
  %x.1 = f32[8,8]{1,0} parameter(0), metadata={op_name="x"}
  %custom-call.5 = f32[8,8]{1,0} custom-call(%x.1), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
 "block_q": 1024,
 "sizes": {"k": 512}
}}, metadata={op_name="jit(f)/jvp(G)/layer_0/mixer/lm/attn/scores/pallas_call" stack_frame_id=2}
  %add.3 = f32[8,8]{1,0} add(%custom-call.5, %x.1), backend_config={"note":"a stray { in a string"}
  ROOT %copy.2 = f32[8,8]{1,0} copy(%add.3), metadata={
    op_name="jit(f)/step/optim/mul"
    stack_frame_id=3}
}
"""


def test_an_instruction_over_three_lines_keeps_its_op_name():
    names = xla_obs.instruction_op_names(THREE_LINES)
    assert names == {
        "x.1": "x",
        "custom-call.5":
            "jit(f)/jvp(G)/layer_0/mixer/lm/attn/scores/pallas_call",
        "copy.2": "jit(f)/step/optim/mul"}
    assert xla_obs.scoped_instructions(names) == 2


def test_the_compilers_own_three_line_print_is_read():
    """What XLA itself prints for an instruction whose frontend attribute
    holds a JSON string over several lines."""
    from jax.experimental.xla_metadata import set_xla_metadata

    def f(x):
        with jax.named_scope("lm/attn/scores"):
            with set_xla_metadata(kernel_metadata=json.dumps(
                    {"a": 1, "b": {"c": 2}}, indent=1)):
                return jnp.sin(x) @ x

    text = jax.jit(f).lower(jnp.ones((8, 8))).compile().as_text()
    head = [line for line in text.splitlines() if "dot(" in line]
    assert head and "op_name" not in head[0]     # it IS on a later line
    names = xla_obs.instruction_op_names(text)
    dots = [v for k, v in names.items() if k.startswith("dot")]
    assert dots and all("lm/attn/scores" in v for v in dots)


def test_with_telemetry_off_nothing_is_kept():
    old_tm = tcore._TELEMETRY
    xla_obs._reset_for_tests()
    tcore._TELEMETRY = tcore.Telemetry(enabled=False)
    try:
        def f(x):
            with jax.named_scope("step/optim"):
                return x * 2.0

        program = xla_obs.compiled_program("toy", f)
        program(jnp.ones((4,)))
        entry = xla_obs.ledger().records[-1]
        assert entry["label"] == "toy"
        assert "scoped_instructions" not in entry
        assert xla_obs.ledger().label_op_names == {}
        # and with the ledger itself off, not even an entry
        xla_obs._reset_for_tests()
        xla_obs.settings().enabled = False
        xla_obs.compiled_program("toy", f)(jnp.ones((4,)))
        assert xla_obs.ledger().records == []
        assert xla_obs.ledger().label_op_names == {}
    finally:
        tcore._TELEMETRY = old_tm
        xla_obs._reset_for_tests()


def test_trace_at_step_leaves_the_maps_beside_the_trace(tmp_path,
                                                        monkeypatch):
    old_tm = tcore._TELEMETRY
    xla_obs._reset_for_tests()
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda path, **kw: calls.append(("start", path)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    try:
        tm = telemetry.configure(None, logdir=str(tmp_path), enabled=True,
                                 trace_at_step=1, trace_num_steps=1)

        def f(x):
            with jax.named_scope("step/optim"):
                return x * 2.0

        xla_obs.compiled_program("gen_step", f)(jnp.ones((4,)))
        tm._maybe_trace(1)
        tm._maybe_trace(2)
        assert [c[0] for c in calls] == ["start", "stop"]
        with open(tmp_path / "trace" / "scopes.json") as fh:
            maps = json.load(fh)
        assert list(maps) == ["gen_step"]
        assert any("step/optim" in v for v in maps["gen_step"].values())
    finally:
        tcore._TELEMETRY.shutdown()
        tcore._TELEMETRY = old_tm
        xla_obs._reset_for_tests()
