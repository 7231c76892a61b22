"""Compile for a described TPU v5e, without the chip (ISSUE 45): the fused
attention kernel's two passes at seven query heads a key-value head under
the cell's window of 4,096, the held experts' grouped products at
2560 x 768, and `configs/projects/smallthinker/21b_a3b_ep4_share.yaml`'s
whole training step at its own shapes. Nothing runs. The fixtures are
`test_tpu_compile.py`'s."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_tpu_compile import (ROOT, _compile, _sds,  # noqa: F401
                              no_persistent_cache, one_chip, topo)


def _custom_calls(compiled):
    calls = [line.split("=")[0].strip().lstrip("%").split(".")[0]
             for line in compiled.as_text().splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    return {name: calls.count(name) for name in set(calls)}


@pytest.mark.parametrize("window", [4096, None])
def test_the_kernel_compiles_at_seven_heads_a_key_value_head(one_chip,
                                                              window):
    """One sequence of 16,384, 28 query heads on 4 of 128, under the
    window layers' 4,096 and as the full layer: the two passes compile,
    each one custom call (under the window the grid's innermost axis is 5
    steps long), the backward looping a group's seven heads under one
    key-value head's ``dk`` and ``dv`` in 16 MiB of VMEM."""
    from imaginaire_tpu.ops import attention
    from imaginaire_tpu.ops.pallas import causal_attention_kernel as kernel

    q = _sds((1, 16384, 28, 128), jnp.bfloat16, one_chip)
    kv = _sds((1, 16384, 4, 128), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        out = attention.fused_causal_attention(q, k, v, attention.TILES,
                                               False, window)
        return jnp.sum(out.astype(jnp.float32))

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    calls = _custom_calls(compiled)
    assert len(calls) == 2 and set(calls.values()) == {1}
    for name in ("causal_gqa_fwd", "causal_gqa_bwd"):
        assert sum(name in call for call in calls) == 1
    assert attention.visited_tiles(16384, 4096)["fwd"] == (70, 136)
    assert kernel.accumulator_bytes(16384, 128) == 16 * 2 ** 20
    # the operands, their gradients and the forward's residuals: no
    # score, and no partial sum of a gradient, stands in HBM
    assert compiled.memory_analysis().temp_size_in_bytes < 7e8


def test_the_grouped_kernels_compile_at_the_first_width_under_1024(one_chip):
    """16 held experts of 2560 x 768 on the short tier's 49,152 rows:
    width tiles of 768 up and 640 down, forward and both gradients, three
    custom calls a product."""
    from imaginaire_tpu.ops import grouped_matmul

    assert grouped_matmul.tiles_of(2560, 768) == grouped_matmul.Tiles(
        fwd=(128, 768), dlhs=(128, 640))
    assert grouped_matmul.tiles_of(768, 2560) == grouped_matmul.Tiles(
        fwd=(128, 640), dlhs=(128, 768))
    sizes = _sds((16,), jnp.int32, one_chip)
    for contracted, width in ((2560, 768), (768, 2560)):
        lhs = _sds((49152, contracted), jnp.bfloat16, one_chip)
        rhs = _sds((16, contracted, width), jnp.bfloat16, one_chip)

        def loss(lhs, rhs, sizes):
            out = grouped_matmul.kernel_grouped_matmul(lhs, rhs, sizes)
            return jnp.sum(out.astype(jnp.float32))

        compiled = _compile(jax.value_and_grad(loss, argnums=(0, 1)), lhs,
                            rhs, sizes)
        calls = _custom_calls(compiled)
        assert len(calls) == 3 and set(calls.values()) == {1}
        for name in ("grouped_rows_fwd", "grouped_rows_dlhs",
                     "grouped_weights_drhs"):
            assert sum(name in call for call in calls) == 1


def test_the_early_router_share_s_step_fits_one_chip(one_chip, monkeypatch):
    """The whole training step at one sequence of 16,384 (from
    ``jax.eval_shape`` shapes: no weight is materialized), lowered and
    compiled as on the chip: four attention layers on the fused arm,
    three of them under the window; the held experts' products at
    2560 x 768 and 768 x 2560 on this repo's grouped kernel in both
    tiers; arguments and temporaries together under one chip's 16.9e9
    bytes (ISSUE 45 expected near 13e9: 13.19e9 as it stands, 7.88e9 of
    them the standing state)."""
    from imaginaire_tpu.config import Config
    from imaginaire_tpu.registry import resolve
    from imaginaire_tpu.trainers import lm

    cfg = Config(os.path.join(ROOT, "configs", "projects", "smallthinker",
                              "21b_a3b_ep4_share.yaml"))
    shape = (int(cfg.data.train.batch_size), int(cfg.data.seq_len))
    assert shape == (1, 16384)
    trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
    # the arms decide as they would on the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    meta = lm.attn_impl(trainer.cfg.gen, shape)
    assert meta["layers"] == dict.fromkeys("0246", "fused")
    assert meta["windows"] == dict.fromkeys("246", 4096)
    assert meta["visited_tiles"]["2"] == dict.fromkeys(
        ("fwd", "dq", "dkv"), [70, 136])
    moe = lm.moe_impl(trainer.cfg.gen, shape)
    assert moe["layers"] == dict.fromkeys("1357", "kernel")
    # the even share is a row and a half a token (16,384 x 6 x 16 / 64),
    # so the short tier is three
    assert moe["tiers"] == [49152, 98304]

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: _sds(s.shape, s.dtype, one_chip), tree)

    data = {"tokens": jax.ShapeDtypeStruct(shape, jnp.int32)}
    state = jax.eval_shape(trainer._init_state,
                           jax.ShapeDtypeStruct((2,), np.uint32), data)
    compiled = trainer._jit_gen_step.lower(on_chip(state),
                                           on_chip(data)).compile()
    trainer.state = None
    ma = compiled.memory_analysis()
    # 656,529,920 parameters and two Adam moments in float32
    assert 7.87e9 < ma.argument_size_in_bytes < 7.89e9
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 12.5e9 < total < 14.0e9 < 16.9e9, total
    counts = _custom_calls(compiled)
    # a block keeps its kernel's output and log-sum-exp: the forward
    # kernel runs once a layer
    assert {k: v for k, v in counts.items() if k.startswith("causal")} == {
        "causal_gqa_fwd": 4, "causal_gqa_bwd": 4}
    # four layers, two tiers, three products a pass: forward and again
    # inside the backward branch (no norm after the mixer reads the
    # result, so the block's recompute holds none); then the two
    # gradients
    assert {k: v for k, v in counts.items() if k.startswith("grouped")} == {
        "grouped_rows_fwd": 48, "grouped_rows_dlhs": 24,
        "grouped_weights_drhs": 24}
