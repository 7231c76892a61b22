"""Compile for a described TPU v5e, without the chip (ISSUE 41): the fused
attention kernel's two passes under the cell's window, and
`configs/projects/afmoe/mini_ep8_share.yaml`'s whole training step at its
own shapes. Nothing runs. The fixtures are `test_tpu_compile.py`'s.

The whole step is in the slow tier (`-m slow`): it holds a worker 85 s
alone and 170 s beside five others, `test_tpu_compile.py` already compiles
a whole token step with the same kernels (the short-convolution share's)
in the fast tier, and the fast tier is at the edge of its clock (ISSUE 41's
first satellite). Run it before a chip call that changes what a step
keeps or a kernel's tiles:

    python -m pytest tests/test_tpu_compile_afmoe.py -m slow
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_tpu_compile import (ROOT, _compile, _sds,  # noqa: F401
                              no_persistent_cache, one_chip, topo)


@pytest.mark.parametrize("window", [2048, None])
def test_the_windowed_kernel_compiles_at_the_cells_shape(one_chip, window):
    """One sequence of 16,384, 32 query heads on 4 of 128, under the
    window layers' 2,048 and as the full layer: the two passes compile,
    each one custom call (under a window the grid's innermost axis is 3
    steps long), the backward with a key-value head's ``dk`` and ``dv``
    standing in 16 MiB of the 50 MiB of VMEM it asks for."""
    from imaginaire_tpu.ops import attention
    from imaginaire_tpu.ops.pallas import causal_attention_kernel as kernel

    q = _sds((1, 16384, 32, 128), jnp.bfloat16, one_chip)
    kv = _sds((1, 16384, 4, 128), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        out = attention.fused_causal_attention(q, k, v, attention.TILES,
                                               False, window)
        return jnp.sum(out.astype(jnp.float32))

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    calls = [line for line in compiled.as_text().splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert len(calls) == 2
    for name in ("causal_gqa_fwd", "causal_gqa_bwd"):
        assert sum(name in line.split("=")[0] for line in calls) == 1
    assert attention.visited_tiles(16384, 2048)["fwd"] == (45, 136)
    assert kernel.accumulator_bytes(16384, 128) == 16 * 2 ** 20
    assert 48 * 2 ** 20 < kernel.backward_vmem_bytes(
        16384, 128, *attention.TILES.bwd, 2) < 56 * 2 ** 20
    # the operands, their gradients and the forward's residuals: no
    # score, and no partial sum of a gradient, stands in HBM
    assert compiled.memory_analysis().temp_size_in_bytes < 7e8


@pytest.mark.slow
def test_the_sliding_window_share_s_step_fits_one_chip(one_chip,
                                                       monkeypatch):
    """The whole training step at one sequence of 16,384 (from
    ``jax.eval_shape`` shapes: no weight is materialized), lowered and
    compiled as on the chip: five attention layers on the fused arm, four
    of them under the window; the held experts' products at 2048 x 1024
    and 1024 x 2048 on this repo's grouped kernel in both tiers; state
    and temporaries together under one chip's 16.9e9 bytes (ISSUE 41:
    14.0e9 as it stands, 8.47e9 of them the standing state)."""
    from imaginaire_tpu.config import Config
    from imaginaire_tpu.registry import resolve
    from imaginaire_tpu.trainers import lm

    cfg = Config(os.path.join(ROOT, "configs", "projects", "afmoe",
                              "mini_ep8_share.yaml"))
    shape = (int(cfg.data.train.batch_size), int(cfg.data.seq_len))
    assert shape == (1, 16384)
    trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
    # the arms decide as they would on the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    meta = lm.attn_impl(trainer.cfg.gen, shape)
    assert meta["layers"] == dict.fromkeys("02468", "fused")
    assert meta["windows"] == dict.fromkeys("0268", 2048)
    assert meta["visited_tiles"]["0"] == dict.fromkeys(
        ("fwd", "dq", "dkv"), [45, 136])
    moe = lm.moe_impl(trainer.cfg.gen, shape)
    assert moe["layers"] == dict.fromkeys("3579", "kernel")
    # the even share is a row a token (16,384 x 8 x 16 / 128), so the
    # short tier is two
    assert moe["tiers"] == [32768, 131072]

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
    assert 8.4e9 < ma.argument_size_in_bytes < 8.5e9
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 13.0e9 < total < 15.0e9 < 16.9e9, total
    calls = [line.split("=")[0].strip().lstrip("%").split(".")[0]
             for line in compiled.as_text().splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    counts = {name: calls.count(name) for name in set(calls)}
    # a block keeps its kernel's output and log-sum-exp: the forward
    # kernel runs once a layer
    assert {k: v for k, v in counts.items() if k.startswith("causal")} == {
        "causal_gqa_fwd": 5, "causal_gqa_bwd": 5}
    # four layers, two tiers, three products a pass: forward, again in the
    # block's recompute (the norm after the mixer reads the result) and
    # again inside the backward branch; then the two gradients
    assert {k: v for k, v in counts.items() if k.startswith("grouped")} == {
        "grouped_rows_fwd": 72, "grouped_rows_dlhs": 24,
        "grouped_weights_drhs": 24}
