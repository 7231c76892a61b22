"""Compile for a described TPU v5e, without the chip (section 2 of the
on-chip-measurement guide): the Pallas kernels that stay selectable, the
XLA formulations every op's ``auto`` resolves to, and the zoo-width SPADE
serving forward, each at its production shape. Nothing runs; what the
chip's compiler refuses fails here, at no chip time.

The topology is described inside a fixture, never at import: only one
process may load the TPU's library, and every worker imports this file.
"""

import collections
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM_BYTES = 16 * 10 ** 9  # one v5e chip


@pytest.fixture(scope="module")
def no_persistent_cache():
    """An executable compiled for a described chip is written to the
    persistent cache but cannot be read back: switch it off around
    these compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    # lint: allow(bare-jit) -- compile-only probe, nothing is dispatched
    return jax.jit(fn).lower(*args).compile()


# ------------------------------------------------------- Pallas kernels


@pytest.mark.parametrize("policy,forward_calls", [("save_nothing", 2),
                                                  ("blocks", 1)])
@pytest.mark.parametrize("q_heads,kv_heads,dim", [(32, 2, 128),
                                                  (20, 20, 256),
                                                  (8, 1, 128),
                                                  (32, 8, 64)])
def test_fused_attention_compiles_at_the_token_cells_shape(
        one_chip, q_heads, kv_heads, dim, policy, forward_calls):
    """The two passes of ``ops/attention.py``'s fused arm (the forward
    and the one backward sweep, ISSUE 42, under the VMEM its standing
    ``dk`` and ``dv`` ask for) at
    nemotron3_nano_30b_a3b's attention layer (32 query heads over 2, head
    size 128, 8,192 positions), at glm4_7_flash's (20 heads on 20, head
    size 256) and at lfm2_8b_a1b's (32 on 8 at head size 64, which the
    arm zero-pads to the kernel's 128), with the tiles the program uses. Each
    kernel's instruction stands on one line of the optimized HLO with its
    ``op_name`` under the caller's scope: that is how a trace's events
    are counted under ``lm/attn/scores``. Under a checkpoint of
    ``optim/remat.py``'s ``blocks`` the forward kernel's output and
    log-sum-exp are kept, so the recompute holds no second forward call
    (ISSUE 33); under ``save_nothing`` it holds one."""
    from imaginaire_tpu.ops import attention
    from imaginaire_tpu.optim.remat import POLICIES

    def loss(q, k, v):
        with jax.named_scope("lm/attn/scores"):
            out = attention.fused_causal_attention(q, k, v)
        return jnp.sum(out.astype(jnp.float32))

    q = _sds((1, 8192, q_heads, dim), jnp.bfloat16, one_chip)
    kv = _sds((1, 8192, kv_heads, dim), jnp.bfloat16, one_chip)
    compiled = _compile(
        jax.value_and_grad(
            jax.checkpoint(loss, policy=POLICIES[policy].policy),
            argnums=(0, 1, 2)), q, kv, kv)
    calls = [line for line in compiled.as_text().splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert sorted(line.split("=")[0].strip().lstrip("%").split(".")[0]
                  for line in calls) == [
        # the backward, the forward and its recompute under the checkpoint
        "causal_gqa_bwd", *["causal_gqa_fwd"] * forward_calls]
    assert all('op_name="' in line and "lm/attn/scores" in line
               for line in calls)
    # no score leaves the chip: the whole layer's temporaries are a few
    # times its operands (67 MB of queries), nowhere near the 4 GB of
    # float32 scores
    assert compiled.memory_analysis().temp_size_in_bytes < 6e8


@pytest.mark.parametrize("hidden,width", [
    (2688, 1856),
    pytest.param(2048, 1536, marks=pytest.mark.slow),
    pytest.param(4096, 1280, marks=pytest.mark.slow)])
def test_grouped_products_compile_at_the_token_cells_widths(one_chip, hidden,
                                                            width):
    """The three kernels of ``ops/grouped_matmul.py`` for a held expert's
    up and down products at nemotron3_nano_30b_a3b's widths (1856 is 14
    lane tiles and a half: a ragged width tile going up, a whole ragged
    contraction coming down), glm4_7_flash's and solar_open2_250b's, on
    the 8,192-row tier with the tiles the program picks. Each kernel's
    instruction stands on one line of the optimized HLO with its
    ``op_name`` under the caller's scope, which is how a trace's events
    are counted under ``lm/moe/experts`` (ISSUE 38)."""
    from imaginaire_tpu.ops import grouped_matmul

    def loss(x, w_up, w_down, sizes):
        with jax.named_scope("lm/moe/experts"):
            act = grouped_matmul.kernel_grouped_matmul(x, w_up, sizes)
            out = grouped_matmul.kernel_grouped_matmul(
                jnp.square(act), w_down, sizes)
        return jnp.sum(out.astype(jnp.float32))

    compiled = _compile(
        jax.value_and_grad(loss, argnums=(0, 1, 2)),
        _sds((8192, hidden), jnp.bfloat16, one_chip),
        _sds((8, hidden, width), jnp.bfloat16, one_chip),
        _sds((8, width, hidden), jnp.bfloat16, one_chip),
        _sds((8,), jnp.int32, one_chip))
    calls = [line for line in compiled.as_text().splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert sorted(line.split("=")[0].strip().lstrip("%").split(".")[0]
                  for line in calls) == [
        "grouped_rows_dlhs", "grouped_rows_dlhs", "grouped_rows_fwd",
        "grouped_rows_fwd", "grouped_weights_drhs", "grouped_weights_drhs"]
    assert all('op_name="' in line and "lm/moe/experts" in line
               for line in calls)
    # no padded or transposed copy of a weight stack stands beside it
    stack = 8 * hidden * width * 2
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5 * stack


def test_kda_layer_compiles_at_the_token_cells_shape(one_chip, monkeypatch):
    """A Kimi Delta Attention mixer of solar_open2_250b (8 heads of 128,
    chunks of 64, 8,192 positions, bfloat16 compute), value and gradients
    under the block's checkpoint, on the arm the chip takes (ISSUE 43):
    the chip's compiler takes the delta rule's two kernels, one forward
    sweep (the block keeps its output and the chunks' entry states, so
    the recompute holds no second one) and one backward sweep, each a
    custom call on one line under ``lm/attn/kda_scan``; no ``while`` is
    left under that scope; nothing (..., 16, 16, 128) or (..., 64, 64,
    128) of the decays stands in HBM; the layer's temporaries are 0.464
    GB (464,229,376 bytes as this test compiles it), the kept float32
    output and entry states in them, where the ``chunks`` arm's are
    0.950 (the carry's kept steps set that peak). Every instruction of
    the mixer names one of its scopes."""
    import re

    from imaginaire_tpu.config import Config
    from imaginaire_tpu.models.generators import hybrid_lm
    from imaginaire_tpu.ops import delta_rule
    from imaginaire_tpu.optim.remat import POLICIES

    gen = Config(os.path.join(ROOT, "configs", "projects", "solar_open2",
                              "250b_ep40_tp8_share.yaml")).gen
    gen["compute_dtype"] = "bfloat16"     # as the trainer sets it
    g = hybrid_lm.model_settings(gen)
    assert (g.kda_num_heads, g.kda_head_dim, g.kda_chunk_size) == (8, 128, 64)
    mixer = hybrid_lm.KDAMixer(g)
    params = jax.eval_shape(lambda: mixer.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 64, g.hidden_size),
                                         jnp.bfloat16)))
    params = jax.tree_util.tree_map(
        lambda leaf: _sds(leaf.shape, leaf.dtype, one_chip), params)
    # the arm decides as it would on the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert delta_rule.arm_of(128, 64, 8192) == "fused"

    def loss(params, u):
        return jnp.sum(mixer.apply(params, u).astype(jnp.float32))

    compiled = _compile(
        jax.value_and_grad(
            jax.checkpoint(loss, policy=POLICIES["blocks"].policy),
            argnums=(0, 1)),
        params, _sds((1, 8192, g.hidden_size), jnp.bfloat16, one_chip))
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert _kernel_calls(text) == ["delta_rule_bwd", "delta_rule_fwd"]
    assert all('op_name="' in line and "lm/attn/kda_scan" in line
               for line in calls)
    assert not [line for line in text.splitlines()
                if " while(" in line and "lm/attn/kda_scan" in line]
    assert not re.search(r"f32\[(\d+,)*16,16,128\]", text)
    assert not re.search(r"f32\[(\d+,)*64,64,128\]", text)
    scopes = {scope for name in re.findall(r'op_name="([^"]*)"', text)
              for scope in re.findall(r"lm/attn/\w+", name)[-1:]}
    assert scopes == {"lm/attn/kda_proj", "lm/attn/kda_conv",
                      "lm/attn/kda_scan", "lm/attn/kda_gate_norm",
                      "lm/attn/out"}


def _delta_rule_operands(one_chip, length, heads, dim):
    rows = _sds((1, length, heads, dim), jnp.bfloat16, one_chip)
    return (rows, rows, rows,
            _sds((1, length, heads, dim), jnp.float32, one_chip),
            _sds((1, length, heads), jnp.float32, one_chip))


def _kernel_calls(text):
    return sorted(
        line.split("=")[0].strip().lstrip("%").split(".")[0]
        for line in text.splitlines()
        if "custom-call(" in line and "tpu_custom_call" in line)


@pytest.mark.parametrize("dim,chunk", [(256, 64), (128, 128), (128, 32),
                                       (128, 16)])
def test_delta_rule_kernels_compile_at_the_other_shapes_the_rule_sends_them(
        one_chip, monkeypatch, dim, chunk):
    """What ``arm_of`` calls ``fused`` beside the token cell's 128 and
    64: head size 256 (a (8, 256, 256) state in scratch and blocks twice
    as wide under the same VMEM limit) and chunks of one, two and eight
    sub-blocks, value and gradients at 8 heads and 8,192 positions under
    the committed ``TILES``. The chip's compiler takes both sweeps."""
    from imaginaire_tpu.ops import delta_rule

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert delta_rule.arm_of(dim, chunk, 8192) == "fused"

    def loss(*xs):
        return jnp.sum(delta_rule.delta_rule(*xs, chunk).astype(jnp.float32))

    compiled = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)),
                        *_delta_rule_operands(one_chip, 8192, 8, dim))
    assert _kernel_calls(compiled.as_text()) == ["delta_rule_bwd",
                                                 "delta_rule_fwd"]


def test_the_chunks_arm_compiles_for_the_chip_at_a_ragged_length(one_chip):
    """A length the chunk does not divide takes ``kda_scan`` on a TPU too
    (``arm_of``), so the chip's compiler still has to take the XLA form
    and its gradient: the map over blocks of chunks and the carry as
    ``while`` loops, the sub-blocks' float32 (..., 16, 16, 128) decays,
    no (..., 64, 64, 128), and no kernel. ``default_backend`` is this
    process's own here: the CPU takes the same arm."""
    import re

    from imaginaire_tpu.ops import delta_rule

    length = 1000
    assert delta_rule.arm_of(128, 64, length) == "chunks"

    def loss(*xs):
        return jnp.sum(delta_rule.delta_rule(*xs, 64).astype(jnp.float32))

    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)),
                    *_delta_rule_operands(one_chip, length, 2, 128)).as_text()
    assert _kernel_calls(text) == []
    assert " while(" in text
    assert re.search(r"f32\[(\d+,)*16,16,128\]", text)
    assert not re.search(r"f32\[(\d+,)*64,64,128\]", text)


def _ssd_operands(one_chip, length, heads, dim, groups, state):
    lead = (1, length)
    return (_sds(lead + (heads, dim), jnp.bfloat16, one_chip),
            _sds(lead + (heads,), jnp.float32, one_chip),
            _sds((heads,), jnp.float32, one_chip),
            _sds(lead + (groups, state), jnp.bfloat16, one_chip),
            _sds(lead + (groups, state), jnp.bfloat16, one_chip))


def test_mamba2_layer_compiles_at_the_token_cells_shape(one_chip,
                                                        monkeypatch):
    """A Mamba-2 mixer of nemotron3_nano_30b_a3b (64 heads of 64 in 8
    groups, state 128, chunks of 128, 8,192 positions, bfloat16 compute),
    value and gradients under the block's checkpoint, on the arm the chip
    takes (ISSUE 46): the chip's compiler takes the scan's two kernels,
    one forward sweep (the block keeps its output and the chunks' entry
    states, so the recompute holds no second one) and one backward sweep,
    each a custom call on one line under ``lm/mamba2/ssd_scan``; no
    ``while`` is left under that scope; nothing (..., 128, 128) of a
    head's decays or weights and no (..., 64, 128) state a chunk and head
    in the ``chunks`` arm's layout stands in HBM; the layer's temporaries
    are 1.38 GB (1,383,805,952 bytes as this test compiles it), the kept
    output and entry states in them, where the ``chunks`` arm's are 1.87.
    Every instruction of the mixer names one of its scopes."""
    import re

    from imaginaire_tpu.config import Config
    from imaginaire_tpu.models.generators import hybrid_lm
    from imaginaire_tpu.ops import state_space
    from imaginaire_tpu.optim.remat import POLICIES

    gen = Config(os.path.join(ROOT, "configs", "projects", "nemotron_h",
                              "nano_30b_a3b_ep16_share.yaml")).gen
    gen["compute_dtype"] = "bfloat16"     # as the trainer sets it
    g = hybrid_lm.model_settings(gen)
    sizes = (g.mamba_head_dim, g.ssm_state_size, g.chunk_size, 8192,
             g.mamba_num_heads, g.n_groups)
    assert sizes == (64, 128, 128, 8192, 64, 8)
    mixer = hybrid_lm.Mamba2Mixer(g)
    params = jax.eval_shape(lambda: mixer.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128, g.hidden_size),
                                         jnp.bfloat16)))
    params = jax.tree_util.tree_map(
        lambda leaf: _sds(leaf.shape, leaf.dtype, one_chip), params)
    # the arm decides as it would on the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert state_space.arm_of(*sizes) == "fused"

    def loss(params, u):
        return jnp.sum(mixer.apply(params, u).astype(jnp.float32))

    compiled = _compile(
        jax.value_and_grad(
            jax.checkpoint(loss, policy=POLICIES["blocks"].policy),
            argnums=(0, 1)),
        params, _sds((1, 8192, g.hidden_size), jnp.bfloat16, one_chip))
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert _kernel_calls(text) == ["ssd_scan_bwd", "ssd_scan_fwd"]
    assert all('op_name="' in line and "lm/mamba2/ssd_scan" in line
               for line in calls)
    assert not [line for line in text.splitlines()
                if " while(" in line and "lm/mamba2/ssd_scan" in line]
    assert not re.search(r"(f32|bf16)\[(\d+,)*128,128\]", text)
    assert not re.search(r"f32\[(\d+,)*64,64,128\]", text)
    scopes = {scope for name in re.findall(r'op_name="([^"]*)"', text)
              for scope in re.findall(r"lm/mamba2/\w+", name)[-1:]}
    assert scopes == {"lm/mamba2/in_proj", "lm/mamba2/conv",
                      "lm/mamba2/ssd_scan", "lm/mamba2/gate_norm",
                      "lm/mamba2/out_proj"}


@pytest.mark.parametrize("dim,state,chunk,heads,groups", [
    (128, 128, 128, 32, 4), (64, 256, 256, 16, 1), (64, 128, 128, 24, 1),
    (128, 256, 128, 8, 1)])
def test_ssd_scan_kernels_compile_at_the_other_shapes_the_rule_sends_them(
        one_chip, monkeypatch, dim, state, chunk, heads, groups):
    """Heads of 128 (one to a lane tile), a state of 256, chunks of 256
    and groups of sixteen and of twenty-four heads: what
    ``state_space.arm_of`` lets through beside the token cell's shape."""
    from imaginaire_tpu.ops import state_space

    length = 2048
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert state_space.arm_of(dim, state, chunk, length, heads,
                              groups) == "fused"

    def loss(*xs):
        return jnp.sum(state_space.ssd_scan(*xs, chunk).astype(jnp.float32))

    text = _compile(
        jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)),
        *_ssd_operands(one_chip, length, heads, dim, groups, state)).as_text()
    assert _kernel_calls(text) == ["ssd_scan_bwd", "ssd_scan_fwd"]


def test_the_ssd_chunks_arm_compiles_for_the_chip_at_a_ragged_length(
        one_chip, monkeypatch):
    """A length the chunk does not divide takes ``ssd_chunks`` on a TPU
    too (``arm_of``), so the chip's compiler still has to take the XLA
    form and its gradient: the carry as a ``while`` loop, a head's
    (128, 128) decays in float32, and no kernel."""
    import re

    from imaginaire_tpu.ops import state_space

    length = 1000
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert state_space.arm_of(64, 128, 128, length, 16, 2) == "chunks"

    def loss(*xs):
        return jnp.sum(state_space.ssd_scan(*xs, 128).astype(jnp.float32))

    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)),
                    *_ssd_operands(one_chip, length, 16, 64, 2, 128)
                    ).as_text()
    assert _kernel_calls(text) == []
    assert " while(" in text
    assert re.search(r"f32\[(\d+,)*128,128\]", text)


def test_the_mamba2_share_s_step_fits_one_chip(one_chip, monkeypatch):
    """configs/projects/nemotron_h/nano_30b_a3b_ep16_share.yaml's whole
    training step at its own shapes (one sequence of 8,192; from
    ``jax.eval_shape`` shapes: no weight is materialized), lowered and
    compiled as on the chip: the four Mamba-2 layers' scans on the fused
    arm, a forward and a backward sweep each and no second forward sweep
    in a block's recompute (each block keeps its sweep's bfloat16 output
    and float32 entry states, 201 MB a layer), the attention layer's
    scores on the fused arm, and state and temporaries together under one
    chip's 16.9e9 bytes (ISSUE 46: 11.7e9 as it stands)."""
    from imaginaire_tpu.config import Config
    from imaginaire_tpu.registry import resolve
    from imaginaire_tpu.trainers import lm

    cfg = Config(os.path.join(ROOT, "configs", "projects", "nemotron_h",
                              "nano_30b_a3b_ep16_share.yaml"))
    shape = (int(cfg.data.train.batch_size), int(cfg.data.seq_len))
    assert shape == (1, 8192)
    trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
    # the arms decide as they would on the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    meta = lm.ssd_impl(trainer.cfg.gen, shape)
    assert meta["arm"] == dict.fromkeys("0247", "fused")
    assert sum(meta["kept_bytes"].values()) == 4 * 201_326_592
    assert lm.attn_impl(trainer.cfg.gen, shape)["layers"] == {"5": "fused"}

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
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 11.0e9 < total < 12.5e9 < 16.9e9, total
    text = compiled.as_text()
    calls = collections.Counter(_kernel_calls(text))
    assert (calls["ssd_scan_fwd"], calls["ssd_scan_bwd"]) == (4, 4)
    assert (calls["causal_gqa_fwd"], calls["causal_gqa_bwd"]) == (1, 1)
    assert not [line for line in text.splitlines()
                if " while(" in line and "lm/mamba2/ssd_scan" in line]


def test_the_short_convolution_share_s_step_fits_one_chip(one_chip,
                                                          monkeypatch):
    """configs/projects/lfm2_moe/8b_a1b_ep4_share.yaml's whole training
    step at its own shapes (two sequences of 8,192; from
    ``jax.eval_shape`` shapes: no weight is materialized), lowered and
    compiled as on the chip: the attention layer's scores at head size 64
    on the fused arm, the held experts' products at 2048 x 1792 and
    1792 x 2048 on this repo's grouped kernel in both tiers, and state
    and temporaries together under one chip's 16.9e9 bytes (ISSUE 39:
    10.6e9 as it stands)."""
    from imaginaire_tpu.config import Config
    from imaginaire_tpu.ops import attention, grouped_matmul
    from imaginaire_tpu.registry import resolve
    from imaginaire_tpu.trainers import lm

    cfg = Config(os.path.join(ROOT, "configs", "projects", "lfm2_moe",
                              "8b_a1b_ep4_share.yaml"))
    shape = (int(cfg.data.train.batch_size), int(cfg.data.seq_len))
    assert shape == (2, 8192)
    trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
    # the arms decide as they would on the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    meta = lm.attn_impl(trainer.cfg.gen, shape)
    assert meta["layers"] == {"2": "fused"}
    assert (meta["head_dim"], meta["kernel_head_dim"]) == (64, 128)
    moe = lm.moe_impl(trainer.cfg.gen, shape)
    assert moe["layers"] == dict.fromkeys("3579", "kernel")
    # the even share is a row a token (16,384 x 4 x 8 / 32), so the
    # short tier is two
    assert moe["tiers"] == [32768, 65536]
    assert (moe["tiles"]["up"]["fwd"], moe["tiles"]["down"]["fwd"]) == (
        (128, 896), (128, 1024))
    assert attention.arm_of(64, 8192) == "fused"
    assert grouped_matmul.arm_of(16384, 2048, 1792) == "kernel"

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
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 10.0e9 < total < 11.5e9 < 16.9e9, total
    calls = [line.split("=")[0].strip().lstrip("%").split(".")[0]
             for line in compiled.as_text().splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert {name: calls.count(name) for name in set(calls)} == {
        "causal_gqa_fwd": 1, "causal_gqa_bwd": 1,
        # four layers, two tiers, three products a pass: forward and
        # again inside the backward branch, then the two gradients
        "grouped_rows_fwd": 48, "grouped_rows_dlhs": 24,
        "grouped_weights_drhs": 24}


# -------------------------------------------- what ``auto`` resolves to


def test_correlation_auto_compiles(one_chip):
    from imaginaire_tpu.ops import correlation, correlation_mod

    assert correlation_mod.AUTO_IMPLEMENTATION == "mxu"
    x = _sds((1, 64, 128, 256), jnp.float32, one_chip)
    compiled = _compile(
        lambda a, b: correlation(a, b, implementation="auto"), x, x)
    assert "tpu_custom_call" not in compiled.as_text()


def test_resample2d_auto_compiles(one_chip):
    from imaginaire_tpu.ops import resample2d

    compiled = _compile(
        lambda x, f: resample2d(x, f, implementation="auto"),
        _sds((2, 512, 1024, 3), jnp.float32, one_chip),
        _sds((2, 512, 1024, 2), jnp.float32, one_chip))
    assert "tpu_custom_call" not in compiled.as_text()


def test_spade_modulation_auto_value_and_grad_compiles(one_chip):
    from imaginaire_tpu.ops import spade_modulation, spade_modulation_mod

    assert spade_modulation_mod.AUTO_IMPLEMENTATION == "fused"

    def loss(x, g, b):
        out = spade_modulation(x, (g,), (b,), implementation="auto")
        return jnp.sum(out.astype(jnp.float32) ** 2)

    x = _sds((4, 256, 256, 128), jnp.bfloat16, one_chip)
    compiled = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                        x, x, x)
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_BYTES


# ------------------------------------------- the serving forward, bs 4


def test_zoo_spade_serving_forward_fits_one_chip(one_chip):
    """configs/projects/spade/cocostuff/base128_bs4.yaml as shipped (nf
    128, 256x256, 185 label channels), the forward inference.py serves,
    from ``jax.eval_shape`` shapes: no weight is materialized."""
    from imaginaire_tpu.config import Config, cfg_get
    from imaginaire_tpu.registry import resolve
    from imaginaire_tpu.utils.data import (
        get_paired_input_label_channel_number,
    )

    cfg = Config(os.path.join(ROOT, "configs", "projects", "spade",
                              "cocostuff", "base128_bs4.yaml"))
    cfg.trainer.perceptual_loss.allow_random_init = True
    cfg.trainer.perceptual_loss.pop("weights_path", None)
    trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
    bs = int(cfg.data.val.batch_size)
    n_lab = get_paired_input_label_channel_number(cfg.data)
    assert (bs, n_lab, int(cfg.gen.num_filters)) == (4, 185, 128)
    batch = {"images": jax.ShapeDtypeStruct((bs, 256, 256, 3), np.float32),
             "label": jax.ShapeDtypeStruct((bs, 256, 256, n_lab),
                                           np.float32)}

    def variables_of(key, data):
        trainer.init_state(key, data)
        return trainer.inference_params()

    variables = jax.eval_shape(
        variables_of, jax.ShapeDtypeStruct((2,), np.uint32), batch)
    trainer.state = None  # eval_shape left shapes there
    inference_args = dict(cfg_get(cfg, "inference_args", None) or {})
    net = trainer.net_G

    def forward(variables, data, rng):
        return net.apply(variables, data, training=False,
                         rngs={"noise": rng}, method=net.inference,
                         **inference_args)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: _sds(s.shape, s.dtype, one_chip), tree)

    compiled = _compile(forward, on_chip(variables), on_chip(batch),
                        _sds((2,), np.uint32, one_chip))
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 0 < total < HBM_BYTES, total
