"""``ops/state_space.py`` (ISSUE 46): the fused Mamba-2 scan's two sweeps
(in Pallas's interpreter, on the CPU) against the ``chunks`` arm and
against the reference's step-by-step recurrence, output and all five
gradients; where a head forgets everything within a chunk; the rule that
picks the arm; what a recomputed block keeps; the trainer's meta."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hybrid_lm_util import ROOT, pallas_bodies, pallas_calls, tiny_cfg

from imaginaire_tpu.analysis import islands, jaxpr_audit
from imaginaire_tpu.ops import state_space

CHUNK, DIM, STATE = 128, 64, 128
NAMES = ("y", "dx", "ddt", "da", "db", "dc")
# one chunk a grid step, and a sweep each way with two
ONE = state_space.Tiles(fwd=1, bwd=1)
TWO = state_space.Tiles(fwd=2, bwd=2)


def _operands(length, bsz, heads, groups, dtype=jnp.float32, dim=DIM,
              state=STATE):
    """``x`` (bsz, length, heads, dim), ``b``, ``c`` (bsz, length, groups,
    state) in ``dtype``; step sizes a softplus draw around 0.13, rates
    from 1 to 16 as ``A_log`` is drawn."""
    keys = jax.random.split(jax.random.PRNGKey(length + heads), 5)
    x = jax.random.normal(keys[0], (bsz, length, heads, dim))
    b = jax.random.normal(keys[1], (bsz, length, groups, state)) * 0.3
    c = jax.random.normal(keys[2], (bsz, length, groups, state)) * 0.3
    dt = jax.nn.softplus(jax.random.normal(keys[3], (bsz, length, heads)) - 2)
    a = -jnp.exp(jax.random.uniform(keys[4], (heads,), minval=0.0,
                                    maxval=2.77))
    return x.astype(dtype), dt, a, b.astype(dtype), c.astype(dtype)


def _with_gradients(fn, operands):
    """(output, five gradients) of ``fn`` under one fixed random
    projection of its output, in float32."""
    def run(*args):
        out = fn(*args).astype(jnp.float32)
        weights = jax.random.normal(jax.random.PRNGKey(9), out.shape)
        return jnp.sum(out * weights), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        run, argnums=(0, 1, 2, 3, 4), has_aux=True))(*operands)
    return (out, *(g.astype(jnp.float32) for g in grads))


def _fused(tiles, chunk=CHUNK):
    return lambda *xs: state_space.fused_ssd_scan(*xs, chunk, tiles, True)


def _chunks(*xs, chunk=CHUNK):
    return state_space.ssd_chunks(*xs, chunk)


def _recurrence(x, dt, a, b, c):
    from benchmark.reference import nemotron_h_train as reference

    x, b, c = (v.astype(jnp.float32) for v in (x, b, c))
    return jax.vmap(reference.recurrence,
                    in_axes=(0, 0, None, 0, 0))(x, dt, a, b, c)


def _close(ours, theirs, tol):
    ours, theirs = np.asarray(ours), np.asarray(theirs)
    scale = max(float(np.abs(theirs).max()), 1e-6)
    assert float(np.abs(ours - theirs).max()) <= tol * scale


LAYOUTS = {
    # (length, sequences, heads, groups), tiles, operands' dtype
    "two_chunks_two_groups": ((256, 2, 16, 2), ONE, jnp.float32),
    "three_chunks_a_step_each": ((384, 1, 8, 1), TWO, jnp.float32),
    "four_chunks_two_a_step": ((512, 1, 16, 2), TWO, jnp.float32),
    "bfloat16": ((256, 1, 16, 2), ONE, jnp.bfloat16),
    "bfloat16_two_a_step": ((256, 2, 8, 1), TWO, jnp.bfloat16),
}


@functools.lru_cache(maxsize=None)
def _three_ways(layout):
    shape, tiles, dtype = LAYOUTS[layout]
    operands = _operands(*shape, dtype)
    return tuple(_with_gradients(fn, operands)
                 for fn in (_fused(tiles), _chunks, _recurrence))


@pytest.mark.parametrize("which", range(6), ids=NAMES)
@pytest.mark.parametrize("layout", LAYOUTS)
def test_fused_arm_matches_chunks_arm_and_the_recurrence(layout, which):
    """Output and every gradient at heads of 64, state 128 and chunks of
    128. In float32 the sums of log-decays reach some -100 a chunk, so a
    rounding of theirs is 1e-5 of a decay: 1e-4 of the largest value
    holds all three to each other (``tests/test_hybrid_lm_layers.py``
    holds the ``chunks`` arm to the recurrence at that for a gradient).
    Under bfloat16 operands both arms round the weights, ``dt x`` and the
    state that ``c`` reads to bfloat16 before their products and their
    results after (half a unit in the last place: 4e-3 of the value); the
    recurrence reads the same rounded operands in float32 and rounds
    nothing after."""
    fused, chunks, recurrence = _three_ways(layout)
    tol = rounded = 1e-4
    if LAYOUTS[layout][2] == jnp.bfloat16:
        tol, rounded = 1e-2, 2e-2
    _close(fused[which], chunks[which], tol)
    _close(fused[which], recurrence[which], rounded)


OTHER_SHAPES = {
    # what else ``arm_of`` sends to the kernels: (head size, state, chunk,
    # length, heads, groups), two chunks each
    "head_128": (128, 128, 128, 256, 8, 1),
    "state_256": (64, 256, 128, 256, 8, 1),
    "chunk_256": (64, 128, 256, 512, 8, 1),
    "sixteen_heads_a_group": (64, 128, 128, 256, 16, 1),
}


@functools.lru_cache(maxsize=None)
def _three_ways_at(shape):
    dim, state, chunk, length, heads, groups = OTHER_SHAPES[shape]
    operands = _operands(length, 1, heads, groups, dim=dim, state=state)
    return tuple(_with_gradients(fn, operands) for fn in (
        _fused(ONE, chunk), functools.partial(_chunks, chunk=chunk),
        _recurrence))


@pytest.mark.parametrize("which", range(6), ids=NAMES)
@pytest.mark.parametrize("shape", OTHER_SHAPES)
def test_fused_arm_at_the_other_shapes_the_rule_sends_it(shape, which):
    """Heads of 128 (one to a lane tile), a state of 256, chunks of 256
    and sixteen heads to a group, each against the ``chunks`` arm and the
    recurrence at the tolerances of the cell's shape."""
    fused, chunks, recurrence = _three_ways_at(shape)
    _close(fused[which], chunks[which], 1e-4)
    _close(fused[which], recurrence[which], 1e-4)


def test_fused_arm_holds_where_a_head_forgets_everything_within_a_chunk():
    """Step sizes of 1 to 3 at rates up to 16: ``dt a`` sums below -88
    (where float32's ``exp`` is 0) within a few steps in half the heads,
    beside heads that hold for the whole sequence. The kernels form no
    exponent above 0, forward or backward, so output and gradients are
    finite, and the recurrence's."""
    x, _, _, b, c = _operands(256, 1, 8, 1)
    dt = 1.0 + 2.0 * jax.random.uniform(jax.random.PRNGKey(4), (1, 256, 8))
    a = -jnp.where(jnp.arange(8) % 2 == 0, 16.0, 1e-3)
    sums = jnp.cumsum((dt * a).reshape(1, 2, CHUNK, 8), axis=2)
    assert float(sums.min()) < -4000 and float(sums[..., 1].min()) > -1
    fused, recurrence = (_with_gradients(fn, (x, dt, a, b, c))
                         for fn in (_fused(TWO), _recurrence))
    for ours, theirs in zip(fused, recurrence):
        assert bool(jnp.isfinite(ours).all())
        _close(ours, theirs, 1e-4)


def test_nothing_after_a_position_reaches_it():
    """The sweeps are causal across a chunk's edge and inside a chunk:
    changing the operands from position ``t`` on leaves every earlier
    output as it was, to the bit."""
    x, dt, a, b, c = _operands(256, 1, 8, 1)
    out = _fused(ONE)(x, dt, a, b, c)
    for t in (40, 128, 200):
        changed = tuple(v.at[:, t:].multiply(0.5) for v in (x, dt, b, c))
        again = _fused(ONE)(changed[0], changed[1], a, *changed[2:])
        np.testing.assert_array_equal(np.asarray(out[:, :t]),
                                      np.asarray(again[:, :t]))
        assert float(jnp.abs(out[:, t:] - again[:, t:]).max()) > 0


@pytest.mark.parametrize(
    "backend,dim,state,chunk,length,heads,groups,arm", [
        ("tpu", 64, 128, 128, 8192, 64, 8, "fused"),    # the cell's
        ("tpu", 128, 128, 128, 8192, 64, 8, "fused"),
        ("tpu", 64, 256, 256, 8192, 128, 8, "fused"),
        ("tpu", 64, 128, 128, 8192, 24, 1, "fused"),
        ("cpu", 64, 128, 128, 8192, 64, 8, "chunks"),   # where the tests run
        ("tpu", 16, 16, 16, 64, 8, 2, "chunks"),        # the unit-test YAML
        ("tpu", 32, 128, 128, 8192, 64, 8, "chunks"),   # four heads a tile
        ("tpu", 64, 64, 128, 8192, 64, 8, "chunks"),    # half a lane tile
        ("tpu", 64, 128, 64, 8192, 64, 8, "chunks"),    # a chunk under 128
        ("tpu", 64, 128, 128, 8190, 64, 8, "chunks"),   # a ragged length
        ("tpu", 64, 128, 128, 8192, 32, 8, "chunks"),   # four heads a group
        ("tpu", 64, 128, 128, 8192, 60, 8, "chunks"),   # groups uneven
    ])
def test_the_rule(monkeypatch, backend, dim, state, chunk, length, heads,
                  groups, arm):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert state_space.arm_of(dim, state, chunk, length, heads,
                              groups) == arm


def test_ssd_scan_takes_the_chunks_arm_here():
    """On the CPU ``ssd_scan`` is ``ssd_chunks``: no kernel in its
    program, at the cell's head size too."""
    operands = _operands(256, 1, 8, 1)
    traced = jax.make_jaxpr(
        lambda *xs: state_space.ssd_scan(*xs, CHUNK))(*operands)
    assert pallas_calls(traced.jaxpr) == []
    np.testing.assert_array_equal(
        np.asarray(state_space.ssd_scan(*operands, CHUNK)),
        np.asarray(_chunks(*operands)))


def test_the_tiles_divide_the_cells_chunks():
    """8,192 positions in chunks of 128 are 64 chunks: each sweep's tile
    divides them, and a length whose chunks it does not divide takes
    their common divisor."""
    for tile in state_space.TILES:
        assert state_space.per_step(8192, 128, tile) == tile
    assert state_space.per_step(384, 128, 2) == 1
    assert state_space.per_step(768, 128, 4) == 2


# -------------------------------------------- under a block's recompute


def _block_gradients(policy):
    """(the kernel calls in the gradient's jaxpr, the gradients) of a
    block ``u -> x, dt, b, c -> fused scan -> W_o`` recomputed under a
    remat policy."""
    from imaginaire_tpu.optim.remat import POLICIES

    hidden, length, heads = 32, 256, 8
    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    u = jax.random.normal(keys[0], (1, length, hidden)).astype(jnp.bfloat16)
    widths = (heads * DIM, STATE, STATE, heads)
    kernels = [(jax.random.normal(key, (hidden, width)) / 8
                ).astype(jnp.bfloat16)
               for key, width in zip(keys[1:5], widths)]
    kernels.append((jax.random.normal(keys[5], (heads * DIM, hidden)) / 16
                    ).astype(jnp.bfloat16))
    a = -jnp.exp(jax.random.uniform(keys[6], (heads,), maxval=2.77))

    def block(u, kernels):
        w_x, w_b, w_c, w_dt, w_o = kernels
        x = (u @ w_x).reshape(1, length, heads, DIM)
        b, c = ((u @ w).reshape(1, length, 1, STATE) for w in (w_b, w_c))
        dt = jax.nn.softplus((u @ w_dt).astype(jnp.float32) - 2.0)
        y = state_space.fused_ssd_scan(x, dt, a, b, c, CHUNK, ONE, True)
        return y.reshape(1, length, -1) @ w_o

    def loss(u, kernels):
        out = jax.checkpoint(block, policy=POLICIES[policy].policy)(u, kernels)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    grad = jax.grad(loss, argnums=(0, 1))
    return (pallas_calls(jax.make_jaxpr(grad)(u, kernels).jaxpr),
            jax.tree_util.tree_leaves(grad(u, kernels)))


def test_a_recomputed_block_runs_the_forward_sweep_once():
    """Under ``blocks`` the block keeps what the forward sweep names
    ``KERNEL_RESIDUAL`` (its output and the chunks' entry states), so its
    recompute holds no second forward sweep; under ``save_nothing`` it
    holds one; and the kept arrays are the ones the second sweep would
    have written, so no gradient moves by a bit."""
    kept_calls, kept = _block_gradients("blocks")
    again_calls, again = _block_gradients("save_nothing")
    assert sorted(kept_calls) == ["ssd_scan_bwd", "ssd_scan_fwd"]
    assert sorted(again_calls) == [
        "ssd_scan_bwd", "ssd_scan_fwd", "ssd_scan_fwd"]
    assert len(kept) == len(again) == 6
    for a, b in zip(kept, again):
        assert np.abs(np.asarray(a, np.float32)).max() > 0
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_the_forward_sweep_names_its_output_and_states_kernel_residual():
    """The two ``name`` equations of the forward rule carry
    ``KERNEL_RESIDUAL``, the name ``POLICIES["blocks"]`` saves: the output
    in the compute dtype and the float32 state each chunk starts from; a
    call that is not differentiated asks the kernel for no states."""
    from imaginaire_tpu.ops.attention import KERNEL_RESIDUAL

    operands = _operands(256, 1, 8, 1, jnp.bfloat16)
    traced = jax.make_jaxpr(
        lambda *xs: jax.vjp(_fused(ONE), *xs)[0])(*operands)
    named = [eqn for _, eqn in jaxpr_audit.iter_eqns(traced.jaxpr)
             if eqn.primitive.name == "name"]
    assert [eqn.params["name"] for eqn in named] == [KERNEL_RESIDUAL] * 2
    kept = sorted((eqn.outvars[0].aval.shape, eqn.outvars[0].aval.dtype)
                  for eqn in named)
    assert kept == [((1, 2, STATE, 8 * DIM), jnp.float32),
                    ((1, 256, 8, DIM), jnp.bfloat16)]
    assert state_space.residual_bytes(
        1, 256, 8, DIM, STATE, CHUNK, jnp.bfloat16) == (
        256 * 8 * DIM * 2 + 2 * STATE * 8 * DIM * 4)
    plain = jax.make_jaxpr(_fused(ONE))(*operands)
    (call,) = [eqn for _, eqn in jaxpr_audit.iter_eqns(plain.jaxpr)
               if eqn.primitive.name == "pallas_call"]
    assert len(call.outvars) == 1


def _kernels_in_the_island(jaxpr):
    """The names of the ``pallas_call``s inside the jitted calls that
    stand under the ``ssm_scan`` island's scope (the layers of a model
    share one jitted function a sweep), sorted."""
    return sorted(
        name for _, eqn in jaxpr_audit.iter_eqns(jaxpr)
        if eqn.primitive.name == "jit"
        and islands.island_of(eqn.source_info.name_stack) == "ssm_scan"
        for name in pallas_calls(eqn.params["jaxpr"].jaxpr))


def _mamba_cfg(**gen):
    return tiny_cfg(compute_dtype="bfloat16", mamba_num_heads=8,
                    mamba_head_dim=DIM, n_groups=1, ssm_state_size=STATE,
                    chunk_size=CHUNK, **gen)


def test_the_mixer_on_the_fused_arm_keeps_the_island(monkeypatch):
    """A Mamba-2 mixer of the unit-test preset at 8 heads of 64, state 128
    and chunks of 128 under bfloat16 compute, with the backend read as a
    TPU: its gradient holds the two kernels inside ``ssm_scan``, no
    ``scan`` and no ``cumsum`` there, and no cast down in any island,
    forward or backward."""
    from imaginaire_tpu.models.generators import hybrid_lm

    g = hybrid_lm.model_settings(_mamba_cfg().gen)
    mixer = hybrid_lm.Mamba2Mixer(g)
    u = jax.random.normal(jax.random.PRNGKey(3), (1, 256, g.hidden_size),
                          jnp.bfloat16)
    params = mixer.init(jax.random.PRNGKey(0), u)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def loss(params, u):
        return jnp.sum(mixer.apply(params, u).astype(jnp.float32))

    traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, u)
    violations, stats = jaxpr_audit.audit_jaxpr("ssd", traced.jaxpr)
    assert [v for v in violations if v.rule == "island_cast"] == []
    assert stats["island_casts"] == 0
    inside = {eqn.primitive.name
              for _, eqn in jaxpr_audit.iter_eqns(traced.jaxpr)
              if islands.island_of(eqn.source_info.name_stack) == "ssm_scan"}
    assert _kernels_in_the_island(traced.jaxpr) == [
        "ssd_scan_bwd", "ssd_scan_fwd"]
    assert "exp" in inside and not {"scan", "cumsum", "while"} & inside


def test_the_kernels_products_are_the_compute_dtype_summed_in_float32():
    """Inside both kernels' bodies under bfloat16 operands: every
    ``dot_general`` gives float32; its operands are both bfloat16 (the
    products the ``chunks`` arm makes in bfloat16) or both float32 under
    ``Precision.HIGHEST`` (the sums of log-decays and of their
    gradients); every ``exp`` is float32; and nothing narrower than
    bfloat16 is made anywhere."""
    operands = _operands(256, 1, 8, 1, jnp.bfloat16)

    def loss(*xs):
        return jnp.sum(_fused(ONE)(*xs).astype(jnp.float32))

    traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(
        *operands)
    bodies = dict(pallas_bodies(traced.jaxpr))
    assert sorted(bodies) == ["ssd_scan_bwd", "ssd_scan_fwd"]
    for name, body in bodies.items():
        inside = [eqn for _, eqn in jaxpr_audit.iter_eqns(body)]
        products = [eqn for eqn in inside
                    if eqn.primitive.name == "dot_general"]
        assert len(products) >= (12 if name == "ssd_scan_fwd" else 27)
        sums = 0
        for eqn in products:
            assert eqn.outvars[0].aval.dtype == jnp.float32
            dtypes = {v.aval.dtype for v in eqn.invars}
            if dtypes == {jnp.dtype(jnp.float32)}:
                sums += 1
                assert all(p == jax.lax.Precision.HIGHEST
                           for p in eqn.params["precision"])
            else:
                assert dtypes == {jnp.dtype(jnp.bfloat16)}
        assert sums == (1 if name == "ssd_scan_fwd" else 3)
        decays = [eqn for eqn in inside if eqn.primitive.name == "exp"]
        assert len(decays) >= 8
        assert all(eqn.invars[0].aval.dtype == jnp.float32 for eqn in decays)
        for eqn in inside:
            for var in eqn.outvars:
                dtype = getattr(var.aval, "dtype", None)
                if dtype is not None and jnp.issubdtype(dtype, jnp.floating):
                    assert dtype in (jnp.float32, jnp.bfloat16)


# ------------------------------------------------------------- the meta


def _nemotron_gen(**over):
    from imaginaire_tpu.config import Config

    gen = Config(os.path.join(ROOT, "configs", "projects", "nemotron_h",
                              "nano_30b_a3b_ep16_share.yaml")).gen
    gen["compute_dtype"] = "bfloat16"
    for key, value in over.items():
        gen[key] = value
    return gen


@pytest.mark.parametrize("backend,arm", [("tpu", "fused"), ("cpu", "chunks")])
def test_ssd_impl_says_which_arm_and_what_the_blocks_keep(monkeypatch,
                                                          backend, arm):
    """The ``ssd_impl`` meta of the cell's step (one sequence of 8,192):
    the arm of layers 0, 2, 4 and 7, the kernel's tile constants and the
    bytes a layer's block keeps (its bfloat16 output, 67.1 MB, and 64
    chunks' float32 entry states of 64 heads, 134.2 MB; nothing on the
    ``chunks`` arm, and nothing under a policy that keeps no kernel
    residual); the report prints them."""
    from imaginaire_tpu.telemetry.report import render_report
    from imaginaire_tpu.trainers import lm

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    meta = lm.ssd_impl(_nemotron_gen(), (1, 8192))
    assert meta["layers"] == [0, 2, 4, 7]
    assert (meta["heads"], meta["head_dim"], meta["groups"], meta["state"],
            meta["chunk"]) == (64, 64, 8, 128, 128)
    assert meta["arm"] == dict.fromkeys("0247", arm)
    assert meta["tiles"] == state_space.TILES._asdict()
    a_layer = 8192 * 64 * 64 * 2 + 64 * 128 * 64 * 64 * 4
    assert a_layer == 67_108_864 + 134_217_728
    assert meta["kept_bytes"] == dict.fromkeys(
        "0247", a_layer if arm == "fused" else 0)
    assert lm.ssd_impl(_nemotron_gen(remat="save_nothing"),
                       (1, 8192))["kept_bytes"] == dict.fromkeys("0247", 0)
    report = render_report([{"kind": "meta", "name": "ssd_impl", **meta}])
    assert ("- ssd_impl: layers 0, 2, 4, 7; 64 heads of 64 in 8 groups, "
            "state 128, chunks of 128 steps; "
            f"layer 0 {arm}, layer 2 {arm}, layer 4 {arm}, layer 7 {arm}; "
            f"fused tiles (chunks a grid step) fwd {state_space.TILES.fwd}, "
            f"bwd {state_space.TILES.bwd}; the blocks keep "
            f"{4 * a_layer if arm == 'fused' else 0} bytes") in report


def test_the_unit_test_model_takes_the_chunks_arm_on_any_backend(
        monkeypatch):
    """``configs/unit_test/hybrid_lm.yaml``'s heads of 16 are under the
    kernels' lane tile: ``chunks``, on a TPU too."""
    from imaginaire_tpu.trainers import lm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    meta = lm.ssd_impl(tiny_cfg().gen, (2, 64))
    assert meta["layers"] == [0, 2]
    assert meta["arm"] == {"0": "chunks", "2": "chunks"}
    assert meta["kept_bytes"] == {"0": 0, "2": 0}


def test_a_model_without_mamba_layers_has_no_ssd_impl():
    from imaginaire_tpu.config import Config
    from imaginaire_tpu.trainers import lm

    for name in ("glm4_moe_lite", "solar_open2", "lfm2_moe", "afmoe",
                 "smallthinker"):
        gen = Config(os.path.join(ROOT, "configs", "unit_test",
                                  name + ".yaml")).gen
        assert lm.ssd_impl(gen, (2, 64)) is None
