"""``ops/delta_rule.py`` (ISSUE 43): the fused delta-rule kernels (in
Pallas's interpreter, on the CPU) against the ``chunks`` arm and against
the reference's step-by-step recurrence, output and all five gradients;
where a channel forgets everything in a step; the rule that picks the
arm; what a recomputed block keeps; the trainer's meta."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hybrid_lm_util import ROOT, pallas_bodies, pallas_calls, tiny_cfg

from imaginaire_tpu.analysis import islands, jaxpr_audit
from imaginaire_tpu.ops import delta_rule

CHUNK, DIM = 64, 128
NAMES = ("out", "dq", "dk", "dv", "da", "dbeta")
# one chunk a grid step, and a sweep each way with two
ONE = delta_rule.Tiles(fwd=1, bwd=1)
TWO = delta_rule.Tiles(fwd=2, bwd=2)


def _operands(length, bsz, heads, dtype=jnp.float32, dim=DIM):
    """q, k (l2-normed), v (bsz, length, heads, dim) in ``dtype``; the
    log-decays of channels from rate e^-6 (hold for the whole sequence)
    to e^2.5 (forget in a step); beta up to 2, most of it above 1."""
    from benchmark.reference import solar_open2_train as reference

    keys = jax.random.split(jax.random.PRNGKey(length + heads), 6)
    q, k, v = (jax.random.normal(key, (bsz, length, heads, dim))
               for key in keys[:3])
    rate = jnp.exp(jax.random.uniform(keys[3], (heads, dim), minval=-6.0,
                                      maxval=2.5))
    steps = jax.nn.softplus(
        jax.random.normal(keys[4], (bsz, length, heads, dim)))
    beta = 2 * jax.nn.sigmoid(
        jax.random.normal(keys[5], (bsz, length, heads)) + 1.0)
    q, k, v = (x.astype(dtype)
               for x in (reference.l2_norm(q), reference.l2_norm(k), v))
    return q, k, v, -rate * steps, beta


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
    return lambda *xs: delta_rule.fused_delta_rule(*xs, chunk, tiles, True)


def _chunks(*xs, chunk=CHUNK):
    return delta_rule.kda_scan(*xs, chunk)


def _recurrence(q, k, v, a, beta):
    from benchmark.reference import solar_open2_train as reference

    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    return jax.vmap(reference.delta_rule)(q, k, v, a, beta)


def _close(ours, theirs, tol):
    ours, theirs = np.asarray(ours), np.asarray(theirs)
    scale = max(float(np.abs(theirs).max()), 1e-6)
    assert float(np.abs(ours - theirs).max()) <= tol * scale


LAYOUTS = {
    # (length, sequences, heads), tiles, operands' dtype
    "two_chunks": ((128, 2, 2), ONE, jnp.float32),
    "three_chunks_a_step_each": ((192, 1, 1), TWO, jnp.float32),
    "four_chunks_two_a_step": ((256, 1, 3), TWO, jnp.float32),
    "bfloat16": ((128, 1, 2), ONE, jnp.bfloat16),
}


@functools.lru_cache(maxsize=None)
def _three_ways(layout):
    shape, tiles, dtype = LAYOUTS[layout]
    operands = _operands(*shape, dtype)
    assert float(operands[3].min()) < -20 and float(operands[4].max()) > 1.9
    return tuple(_with_gradients(fn, operands)
                 for fn in (_fused(tiles), _chunks, _recurrence))


@pytest.mark.parametrize("which", range(6), ids=NAMES)
@pytest.mark.parametrize("layout", LAYOUTS)
def test_fused_arm_matches_chunks_arm_and_the_recurrence(layout, which):
    """Output and every gradient at head size 128 and chunks of 64, at the
    tolerances the ``chunks`` arm is held to the recurrence by
    (``tests/test_hybrid_lm_layers.py``: 2e-5 of the largest value for the
    output, 1e-4 for a gradient). Under bfloat16 operands both arms round
    their float32 results to bfloat16 (half a unit in the last place: 4e-3
    of the value) and read a cotangent rounded so; the recurrence reads
    the same rounded operands in float32 and rounds nothing after."""
    fused, chunks, recurrence = _three_ways(layout)
    tol = rounded = 2e-5 if which == 0 else 1e-4
    if LAYOUTS[layout][2] == jnp.bfloat16:
        tol, rounded = (8e-3 if which < 4 else 1e-4), 8e-3
    _close(fused[which], chunks[which], tol)
    _close(fused[which], recurrence[which], rounded)


OTHER_SHAPES = {
    # what else ``arm_of`` sends to the kernels: (head size, chunk, length,
    # heads), two chunks each
    "head_256": (256, 64, 128, 1),
    "chunk_128": (128, 128, 256, 1),
    "chunk_32": (128, 32, 64, 2),
    "chunk_16": (128, 16, 32, 2),
}


@functools.lru_cache(maxsize=None)
def _three_ways_at(shape):
    dim, chunk, length, heads = OTHER_SHAPES[shape]
    operands = _operands(length, 1, heads, dim=dim)
    return tuple(_with_gradients(fn, operands) for fn in (
        _fused(ONE, chunk), functools.partial(_chunks, chunk=chunk),
        _recurrence))


@pytest.mark.parametrize("which", range(6), ids=NAMES)
@pytest.mark.parametrize("shape", OTHER_SHAPES)
def test_fused_arm_at_the_other_shapes_the_rule_sends_it(shape, which):
    """Head size 256 (a (256, 256) state), and chunks of one, two and
    eight sub-blocks (no merge by halves, one level of it, three), each
    against the ``chunks`` arm and the recurrence at the tolerances of
    the cell's shape."""
    fused, chunks, recurrence = _three_ways_at(shape)
    tol = 2e-5 if which == 0 else 1e-4
    _close(fused[which], chunks[which], tol)
    _close(fused[which], recurrence[which], tol)


def test_fused_arm_holds_where_a_channel_forgets_everything_in_a_step():
    """The case of ``test_kda_scan_holds_where_a_channel_forgets_
    everything_in_a_step``: log-decays down to -80 a step in some channels
    (a sub-block's factors ``e^(c_i - r_I)`` and ``e^(r_I - c_j)`` underflow
    to 0 there) beside 0 in others. The kernel forms no exponent above 0,
    forward or backward, so output and gradients are finite, and the
    reference's."""
    q, k, v, _, beta = _operands(128, 1, 2)
    steps = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(4),
                                              q.shape))
    depth = jnp.where(jnp.arange(DIM) % 3 == 0, 80.0, 0.0).at[1].set(5.0)
    a = -depth * jnp.minimum(steps, 1.0)
    assert float(a.min()) == -80.0 and float(a.max()) == 0.0
    assert float(jnp.cumsum(a, 1).min()) < -5000
    fused, recurrence = (_with_gradients(fn, (q, k, v, a, beta))
                         for fn in (_fused(TWO), _recurrence))
    for which, (ours, theirs) in enumerate(zip(fused, recurrence)):
        assert bool(jnp.isfinite(ours).all())
        _close(ours, theirs, 2e-5 if which == 0 else 1e-4)


def test_nothing_after_a_position_reaches_it():
    """The sweeps are causal across a chunk's edge and inside a chunk:
    changing the operands from position ``t`` on leaves every earlier
    output as it was, to the bit."""
    operands = _operands(128, 1, 1)
    out = _fused(ONE)(*operands)
    for t in (40, 64, 100):
        changed = tuple(x.at[:, t:].multiply(0.5) for x in operands)
        again = _fused(ONE)(*changed)
        np.testing.assert_array_equal(np.asarray(out[:, :t]),
                                      np.asarray(again[:, :t]))
        assert float(jnp.abs(out[:, t:] - again[:, t:]).max()) > 0


@pytest.mark.parametrize("backend,dim,chunk,length,arm", [
    ("tpu", 128, 64, 8192, "fused"),
    ("tpu", 256, 64, 8192, "fused"),
    ("tpu", 128, 16, 64, "fused"),
    ("tpu", 128, 32, 64, "fused"),
    ("tpu", 128, 128, 8192, "fused"),
    ("cpu", 128, 64, 8192, "chunks"),     # where the tests run
    ("tpu", 16, 16, 64, "chunks"),        # the unit-test YAML's head
    ("tpu", 128, 64, 8190, "chunks"),     # a ragged length
    ("tpu", 128, 24, 8184, "chunks"),     # a chunk 16 does not divide
    ("tpu", 128, 48, 8208, "chunks"),     # three sub-blocks: no halves
])
def test_the_rule(monkeypatch, backend, dim, chunk, length, arm):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert delta_rule.arm_of(dim, chunk, length) == arm


def test_delta_rule_takes_the_chunks_arm_here():
    """On the CPU ``delta_rule`` is ``kda_scan``: no kernel in its
    program, at the cell's head size too."""
    operands = _operands(128, 1, 1)
    traced = jax.make_jaxpr(
        lambda *xs: delta_rule.delta_rule(*xs, CHUNK))(*operands)
    assert pallas_calls(traced.jaxpr) == []
    np.testing.assert_array_equal(
        np.asarray(delta_rule.delta_rule(*operands, CHUNK)),
        np.asarray(_chunks(*operands)))


def test_the_tiles_divide_the_cells_chunks():
    """8,192 positions in chunks of 64 are 128 chunks: each sweep's tile
    divides them, and a length whose chunks it does not divide takes
    their common divisor."""
    for tile in delta_rule.TILES:
        assert delta_rule.per_step(8192, 64, tile) == tile
    assert delta_rule.per_step(192, 64, 2) == 1
    assert delta_rule.per_step(384, 64, 4) == 2


# -------------------------------------------- under a block's recompute


def _block_gradients(policy):
    """(the kernel calls in the gradient's jaxpr, the gradients) of a
    block ``x -> q, k, v, a, beta -> fused delta rule -> W_o`` recomputed
    under a remat policy."""
    from benchmark.reference import solar_open2_train as reference

    from imaginaire_tpu.optim.remat import POLICIES

    hidden, length, heads = 32, 128, 2
    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    x = jax.random.normal(keys[0], (1, length, hidden)).astype(jnp.bfloat16)
    kernels = [(jax.random.normal(key, (hidden, heads * DIM)) / 8
                ).astype(jnp.bfloat16) for key in keys[1:5]]
    kernels.append((jax.random.normal(keys[5], (hidden, heads)) / 8
                    ).astype(jnp.bfloat16))
    kernels.append((jax.random.normal(keys[6], (heads * DIM, hidden)) / 16
                    ).astype(jnp.bfloat16))

    def block(x, kernels):
        w_q, w_k, w_v, w_a, w_b, w_o = kernels
        q, k, v, f = ((x @ w).reshape(1, length, heads, DIM)
                      for w in (w_q, w_k, w_v, w_a))
        q, k = (reference.l2_norm(y.astype(jnp.float32)).astype(y.dtype)
                for y in (q, k))
        a = -jax.nn.softplus(f.astype(jnp.float32))
        beta = 2.0 * jax.nn.sigmoid((x @ w_b).astype(jnp.float32))
        out = delta_rule.fused_delta_rule(q, k, v, a, beta, CHUNK, ONE, True)
        return out.reshape(1, length, -1) @ w_o

    def loss(x, kernels):
        out = jax.checkpoint(block, policy=POLICIES[policy].policy)(x, kernels)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    grad = jax.grad(loss, argnums=(0, 1))
    return (pallas_calls(jax.make_jaxpr(grad)(x, kernels).jaxpr),
            jax.tree_util.tree_leaves(grad(x, kernels)))


def test_a_recomputed_block_runs_the_forward_sweep_once():
    """Under ``blocks`` the block keeps what the forward sweep names
    ``KERNEL_RESIDUAL`` (its output and the chunks' entry states), so its
    recompute holds no second forward sweep; under ``save_nothing`` it
    holds one; and the kept arrays are the ones the second sweep would
    have written, so no gradient moves by a bit."""
    kept_calls, kept = _block_gradients("blocks")
    again_calls, again = _block_gradients("save_nothing")
    assert sorted(kept_calls) == ["delta_rule_bwd", "delta_rule_fwd"]
    assert sorted(again_calls) == [
        "delta_rule_bwd", "delta_rule_fwd", "delta_rule_fwd"]
    assert len(kept) == len(again) == 7
    for a, b in zip(kept, again):
        assert np.abs(np.asarray(a, np.float32)).max() > 0
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_the_forward_sweep_names_its_output_and_states_kernel_residual():
    """The two ``name`` equations of the forward rule carry
    ``KERNEL_RESIDUAL``, the name ``POLICIES["blocks"]`` saves; a call
    that is not differentiated asks the kernel for no states."""
    from imaginaire_tpu.ops.attention import KERNEL_RESIDUAL

    operands = _operands(128, 1, 2, jnp.bfloat16)
    traced = jax.make_jaxpr(
        lambda *xs: jax.vjp(_fused(ONE), *xs)[0])(*operands)
    named = [eqn for _, eqn in jaxpr_audit.iter_eqns(traced.jaxpr)
             if eqn.primitive.name == "name"]
    assert [eqn.params["name"] for eqn in named] == [KERNEL_RESIDUAL] * 2
    kept = sorted((eqn.outvars[0].aval.shape, eqn.outvars[0].aval.dtype)
                  for eqn in named)
    assert kept == [((1, 2, 2, DIM, DIM), jnp.float32),
                    ((1, 128, 2, DIM), jnp.float32)]
    assert delta_rule.residual_bytes(1, 128, 2, DIM, CHUNK) == (
        128 * 2 * DIM * 4 + 2 * 2 * DIM * DIM * 4)
    plain = jax.make_jaxpr(_fused(ONE))(*operands)
    (call,) = [eqn for _, eqn in jaxpr_audit.iter_eqns(plain.jaxpr)
               if eqn.primitive.name == "pallas_call"]
    assert len(call.outvars) == 1


def _kernels_in_the_island(jaxpr):
    """The names of the ``pallas_call``s inside the jitted calls that
    stand under the ``delta_rule`` island's scope (the layers of a model
    share one jitted function a sweep), sorted."""
    return sorted(
        name for _, eqn in jaxpr_audit.iter_eqns(jaxpr)
        if eqn.primitive.name == "jit"
        and islands.island_of(eqn.source_info.name_stack) == "delta_rule"
        for name in pallas_calls(eqn.params["jaxpr"].jaxpr))


def test_the_kernels_are_a_float32_island_without_a_cast_down():
    """Forward and backward, the ``pallas_call``s stand inside the
    ``delta_rule`` island and the step's graph audit finds no cast down
    in it under bfloat16 operands: the kernels read and write float32,
    and the casts stand outside the island as ``kda_scan``'s do."""
    operands = _operands(128, 1, 2, jnp.bfloat16)

    def loss(*xs):
        return jnp.sum(_fused(ONE)(*xs).astype(jnp.float32))

    traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(
        *operands)
    violations, stats = jaxpr_audit.audit_jaxpr("kda", traced.jaxpr)
    assert [v for v in violations if v.rule == "island_cast"] == []
    assert stats["island_casts"] == 0
    assert _kernels_in_the_island(traced.jaxpr) == [
        "delta_rule_bwd", "delta_rule_fwd"]


def test_the_mixer_on_the_fused_arm_is_a_float32_island(monkeypatch):
    """``test_the_delta_rule_is_a_float32_island_under_bfloat16_compute``
    on the arm the chip takes: a Kimi Delta Attention mixer of the
    unit-test preset at 2 heads of 128 and chunks of 64 under bfloat16
    compute, with the backend read as a TPU. Its gradient holds the two
    kernels inside ``delta_rule``, no ``scan`` there, and no cast down in
    any island, forward or backward."""
    from imaginaire_tpu.models.generators import hybrid_lm

    cfg = tiny_cfg("solar_open2", compute_dtype="bfloat16", kda_chunk_size=64,
                   linear_attn_config={"short_conv_kernel_size": 4,
                                       "head_dim": DIM, "num_heads": 2})
    g = hybrid_lm.model_settings(cfg.gen)
    mixer = hybrid_lm.KDAMixer(g)
    u = jax.random.normal(jax.random.PRNGKey(3), (1, 128, g.hidden_size),
                          jnp.bfloat16)
    params = mixer.init(jax.random.PRNGKey(0), u)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def loss(params, u):
        return jnp.sum(mixer.apply(params, u).astype(jnp.float32))

    traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, u)
    violations, stats = jaxpr_audit.audit_jaxpr("kda", traced.jaxpr)
    assert [v for v in violations if v.rule == "island_cast"] == []
    assert stats["island_casts"] == 0
    inside = {eqn.primitive.name
              for _, eqn in jaxpr_audit.iter_eqns(traced.jaxpr)
              if islands.island_of(eqn.source_info.name_stack)
              == "delta_rule"}
    assert _kernels_in_the_island(traced.jaxpr) == [
        "delta_rule_bwd", "delta_rule_fwd"]
    assert {"exp", "logistic"} <= inside and "scan" not in inside


def test_every_product_in_the_kernels_is_float32_at_highest_precision():
    """Inside both kernels' bodies every ``dot_general`` takes float32
    operands under ``Precision.HIGHEST``, and every ``exp`` float32."""
    operands = _operands(128, 1, 1, jnp.bfloat16)

    def loss(*xs):
        return jnp.sum(_fused(ONE)(*xs).astype(jnp.float32))

    traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(
        *operands)
    bodies = dict(pallas_bodies(traced.jaxpr))
    assert sorted(bodies) == ["delta_rule_bwd", "delta_rule_fwd"]
    for body in bodies.values():
        inside = [eqn for _, eqn in jaxpr_audit.iter_eqns(body)]
        products = [eqn for eqn in inside
                    if eqn.primitive.name == "dot_general"]
        assert len(products) >= 10
        for eqn in products:
            assert all(v.aval.dtype == jnp.float32 for v in eqn.invars)
            assert all(p == jax.lax.Precision.HIGHEST
                       for p in eqn.params["precision"])
        decays = [eqn for eqn in inside if eqn.primitive.name == "exp"]
        assert len(decays) >= 64
        assert all(eqn.invars[0].aval.dtype == jnp.float32 for eqn in decays)


# ------------------------------------------------------------- the meta


def _solar_gen(**over):
    from imaginaire_tpu.config import Config

    gen = Config(os.path.join(ROOT, "configs", "projects", "solar_open2",
                              "250b_ep40_tp8_share.yaml")).gen
    gen["compute_dtype"] = "bfloat16"
    for key, value in over.items():
        gen[key] = value
    return gen


@pytest.mark.parametrize("backend,arm", [("tpu", "fused"), ("cpu", "chunks")])
def test_kda_impl_says_which_arm_and_what_the_blocks_keep(monkeypatch,
                                                          backend, arm):
    """The ``kda_impl`` meta of the cell's step (one sequence of 8,192):
    the arm of layers 2, 4 and 6, the kernel's tile constants and the
    bytes a layer's block keeps (its float32 output, 33.6 MB, and 128
    chunks' float32 entry states of 8 heads, 67.1 MB; nothing on the
    ``chunks`` arm, and nothing under a policy that keeps no kernel
    residual); the report prints them."""
    from imaginaire_tpu.telemetry.report import render_report
    from imaginaire_tpu.trainers import lm

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    meta = lm.kda_impl(_solar_gen(), (1, 8192))
    assert meta["layers"] == [2, 4, 6]
    assert meta["arm"] == dict.fromkeys("246", arm)
    assert meta["tiles"] == delta_rule.TILES._asdict()
    a_layer = 8192 * 8 * 128 * 4 + 8 * 128 * 128 * 128 * 4
    assert a_layer == 33_554_432 + 67_108_864
    assert meta["kept_bytes"] == dict.fromkeys(
        "246", a_layer if arm == "fused" else 0)
    assert lm.kda_impl(_solar_gen(remat="save_nothing"),
                       (1, 8192))["kept_bytes"] == dict.fromkeys("246", 0)
    report = render_report([{"kind": "meta", "name": "kda_impl", **meta}])
    assert ("- kda_impl: layers 2, 4, 6; 8 heads of 128 held; chunks of 64 "
            "steps in sub-blocks of 16, 8 at once; "
            f"layer 2 {arm}, layer 4 {arm}, layer 6 {arm}; fused tiles "
            f"(chunks a grid step) fwd {delta_rule.TILES.fwd}, bwd "
            f"{delta_rule.TILES.bwd}; the blocks keep "
            f"{3 * a_layer if arm == 'fused' else 0} bytes") in report


def test_a_model_without_delta_rule_layers_has_no_kda_impl():
    from imaginaire_tpu.config import Config
    from imaginaire_tpu.trainers import lm

    for name in ("hybrid_lm", "glm4_moe_lite", "lfm2_moe", "afmoe"):
        gen = Config(os.path.join(ROOT, "configs", "unit_test",
                                  name + ".yaml")).gen
        assert lm.kda_impl(gen, (2, 64)) is None
