"""The hybrid token model's mixers against the plain reference's, values
and gradients, at the tiny preset's sizes on the CPU (ISSUE 27 (a), (c),
(d)): the chunked state-space scan against the step-by-step recurrence,
also at a length the chunk does not divide; causal grouped-query attention
by query blocks; dropless routing over a held share of the experts; the
shares adding up to the whole layer; skew and an overfull buffer; the
expert layer on the filled prefix of its buffer against the whole buffer
(ISSUE 28)."""

import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hybrid_lm_util import layer_params, seeded, tiny_cfg

from imaginaire_tpu.models.generators import hybrid_lm


def _close(ours, theirs, tol=2e-5):
    ours, theirs = np.asarray(ours), np.asarray(theirs)
    scale = max(float(np.abs(theirs).max()), 1e-6)
    assert float(np.abs(ours - theirs).max()) <= tol * scale


def _value_and_grads(fn, args):
    """fn(*args) and the gradients to every argument of its fixed random
    projection, in one compiled program."""
    def run(*args):
        out = fn(*args)
        weights = jax.random.normal(jax.random.PRNGKey(9), out.shape)
        return jnp.sum(out * weights), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        run, argnums=tuple(range(len(args))), has_aux=True))(*args)
    return out, grads


def _inputs(cfg, length, seed=3):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (2, length, cfg.gen.hidden_size), jnp.float32)


@pytest.mark.parametrize("length", [64, 50])
def test_ssd_scan_is_the_step_by_step_recurrence(length):
    cfg = tiny_cfg()
    reference, sizes, _, _ = seeded(cfg, 1)
    heads, p = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    groups, n = sizes["n_groups"], sizes["ssm_state_size"]
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(keys[0], (2, length, heads, p))
    b = jax.random.normal(keys[1], (2, length, groups, n))
    c = jax.random.normal(keys[2], (2, length, groups, n))
    dt = jax.nn.softplus(jax.random.normal(keys[3], (2, length, heads)) - 2)
    a = -jnp.exp(jax.random.uniform(keys[4], (heads,), minval=0.0,
                                    maxval=2.5))

    def ours(x, dt, a, b, c):
        return hybrid_lm.ssd_scan(x, dt, a, b, c, sizes["chunk_size"])

    def theirs(x, dt, a, b, c):
        return jax.vmap(reference.recurrence,
                        in_axes=(0, 0, None, 0, 0))(x, dt, a, b, c)

    (y_ours, g_ours), (y_theirs, g_theirs) = (
        _value_and_grads(f, (x, dt, a, b, c)) for f in (ours, theirs))
    _close(y_ours, y_theirs)
    for mine, plain in zip(g_ours, g_theirs):
        _close(mine, plain, tol=1e-4)


@pytest.mark.parametrize("kind,index,length", [
    ("M", 0, 50), ("*", 3, 50), ("E", 1, 64)])
def test_mixer_follows_the_reference(kind, index, length):
    """Values and gradients (to the input and to every parameter) of one
    mixer; attention at two query blocks, the second one ragged at 50."""
    cfg = tiny_cfg()
    reference, sizes, train, buffers = seeded(cfg, 5)
    settings = hybrid_lm.model_settings(cfg.gen)
    module = hybrid_lm._MIXERS[kind](settings)
    u = _inputs(cfg, length)
    prefix = f"layer_{index}/mixer/"
    params = layer_params(train, index)
    variables = {"buffers": layer_params(buffers, index)}

    def ours(params, u):
        out = module.apply({"params": params, **variables}, u)
        return out[0] if kind == "E" else out

    def theirs(params, u):
        p = {**{prefix + k: v for k, v in params.items()}, **buffers}
        if kind == "E":
            return reference.moe(p, prefix, sizes, u, "float32", 0.0)[0]
        fn = reference.mamba2 if kind == "M" else reference.attention
        return fn(p, prefix, sizes, u, "float32")

    (y_ours, g_ours), (y_theirs, g_theirs) = (
        _value_and_grads(f, (params, u)) for f in (ours, theirs))
    _close(y_ours, y_theirs)
    _close(g_ours[1], g_theirs[1], tol=1e-4)
    for name in params:
        _close(g_ours[0][name], g_theirs[0][name], tol=1e-4)


def test_the_shares_add_up_to_the_whole_layer():
    """ISSUE 27 (c): the routed parts that the two shares of four experts
    give, with the shared expert counted once, are what the uncut
    reference gives for the layer with all eight experts."""
    whole_cfg = tiny_cfg(experts_held={"first": 0, "count": 8, "of": 8})
    reference, sizes, train, buffers = seeded(whole_cfg, 7)
    prefix = "layer_1/mixer/"
    u = _inputs(whole_cfg, 64)
    p = {**train, **buffers}
    whole, _ = jax.jit(lambda p, u: reference.moe(
        p, prefix, sizes, u, "float32", 0.0))(p, u)
    x = u.reshape(-1, u.shape[-1])
    shared = reference.relu2(x @ p[prefix + "shared_up"]) \
        @ p[prefix + "shared_down"]
    total = shared.reshape(u.shape)
    held_rows = 0.0
    for first in (0, 4):
        cfg = tiny_cfg(experts_held={"first": first, "count": 4, "of": 8})
        module = hybrid_lm.MoEMixer(hybrid_lm.model_settings(cfg.gen))
        params = dict(layer_params(train, 1))
        for name in ("experts_up", "experts_down"):
            params[name] = params[name][first:first + 4]
        out, stats = jax.jit(module.apply)(
            {"params": params, "buffers": layer_params(buffers, 1)}, u)
        total = total + out - shared.reshape(u.shape)
        held_rows += float(stats["held_assignments"])
    _close(total, whole)
    # every assignment landed on exactly one share
    assert held_rows == u.shape[0] * u.shape[1] * sizes["num_experts_per_tok"]


def _route_all_to(expert, tokens, top_k=2):
    experts = jnp.stack([jnp.full((tokens,), expert, jnp.int32),
                         jnp.full((tokens,), 7, jnp.int32)], axis=1)
    return experts, jnp.full((tokens, top_k), 0.5, jnp.float32)


def test_skew_loses_no_assignment():
    """ISSUE 27 (d): every token on one held expert; the buffer holds
    them all and the counts say so."""
    experts, weights = _route_all_to(2, 128)
    token, weight, valid, group_sizes, stats = hybrid_lm.route_held(
        experts, weights, first=0, count=4, rows=256)
    assert [int(n) for n in group_sizes] == [0, 0, 128, 0]
    assert int(valid.sum()) == 128 and float(stats["overflow"]) == 0
    assert sorted(np.asarray(token)[np.asarray(valid)]) == list(range(128))
    assert float(weight.sum()) == 64.0
    assert float(stats["held_assignments"]) == 128
    assert float(stats["load_max_over_mean"]) == 4.0
    assert float(stats["buffer_occupancy"]) == 0.5


def test_an_overfull_buffer_is_counted_not_silent():
    experts, weights = _route_all_to(1, 128)
    _, weight, valid, group_sizes, stats = hybrid_lm.route_held(
        experts, weights, first=0, count=4, rows=96)
    assert float(stats["overflow"]) == 32
    assert int(group_sizes.sum()) == 96 and int(valid.sum()) == 96
    assert float(stats["held_assignments"]) == 128


# --- the filled prefix (ISSUE 28): 128 tokens, top 2, experts 0 to 3 of 8
# held, a buffer of 256 rows; the short tier is 128 rows, a row a token


def _steered(held):
    """A router kernel and an input whose first eight features name each
    token's two experts, so that exactly `held` of the 256 assignments
    land on the held experts; feature 8 is one everywhere."""
    cfg = tiny_cfg()
    hidden = cfg.gen.hidden_size
    pairs = []
    for t in range(128):
        if 2 * t + 1 < held:
            pairs.append((t % 4, (t + 1) % 4))          # both held
        elif 2 * t < held:
            pairs.append((t % 4, 4 + t % 4))            # one held
        else:
            pairs.append((4 + t % 4, 4 + (t + 1) % 4))  # none held
    u = np.array(_inputs(cfg, 64)).reshape(128, hidden)
    u[:, :8] = -1.0
    for t, pair in enumerate(pairs):
        u[t, list(pair)] = 1.0
    u[:, 8] = 1.0
    router = 0.02 * np.array(jax.random.normal(
        jax.random.PRNGKey(4), (hidden, 8)))
    router[:8] = 6.0 * np.eye(8)
    return jnp.asarray(router), jnp.asarray(u.reshape(2, 64, hidden))


@functools.lru_cache(maxsize=None)
def _prefix_and_whole_buffer():
    """The layer as the model runs it and its single-tier twin (the same
    module computing on the whole buffer, whatever it holds), each with
    value, stats and gradients in one program, compiled once for the
    four cases."""
    cfg = tiny_cfg()
    g = hybrid_lm.model_settings(cfg.gen)
    module = hybrid_lm.MoEMixer(g)

    def layer(params, buffers, u):
        return module.apply({"params": params, "buffers": buffers}, u)

    def whole_buffer(tiers, n_held, *operands):
        return hybrid_lm.held_experts_part(*operands, rows=tiers[-1])

    def run(params, buffers, u):
        out, stats = layer(params, buffers, u)
        weights = jax.random.normal(jax.random.PRNGKey(9), out.shape)
        return jnp.sum(out * weights), (out, stats)

    _, _, train, buffers = seeded(cfg, 5)
    params, buffers = layer_params(train, 1), layer_params(buffers, 1)
    u = _inputs(cfg, 64)
    programs = []
    for tier_rule in (hybrid_lm.on_filled_prefix, whole_buffer):
        with mock.patch.object(hybrid_lm, "on_filled_prefix", tier_rule):
            programs.append(jax.jit(jax.value_and_grad(
                run, argnums=(0, 2), has_aux=True)).lower(
                    params, buffers, u).compile())
    return (*programs, params, buffers, g)


@pytest.mark.parametrize("held,compact", [
    (40, 1.0), (128, 1.0), (129, 0.0), (256, 0.0)],
    ids=["well_under", "exactly_a_row_a_token", "one_more", "every_one"])
def test_the_filled_prefix_is_the_whole_buffer(held, compact):
    """Output, gradients to the input and to every kernel, and all stats
    equal the single-tier layer's on both sides of the threshold;
    `compact` says which tier ran; nothing overflows and no assignment is
    lost where the whole buffer is needed."""
    tiered, whole, params, buffers, g = _prefix_and_whole_buffer()
    router, u = _steered(held)
    params = dict(params, router=router)
    (_, (out, stats)), grads = tiered(params, buffers, u)
    (_, (out_w, stats_w)), grads_w = whole(params, buffers, u)
    assert float(stats["compact"]) == compact
    assert float(stats["held_assignments"]) == held
    assert float(stats["overflow"]) == 0
    assert {k: float(v) for k, v in stats.items()} == {
        k: float(v) for k, v in stats_w.items()}
    # to rounding: XLA:CPU's products sum in another order at another
    # number of rows (a lost assignment would show at 1e-2)
    _close(out, out_w, tol=2e-6)
    _close(grads[1], grads_w[1], tol=2e-6)
    for name in params:
        _close(grads[0][name], grads_w[0][name], tol=2e-6)
    assert float(jnp.abs(grads[0]["experts_up"]).max()) > 0
    # every held expert a constant map of feature 8, the shared expert
    # silent: a token's result is 0.25 times the routing weights that
    # reached it, and their sum is the router's over the held experts
    probe = dict(
        params, shared_up=jnp.zeros_like(params["shared_up"]),
        experts_up=jnp.zeros_like(params["experts_up"]).at[:, 8].set(0.5),
        experts_down=jnp.full_like(params["experts_down"],
                                   1.0 / g.moe_intermediate_size))
    (_, (out, _)), _ = tiered(probe, buffers, u)
    experts, weights = hybrid_lm.route(
        u.reshape(128, -1), router, buffers["score_bias"],
        g.num_experts_per_tok, g.routed_scaling_factor)
    sent = float(jnp.where(experts < g.held_count, weights, 0.0).sum())
    reached = float(out.sum()) / (0.25 * g.hidden_size)
    assert abs(reached - sent) <= 1e-4 * sent and sent > 0


def test_bad_held_share_and_pattern_fail_loudly():
    with pytest.raises(ValueError, match="experts_held"):
        hybrid_lm.model_settings(
            tiny_cfg(experts_held={"first": 6, "count": 4, "of": 8}).gen)
    with pytest.raises(ValueError, match="pattern"):
        hybrid_lm.model_settings(tiny_cfg(pattern="MXE").gen)
