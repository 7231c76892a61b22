"""The early-router token model (ISSUE 45;
`configs/unit_test/smallthinker.yaml`) against its plain reference
`benchmark/reference/smallthinker_train.py`: every mixer, the model's loss
and every leaf's gradient, three trainer steps against the reference's
Adam, the four expert shares against the uncut layer; the router told to
read the attention layer's input and nothing else; the softmax over the
chosen logits; the `relu` gate; the share's parameter count; and the five
accepted token models left as they were (the text their loss and
gradients lower to).

The bodies that every token model shares are the accepted models' own
tests, called here with this preset: one place holds each assertion."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_afmoe_model as accepted
import test_hybrid_lm_layers as layers
import test_hybrid_lm_trainer as through_trainer
from hybrid_lm_util import (layer_params, seeded, sizes_of, tiny_cfg,
                            unflatten)

from imaginaire_tpu.models.generators import hybrid_lm
from imaginaire_tpu.ops import held_experts

PRESET = "smallthinker"


# ------------------------------------------------- against the reference


@pytest.mark.parametrize("kind,index,length", [
    # the full layer and a window layer (24 keys) at 14 query heads on 2,
    # under, and at the ragged second of, two query blocks; an expert
    # layer routed by its own input (the mixer alone has no other)
    ("*", 0, 50), ("W", 2, 50), ("W", 4, 64), ("E", 1, 64), ("E", 5, 64)])
def test_mixer_follows_the_reference(kind, index, length):
    layers.test_mixer_follows_the_reference(PRESET, kind, index, length)


def test_model_loss_and_gradients_follow_the_reference():
    """The whole model: every router on the attention layer's input, the
    window layers' turn and the full layer's none, no buffer."""
    through_trainer.test_model_loss_and_gradients_follow_the_reference(
        PRESET)


def test_trainer_steps_follow_the_reference_adam():
    """Three `gen_update` steps, as many as the benchmark's cell checks."""
    through_trainer.test_trainer_steps_follow_the_reference_adam(PRESET, 3)


def test_the_four_shares_add_up_to_the_whole_layer():
    """The routed parts that four shares of two experts give (there is no
    shared expert) are what the uncut reference gives for the layer with
    all eight experts."""
    layers.test_the_shares_add_up_to_the_whole_layer(PRESET, 2)


# --------------------------------------------------- the router, early


def _model(**gen):
    cfg = tiny_cfg(PRESET, **gen)
    reference, sizes, train, buffers = seeded(cfg, 5, PRESET)
    assert buffers == {}
    return cfg, reference, sizes, train


def _routed(cfg, train, tokens, intermediates=False):
    """The model's outputs on `tokens`, and with `intermediates` every
    block's outputs (``Block`` hands ``u`` on as its third)."""
    net = hybrid_lm.Generator(cfg.gen)
    return net.apply({"params": unflatten(train)}, {"tokens": tokens},
                     capture_intermediates=intermediates,
                     mutable=["intermediates"] if intermediates else False)


def test_the_router_reads_the_attention_layers_normed_input():
    """Layer by layer, with the residual stream rebuilt by hand from the
    reference's functions: the held counts of every expert layer are
    those of `top-k(RMSNorm_1(h) W_r)` of the ATTENTION layer's input,
    and differ from the same product of the experts' own normed input;
    a router moved back behind attention fails here."""
    cfg, reference, sizes, train = _model(remat="none")
    tokens = jnp.asarray(through_trainer._tokens(cfg, seed=3))
    out = _routed(cfg, train, tokens)
    eps, first, count = (sizes["norm_eps"], sizes["experts_held"]["first"],
                         sizes["experts_held"]["count"])
    h = train["embedding"][tokens]
    told_apart = 0
    for index, kind in reference.published_layers(sizes):
        u1 = reference.rms_norm(h, train[f"layer_{index}/scale"], eps)
        h = h + reference._MIXERS[kind](
            train, f"layer_{index}/mixer/", sizes, u1, "float32")
        u2 = reference.rms_norm(h, train[f"layer_{index + 1}/scale"], eps)
        prefix = f"layer_{index + 1}/mixer/"
        held = {}
        for name, read in (("early", u1), ("late", u2)):
            logits = read.reshape(-1, read.shape[-1]) @ train[
                prefix + "router"]
            _, chosen = jax.lax.top_k(logits, sizes["num_experts_per_tok"])
            held[name] = int(((chosen >= first)
                              & (chosen < first + count)).sum())
        ours = int(out[f"moe/{index + 1}/held_assignments"])
        assert ours == held["early"]
        told_apart += held["early"] != held["late"]
        part, _ = reference.moe(train, prefix, sizes, u2, "float32", 0.0,
                                router_input=u1)
        h = h + part
    # the two readings differ in every layer at this seed: the assertion
    # above cannot pass by coincidence
    assert told_apart == 4


def test_the_experts_read_their_own_normed_input():
    """The mixer on (u2, u1): the routing follows u1 alone and the
    experts' products u2 alone. Moving u2 keeps the chosen experts and
    moves the result; moving u1 between two inputs that choose alike
    changes only the weights."""
    cfg, reference, sizes, train = _model()
    g = hybrid_lm.model_settings(cfg.gen)
    module = hybrid_lm.MoEMixer(g)
    params = layer_params(train, 1)
    u1, u2 = (jax.random.normal(jax.random.PRNGKey(k), (2, 64, 64))
              for k in (1, 2))

    def ours(u, read):
        return module.apply({"params": params}, u, read)

    def theirs(u, read):
        return reference.moe(
            {"layer_1/mixer/" + k: v for k, v in params.items()},
            "layer_1/mixer/", sizes, u, "float32", 0.0, router_input=read)

    (y, stats), (want, aux) = ours(u2, u1), theirs(u2, u1)
    layers._close(y, want)
    assert float(stats["held_assignments"]) == float(aux["held_assignments"])
    # routed by its own input the layer is another function
    assert float(jnp.abs(ours(u2, u2)[0] - y).max()) > 1e-3
    # the count follows the router's input, whatever the experts read
    assert float(ours(u1 + 1.0, u1)[1]["held_assignments"]) == float(
        stats["held_assignments"])
    grads = jax.grad(lambda u, read: ours(u, read)[0].sum(),
                     argnums=(0, 1))(u2, u1)
    wanted = jax.grad(lambda u, read: theirs(u, read)[0].sum(),
                      argnums=(0, 1))(u2, u1)
    for got, want in zip(grads, wanted):
        layers._close(got, want, tol=1e-4)
    # the second path: a gradient reaches the attention layer's input
    # through the router's weights
    assert float(jnp.abs(grads[1]).max()) > 0


def test_the_attention_block_hands_its_normed_input_on():
    """`Block`: the letter before an `E` returns `u` third under
    `use_early_router`, and no other block does."""
    cfg, _, _, train = _model(remat="none")
    g = hybrid_lm.model_settings(cfg.gen)
    tokens = jnp.asarray(through_trainer._tokens(cfg, seed=3))
    _, state = _routed(cfg, train, tokens, intermediates=True)
    outs = {name: len(found["__call__"][0])
            for name, found in state["intermediates"].items()
            if name.startswith("layer_")}
    assert outs == {f"layer_{i}": 2 + (i % 2 == 0) for i in range(8)}
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 64))
    params = {"scale": 1.0 + 0.1 * jnp.arange(64.0) / 64,
              "mixer": layer_params(train, 0)}
    out, stats, u = hybrid_lm.Block(g, "*", hands_on=True).apply(
        {"params": params}, h)
    assert stats == {}
    layers._close(u, hybrid_lm.rms_norm(h, params["scale"], g.norm_eps))
    alone = hybrid_lm.Block(g, "*").apply({"params": params}, h)
    assert len(alone) == 2
    np.testing.assert_array_equal(np.asarray(alone[0]), np.asarray(out))


@pytest.mark.parametrize("change,message", [
    (dict(pattern="E*EWEWEW"), "has none before the 'E' at \\[0\\]"),
    (dict(pattern="*EEWEWEW"), "has none before the 'E' at \\[2\\]"),
    (dict(pattern="*EWE", nextn_pattern="E", nextn_loss_weight=0.3),
     "'E' has none"),
    (dict(hidden_act="gelu"), "gen.hidden_act 'gelu' is not one of"),
])
def test_an_early_router_without_its_attention_layer_fails_loudly(
        change, message):
    with pytest.raises(ValueError, match=message):
        hybrid_lm.model_settings(tiny_cfg(PRESET, **change).gen)


# ------------------------------------------------- the scoring, the gate


@pytest.mark.parametrize("experts,top_k", [(64, 6), (8, 2)])
def test_softmax_of_the_chosen_is_softmax_over_all_renormed(experts, top_k):
    """`moe_primary_router_apply_softmax` with `norm_topk_prob`: the
    softmax over the chosen logits is the softmax over every expert,
    renormed over the chosen; the choice is the top k by logit."""
    x = jax.random.normal(jax.random.PRNGKey(0), (128, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (64, experts)) / 8
    chosen, weights = hybrid_lm.route(x, w, None, top_k,
                                      softmax_of_chosen=True)
    logits = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
    want = np.sort(np.argsort(-logits, -1)[:, :top_k], -1)
    np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1), want)
    over_all = np.exp(logits - logits.max(-1, keepdims=True))
    over_all /= over_all.sum(-1, keepdims=True)
    picked = np.take_along_axis(over_all, np.asarray(chosen), -1)
    np.testing.assert_allclose(np.asarray(weights),
                               picked / picked.sum(-1, keepdims=True),
                               rtol=2e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)
    # the accepted scoring, its factor absent: the sum is still one
    bias = jnp.zeros((experts,))
    _, by_sigmoid = hybrid_lm.route(x, w, bias, top_k)
    np.testing.assert_allclose(np.asarray(by_sigmoid).sum(-1), 1.0,
                               rtol=1e-6)
    _, scaled = hybrid_lm.route(x, w, bias, top_k, 2.5)
    np.testing.assert_allclose(np.asarray(scaled),
                               2.5 * np.asarray(by_sigmoid), rtol=1e-6)


def test_a_router_scored_by_softmax_holds_no_bias_and_no_factor():
    """The tree of the unit-test model: no `buffers` collection at all;
    the accepted scorings keep their `score_bias`."""
    cfg = tiny_cfg(PRESET)
    g = hybrid_lm.model_settings(cfg.gen)
    assert (g.routed_scaling_factor, g.moe_primary_router_apply_softmax,
            g.use_early_router) == (None, True, True)
    variables = jax.eval_shape(
        hybrid_lm.Generator(cfg.gen).init, jax.random.PRNGKey(0),
        {"tokens": jnp.zeros((1, 64), jnp.int32)})
    assert set(variables) == {"params"}
    assert set(variables["params"]["layer_1"]["mixer"]) == {
        "router", "experts_gate", "experts_up", "experts_down"}
    late = hybrid_lm.model_settings(tiny_cfg("lfm2_moe").gen)
    assert (late.moe_primary_router_apply_softmax,
            late.use_early_router) == (False, False)


def test_relu_gates_by_its_formula_and_passes_no_gradient_at_zero():
    """`hidden_act: relu`, gated: `relu(gate) * up`; where the gate's
    product is 0 or under it the result and both gradients are 0."""
    gate = jnp.array([-2.0, -0.0, 0.0, 0.5, 3.0])
    up = jnp.array([1.5, -2.0, 4.0, -3.0, 0.25])

    def act(gate, up):
        return held_experts.hidden_activation([gate, up], "relu")

    np.testing.assert_array_equal(
        np.asarray(act(gate, up)), np.maximum(np.asarray(gate), 0) * up)
    d_gate, d_up = jax.grad(lambda g, u: act(g, u).sum(), (0, 1))(gate, up)
    np.testing.assert_array_equal(np.asarray(d_gate),
                                  [0.0, 0.0, 0.0, -3.0, 0.25])
    np.testing.assert_array_equal(np.asarray(d_up),
                                  [0.0, 0.0, 0.0, 0.5, 3.0])
    # the accepted gate, by name and by default
    silu = jax.nn.silu(gate) * up
    for args in (("silu",), ()):
        np.testing.assert_array_equal(np.asarray(
            held_experts.hidden_activation([gate, up], *args)),
            np.asarray(silu))
    assert hybrid_lm.GATED == {"relu2": False, "silu": True, "relu": True}


@pytest.mark.parametrize("held", [1, 513, 8192])
def test_the_held_experts_backward_follows_the_relu_gate(held):
    """`held_experts_part_bwd` is a `jax.vjp` of the products, so the
    gate's derivative follows: under `relu` the result and the gradients
    to `x`, every kernel and `weight` are those of the whole tier
    gathered, multiplied and scatter-added with the same gate."""
    import functools

    operands, ct, rows = layers._segmented_case(held, True)
    floats, placed = operands[:3], operands[3:]
    part = functools.partial(held_experts.held_experts_part, rows=rows)
    out = jax.jit(functools.partial(part, gate="relu"))(*operands)
    assert float(jnp.abs(out - jax.jit(part)(*operands)).max()) > 1e-3
    grads = jax.jit(functools.partial(
        held_experts.held_experts_part_bwd, rows=rows, gate="relu"))(
            ct, *operands)
    want, vjp = jax.vjp(lambda *floats: layers._plain_held_experts_part(
        *floats, *placed, rows=rows, gate="relu"), *floats)
    layers._close(out, want)
    for ours, theirs in zip(jax.tree.leaves(grads),
                            jax.tree.leaves(vjp(ct))):
        assert ours.shape == theirs.shape
        layers._close(ours, theirs)


# ------------------------------------------------------------- the share


def test_the_share_holds_what_the_issue_counted():
    """ISSUE 45's count of the share, by the program's own tree and by
    the reference's list: four attention layers, four expert layers of a
    router and 16 relu-gated experts with no shared expert and no
    buffer, embedding and head, nine norm scales."""
    from benchmark.reference import smallthinker_train as reference
    from imaginaire_tpu.config import Config

    yaml = "smallthinker/21b_a3b_ep4_share.yaml"
    count, _ = layers.tree_digest(yaml)
    assert count == (4 * (20_971_520 + 163_840 + 16 * 5_898_240)
                     + 2 * 37_984 * 2560 + 9 * 2560)
    assert count == 656_529_920
    import os

    from hybrid_lm_util import ROOT

    cfg = Config(os.path.join(ROOT, "configs", "projects", yaml))
    assert reference.parameter_count(sizes_of(cfg)) == count


def test_the_reference_counts_the_band_and_nothing_else():
    """`work` at the cell's sizes: 58,722,304 query-key pairs a head in a
    window layer, 4 x 128 operations a pair forward, three passes."""
    from benchmark.reference import smallthinker_train as reference

    sizes = dict(num_attention_heads=28, num_key_value_heads=4, head_dim=128,
                 sliding_window=4096, hidden_size=2560,
                 moe_intermediate_size=768, pattern="*EWEWEWE",
                 experts_held={"first": 0, "count": 16, "of": 64})
    pairs = sum(min(i + 1, 4096) for i in range(16384))
    assert pairs == 58_722_304
    operations, _ = reference.window_work(sizes, 1, 16384)
    assert operations == 3 * 4 * 128 * 28 * pairs
    work = reference.work(sizes, 1, 16384, {1: 24576.0, 3: 100.0})
    assert work["attn_window"][0] == 3 * operations
    assert work["attn_scores"][0] == 3 * 4 * 128 * 28 * (
        16384 * 16385 // 2)
    assert work["moe_experts"][0] == 3 * 3 * 2 * 24676 * 2560 * 768


# ------------------------------------------- the accepted models, unmoved


# `test_afmoe_model.py`'s table holds four; the fifth token model's line,
# at the commit before ISSUE 45 (SPADE's two step programs import nothing
# this PR changes)
_ACCEPTED_LOWERINGS = {
    **accepted._ACCEPTED_LOWERINGS,
    "afmoe":
        "c8155f1808a476e8a75a70e7c42d757686d1017f2cba6bd9c5e98e11eac0be79",
}


@pytest.mark.parametrize("preset", sorted(_ACCEPTED_LOWERINGS))
def test_the_accepted_models_lower_to_the_text_they_lowered_to(
        preset, monkeypatch):
    """The early router, the second scoring, the `relu` gate and the
    factor a model may lack are absent from the five accepted YAMLs, and
    absent they add no operation: the parent's compile-cache entries
    serve this tree."""
    monkeypatch.setattr(accepted, "_ACCEPTED_LOWERINGS", _ACCEPTED_LOWERINGS)
    accepted.test_the_accepted_models_lower_to_the_text_they_lowered_to(
        preset)
