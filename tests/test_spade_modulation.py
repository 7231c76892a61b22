"""Goldens for the fused SPADE norm->modulate epilogue (ISSUE 16).

The numpy reference below re-derives the epilogue independently of the
jnp/fused implementations: biased instance-norm statistics over
the spatial axes in float64, then ``y = x_hat * (1 + sum(g)) + sum(b)``.
Layer tests pin the integration contract: fused vs unfused is invisible
to everything but the compiler — same outputs, same param tree, same
checkpoint bytes, and the refusal cases (masked partial path, non-
instance base, broadcast maps) fall back to the reference composition.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import serialization

from imaginaire_tpu.layers.activation_norm import (
    AdaptiveNorm,
    HyperSpatiallyAdaptiveNorm,
    SpatiallyAdaptiveNorm,
)
from imaginaire_tpu.ops import spade_modulation
from imaginaire_tpu.ops.spade_modulation import AUTO_IMPLEMENTATION

# downscaled-channel stand-ins for the spade-128/256/512 pyramid levels
# (full-channel shapes are too slow for the CPU tier); the last
# is the multi-cond accumulation case (seg + edge + prior-frame maps)
SHAPES = [((2, 32, 32, 8), 1),    # spade-128 deep block
          ((2, 16, 16, 12), 2),   # spade-256 deep block, 2 conditions
          ((1, 64, 64, 4), 3)]    # spade-512 mid block, 3 conditions


def np_spade(x, gammas, betas, eps=1e-5):
    x64 = x.astype(np.float64)
    mean = x64.mean(axis=(1, 2), keepdims=True)
    var = x64.var(axis=(1, 2), keepdims=True)  # biased, like the layer
    xhat = (x64 - mean) / np.sqrt(var + eps)
    g = np.sum([gi.astype(np.float64) for gi in gammas], axis=0)
    b = np.sum([bi.astype(np.float64) for bi in betas], axis=0)
    return (xhat * (1.0 + g) + b).astype(np.float32)


def _case(rng, shape, n_pairs, dtype=np.float32):
    x = rng.randn(*shape).astype(dtype)
    gs = [(rng.randn(*shape) * 0.1).astype(dtype) for _ in range(n_pairs)]
    bs = [(rng.randn(*shape) * 0.1).astype(dtype) for _ in range(n_pairs)]
    return x, gs, bs


@pytest.mark.parametrize("shape,n_pairs", SHAPES)
@pytest.mark.parametrize("impl", ["jnp", "fused"])
def test_forward_matches_reference(rng, impl, shape, n_pairs):
    x, gs, bs = _case(rng, shape, n_pairs)
    got = np.asarray(spade_modulation(
        jnp.asarray(x), [jnp.asarray(g) for g in gs],
        [jnp.asarray(b) for b in bs], implementation=impl))
    np.testing.assert_allclose(got, np_spade(x, gs, bs),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("shape,n_pairs", SHAPES[:2])
@pytest.mark.parametrize("impl", ["fused"])
def test_grad_matches_jnp_autodiff(rng, impl, shape, n_pairs):
    """The hand-written custom_vjp must match XLA autodiff through the
    jnp composition, for dx and every dgamma_i/dbeta_i of the multi-cond
    accumulation."""
    x, gs, bs = _case(rng, shape, n_pairs)
    args = (jnp.asarray(x), tuple(jnp.asarray(g) for g in gs),
            tuple(jnp.asarray(b) for b in bs))

    def loss(impl_):
        def f(x_, gs_, bs_):
            out = spade_modulation(x_, gs_, bs_, implementation=impl_)
            return jnp.sum(jnp.sin(out))  # non-trivial cotangent
        return f

    want = jax.grad(loss("jnp"), argnums=(0, 1, 2))(*args)
    got = jax.grad(loss(impl), argnums=(0, 1, 2))(*args)
    for w, g in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("impl", ["jnp", "fused"])
def test_bf16_inputs_fp32_stats(rng, impl):
    """bf16 compute dtype: stats still reduce in fp32 (the norm_stats
    island guard executes inside every implementation), the output stays
    bf16, and values track the f32 reference at bf16 resolution."""
    shape, n_pairs = (2, 16, 16, 8), 2
    x, gs, bs = _case(rng, shape, n_pairs)
    to_bf = lambda a: jnp.asarray(a).astype(jnp.bfloat16)  # noqa: E731
    out = jax.jit(
        lambda x_, gs_, bs_: spade_modulation(
            x_, gs_, bs_, implementation=impl)
    )(to_bf(x), tuple(map(to_bf, gs)), tuple(map(to_bf, bs)))
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np_spade(x, gs, bs), rtol=0.1, atol=0.1)


def test_fused_bf16_grad_dtypes(rng):
    x, gs, bs = _case(rng, (2, 8, 8, 4), 2)
    to_bf = lambda a: jnp.asarray(a).astype(jnp.bfloat16)  # noqa: E731
    dx, dgs, dbs = jax.grad(
        lambda x_, gs_, bs_: jnp.sum(spade_modulation(
            x_, gs_, bs_, implementation="fused").astype(jnp.float32)),
        argnums=(0, 1, 2),
    )(to_bf(x), tuple(map(to_bf, gs)), tuple(map(to_bf, bs)))
    assert dx.dtype == jnp.bfloat16
    assert all(t.dtype == jnp.bfloat16 for t in dgs + dbs)


def test_validation_errors(rng):
    x = jnp.asarray(rng.randn(2, 4, 4, 3).astype(np.float32))
    g = jnp.asarray(rng.randn(2, 4, 4, 3).astype(np.float32))
    with pytest.raises(ValueError, match="NHWC"):
        spade_modulation(x[0], [g[0]], [g[0]])
    with pytest.raises(ValueError, match="matched non-empty"):
        spade_modulation(x, [], [])
    with pytest.raises(ValueError, match="matched non-empty"):
        spade_modulation(x, [g, g], [g])
    with pytest.raises(ValueError, match="refusal"):
        spade_modulation(x, [g[:, :1, :1]], [g[:, :1, :1]])
    with pytest.raises(ValueError, match="unknown implementation"):
        spade_modulation(x, [g], [g], implementation="cuda")


# ---------------------------------------------------------------- layers


def _spade_layer(fused, **kw):
    return SpatiallyAdaptiveNorm(
        num_filters=8, base_norm=kw.pop("base_norm", "instance"),
        fused_modulation=fused, **kw)


def test_layer_fused_matches_unfused_multicond(rng, key):
    """SpatiallyAdaptiveNorm: fusing the whole multi-cond accumulation
    changes nothing observable — identical params, identical output."""
    x = jnp.asarray(rng.randn(2, 16, 16, 8).astype(np.float32))
    c1 = jnp.asarray(rng.randn(2, 8, 8, 3).astype(np.float32))
    c2 = jnp.asarray(rng.randn(2, 16, 16, 5).astype(np.float32))
    outs, trees = {}, {}
    for fused in ("fused", "none"):
        layer = _spade_layer(fused)
        params = layer.init(key, x, c1, c2)
        outs[fused] = layer.apply(params, x, c1, c2)
        trees[fused] = params
    assert jax.tree_util.tree_structure(trees["fused"]) \
        == jax.tree_util.tree_structure(trees["none"])
    # same init key + same tree -> checkpoint bytes must be identical:
    # a checkpoint written unfused restores into the fused model
    assert serialization.to_bytes(trees["fused"]) \
        == serialization.to_bytes(trees["none"])
    np.testing.assert_allclose(np.asarray(outs["fused"]),
                               np.asarray(outs["none"]),
                               rtol=1e-5, atol=1e-6)


def test_layer_partial_mask_refuses_to_fuse(rng, key):
    """partial=True with a mask stays on the reference composition:
    fused on/off must be bitwise the same code path."""
    x = jnp.asarray(rng.randn(2, 8, 8, 6).astype(np.float32))
    cond = jnp.asarray(rng.randn(2, 8, 8, 3).astype(np.float32))
    mask = jnp.asarray((rng.rand(2, 8, 8, 1) > 0.5).astype(np.float32))
    outs = {}
    for fused in ("fused", "none"):
        layer = _spade_layer(fused, partial=True)
        params = layer.init(key, x, (cond, mask))
        outs[fused] = layer.apply(params, x, (cond, mask))
    np.testing.assert_array_equal(np.asarray(outs["fused"]),
                                  np.asarray(outs["none"]))


def test_layer_sync_batch_base_refuses_to_fuse(rng, key):
    """The op implements instance statistics only; a sync_batch base
    (the cocostuff SPADE configs) must fall back identically."""
    x = jnp.asarray(rng.randn(2, 8, 8, 6).astype(np.float32))
    cond = jnp.asarray(rng.randn(2, 8, 8, 3).astype(np.float32))
    outs = {}
    for fused in ("fused", "none"):
        layer = _spade_layer(fused, base_norm="sync_batch")
        params = layer.init(key, x, cond)
        outs[fused] = layer.apply(params, x, cond, training=True,
                                  mutable=["batch_stats"])[0]
    np.testing.assert_array_equal(np.asarray(outs["fused"]),
                                  np.asarray(outs["none"]))


def test_hyper_layer_runtime_weight_path(rng, key):
    """HyperSpatiallyAdaptiveNorm: the first pair — produced by the
    predicted per-sample conv — fuses with the norm; later pairs apply
    sequentially. Fused on/off must agree with identical params."""
    b, c, cc = 2, 6, 4
    x = jnp.asarray(rng.randn(b, 8, 8, c).astype(np.float32))
    cond0 = jnp.asarray(rng.randn(b, 8, 8, cc).astype(np.float32))
    cond1 = jnp.asarray(rng.randn(b, 8, 8, 3).astype(np.float32))
    w = jnp.asarray((rng.randn(b, 3, 3, cc, 2 * c) * 0.1)
                    .astype(np.float32))
    bias = jnp.asarray((rng.randn(b, 2 * c) * 0.1).astype(np.float32))
    outs, trees = {}, {}
    for fused in ("fused", "none"):
        layer = HyperSpatiallyAdaptiveNorm(base_norm="instance",
                                           fused_modulation=fused)
        params = layer.init(key, x, cond0, cond1, norm_weights=(w, bias))
        outs[fused] = layer.apply(params, x, cond0, cond1,
                                  norm_weights=(w, bias))
        trees[fused] = params
    assert serialization.to_bytes(trees["fused"]) \
        == serialization.to_bytes(trees["none"])
    np.testing.assert_allclose(np.asarray(outs["fused"]),
                               np.asarray(outs["none"]),
                               rtol=1e-5, atol=1e-6)


def test_adaptive_norm_conv_fuses_linear_refuses(rng, key):
    """AdaptiveNorm: the 'conv' projection emits full-spatial maps and
    fuses; the 'linear' projection's broadcast (B,1,1,C) maps hit the
    op's shape refusal and stay on the reference composition."""
    x = jnp.asarray(rng.randn(2, 8, 8, 6).astype(np.float32))
    style = jnp.asarray(rng.randn(2, 16).astype(np.float32))
    cond = jnp.asarray(rng.randn(2, 8, 8, 16).astype(np.float32))
    for projection, c in (("conv", cond), ("linear", style)):
        outs = {}
        for fused in ("fused", "none"):
            layer = AdaptiveNorm(projection=projection,
                                 base_norm="instance",
                                 fused_modulation=fused)
            params = layer.init(key, x, c)
            outs[fused] = layer.apply(params, x, c)
        np.testing.assert_allclose(np.asarray(outs["fused"]),
                                   np.asarray(outs["none"]),
                                   rtol=1e-5, atol=1e-6)


# ------------------------------------------------- decision-table pins


def test_auto_dispatch_resolves(rng):
    x, gs, bs = _case(rng, (1, 8, 8, 4), 1)
    a = spade_modulation(jnp.asarray(x), [jnp.asarray(gs[0])],
                         [jnp.asarray(bs[0])], implementation="auto")
    b = spade_modulation(jnp.asarray(x), [jnp.asarray(gs[0])],
                         [jnp.asarray(bs[0])],
                         implementation=AUTO_IMPLEMENTATION)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert AUTO_IMPLEMENTATION in ("jnp", "fused")
