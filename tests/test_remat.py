"""The shared per-block remat policy surface (ISSUE 10,
imaginaire_tpu/optim/remat.py): one registry, one resolver, one error
message; wrapped blocks keep the checkpoint-compatible param tree and
match the unwrapped forward bit-for-bit on CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from imaginaire_tpu.layers import Res2dBlock
from imaginaire_tpu.optim.remat import (
    POLICIES,
    call_block,
    is_positional,
    remat_block,
    remat_block_cls,
    remat_hyper_block_cls,
    resolve_policy,
)

ENABLED = ("blocks", "dots_saveable", "save_nothing")


class TestRegistry:
    def test_registry_names(self):
        assert set(POLICIES) == {"none", "blocks", "dots_saveable",
                                 "save_nothing"}
        assert not POLICIES["none"].enabled
        for name in ENABLED:
            assert POLICIES[name].enabled

    def test_resolver_accepts_none_and_instances(self):
        assert resolve_policy(None).name == "none"
        pol = POLICIES["blocks"]
        assert resolve_policy(pol) is pol

    def test_one_error_message_names_the_knob(self):
        with pytest.raises(ValueError, match="gen.remat"):
            resolve_policy("block", where="gen.remat")
        # every valid name is listed in the message
        with pytest.raises(ValueError, match="dots_saveable"):
            resolve_policy("nope")
        # jax's own name of the keep-nothing policy is no second key
        with pytest.raises(ValueError, match="save_nothing"):
            resolve_policy("nothing_saveable")

    def test_wrapped_class_cached_per_policy(self):
        a = remat_block_cls(Res2dBlock, "blocks")
        b = remat_block_cls(Res2dBlock, "blocks")
        c = remat_block_cls(Res2dBlock, "dots_saveable")
        assert a is b and a is not c
        assert remat_block_cls(Res2dBlock, "none") is Res2dBlock
        # hyper wrappers get their own cache slot
        assert remat_hyper_block_cls(Res2dBlock, "blocks") is not a

    def test_positional_marker_and_dispatch(self):
        plain = Res2dBlock(8, name="blk")
        assert not is_positional(plain)
        wrapped = remat_block_cls(Res2dBlock, "blocks")(8, name="blk")
        assert is_positional(wrapped)


@pytest.mark.parametrize("policy", ENABLED)
class TestPolicyParity:
    """Every enabled policy must be a pure memory/speed trade: identical
    param tree (checkpoint compatibility) and identical forward values
    against the unwrapped block."""

    def _init_and_apply(self, make, x, *cond):
        mod = make()
        variables = mod.init(jax.random.PRNGKey(0), x, *cond,
                             training=False)
        out = mod.apply(variables, x, *cond, training=False)
        return variables, out

    def test_res_block(self, rng, policy):
        x = jnp.asarray(rng.randn(1, 16, 16, 8).astype(np.float32))
        base_vars, base_out = self._init_and_apply(
            lambda: _Wrap("none"), x)
        pol_vars, pol_out = self._init_and_apply(lambda: _Wrap(policy), x)
        assert jax.tree_util.tree_structure(base_vars) \
            == jax.tree_util.tree_structure(pol_vars)
        np.testing.assert_allclose(np.asarray(base_out),
                                   np.asarray(pol_out), atol=1e-6)

    def test_grad_parity(self, rng, policy):
        """remat changes WHERE activations come from on the backward
        pass, never their values: grads match the unwrapped block."""
        x = jnp.asarray(rng.randn(1, 8, 8, 4).astype(np.float32))

        def loss(variables, mod):
            return jnp.sum(mod.apply(variables, x, training=False) ** 2)

        base = _Wrap("none", features=4)
        variables = base.init(jax.random.PRNGKey(0), x, training=False)
        g_base = jax.grad(loss)(variables, base)
        g_pol = jax.grad(loss)(variables, _Wrap(policy, features=4))
        for a, b in zip(jax.tree_util.tree_leaves(g_base),
                        jax.tree_util.tree_leaves(g_pol)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)


class _Wrap:
    """Tiny harness module: one rematted Res2dBlock, fixed name so the
    param tree is policy-invariant."""

    def __new__(cls, policy, features=8):
        import flax.linen as nn

        class M(nn.Module):
            @nn.compact
            def __call__(self, x, training=False):
                return remat_block(Res2dBlock, policy, where="gen.remat",
                                   out_channels=features,
                                   name="res")(x, training=training)

        return M()


class TestFamilies:
    """The knob reaches every family's blocks through the same surface:
    spot-check one generator-side and one discriminator-side module per
    convention (compact factory vs setup-stored instances)."""

    @pytest.mark.parametrize("policy", ["dots_saveable"])
    def test_funit_content_encoder(self, rng, policy):
        from imaginaire_tpu.models.generators.funit import (
            FUNITContentEncoder,
        )

        x = jnp.asarray(rng.randn(1, 32, 32, 3).astype(np.float32))
        trees, outs = [], []
        for pol in ("none", policy):
            enc = FUNITContentEncoder(num_downsamples=1, num_res_blocks=1,
                                      num_filters=4, remat=pol)
            variables = enc.init(jax.random.PRNGKey(0), x, training=False)
            trees.append(jax.tree_util.tree_structure(variables))
            outs.append(enc.apply(variables, x, training=False))
        assert trees[0] == trees[1]
        np.testing.assert_allclose(np.asarray(outs[0]),
                                   np.asarray(outs[1]), atol=1e-6)

    @pytest.mark.parametrize("policy", ["save_nothing"])
    def test_patch_discriminator(self, rng, policy):
        from imaginaire_tpu.models.discriminators.multires_patch import (
            NLayerPatchDiscriminator,
        )

        x = jnp.asarray(rng.randn(1, 32, 32, 3).astype(np.float32))
        trees, outs = [], []
        for pol in ("none", policy):
            d = NLayerPatchDiscriminator(num_filters=4, num_layers=2,
                                         remat=pol)
            variables = d.init(jax.random.PRNGKey(0), x, training=False)
            trees.append(jax.tree_util.tree_structure(variables))
            logits, _ = d.apply(variables, x, training=False)
            outs.append(logits)
        assert trees[0] == trees[1]
        np.testing.assert_allclose(np.asarray(outs[0]),
                                   np.asarray(outs[1]), atol=1e-6)

    def test_bad_value_same_message_everywhere(self, rng):
        """Family-local string checks are gone: a typo'd policy fails
        through resolve_policy with the shared message, at trace time."""
        from imaginaire_tpu.models.discriminators.multires_patch import (
            NLayerPatchDiscriminator,
        )
        from imaginaire_tpu.models.generators.funit import (
            FUNITContentEncoder,
        )

        x = jnp.asarray(rng.randn(1, 16, 16, 3).astype(np.float32))
        with pytest.raises(ValueError, match="gen.remat"):
            FUNITContentEncoder(num_filters=4, remat="block").init(
                jax.random.PRNGKey(0), x, training=False)
        with pytest.raises(ValueError, match="dis.remat"):
            NLayerPatchDiscriminator(num_filters=4, remat="offload").init(
                jax.random.PRNGKey(0), x, training=False)

    def test_vid2vid_call_block_dispatch(self, rng):
        """setup-based families store wrapped INSTANCES and dispatch via
        call_block: positional wrapper takes training first, plain
        blocks keep the kwarg path."""
        wrapped_cls = remat_block_cls(Res2dBlock, "blocks")
        import flax.linen as nn

        class M(nn.Module):
            def setup(self):
                self.blk = wrapped_cls(4, name="res")
                self.plain = Res2dBlock(4, name="res2")

            def __call__(self, x, training=False):
                x = call_block(self.blk, x, training=training)
                return call_block(self.plain, x, training=training)

        x = jnp.asarray(rng.randn(1, 8, 8, 4).astype(np.float32))
        m = M()
        variables = m.init(jax.random.PRNGKey(0), x, training=False)
        out = m.apply(variables, x, training=False)
        assert out.shape == x.shape
        assert np.isfinite(np.asarray(out)).all()


# --------------------------- what a recomputed block keeps (ISSUE 33)


def _residuals(module, variables, *inputs):
    """The arrays the module's forward pass leaves for its backward pass,
    inputs and parameters included."""
    _, vjp = jax.vjp(lambda v, *xs: module.apply(v, *xs, training=True),
                     variables, *inputs)
    return jax.tree_util.tree_leaves(vjp)


def _spade_block(policy):
    import flax.linen as nn

    class M(nn.Module):
        @nn.compact
        def __call__(self, x, seg, training=False):
            return remat_block(
                Res2dBlock, policy, where="gen.remat", out_channels=6,
                weight_norm_type="spectral",
                activation_norm_type="spatially_adaptive",
                activation_norm_params={"num_filters": 8,
                                        "activation_norm_type": "instance"},
                order="NACNAC", name="res")(x, seg, training=training)

    return M(), (jnp.ones((1, 8, 8, 4)), jnp.ones((1, 8, 8, 3)))


def _lm_block(kind, policy, **gen):
    import flax.linen as nn
    from hybrid_lm_util import tiny_cfg

    from imaginaire_tpu.models.generators import hybrid_lm

    g = hybrid_lm.model_settings(tiny_cfg(**gen).gen)

    class M(nn.Module):
        @nn.compact
        def __call__(self, h, training=False):
            return remat_block(hybrid_lm.Block, policy, where="gen.remat",
                               cfg=g, kind=kind,
                               name="layer_0")(h, training=training)[0]

    return M(), (jnp.ones((1, 256, g.hidden_size)) / 8,)


@pytest.mark.parametrize("build", [
    _spade_block, lambda policy: _lm_block("M", policy)],
    ids=["spade", "mamba2"])
def test_a_block_that_names_nothing_keeps_nothing_under_blocks(build):
    """``blocks`` keeps what a block names ``KERNEL_RESIDUAL``; a block
    with no such value (every GAN family's, a Mamba-2 block) leaves its
    backward pass what it leaves under ``save_nothing``, and fewer arrays
    than with no recompute at all."""
    kept = {}
    for policy in ("none", "blocks", "save_nothing"):
        module, inputs = build(policy)
        variables = module.init(jax.random.PRNGKey(0), *inputs)
        kept[policy] = sorted(
            (r.shape, str(r.dtype))
            for r in _residuals(module, variables, *inputs))
    assert kept["blocks"] == kept["save_nothing"]
    assert len(kept["blocks"]) < len(kept["none"])


@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_an_attention_block_through_the_flax_lift(monkeypatch, policy):
    """A ``hybrid_lm.Block`` of kind ``*`` built through ``remat_block``
    (``nn.remat``, not bare ``jax.checkpoint``), the fused arm forced in
    Pallas's interpreter: the gradient runs the forward kernel once where
    the policy keeps the kernel's residuals (``blocks``; ``none``, which
    recomputes nothing) and twice where it does not, which is what the
    registry's ``keeps_kernel_residuals`` says of each."""
    from hybrid_lm_util import pallas_calls

    from imaginaire_tpu.models.generators import hybrid_lm
    from imaginaire_tpu.ops import attention

    tiles = attention.Tiles(fwd=(128, 128), bwd=(128, 128))
    monkeypatch.setattr(
        hybrid_lm, "attention",
        lambda q, k, v: attention.fused_causal_attention(
            q, k, v, tiles, True))
    module, (h,) = _lm_block("*", policy, head_dim=128, num_attention_heads=2,
                             num_key_value_heads=1, compute_dtype="bfloat16")
    h = h.astype(jnp.bfloat16)
    variables = module.init(jax.random.PRNGKey(0), h)

    def loss(variables, h):
        out = module.apply(variables, h, training=True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    calls = pallas_calls(
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(variables, h).jaxpr)
    forward = calls.count("causal_gqa_fwd")
    assert sorted(calls) == sorted(
        ["causal_gqa_fwd"] * forward + ["causal_gqa_bwd"])
    assert forward == {"none": 1, "blocks": 1, "dots_saveable": 2,
                       "save_nothing": 2}[policy]
    assert POLICIES[policy].keeps_kernel_residuals == (forward == 1)
