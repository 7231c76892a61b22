"""Generator/discriminator model tests (ref architectures in
imaginaire/generators/spade.py, imaginaire/discriminators/{multires_patch,
fpse,spade,residual,mlp_multiclass}.py)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from imaginaire_tpu.config import AttrDict
from imaginaire_tpu.models.discriminators import mlp_multiclass as mlp_d
from imaginaire_tpu.models.discriminators import multires_patch as mrp_d
from imaginaire_tpu.models.discriminators import residual as res_d
from imaginaire_tpu.models.discriminators import spade as spade_d
from imaginaire_tpu.models.generators import spade as spade_g


def make_data_cfg(crop=64):
    return AttrDict({
        "type": "imaginaire_tpu.data.paired_images",
        "input_types": [
            {"images": {"num_channels": 3}},
            {"seg_maps": {"num_channels": 5, "is_mask": True}},
        ],
        "input_image": ["images"],
        "input_labels": ["seg_maps"],
        "train": {"augmentations": {"resize_smallest_side": crop,
                                    "random_crop_h_w": f"{crop},{crop}"}},
    })


@pytest.fixture
def batch(rng):
    h = w = 64
    return {
        "images": jnp.asarray(rng.rand(2, h, w, 3).astype(np.float32)) * 2 - 1,
        "label": jnp.asarray(
            (rng.rand(2, h, w, 5) > 0.8).astype(np.float32)),
    }


class TestSPADEGenerator:
    def test_forward_shapes_with_style(self, key, batch):
        gen_cfg = AttrDict({"num_filters": 8, "style_dims": 16,
                            "activation_norm_params": {"num_filters": 8}})
        # crop 64 is not a supported generator size; use the 256 ladder on
        # 64px input: base=16 → start 4x4. The generator supports any
        # H,W divisible by base; out_image_small_side_size selects the head.
        data_cfg = make_data_cfg(crop=256)
        g = spade_g.Generator(gen_cfg, data_cfg)
        imgs = jax.image.resize(batch["images"], (2, 256, 256, 3), "bilinear")
        lbls = jax.image.resize(batch["label"], (2, 256, 256, 5), "nearest")
        data = {"images": imgs, "label": lbls}
        variables = g.init({"params": key, "noise": key}, data, training=False)
        out = g.apply(variables, data, training=False,
                      rngs={"noise": key})
        assert out["fake_images"].shape == (2, 256, 256, 3)
        assert out["mu"].shape == (2, 16)
        assert out["logvar"].shape == (2, 16)
        assert np.all(np.abs(np.asarray(out["fake_images"])) <= 1.0)

    def test_random_style(self, key, batch):
        gen_cfg = AttrDict({"num_filters": 4, "style_dims": 8,
                            "activation_norm_params": {"num_filters": 4}})
        data_cfg = make_data_cfg(crop=256)
        g = spade_g.Generator(gen_cfg, data_cfg)
        lbls = jax.image.resize(batch["label"], (2, 256, 256, 5), "nearest")
        data = {"images": jnp.zeros((2, 256, 256, 3)), "label": lbls}
        variables = g.init({"params": key, "noise": key}, data, training=False)
        out = g.apply(variables, data, random_style=True, rngs={"noise": key})
        assert out["fake_images"].shape == (2, 256, 256, 3)
        assert out["mu"] is None

    def test_no_style_encoder(self, key, batch):
        gen_cfg = AttrDict({"num_filters": 4,
                            "activation_norm_params": {"num_filters": 4}})
        data_cfg = make_data_cfg(crop=256)
        g = spade_g.Generator(gen_cfg, data_cfg)
        lbls = jax.image.resize(batch["label"], (2, 256, 256, 5), "nearest")
        data = {"label": lbls, "images": jnp.zeros((2, 256, 256, 3))}
        variables = g.init({"params": key, "noise": key}, data, training=False)
        out = g.apply(variables, data)
        assert out["fake_images"].shape == (2, 256, 256, 3)
        assert "mu" not in out


class TestPatchDiscriminators:
    def test_nlayer_patch_shapes(self, key, batch):
        d = mrp_d.NLayerPatchDiscriminator(num_filters=8, num_layers=3,
                                           max_num_filters=32)
        x = jnp.concatenate([batch["label"], batch["images"]], axis=-1)
        (logits, feats), _ = d.init_with_output(key, x)
        # 3 stride-2 convs (layer0 + 2 of 3 inner) → 64/8=8 spatial.
        assert logits.shape == (2, 8, 8, 1)
        assert len(feats) == 4

    def test_multires_returns_per_scale(self, key, batch):
        d = mrp_d.MultiResPatchDiscriminator(num_discriminators=3,
                                             num_filters=8, num_layers=2,
                                             max_num_filters=32)
        (outs, feats, inputs), _ = d.init_with_output(key, batch["images"])
        assert len(outs) == len(feats) == len(inputs) == 3
        assert inputs[1].shape == (2, 32, 32, 3)

    def test_weight_shared_param_count(self, key, batch):
        shared = mrp_d.MultiResPatchDiscriminator(
            num_discriminators=3, num_filters=8, num_layers=2,
            max_num_filters=32, weight_shared=True)
        sep = mrp_d.MultiResPatchDiscriminator(
            num_discriminators=3, num_filters=8, num_layers=2,
            max_num_filters=32)
        n_shared = sum(a.size for a in jax.tree_util.tree_leaves(
            shared.init(key, batch["images"])["params"]))
        n_sep = sum(a.size for a in jax.tree_util.tree_leaves(
            sep.init(key, batch["images"])["params"]))
        assert n_sep == 3 * n_shared

    def test_config_wrapper(self, key, batch):
        dis_cfg = AttrDict({"num_filters": 8, "num_layers": 2,
                            "max_num_filters": 32, "num_discriminators": 2})
        d = mrp_d.Discriminator(dis_cfg, make_data_cfg())
        out, _ = d.init_with_output(
            key, {"images": batch["images"], "label": batch["label"]},
            {"fake_images": batch["images"]})
        assert len(out["fake_outputs"]) == 2
        assert len(out["real_features"]) == 2


class TestSPADEDiscriminator:
    def test_fpse_plus_patch(self, key, batch):
        dis_cfg = AttrDict({"num_filters": 8, "num_layers": 2,
                            "max_num_filters": 32, "num_discriminators": 2})
        d = spade_d.Discriminator(dis_cfg, make_data_cfg())
        out, _ = d.init_with_output(
            key, {"images": batch["images"], "label": batch["label"]},
            {"fake_images": batch["images"]})
        # 3 FPSE scales + 2 patch Ds.
        assert len(out["fake_outputs"]) == 5
        assert len(out["fake_features"]) == 2
        # FPSE pred2 at 1/4 res of 64 → 16.
        assert out["fake_outputs"][0].shape == (2, 16, 16, 1)


def test_res_discriminator(key, batch):
    d = res_d.ResDiscriminator(num_filters=8, max_num_filters=32, num_layers=2)
    x = jax.image.resize(batch["images"], (2, 16, 16, 3), "bilinear")
    (outputs, features, images), _ = d.init_with_output(key, x)
    assert outputs.shape == (2, 1)


def test_mlp_multiclass(key, rng):
    dis_cfg = AttrDict({"input_dims": 64, "num_labels": 7, "num_layers": 2,
                        "num_filters": 16})
    d = mlp_d.Discriminator(dis_cfg)
    data = {"data": jnp.asarray(rng.randn(3, 64).astype(np.float32))}
    out, _ = d.init_with_output({"params": key, "dropout": key}, data,
                                training=True)
    assert out["results"].shape == (3, 7)


class TestSpadeRemat:
    """gen.remat knob (TPU memory/speed lever)."""

    def test_param_tree_identical_and_bad_value_loud(self, rng, tmp_path):
        import jax
        import jax.numpy as jnp

        from imaginaire_tpu.config import Config
        from imaginaire_tpu.registry import resolve

        cfg_path = os.path.join(os.path.dirname(__file__), "..", "configs",
                                "unit_test", "spade.yaml")
        data = {"images": jnp.asarray(
                    rng.rand(1, 256, 256, 3).astype(np.float32)),
                "label": jnp.asarray(
                    (rng.rand(1, 256, 256, 14) > 0.9).astype(np.float32))}
        trees = []
        for remat in ("none", "blocks"):
            cfg = Config(cfg_path)
            cfg.logdir = str(tmp_path)
            cfg.gen.remat = remat
            gen = resolve(cfg.gen.type, "Generator")(cfg.gen, cfg.data)
            variables = gen.init({"params": jax.random.PRNGKey(0),
                                  "noise": jax.random.PRNGKey(1)}, data)
            trees.append(jax.tree_util.tree_structure(variables["params"]))
        # the knob must be checkpoint-compatible: same parameter tree
        assert trees[0] == trees[1]

        cfg = Config(cfg_path)
        cfg.gen.remat = "block"  # typo'd value must fail loudly
        gen = resolve(cfg.gen.type, "Generator")(cfg.gen, cfg.data)
        with pytest.raises(ValueError, match="remat"):
            gen.init({"params": jax.random.PRNGKey(0),
                      "noise": jax.random.PRNGKey(1)}, data)
