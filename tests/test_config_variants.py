"""The reference ships 12 unit-test configs (scripts/test_training.sh);
these cover the variant configs not exercised by the main per-algorithm
tests: munit_patch (patch-wise D), coco_funit (usb generator),
fs_vid2vid_pose (pose labels + region Ds)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from imaginaire_tpu.config import Config
from imaginaire_tpu.registry import resolve
from project_configs import (
    FAMILY_REPS,
    PROJECT_CFGS,
    build_project_trainer,
    project_batch,
    step_one,
)

HERE = os.path.dirname(__file__)
CFGS = os.path.join(HERE, "..", "configs", "unit_test")


def _unpaired_batch(rng, h=64, w=64):
    def img():
        return jnp.asarray(rng.rand(1, h, w, 3).astype(np.float32) * 2 - 1)

    return {"images_a": img(), "images_b": img()}


@pytest.mark.slow
def test_munit_patch_two_iterations(rng, tmp_path):
    cfg = Config(os.path.join(CFGS, "munit_patch.yaml"))
    cfg.logdir = str(tmp_path)
    assert cfg.dis.patch_wise is True
    trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
    batch = _unpaired_batch(rng)
    trainer.init_state(jax.random.PRNGKey(0), batch)
    for it in range(1, 3):
        b = trainer.start_of_iteration(batch, it)
        trainer.dis_update(b)
        g = trainer.gen_update(b)
    for name, v in g.items():
        assert np.isfinite(float(jax.device_get(v))), name


@pytest.mark.slow
def test_coco_funit_two_iterations(rng, tmp_path):
    cfg = Config(os.path.join(CFGS, "coco_funit.yaml"))
    cfg.logdir = str(tmp_path)
    assert cfg.gen.type.endswith("coco_funit")
    trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
    batch = {
        "images_content": jnp.asarray(
            rng.rand(1, 64, 64, 3).astype(np.float32) * 2 - 1),
        "labels_content": jnp.asarray([0]),
        "images_style": jnp.asarray(
            rng.rand(1, 64, 64, 3).astype(np.float32) * 2 - 1),
        "labels_style": jnp.asarray([1]),
    }
    trainer.init_state(jax.random.PRNGKey(0), batch)
    for it in range(1, 3):
        b = trainer.start_of_iteration(batch, it)
        trainer.dis_update(b)
        g = trainer.gen_update(b)
    for name, v in g.items():
        assert np.isfinite(float(jax.device_get(v))), name


def test_fs_vid2vid_pose_dataset():
    cfg = Config(os.path.join(CFGS, "fs_vid2vid_pose.yaml"))
    ds = resolve(cfg.data.type, "Dataset")(cfg)
    item = ds[0]
    assert item["images"].shape == (2, 64, 64, 3)
    assert item["label"].shape == (2, 64, 64, 27)
    assert item["ref_images"].shape[1:] == (64, 64, 3)
    assert item["ref_labels"].shape[1:] == (64, 64, 27)


@pytest.mark.slow
def test_fs_vid2vid_pose_two_iterations(tmp_path):
    cfg = Config(os.path.join(CFGS, "fs_vid2vid_pose.yaml"))
    cfg.logdir = str(tmp_path)
    ds = resolve(cfg.data.type, "Dataset")(cfg)
    item = ds[0]
    batch = {k: jnp.asarray(v)[None] for k, v in item.items()
             if isinstance(v, np.ndarray) and v.ndim >= 3}
    trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
    trainer.init_state(jax.random.PRNGKey(0), batch)
    for it in range(1, 3):
        b = trainer.start_of_iteration(batch, it)
        trainer.dis_update(b)
        g = trainer.gen_update(b)
    for name, v in g.items():
        assert np.isfinite(float(jax.device_get(v))), name
    assert "GAN_face" in g and "GAN_hand" in g


@pytest.mark.slow
def test_fs_vid2vid_inference_finetune(tmp_path):
    """Few-shot inference-time finetune (ref: trainers/fs_vid2vid.py:
    264-292): masked G updates on rolled reference frames; only the
    weight-generator/up/conv_img params move."""
    cfg = Config(os.path.join(CFGS, "fs_vid2vid.yaml"))
    cfg.logdir = str(tmp_path)
    rng = np.random.RandomState(0)

    def img(k=1):
        return jnp.asarray(rng.rand(1, k, 32, 32, 3).astype(np.float32)
                           * 2 - 1)

    batch = {"images": img(2),
             "label": jnp.asarray((rng.rand(1, 2, 32, 32, 13) > 0.9)
                                  .astype(np.float32)),
             "ref_images": img(1),
             "ref_labels": jnp.asarray((rng.rand(1, 1, 32, 32, 13) > 0.9)
                                       .astype(np.float32))}
    trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
    trainer.init_state(jax.random.PRNGKey(0), batch)
    before = jax.tree_util.tree_map(
        lambda x: np.array(x), trainer.state["vars_G"]["params"])
    trainer.finetune(batch, {"finetune_iter": 1})
    assert trainer.has_finetuned
    after = trainer.state["vars_G"]["params"]
    flat_b = jax.tree_util.tree_leaves_with_path(before)
    flat_a = dict(jax.tree_util.tree_leaves_with_path(after))
    moved = frozen = 0
    for path, b in flat_b:
        a = flat_a[path]
        names = [str(p.key) for p in path if hasattr(p, "key")]
        masked_in = any(n.startswith(pref) for n in names
                        for pref in ("weight_generator", "conv_img", "up"))
        changed = not np.allclose(np.asarray(a), b)
        if masked_in:
            moved += changed
        else:
            assert not changed, f"frozen param moved: {names}"
            frozen += 1
    assert moved > 0 and frozen > 0


# ---------------------------------------------------------------------------
# Every shipped full-scale project config constructs its trainer; one
# representative per trainer family takes a tiny full-width step here,
# and the rest in tests/test_config_steps_*.py (helpers:
# tests/project_configs.py).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rel", PROJECT_CFGS)
def test_project_config_constructs(rel, rng, tmp_path):
    """Every shipped full-scale config parses and builds its trainer
    (models, optimizers, losses) and a family batch synthesizes."""
    cfg, trainer = build_project_trainer(rel, tmp_path)
    batch = project_batch(cfg, rng)
    assert trainer.net_G is not None
    assert set(batch)


@pytest.mark.slow
@pytest.mark.parametrize("rel", FAMILY_REPS)
def test_project_family_rep_steps(rel, rng, tmp_path):
    """One tiny full-width training step per trainer family (spatial
    size shrunk, channel budget kept)."""
    step_one(rel, rng, tmp_path)
