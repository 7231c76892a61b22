"""Shared helpers of the per-project-config tests: every shipped
full-scale config under configs/projects builds its trainer and takes
one tiny training step (full channel widths, spatial size shrunk).
Imported by tests/test_config_variants.py and the
tests/test_config_steps_*.py sweep files."""

import os

import jax
import jax.numpy as jnp
import numpy as np

from imaginaire_tpu.config import Config, cfg_get
from imaginaire_tpu.registry import resolve

HERE = os.path.dirname(__file__)

# Every shipped full-scale project config must construct its trainer and
# survive one tiny training step (the reference's equivalent contract is
# scripts/test_training.sh over unit configs). Full-scale channel widths
# are kept; only the spatial size is shrunk.

PROJECTS = os.path.join(HERE, "..", "configs", "projects")
PROJECT_CFGS = sorted(
    os.path.relpath(os.path.join(dp, f), PROJECTS)
    for dp, _, fs in os.walk(PROJECTS) for f in fs if f.endswith(".yaml"))


def _label_channels(cfg):
    from imaginaire_tpu.utils.data import get_paired_input_label_channel_number

    return get_paired_input_label_channel_number(cfg.data)


def project_batch(cfg, rng):
    """Synthetic tiny batch matching the config's trainer family."""
    t = str(cfg.trainer.type)

    def img(*shape):
        return jnp.asarray(rng.rand(*shape, 3).astype(np.float32) * 2 - 1)

    if t.endswith("funit"):  # funit + coco_funit (before the unit check:
        # 'funit'.endswith('unit') is also True)
        return {"images_content": img(1, 64, 64),
                "images_style": img(1, 64, 64),
                "labels_content": jnp.asarray([0], jnp.int32),
                "labels_style": jnp.asarray([1], jnp.int32)}
    if t.endswith(("munit", "unit")):
        # 256px (the configs' real crop): munit's 6 stride-2 residual
        # blocks plus the kernel-4 VALID aggregation underflow below that
        return {"images_a": img(1, 256, 256), "images_b": img(1, 256, 256)}
    n = _label_channels(cfg)
    if t.endswith("fs_vid2vid"):
        label = (rng.rand(1, 64, 64, n) > 0.9).astype(np.float32)
        return {"images": img(1, 2, 64, 64),
                "label": jnp.asarray(label[:, None].repeat(2, 1)),
                "ref_images": img(1, 1, 64, 64),
                "ref_labels": jnp.asarray(label[:, None])}
    if t.endswith("vid2vid"):  # vid2vid + wc_vid2vid at the 128px minimum
        label = (rng.rand(1, 128, 128, n) > 0.9).astype(np.float32)
        return {"images": img(1, 3, 128, 128),
                "label": jnp.asarray(label[:, None].repeat(3, 1))}
    # image family: the full-scale patch-D stacks (5 stride-2 layers on a
    # half-res second scale) collapse to empty outputs below 128px — the
    # reference torch Conv2d would hard-error at the same size
    label = (rng.rand(1, 128, 128, n) > 0.9).astype(np.float32)
    return {"images": img(1, 128, 128), "label": jnp.asarray(label)}


def build_project_trainer(rel, tmp_path):
    cfg = Config(os.path.join(PROJECTS, rel))
    cfg.logdir = str(tmp_path)
    # no pretrained weights in CI: random-init the perceptual/flow
    # teachers (cost-equivalent; numerics are covered by the goldens)
    if cfg_get(cfg.trainer, "perceptual_loss", None) is not None:
        cfg.trainer.perceptual_loss.allow_random_init = True
        cfg.trainer.perceptual_loss.pop("weights_path", None)
    if cfg_get(cfg, "flow_network", None) is not None:
        cfg.flow_network.allow_random_init = True
        cfg.flow_network.pop("weights_path", None)
    t = str(cfg.trainer.type)
    if t.endswith("vid2vid") and not t.endswith("fs_vid2vid"):
        # the vid2vid/wc generators statically size their bottleneck from
        # the config crop (crop // 2^num_layers, num_layers=7) — shrink
        # the crop to the 128px architecture minimum so the tiny step
        # matches the generator's static shapes
        # the generator bottleneck sizes itself from the VAL augmentations
        # (models/generators/vid2vid.py:122-131), the batch matches train
        _shrink_crops(cfg)
    sim = cfg_get(cfg.gen, "single_image_model", None)
    if sim is not None:
        # no trained single-image checkpoint in CI: random weights, and
        # the frozen SPADE must emit frames at the shrunk 128px crop —
        # write a crop-patched copy of its config
        sim.allow_random_init = True
        sim.pop("checkpoint", None)
        single = Config(sim.config if os.path.exists(sim.config)
                        else os.path.join(HERE, "..", sim.config))
        _shrink_crops(single)
        patched = os.path.join(str(tmp_path), "single_image_model.yaml")
        with open(patched, "w") as f:
            f.write(single.yaml())
        sim.config = patched
    trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
    if sim is not None and getattr(trainer, "single_image_model",
                                   None) is not None:
        # SPADE's minimum output side is 256; the shrunk 128px step can't
        # run the real frozen model, so stub the jitted apply (shape- and
        # gating-faithful; the real 256px takeover apply is covered by
        # tests/test_wc_vid2vid.py::TestSingleImageModel)
        trainer.single_image_vars = {}
        trainer._jit_single = lambda v, d, k: {
            "fake_images": jnp.zeros(d["label"].shape[:3] + (3,),
                                     d["label"].dtype) + 0.1}
    return cfg, trainer


def _shrink_crops(cfg):
    for split in ("train", "val"):
        aug = cfg_get(cfg.data, split, None)
        aug = cfg_get(aug, "augmentations", None) if aug else None
        if aug is None:
            continue
        for key in ("random_crop_h_w", "resize_h_w", "center_crop_h_w"):
            if cfg_get(aug, key, None) is not None:
                aug[key] = "128, 128"
        aug.pop("resize_smallest_side", None)


def step_one(rel, rng, tmp_path):
    cfg, trainer = build_project_trainer(rel, tmp_path)
    batch = project_batch(cfg, rng)
    trainer.init_state(jax.random.PRNGKey(0), batch)
    batch = trainer.start_of_iteration(batch, 1)
    trainer.dis_update(batch)
    g = trainer.gen_update(batch)
    for name, v in g.items():
        assert np.isfinite(float(jax.device_get(v))), (rel, name)


# full-width step representatives: the configs whose training paths are
# NOT already stepped by the per-family unit-config tests — the
# ring-capable spade-attention variant and the three video configs with
# new modalities (pose person-crop, hed guidance). The image families'
# paths run 2-iteration unit configs in their own test files; their
# full-width steps live in the opt-in projects_full sweep.
FAMILY_REPS = [
    "spade/cocostuff/base128_bs4_attn.yaml",
    "vid2vid/dancing/bf16.yaml",
    "fs_vid2vid/YouTubeDancing/bf16.yaml",
    "wc_vid2vid/mannequin/hed_bf16.yaml",
]


def sweep_cases(*families):
    """The configs of ``families`` that the family-representative test
    does not already step: one ``test_config_steps_<group>.py`` file per
    group, so the sweep spreads over the workers of a ``--dist
    loadfile`` run."""
    return [c for c in PROJECT_CFGS
            if c.split(os.sep)[0] in families and c not in FAMILY_REPS]
