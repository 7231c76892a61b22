"""Per-region (face/hand) additional discriminators
(ref: imaginaire/discriminators/fs_vid2vid.py:105-135,
model_utils/fs_vid2vid.py:631-779) and the pose-driven vid2vid data
pipeline (ref: configs/unit_test/vid2vid_pose.yaml)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from imaginaire_tpu.config import Config
from imaginaire_tpu.losses.gan import gan_loss
from imaginaire_tpu.model_utils.fs_vid2vid import (
    crop_face_from_output,
    crop_hand_from_output,
    get_face_bbox_for_output,
    get_hand_bbox_for_output,
)
from imaginaire_tpu.registry import resolve
from imaginaire_tpu.telemetry import xla_obs

HERE = os.path.dirname(__file__)
CFG = os.path.join(HERE, "..", "configs", "unit_test", "vid2vid_pose.yaml")

OPENPOSE_CFG = {"input_labels": ["poses-openpose"],
                "input_types": [{"poses-openpose": {"num_channels": 27}}]}


def _pose_label(b=2, h=64, w=64, face_at=(10, 40), hands_at=((40, 10),
                                                             (40, 54))):
    """27-channel one-hot openpose label: face stroke in ch 26, hands in
    ch 24/25 (visualization.pose.connect_pose_keypoints layout)."""
    label = np.zeros((b, h, w, 27), np.float32)
    fy, fx = face_at
    label[:, fy:fy + 8, fx - 4:fx + 4, 26] = 1.0
    for i, (hy, hx) in enumerate(hands_at):
        label[:, hy:hy + 4, hx:hx + 4, 24 + i] = 1.0
    return jnp.asarray(label)


class TestFaceCrop:
    def test_bbox_centers_on_face(self):
        boxes = np.asarray(get_face_bbox_for_output(
            OPENPOSE_CFG, _pose_label()))
        assert boxes.shape == (2, 4)
        ys, ye, xs, xe = boxes[0]
        # box is square, at least 32px, and contains the face stroke center
        assert ye - ys == xe - xs >= 32
        assert ys <= 14 + 4 and xs <= 40 <= xe

    def test_crop_shape_and_content(self):
        h = w = 64
        label = _pose_label(h=h, w=w)
        image = jnp.zeros((2, h, w, 3)).at[:, 8:24, 32:48, :].set(1.0)
        crops = crop_face_from_output(OPENPOSE_CFG, image, label)
        assert crops.shape == (2, 16, 16, 3)  # 64//32*8
        # the face neighborhood is the bright region
        assert float(jnp.mean(crops)) > 0.15

    def test_no_face_fallback(self):
        label = jnp.zeros((1, 64, 64, 27))
        crops = crop_face_from_output(OPENPOSE_CFG, _pose_label(b=1) * 0,
                                      label)
        assert crops.shape == (1, 16, 16, 3)
        assert np.all(np.isfinite(np.asarray(crops)))

    def test_list_input(self):
        label = _pose_label(b=1)
        image = jnp.ones((1, 64, 64, 3))
        crops = crop_face_from_output(OPENPOSE_CFG, [image, image], label)
        assert isinstance(crops, list) and len(crops) == 2


class TestHandCrop:
    def test_valid_mask(self):
        label = np.array(_pose_label(b=2), copy=True)
        label[1, ..., 24] = 0  # sample 1 has no left hand
        ycs, xcs, valid = get_hand_bbox_for_output(OPENPOSE_CFG,
                                                   jnp.asarray(label))
        assert valid.shape == (2, 2)
        assert bool(valid[0, 0]) and not bool(valid[1, 0])
        assert bool(valid[0, 1]) and bool(valid[1, 1])

    def test_crops_stack_both_hands(self):
        image = jnp.ones((2, 64, 64, 3))
        crops, valid = crop_hand_from_output(OPENPOSE_CFG, image,
                                             _pose_label())
        assert crops.shape == (4, 8, 8, 3)  # 2 hands x batch 2, 64//64*8
        assert valid.shape == (4,)


class TestSampleWeightedGANLoss:
    def test_zero_weight_samples_excluded(self):
        logits = jnp.asarray(np.array([[1.0], [100.0]], np.float32))
        w = jnp.asarray([1.0, 0.0])
        masked = float(gan_loss(logits, True, "hinge", False,
                                sample_weight=w))
        only_first = float(gan_loss(logits[:1], True, "hinge", False))
        np.testing.assert_allclose(masked, only_first, rtol=1e-6)

    def test_all_weights_one_matches_mean(self):
        logits = jnp.asarray(np.random.RandomState(0)
                             .randn(4, 3, 3, 1).astype(np.float32))
        w = jnp.ones((4,))
        np.testing.assert_allclose(
            float(gan_loss(logits, True, "hinge", True, sample_weight=w)),
            float(gan_loss(logits, True, "hinge", True)), rtol=1e-5)


class TestPoseDataset:
    def test_pipeline_shapes(self):
        cfg = Config(CFG)
        ds = resolve(cfg.data.type, "Dataset")(cfg)
        item = ds[0]
        assert item["images"].shape == (3, 64, 64, 3)
        assert item["label"].shape == (3, 64, 64, 27)
        # face channel rendered
        assert item["label"][..., 26].max() > 0
        # hand channels rendered
        assert item["label"][..., 24].max() > 0
        assert item["label"][..., 25].max() > 0


@pytest.mark.slow
class TestPoseTraining:
    def test_two_iterations_with_region_ds(self, tmp_path):
        cfg = Config(CFG)
        cfg.logdir = str(tmp_path)
        ds = resolve(cfg.data.type, "Dataset")(cfg)
        item = ds[0]
        batch = {"images": jnp.asarray(item["images"])[None],
                 "label": jnp.asarray(item["label"])[None]}
        trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
        trainer.init_state(jax.random.PRNGKey(0), batch)
        for it in range(1, 3):
            b = trainer.start_of_iteration(batch, it)
            trainer.dis_update(b)
            mark = xla_obs.ledger().snapshot()
            g = trainer.gen_update(b)
        # the second rollout, of the same shapes, compiled nothing
        assert xla_obs.snapshot_delta(mark)["compiles"] == 0
        for name, v in g.items():
            assert np.isfinite(float(jax.device_get(v))), name
        assert "GAN_face" in g and "GAN_hand" in g
        assert "FeatureMatching_face" in g
