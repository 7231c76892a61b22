"""fs-vid2vid: few-shot video dataset, weight-generator driven training
rollout, K>1 attention, reference warping."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from imaginaire_tpu.config import Config
from imaginaire_tpu.registry import resolve
from imaginaire_tpu.telemetry import xla_obs

CFG = os.path.join(os.path.dirname(__file__), "..", "configs", "unit_test",
                   "fs_vid2vid.yaml")


def fewshot_video_batch(rng, t=2, k=1, h=64, w=64, labels=12):
    return {
        "images": jnp.asarray(
            rng.rand(1, t, h, w, 3).astype(np.float32)) * 2 - 1,
        "label": jnp.asarray(
            (rng.rand(1, t, h, w, labels) > 0.9).astype(np.float32)),
        "ref_images": jnp.asarray(
            rng.rand(1, k, h, w, 3).astype(np.float32)) * 2 - 1,
        "ref_labels": jnp.asarray(
            (rng.rand(1, k, h, w, labels) > 0.9).astype(np.float32)),
    }


class TestFewShotVideoDataset:
    def test_window_and_refs_disjoint(self):
        cfg = Config(CFG)
        ds = resolve(cfg.data.type, "Dataset")(cfg)
        item = ds[0]
        assert item["images"].shape == (2, 64, 64, 3)
        assert item["ref_images"].shape == (1, 64, 64, 3)
        assert item["ref_labels"].shape == (1, 64, 64, 12)

    def test_inference_pinning(self):
        cfg = Config(CFG)
        ds = resolve(cfg.data.type, "Dataset")(cfg, is_inference=True)
        ds.set_inference_sequence_idx(0, k_shot_frame_index=1)
        item = ds[0]
        assert item["images"].shape == (1, 64, 64, 3)
        assert item["ref_images"].shape == (1, 64, 64, 3)


@pytest.mark.slow
class TestFsVid2VidTraining:
    def test_rollout_two_iterations(self, rng, tmp_path):
        cfg = Config(CFG)
        cfg.logdir = str(tmp_path)
        trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
        trainer.init_state(jax.random.PRNGKey(0), fewshot_video_batch(rng))
        trainer.start_of_epoch(0)
        for it in range(1, 3):
            batch = trainer.start_of_iteration(fewshot_video_batch(rng), it)
            trainer.dis_update(batch)
            mark = xla_obs.ledger().snapshot()
            g = trainer.gen_update(batch)
            trainer.end_of_iteration(batch, 0, it)
        # the second rollout, of the same shapes, compiled nothing
        assert xla_obs.snapshot_delta(mark)["compiles"] == 0
        for name, v in g.items():
            assert np.isfinite(float(jax.device_get(v))), name
        # ref-warp flow loss active from frame 0 (warp_ref=True)
        assert "Flow" in g
        assert {"GAN", "FeatureMatching", "Perceptual", "total"} <= set(g)

    def test_generator_ref_warp_outputs(self, rng, tmp_path):
        cfg = Config(CFG)
        cfg.logdir = str(tmp_path)
        trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
        data = fewshot_video_batch(rng)
        trainer.init_state(jax.random.PRNGKey(0), data)
        data_t = trainer._get_data_t(data, 0, None, None)
        out, _ = trainer._apply_G(trainer.state["vars_G"], data_t,
                                  jax.random.PRNGKey(0), False)
        assert out["fake_images"].shape == (1, 64, 64, 3)
        # reference warp present from the first frame
        assert out["warped_images"][0].shape == (1, 64, 64, 3)
        assert out["fake_flow_maps"][0].shape == (1, 64, 64, 2)
        # no prev warp on the first frame
        assert out["warped_images"][1] is None

    def test_attention_with_k2(self, rng, tmp_path):
        """K=2 reference images activate the attention module and produce
        a ref_idx."""
        cfg = Config(CFG)
        cfg.logdir = str(tmp_path)
        cfg.data.initial_few_shot_K = 2
        trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
        data = fewshot_video_batch(rng, k=2)
        trainer.init_state(jax.random.PRNGKey(0), data)
        data_t = trainer._get_data_t(data, 0, None, None)
        out, _ = trainer._apply_G(trainer.state["vars_G"], data_t,
                                  jax.random.PRNGKey(0), False)
        assert out["ref_idx"] is not None
        assert out["attention_visualization"] is not None
        assert out["fake_images"].shape == (1, 64, 64, 3)
