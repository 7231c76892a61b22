"""vid2vid: video dataset + curriculum, interleaved rollout training,
flow warp and temporal discriminator activation (mirrors the reference's
2-iter smoke strategy, SURVEY.md §4)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from imaginaire_tpu.config import Config
from imaginaire_tpu.registry import resolve
from imaginaire_tpu.telemetry import xla_obs

CFG = os.path.join(os.path.dirname(__file__), "..", "configs", "unit_test",
                   "vid2vid_street.yaml")


def video_batch(rng, t=3, h=64, w=64, labels=12):
    return {
        "images": jnp.asarray(
            rng.rand(1, t, h, w, 3).astype(np.float32)) * 2 - 1,
        "label": jnp.asarray(
            (rng.rand(1, t, h, w, labels) > 0.9).astype(np.float32)),
    }


class TestPairedVideoDataset:
    def test_sequence_sampling_and_curriculum(self):
        cfg = Config(CFG)
        ds = resolve(cfg.data.type, "Dataset")(cfg)
        assert ds.sequence_length == 3
        item = ds[0]
        assert item["images"].shape == (3, 64, 64, 3)
        assert item["label"].shape == (3, 64, 64, 12)
        ds.set_sequence_length(1)
        item = ds[0]
        assert item["images"].shape == (1, 64, 64, 3)
        # requesting beyond the max clamps
        ds.set_sequence_length(100)
        assert ds.sequence_length == 3


@pytest.mark.slow
class TestVid2VidTraining:
    def test_rollout_two_iterations(self, rng, tmp_path):
        """3-frame interleaved rollout: frame 0 runs the first-frame
        trunk, frame 2 has num_frames_G-1 prevs so the flow warp and the
        temporal discriminator activate."""
        cfg = Config(CFG)
        cfg.logdir = str(tmp_path)
        trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
        trainer.init_state(jax.random.PRNGKey(0), video_batch(rng))
        trainer.start_of_epoch(0)
        for it in range(1, 3):
            batch = trainer.start_of_iteration(video_batch(rng), it)
            trainer.dis_update(batch)  # no-op by contract
            mark = xla_obs.ledger().snapshot()
            g = trainer.gen_update(batch)
            trainer.end_of_iteration(batch, 0, it)
        # the second rollout, of the same shapes, compiled nothing
        assert xla_obs.snapshot_delta(mark)["compiles"] == 0
        for name, v in g.items():
            assert np.isfinite(float(jax.device_get(v))), name
        # flow loss active (warp happened) and temporal GAN active
        assert "Flow" in g
        assert "GAN_T0" in g
        assert {"GAN", "FeatureMatching", "Perceptual", "total"} <= set(g)

    def test_single_frame_no_temporal(self, rng, tmp_path):
        """A 1-frame sequence uses only the image path: no flow, no
        temporal loss."""
        cfg = Config(CFG)
        cfg.logdir = str(tmp_path)
        trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
        trainer.init_state(jax.random.PRNGKey(0), video_batch(rng, t=1))
        batch = trainer.start_of_iteration(video_batch(rng, t=1), 1)
        g = trainer.gen_update(batch)
        assert "Flow" not in g
        assert "GAN_T0" not in g
        for name, v in g.items():
            assert np.isfinite(float(jax.device_get(v))), name

    def test_generator_paths(self, rng, tmp_path):
        """First-frame vs continuation vs warp paths produce the right
        outputs."""
        cfg = Config(CFG)
        cfg.logdir = str(tmp_path)
        trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
        data = video_batch(rng)
        trainer.init_state(jax.random.PRNGKey(0), data)
        variables = trainer.state["vars_G"]
        label = data["label"][:, 0]
        # first frame: no flow outputs
        out, _ = trainer._apply_G(variables, {"label": label},
                                  jax.random.PRNGKey(0), False)
        assert out["fake_images"].shape == (1, 64, 64, 3)
        assert out["fake_flow_maps"] is None
        # continuation with full prev stack: flow + warp + mask present
        prevs = {
            "label": data["label"][:, 2],
            "prev_labels": data["label"][:, :2],
            "prev_images": data["images"][:, :2],
        }
        out2, _ = trainer._apply_G(variables, prevs, jax.random.PRNGKey(0),
                                   False)
        assert out2["fake_flow_maps"].shape == (1, 64, 64, 2)
        assert out2["fake_occlusion_masks"].shape == (1, 64, 64, 1)
        assert out2["warped_images"].shape == (1, 64, 64, 3)

    def test_flownet_teacher_wiring(self, rng, tmp_path):
        """cfg.flow_network activates the FlowNet2-teacher FlowLoss path:
        weights registered, teacher params in loss_params, and the
        teacher-driven loss terms compute on real data shapes."""
        import jax.numpy as jnp

        from imaginaire_tpu.losses.flow import FlowLoss

        cfg = Config(CFG)
        cfg.logdir = str(tmp_path)
        cfg.flow_network = {"allow_random_init": True}
        trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
        assert trainer.flow_net_wrapper is not None
        assert {"Flow_L1", "Flow_Warp", "Flow_Mask"} <= set(trainer.weights)
        # FlowLoss consumes the teacher's (flow, conf) on vid2vid outputs
        a = jnp.asarray(rng.rand(1, 64, 64, 3).astype(np.float32))
        b = jnp.asarray(rng.rand(1, 64, 64, 3).astype(np.float32))
        fl = FlowLoss(trainer.flow_net_wrapper)
        out = {"fake_images": a,
               "warped_images": b,
               "fake_flow_maps": jnp.zeros((1, 64, 64, 2)),
               "fake_occlusion_masks": jnp.full((1, 64, 64, 1), 0.5)}
        l1, warp, mask = fl({"image": a, "real_prev_image": b}, out)
        for v in (l1, warp, mask):
            assert np.isfinite(float(v))

    def test_curriculum_epoch_schedule(self, rng, tmp_path):
        cfg = Config(CFG)
        cfg.logdir = str(tmp_path)
        cfg.single_frame_epoch = 2
        cfg.num_epochs_temporal_step = 2

        class FakeLoader:
            class dataset:
                sequence_length_max = 3
                seq = None

                @classmethod
                def set_sequence_length(cls, n):
                    cls.seq = n

            def __len__(self):
                return 1

        trainer = resolve(cfg.trainer.type, "Trainer")(
            cfg, train_data_loader=FakeLoader())
        trainer._start_of_epoch(0)
        assert trainer.sequence_length == 1
        trainer._start_of_epoch(2)  # temporal init
        assert trainer.sequence_length == 3  # initial (3) clamped to max
        assert FakeLoader.dataset.seq == 3


class TestDensePosePreprocessing:
    def test_pre_process_densepose(self):
        from imaginaire_tpu.config import AttrDict
        from imaginaire_tpu.model_utils.fs_vid2vid import pre_process_densepose

        rng = np.random.RandomState(0)
        pose = rng.rand(1, 8, 8, 6).astype(np.float32)
        pose[..., 2] = rng.randint(0, 25, (1, 8, 8)) / 255.0  # part ids
        cfg = AttrDict({"random_drop_prob": 0.0})
        out = pre_process_densepose(cfg, pose)
        assert out.min() >= -1.0 and out.max() <= 1.0
        # part channel rescaled 24 -> 255 range before normalization
        np.testing.assert_allclose(
            out[..., 2], (pose[..., 2] * 255 / 24) * 2 - 1, rtol=1e-5)

    def test_random_drop_zeroes_parts(self):
        import random

        from imaginaire_tpu.config import AttrDict
        from imaginaire_tpu.model_utils.fs_vid2vid import pre_process_densepose

        pose = np.ones((1, 4, 4, 3), np.float32) * 0.5
        pose[..., 2] = 5 / 255.0  # every pixel is part 5
        cfg = AttrDict({"random_drop_prob": 1.0})
        out = pre_process_densepose(cfg, pose, rng=random.Random(0))
        # part 5 dropped everywhere -> densepose channels at -1 (zero
        # before renormalization)
        np.testing.assert_allclose(out[..., :3], -1.0)


@pytest.mark.slow
class TestVideoFID:
    def test_video_fid_end_to_end(self, tmp_path):
        """Video FID: pinned-sequence val loader -> reset/test_single
        rollout -> Inception activations -> Frechet distance
        (ref: trainers/vid2vid.py:697-757, evaluation/common.py:79-158)."""
        from imaginaire_tpu.data.loader import DataLoader

        cfg = Config(CFG)
        cfg.logdir = str(tmp_path)
        cfg.trainer.fid_random_init = True  # no ported weights in tests
        cfg.trainer.num_videos_to_test = 1
        ds_cls = resolve(cfg.data.type, "Dataset")
        val_ds = ds_cls(cfg, is_inference=True)
        assert val_ds.num_inference_sequences() == 1
        val_ds.set_inference_sequence_idx(0)
        assert len(val_ds) == 3  # 3 fixture frames
        item = val_ds[0]
        assert item["images"].shape == (1, 64, 64, 3)
        loader = DataLoader(val_ds, batch_size=1, shuffle=False,
                            drop_last=False)
        trainer = resolve(cfg.trainer.type, "Trainer")(
            cfg, val_data_loader=loader)
        rng = np.random.RandomState(0)
        batch = {
            "images": jnp.asarray(
                rng.rand(1, 3, 64, 64, 3).astype(np.float32)) * 2 - 1,
            "label": jnp.asarray(
                (rng.rand(1, 3, 64, 64, 12) > 0.9).astype(np.float32)),
        }
        trainer.init_state(jax.random.PRNGKey(0), batch)
        fid = trainer._compute_fid()
        assert fid is not None and np.isfinite(fid) and fid > 0
        # cached real stats file written
        import glob
        assert glob.glob(str(tmp_path) + "/real_stats_video_*.npz")

    def test_video_kid_prdc(self, tmp_path):
        """Video-family KID/PRDC: the same pinned-sequence rollout as
        video FID feeds kid/prdc_from_activations
        (ref: evaluation/kid.py:29, prdc.py)."""
        from imaginaire_tpu.data.loader import DataLoader

        cfg = Config(CFG)
        cfg.logdir = str(tmp_path)
        cfg.trainer.fid_random_init = True
        cfg.trainer.num_videos_to_test = 1
        ds_cls = resolve(cfg.data.type, "Dataset")
        val_ds = ds_cls(cfg, is_inference=True)
        loader = DataLoader(val_ds, batch_size=1, shuffle=False,
                            drop_last=False)
        trainer = resolve(cfg.trainer.type, "Trainer")(
            cfg, val_data_loader=loader)
        rng = np.random.RandomState(0)
        batch = {
            "images": jnp.asarray(
                rng.rand(1, 3, 64, 64, 3).astype(np.float32)) * 2 - 1,
            "label": jnp.asarray(
                (rng.rand(1, 3, 64, 64, 12) > 0.9).astype(np.float32)),
        }
        trainer.init_state(jax.random.PRNGKey(0), batch)
        out = trainer.compute_extra_metrics(["kid", "prdc"])
        assert np.isfinite(out["KID"])
        for k in ("precision", "recall", "density", "coverage"):
            v = out[f"PRDC_{k}"]
            assert np.isfinite(v) and 0.0 <= v, (k, v)
        # unsupported requests return {} (evaluate.py turns that into a
        # hard failure)
        assert trainer.compute_extra_metrics(["nope"]) == {}


@pytest.mark.slow
class TestVideoInference:
    def test_test_writes_all_frames_per_sequence(self, tmp_path):
        """trainer.test over an inference dataset pins each sequence and
        writes every frame (ref: trainers/vid2vid.py:330-417)."""
        from imaginaire_tpu.data.loader import DataLoader

        cfg = Config(CFG)
        cfg.logdir = str(tmp_path)
        ds = resolve(cfg.data.type, "Dataset")(cfg, is_inference=True)
        loader = DataLoader(ds, batch_size=1, shuffle=False,
                            drop_last=False)
        trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
        rng = np.random.RandomState(0)
        batch = {
            "images": jnp.asarray(
                rng.rand(1, 3, 64, 64, 3).astype(np.float32)) * 2 - 1,
            "label": jnp.asarray(
                (rng.rand(1, 3, 64, 64, 12) > 0.9).astype(np.float32)),
        }
        trainer.init_state(jax.random.PRNGKey(0), batch)
        out_dir = str(tmp_path / "out")
        trainer.test(loader, out_dir, None)
        import glob
        frames = sorted(glob.glob(out_dir + "/seq0000/*.jpg"))
        assert len(frames) == 3  # all fixture frames, not just frame 0


@pytest.mark.slow
class TestMultiDeviceVid2Vid:
    def test_sharded_interleaved_rollout(self, rng, tmp_path):
        """The interleaved per-frame D/G rollout with a temporal D,
        batch sharded over the 8-device 'data' mesh — the framework's
        most complex multi-device path (VERDICT r2 #4; ref:
        imaginaire/trainers/vid2vid.py:238-288)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from imaginaire_tpu.parallel.mesh import create_mesh, get_mesh, set_mesh

        old = get_mesh()
        try:
            mesh = create_mesh(("data",))
            set_mesh(mesh)
            cfg = Config(CFG)
            cfg.logdir = str(tmp_path)
            trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
            n = mesh.devices.size
            batch = {
                "images": jnp.asarray(
                    rng.rand(n, 3, 64, 64, 3).astype(np.float32)) * 2 - 1,
                "label": jnp.asarray(
                    (rng.rand(n, 3, 64, 64, 12) > 0.9).astype(np.float32)),
            }
            trainer.init_state(jax.random.PRNGKey(0), batch)
            trainer.state = jax.device_put(trainer.state,
                                           NamedSharding(mesh, P()))
            batch = jax.device_put(batch, NamedSharding(mesh, P("data")))
            with mesh:
                batch = trainer.start_of_iteration(batch, 1)
                g = trainer.gen_update(batch)  # per-frame D updates inside
            for name, v in g.items():
                assert np.isfinite(float(jax.device_get(v))), name
            assert any(k.startswith("GAN_T") for k in g), g.keys()
        finally:
            set_mesh(old)


def _rollout_batch(cfg):
    """One dataset item of the config as a batch of one clip."""
    item = resolve(cfg.data.type, "Dataset")(cfg)[0]
    return {k: jnp.asarray(v)[None] for k, v in item.items()
            if isinstance(v, np.ndarray) and v.ndim >= 3}


class TestRolloutDispatch:
    """The per-frame sequential loop is the video trainers' one dispatch
    form. The step programs are stand-ins that count (what they compute
    is the slow tier's business, ``test_rollout_two_iterations``): per
    frame a D step then a G step, each taking the state the last one
    returned, each handed to the health monitor before the next is
    issued, nothing left to hand over when ``gen_update`` returns."""

    @pytest.mark.parametrize("name", ["vid2vid_street.yaml",
                                      "vid2vid_pose.yaml",
                                      "fs_vid2vid.yaml"])
    def test_each_frame_d_then_g_in_order(self, name, tmp_path):
        cfg = Config(os.path.join(os.path.dirname(CFG), name))
        cfg.logdir = str(tmp_path)
        data = _rollout_batch(cfg)
        frames = data["images"].shape[1]
        trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
        events = []

        def dis_step(state, data_t):
            events.append(("D step", state["n"]))
            return {"n": state["n"] + 1}, {"GAN": jnp.float32(1)}, None

        def gen_step(state, data_t):
            events.append(("G step", state["n"]))
            # frame t sees the t fakes before it, newest last
            prev = data_t.get("prev_images")
            seen = [] if prev is None else [
                int(v) for v in np.asarray(prev[0, :, 0, 0, 0])]
            t = state["n"] // 2
            assert seen == list(range(t))[-(trainer.num_frames_G - 1):]
            fake = jnp.full_like(data_t["image"], t)
            return ({"n": state["n"] + 1}, {"total": jnp.float32(t)},
                    fake, None)

        def observe(owner, which, losses, health, batch, iteration):
            events.append((f"{which} observed", owner.state["n"]))

        trainer._jit_vid_dis, trainer._jit_vid_gen = dis_step, gen_step
        trainer.diag.observe = observe
        trainer.state = {"n": 0}
        trainer.current_iteration = 1
        losses = trainer.gen_update(trainer._start_of_iteration(data, 1))
        assert events == [
            event for t in range(frames) for event in (
                ("D step", 2 * t), ("D observed", 2 * t + 1),
                ("G step", 2 * t + 1), ("G observed", 2 * t + 2))]
        assert trainer.state == {"n": 2 * frames}
        # the rollout's loss is the mean over its frames
        assert float(losses["total"]) == (frames - 1) / 2
