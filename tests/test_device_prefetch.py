"""DevicePrefetcher coverage (data/device_prefetch.py): the async
input pipeline must yield committed ``NamedSharding`` batches over the
8-device conftest mesh, bound its read-ahead to the configured depth,
tear down cleanly on early abandon, propagate producer exceptions, pass
string keys through untouched — and preserve the wc-vid2vid first-window
crop-barrier ordering when stacked on a worker-threaded loader
(mirrors tests/test_person_crop_pipeline.py::TestFirstWindowBarrier at
prefetch depth > 1)."""

import os
import threading
import time

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from imaginaire_tpu.data.device_prefetch import (
    DevicePrefetcher,
    PrefetchedBatch,
    prefetch_settings,
)
from imaginaire_tpu.parallel.mesh import create_mesh, peek_mesh, set_mesh


@pytest.fixture
def data_mesh():
    old = peek_mesh()
    mesh = create_mesh(("data",))
    set_mesh(mesh)
    try:
        yield mesh
    finally:
        set_mesh(old)


def _batch(i, bs=8):
    rng = np.random.RandomState(i)
    return {
        "images": rng.rand(bs, 8, 8, 3).astype(np.float32),
        "label": rng.randint(0, 5, (bs, 8, 8)).astype(np.int32),
        "key": [f"item_{i}_{j}" for j in range(bs)],
        "nested": {"aux": rng.rand(bs, 2).astype(np.float32)},
    }


class _ListLoader:
    """Minimal loader: re-iterable, records how many batches were
    pulled (the producer's read-ahead)."""

    def __init__(self, batches, delay=0.0):
        self.batches = batches
        self.delay = delay
        self.pulled = 0

    def __len__(self):
        return len(self.batches)

    def __iter__(self):
        for b in self.batches:
            if self.delay:
                time.sleep(self.delay)
            self.pulled += 1
            yield dict(b) if isinstance(b, dict) else b


class TestShardingAndPassthrough:
    def test_committed_named_sharding_over_data_axis(self, data_mesh):
        pf = DevicePrefetcher(_ListLoader([_batch(0)]), depth=2)
        (out,) = list(pf)
        assert isinstance(out, PrefetchedBatch)
        for key in ("images", "label"):
            arr = out[key]
            assert isinstance(arr, jax.Array) and arr.committed
            assert isinstance(arr.sharding, NamedSharding)
            assert arr.sharding.spec == P(
                "data", *([None] * (arr.ndim - 1)))
            assert len(arr.sharding.mesh.devices.flat) == 8
        # nested numeric leaves get the same treatment
        assert out["nested"]["aux"].sharding.spec == P("data", None)

    def test_indivisible_batch_falls_back_uncommitted(self, data_mesh):
        """Nothing shards (3 % 8 != 0 on every leaf): the transfer keeps
        to_device's uncommitted placement instead of dragging the step
        program onto the full mesh for a replicated batch."""
        pf = DevicePrefetcher(_ListLoader([_batch(0, bs=3)]), depth=1)
        (out,) = list(pf)
        assert isinstance(out, PrefetchedBatch)
        assert isinstance(out["images"], jax.Array)
        assert not out["images"].committed

    def test_mixed_divisibility_replicates_odd_leaves(self, data_mesh):
        """Sharded main leaves carry replicated odd-sized siblings."""
        batch = dict(_batch(0), aux=np.zeros((3, 2), np.float32))
        pf = DevicePrefetcher(_ListLoader([batch]), depth=1)
        (out,) = list(pf)
        assert out["images"].sharding.spec == P("data", None, None, None)
        assert out["aux"].committed and out["aux"].sharding.spec == P()

    def test_string_keys_and_host_objects_pass_through(self, data_mesh):
        sentinel = object()
        batch = dict(_batch(1), _point_cloud=sentinel)
        pf = DevicePrefetcher(_ListLoader([batch]), depth=1)
        (out,) = list(pf)
        assert out["key"] == batch["key"]  # same host list, untouched
        assert out["_point_cloud"] is sentinel  # '_' host payloads kept
        assert not isinstance(out["key"], jax.Array)

    def test_host_preprocess_runs_with_pass_index(self, data_mesh):
        seen = []

        def prep(batch, index):
            seen.append(index)
            batch = dict(batch)
            batch["images"] = batch["images"] + 1.0
            return batch

        src = [_batch(i) for i in range(3)]
        pf = DevicePrefetcher(_ListLoader(src), host_preprocess=prep,
                              depth=2)
        outs = list(pf)
        assert seen == [0, 1, 2]
        np.testing.assert_allclose(np.asarray(outs[0]["images"]),
                                   src[0]["images"] + 1.0, rtol=1e-6)


class TestPipelineBehavior:
    def test_read_ahead_bounded_by_depth(self, data_mesh):
        loader = _ListLoader([_batch(i) for i in range(8)])
        pf = DevicePrefetcher(loader, depth=2)
        it = iter(pf)
        first = next(it)
        assert isinstance(first, PrefetchedBatch)
        # the producer may hold: 1 yielded + depth queued + 1 in flight
        deadline = time.time() + 2.0
        while loader.pulled < 2 and time.time() < deadline:
            time.sleep(0.01)  # overlap proof: read-ahead while we hold one
        assert 2 <= loader.pulled <= 1 + pf.depth + 1
        time.sleep(0.2)  # producer must stay blocked at the bound
        assert loader.pulled <= 1 + pf.depth + 1
        it.close()

    def test_early_abandon_unwinds_and_stays_reiterable(self, data_mesh):
        loader = _ListLoader([_batch(i) for i in range(16)])
        pf = DevicePrefetcher(loader, depth=2)
        for out in pf:  # abandon after the first batch (GeneratorExit)
            assert isinstance(out, PrefetchedBatch)
            break
        n_threads = threading.active_count()
        deadline = time.time() + 5.0
        while time.time() < deadline and any(
                t.name == "device-prefetch" and t.is_alive()
                for t in threading.enumerate()):
            time.sleep(0.01)
        assert not any(t.name == "device-prefetch" and t.is_alive()
                       for t in threading.enumerate()), \
            f"producer leaked ({n_threads} threads alive)"
        # a fresh pass over the same wrapper works (re-iterable contract)
        assert len(list(pf)) == 16

    def test_worker_exception_propagates(self, data_mesh):
        class Boom(RuntimeError):
            pass

        def bad_source():
            yield _batch(0)
            raise Boom("decode failed")

        class _GenLoader:
            def __iter__(self):
                return bad_source()

        pf = DevicePrefetcher(_GenLoader(), depth=2)
        with pytest.raises(Boom, match="decode failed"):
            list(pf)

    def test_preprocess_exception_propagates(self, data_mesh):
        pf = DevicePrefetcher(
            _ListLoader([_batch(0)]),
            host_preprocess=lambda b, i: (_ for _ in ()).throw(
                ValueError("hook failed")),
            depth=1)
        with pytest.raises(ValueError, match="hook failed"):
            list(pf)

    def test_stats_drain_without_device_sync(self, data_mesh):
        pf = DevicePrefetcher(_ListLoader([_batch(i) for i in range(3)]),
                              depth=2)
        list(pf)
        stats = pf.drain_stats()
        for name in ("data/host_wait_ms", "data/transfer_ms",
                     "data/queue_depth"):
            assert name in stats and len(stats[name]) >= 1
            assert all(isinstance(v, float) for v in stats[name])
        assert pf.drain_stats() == {}  # drained


class TestConfigKnob:
    def test_settings_default_bool_and_mapping(self):
        assert prefetch_settings({}) == (True, 2)
        assert prefetch_settings({"data": {"device_prefetch": False}}) \
            == (False, 2)
        assert prefetch_settings(
            {"data": {"device_prefetch": {"enabled": False}}}) == (False, 2)
        on, depth = prefetch_settings(
            {"data": {"device_prefetch": {"depth": 5}}})
        assert on and depth == 5

    def test_trainer_sync_path_when_off(self, data_mesh):
        """data.device_prefetch off: data_prefetcher is the identity and
        start_of_iteration keeps the synchronous to_device transfer."""
        from imaginaire_tpu.config import as_attrdict
        from imaginaire_tpu.trainers.base import BaseTrainer

        class Stub(BaseTrainer):
            def __init__(self, cfg):  # bypass net/optimizer construction
                self.cfg = as_attrdict(cfg)
                self.meters = {}
                self.current_iteration = 0

        trainer = Stub({"data": {"device_prefetch": {"enabled": False}},
                        "trainer": {}})
        loader = _ListLoader([_batch(0)])
        assert trainer.data_prefetcher(loader) is loader
        out = trainer.start_of_iteration(dict(_batch(0)), 0)
        assert isinstance(out["images"], jax.Array)
        assert out["key"][0] == "item_0_0"

    def test_trainer_wraps_and_skips_reprep_when_on(self, data_mesh):
        from imaginaire_tpu.config import as_attrdict
        from imaginaire_tpu.trainers.base import BaseTrainer

        calls = []

        class Stub(BaseTrainer):
            def __init__(self, cfg):
                self.cfg = as_attrdict(cfg)
                self.meters = {}
                self.current_iteration = 0

            def _start_of_iteration(self, data, current_iteration):
                calls.append(current_iteration)
                return data

        trainer = Stub({"data": {"device_prefetch": {"depth": 3}},
                        "trainer": {}})
        feed = trainer.data_prefetcher(
            _ListLoader([_batch(i) for i in range(2)]),
            iteration_of=lambda index: 100 + index)
        assert isinstance(feed, DevicePrefetcher) and feed.depth == 3
        outs = [trainer.start_of_iteration(d, 100 + i)
                for i, d in enumerate(feed)]
        # the hook ran once per batch, in the producer, with the
        # consuming iteration number — start_of_iteration didn't re-run it
        assert calls == [100, 101]
        assert all(isinstance(o, PrefetchedBatch) for o in outs)
        assert outs[0]["images"].committed
        trainer.write_data_meters(feed.drain_stats())
        assert "data/transfer_ms" in trainer.meters


class TestFirstWindowBarrierThroughPrefetch:
    def test_prefetch_depth2_preserves_frame0_bbox_sharing(self,
                                                           tmp_path,
                                                           data_mesh):
        """Stacking the device prefetcher (depth 2) on a worker-threaded
        loader must keep the wc/fs-vid2vid first-window barrier
        ordering: every frame of a pinned sequence uses frame 0's crop
        bbox even while the prefetcher pulls windows ahead (mirror of
        test_person_crop_pipeline.py::TestFirstWindowBarrier)."""
        import os

        cv2 = pytest.importorskip("cv2")

        from imaginaire_tpu.config import Config
        from imaginaire_tpu.data.loader import DataLoader
        from imaginaire_tpu.registry import resolve
        import imaginaire_tpu.model_utils.fs_vid2vid as fsu

        root = str(tmp_path / "raw")
        t = 8
        for dtype in ("images", "pose_maps-densepose"):
            os.makedirs(os.path.join(root, dtype, "seq0"), exist_ok=True)
        rng = np.random.RandomState(0)
        for i in range(t):
            img = rng.randint(0, 255, (96, 128, 3), np.uint8)
            cv2.imwrite(os.path.join(root, "images", "seq0",
                                     f"{i:05d}.jpg"), img)
            dp = np.zeros((96, 128, 3), np.uint8)
            dp[20 + 3 * i:60 + 3 * i, 30 + 4 * i:70 + 4 * i] = 120
            cv2.imwrite(os.path.join(root, "pose_maps-densepose", "seq0",
                                     f"{i:05d}.png"), dp)

        cfg = Config()
        cfg.data = {
            "name": "prefetch_barrier_test",
            "type": "imaginaire_tpu.data.paired_videos",
            "num_frames_G": 3, "num_frames_D": 3, "num_workers": 0,
            "for_pose_dataset": {"pose_type": "both",
                                 "remove_face_labels": False,
                                 "basic_points_only": False,
                                 "random_drop_prob": 0.0},
            "input_types": [
                {"images": {"ext": "jpg", "num_channels": 3,
                            "interpolator": "BILINEAR",
                            "normalize": True}},
                {"pose_maps-densepose": {"ext": "png", "num_channels": 3,
                                         "interpolator": "NEAREST",
                                         "normalize": False}},
            ],
            "full_data_ops": "imaginaire_tpu.model_utils."
                             "fs_vid2vid::crop_person_from_data",
            "input_image": ["images"],
            "input_labels": ["pose_maps-densepose"],
            "keypoint_data_types": [],
            "output_h_w": "64, 32",
            "train": {"roots": [root], "batch_size": 1,
                      "initial_sequence_length": 3,
                      "augmentations": {"resize_h_w": "96, 128",
                                        "horizontal_flip": False}},
            "val": {"roots": [root], "batch_size": 1,
                    "augmentations": {"resize_h_w": "96, 128",
                                      "horizontal_flip": False}},
        }

        used_coords = []
        orig = fsu.crop_person_from_data
        record_lock = threading.Lock()

        def recording(cfg_, is_inference, data, rng=None):
            dp0 = np.asarray(data["pose_maps-densepose"][0])
            if int(np.nonzero(dp0.sum((1, 2)))[0][0]) == 20:
                time.sleep(0.5)  # frame 0 slow: later frames must wait
            out = orig(cfg_, is_inference, data, rng=rng)
            with record_lock:
                used_coords.append(
                    tuple(out["common_attr"]["crop_coords"]))
            return out

        fsu.crop_person_from_data = recording
        try:
            ds = resolve(cfg.data.type, "Dataset")(cfg, is_inference=True)
            ds.set_inference_sequence_idx(0)
            loader = DataLoader(ds, batch_size=4, shuffle=False,
                                drop_last=False, num_workers=4,
                                prefetch_batches=2,
                                shard_by_process=False)
            pf = DevicePrefetcher(loader, depth=2)
            n = 0
            for out in pf:
                assert isinstance(out, PrefetchedBatch)
                assert isinstance(out["images"], jax.Array)
                n += 1
        finally:
            fsu.crop_person_from_data = orig
        assert n == 2 and len(used_coords) == t
        assert len(set(used_coords)) == 1, \
            f"every frame must reuse frame 0's bbox, got {set(used_coords)}"


# ------------------------------------------------- index-map labels (feed)

_CFGS = os.path.join(os.path.dirname(__file__), "..", "configs", "unit_test")
_FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "spade", "raw")


def _image_cfg(name, batch_size=8):
    from imaginaire_tpu.config import Config

    cfg = Config(os.path.join(_CFGS, name))
    for split in ("train", "val"):
        cfg.data[split].roots = [_FIXTURES]
        cfg.data[split].batch_size = batch_size
    cfg.data.val.augmentations = {"resize_h_w": "64, 64"}
    return cfg


def _val_loaders(cfg, batch_size=8):
    """(compact, host): the config's validation loader as the dataset
    ships it (index-map label) and made to one-hot encode on the host,
    as every dataset did before; the 3 fixture frames cycle to a full
    batch."""
    from imaginaire_tpu.data.loader import DataLoader
    from imaginaire_tpu.data.paired_images import Dataset

    class Cycled(Dataset):
        def __len__(self):
            return 2 * batch_size

    compact, host = (Cycled(cfg, is_inference=True) for _ in range(2))
    assert compact.index_map_label == "seg_maps"
    host.index_map_label = None
    return tuple(DataLoader(ds, batch_size, shuffle=False)
                 for ds in (compact, host))


def _through(trainer, loader, path):
    """The loader's first batch as ``path`` hands it to a step."""
    if path == "prefetcher":
        feed = iter(trainer.data_prefetcher(loader))
        try:
            return trainer.start_of_iteration(next(feed), 0)
        finally:
            feed.close()
    batch = next(iter(loader))
    if path == "synchronous":
        return trainer.start_of_iteration(batch, 0)
    from imaginaire_tpu.utils.misc import numeric_only

    return trainer._eval_preprocess(numeric_only(batch))


def _leaves(batch):
    from imaginaire_tpu.utils.misc import numeric_only

    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(numeric_only(batch))[0]}


def _assert_same_leaves(got, want):
    """Names, shapes, dtypes, shardings and values: what a step
    program's signature and result are made of."""
    from imaginaire_tpu.telemetry.xla_obs import fingerprint

    got, want = _leaves(got), _leaves(want)
    assert fingerprint(got)[1] == fingerprint(want)[1]
    for name in want:
        assert got[name].sharding == want[name].sharding, name
        assert got[name].committed == want[name].committed, name
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(want[name]), err_msg=name)


class TestIndexMapLabelsThroughTheFeed:
    @pytest.mark.parametrize("path", ["prefetcher", "synchronous",
                                      "evaluation"])
    def test_fed_batch_is_the_host_encoded_batch(self, data_mesh, path):
        """SPADE: whichever path places the batch, the steps get the
        leaves a host-encoded batch gives, so their signatures (and
        cache entries) are unchanged."""
        from imaginaire_tpu.registry import resolve

        cfg = _image_cfg("spade.yaml")
        trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
        compact, host = _val_loaders(cfg)
        raw = next(iter(compact))
        assert raw["label"].dtype == np.int32 and "label_float" in raw
        got = _through(trainer, compact, path)
        want = _through(trainer, host, path)
        assert sorted(_leaves(got)) == ["['images']", "['is_flipped']",
                                        "['label']"]
        assert got["label"].dtype == np.float32
        assert got["label"].shape == (8, 64, 64, 14)
        if path != "synchronous":
            assert got["label"].sharding == NamedSharding(
                data_mesh, P("data", None, None, None))
        _assert_same_leaves(got, want)

    @pytest.mark.parametrize("path", ["prefetcher", "synchronous"])
    def test_pix2pixhd_pre_process_follows_the_expansion(self, data_mesh,
                                                         path):
        """pix2pixHD's hook reads the stack's channels: same ``label``
        (edges in the last channel) and ``instance_maps`` as from a
        host-encoded batch."""
        from imaginaire_tpu.registry import resolve

        cfg = _image_cfg("pix2pixHD.yaml")
        trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
        compact, host = _val_loaders(cfg)
        got = _through(trainer, compact, path)
        want = _through(trainer, host, path)
        assert got["instance_maps"].dtype == np.int32
        assert got["label"].shape == (8, 64, 64, 9)
        _assert_same_leaves(got, want)
        # what the parent's host hook built: pre_process on the host
        # stack, before the placement
        old = trainer.pre_process(
            trainer._resize_data(next(iter(host))))
        for name in ("label", "instance_maps"):
            np.testing.assert_array_equal(np.asarray(got[name]),
                                          np.asarray(old[name]))

    def test_expansion_is_enqueued_in_the_producer_and_metered(
            self, data_mesh):
        """One ``prefetch_expand`` span a batch on the producer thread,
        ``data/h2d_mb`` the compact bytes, and one ledgered program."""
        from imaginaire_tpu import telemetry
        from imaginaire_tpu.registry import resolve
        from imaginaire_tpu.telemetry import xla_obs

        cfg = _image_cfg("spade.yaml")
        trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
        compact, host = _val_loaders(cfg)
        spans = []
        tm = telemetry.get()
        real_span = tm.span

        def span(name, **kw):
            spans.append((name, threading.current_thread().name))
            return real_span(name, **kw)

        tm.span = span
        mark = xla_obs.ledger().snapshot()
        try:
            feed = trainer.data_prefetcher(compact)
            batches = list(feed)
        finally:
            del tm.span
        assert len(batches) == 2
        assert [s for s in spans if s[0] == "prefetch_expand"] == \
            [("prefetch_expand", "device-prefetch")] * 2
        order = [s[0] for s in spans if s[0].startswith("prefetch_")][:5]
        assert order == ["prefetch_host", "prefetch_preprocess",
                         "prefetch_transfer", "prefetch_expand",
                         "prefetch_put"]
        stats = feed.drain_stats()
        compact_mb = sum(v.nbytes for v in next(iter(compact)).values()
                         if isinstance(v, np.ndarray)) / 1e6
        host_mb = sum(v.nbytes for v in next(iter(host)).values()
                      if isinstance(v, np.ndarray)) / 1e6
        assert stats["data/h2d_mb"] == pytest.approx([compact_mb] * 2)
        assert host_mb > 3 * compact_mb
        delta = xla_obs.snapshot_delta(mark)
        assert delta["recompiles"] == 0

    def test_inference_path_gives_the_same_images(self, data_mesh,
                                                  tmp_path):
        """``BaseTrainer.test`` (``inference.py``'s loop) on the
        unit-test SPADE config: the compact dataset gives the images a
        host-encoded one gives, byte for byte."""
        from imaginaire_tpu.registry import resolve

        cfg = _image_cfg("spade.yaml")
        trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
        compact, host = _val_loaders(cfg)
        sample = trainer.start_of_iteration(next(iter(compact)), 0)
        trainer.init_state(jax.random.PRNGKey(0), sample)
        written = {}
        for name, loader in (("compact", compact), ("host", host)):
            out = tmp_path / name
            trainer.test(loader, str(out))
            written[name] = {
                os.path.relpath(os.path.join(d, f), out):
                    open(os.path.join(d, f), "rb").read()
                for d, _, files in os.walk(out) for f in files}
        assert len(written["compact"]) == 3  # the fixture's 3 frames
        assert written["compact"] == written["host"]
