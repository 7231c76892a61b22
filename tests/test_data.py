"""Data subsystem tests: folder backend, augmentor, one-hot w/ dont-care,
label concat, loader sharding, packed backend round-trip."""

import os

import numpy as np
import pytest

from imaginaire_tpu.config import Config
from imaginaire_tpu.data.backends import PackedBackend, build_packed_dataset
from imaginaire_tpu.data.loader import DataLoader, get_train_and_val_dataloader
from imaginaire_tpu.data.paired_images import Dataset as PairedImages

CFG_PATH = os.path.join(os.path.dirname(__file__), "..", "configs",
                        "unit_test", "spade.yaml")
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "spade", "raw")


@pytest.fixture
def cfg():
    c = Config(CFG_PATH)
    # point roots at the fixture dir regardless of cwd
    c.data.train.roots = [FIXTURES]
    c.data.val.roots = [FIXTURES]
    return c


def _host_encoding(ds):
    """``ds`` made to one-hot encode on the host, as every dataset did
    before the index map: what the fed batch is compared against. No
    config selects this."""
    ds.index_map_label = None
    return ds


class TestPairedImages:
    def test_item_shapes_and_ranges(self, cfg):
        ds = PairedImages(cfg)
        assert len(ds) == 3
        item = ds[0]
        # the one mask label leads the list: it ships as its index map
        # (12 seg classes + dont-care = index 12), the edge map beside it
        assert item["label"].shape == (256, 256)
        assert item["label"].dtype == np.int32
        assert item["label"].min() >= 0 and item["label"].max() <= 12
        assert item["label_float"].shape == (256, 256, 1)
        assert item["images"].shape == (256, 256, 3)
        assert item["images"].min() >= -1.0 and item["images"].max() <= 1.0
        assert item["key"].startswith("seq0001/")

    def test_host_encoded_item_is_one_hot(self, cfg):
        ds = _host_encoding(PairedImages(cfg))
        item = ds[0]
        # 12 seg + 1 dont-care + 1 edge = 14 label channels.
        assert item["label"].shape == (256, 256, 14)
        assert "label_float" not in item
        # one-hot: each pixel's seg channels sum to 1
        seg = item["label"][..., :13]
        np.testing.assert_allclose(seg.sum(-1), 1.0)

    def test_dont_care_encoding(self, cfg):
        ds = PairedImages(cfg)
        # fixture writes 255 into the top-left corner -> dont-care channel 12
        cfg.data.val.augmentations = {"resize_h_w": "256, 256"}
        ds_val = PairedImages(cfg, is_inference=True)
        assert ds_val[0]["label"][0, 0] == 12
        item = _host_encoding(ds_val)[0]
        assert item["label"].shape[-1] == 14
        assert item["label"][0, 0, 12] == 1.0

    def test_label_lengths(self, cfg):
        ds = PairedImages(cfg)
        assert ds.get_label_lengths() == {"seg_maps": 13, "edge_maps": 1}

    def test_augmentation_determinism_of_shapes(self, cfg):
        ds = PairedImages(cfg)
        for i in range(3):
            item = ds[i]
            assert item["images"].shape == (256, 256, 3)


class TestLoader:
    def test_batching(self, cfg):
        train, val = get_train_and_val_dataloader(cfg)
        batch = next(iter(train))
        assert batch["images"].shape == (1, 256, 256, 3)
        assert batch["label"].shape == (1, 256, 256)
        assert batch["label_float"].shape == (1, 256, 256, 1)
        assert len(train) == 3

    def test_epoch_reshuffle(self, cfg):
        ds = PairedImages(cfg)
        loader = DataLoader(ds, batch_size=1, shuffle=True, seed=1)
        loader.set_epoch(0)
        keys0 = [b["key"][0] for b in loader]
        loader.set_epoch(1)
        keys1 = [b["key"][0] for b in loader]
        assert sorted(keys0) == sorted(keys1)


class TestPackedBackend:
    def test_roundtrip(self, cfg, tmp_path):
        out = build_packed_dataset(FIXTURES, str(tmp_path / "packed"),
                                   ["images", "seg_maps", "edge_maps"])
        backend = PackedBackend(os.path.join(out, "images"))
        img = backend.getitem("seq0001/00000")
        assert img.shape == (300, 320, 3)
        # packed dataset is directly usable by the Dataset class
        cfg.data.train.roots = [out]
        cfg.data.train.is_packed = True
        ds = PairedImages(cfg)
        item = ds[0]
        assert item["images"].shape == (256, 256, 3)


class TestNativeIO:
    def test_native_reader_matches_python(self, tmp_path):
        """The C++ thread-pool reader returns byte-identical payloads to
        Python IO, single and batched."""
        import numpy as np

        from imaginaire_tpu.native import NativeBlobReader, load_library

        if load_library() is None:
            import pytest

            pytest.skip("no native toolchain")
        blob = tmp_path / "data.bin"
        rng = np.random.RandomState(0)
        payloads = [rng.bytes(rng.randint(10, 5000)) for _ in range(20)]
        extents = []
        with open(blob, "wb") as f:
            for p in payloads:
                extents.append((f.tell(), len(p)))
                f.write(p)
        r = NativeBlobReader(str(blob))
        for (off, length), want in zip(extents, payloads):
            assert r.read(off, length) == want
        got = r.read_batch(extents)
        assert got == payloads
        r.close()

    def test_packed_backend_native_path(self, tmp_path):
        """PackedBackend serves images through the native reader."""
        import numpy as np
        from PIL import Image

        from imaginaire_tpu.data.backends import (
            PackedBackend,
            build_packed_dataset,
        )

        raw = tmp_path / "raw"
        for i in range(3):
            d = raw / "images" / "seqA"
            d.mkdir(parents=True, exist_ok=True)
            Image.fromarray(
                np.random.RandomState(i).randint(0, 255, (8, 8, 3),
                                                 np.uint8)).save(
                d / f"{i:05d}.png")
        out = build_packed_dataset(str(raw), str(tmp_path / "packed"),
                                   ["images"])
        be = PackedBackend(str(tmp_path / "packed" / "images"))
        img = be.getitem("seqA/00000")
        assert img.shape == (8, 8, 3)
        imgs = be.getitems(["seqA/00000", "seqA/00002"])
        assert len(imgs) == 2 and imgs[1].shape == (8, 8, 3)

    def test_loader_num_workers_same_batches(self):
        """Prefetching workers yield the same batches as the serial path."""
        import numpy as np

        from imaginaire_tpu.data.loader import DataLoader

        class DS:
            def __len__(self):
                return 10

            def __getitem__(self, i):
                return {"x": np.full((2, 2), i, np.float32), "key": str(i)}

        serial = list(DataLoader(DS(), 2, shuffle=True, seed=3))
        threaded = list(DataLoader(DS(), 2, shuffle=True, seed=3,
                                   num_workers=4))
        assert len(serial) == len(threaded) == 5
        for a, b in zip(serial, threaded):
            np.testing.assert_array_equal(a["x"], b["x"])
            assert a["key"] == b["key"]

    def test_loader_early_abandon_no_deadlock(self):
        """next(iter(loader)) then dropping the iterator must not hang
        (train.py fetches one sample batch before the epoch loop)."""
        import numpy as np

        from imaginaire_tpu.data.loader import DataLoader

        class DS:
            def __len__(self):
                return 50

            def __getitem__(self, i):
                return {"x": np.zeros((4,), np.float32)}

        loader = DataLoader(DS(), 2, num_workers=4, prefetch_batches=2)
        first = next(iter(loader))  # iterator abandoned immediately
        assert first["x"].shape == (2, 4)
        # breaking mid-epoch must also unwind cleanly
        for i, _ in enumerate(loader):
            if i == 1:
                break

    def test_loader_worker_exception_propagates(self):
        """A failing sample must raise in the consumer, not hang."""
        import numpy as np
        import pytest

        from imaginaire_tpu.data.loader import DataLoader

        class DS:
            def __len__(self):
                return 10

            def __getitem__(self, i):
                if i == 3:
                    raise ValueError("corrupt sample")
                return {"x": np.zeros((4,), np.float32)}

        loader = DataLoader(DS(), 2, shuffle=False, num_workers=2)
        with pytest.raises(ValueError, match="corrupt sample"):
            list(loader)


def _video_cfg(tmp_path, n_frames=40, seq_len=3, max_time_step=3,
               dataset_type="imaginaire_tpu.data.paired_videos",
               extra_train=None, extra_data=None):
    """A folder-backed video config over a synthetic sequence of
    ``n_frames`` (never actually decoded — tests stub load_item)."""
    seq_dir = tmp_path / "raw" / "images" / "seq0"
    seq_dir.mkdir(parents=True, exist_ok=True)
    for i in range(n_frames):
        (seq_dir / f"{i:05d}.jpg").touch()
    c = Config(CFG_PATH)
    train = {"roots": [str(tmp_path / "raw")], "batch_size": 1,
             "initial_sequence_length": seq_len,
             "augmentations": {"resize_h_w": "16, 16",
                               "max_time_step": max_time_step}}
    train.update(extra_train or {})
    c.data = type(c.data)(dict(extra_data or {}, **{
        "name": "stride_fixture",
        "type": dataset_type,
        "num_frames_G": seq_len,
        "num_workers": 0,
        "input_types": [
            {"images": {"ext": "jpg", "num_channels": 3,
                        "interpolator": "BILINEAR", "normalize": True}}],
        "input_image": ["images"],
        "input_labels": [],
        "train": train,
        "val": {"roots": [str(tmp_path / "raw")], "batch_size": 1,
                "augmentations": {"resize_h_w": "16, 16"}},
    }))
    return c


def _stub_io(ds):
    """Bypass decode: __getitem__ returns the chosen frame stems."""
    ds.load_item = lambda root_idx, seq, frames: {"images": list(frames)}
    ds.process_item = lambda raw, thread_common_attr=True: raw
    ds.concat_labels = lambda out, squeeze_time=False: out
    return ds


class TestTemporalStride:
    """max_time_step strided window sampling
    (ref: datasets/paired_videos.py:167-191)."""

    def test_window_indices_honor_stride(self, tmp_path):
        import random

        from imaginaire_tpu.registry import resolve

        cfg = _video_cfg(tmp_path, n_frames=40, seq_len=3, max_time_step=3)
        ds = _stub_io(resolve(cfg.data.type, "Dataset")(cfg))
        random.seed(7)
        strides = set()
        for draw in range(60):
            frames = ds[draw]["images"]
            assert len(frames) == 3
            idx = [int(s) for s in frames]
            assert 0 <= idx[0] and idx[-1] < 40
            diffs = {b - a for a, b in zip(idx, idx[1:])}
            assert len(diffs) == 1, "stride must be constant in a window"
            step = diffs.pop()
            assert 1 <= step <= 3
            strides.add(step)
        assert strides == {1, 2, 3}, \
            f"all strides in [1, max_time_step] should occur, got {strides}"

    def test_stride_falls_back_when_window_exceeds_longest(self, tmp_path):
        import random

        from imaginaire_tpu.registry import resolve

        # seq_len=5: stride s needs 1+4s frames; only s<=2 fits 12
        cfg = _video_cfg(tmp_path, n_frames=12, seq_len=5, max_time_step=10)
        ds = _stub_io(resolve(cfg.data.type, "Dataset")(cfg))
        random.seed(3)
        for draw in range(40):
            frames = ds[draw]["images"]
            assert len(frames) == 5
            idx = [int(s) for s in frames]
            step = idx[1] - idx[0]
            assert step in (1, 2)
            assert idx[-1] < 12

    def test_few_shot_stride_and_disjoint_refs(self, tmp_path):
        import random

        from imaginaire_tpu.registry import resolve

        cfg = _video_cfg(
            tmp_path, n_frames=40, seq_len=3, max_time_step=3,
            dataset_type="imaginaire_tpu.data.paired_few_shot_videos",
            extra_data={"initial_few_shot_K": 2})
        ds = _stub_io(resolve(cfg.data.type, "Dataset")(cfg))
        random.seed(11)
        strides = set()
        for draw in range(60):
            item = ds[draw]
            frames = [int(s) for s in item["images"]]
            refs = [int(s) for s in item["ref_images"]]
            assert len(frames) == 3 and len(refs) == 2
            step = frames[1] - frames[0]
            assert frames[2] - frames[1] == step and 1 <= step <= 3
            strides.add(step)
            # refs disjoint from the RAW window [start, end), not just
            # the strided picks (ref: paired_few_shot_videos.py:182-189)
            lo, hi = frames[0], frames[0] + (len(frames) - 1) * step + 1
            assert all(r < lo or r >= hi for r in refs)
        assert strides == {1, 2, 3}

    def test_knob_never_parses_without_effect(self, cfg):
        """A non-video dataset handed max_time_step>1 must refuse it."""
        cfg.data.train.augmentations.max_time_step = 2
        with pytest.raises(ValueError, match="max_time_step"):
            PairedImages(cfg)


class TestOneHotOnDevice:
    """The dataset ships its one leading mask label as an int32 index
    map plus float extras, and the feed's device-side one-hot must
    reproduce the host encoding exactly (data/base.py::_encode_index_map,
    data/device_prefetch.py::expand_index_labels behind
    BaseTrainer._on_device)."""

    def _pair(self, cfg):
        # no crop: the fixture's out-of-range corner (255) stays in view
        cfg.data.val.augmentations = {"resize_h_w": "256, 256"}
        host = _host_encoding(PairedImages(cfg, is_inference=True))
        dev = PairedImages(cfg, is_inference=True)
        return host[0], dev[0]

    def test_matches_host_onehot(self, cfg):
        """No knob: the unit-test config as shipped emits the index map."""
        a, b = self._pair(cfg)
        assert b["label"].dtype == np.int32
        assert b["label"].shape == (256, 256)
        assert b["label_float"].shape == (256, 256, 1)
        # 13 = 12 seg + dont-care
        onehot = np.eye(13, dtype=np.float32)[b["label"]]
        recombined = np.concatenate([onehot, b["label_float"]], axis=-1)
        np.testing.assert_array_equal(recombined, a["label"])

    @pytest.mark.parametrize("use_dont_care", [True, False])
    def test_fed_label_is_the_host_stack_bit_for_bit(self, cfg,
                                                     use_dont_care):
        """Through the trainer's feed: float32, the host encoding's
        shape and values exactly, out-of-range indices included (the
        fixture writes 255 into the top-left corner)."""
        from imaginaire_tpu.data.base import BaseDataset
        from imaginaire_tpu.registry import resolve

        for spec in cfg.data.input_types:
            if "seg_maps" in spec:
                spec["seg_maps"]["use_dont_care"] = use_dont_care
        a, b = self._pair(cfg)
        assert b["label"][0, 0] == 12  # out of range -> dont-care index
        want = np.concatenate(
            [BaseDataset._encode_onehot(b["label"][..., None], 12,
                                        use_dont_care), b["label_float"]],
            axis=-1)
        assert want.shape[-1] == (14 if use_dont_care else 13)
        np.testing.assert_array_equal(want, a["label"])
        trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
        out = trainer.start_of_iteration(
            {"images": b["images"][None], "label": b["label"][None],
             "label_float": b["label_float"][None]}, 0)
        assert "label_float" not in out
        assert out["label"].dtype == np.float32
        assert out["label"].shape == (1,) + want.shape
        np.testing.assert_array_equal(np.asarray(out["label"]), want[None])
        if not use_dont_care:
            assert not np.asarray(out["label"])[0, 0, 0].any()

    def test_video_types_encode_on_the_host(self):
        """A video dataset folds past labels into channels on the host:
        it keeps the one-hot stack, and no longer raises."""
        from imaginaire_tpu.data.paired_videos import Dataset as PairedVideos

        cfg = Config(os.path.join(os.path.dirname(__file__), "..", "configs",
                                  "unit_test", "vid2vid_street.yaml"))
        cfg.data.train.roots = [FIXTURES]
        ds = PairedVideos(cfg)
        assert [t for t in ds.input_labels if ds.is_mask[t]]
        assert ds.index_map_label is None
        item = ds[0]
        assert item["label"].dtype == np.float32
        assert item["label"].ndim == 4  # (T, H, W, C)
        assert item["label"].shape[-1] == sum(
            ds.get_label_lengths().values())
        assert "label_float" not in item

    @pytest.mark.parametrize("layout", ["two_masks", "mask_not_first"])
    def test_other_label_layouts_encode_on_the_host(self, cfg, layout):
        """Two mask types, or the mask not leading the list: the channel
        order is not the index map's, so the dataset encodes as before."""
        if layout == "two_masks":
            for spec in cfg.data.input_types:
                if "edge_maps" in spec:
                    spec["edge_maps"]["is_mask"] = True
        else:
            cfg.data.input_labels = ["edge_maps", "seg_maps"]
        ds = PairedImages(cfg)
        assert ds.index_map_label is None
        item = ds[0]
        assert item["label"].dtype == np.float32
        assert item["label"].shape == (256, 256, 14)
        assert "label_float" not in item
        # the seg one-hot (12 + dont-care) sits where the list puts it
        seg = item["label"][..., 1:] if layout == "mask_not_first" \
            else item["label"][..., :13]
        np.testing.assert_allclose(seg.sum(-1), 1.0)
