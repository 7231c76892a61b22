"""Config-driven multi-type dataset base (ref: imaginaire/datasets/base.py).

Per data type the config declares ext / num_channels / normalize /
interpolator / use_dont_care / is_mask / pre+post aug ops
(ref: base.py:92-150). Items come out as channel-last float32 numpy with:
  - images normalized to [-1, 1] when ``normalize`` (ref: base.py:203-237),
  - 1-channel label maps one-hot expanded to num_channels (+1 dont-care
    channel kept when use_dont_care, ref: base.py:272-298),
  - all ``input_labels`` types concatenated into ``data['label']``
    (ref: paired_videos.py:276-283); an image dataset with one leading
    mask label ships it as its int32 index map instead and the feed
    builds the same stack on the device (``index_map_label``).
"""

from __future__ import annotations

import importlib
import os
import threading

import numpy as np

from imaginaire_tpu.config import as_attrdict, cfg_get
from imaginaire_tpu.data.augment import Augmentor
from imaginaire_tpu.data.backends import (
    FolderBackend,
    LMDBBackend,
    PackedBackend,
    create_folder_metadata,
)


class BaseDataset:
    def __init__(self, cfg, is_inference=False, is_test=False):
        cfg = as_attrdict(cfg)
        self.cfg = cfg
        self.is_inference = is_inference
        self.is_test = is_test
        self._common_attr = None
        self._common_attr_lock = threading.Lock()
        self.cfgdata = cfg.test_data if is_test else cfg.data
        data_info = (self.cfgdata.test if is_test
                     else (self.cfgdata.val if is_inference else self.cfgdata.train))
        self.data_info = data_info
        self.name = cfg_get(self.cfgdata, "name", "dataset")
        self.roots = list(data_info.roots)
        self.batch_size = cfg_get(data_info, "batch_size", 1)

        backend = "folder"
        if cfg_get(data_info, "is_lmdb", False):
            backend = "lmdb"
        elif cfg_get(data_info, "is_packed", False):
            backend = "packed"
        self.backend_kind = backend

        # Per-type properties (ref: base.py:92-150).
        self.data_types = []
        self.image_data_types = []
        self.extensions = {}
        self.normalize = {}
        self.interpolators = {}
        self.num_channels = {}
        self.use_dont_care = {}
        self.is_mask = {}
        self.pre_aug_ops = {}
        self.post_aug_ops = {}
        for data_type in self.cfgdata.input_types:
            (name, info), = data_type.items()
            self.data_types.append(name)
            self.image_data_types.append(name)
            self.extensions[name] = cfg_get(info, "ext", None)
            self.normalize[name] = cfg_get(info, "normalize", False)
            self.interpolators[name] = cfg_get(info, "interpolator", None)
            self.num_channels[name] = cfg_get(info, "num_channels", None)
            self.use_dont_care[name] = cfg_get(info, "use_dont_care", False)
            self.is_mask[name] = cfg_get(info, "is_mask", False)
            self.pre_aug_ops[name] = _parse_ops(cfg_get(info, "pre_aug_ops", "None"))
            self.post_aug_ops[name] = _parse_ops(cfg_get(info, "post_aug_ops", "None"))
        self.input_labels = list(cfg_get(self.cfgdata, "input_labels", None) or [])
        # The mask label type that crosses the host as its (H,W) int32
        # index map (0.26 MB at 256x256 against 48.5 MB of float32
        # one-hot for COCO-Stuff's 183 classes); the feed expands it on
        # the device (``device_prefetch.expand_index_labels``, called by
        # ``BaseTrainer._on_device``) to exactly the stack
        # ``_encode_onehot`` builds. Decided from what the dataset sees
        # of itself: an image dataset whose labels hold exactly one mask
        # type, first in the list (mask channels lead the stack). Video
        # types fold past labels into channels on the host
        # (trainers/vid2vid._start_of_iteration; the type-name test also
        # catches paired_few_shot_videos_native, which has no temporal
        # stride) and every other layout encodes on the host.
        mask_labels = [t for t in self.input_labels
                       if self.is_mask.get(t, False)]
        is_video = (self.supports_temporal_stride
                    or "video" in str(cfg_get(self.cfgdata, "type", "")))
        self.index_map_label = (
            mask_labels[0]
            if (len(mask_labels) == 1 and not is_video
                and mask_labels[0] == self.input_labels[0]) else None)
        self.input_image = list(cfg_get(self.cfgdata, "input_image", None) or [])
        self.keypoint_data_types = list(
            cfg_get(self.cfgdata, "keypoint_data_types", None) or [])
        self.full_data_ops = _parse_ops(
            cfg_get(self.cfgdata, "full_data_ops", "None"))

        # Backends + sequence lists per root.
        self.backends = {t: [] for t in self.data_types}
        self.sequence_lists = []
        for root in self.roots:
            if backend == "folder":
                self.sequence_lists.append(
                    create_folder_metadata(root, self.data_types))
            else:
                import json

                with open(os.path.join(root, "all_filenames.json")) as f:
                    self.sequence_lists.append(json.load(f))
            for t in self.data_types:
                path = os.path.join(root, t)
                if backend == "folder":
                    self.backends[t].append(FolderBackend(path, self.extensions[t]))
                elif backend == "lmdb":
                    self.backends[t].append(LMDBBackend(path, self.extensions[t]))
                else:
                    self.backends[t].append(PackedBackend(path, self.extensions[t]))

        aug_cfg = cfg_get(data_info, "augmentations", None) or {}
        self.augmentor = Augmentor(aug_cfg, self.interpolators,
                                   keypoint_data_types=self.keypoint_data_types)
        if self.augmentor.max_time_step > 1 and not self.supports_temporal_stride:
            # the knob must never parse without effect: silently accepting
            # it would change training semantics vs the reference
            # (ref: datasets/paired_videos.py:167-191)
            raise ValueError(
                f"augmentations.max_time_step={self.augmentor.max_time_step} "
                f"is configured, but {type(self).__module__} does not "
                "implement strided temporal sampling; use a video dataset "
                "type or drop the knob")

    # video subclasses honoring augmentations.max_time_step set this True
    supports_temporal_stride = False

    # ------------------------------------------------------------------ api

    def __len__(self):
        raise NotImplementedError

    def __getitem__(self, index):
        raise NotImplementedError

    def get_label_lengths(self):
        """{label type: channel count incl. dont-care} (ref: base.py:204-218)."""
        lengths = {}
        for t in self.input_labels:
            n = self.num_channels[t]
            if self.use_dont_care[t]:
                n += 1
            lengths[t] = n
        return lengths

    # ------------------------------------------------------------- loading

    def load_item(self, lmdb_idx, sequence_name, filenames):
        """Load all data types for the given frames -> {type: [HWC arrays]}.

        Backends exposing ``getitems`` (the packed shard's native
        thread-pool reader) fetch a whole frame window in one concurrent
        batched read — the hot path for video datasets."""
        data = {}
        for t in self.data_types:
            backend = self.backends[t][lmdb_idx]
            keys = [f"{sequence_name}/{fname}" for fname in filenames]
            if len(keys) > 1 and hasattr(backend, "getitems"):
                data[t] = backend.getitems(keys)
            else:
                data[t] = [backend.getitem(k) for k in keys]
        return data

    def process_item(self, data, thread_common_attr=True):
        """pre-ops -> joint augmentation -> post-ops -> normalize/one-hot ->
        concat labels. Returns dict of (T,H,W,C) or (H,W,C) float arrays.

        ``thread_common_attr=False`` processes the item WITHOUT reading or
        writing the sequence-level common-attribute stash — the few-shot
        reference window must compute its own person bbox, not inherit
        the driving window's (ref: fs_vid2vid.py:242-256 computes
        ref_crop_coords separately)."""
        # Key the 0-255 -> 0-1 rescale off the SOURCE dtype, not a value
        # heuristic (float-valued data like .npy flow fields can exceed
        # 1.5 and must not be divided by 255).
        was_uint8 = {t: (len(data[t]) > 0 and
                         getattr(data[t][0], "dtype", None) == np.uint8)
                     for t in self.data_types}
        data = self._apply_ops(data, self.pre_aug_ops)
        data, is_flipped = self.augmentor.perform_augmentation(
            data, paired=True)
        # Keep the co-transformed keypoint coordinates as '<type>_xy'
        # before the vis:: op rasterizes them into label maps
        # (ref: paired_few_shot_videos.py:241-246); full-data ops like
        # crop_face_from_data consume these.
        kp_copies = {}
        for t in self.keypoint_data_types:
            frames = data.get(t)
            if frames and not isinstance(frames[0], dict):
                try:
                    kp_copies[t + "_xy"] = np.stack(
                        [np.asarray(f, np.float32) for f in frames])
                except (ValueError, TypeError):
                    # ragged per-frame keypoint counts, or structured
                    # multi-person lists (openpose_to_npy without
                    # largest-only): no flat stash
                    pass
        data = self._apply_ops(data, self.post_aug_ops)
        data.update(kp_copies)
        # thread common attributes (e.g. crop_person_from_data's inference
        # crop bbox) from the first processed window into later windows of
        # the same sequence (ref: paired_few_shot_videos.py:296-312;
        # cleared by set_inference_sequence_idx). The loader's prefetch
        # workers are THREADS over this shared dataset, so the stash is
        # lock-protected; windows that started before the first stash
        # landed still compute their own bbox (same first-windows caveat
        # as the reference's worker-index dance). The sequential eval
        # frame loaders (video FID / test loops) are unaffected.
        if thread_common_attr and self.is_inference:
            with self._common_attr_lock:
                if getattr(self, "_common_attr", None):
                    data.setdefault("common_attr", self._common_attr)
        data = self._apply_full_data_ops(data)
        if "common_attr" in data:
            stashed = data.pop("common_attr")
            if thread_common_attr and self.is_inference:
                with self._common_attr_lock:
                    self._common_attr = stashed

        out = {}
        for k in kp_copies:
            if k in data:
                out[k] = data[k]
        for t in self.data_types:
            if t not in data:
                continue  # consumed by a full-data op (e.g. instance maps)
            if not isinstance(data[t], (list, tuple)):
                # a convert:: op replaced the frame list with a structured
                # payload (e.g. decode_unprojections' {resolution: array}
                # dict) — pass it through; consumers read it directly
                out[t] = data[t]
                continue
            frames = []
            for arr in data[t]:
                arr = np.asarray(arr)
                vis_output = arr.ndim == 3 and t in self.keypoint_data_types
                arr = arr.astype(np.float32)
                if self.is_mask[t] or (self.num_channels[t] and arr.ndim == 3
                                       and arr.shape[-1] == 1
                                       and self.num_channels[t] > 1
                                       and not vis_output):
                    if t == self.index_map_label:
                        arr = self._encode_index_map(
                            arr, self.num_channels[t])
                    else:
                        arr = self._encode_onehot(
                            arr, self.num_channels[t], self.use_dont_care[t])
                else:
                    if was_uint8[t]:
                        arr = arr / 255.0
                    if self.normalize[t]:
                        arr = arr * 2.0 - 1.0
                frames.append(arr)
            out[t] = np.stack(frames, axis=0)
        out["is_flipped"] = np.asarray(is_flipped)
        return out

    @staticmethod
    def _encode_index_map(label_map, num_labels):
        """(H,W,1) -> (H,W,1) int32 with the same out-of-range mapping as
        ``_encode_onehot`` (OOR/negative -> dont-care index num_labels);
        the device-side ``jax.nn.one_hot`` then reproduces the host
        encoding exactly (a dropped dont-care channel falls out as the
        all-zero row one_hot gives out-of-range indices)."""
        idx = label_map[..., :1].astype(np.int32)
        idx[(idx < 0) | (idx >= num_labels)] = num_labels
        return idx

    @staticmethod
    def _encode_onehot(label_map, num_labels, use_dont_care):
        """(H,W,1) index map -> (H,W,num_labels[+1]) one-hot
        (ref: base.py:272-298): out-of-range and negative indices become
        the dont-care index; channel kept only when use_dont_care."""
        idx = label_map[..., 0].astype(np.int64)
        idx[(idx < 0) | (idx >= num_labels)] = num_labels
        out = np.zeros(idx.shape + (num_labels + 1,), dtype=np.float32)
        np.put_along_axis(out, idx[..., None], 1.0, axis=-1)
        if not use_dont_care:
            out = out[..., :num_labels]
        return out

    def concat_labels(self, out, squeeze_time=False):
        """(ref: paired_videos.py:276-283).

        Where the dataset ships its mask label as an index map
        (``index_map_label``), ``label`` is that int32 map (channel dim
        dropped) and the remaining float label types concatenate under
        ``label_float``: the feed appends them after the device-side
        one-hot, which keeps the reference's channel order (mask
        channels first)."""
        if self.index_map_label is not None:
            out["label"] = out.pop(self.index_map_label)[..., 0]  # (T,H,W)
            floats = [out.pop(t) for t in self.input_labels[1:]]
            if floats:
                out["label_float"] = np.concatenate(floats, axis=-1)
        elif self.input_labels:
            labels = [out.pop(t) for t in self.input_labels]
            out["label"] = np.concatenate(labels, axis=-1)
        if squeeze_time:
            for k in list(out.keys()):
                v = out[k]
                # the index map carries no channel dim
                min_ndim = 3 if (k == "label" and self.index_map_label) \
                    else 4
                if isinstance(v, np.ndarray) and v.ndim >= min_ndim:
                    out[k] = v[0] if v.shape[0] == 1 else v
        return out

    def _apply_ops(self, data, op_dict):
        """Plugin ops with the reference's spec grammar
        (ref: base.py:386-515): builtins (decode_json/decode_pkl/
        to_numpy), 'module::function' per-type ops, and the prefixed
        'vis::module::function' (receives the augmentation geometry and
        turns keypoints into rendered label maps) / 
        'convert::module::function' forms."""
        for t, ops in op_dict.items():
            if t not in data:
                continue
            for spec in ops:
                fn, op_type = self._resolve_op(spec)
                data[t] = fn(data[t])
        return data

    def _apply_full_data_ops(self, data):
        """Ops over the whole data dict (ref: base.py:399-406)."""
        for spec in self.full_data_ops:
            module, fn_name = spec.split("::")
            fn = getattr(importlib.import_module(module), fn_name)
            data = fn(self.cfgdata, self.is_inference, data)
        return data

    def _resolve_op(self, spec):
        """(ref: base.py:434-515)."""
        import json
        import pickle
        from functools import partial

        if spec == "decode_json":
            return (lambda frames: [json.loads(f) if isinstance(f, (str, bytes))
                                    else f for f in frames]), None
        if spec == "decode_pkl":
            return (lambda frames: [pickle.loads(f) for f in frames]), None
        if spec == "to_numpy":
            return (lambda frames: [np.asarray(f) for f in frames]), None
        parts = str(spec).split("::")
        if len(parts) == 2:
            module, fn_name = parts
            return getattr(importlib.import_module(module), fn_name), None
        if len(parts) == 3:
            op_type, module, fn_name = parts
            fn = getattr(importlib.import_module(module), fn_name)
            if op_type == "vis":
                aug = self.augmentor
                return partial(fn, aug.resize_h, aug.resize_w, aug.crop_h,
                               aug.crop_w, aug.original_h, aug.original_w,
                               aug.is_flipped, self.cfgdata), "vis"
            if op_type == "convert":
                return fn, "convert"
        raise ValueError(f"Unknown op spec {spec!r}")


def _parse_ops(spec):
    if not spec or spec == "None":
        return []
    return [item.strip() for item in str(spec).split(",")
            if item.strip() and item.strip() != "None"]
