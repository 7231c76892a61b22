"""Packed token sequences (a language model's pretraining feed).

``roots`` name ``.npy`` files of shape (sequences, seq_len), int32:
documents closed by an end-of-document id, concatenated and cut into
sequences with no padding. An item is ``{"tokens": (seq_len,) int32}``;
the loader stacks them to (B, L). Nothing is decoded or augmented, and
the batch has no ``label``: the feed's index-map rule
(``device_prefetch.expand_index_labels``) leaves it as it is.
"""

from __future__ import annotations

import os

import numpy as np

from imaginaire_tpu.config import cfg_get


class Dataset:
    index_map_label = None

    def __init__(self, cfg, is_inference=False, is_test=False):
        data_cfg = cfg.test_data if is_test else cfg.data
        split = data_cfg.test if is_test else (
            data_cfg.val if is_inference else data_cfg.train)
        self.seq_len = int(data_cfg.seq_len)
        self.vocab_size = int(data_cfg.vocab_size)
        shards = []
        for root in cfg_get(split, "roots", None) or []:
            paths = ([root] if root.endswith(".npy") else sorted(
                os.path.join(root, f) for f in os.listdir(root)
                if f.endswith(".npy")))
            shards.extend(np.load(p, mmap_mode="r") for p in paths)
        if not shards:
            raise ValueError(f"{data_cfg.type}: no .npy shard under "
                             f"{list(cfg_get(split, 'roots', None) or [])}")
        for shard in shards:
            if shard.ndim != 2 or shard.shape[1] != self.seq_len:
                raise ValueError(
                    f"a shard of shape {shard.shape} does not hold "
                    f"sequences of data.seq_len {self.seq_len}")
        self.shards = shards
        self.offsets = np.cumsum([0] + [len(s) for s in shards])

    def __len__(self):
        return int(self.offsets[-1])

    def __getitem__(self, index):
        shard = int(np.searchsorted(self.offsets, index, side="right")) - 1
        tokens = np.asarray(self.shards[shard][index - self.offsets[shard]],
                            np.int32)
        if tokens.max() >= self.vocab_size or tokens.min() < 0:
            raise ValueError(f"sequence {index} holds ids outside "
                             f"data.vocab_size {self.vocab_size}")
        return {"tokens": tokens}
