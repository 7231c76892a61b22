"""Seeded datasets in the program's packed format, made once per
checkout and found again by a stamp.

`packed_cocostuff` is a copy of `imaginaire_tpu/data/fixtures.py`'s
generator (the yardstick keeps its own: see PERF.md, Open questions): jpg
images, png class-index maps (blocky, with dont-care speckle) and png edge
maps at `side` pixels. Packing is the program's own
(`data/backends.build_packed_dataset`): the packed shard is its input
format, not the benchmark's.
"""

from __future__ import annotations

import os
import shutil

import numpy as np


def packed_cocostuff(base, n_imgs, side=288, seed=0, n_classes=183):
    import cv2

    raw = os.path.join(base, "raw")
    packed = os.path.join(base, "packed")
    stamp = os.path.join(packed,
                         f".stamp_{n_imgs}_{side}_{seed}_{n_classes}")
    if os.path.exists(stamp):
        return packed
    shutil.rmtree(base, ignore_errors=True)
    rng = np.random.RandomState(seed)
    for i in range(n_imgs):
        seq = f"seq{i // 16:03d}"
        stem = f"{i:06d}"
        dirs = {t: os.path.join(raw, t, seq)
                for t in ("images", "seg_maps", "edge_maps")}
        for d in dirs.values():
            os.makedirs(d, exist_ok=True)
        img = rng.randint(0, 256, (side, side, 3)).astype(np.uint8)
        cv2.imwrite(os.path.join(dirs["images"], stem + ".jpg"), img,
                    [cv2.IMWRITE_JPEG_QUALITY, 90])
        blocks = rng.randint(0, n_classes,
                             (side // 16 + 1, side // 16 + 1))
        seg = np.repeat(np.repeat(blocks, 16, 0), 16, 1)[:side, :side]
        seg = seg.astype(np.uint8)
        seg[rng.rand(side, side) < 0.02] = 255  # dont-care speckle
        cv2.imwrite(os.path.join(dirs["seg_maps"], stem + ".png"), seg)
        cv2.imwrite(os.path.join(dirs["edge_maps"], stem + ".png"),
                    cv2.Canny(seg, 1, 1))
    from imaginaire_tpu.data.backends import build_packed_dataset

    build_packed_dataset(raw, packed, ["images", "seg_maps", "edge_maps"])
    shutil.rmtree(raw, ignore_errors=True)
    open(stamp, "w").close()
    return packed
