"""Synthetic datasets made from a seed, for benches and smoke runs that
must need nothing outside the checkout."""

import os
import shutil

import numpy as np


def make_packed_cocostuff_fixture(base, n_imgs=64, side=288, seed=0,
                                  n_classes=183):
    """Synthesize a COCO-Stuff-shaped packed-shard fixture under
    ``base`` (once per (n_imgs, side, seed, n_classes): a stamp file
    marks a finished build): jpg images + png class-index seg maps
    (blocky, with dont-care speckle) + png edge maps, packed by
    data/backends.build_packed_dataset (SURVEY §7 hard-part #6).
    Returns the packed root."""
    import cv2

    raw = os.path.join(base, "raw")
    packed = os.path.join(base, "packed")
    stamp = os.path.join(
        packed, f".stamp_{n_imgs}_{side}_{seed}_{n_classes}")
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
        # blocky class maps: real seg labels are piecewise-constant, and
        # pixel noise would make the png decode cost unrealistically high
        blocks = rng.randint(0, n_classes,
                             (side // 16 + 1, side // 16 + 1))
        seg = np.repeat(np.repeat(blocks, 16, 0), 16, 1)[:side, :side]
        seg = seg.astype(np.uint8)
        seg[rng.rand(side, side) < 0.02] = 255  # dont-care speckle
        cv2.imwrite(os.path.join(dirs["seg_maps"], stem + ".png"), seg)
        edge = cv2.Canny(seg, 1, 1)
        cv2.imwrite(os.path.join(dirs["edge_maps"], stem + ".png"), edge)
    from imaginaire_tpu.data.backends import build_packed_dataset

    build_packed_dataset(raw, packed, ["images", "seg_maps", "edge_maps"])
    open(stamp, "w").close()
    return packed
