"""Seeded COCO-Stuff-shaped label stacks for serving requests.

A request carries what `inference.py`'s loader hands the engine today:
one float32 one-hot stack (1, H, W, C) of C = classes + dont-care + edge
channels. The maps are blocky (a coarse random class grid, nearest
upsampled, as segmentation masks are piecewise constant) with an edge
channel marking class borders.
"""

from __future__ import annotations

import numpy as np


def label_stack(rng, side, num_labels, cells=8):
    """One (1, side, side, num_labels) float32 stack. The last channel is
    the edge map, the one before it the dont-care class."""
    classes = num_labels - 1
    grid = rng.integers(0, classes, size=(cells, cells))
    # shift the grid so that block borders differ from stack to stack
    index = np.kron(grid, np.ones((side // cells, side // cells), np.int64))
    index = np.roll(index, tuple(rng.integers(0, side // cells, 2)), (0, 1))
    out = np.zeros((1, side, side, num_labels), np.float32)
    rows, cols = np.meshgrid(np.arange(side), np.arange(side), indexing="ij")
    out[0, rows, cols, index] = 1.0
    edge = np.zeros((side, side), bool)
    edge[:, 1:] |= index[:, 1:] != index[:, :-1]
    edge[1:, :] |= index[1:, :] != index[:-1, :]
    out[0, :, :, -1] = edge
    return out


def label_pool(seed, count, side, num_labels):
    rng = np.random.default_rng([int(seed), 0x1ABE1])
    return [label_stack(rng, side, num_labels) for _ in range(count)]
