"""correlation: FlowNetC cost volume between two feature maps.

Semantics match the reference CUDA kernel (ref:
third_party/correlation/src/correlation_cuda_kernel.cu;
correlation_cuda.cc:10-43 for the shape math): for displacement (dy, dx)
on a ``(2*max_displacement/stride2 + 1)^2`` grid, the output channel is
the patch dot-product of x1 at (i, j) and x2 at (i + dy, j + dx),
normalized by ``kernel_size^2 * C`` (the CUDA ``sumelems``). x2 is
zero-padded by ``pad_size`` exactly like the CUDA rInput staging.

Layout: NHWC in, output (B, H, W, D) with D displacement channels ordered
row-major over (dy, dx) — same channel order as the CUDA op, so FlowNetC
weights port directly.

TPU notes: the displacement loop is a ``lax.scan`` over a static grid
(one compiled slice+dot per step, compiler-friendly), and the reduction
over channels is a contraction XLA can fuse; the 'mxu' formulation turns
the channel dot into per-displacement-row MXU matmuls plus a band gather.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax

# 'auto' is pinned to the XLA 'mxu' formulation for the FlowNetC
# configuration; not measured on this installation. Shapes the mxu band
# grid cannot represent take 'jnp' in the dispatch below.
AUTO_IMPLEMENTATION = "mxu"


def _displacement_grid(max_displacement, stride2):
    steps = np.arange(-max_displacement, max_displacement + 1, stride2, dtype=np.int32)
    dyx = np.stack(np.meshgrid(steps, steps, indexing="ij"), axis=-1).reshape(-1, 2)
    return jnp.asarray(dyx)  # (D, 2) row-major over (dy, dx)


def _correlation_jnp(x1, x2, pad_size, kernel_size, max_displacement, stride1, stride2):
    if stride1 != 1:
        raise NotImplementedError("stride1 != 1 not used by FlowNetC")
    b, h, w, c = x1.shape
    k = kernel_size
    kr = (k - 1) // 2
    pad = pad_size + kr
    x2p = jnp.pad(x2, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    x1p = jnp.pad(x1, ((0, 0), (kr, kr), (kr, kr), (0, 0)))
    grid = _displacement_grid(max_displacement, stride2)
    sumelems = float(k * k * c)

    def patch_sum(prod):
        # sum over a k x k window centered at each pixel (k is small & odd)
        out = jnp.zeros((b, h, w), prod.dtype)
        for oy in range(k):
            for ox in range(k):
                out = out + lax.dynamic_slice(prod, (0, oy, ox), (b, h, w))
        return out

    def step(_, dyx):
        dy, dx = dyx[0], dyx[1]
        x2s = lax.dynamic_slice(
            x2p, (0, pad_size + dy, pad_size + dx, 0), (b, h + 2 * kr, w + 2 * kr, c)
        )
        prod = jnp.sum(x1p * x2s, axis=-1)  # channel contraction
        return None, patch_sum(prod) / sumelems

    _, maps = lax.scan(step, None, grid)  # (D, B, H, W)
    return jnp.transpose(maps, (1, 2, 3, 0))


def _correlation_mxu(x1, x2, pad_size, max_displacement, stride2):
    """Cost volume as MXU matmuls (kernel_size == 1, the FlowNetC case).

    The naive formulation walks 441 displacements, re-reading x1 from
    HBM each pass — bandwidth-bound VPU work. Here, per VERTICAL
    displacement, ``einsum('bhwc,bhvc->bhwv')`` computes every
    horizontal pairing at once — a (W, W+2*max_d, C) matmul the MXU
    tiles natively — and a strided band-gather keeps the n_dx wanted
    diagonals. ~(W+2p)/n_dx = 8x more MACs, but on the matrix unit with
    one HBM pass per dy instead of n_dx; the arithmetic is identical to
    _correlation_jnp (same channel order, same normalization).
    """
    b, h, w, c = x1.shape
    n_d = 2 * (max_displacement // stride2) + 1
    x2p = jnp.pad(x2, ((0, 0), (pad_size, pad_size), (pad_size, pad_size), (0, 0)))
    col0 = pad_size - max_displacement
    wide = w + 2 * max_displacement
    # band indices: output (j, dxi) reads pair column j + dxi*stride2
    idx = (jnp.arange(w)[:, None] + jnp.arange(n_d)[None, :] * stride2)

    def step(_, dyi):
        row0 = pad_size - max_displacement + dyi * stride2
        x2s = lax.dynamic_slice(x2p, (0, row0, col0, 0), (b, h, wide, c))
        pairs = jnp.einsum("bhwc,bhvc->bhwv", x1, x2s,
                           preferred_element_type=jnp.float32)
        band = jnp.take_along_axis(
            pairs, idx[None, None].astype(jnp.int32), axis=-1)
        return None, (band / c).astype(x1.dtype)

    _, maps = lax.scan(step, None, jnp.arange(n_d))  # (n_dy, B, H, W, n_dx)
    return jnp.transpose(maps, (1, 2, 3, 0, 4)).reshape(b, h, w, n_d * n_d)


def correlation(
    x1,
    x2,
    pad_size=20,
    kernel_size=1,
    max_displacement=20,
    stride1=1,
    stride2=2,
    implementation="auto",
):
    """FlowNetC cost volume. Returns (B, H, W, D)."""
    if x1.shape != x2.shape or x1.ndim != 4:
        raise ValueError(f"correlation expects matching NHWC inputs, got {x1.shape}, {x2.shape}")
    if pad_size < max_displacement:
        raise ValueError("pad_size must cover max_displacement")
    if implementation == "auto":
        # the 'mxu' matmul+band-gather formulation serves the FlowNetC
        # configuration; the scan path serves general
        # kernel_size/stride1
        implementation = AUTO_IMPLEMENTATION \
            if (kernel_size == 1 and stride1 == 1
                and max_displacement % stride2 == 0) \
            else "jnp"
    if implementation == "mxu":
        if kernel_size != 1 or stride1 != 1 \
                or max_displacement % stride2 != 0:
            # the band grid assumes a symmetric displacement range; an
            # indivisible max_displacement would silently drop the +md
            # band the scan path keeps
            raise NotImplementedError(
                "mxu correlation supports kernel_size=1, stride1=1, "
                "max_displacement divisible by stride2 (the FlowNetC "
                "configuration)")
        return _correlation_mxu(x1, x2, pad_size, max_displacement, stride2)
    if implementation == "jnp":
        return _correlation_jnp(x1, x2, pad_size, kernel_size, max_displacement, stride1, stride2)
    raise ValueError(f"unknown implementation {implementation!r}")
