"""resample2d: backward-warp an image by an optical flow field.

Semantics match the reference CUDA kernel
(ref: third_party/resample2d/src/resample2d_kernel.cu:16-75): for every
output pixel (y, x), read flow (dx, dy) = flow[y, x], bilinearly sample
``x`` at (x + dx, y + dy) with border-clamped neighbor indices; bilinear
weights come from the *unclamped* fractional coordinates (corner cases at
the border follow the CUDA code's clamp-after-weighting behavior,
resample2d_kernel.cu:52-55).

Also covers the pure-PyTorch twin the fork actually uses for warping
(ref: model_utils/fs_vid2vid.py:14-38 `resample` via grid_sample with
border padding) — identical math for align_corners bilinear + border pad.

Layout: NHWC. flow[..., 0] = horizontal displacement (pixels, +x right),
flow[..., 1] = vertical displacement (+y down).

The backward pass of the CUDA op scatters gradients with atomicAdd
(resample2d_kernel.cu:122-125). Here the jnp forward is built from
gathers, so jax autodiff produces exactly that scatter-add under XLA.
"""

from __future__ import annotations

import jax.numpy as jnp

# pinned to the XLA formulation; not measured on this installation
AUTO_IMPLEMENTATION = "jnp"


def _bilinear_warp(x, flow):
    """Differentiable jnp implementation (B, H, W, C) x (B, H, W, 2)."""
    b, h, w, c = x.shape
    dtype = jnp.promote_types(x.dtype, flow.dtype)
    xf = jnp.arange(w, dtype=dtype)[None, None, :] + flow[..., 0].astype(dtype)
    yf = jnp.arange(h, dtype=dtype)[None, :, None] + flow[..., 1].astype(dtype)

    x0 = jnp.floor(xf)
    y0 = jnp.floor(yf)
    ax = xf - x0  # fractional parts BEFORE clamping (cu:52-55)
    ay = yf - y0

    x0i = jnp.clip(x0.astype(jnp.int32), 0, w - 1)
    x1i = jnp.clip(x0.astype(jnp.int32) + 1, 0, w - 1)
    y0i = jnp.clip(y0.astype(jnp.int32), 0, h - 1)
    y1i = jnp.clip(y0.astype(jnp.int32) + 1, 0, h - 1)

    def gather(yi, xi):
        # x[b, yi[b,h,w], xi[b,h,w], :] — one gather per corner.
        bidx = jnp.arange(b)[:, None, None]
        return x[bidx, yi, xi]

    w00 = ((1.0 - ay) * (1.0 - ax))[..., None]
    w01 = ((1.0 - ay) * ax)[..., None]
    w10 = (ay * (1.0 - ax))[..., None]
    w11 = (ay * ax)[..., None]
    out = (
        w00 * gather(y0i, x0i)
        + w01 * gather(y0i, x1i)
        + w10 * gather(y1i, x0i)
        + w11 * gather(y1i, x1i)
    )
    return out.astype(x.dtype)


def resample2d(x, flow, implementation="auto"):
    """Warp ``x`` backward by ``flow`` (NHWC).

    implementation: 'jnp' | 'auto'
    """
    if x.ndim != 4 or flow.ndim != 4 or flow.shape[-1] != 2:
        raise ValueError(f"resample2d expects NHWC x and (B,H,W,2) flow, got {x.shape}, {flow.shape}")
    if implementation == "auto":
        implementation = AUTO_IMPLEMENTATION
    if implementation == "jnp":
        return _bilinear_warp(x, flow)
    raise ValueError(f"unknown implementation {implementation!r}")
