"""channelnorm: per-pixel L-p norm across the channel axis.

Semantics match the reference CUDA kernel (ref:
third_party/channelnorm/src/channelnorm_kernel.cu:40-60): output (B,H,W,1)
with value ``(sum_c |x_c|^p)^(1/p)``; the reference hardcodes the sqrt for
p=2 at channelnorm_kernel.cu:58. Used by FlowNet2 to normalize flow
magnitudes.

The jnp forward is fully differentiable (the CUDA op ships a custom
backward; XLA autodiff derives the same) and is the only implementation:
XLA already fuses square, reduce and sqrt, and a kernel over an (N, C)
layout idles 128-wide lanes at the common C=2-3.
"""

from __future__ import annotations

import jax.numpy as jnp

# the one implementation, under the name the other ops' tables use
AUTO_IMPLEMENTATION = "jnp"


def channelnorm(x, p=2):
    """L-p norm over the trailing channel axis of an NHWC tensor -> (B,H,W,1)."""
    if p == 2:
        # small-eps-free: matches CUDA sqrt(sum x^2)
        return jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))
    return jnp.power(jnp.sum(jnp.abs(x) ** p, axis=-1, keepdims=True), 1.0 / p)
