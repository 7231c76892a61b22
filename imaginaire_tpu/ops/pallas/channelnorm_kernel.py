"""Fused channel L-p norm Pallas kernel.

One VMEM pass per row-block: |x|^p, channel reduction and the p-th root
fused. Rows = flattened B*H*W, lanes = C, mostly idle at the common
C=2-3, while XLA fuses the jnp path into neighboring ops — so
``channelnorm(implementation='auto')`` picks jnp (not measured on this
installation). The kernel compiles for a TPU v5e
(tests/test_tpu_compile.py) and is kept for parity testing and as a
fusion example.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(p, x_ref, o_ref):
    v = x_ref[:].astype(jnp.float32)
    if p == 2:
        acc = jnp.sum(v * v, axis=1, keepdims=True)
        o_ref[:] = jnp.sqrt(acc).astype(o_ref.dtype)
    else:
        acc = jnp.sum(jnp.abs(v) ** p, axis=1, keepdims=True)
        o_ref[:] = (acc ** (1.0 / p)).astype(o_ref.dtype)


# lint: allow(bare-jit) -- static-argnames micro-kernel; ops/channelnorm.py's step programs are ledgered
@functools.partial(jax.jit, static_argnames=("p", "interpret", "block_rows"))
def channelnorm_pallas(x, p=2, interpret=False, block_rows=1024):
    b, h, w, c = x.shape
    n = b * h * w
    x2 = x.reshape(n, c)
    rows = min(block_rows, n)
    # pad rows up to a multiple of the block
    padded = ((n + rows - 1) // rows) * rows
    if padded != n:
        x2 = jnp.pad(x2, ((0, padded - n), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, p),
        out_shape=jax.ShapeDtypeStruct((padded, 1), x.dtype),
        grid=(padded // rows,),
        in_specs=[pl.BlockSpec((rows, c), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        interpret=interpret,
    )(x2)
    return out[:n].reshape(b, h, w, 1)
