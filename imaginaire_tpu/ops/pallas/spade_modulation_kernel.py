"""Fused SPADE norm->modulate epilogue Pallas kernels (ISSUE 16).

Two VMEM passes over x, zero full-size intermediates in HBM:

  pass 1 (stats):  per-(sample, channel) sum / sum-of-squares accumulate
                   in fp32 across spatial blocks — the ``norm_stats``
                   island, reduced inside the kernel — and finalize to
                   mean / rstd, each only (B, C) fp32 in HBM.
  pass 2 (apply):  re-read x and every (γ_i, β_i) block, compute
                   ``(x - mean) * rstd * (1 + Σγ_i) + Σβ_i`` in fp32
                   registers and write the output block directly —
                   ``norm(x)``, ``Σγ`` and ``Σβ`` never materialize.

Layout: x is flattened to (B, S=H*W, C) and zero-padded to block
multiples. Zero rows are sound for the stats pass (they add 0 to both
accumulators while the divisor stays the true S); padded rows/lanes of
the apply pass are sliced away on return.

The stats kernel relies on the TPU grid being a sequential pipelined
loop: the (B, C)-block outputs are revisited on every consecutive
spatial step, so they double as fp32 accumulators (same pattern as the
guide's accumulation example). The apply grid is embarrassingly
parallel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from imaginaire_tpu.analysis import islands

_BLOCK_S = 512  # spatial rows per block (multiple of the f32 sublane 8)
_BLOCK_C = 128  # channel lanes per block (the TPU lane width)


def _stats_kernel(n_sb, inv_s, eps, x_ref, mean_ref, rstd_ref):
    sb = pl.program_id(2)

    @pl.when(sb == 0)
    def _zero():
        mean_ref[...] = jnp.zeros_like(mean_ref)
        rstd_ref[...] = jnp.zeros_like(rstd_ref)

    x = x_ref[0].astype(jnp.float32)
    mean_ref[0] += jnp.sum(x, axis=0, keepdims=True)
    rstd_ref[0] += jnp.sum(x * x, axis=0, keepdims=True)

    @pl.when(sb == n_sb - 1)
    def _finalize():
        mean = mean_ref[...] * inv_s
        # biased variance (denominator S), matching jnp.var / the
        # reference InstanceNorm2d
        var = jnp.maximum(rstd_ref[...] * inv_s - mean * mean, 0.0)
        mean_ref[...] = mean
        rstd_ref[...] = jax.lax.rsqrt(var + eps)


def _apply_kernel(n_pairs, *refs):
    x_ref = refs[0]
    gamma_refs = refs[1 : 1 + n_pairs]
    beta_refs = refs[1 + n_pairs : 1 + 2 * n_pairs]
    mean_ref, rstd_ref, o_ref = refs[1 + 2 * n_pairs :]
    x = x_ref[0].astype(jnp.float32)
    xhat = (x - mean_ref[0]) * rstd_ref[0]
    gs = jnp.float32(1.0)
    for g_ref in gamma_refs:
        gs = gs + g_ref[0].astype(jnp.float32)
    bs = jnp.float32(0.0)
    for b_ref in beta_refs:
        bs = bs + b_ref[0].astype(jnp.float32)
    o_ref[0] = (xhat * gs + bs).astype(o_ref.dtype)


def _pad2(a, s_pad, c_pad):
    b, s, c = a.shape
    if (s, c) == (s_pad, c_pad):
        return a
    return jnp.pad(a, ((0, 0), (0, s_pad - s), (0, c_pad - c)))


# lint: allow(bare-jit) -- static-argnames micro-kernel; the op's step programs are ledgered
@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def spade_modulation_fwd_pallas(x, gammas, betas, eps=1e-5,
                                interpret=False):
    """Fused forward. x: (B, H, W, C); gammas/betas: tuples of tensors
    shaped like x. Returns (out, mean, rstd) with mean/rstd (B, 1, 1, C)
    fp32 — the only extra HBM the op leaves behind (residuals for the
    custom_vjp backward in ops/spade_modulation.py)."""
    b, h, w, c = x.shape
    s = h * w
    bs_ = min(_BLOCK_S, max(8, ((s + 7) // 8) * 8))
    bc = min(_BLOCK_C, max(8, ((c + 7) // 8) * 8))
    s_pad = ((s + bs_ - 1) // bs_) * bs_
    c_pad = ((c + bc - 1) // bc) * bc
    n_sb, n_cb = s_pad // bs_, c_pad // bc

    x3 = _pad2(x.reshape(b, s, c), s_pad, c_pad)
    g3 = tuple(_pad2(g.reshape(b, s, c), s_pad, c_pad) for g in gammas)
    b3 = tuple(_pad2(t.reshape(b, s, c), s_pad, c_pad) for t in betas)

    row_spec = pl.BlockSpec((1, bs_, bc), lambda bi, ci, si: (bi, si, ci))
    # a unit axis between batch and channels: the chip's compiler wants
    # the last two dims of a block divisible by (8, 128) or equal to the
    # array's own, and a (1, bc) block over (B, C) is neither
    stat_spec = pl.BlockSpec((1, 1, bc), lambda bi, ci, si: (bi, 0, ci))

    with islands.scope("norm_stats"):
        mean, rstd = pl.pallas_call(
            functools.partial(_stats_kernel, n_sb, 1.0 / s, eps),
            grid=(b, n_cb, n_sb),
            in_specs=[row_spec],
            out_specs=(stat_spec, stat_spec),
            out_shape=(jax.ShapeDtypeStruct((b, 1, c_pad), jnp.float32),
                       jax.ShapeDtypeStruct((b, 1, c_pad), jnp.float32)),
            interpret=interpret,
        )(x3)
        islands.guard("norm_stats", mean=mean, rstd=rstd)

    out = pl.pallas_call(
        functools.partial(_apply_kernel, len(g3)),
        grid=(b, n_cb, n_sb),
        in_specs=[row_spec] * (1 + 2 * len(g3)) + [stat_spec, stat_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((b, s_pad, c_pad), x.dtype),
        interpret=interpret,
    )(x3, *g3, *b3, mean, rstd)

    out = out[:, :s, :c].reshape(b, h, w, c)
    mean = mean[:, :, :c].reshape(b, 1, 1, c)
    rstd = rstd[:, :, :c].reshape(b, 1, 1, c)
    return out, mean, rstd
