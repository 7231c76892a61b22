"""spade_modulation: the fused SPADE norm->modulate epilogue (ISSUE 16).

The SPADE-family norms (layers/activation_norm.py) all end in the same
epilogue: instance-normalize x, then ``y = norm(x) * (1 + Σγ_i) + Σβ_i``
with per-condition spatial γ/β maps (ref: layers/activation_norm.py:109-234
``SpatiallyAdaptiveNorm``). Left to autodiff, that composition saves
``norm(x)`` AND the summed γ map as full B×H×W×C residuals for the
backward pass — at spade-512 that is the synthesis hot path's largest
activation cost after the segmap-embed conv scratch (an earlier
installation's CPU memory analysis; not measured on this one).

This op computes the whole epilogue in one differentiable call:

  - instance-norm statistics reduce in fp32 (the ``norm_stats`` island —
    same semantics as ``InstanceNorm``: biased variance over the spatial
    axes, ``eps`` inside the rsqrt, exit cast back to x.dtype OUTSIDE
    the island scope);
  - a hand-written ``custom_vjp`` keeps only (x, γ_i, mean, rstd) as
    residuals — mean/rstd are (B, 1, 1, C) fp32 — and rebuilds
    ``x̂``/``1 + Σγ`` in the backward, so the normalized tensor and the
    summed γ/β maps never persist to HBM;
  - the γ/β lists fuse the multi-condition accumulation too: gradients
    are ``dβ_i = g`` and ``dγ_i = g · x̂`` for every i, and
    ``dx = rstd · (ĝ − mean_sp(ĝ) − x̂ · mean_sp(ĝ · x̂))`` with
    ``ĝ = g · (1 + Σγ)`` and spatial means (the standard instance-norm
    backward, ref: torch instance_norm backward semantics).

implementations:
  'jnp'              plain jnp composition (autodiff reference)
  'fused'            same forward math under the custom_vjp (residual
                     trimming only; runs on every backend)
  'auto'             the pin, see AUTO_IMPLEMENTATION below
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from imaginaire_tpu.analysis import islands

# pinned to the XLA 'fused' formulation (the custom_vjp
# residual-trimming path); not measured on this installation. 'jnp' and
# 'fused' share their forward math: what differs is what the backward keeps.
AUTO_IMPLEMENTATION = "fused"

_SPATIAL_AXES = (1, 2)  # NHWC instance-norm reduction axes


def _stats(x32, eps):
    """fp32 instance-norm statistics — the `norm_stats` island. Returns
    (mean, rstd), both (B, 1, 1, C) fp32; the caller casts back to the
    compute dtype OUTSIDE the island scope."""
    with islands.scope("norm_stats"):
        mean = jnp.mean(x32, axis=_SPATIAL_AXES, keepdims=True)
        var = jnp.var(x32, axis=_SPATIAL_AXES, keepdims=True)
        islands.guard("norm_stats", mean=mean, var=var)
        rstd = jnp.reciprocal(jnp.sqrt(var + eps))
    return mean, rstd


def _apply(x, mean, rstd, gammas, betas):
    """The modulate half, given fp32 stats: mirrors the unfused layer
    math exactly (normalize in fp32, exit-cast, then combine in the
    compute dtype) so 'jnp' is a drop-in for the composition it
    replaces."""
    y = ((x.astype(jnp.float32) - mean) * rstd).astype(x.dtype)
    gamma_sum = functools.reduce(lambda a, b: a + b, gammas)
    beta_sum = functools.reduce(lambda a, b: a + b, betas)
    return y * (1.0 + gamma_sum) + beta_sum


def _spade_modulation_jnp(x, gammas, betas, eps):
    mean, rstd = _stats(x.astype(jnp.float32), eps)
    return _apply(x, mean, rstd, gammas, betas)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _spade_modulation_fused(x, gammas, betas, eps):
    out, _ = _fused_fwd(x, gammas, betas, eps)
    return out


def _fused_fwd(x, gammas, betas, eps):
    mean, rstd = _stats(x.astype(jnp.float32), eps)
    out = _apply(x, mean, rstd, gammas, betas)
    # scalar dtype tokens stand in for the betas: dβ_i is just g cast to
    # β_i's dtype, so the full β tensors need not survive as residuals
    beta_tokens = tuple(jnp.zeros((), b.dtype) for b in betas)
    return out, (x, gammas, beta_tokens, mean, rstd)


def _fused_bwd(eps, res, g):
    x, gammas, beta_tokens, mean, rstd = res
    g32 = g.astype(jnp.float32)
    xhat = (x.astype(jnp.float32) - mean) * rstd
    gs = functools.reduce(lambda a, b: a + b.astype(jnp.float32),
                          gammas, jnp.float32(1.0))
    ghat = g32 * gs
    # backward statistics reduce in fp32 like the forward's — same
    # island, exit casts below stay outside the scope
    with islands.scope("norm_stats"):
        m1 = jnp.mean(ghat, axis=_SPATIAL_AXES, keepdims=True)
        m2 = jnp.mean(ghat * xhat, axis=_SPATIAL_AXES, keepdims=True)
        islands.guard("norm_stats", m1=m1, m2=m2)
        dx32 = rstd * (ghat - m1 - xhat * m2)
    dgamma32 = g32 * xhat  # shared by every γ_i (additive accumulation)
    dgammas = tuple(dgamma32.astype(gi.dtype) for gi in gammas)
    dbetas = tuple(g.astype(t.dtype) for t in beta_tokens)
    return dx32.astype(x.dtype), dgammas, dbetas


_spade_modulation_fused.defvjp(_fused_fwd, _fused_bwd)


def spade_modulation(x, gammas, betas, *, eps=1e-5, implementation="auto"):
    """``instance_norm(x) * (1 + Σγ_i) + Σβ_i`` in one fused call.

    x: (B, H, W, C); gammas/betas: equal-length sequences of tensors
    shaped exactly like x (one pair per SPADE condition input).

    implementation: 'jnp' | 'fused' | 'auto' (see module docstring).
    """
    gammas = tuple(gammas)
    betas = tuple(betas)
    if x.ndim != 4:
        raise ValueError(f"spade_modulation expects NHWC x, got {x.shape}")
    if not gammas or len(gammas) != len(betas):
        raise ValueError(
            f"spade_modulation needs matched non-empty gamma/beta lists, "
            f"got {len(gammas)} gammas / {len(betas)} betas")
    for t in gammas + betas:
        if tuple(t.shape) != tuple(x.shape):
            raise ValueError(
                f"spade_modulation gamma/beta must match x {x.shape}, "
                f"got {t.shape} — broadcast maps (AdaptiveNorm 'linear') "
                f"are the caller's refusal case")
    eps = float(eps)
    if implementation == "auto":
        implementation = AUTO_IMPLEMENTATION
    if implementation == "jnp":
        return _spade_modulation_jnp(x, gammas, betas, eps)
    if implementation == "fused":
        return _spade_modulation_fused(x, gammas, betas, eps)
    raise ValueError(f"unknown implementation {implementation!r}")
