"""Activation normalization layers, incl. AdaIN / SPADE / hyper-SPADE.

ref: imaginaire/layers/activation_norm.py (AdaptiveNorm:22,
SpatiallyAdaptiveNorm:109, HyperSpatiallyAdaptiveNorm:237, LayerNorm2d:329,
factory:377).

All norms here expose the uniform call signature
``norm(x, *cond_inputs, training=...)`` so conv blocks can thread
conditional inputs without caring which norm they hold. Layout NHWC;
'batch' and 'sync_batch' are the same op under jit-sharded batches (the
global-batch mean IS the cross-replica mean; see parallel/sharding.py).
"""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp
from flax import linen as nn

from imaginaire_tpu.analysis import islands
from imaginaire_tpu.layers import hyper_ops


def _fusable_modulation(impl, base_norm, x, pairs, masked=False):
    """Whether the SPADE epilogue can route through the fused
    ``ops.spade_modulation`` op (ISSUE 16). Refusal cases fall back to
    the unfused composition: the op implements *instance*-norm
    statistics only, needs full-spatial γ/β maps (AdaptiveNorm's
    'linear' broadcast refuses via the shape check), and the
    ``partial=True`` masked path stays on the reference composition."""
    if impl in ("", "none", "off", "unfused", None):
        return False
    if masked or base_norm != "instance" or x.ndim != 4 or not pairs:
        return False
    return all(
        tuple(g.shape) == tuple(x.shape) == tuple(b.shape)
        for g, b in pairs)


def default_fused_modulation(anp, remat):
    """Generator-side default for the epilogue-fusion knob, given the
    model's remat policy. From a CPU memory analysis on an earlier
    installation at spade-512 bs 4 (not measured on this one):
    ``custom_vjp`` residuals are OPAQUE to ``jax.checkpoint``, so
    inside a rematted block the fused op pins (x, γ, stats) residuals
    the block policy would otherwise discard and recompute — fusion
    and block-remat are alternative mechanisms for the same residuals,
    not additive (fused+blocks: 22.61 GiB at baseline flops vs
    unfused+blocks 22.09 GiB at +4% flops). So under an enabled remat
    policy the default is 'none'; an explicit config knob always wins
    (memory_autotune sets it explicitly to measure both arms)."""
    from imaginaire_tpu.optim.remat import resolve_policy

    anp = dict(anp)
    if "fused_modulation" not in anp \
            and resolve_policy(remat, where="gen.remat").enabled:
        anp["fused_modulation"] = "none"
    return anp


def _resize(x, hw, method="nearest"):
    b, h, w, c = x.shape
    if (h, w) == tuple(hw):
        return x
    import jax

    return jax.image.resize(x, (b, hw[0], hw[1], c), method=method)


def _resize_nearest(x, hw):
    return _resize(x, hw, "nearest")


class NoNorm(nn.Module):
    @nn.compact
    def __call__(self, x, *cond, training=False):
        return x


class InstanceNorm(nn.Module):
    """Per-sample, per-channel spatial normalization (torch InstanceNorm2d
    semantics: affine=True by default in the reference's usage)."""

    affine: bool = True
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x, *cond, training=False):
        axes = tuple(range(1, x.ndim - 1))
        # statistics in fp32 even under a bf16 compute policy: the
        # `norm_stats` island (analysis/islands.py) — the exit cast back
        # to x.dtype stays OUTSIDE the scope
        x32 = x.astype(jnp.float32)
        with islands.scope("norm_stats"):
            mean = jnp.mean(x32, axis=axes, keepdims=True)
            var = jnp.var(x32, axis=axes, keepdims=True)
            islands.guard("norm_stats", mean=mean, var=var)
            y32 = (x32 - mean) * jnp.reciprocal(jnp.sqrt(var + self.eps))
        y = y32.astype(x.dtype)
        if self.affine:
            c = x.shape[-1]
            scale = self.param("scale", nn.initializers.ones, (c,))
            bias = self.param("bias", nn.initializers.zeros, (c,))
            y = y * scale.astype(y.dtype) + bias.astype(y.dtype)
        return y


class BatchNorm(nn.Module):
    """BatchNorm over the *global* batch — the TPU-native SyncBatchNorm
    (ref: layers/activation_norm.py:403-410). flax momentum 0.9 == torch
    momentum 0.1."""

    affine: bool = True
    eps: float = 1e-5
    momentum: float = 0.9

    @nn.compact
    def __call__(self, x, *cond, training=False):
        return nn.BatchNorm(
            use_running_average=not training,
            momentum=self.momentum,
            epsilon=self.eps,
            use_bias=self.affine,
            use_scale=self.affine,
        )(x)


class LayerNorm(nn.Module):
    """Channel-dim layer norm."""

    affine: bool = True
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x, *cond, training=False):
        return nn.LayerNorm(epsilon=self.eps, use_bias=self.affine, use_scale=self.affine)(x)


class LayerNorm2d(nn.Module):
    """Per-sample whole-tensor normalization with per-channel affine
    (ref: layers/activation_norm.py:329-374)."""

    affine: bool = True
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x, *cond, training=False):
        axes = tuple(range(1, x.ndim))
        # `norm_stats` fp32 island — exit cast outside the scope
        x32 = x.astype(jnp.float32)
        with islands.scope("norm_stats"):
            mean = jnp.mean(x32, axis=axes, keepdims=True)
            std = jnp.sqrt(jnp.var(x32, axis=axes, keepdims=True)
                           + self.eps)
            islands.guard("norm_stats", mean=mean, std=std)
            y32 = (x32 - mean) / std
        y = y32.astype(x.dtype)
        if self.affine:
            c = x.shape[-1]
            gamma = self.param("gamma", nn.initializers.ones, (c,))
            beta = self.param("beta", nn.initializers.zeros, (c,))
            y = gamma.astype(y.dtype) * y + beta.astype(y.dtype)
        return y


class GroupNorm(nn.Module):
    num_groups: int = 32
    affine: bool = True
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x, *cond, training=False):
        return nn.GroupNorm(
            num_groups=self.num_groups,
            epsilon=self.eps,
            use_bias=self.affine,
            use_scale=self.affine,
        )(x)


class AdaptiveNorm(nn.Module):
    """AdaIN: param-free base norm + γ/β projected from a style vector
    (ref: layers/activation_norm.py:22-106)."""

    projection: str = "linear"  # 'linear' | 'conv'
    base_norm: str = "instance"
    separate_projection: bool = False
    projection_bias: bool = True
    weight_norm_type: str = ""
    fused_modulation: str = "auto"  # ops.spade_modulation implementation

    @nn.compact
    def __call__(self, x, cond, training=False):
        from imaginaire_tpu.layers.conv import LinearBlock
        from imaginaire_tpu.ops.spade_modulation import spade_modulation

        c = x.shape[-1]

        def dense(feats, name):
            return LinearBlock(feats, bias=self.projection_bias, order="C",
                               weight_norm_type=self.weight_norm_type, name=name)

        if self.projection == "linear":
            if self.separate_projection:
                gamma = dense(c, "fc_gamma")(cond, training=training)
                beta = dense(c, "fc_beta")(cond, training=training)
            else:
                gb = dense(2 * c, "fc")(cond, training=training)
                gamma, beta = jnp.split(gb, 2, axis=-1)
            # broadcast (B, C) over spatial dims
            shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (c,)
            gamma = gamma.reshape(shape)
            beta = beta.reshape(shape)
        else:
            gb = nn.Conv(2 * c, (1, 1), use_bias=self.projection_bias, name="conv")(cond)
            gamma, beta = jnp.split(gb, 2, axis=-1)
        # the spatially-broadcast ('conv' projection) case fuses the
        # norm->modulate epilogue; the 'linear' broadcast maps refuse via
        # the full-spatial shape check (ISSUE 16)
        if _fusable_modulation(self.fused_modulation, self.base_norm, x,
                               [(gamma, beta)]):
            return spade_modulation(x, [gamma], [beta],
                                    implementation=self.fused_modulation)
        y = _base_norm(self.base_norm, affine=False)(x, training=training)
        return y * (1.0 + gamma) + beta


class SpatiallyAdaptiveNorm(nn.Module):
    """SPADE (ref: layers/activation_norm.py:109-234).

    Each conditioning map is resized (nearest) to x's spatial size, pushed
    through a small conv MLP, and contributes additive spatial γ/β maps:
    ``out = norm(x) * (1 + Σγ_i) + Σβ_i``. ``partial=True`` threads a
    validity mask through mask-aware convs (wc-vid2vid guidance,
    ref: activation_norm.py:184-199).
    """

    num_filters: int = 128
    kernel_size: int = 3
    base_norm: str = "sync_batch"
    separate_projection: bool = True
    partial: bool = False
    interpolation: str = "nearest"
    weight_norm_type: str = ""
    fused_modulation: str = "auto"  # ops.spade_modulation implementation

    @nn.compact
    def __call__(self, x, *cond_inputs, training=False):
        from imaginaire_tpu.layers.conv import Conv2dBlock, PartialConv2d
        from imaginaire_tpu.ops.spade_modulation import spade_modulation

        c = x.shape[-1]
        hw = x.shape[1:3]

        def conv(feats, name):
            return Conv2dBlock(feats, kernel_size=self.kernel_size, order="C",
                               weight_norm_type=self.weight_norm_type, name=name)

        pairs = []
        masked = False
        for i, cond in enumerate(cond_inputs):
            if cond is None:
                continue
            mask = None
            if isinstance(cond, (tuple, list)):
                cond, mask = cond
            cond = _resize(cond, hw, self.interpolation)
            if mask is not None:
                mask = _resize(mask, hw, self.interpolation)
            if self.partial and mask is not None:
                hidden, _ = PartialConv2d(
                    self.num_filters, self.kernel_size, name=f"mlp_{i}"
                )(cond, mask)
                hidden = nn.relu(hidden)
                masked = True
            elif self.num_filters > 0:
                hidden = nn.relu(conv(self.num_filters, f"mlp_{i}")(cond, training=training))
            else:
                hidden = cond
            if self.separate_projection:
                gamma = conv(c, f"gamma_{i}")(hidden, training=training)
                beta = conv(c, f"beta_{i}")(hidden, training=training)
            else:
                gb = conv(2 * c, f"gb_{i}")(hidden, training=training)
                gamma, beta = jnp.split(gb, 2, axis=-1)
            pairs.append((gamma, beta))
        if _fusable_modulation(self.fused_modulation, self.base_norm, x,
                               pairs, masked=masked):
            # the whole multi-cond accumulation fuses: norm(x), Σγ and
            # Σβ never materialize (ops/spade_modulation.py, ISSUE 16).
            # The base norm here is the paramless InstanceNorm, so the
            # param tree is identical across implementations.
            return spade_modulation(x, [g for g, _ in pairs],
                                    [b for _, b in pairs],
                                    implementation=self.fused_modulation)
        y = _base_norm(self.base_norm, affine=False)(x, training=training)
        gamma_sum = None
        beta_sum = None
        for gamma, beta in pairs:
            gamma_sum = gamma if gamma_sum is None else gamma_sum + gamma
            beta_sum = beta if beta_sum is None else beta_sum + beta
        if gamma_sum is None:
            return y
        return y * (1.0 + gamma_sum) + beta_sum


class HyperSpatiallyAdaptiveNorm(nn.Module):
    """SPADE whose first-cond MLP weights are *runtime inputs* predicted by a
    weight generator (fs-vid2vid; ref: layers/activation_norm.py:237-326).

    ``norm_weights=(w, b)`` with w: (B, kh, kw, cin, cout) per-sample conv
    kernels applied via vmap'd conv — replacing the reference's per-sample
    Python loop with one batched XLA conv.
    """

    num_filters: int = 0
    kernel_size: int = 3
    base_norm: str = "instance"
    fused_modulation: str = "auto"  # ops.spade_modulation implementation

    @nn.compact
    def __call__(self, x, *cond_inputs, norm_weights=None, training=False):
        from imaginaire_tpu.ops.spade_modulation import spade_modulation

        c = x.shape[-1]
        hw = x.shape[1:3]
        pairs = []  # (gamma, beta, had_mask)
        for i, cond in enumerate(cond_inputs):
            if cond is None:
                continue
            mask = None
            if isinstance(cond, (tuple, list)):
                cond, mask = cond
                mask = _resize(mask, hw, "bilinear")
            cond = _resize_nearest(cond, hw)
            if i == 0 and norm_weights is not None \
                    and norm_weights[0] is not None:
                # predicted per-sample conv emits the 2c affine params
                # directly (ref: activation_norm.py:279-283, 317-321)
                w, b = norm_weights
                affine = hyper_ops.per_sample_conv2d(cond, w, b,
                                                     padding="SAME")
            else:
                h = cond
                if self.num_filters > 0:
                    h = nn.relu(nn.Conv(
                        self.num_filters,
                        (self.kernel_size, self.kernel_size),
                        padding="SAME", name=f"mlp_{i}")(h))
                affine = nn.Conv(2 * c, (self.kernel_size, self.kernel_size),
                                 padding="SAME", name=f"gb_{i}")(h)
            gamma, beta = jnp.split(affine, 2, axis=-1)
            if mask is not None:
                gamma = gamma * (1 - mask)
                beta = beta * (1 - mask)
            pairs.append((gamma, beta, mask is not None))
        # The combine here is SEQUENTIAL per condition (not summed), so
        # only the first γ/β pair — the one applied directly to norm(x),
        # incl. the runtime-weight path — fuses with the normalization;
        # a masked first pair refuses (ISSUE 16).
        start = 0
        if pairs and _fusable_modulation(
                self.fused_modulation, self.base_norm, x,
                [pairs[0][:2]], masked=pairs[0][2]):
            out = spade_modulation(x, [pairs[0][0]], [pairs[0][1]],
                                   implementation=self.fused_modulation)
            start = 1
        else:
            out = _base_norm(self.base_norm, affine=False)(x,
                                                           training=training)
        for gamma, beta, _ in pairs[start:]:
            out = out * (1.0 + gamma) + beta
        return out


def _base_norm(kind, affine):
    if kind in ("", "none", None):
        return NoNorm()
    if kind in ("batch", "sync_batch"):
        return BatchNorm(affine=affine)
    if kind == "instance":
        return InstanceNorm(affine=affine)
    if kind == "layer":
        return LayerNorm(affine=affine)
    if kind == "layer_2d":
        return LayerNorm2d(affine=affine)
    raise ValueError(f"unknown base norm {kind!r}")


CONDITIONAL_NORMS = ("adaptive", "spatially_adaptive", "hyper_spatially_adaptive")


def get_activation_norm_layer(norm_type, norm_params=None, name=None):
    """Norm factory (ref: layers/activation_norm.py:377-432). Returns a
    module with the uniform ``(x, *cond, training=)`` signature, or None."""
    p: dict[str, Any] = dict(norm_params or {})
    kw = {"name": name} if name else {}
    # Accept the reference's '<x>_norm' spellings (e.g. mlp_multiclass
    # passes 'batch_norm', ref: discriminators/mlp_multiclass.py:28-30).
    if isinstance(norm_type, str) and norm_type.endswith("_norm"):
        norm_type = norm_type[: -len("_norm")]
    if norm_type in ("", "none", None):
        return None
    if norm_type in ("batch", "sync_batch"):
        return BatchNorm(affine=p.get("affine", True), **kw)
    if norm_type == "instance":
        return InstanceNorm(affine=p.get("affine", True), **kw)
    if norm_type == "layer":
        return LayerNorm(affine=p.get("affine", True), **kw)
    if norm_type == "layer_2d":
        return LayerNorm2d(affine=p.get("affine", True), **kw)
    if norm_type == "group":
        return GroupNorm(num_groups=p.get("num_groups", 32), affine=p.get("affine", True), **kw)
    if norm_type == "adaptive":
        return AdaptiveNorm(
            projection=p.get("projection", "linear"),
            base_norm=p.get("activation_norm_type", "instance"),
            separate_projection=p.get("separate_projection", False),
            weight_norm_type=p.get("weight_norm_type", ""),
            fused_modulation=p.get("fused_modulation", "auto"),
            **kw,
        )
    if norm_type == "spatially_adaptive":
        return SpatiallyAdaptiveNorm(
            num_filters=p.get("num_filters", 128),
            kernel_size=p.get("kernel_size", 3),
            base_norm=p.get("activation_norm_type", "sync_batch"),
            separate_projection=p.get("separate_projection", True),
            partial=p.get("partial", False),
            interpolation=p.get("interpolation", "nearest"),
            weight_norm_type=p.get("weight_norm_type", ""),
            fused_modulation=p.get("fused_modulation", "auto"),
            **kw,
        )
    if norm_type == "hyper_spatially_adaptive":
        return HyperSpatiallyAdaptiveNorm(
            num_filters=p.get("num_filters", 0),
            kernel_size=p.get("kernel_size", 3),
            base_norm=p.get("activation_norm_type", "instance"),
            fused_modulation=p.get("fused_modulation", "auto"),
            **kw,
        )
    raise ValueError(f"unknown activation norm {norm_type!r}")
