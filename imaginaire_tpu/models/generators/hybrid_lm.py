"""Hybrid token model: Mamba-2 mixers, causal attention, delta-rule linear
attention, gated short convolutions, dense and mixture-of-experts
feed-forwards, laid out by a pattern string (Nemotron-H,
``hybrid_override_pattern``: ``M`` a Mamba-2 mixer, ``*`` attention, ``-``
a dense feed-forward, ``E`` a mixture of experts; ``K``, ``C`` and ``W``
are this file's own letters for a Kimi Delta Attention mixer, for LFM2's
gated short convolution and for grouped-query attention under a sliding
window). A transformer block is two letters: ``*-``, ``*E``, ``KE``,
``C-``, ``CE``, ``W-`` or ``WE``.

Every layer is one mixer behind a pre-norm residual,
``h = h + Mixer(RMSNorm(h))``, and where ``use_post_norm`` says so the
mixer's result is normed again before it is added, ``h = h +
RMSNorm_post(Mixer(RMSNorm(h)))``, under a learned scale of its own (a
block of two letters then has four norms); the embedding is multiplied by
``embed_scale`` where the config gives one; logits are ``RMSNorm(h)
W_head``. The module returns the mean next-token cross-entropy itself,
over token chunks, so the (tokens, vocabulary) logits never stand whole.

What a layer is follows from the sizes the config gives, which are the
published ones under their published names; a size a model does not have
is absent (``_NEEDS`` says what each letter reads). ``*`` is latent
attention where there is a ``kv_lora_rank`` (DeepSeek-V2's: queries and
keys-values through normed low-rank latents, one rotary key shared by all
heads beside each head's un-rotated part) and grouped-query attention
otherwise: without a position embedding where the config gives no
``rope_theta``, with the rotary turn over the whole head where it gives
one; with queries and keys RMS-normed over the head's channels before the
turn where ``use_qk_norm`` says so, and with a sigmoid gate on its output
where ``use_gqa_gate`` does. ``W`` is the same grouped-query layer
whose queries see the ``sliding_window`` keys up to their own, themselves
counted; it always takes the rotary turn, and in a model that has both
letters ``use_rope_on_full_attention`` false leaves the ``*`` layers
without one under the same ``rope_theta``. ``K`` is the gated delta rule with a
decay a key channel (``KDAMixer``; its sizes are the published
``linear_attn_config`` group's). ``C`` is a depthwise causal convolution
of ``conv_L_cache`` taps between two elementwise gates
(``ShortConvMixer``). An ``E`` layer has a shared expert beside the routed
ones where the config gives ``moe_shared_expert_intermediate_size``, and
none where it does not; a factor on its routed sum where it gives
``routed_scaling_factor``; its router scores by sigmoid under a fixed
bias, or where ``moe_primary_router_apply_softmax`` says so by the
softmax over the chosen logits with no bias (``route``); and where
``use_early_router`` says so the router reads not the layer's own normed
input but that of the attention layer before it (SmallThinker's
``moe_enable_early_router``: the routing depends on nothing attention
computes), which that layer's block hands on (``Block``). ``hidden_act``
``silu`` makes every feed-forward gated, ``W_down (silu(W_gate x) * W_up
x)``, ``relu`` gates by ``relu`` in its place; ``relu2`` (the default:
Nemotron-H's ``mlp_hidden_act``) is ``W_down relu(W_up x)^2``.
``nextn_pattern`` adds one multi-token-prediction module
(DeepSeek-V3's): position ``i``'s final hidden state and token ``i+1``'s
embedding, each normed, merged by one product, through the module's own
layers to the logits for token ``i+2`` under the main model's embedding
and head; its loss is returned beside the main one.

A share of a deployment: a head count under the published head size is
a head-parallel rank's heads (their rows of ``W_o`` give the rank's part
of the mixer's result), and ``experts_held`` names the
routed experts this chip holds (``{first, count, of}``). The router keeps
its ``of`` outputs and its experts per token; the layer computes the
held experts' part of the result for the tokens routed to them and
leaves the absent experts' terms out. ``vocab_slice`` is the slice of
the vocabulary held here: ids, logits and loss are over the slice.

How a layer is computed lives in ``ops/``, one module a block of numerics
that has arms, constants or a written-out gradient, each taking shapes
and sizes (never ``Settings``) and, where it has more than one arm,
picking its own from what it observes:
``ops/attention.py`` (the causal scores, under a window or not),
``ops/state_space.py`` (the Mamba-2 recurrence's chunked dual form,
``ssd_scan``), ``ops/delta_rule.py`` (the chunked WY form of the delta
rule), ``ops/held_experts.py`` (dropless routing with static shapes over
the held share: the sort into a buffer of ``expert_buffer_rows`` rows,
the tiers of its filled prefix, the rows' movement and the backward pass
written out) and ``ops/grouped_matmul.py`` (the experts' products). The
fp32 islands (router scores, the scan's step sizes, decays and carried
state, the delta rule's decays, solve and state, the rotary angles, RMS
statistics, the loss) are declared in ``analysis/islands.py``.

Precision: the parameters arrive in float32 and each layer casts its
kernels to ``compute_dtype`` where it uses them, inside the layer's
recompute block, so no reduced-precision copy of the whole model stands
beside the float32 one (1.3 GB at the published widths). What the fp32
islands read (norm weights, the router, the scan's step and decay
parameters) is never cast.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from imaginaire_tpu.analysis import islands
from imaginaire_tpu.config import cfg_get
from imaginaire_tpu.ops import delta_rule, held_experts, state_space
from imaginaire_tpu.ops.attention import attention
from imaginaire_tpu.optim.remat import remat_block


def _kernel_init(key, shape, dtype=jnp.float32):
    # (..., fan_in, out): a product's kernel, an expert stack, a
    # depthwise convolution's (taps, channels)
    return jax.random.normal(key, shape, dtype) / math.sqrt(shape[-2])


def rms_norm(x, scale, eps, groups=1):
    """RMSNorm of the last axis in ``groups`` equal parts, statistics in
    float32; the result in ``x``'s dtype."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    scale = scale.astype(jnp.float32)
    with islands.scope("norm_stats"):
        shaped = x32.reshape(*x32.shape[:-1], groups, -1)
        var = jnp.mean(jnp.square(shaped), axis=-1, keepdims=True)
        y = (shaped * lax.rsqrt(var + eps)).reshape(x32.shape) * scale
    return y.astype(dtype)


# ``hidden_act`` -> whether a feed-forward has a gate beside its
# up-product (a gated one's name is its gate's: ``held_experts.GATES``)
GATED = {"relu2": False, **dict.fromkeys(held_experts.GATES, True)}


def feed_forward(x, kernels, gate="silu"):
    """``W_down act(...)`` of ``kernels`` (gate, up, down) or (up, down),
    each cast to ``x``'s dtype where it is used; ``gate`` is the model's
    ``hidden_act``, which a gated one reads."""
    hidden = held_experts.hidden_activation(
        [x @ w.astype(x.dtype) for w in kernels[:-1]], gate)
    return hidden @ kernels[-1].astype(x.dtype)


def _feed_forward_params(module, g, prefix, in_shape, down_shape):
    """A feed-forward's kernels as parameters of ``module``:
    ``<prefix>gate`` (where ``hidden_act`` is gated), ``<prefix>up``,
    ``<prefix>down``."""
    names = ("gate", "up") if GATED[g.hidden_act] else ("up",)
    return (*(module.param(prefix + name, _kernel_init, in_shape)
              for name in names),
            module.param(prefix + "down", _kernel_init, down_shape))


# ----------------------------------------------------------------- Mamba-2


def causal_conv1d(x, kernel, bias):
    """Depthwise causal convolution along axis 1: ``y_t = sum_k
    kernel[k] x_{t-(K-1)+k} + bias``; ``x`` (B, L, C), ``kernel`` (K, C)."""
    k = kernel.shape[0]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    length = x.shape[1]
    out = bias
    for i in range(k):
        out = out + padded[:, i:i + length] * kernel[i]
    return out


class Mamba2Mixer(nn.Module):
    cfg: Any

    @nn.compact
    def __call__(self, u):
        g = self.cfg
        heads, head_dim = g.mamba_num_heads, g.mamba_head_dim
        groups, state = g.n_groups, g.ssm_state_size
        inner = heads * head_dim
        conv_dim = inner + 2 * groups * state
        dtype = u.dtype
        w_in = self.param("in_proj", _kernel_init,
                          (g.hidden_size, inner + conv_dim + heads))
        w_conv = self.param("conv_kernel", _kernel_init,
                            (g.conv_kernel, conv_dim))
        b_conv = self.param("conv_bias", nn.initializers.zeros, (conv_dim,))
        dt_bias = self.param(
            "dt_bias", _dt_bias_init(g.time_step_min, g.time_step_max,
                                     g.time_step_floor), (heads,))
        a_log = self.param("A_log", _a_log_init, (heads,))
        d_skip = self.param("D", nn.initializers.ones, (heads,))
        w_norm = self.param("gate_scale", nn.initializers.ones, (inner,))
        w_out = self.param("out_proj", _kernel_init, (inner, g.hidden_size))

        with jax.named_scope("lm/mamba2/in_proj"):
            zxbcdt = u @ w_in.astype(dtype)
            z, xbc, dt = jnp.split(zxbcdt, [inner, inner + conv_dim], -1)
        with jax.named_scope("lm/mamba2/conv"):
            xbc = jax.nn.silu(causal_conv1d(xbc, w_conv.astype(dtype),
                                            b_conv.astype(dtype)))
            x, b, c = jnp.split(xbc, [inner, inner + groups * state], -1)
        with jax.named_scope("lm/mamba2/ssd_scan"):
            dt32 = dt.astype(jnp.float32)
            bias32 = dt_bias.astype(jnp.float32)
            a32 = a_log.astype(jnp.float32)
            with islands.scope("ssm_scan"):
                dt32 = jax.nn.softplus(dt32 + bias32)
                a32 = -jnp.exp(a32)
            lead = x.shape[:2]
            x = x.reshape(*lead, heads, head_dim)
            y = state_space.ssd_scan(
                x, dt32, a32, b.reshape(*lead, groups, state),
                c.reshape(*lead, groups, state), g.chunk_size)
            y = y + x * d_skip.astype(dtype)[:, None]
            y = y.reshape(*lead, inner)
        with jax.named_scope("lm/mamba2/gate_norm"):
            y = rms_norm(y * jax.nn.silu(z), w_norm, g.norm_eps,
                         groups=groups)
        with jax.named_scope("lm/mamba2/out_proj"):
            return y @ w_out.astype(dtype)


def _dt_bias_init(step_min, step_max, floor):
    def init(key, shape, dtype=jnp.float32):
        lo, hi = math.log(step_min), math.log(step_max)
        dt = jnp.exp(jax.random.uniform(key, shape, dtype, lo, hi))
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))       # softplus^-1
    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


# ------------------------------------------- delta-rule linear attention

# softplus(dt_bias) is drawn as Mamba-2's step size is: log-uniform over
# these two, floored at the third
KDA_TIME_STEP = (1e-3, 1e-1, 1e-4)


def l2_norm(x):
    """``x`` over the norm of its last axis, in float32; the result in
    ``x``'s dtype."""
    x32 = x.astype(jnp.float32)
    with islands.scope("norm_stats"):
        y = x32 * lax.rsqrt(jnp.sum(jnp.square(x32), -1, keepdims=True)
                            + 1e-6)
    return y.astype(x.dtype)


class KDAMixer(nn.Module):
    """Kimi Delta Attention (Kimi Linear, arXiv:2510.26692), a head of
    size ``d``: ``q``, ``k`` the l2-normed and ``v`` the plain ``silu`` of
    a depthwise causal convolution of ``u W``; the log-decay of each key
    channel ``a = -exp(A_log) softplus((u W_f1) W_f2 + dt_bias)``;
    ``beta = 2 sigmoid(u W_b)`` (the 2 is ``kda_allow_neg_eigval``: the
    factor ``I - beta k k^T`` may turn a direction over); ``o`` by
    ``kda_scan``; ``y = (RMSNorm_head(o) * sigmoid((u W_g1) W_g2)) W_o``.
    The decay's and the output gate's projections are low-rank through
    ``d`` (``kda_use_full_proj: false``)."""
    cfg: Any

    @nn.compact
    def __call__(self, u):
        g = self.cfg
        heads, dim = g.kda_num_heads, g.kda_head_dim
        hidden, inner = g.hidden_size, g.kda_num_heads * g.kda_head_dim
        dtype = u.dtype

        def kernel(name, shape):
            return self.param(name, _kernel_init, shape)

        w_qkv = [kernel(n + "_proj", (hidden, inner)) for n in "qkv"]
        w_conv = [kernel(n + "_conv", (g.kda_conv_kernel, inner))
                  for n in "qkv"]
        w_f = kernel("f_a_proj", (hidden, dim)), kernel("f_b_proj",
                                                        (dim, inner))
        dt_bias = self.param("dt_bias", _dt_bias_init(*KDA_TIME_STEP),
                             (inner,))
        a_log = self.param("A_log", _a_log_init, (heads,))
        w_b = kernel("b_proj", (hidden, heads))
        w_g = kernel("g_a_proj", (hidden, dim)), kernel("g_b_proj",
                                                        (dim, inner))
        w_norm = self.param("gate_scale", nn.initializers.ones, (dim,))
        w_o = kernel("o_proj", (inner, hidden))
        lead = u.shape[:2]

        with jax.named_scope("lm/attn/kda_proj"):
            q, k, v = (u @ w.astype(dtype) for w in w_qkv)
            f, gate = ((u @ a.astype(dtype)) @ b.astype(dtype)
                       for a, b in (w_f, w_g))
            beta = u @ w_b.astype(dtype)
        with jax.named_scope("lm/attn/kda_conv"):
            q, k, v = (
                jax.nn.silu(causal_conv1d(
                    x, w.astype(dtype), jnp.zeros((), dtype))).reshape(
                        *lead, heads, dim)
                for x, w in zip((q, k, v), w_conv))
            q, k = l2_norm(q), l2_norm(k)
        with jax.named_scope("lm/attn/kda_scan"):
            f32 = f.astype(jnp.float32)
            beta32 = beta.astype(jnp.float32)
            bias32 = dt_bias.astype(jnp.float32)
            a32 = a_log.astype(jnp.float32)
            with islands.scope("delta_rule"):
                a = (-jnp.exp(a32)[:, None] * jax.nn.softplus(
                    f32 + bias32).reshape(*lead, heads, dim))
                beta32 = 2.0 * jax.nn.sigmoid(beta32)
            o = delta_rule.delta_rule(q, k, v, a, beta32,
                                      g.kda_chunk_size)
        with jax.named_scope("lm/attn/kda_gate_norm"):
            y = (rms_norm(o, w_norm, g.norm_eps)
                 * jax.nn.sigmoid(gate).reshape(o.shape))
        with jax.named_scope("lm/attn/out"):
            return y.reshape(*lead, inner) @ w_o.astype(dtype)


# ------------------------------------------------- gated short convolution


class ShortConvMixer(nn.Module):
    """LFM2's gated short convolution: ``[B, C, x] = u W_in`` (three
    equal parts, in that order), ``z_t = sum_k w_k (B x)_{t-(K-1)+k}``
    (depthwise, causal, ``conv_L_cache`` taps a channel, no bias, no
    activation), ``y = (C z) W_out``; the products between are
    elementwise."""
    cfg: Any

    @nn.compact
    def __call__(self, u):
        g = self.cfg
        hidden = g.hidden_size
        dtype = u.dtype
        w_in = self.param("in_proj", _kernel_init, (hidden, 3 * hidden))
        w_conv = self.param("conv_kernel", _kernel_init,
                            (g.conv_L_cache, hidden))
        w_out = self.param("out_proj", _kernel_init, (hidden, hidden))
        with jax.named_scope("lm/attn/sconv_proj"):
            b, c, x = jnp.split(u @ w_in.astype(dtype), 3, -1)
        with jax.named_scope("lm/attn/sconv_conv"):
            y = c * causal_conv1d(b * x, w_conv.astype(dtype),
                                  jnp.zeros((), dtype))
        with jax.named_scope("lm/attn/out"):
            return y @ w_out.astype(dtype)


# --------------------------------------------------------------- attention


class AttentionMixer(nn.Module):
    """Causal grouped-query attention: ``q``, ``k``, ``v`` by three
    products; where ``use_qk_norm``, ``q`` and ``k`` RMS-normed over the
    head's channels (one learned scale for ``q`` and one for ``k``, which
    the heads share); where there is a ``rope_theta``, both turned by
    ``rotary`` over the whole head; the causal scores at ``1/sqrt(head
    size)``; where ``use_gqa_gate``, a sigmoid gate on the result; then
    ``W_o``. ``windowed`` is the letter ``W``: a query sees the
    ``sliding_window`` keys up to its own, the turn is always taken, and
    the scores stand under ``lm/attn/window_scores``; the letter ``*``
    takes the turn unless ``use_rope_on_full_attention`` is false."""
    cfg: Any
    windowed: bool = False

    @nn.compact
    def __call__(self, u):
        g = self.cfg
        q_dim = g.num_attention_heads * g.head_dim
        kv_dim = g.num_key_value_heads * g.head_dim
        dtype = u.dtype
        w_q = self.param("q_proj", _kernel_init, (g.hidden_size, q_dim))
        w_k = self.param("k_proj", _kernel_init, (g.hidden_size, kv_dim))
        w_v = self.param("v_proj", _kernel_init, (g.hidden_size, kv_dim))
        w_o = self.param("o_proj", _kernel_init, (q_dim, g.hidden_size))
        w_gate = (self.param("gate_proj", _kernel_init,
                             (g.hidden_size, q_dim))
                  if g.use_gqa_gate else None)
        head_scales = ([self.param(name, nn.initializers.ones, (g.head_dim,))
                        for name in ("q_norm_scale", "k_norm_scale")]
                       if g.use_qk_norm else None)
        lead = u.shape[:2]
        with jax.named_scope("lm/attn/qkv"):
            q = (u @ w_q.astype(dtype)).reshape(
                *lead, g.num_attention_heads, g.head_dim)
            k = (u @ w_k.astype(dtype)).reshape(
                *lead, g.num_key_value_heads, g.head_dim)
            v = (u @ w_v.astype(dtype)).reshape(
                *lead, g.num_key_value_heads, g.head_dim)
        if head_scales is not None:
            with jax.named_scope("lm/attn/qk_norm"):
                q, k = (rms_norm(x, scale, g.norm_eps)
                        for x, scale in zip((q, k), head_scales))
        if g.rope_theta is not None and (self.windowed
                                         or g.use_rope_on_full_attention):
            with jax.named_scope("lm/attn/rope"):
                q, k = rotary(q, g.rope_theta), rotary(k, g.rope_theta)
        if self.windowed:
            with jax.named_scope("lm/attn/window_scores"):
                y = attention(q, k, v, g.sliding_window)
        else:
            with jax.named_scope("lm/attn/scores"):
                y = attention(q, k, v)
        if w_gate is not None:
            # one value a head channel (``use_gqa_gate``)
            with jax.named_scope("lm/attn/gate"):
                y = y * jax.nn.sigmoid(u @ w_gate.astype(dtype))
        with jax.named_scope("lm/attn/out"):
            return y @ w_o.astype(dtype)


def rotary(x, theta):
    """Rotary position embedding over the whole last axis of ``x`` (B, L,
    ..., d), the position along axis 1, pairs (i, i + d/2): the pair turns
    by the angle ``t theta^(-2i/d)``. Angles, cosines and the turn in
    float32; the result in ``x``'s dtype."""
    dtype = x.dtype
    length, dim = x.shape[1], x.shape[-1]
    x32 = x.astype(jnp.float32)
    with islands.scope("rotary_angles"):
        inverse = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
        angles = jnp.arange(length, dtype=jnp.float32)[:, None] * inverse
        angles = angles.reshape(1, length, *(1,) * (x.ndim - 3), dim // 2)
        cos, sin = jnp.cos(angles), jnp.sin(angles)
        a, b = jnp.split(x32, 2, axis=-1)
        y = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return y.astype(dtype)


class LatentAttentionMixer(nn.Module):
    """Multi-head latent attention in its decompressed (training) form:
    ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` a head ``[q_nope |
    q_rope]``; ``[c_kv | k_rope] = x W_kva``, ``[k_nope | v]`` a head
    ``= RMSNorm(c_kv) W_kvb``; the rotary turn on each head's ``q_rope``
    and on the one ``k_rope`` every head shares; causal softmax of ``[q_nope
    | q_rope] . [k_nope | k_rope] / sqrt(head size)`` over ``v``."""
    cfg: Any

    @nn.compact
    def __call__(self, u):
        g = self.cfg
        heads, rank = g.num_attention_heads, g.kv_lora_rank
        nope, rope = g.qk_nope_head_dim, g.qk_rope_head_dim
        dtype = u.dtype
        ones = nn.initializers.ones
        w_qa = self.param("q_a_proj", _kernel_init,
                          (g.hidden_size, g.q_lora_rank))
        q_scale = self.param("q_a_scale", ones, (g.q_lora_rank,))
        w_qb = self.param("q_b_proj", _kernel_init,
                          (g.q_lora_rank, heads * (nope + rope)))
        w_kva = self.param("kv_a_proj", _kernel_init,
                           (g.hidden_size, rank + rope))
        kv_scale = self.param("kv_a_scale", ones, (rank,))
        w_kvb = self.param("kv_b_proj", _kernel_init,
                           (rank, heads * (nope + g.v_head_dim)))
        w_o = self.param("o_proj", _kernel_init,
                         (heads * g.v_head_dim, g.hidden_size))
        lead = u.shape[:2]
        with jax.named_scope("lm/attn/q_latent"):
            c_q = rms_norm(u @ w_qa.astype(dtype), q_scale, g.norm_eps)
            q = (c_q @ w_qb.astype(dtype)).reshape(*lead, heads, nope + rope)
        with jax.named_scope("lm/attn/kv_latent"):
            c_kv, k_rope = jnp.split(u @ w_kva.astype(dtype), [rank], -1)
            c_kv = rms_norm(c_kv, kv_scale, g.norm_eps)
            k_nope, v = jnp.split(
                (c_kv @ w_kvb.astype(dtype)).reshape(*lead, heads, -1),
                [nope], -1)
        with jax.named_scope("lm/attn/rope"):
            q_nope, q_rope = jnp.split(q, [nope], -1)
            q = jnp.concatenate([q_nope, rotary(q_rope, g.rope_theta)], -1)
            k_rope = rotary(k_rope[:, :, None], g.rope_theta)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope, (*lead, heads, rope))], -1)
        with jax.named_scope("lm/attn/scores"):
            y = attention(q, k, v)
        with jax.named_scope("lm/attn/out"):
            return y @ w_o.astype(dtype)


# ------------------------------------------------------------ feed-forwards


class DenseMixer(nn.Module):
    cfg: Any

    @nn.compact
    def __call__(self, u):
        g = self.cfg
        kernels = _feed_forward_params(
            self, g, "", (g.hidden_size, g.intermediate_size),
            (g.intermediate_size, g.hidden_size))
        with jax.named_scope("lm/mlp/dense"):
            return feed_forward(u, kernels, g.hidden_act)


# ------------------------------------------------------ mixture of experts


def route(x32, w_router, score_bias, top_k, scaling=None,
          softmax_of_chosen=False):
    """The router, in float32, by one of two scorings. By sigmoid: ``s =
    sigmoid(x W_r)``; the ``top_k`` experts of ``s + score_bias``; their
    weights ``s_i / (sum of the selected s + 1e-20)``, times ``scaling``
    where the model has such a factor. ``softmax_of_chosen`` (the
    published ``moe_primary_router_apply_softmax``): the ``top_k``
    experts by their logit ``z = x W_r``, with no bias (``score_bias``
    None); their weights the softmax over the chosen logits alone, which
    is the softmax over all the experts renormed over the chosen.
    Returns (experts (T, k) int32, weights (T, k) float32). No gradient
    reaches the choice or the bias."""
    biased = {} if score_bias is None else {"b": score_bias}
    islands.guard("router_scores", x=x32, w=w_router, **biased)
    with islands.scope("router_scores"):
        logits = jnp.dot(x32, w_router, precision=lax.Precision.HIGHEST)
        if softmax_of_chosen:
            _, experts = lax.top_k(lax.stop_gradient(logits), top_k)
            weights = jax.nn.softmax(
                jnp.take_along_axis(logits, experts, axis=-1), axis=-1)
        else:
            scores = jax.nn.sigmoid(logits)
            _, experts = lax.top_k(
                lax.stop_gradient(scores + score_bias), top_k)
            picked = jnp.take_along_axis(scores, experts, axis=-1)
            weights = picked / (picked.sum(-1, keepdims=True) + 1e-20)
        if scaling is not None:
            weights = weights * scaling
    return experts.astype(jnp.int32), weights


class MoEMixer(nn.Module):
    """The held experts' part of a mixture of experts, and the shared
    expert where the model has one. The router reads ``router_input``
    where one is given (``use_early_router``: what the layer before it
    read), the experts ``u`` either way. A router scored by the softmax
    over its chosen logits has no bias, and no buffer for one."""
    cfg: Any

    @nn.compact
    def __call__(self, u, router_input=None):
        g = self.cfg
        dtype = u.dtype
        hidden, width = g.hidden_size, g.moe_intermediate_size
        shared_width = g.moe_shared_expert_intermediate_size
        w_router = self.param("router", _kernel_init,
                              (hidden, g.n_routed_experts))
        # the score-correction bias: a buffer, no gradient reaches it and
        # no optimizer moves it (the balancing rule that would is the
        # training recipe's, not the model's)
        score_bias = (
            None if g.moe_primary_router_apply_softmax else self.variable(
                "buffers", "score_bias", jnp.zeros, (g.n_routed_experts,),
                jnp.float32).value)
        kernels = _feed_forward_params(
            self, g, "experts_", (g.held_count, hidden, width),
            (g.held_count, width, hidden))
        # a model without a shared expert gives no width for one
        shared = (_feed_forward_params(
            self, g, "shared_", (hidden, shared_width),
            (shared_width, hidden)) if shared_width is not None else None)
        lead = u.shape[:2]
        x = u.reshape(-1, hidden)
        read = x if router_input is None else router_input.reshape(x.shape)
        with jax.named_scope("lm/moe/router"):
            experts, weights = route(
                read.astype(jnp.float32), w_router.astype(jnp.float32),
                score_bias, g.num_experts_per_tok, g.routed_scaling_factor,
                g.moe_primary_router_apply_softmax)
        capacity = g.expert_buffer_rows
        # the held assignments sort first, so they fill the buffer's
        # prefix: a step that holds no more than the short tier's rows
        # computes on that prefix, any other on the whole buffer
        tiers = held_experts.expert_tiers(
            x.shape[0], g.num_experts_per_tok, g.held_count,
            g.n_routed_experts, capacity)
        with jax.named_scope("lm/moe/dispatch"):
            token, weight, _, group_sizes, stats = held_experts.route_held(
                experts, weights, g.held_first, g.held_count, capacity)
            n_held = stats["held_assignments"]
            stats["compact"] = (n_held <= tiers[0]).astype(jnp.float32)
            stats["moved_rows"] = held_experts.moved_rows(tiers, n_held)
        # the switch stands under no scope: its branches' operations
        # carry their own
        with jax.named_scope("lm/moe/experts"):
            kernels = tuple(w.astype(dtype) for w in kernels)
        routed = held_experts.on_filled_prefix(
            tiers, n_held, x, kernels, weight, token, group_sizes,
            g.hidden_act)
        if shared is None:
            return routed.reshape(*lead, hidden), stats
        with jax.named_scope("lm/moe/shared"):
            shared = feed_forward(x, shared, g.hidden_act)
        return (routed + shared).reshape(*lead, hidden), stats


# ------------------------------------------------------------------- model

_MIXERS = {"M": Mamba2Mixer, "*": AttentionMixer, "K": KDAMixer,
           "C": ShortConvMixer, "-": DenseMixer, "E": MoEMixer,
           "W": functools.partial(AttentionMixer, windowed=True)}
# ``Settings`` field -> the key of the published ``linear_attn_config``
# group it is read from
_LINEAR_ATTN = {"kda_num_heads": "num_heads", "kda_head_dim": "head_dim",
                "kda_conv_kernel": "short_conv_kernel_size"}
# the sizes each letter of a pattern reads; ``*`` reads those of the form
# of attention the config has the sizes of, and grouped-query attention
# turns its heads where there is a ``rope_theta`` (none is demanded); ``W``
# is grouped-query attention alone, under a window, and always turns; an
# ``E`` layer has a shared expert where the config gives
# ``moe_shared_expert_intermediate_size`` and a factor on its routed sum
# where it gives ``routed_scaling_factor``
_LATENT = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
           "qk_rope_head_dim", "v_head_dim", "rope_theta")
_NEEDS = {
    "M": ("mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size",
          "conv_kernel", "chunk_size", "time_step_min", "time_step_max",
          "time_step_floor"),
    "*": ("num_attention_heads",),
    "K": ("kda_num_heads", "kda_head_dim", "kda_conv_kernel",
          "kda_chunk_size"),
    "C": ("conv_L_cache",),
    "W": ("num_attention_heads", "num_key_value_heads", "head_dim",
          "sliding_window", "rope_theta"),
    "-": ("intermediate_size",),
    "E": ("n_routed_experts", "num_experts_per_tok",
          "moe_intermediate_size", "expert_buffer_rows"),
}


def mixer_of(g, kind):
    """The module of a pattern's letter at the sizes ``g``."""
    if kind == "*" and g.kv_lora_rank is not None:
        return LatentAttentionMixer
    return _MIXERS[kind]


def attention_head_dim(g):
    """The head size the causal scores run at."""
    if g.kv_lora_rank is not None:
        return g.qk_nope_head_dim + g.qk_rope_head_dim
    return g.head_dim


def layer_kinds(g):
    """The letters of every layer by its index: the pattern's, then the
    multi-token-prediction module's."""
    return g.pattern + (g.nextn_pattern or "")


class Block(nn.Module):
    """One layer of the pattern: ``h + Mixer(RMSNorm(h))`` or, where
    ``use_post_norm``, ``h + RMSNorm_post(Mixer(RMSNorm(h)))``. Returns
    (h, stats): the expert layer's routing counts, {} for the others.
    Under ``use_early_router`` the attention layer before an expert layer
    ``hands_on`` its normed input ``u``, returning (h, stats, u), and the
    expert layer takes it as ``router_input``: its router reads what
    attention read, its experts their own normed input."""
    cfg: Any
    kind: str
    hands_on: bool = False

    @nn.compact
    def __call__(self, h, router_input=None, training=False):
        scale = self.param("scale", nn.initializers.ones,
                           (self.cfg.hidden_size,))
        with jax.named_scope("lm/block/norm"):
            u = rms_norm(h, scale, self.cfg.norm_eps)
        mixer = mixer_of(self.cfg, self.kind)(self.cfg, name="mixer")
        out = mixer(u) if router_input is None else mixer(u, router_input)
        out, stats = out if self.kind == "E" else (out, {})
        if self.cfg.use_post_norm:
            post_scale = self.param("post_scale", nn.initializers.ones,
                                    (self.cfg.hidden_size,))
            with jax.named_scope("lm/block/post_norm"):
                out = rms_norm(out, post_scale, self.cfg.norm_eps)
        with jax.named_scope("lm/block/residual"):
            h = h + out
        return (h, stats, u) if self.hands_on else (h, stats)


# the tokens whose logits stand at a time in the loss: 1024 x the
# vocabulary slice in float32 (0.16 GB at the widest slice shipped, 37,984)
LOSS_CHUNK_TOKENS = 1024


def chunked_cross_entropy(h, w_head, targets, weights, chunk=None):
    """Sum over tokens of ``weights`` times the cross-entropy of
    ``h W_head`` against ``targets``, in float32, ``chunk`` tokens at a
    time (``LOSS_CHUNK_TOKENS``, or all of them where there are fewer)
    under ``jax.checkpoint``: one chunk's logits stand at a time."""
    tokens = h.shape[0]
    chunk = chunk or min(LOSS_CHUNK_TOKENS, tokens)
    pad = (-tokens) % chunk
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad))
        weights = jnp.pad(weights, (0, pad))
    n = (tokens + pad) // chunk

    @jax.checkpoint
    def one(total, inputs):
        hc, tc, wc = inputs
        logits = jnp.dot(hc, w_head, preferred_element_type=jnp.float32)
        with islands.scope("loss_accumulation"):
            nll = (jax.nn.logsumexp(logits, axis=-1)
                   - jnp.take_along_axis(logits, tc[:, None], -1)[:, 0])
            total = total + jnp.sum(nll * wc)
        return total, None

    total, _ = lax.scan(one, jnp.zeros((), jnp.float32),
                        (h.reshape(n, chunk, -1), targets.reshape(n, chunk),
                         weights.reshape(n, chunk)))
    return total


@dataclasses.dataclass(frozen=True)
class Settings:
    """The model's sizes as the modules read them (hashable: flax turns
    a dict field into a FrozenDict). The names are the published
    config's, but for the held share, the module's layers and loss weight,
    the linear-attention group's three sizes (``_LINEAR_ATTN``), the
    program's own ``use_qk_norm`` (beside the published ``use_gqa_gate``:
    whether a grouped-query layer norms each head's queries and keys),
    ``use_rope_on_full_attention`` (false: the ``*`` layers of a model
    whose ``W`` layers turn take no turn), ``use_post_norm`` (a second
    norm, after each mixer) and ``embed_scale`` (the factor on the
    embedding; absent, none), and
    what the program sets itself (``expert_buffer_rows``, ``remat``,
    ``compute_dtype``). A size the model has no layer
    for stays None (``_NEEDS``): no ``rope_theta`` is grouped-query
    attention without a position embedding, no
    ``moe_shared_expert_intermediate_size`` an expert layer without a
    shared expert, no ``routed_scaling_factor`` a routed sum without a
    factor. ``moe_primary_router_apply_softmax`` is the published key of
    the second scoring (``route``). ``conv_L_cache`` is the taps of the
    sixth letter's
    (``C``) convolution; ``conv_bias`` has to be false."""
    pattern: str
    hidden_size: int
    vocab_slice: int
    norm_eps: float
    remat: str
    compute_dtype: str
    hidden_act: str = "relu2"
    mamba_num_heads: int | None = None
    mamba_head_dim: int | None = None
    n_groups: int | None = None
    ssm_state_size: int | None = None
    conv_kernel: int | None = None
    chunk_size: int | None = None
    time_step_min: float | None = None
    time_step_max: float | None = None
    time_step_floor: float | None = None
    num_attention_heads: int | None = None
    num_key_value_heads: int | None = None
    head_dim: int | None = None
    use_gqa_gate: bool = False
    use_qk_norm: bool = False
    use_rope_on_full_attention: bool = True
    sliding_window: int | None = None
    use_post_norm: bool = False
    embed_scale: float | None = None
    conv_L_cache: int | None = None
    conv_bias: bool = False
    kda_num_heads: int | None = None
    kda_head_dim: int | None = None
    kda_conv_kernel: int | None = None
    kda_chunk_size: int | None = None
    q_lora_rank: int | None = None
    kv_lora_rank: int | None = None
    qk_nope_head_dim: int | None = None
    qk_rope_head_dim: int | None = None
    v_head_dim: int | None = None
    rope_theta: float | None = None
    intermediate_size: int | None = None
    n_routed_experts: int | None = None
    num_experts_per_tok: int | None = None
    routed_scaling_factor: float | None = None
    moe_primary_router_apply_softmax: bool = False
    use_early_router: bool = False
    moe_intermediate_size: int | None = None
    moe_shared_expert_intermediate_size: int | None = None
    held_first: int | None = None
    held_count: int | None = None
    expert_buffer_rows: int | None = None
    nextn_pattern: str | None = None
    nextn_loss_weight: float | None = None


def _held_share(gen_cfg):
    """(first, count) of ``gen.experts_held``, checked against the
    router's width; all of the experts where the key is absent."""
    held = dict(cfg_get(gen_cfg, "experts_held", None) or {})
    of = int(held.get("of", gen_cfg["n_routed_experts"]))
    first, count = int(held.get("first", 0)), int(held.get("count", of))
    if of != int(gen_cfg["n_routed_experts"]) \
            or not 0 <= first <= first + count <= of:
        raise ValueError(
            f"gen.experts_held {held} does not lie inside the router's "
            f"{gen_cfg['n_routed_experts']} experts")
    return first, count


def model_settings(gen_cfg):
    """``Settings`` from the config's ``gen`` section: every layer of the
    pattern has its sizes, and the held share lies inside the router's
    width."""
    given = {f.name: gen_cfg[f.name] for f in dataclasses.fields(Settings)
             if f.name in gen_cfg}
    linear = cfg_get(gen_cfg, "linear_attn_config", None) or {}
    given.update({field: linear[key] for field, key in _LINEAR_ATTN.items()
                  if key in linear})
    given.update(vocab_slice=int(cfg_get(gen_cfg, "vocab_slice", None)
                                 or gen_cfg["vocab_size"]),
                 remat=str(cfg_get(gen_cfg, "remat", "none")),
                 compute_dtype=str(cfg_get(gen_cfg, "compute_dtype",
                                           "float32")))
    kinds = gen_cfg["pattern"] + (given.get("nextn_pattern") or "")
    unknown = set(kinds) - set(_MIXERS)
    if unknown:
        raise ValueError(f"gen.pattern {kinds!r} has layers "
                         f"{sorted(unknown)}; known: {sorted(_MIXERS)}")
    if "E" in kinds and "n_routed_experts" in gen_cfg:
        given["held_first"], given["held_count"] = _held_share(gen_cfg)
    g = Settings(**given)
    if g.hidden_act not in GATED:
        raise ValueError(f"gen.hidden_act {g.hidden_act!r} is not one of "
                         f"{sorted(GATED)}")
    if g.kv_lora_rank is not None and "W" in kinds:
        raise ValueError(
            "a sliding-window layer ('W') is grouped-query attention; "
            "gen.kv_lora_rank makes the model's attention latent")
    needs = {kind: _NEEDS[kind] for kind in set(kinds)}
    if "*" in needs:
        needs["*"] += (_LATENT if g.kv_lora_rank is not None
                       else ("num_key_value_heads", "head_dim"))
    missing = {kind: [n for n in names if getattr(g, n) is None]
               for kind, names in needs.items()}
    if any(missing.values()):
        def where(name):
            return ("gen.linear_attn_config." + _LINEAR_ATTN[name]
                    if name in _LINEAR_ATTN else "gen." + name)

        raise ValueError("gen.pattern's layers lack their sizes: " + "; ".join(
            f"{kind!r} needs {', '.join(map(where, names))}"
            for kind, names in sorted(missing.items()) if names))
    if g.kv_lora_rank is not None and "*" in kinds \
            and g.qk_nope_head_dim + g.qk_rope_head_dim != g.v_head_dim:
        raise ValueError(
            "the causal scores run at one head size: gen.qk_nope_head_dim + "
            f"gen.qk_rope_head_dim is {attention_head_dim(g)}, "
            f"gen.v_head_dim {g.v_head_dim}")
    if g.nextn_pattern and g.nextn_loss_weight is None:
        raise ValueError("gen.nextn_pattern needs gen.nextn_loss_weight")
    if g.use_early_router:
        for letters in (g.pattern, g.nextn_pattern or ""):
            orphans = [at for at, kind in enumerate(letters) if kind == "E"
                       and letters[max(at - 1, 0):at] not in ("*", "W")]
            if orphans:
                raise ValueError(
                    "gen.use_early_router: an expert layer's router reads "
                    "the normed input of the attention layer ('*' or 'W') "
                    f"before it; {letters!r} has none before the 'E' at "
                    f"{orphans}")
    if g.conv_bias and "C" in kinds:
        raise ValueError(
            "gen.conv_bias true: the gated short convolution ('C') has no "
            "bias here; no model this program has run has one")
    return g


class Generator(nn.Module):
    """``data["tokens"]`` (B, L) int32 -> {"loss": the mean next-token
    cross-entropy over the L-1 targets of each sequence, "mtp_loss": where
    the model has the module, the mean cross-entropy of its logits against
    the token after the next over the L-2 targets, "moe/<layer>/<stat>":
    each expert layer's routing counts}."""
    gen_cfg: Any = None
    data_cfg: Any = None

    @nn.compact
    def __call__(self, data, training=False):
        g = model_settings(self.gen_cfg)
        tokens = data["tokens"]
        embedding = self.param("embedding", nn.initializers.normal(1.0),
                               (g.vocab_slice, g.hidden_size))
        final_scale = self.param("final_scale", nn.initializers.ones,
                                 (g.hidden_size,))
        w_head = self.param("head", _kernel_init,
                            (g.hidden_size, g.vocab_slice))
        dtype = jnp.dtype(g.compute_dtype)
        out = {}

        def layers(h, kinds, first):
            handed = ()
            for at, kind in enumerate(kinds):
                index = first + at
                # what an early router reads goes from the layer that
                # made it to the expert layer behind it, and no further
                hands_on = g.use_early_router and kinds[at + 1:at + 2] == "E"
                block = remat_block(
                    Block, g.remat, where="gen.remat", cfg=g, kind=kind,
                    hands_on=hands_on, name=f"layer_{index}")
                h, stats, *handed = block(h, *handed, training=training)
                for key, value in stats.items():
                    out[f"moe/{index}/{key}"] = value
            return h

        def head_loss(h, scale, ahead):
            """(mean cross-entropy of position t's logits against token
            t + ``ahead``, the normed ``h``)."""
            with jax.named_scope("lm/final_norm"):
                h = rms_norm(h, scale, g.norm_eps)
            targets = jnp.roll(tokens, -ahead, axis=1).reshape(-1)
            weights = jnp.ones(tokens.shape, jnp.float32)
            for last in range(1, ahead + 1):   # these have no such token
                weights = weights.at[:, -last].set(0.0)
            total = chunked_cross_entropy(
                h.reshape(-1, g.hidden_size), w_head.astype(dtype), targets,
                weights.reshape(-1))
            return total / weights.sum(), h

        def embed(ids):
            rows = embedding[ids]
            if g.embed_scale is not None:
                rows = rows * g.embed_scale
            return rows.astype(dtype)

        with jax.named_scope("lm/embed"):
            h = embed(tokens)
        h = layers(h, g.pattern, 0)
        with jax.named_scope("lm/head_loss"):
            out["loss"], h = head_loss(h, final_scale, 1)
        if not g.nextn_pattern:
            return out
        ones = nn.initializers.ones
        e_scale = self.param("mtp_embed_scale", ones, (g.hidden_size,))
        h_scale = self.param("mtp_hidden_scale", ones, (g.hidden_size,))
        w_merge = self.param("mtp_merge", _kernel_init,
                             (2 * g.hidden_size, g.hidden_size))
        mtp_scale = self.param("mtp_final_scale", ones, (g.hidden_size,))
        with jax.named_scope("lm/mtp/merge"):
            # position i: its final hidden state beside token i+1. The
            # last position has no such token and is given the roll's;
            # nothing reads it: attention is causal and its two targets
            # weigh nothing
            ahead = embed(jnp.roll(tokens, -1, axis=1))
            merged = jnp.concatenate(
                [rms_norm(ahead, e_scale, g.norm_eps),
                 rms_norm(h, h_scale, g.norm_eps)], -1) @ w_merge.astype(dtype)
        h = layers(merged, g.nextn_pattern, len(g.pattern))
        with jax.named_scope("lm/head_loss"):
            out["mtp_loss"], _ = head_loss(h, mtp_scale, 2)
        return out
