"""Hybrid token model: Mamba-2 mixers, sparse grouped-query attention and
mixture-of-experts layers, laid out by a pattern string (Nemotron-H,
``hybrid_override_pattern``: ``M`` a Mamba-2 mixer, ``*`` attention,
``E`` a mixture of experts).

Every layer is one mixer behind a pre-norm residual,
``h = h + Mixer(RMSNorm(h))``; logits are ``RMSNorm(h) W_head``. The
module returns the mean next-token cross-entropy itself, over token
chunks, so the (tokens, vocabulary) logits never stand whole.

A share of an expert-parallel deployment: ``experts_held`` names the
routed experts this chip holds (``{first, count, of}``). The router keeps
its ``of`` outputs and its experts per token; the layer computes the
held experts' part of the result for the tokens routed to them and
leaves the absent experts' terms out. ``vocab_slice`` is the slice of
the vocabulary held here: ids, logits and loss are over the slice.

The numerics are plain ``jax.numpy``/``lax`` but for attention: the
chunked state-space dual form of the Mamba-2 recurrence (``ssd_scan``),
and dropless routing with static shapes (``route_held``: sort the
assignments by expert, the held ones first, into a buffer of
``expert_buffer_rows`` rows, two grouped products by ``lax.ragged_dot``,
scatter back weighted). ``expert_buffer_rows`` is the buffer's capacity,
what the largest routing may hold; a step computes the filled prefix of
it (``on_filled_prefix``: a row a token where its held assignments fit
that, the whole buffer otherwise, the same arithmetic either way).
Causal grouped-query attention lives in ``ops/attention.py`` and picks
its own arm from what it observes: one fused Pallas kernel that keeps the
scores in VMEM where the backend is a TPU, the head size a multiple of
128 and the length a multiple of the kernel's tiles; query blocks of
``attn_query_block`` rows in plain ``jax.numpy`` everywhere else (the
CPU, ragged lengths). The fp32 islands (router scores, the scan's step
sizes, decays and carried state, RMS statistics, the loss) are declared
in ``analysis/islands.py``.

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


def relu2(x):
    return jnp.square(jax.nn.relu(x))


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


def ssd_scan(x, dt, a, b, c, chunk):
    """The Mamba-2 recurrence, per head with state ``S`` (P, N):

        S_t = exp(dt_t a) S_{t-1} + dt_t x_t b_t^T,    y_t = S_t c_t

    evaluated in chunks of ``chunk`` steps: within a chunk by the masked
    ``c b^T`` product, across chunks by the carried state. ``x``
    (B, L, H, P) and ``b``, ``c`` (B, L, G, N) in the compute dtype (head
    ``h`` reads group ``h // (H/G)``); ``dt`` (B, L, H) and ``a`` (H,)
    float32. Step sizes, decays and the carried state stay float32.
    Returns ``y`` (B, L, H, P) in ``x``'s dtype. A length that the chunk
    does not divide is padded with steps of size zero."""
    islands.guard("ssm_scan", dt=dt, a=a)
    bsz, length, heads, _ = x.shape
    groups = b.shape[2]
    per = heads // groups
    pad = (-length) % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    n = (length + pad) // chunk
    dtype = x.dtype

    def chunked(v):
        return v.reshape(bsz, n, chunk, *v.shape[2:])

    x, dt, b, c = chunked(x), chunked(dt), chunked(b), chunked(c)
    x32 = x.astype(jnp.float32)
    with islands.scope("ssm_scan"):
        cum = jnp.cumsum(dt * a, axis=2).swapaxes(2, 3)   # (B, n, H, Q)
        # decay from step s to step l of one chunk, l >= s
        tril = jnp.tril(jnp.ones((chunk, chunk), bool))
        within = jnp.exp(jnp.where(
            tril, cum[..., :, None] - cum[..., None, :], -jnp.inf))
        to_end = jnp.exp(cum[..., -1:] - cum).swapaxes(2, 3)  # (B, n, Q, H)
        from_start = jnp.exp(cum).swapaxes(2, 3)              # (B, n, Q, H)
        chunk_decay = jnp.exp(cum[..., -1])                   # (B, n, H)
        xdt32 = x32 * dt[..., None]
        decayed32 = xdt32 * to_end[..., None]

    def grouped(v):                        # (B, n, Q, H, P) -> (.., G, per, P)
        return v.reshape(*v.shape[:3], groups, per, v.shape[-1])

    xdt = grouped(xdt32.astype(dtype))
    decayed = grouped(decayed32.astype(dtype))
    # within a chunk: (c_l . b_s) decay(l, s) dt_s x_s, summed over s <= l
    cb = jnp.einsum("bnlgk,bnsgk->bngls", c, b,
                    preferred_element_type=jnp.float32)
    within = within.reshape(bsz, n, groups, per, chunk, chunk)
    weights = (cb[:, :, :, None] * within).astype(dtype)
    y = jnp.einsum("bngrls,bnsgrp->bnlgrp", weights, xdt,
                   preferred_element_type=jnp.float32)
    # what each chunk adds to the state by its end
    added = jnp.einsum("bnsgrp,bnsgk->bngrpk", decayed, b,
                       preferred_element_type=jnp.float32)
    with islands.scope("ssm_scan"):
        def carry(state, inputs):
            decay, add = inputs
            return state * decay[..., None, None] + add, state

        added = added.reshape(bsz, n, heads, *added.shape[-2:])
        _, before = lax.scan(carry, jnp.zeros_like(added[:, 0]),
                             (chunk_decay.swapaxes(0, 1),
                              added.swapaxes(0, 1)))
        before = before.swapaxes(0, 1)                 # (B, n, H, P, N)
    # the state carried into the chunk, read at each of its steps
    before = before.reshape(bsz, n, groups, per, *before.shape[-2:])
    read = jnp.einsum("bnlgk,bngrpk->bnlgrp", c, before.astype(dtype),
                      preferred_element_type=jnp.float32)
    with islands.scope("ssm_scan"):
        y = y + read * from_start.reshape(bsz, n, chunk, groups, per, 1)
    y = y.reshape(bsz, n * chunk, heads, -1)[:, :length]
    return y.astype(dtype)


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
        dt_bias = self.param("dt_bias", _dt_bias_init(g), (heads,))
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
            y = ssd_scan(x, dt32, a32,
                         b.reshape(*lead, groups, state),
                         c.reshape(*lead, groups, state), g.chunk_size)
            y = y + x * d_skip.astype(dtype)[:, None]
            y = y.reshape(*lead, inner)
        with jax.named_scope("lm/mamba2/gate_norm"):
            y = rms_norm(y * jax.nn.silu(z), w_norm, g.norm_eps,
                         groups=groups)
        with jax.named_scope("lm/mamba2/out_proj"):
            return y @ w_out.astype(dtype)


def _dt_bias_init(g):
    def init(key, shape, dtype=jnp.float32):
        lo, hi = math.log(g.time_step_min), math.log(g.time_step_max)
        dt = jnp.exp(jax.random.uniform(key, shape, dtype, lo, hi))
        dt = jnp.maximum(dt, g.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))       # softplus^-1
    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


# --------------------------------------------------------------- attention


class AttentionMixer(nn.Module):
    cfg: Any

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
        lead = u.shape[:2]
        with jax.named_scope("lm/attn/qkv"):
            q = (u @ w_q.astype(dtype)).reshape(
                *lead, g.num_attention_heads, g.head_dim)
            k = (u @ w_k.astype(dtype)).reshape(
                *lead, g.num_key_value_heads, g.head_dim)
            v = (u @ w_v.astype(dtype)).reshape(
                *lead, g.num_key_value_heads, g.head_dim)
        with jax.named_scope("lm/attn/scores"):
            y = attention(q, k, v, g.attn_query_block)
        with jax.named_scope("lm/attn/out"):
            return y @ w_o.astype(dtype)


# ------------------------------------------------------ mixture of experts


def route(x32, w_router, score_bias, top_k, scaling):
    """The router, in float32: ``s = sigmoid(x W_r)``; the ``top_k``
    experts of ``s + score_bias``; their weights ``s_i / (sum of the
    selected s + 1e-20) * scaling``. Returns (experts (T, k) int32,
    weights (T, k) float32). No gradient reaches the choice or the bias."""
    islands.guard("router_scores", x=x32, w=w_router, b=score_bias)
    with islands.scope("router_scores"):
        scores = jax.nn.sigmoid(jnp.dot(x32, w_router,
                                        precision=lax.Precision.HIGHEST))
        _, experts = lax.top_k(lax.stop_gradient(scores + score_bias), top_k)
        picked = jnp.take_along_axis(scores, experts, axis=-1)
        weights = picked / (picked.sum(-1, keepdims=True) + 1e-20) * scaling
    return experts.astype(jnp.int32), weights


def route_held(experts, weights, first, count, rows):
    """The held experts' assignments, sorted by expert, in a buffer of
    ``rows`` rows. Returns (token (rows,) int32: the token of each row;
    weight (rows,) float32: its routing weight, 0 on rows no assignment
    fills; valid (rows,) bool: the rows one fills; group_sizes (count,)
    int32: rows of each held expert, as the buffer holds them; stats:
    ``held_assignments``, ``overflow`` (held assignments the buffer has
    no row for), ``load_max_over_mean`` over the held experts,
    ``buffer_occupancy``)."""
    tokens, top_k = experts.shape
    local = (experts - first).reshape(-1)
    held = (local >= 0) & (local < count)
    local = jnp.where(held, local, count)            # the others sort last
    order = jnp.argsort(local, stable=True)[:rows]
    sorted_local = local[order]
    valid = sorted_local < count
    token = (order // top_k).astype(jnp.int32)
    weight = jnp.where(valid, weights.reshape(-1)[order], 0.0)
    sizes = jnp.bincount(local, length=count + 1)[:count]
    n_held = sizes.sum()
    ends = jnp.minimum(jnp.cumsum(sizes), rows)
    group_sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
    mean = jnp.maximum(n_held, 1) / count
    stats = {
        "held_assignments": n_held,
        "overflow": jnp.maximum(n_held - rows, 0),
        "load_max_over_mean": sizes.max() / mean,
        "buffer_occupancy": n_held / rows,
    }
    stats = {k: lax.stop_gradient(v).astype(jnp.float32)
             for k, v in stats.items()}
    return token, weight, valid, group_sizes, stats


def held_experts_part(x, w_up, w_down, weight, token, valid, group_sizes,
                      rows):
    """The held experts' part of the layer's result, computed on the first
    ``rows`` rows of ``route_held``'s buffer: all of it where the step
    holds no more than ``rows`` assignments, since the rows past the held
    ones are masked to zero wherever they are read. ``x`` (T, hidden) and
    the kernels (count, hidden, width), (count, width, hidden) in the
    compute dtype."""
    token, weight = token[:rows], weight[:rows]
    # a row past the groups' end is not the grouped products' to write,
    # forward or backward: whatever stands there is masked on the way in
    # (its gradient is the first product's), between the two and on the
    # way out
    mask = valid[:rows, None]
    with jax.named_scope("lm/moe/dispatch"):
        filled = jnp.where(mask, x[token], 0)
    with jax.named_scope("lm/moe/experts"):
        up = lax.ragged_dot(filled, w_up, group_sizes)
        act = relu2(jnp.where(mask, up, 0))
        out = lax.ragged_dot(act, w_down, group_sizes)
        out = jnp.where(mask, out, 0)
    with jax.named_scope("lm/moe/combine"):
        out = out.astype(jnp.float32) * weight[:, None]
        routed = jnp.zeros(x.shape, jnp.float32).at[token].add(out)
        return routed.astype(x.dtype)


def _tier(tiers, n_held):
    """The first of the ascending ``tiers`` with ``n_held`` rows or more
    (the last, if none has)."""
    return sum((n_held > rows).astype(jnp.int32) for rows in tiers[:-1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def on_filled_prefix(tiers, n_held, x, w_up, w_down, weight, *placed):
    """``held_experts_part`` on the shortest of the static, ascending
    ``tiers`` of rows that holds the step's ``n_held`` assignments, by
    ``lax.switch``. Its gradient is each tier's own, recomputed inside
    the backward branch: differentiating through the switch instead hands
    every tier's intermediates from a forward conditional to a backward
    one, and a step on the short tier would write the long tier's as
    zeros (1.5 GB a layer at the published widths)."""
    return lax.switch(
        _tier(tiers, n_held),
        [functools.partial(held_experts_part, rows=rows) for rows in tiers],
        x, w_up, w_down, weight, *placed)


def _on_filled_prefix_fwd(tiers, n_held, *operands):
    return on_filled_prefix(tiers, n_held, *operands), (n_held, operands)


def _on_filled_prefix_bwd(tiers, saved, ct):
    n_held, (*floats, token, valid, group_sizes) = saved

    def back(rows):
        part = functools.partial(held_experts_part, token=token, valid=valid,
                                 group_sizes=group_sizes, rows=rows)
        return lambda ct, *floats: jax.vjp(part, *floats)[1](ct)

    grads = lax.switch(_tier(tiers, n_held), [back(rows) for rows in tiers],
                       ct, *floats)
    # the kernels' gradients leave the switch in the compute dtype: left
    # to itself the compiler moves their casts to float32 into the
    # branches, and eight leaves of twice the size stand until the
    # optimizer's pass (2 GB of temporaries at the published widths)
    return (None, *lax.optimization_barrier(grads), None, None, None)


on_filled_prefix.defvjp(_on_filled_prefix_fwd, _on_filled_prefix_bwd)


class MoEMixer(nn.Module):
    cfg: Any

    @nn.compact
    def __call__(self, u):
        g = self.cfg
        dtype = u.dtype
        hidden, width = g.hidden_size, g.moe_intermediate_size
        shared_width = g.moe_shared_expert_intermediate_size
        w_router = self.param("router", _kernel_init,
                              (hidden, g.n_routed_experts))
        # the score-correction bias: a buffer, no gradient reaches it and
        # no optimizer moves it (the balancing rule that would is the
        # training recipe's, not the model's)
        score_bias = self.variable("buffers", "score_bias", jnp.zeros,
                                   (g.n_routed_experts,), jnp.float32).value
        w_up = self.param("experts_up", _kernel_init,
                          (g.held_count, hidden, width))
        w_down = self.param("experts_down", _kernel_init,
                            (g.held_count, width, hidden))
        s_up = self.param("shared_up", _kernel_init, (hidden, shared_width))
        s_down = self.param("shared_down", _kernel_init,
                            (shared_width, hidden))
        lead = u.shape[:2]
        x = u.reshape(-1, hidden)
        with jax.named_scope("lm/moe/router"):
            experts, weights = route(
                x.astype(jnp.float32), w_router.astype(jnp.float32),
                score_bias, g.num_experts_per_tok, g.routed_scaling_factor)
        capacity = g.expert_buffer_rows
        # the held assignments sort first, so they fill the buffer's
        # prefix: a step that holds no more than a row a token computes
        # on that prefix, any other on the whole buffer
        tiers = tuple(sorted({min(x.shape[0], capacity), capacity}))
        with jax.named_scope("lm/moe/dispatch"):
            token, weight, valid, group_sizes, stats = route_held(
                experts, weights, g.held_first, g.held_count, capacity)
            n_held = stats["held_assignments"]
            stats["compact"] = (n_held <= tiers[0]).astype(jnp.float32)
        # the switch stands under no scope: its branches' operations
        # carry their own
        with jax.named_scope("lm/moe/experts"):
            w_up, w_down = w_up.astype(dtype), w_down.astype(dtype)
        routed = on_filled_prefix(tiers, n_held, x, w_up, w_down, weight,
                                  token, valid, group_sizes)
        with jax.named_scope("lm/moe/shared"):
            shared = relu2(x @ s_up.astype(dtype)) @ s_down.astype(dtype)
        return (routed + shared).reshape(*lead, hidden), stats


# ------------------------------------------------------------------- model

_MIXERS = {"M": Mamba2Mixer, "*": AttentionMixer, "E": MoEMixer}


class Block(nn.Module):
    """One layer of the pattern: ``h + Mixer(RMSNorm(h))``. Returns
    (h, stats): the expert layer's routing counts, {} for the others."""
    cfg: Any
    kind: str

    @nn.compact
    def __call__(self, h, training=False):
        scale = self.param("scale", nn.initializers.ones,
                           (self.cfg.hidden_size,))
        u = rms_norm(h, scale, self.cfg.norm_eps)
        out = _MIXERS[self.kind](self.cfg, name="mixer")(u)
        out, stats = out if self.kind == "E" else (out, {})
        return h + out, stats


def chunked_cross_entropy(h, w_head, targets, weights, chunk):
    """Sum over tokens of ``weights`` times the cross-entropy of
    ``h W_head`` against ``targets``, in float32, ``chunk`` tokens at a
    time under ``jax.checkpoint``: one chunk's logits stand at a time."""
    tokens = h.shape[0]
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
    config's, but for the held share and the three bounds the program
    sets itself."""
    pattern: str
    hidden_size: int
    vocab_slice: int
    norm_eps: float
    mamba_num_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state_size: int
    conv_kernel: int
    chunk_size: int
    time_step_min: float
    time_step_max: float
    time_step_floor: float
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    n_routed_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    moe_intermediate_size: int
    moe_shared_expert_intermediate_size: int
    held_first: int
    held_count: int
    expert_buffer_rows: int
    attn_query_block: int
    loss_chunk_tokens: int
    remat: str
    compute_dtype: str


def model_settings(gen_cfg):
    """``Settings`` from the config's ``gen`` section, with the held
    share checked against the router's width."""
    held = dict(cfg_get(gen_cfg, "experts_held", None) or {})
    of = int(held.get("of", gen_cfg["n_routed_experts"]))
    first, count = int(held.get("first", 0)), int(held.get("count", of))
    if of != int(gen_cfg["n_routed_experts"]) \
            or not 0 <= first <= first + count <= of:
        raise ValueError(
            f"gen.experts_held {held} does not lie inside the router's "
            f"{gen_cfg['n_routed_experts']} experts")
    unknown = set(gen_cfg["pattern"]) - set(_MIXERS)
    if unknown:
        raise ValueError(f"gen.pattern {gen_cfg['pattern']!r} has layers "
                         f"{sorted(unknown)}; known: {sorted(_MIXERS)}")
    given = {f.name: gen_cfg[f.name] for f in dataclasses.fields(Settings)
             if f.name in gen_cfg}
    given.update(held_first=first, held_count=count,
                 vocab_slice=int(cfg_get(gen_cfg, "vocab_slice", None)
                                 or gen_cfg["vocab_size"]),
                 remat=str(cfg_get(gen_cfg, "remat", "none")),
                 compute_dtype=str(cfg_get(gen_cfg, "compute_dtype",
                                           "float32")))
    return Settings(**given)


class Generator(nn.Module):
    """``data["tokens"]`` (B, L) int32 -> {"loss": the mean next-token
    cross-entropy over the L-1 targets of each sequence, "moe/<layer>/
    <stat>": each expert layer's routing counts}."""
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
        with jax.named_scope("lm/embed"):
            h = embedding[tokens].astype(dtype)
        out = {}
        for index, kind in enumerate(g.pattern):
            block = remat_block(Block, g.remat, where="gen.remat", cfg=g,
                                kind=kind, name=f"layer_{index}")
            h, stats = block(h, training=training)
            for key, value in stats.items():
                out[f"moe/{index}/{key}"] = value
        with jax.named_scope("lm/head_loss"):
            h = rms_norm(h, final_scale, g.norm_eps)
            # position t's logits against token t+1; the last has none
            targets = jnp.roll(tokens, -1, axis=1).reshape(-1)
            weights = jnp.ones(tokens.shape, jnp.float32).at[:, -1].set(0.0)
            total = chunked_cross_entropy(
                h.reshape(-1, g.hidden_size), w_head.astype(dtype), targets,
                weights.reshape(-1), g.loss_chunk_tokens)
            out["loss"] = total / weights.sum()
        return out
