"""Plain reference of the LFM2 mixture-of-experts token model (`lfm2_moe`)
in training: forward, loss, gradients, Adam. Plain `jax.numpy`, float32,
every product at HIGHEST precision, no kernel and no expert buffer (a
layer stands under `jax.checkpoint` only so that the float32 step fits
one chip at the published widths: the arithmetic is the same); imports
nothing of the program (the router's scores and choice, the norm, Adam
and the rounding are `nemotron_h_train.py`'s, the gated feed-forward and
the rotary turn `glm4_moe_lite_train.py`'s, which are this model's too).

`h_0 = E[ids]`; every layer of the model is `h = h + Operator(RMSNorm(h))`
then `h = h + FeedForward(RMSNorm(h))`, two letters of the pattern (`C-`
a leading layer, `*E` the attention layer of a period, `CE` its three
others); logits `RMSNorm(h; w_f) W_head`; the loss is the mean next-token
cross-entropy over each sequence's L - 1 targets.

  C  Gated short convolution. `[B, C, x] = u W_in` (three equal parts, in
     that order); `z_t = sum_{k=0..K-1} w_k (.) (B (.) x)_{t-(K-1)+k}`
     (depthwise, causal, K = `conv_L_cache` taps a channel, no bias, no
     activation); `y = (C (.) z) W_out`. `(.)` is the elementwise product.
  *  Causal grouped-query attention. `q, k, v = u W_q, u W_k, u W_v`; `q`
     and `k` RMS-normed over the head's channels with a learned scale (one
     for `q`, one for `k`, shared by the heads); the rotary turn over the
     whole head, pairs (i, i + d/2), angle `t theta^(-2i/d)`; softmax of
     `q . k / sqrt(d)` over the keys up to the query's own, by query
     blocks so that the scores fit; `W_o`. No bias anywhere.
  -  Dense feed-forward `W_down (silu(x W_gate) * x W_up)`.
  E  Mixture of experts with no shared expert. Router in float32: `s =
     sigmoid(x W_r)`, the top k of `s + expert_bias`, weights `s_i / (sum
     of the selected s + 1e-20) * routed_scaling_factor` (the family's
     public code adds 1e-6: 5e-7 of a sum near 2, under float32's step
     there); every expert gated as the dense layer is; `out = sum over the
     selected experts HELD HERE of w_i f_i(x)`, each held expert computed
     densely over all tokens and masked. The absent experts' terms are
     left out.

`precision`: "float32" (the reference), "bfloat16" (a witness) or "float8"
(the control: what enters every product rounded to e4m3). The router, the
head norm, the rotary turn, the block norms and the loss are float32 in
all three, as they are the program's fp32 islands.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.glm4_moe_lite_train import (  # noqa: F401
    dense, expert_work, gated, rotary)
from benchmark.reference.nemotron_h_train import (  # noqa: F401
    QUERY_BLOCK, adam, attn_work, product, rms_norm, routing, split)


# ------------------------------------------------------------------ layers


def short_conv(p, prefix, sizes, u, precision):
    length = u.shape[1]
    b, c, x = jnp.split(
        product("blh,hf->blf", u, p[prefix + "in_proj"], precision), 3, -1)
    kernel = p[prefix + "conv_kernel"]
    taps = kernel.shape[0]
    shifted = jnp.pad(b * x, ((0, 0), (taps - 1, 0), (0, 0)))
    z = sum(shifted[:, i:i + length] * kernel[i] for i in range(taps))
    return product("blh,hf->blf", c * z, p[prefix + "out_proj"], precision)


def attention(p, prefix, sizes, u, precision):
    q_heads, kv_heads = (sizes["num_attention_heads"],
                         sizes["num_key_value_heads"])
    dim, eps = sizes["head_dim"], sizes["norm_eps"]
    bsz, length, _ = u.shape
    q = product("blh,hf->blf", u, p[prefix + "q_proj"], precision).reshape(
        bsz, length, q_heads, dim)
    k = product("blh,hf->blf", u, p[prefix + "k_proj"], precision).reshape(
        bsz, length, kv_heads, dim)
    v = product("blh,hf->blf", u, p[prefix + "v_proj"], precision).reshape(
        bsz, length, kv_heads, dim)
    if sizes.get("use_qk_norm"):
        q = rms_norm(q, p[prefix + "q_norm_scale"], eps)
        k = rms_norm(k, p[prefix + "k_norm_scale"], eps)
    if sizes.get("rope_theta") is not None:
        q, k = rotary(q, sizes["rope_theta"]), rotary(k, sizes["rope_theta"])
    q = q.reshape(bsz, length, kv_heads, q_heads // kv_heads, dim)

    @jax.checkpoint
    def rows(inputs):
        qb, start = inputs
        s = product("bqgrd,bkgd->bgrqk", qb, k, precision) / math.sqrt(dim)
        pos = start + jnp.arange(qb.shape[1])[:, None]
        s = jnp.where(pos >= jnp.arange(length)[None, :], s, -jnp.inf)
        return product("bgrqk,bkgd->bqgrd", jax.nn.softmax(s, -1), v,
                       precision)

    # one block of query rows after another (a loop, so that one block's
    # scores stand at a time), each against all the keys, masked
    block = min(QUERY_BLOCK, length)
    pad = (-length) % block
    blocks = jnp.pad(q, ((0, 0), (0, pad)) + ((0, 0),) * 3).reshape(
        bsz, -1, block, *q.shape[2:]).swapaxes(0, 1)
    out = lax.map(rows, (blocks, jnp.arange(blocks.shape[0]) * block))
    out = out.swapaxes(0, 1).reshape(bsz, length + pad, -1)[:, :length]
    return product("blf,fh->blh", out, p[prefix + "o_proj"], precision)


def moe(p, prefix, sizes, u, precision, tie_margin):
    held = sizes["experts_held"]
    first, count = held["first"], held["count"]
    x = u.reshape(-1, u.shape[-1])
    gate, margin, edge = routing(p, prefix, sizes, x)
    out = jnp.zeros_like(x)
    for e in range(count):
        out = out + gate[:, first + e, None] * gated(
            p, prefix + "experts_", x, precision, expert=e)
    here = (edge >= first) & (edge < first + count)
    aux = {"held_assignments": jnp.sum(gate[:, first:first + count] > 0),
           # a token whose choice between a held expert and another (or
           # between two, one of them held) hangs on less than the margin
           "ties": jnp.sum((margin < tie_margin) & (here[:, 0] ^ here[:, 1]))}
    return out.reshape(u.shape), aux


_MIXERS = {"C": short_conv, "*": attention, "-": dense}


def loss(train, buffers, sizes, tokens, precision="float32",
         tie_margin=0.0):
    """(mean next-token cross-entropy, {layer index: routing counts}) of
    `tokens` (B, L) int32; `train` the trainable parameters, `buffers`
    the routers' expert biases."""
    p = {**train, **buffers}
    h = p["embedding"][tokens]
    aux = {}
    for index, kind in enumerate(sizes["pattern"]):
        prefix = f"layer_{index}/mixer/"

        def layer(h, p, kind=kind, prefix=prefix, index=index):
            u = rms_norm(h, p[f"layer_{index}/scale"], sizes["norm_eps"])
            if kind == "E":
                out, counts = moe(p, prefix, sizes, u, precision, tie_margin)
                return h + out, counts
            return h + _MIXERS[kind](p, prefix, sizes, u, precision), {}

        h, counts = jax.checkpoint(layer)(h, p)
        if counts:
            aux[index] = counts

    @jax.checkpoint
    def head(h, p):
        h = rms_norm(h, p["final_scale"], sizes["norm_eps"])
        logits = product("blh,hv->blv", h, p["head"], precision)
        picked = jnp.take_along_axis(logits[:, :-1],
                                     tokens[:, 1:, None], -1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(logits[:, :-1], -1) - picked)

    return head(h, p), aux


# ------------------------------------------------------------------- sizes


def layer_kinds(sizes):
    return sizes["pattern"]


def spec(sizes):
    """{name: (shape, kind)} of every parameter and buffer at `sizes`;
    the names are the program's paths below `params` / `buffers`."""
    hidden, vocab = sizes["hidden_size"], sizes["vocab_slice"]
    dim = sizes["head_dim"]
    q_dim = sizes["num_attention_heads"] * dim
    kv_dim = sizes["num_key_value_heads"] * dim
    wide = sizes["intermediate_size"]
    width = sizes["moe_intermediate_size"]
    held, experts = sizes["experts_held"]["count"], sizes["n_routed_experts"]
    out = {"embedding": ((vocab, hidden), "embedding"),
           "final_scale": ((hidden,), "ones"),
           "head": ((hidden, vocab), "kernel")}
    head_norm = ({"q_norm_scale": ((dim,), "ones"),
                  "k_norm_scale": ((dim,), "ones")}
                 if sizes.get("use_qk_norm") else {})
    kinds = {
        "C": {"in_proj": ((hidden, 3 * hidden), "kernel"),
              "conv_kernel": ((sizes["conv_L_cache"], hidden), "kernel"),
              "out_proj": ((hidden, hidden), "kernel")},
        "*": {"q_proj": ((hidden, q_dim), "kernel"),
              "k_proj": ((hidden, kv_dim), "kernel"),
              "v_proj": ((hidden, kv_dim), "kernel"),
              **head_norm,
              "o_proj": ((q_dim, hidden), "kernel")},
        "-": {"gate": ((hidden, wide), "kernel"),
              "up": ((hidden, wide), "kernel"),
              "down": ((wide, hidden), "kernel")},
        "E": {"router": ((hidden, experts), "kernel"),
              "score_bias": ((experts,), "score_bias"),
              "experts_gate": ((held, hidden, width), "kernel"),
              "experts_up": ((held, hidden, width), "kernel"),
              "experts_down": ((held, width, hidden), "kernel")},
    }
    for index, kind in enumerate(layer_kinds(sizes)):
        out[f"layer_{index}/scale"] = ((hidden,), "ones")
        for name, entry in kinds[kind].items():
            out[f"layer_{index}/mixer/{name}"] = entry
    return out


def parameter_count(sizes):
    return sum(math.prod(shape) for shape, _ in spec(sizes).values())


# ------------------------------------------------ operations and bytes


def sconv_conv_work(sizes, batch, seq_len):
    """(operations, bytes) of ONE gated short convolution's elementwise
    part, under the scope `lm/attn/sconv_conv`, forward and backward
    (three passes), the same whatever implements the scope. A token and
    channel forward: the gate `B x` (1 operation), K taps multiplied and
    summed (2 K - 1) and the gate `C z` (1): 2 K + 1, 8 at three taps with
    the sum's first term counted. Bytes in bfloat16: `B`, `C` and `x` read
    and `C z` written, once; the taps' kernel is 12 KB and not counted."""
    hidden, taps = sizes["hidden_size"], sizes["conv_L_cache"]
    tokens = batch * seq_len
    forward = tokens * hidden * (2 * taps + 2)
    io = 2 * tokens * 4 * hidden
    return 3 * forward, 3 * io


def work(sizes, batch, seq_len, held_assignments):
    """{scope family: [operations, bytes]} of a whole step, every layer
    that runs under the scope. `held_assignments`: {layer index: rows
    that landed on the held experts}, as the step itself reported them.
    `attn_scores` counts the published head size whatever size a kernel
    pads a head to."""
    kinds = layer_kinds(sizes)
    out = {
        "attn_scores": [n * kinds.count("*")
                        for n in attn_work(sizes, batch, seq_len)],
        "sconv_conv": [n * kinds.count("C")
                       for n in sconv_conv_work(sizes, batch, seq_len)],
        "moe_experts": None,
    }
    if held_assignments:
        out["moe_experts"] = [sum(n) for n in zip(*(
            expert_work(sizes, rows) for rows in held_assignments.values()))]
    return out


def step_flops(sizes, batch, seq_len, held_assignments):
    """Floating-point operations one training step needs (products and
    the convolutions' elementwise part; recomputation not counted, three
    passes for a differentiated one). `held_assignments`: {layer index:
    rows that landed on the held experts}, as the step itself reported
    them."""
    hidden, tokens = sizes["hidden_size"], batch * seq_len
    q_dim = sizes["num_attention_heads"] * sizes["head_dim"]
    kv_dim = sizes["num_key_value_heads"] * sizes["head_dim"]
    forward = {"C": 0.0, "*": 0.0, "-": 0.0, "E": 0.0,
               "head": 2.0 * tokens * hidden * sizes["vocab_slice"]}
    for index, kind in enumerate(layer_kinds(sizes)):
        if kind == "C":
            forward["C"] += (2 * tokens * hidden * 4 * hidden   # W_in, W_out
                             + sconv_conv_work(sizes, batch, seq_len)[0] / 3)
        elif kind == "*":
            forward["*"] += (2 * tokens * hidden * (2 * q_dim + 2 * kv_dim)
                             + attn_work(sizes, batch, seq_len)[0] / 3)
        elif kind == "-":
            forward["-"] += 3 * 2 * tokens * hidden * sizes[
                "intermediate_size"]
        else:
            forward["E"] += (
                2 * tokens * hidden * sizes["n_routed_experts"]
                + expert_work(sizes, held_assignments[index])[0] / 3)
    return {"forward": forward, "iteration": 3.0 * sum(forward.values())}
