"""Plain reference of the AFMoE token model (`afmoe`: Arcee's Trinity
family) in training: forward, loss, gradients, Adam. Plain `jax.numpy`,
float32, every product at HIGHEST precision, no kernel and no expert
buffer (a layer stands under `jax.checkpoint`, and attention works by
query blocks, only so that the float32 step fits one chip at the
published widths: the arithmetic is the same); imports nothing of the
program (the router's scores and choice, the norm, Adam and the rounding
are `nemotron_h_train.py`'s, the gated feed-forward and the rotary turn
`glm4_moe_lite_train.py`'s, which are this model's too).

`h_0 = E[ids] * embed_scale` (`mup_enabled`: sqrt(hidden_size)); every
layer of the model is, with four norms of their own learned scales,

    h = h + RMSNorm_2(Attn(RMSNorm_1(h)))
    h = h + RMSNorm_4(FF(RMSNorm_3(h)))

two letters of the pattern (`W-` a leading layer, `WE` a
`sliding_attention` layer of a period, `*E` its `full_attention` layer);
logits `RMSNorm(h; w_f) W_head`; the loss is the mean next-token
cross-entropy over each sequence's L - 1 targets. No auxiliary loss.

  W, *  Grouped-query attention. `q, k, v, g = u W_q, u W_k, u W_v,
     u W_g`; `q` and `k` RMS-normed over the head's channels with a
     learned scale (one for `q`, one for `k`, shared by the heads).
     `W` (`sliding_attention`): both turned by the rotary embedding over
     the whole head, pairs (i, i + d/2), angle `t theta^(-2i/d)`; query
     `i` sees keys `j` with `0 <= i - j < sliding_window` (the window
     counts the query itself). `*` (`full_attention`): no position
     embedding, every `j <= i`. Scores `q . k / sqrt(d)`, softmax over
     the keys seen (the window is a mask on the block's scores), `o = P
     v`; `y = (o * sigmoid(g)) W_o`, the gate elementwise, one value a
     head channel. Query head `h` reads key-value head `h // (Hq/Hkv)`.
     No bias anywhere.
  -  Dense feed-forward `W_down (silu(x W_gate) * x W_up)`.
  E  Mixture of experts with a shared expert. Router in float32: `s =
     sigmoid(x W_r)` over all experts, the top k of `s + expert_bias`,
     weights `s_i / (sum of the selected s + 1e-20) * route_scale`
     (`route_norm`); every expert and the shared one gated as the dense
     layer is; `out = Shared(x) + sum over the selected experts HELD HERE
     of w_i f_i(x)`, each held expert computed densely over all tokens
     and masked. The absent experts' terms are left out.

Departures and choices, each noted where it is made: the window counts
the query itself; rotary pairs are (i, i + d/2); the rotary turn on the
window layers only (the family's rule: the config has one `rope_theta`
and no key for it); the expert bias is a buffer at its seeded value
(`load_balance_coeff` moves it in the family's training code, not here);
documents are packed without resets, so the window and the full layer
reach through their boundaries.

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

WINDOWED, FULL = "W", "*"


# ------------------------------------------------------------------ layers


def attention(p, prefix, sizes, u, precision, windowed):
    """A `sliding_attention` layer (`windowed`) or a `full_attention`
    one."""
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
    q = rms_norm(q, p[prefix + "q_norm_scale"], eps)
    k = rms_norm(k, p[prefix + "k_norm_scale"], eps)
    if windowed:
        q, k = rotary(q, sizes["rope_theta"]), rotary(k, sizes["rope_theta"])
    q = q.reshape(bsz, length, kv_heads, q_heads // kv_heads, dim)
    keys = jnp.arange(length)[None, :]

    @jax.checkpoint
    def rows(inputs):
        qb, start = inputs
        s = product("bqgrd,bkgd->bgrqk", qb, k, precision) / math.sqrt(dim)
        pos = start + jnp.arange(qb.shape[1])[:, None]
        seen = pos >= keys
        if windowed:
            seen = seen & (pos - keys < sizes["sliding_window"])
        s = jnp.where(seen, s, -jnp.inf)
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
    gate = jax.nn.sigmoid(
        product("blh,hf->blf", u, p[prefix + "gate_proj"], precision))
    return product("blf,fh->blh", out * gate, p[prefix + "o_proj"],
                   precision)


def moe(p, prefix, sizes, u, precision, tie_margin):
    held = sizes["experts_held"]
    first, count = held["first"], held["count"]
    x = u.reshape(-1, u.shape[-1])
    gate, margin, edge = routing(p, prefix, sizes, x)
    out = gated(p, prefix + "shared_", x, precision)
    for e in range(count):
        out = out + gate[:, first + e, None] * gated(
            p, prefix + "experts_", x, precision, expert=e)
    here = (edge >= first) & (edge < first + count)
    aux = {"held_assignments": jnp.sum(gate[:, first:first + count] > 0),
           # a token whose choice between a held expert and another (or
           # between two, one of them held) hangs on less than the margin
           "ties": jnp.sum((margin < tie_margin) & (here[:, 0] ^ here[:, 1]))}
    return out.reshape(u.shape), aux


def window_attention(p, prefix, sizes, u, precision):
    return attention(p, prefix, sizes, u, precision, windowed=True)


def full_attention(p, prefix, sizes, u, precision):
    return attention(p, prefix, sizes, u, precision, windowed=False)


_MIXERS = {WINDOWED: window_attention, FULL: full_attention, "-": dense}


def loss(train, buffers, sizes, tokens, precision="float32",
         tie_margin=0.0):
    """(mean next-token cross-entropy, {layer index: routing counts}) of
    `tokens` (B, L) int32; `train` the trainable parameters, `buffers`
    the routers' expert biases."""
    p = {**train, **buffers}
    eps = sizes["norm_eps"]
    h = p["embedding"][tokens] * sizes["embed_scale"]
    aux = {}
    for index, kind in enumerate(sizes["pattern"]):

        def layer(h, p, kind=kind, index=index):
            u = rms_norm(h, p[f"layer_{index}/scale"], eps)
            prefix = f"layer_{index}/mixer/"
            if kind == "E":
                out, counts = moe(p, prefix, sizes, u, precision, tie_margin)
            else:
                out, counts = _MIXERS[kind](p, prefix, sizes, u,
                                            precision), {}
            return h + rms_norm(out, p[f"layer_{index}/post_scale"],
                                eps), counts

        h, counts = jax.checkpoint(layer)(h, p)
        if counts:
            aux[index] = counts

    @jax.checkpoint
    def head(h, p):
        h = rms_norm(h, p["final_scale"], eps)
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
    the names are the program's paths below `params` / `buffers`. The
    embedding is drawn as a kernel is (normal / sqrt(rows)): under
    `embed_scale` the residual stream then enters layer 0 at an RMS of
    sqrt(hidden / rows), 0.29 at the published share, where unit-normal
    rows would enter at 45 and a bfloat16 stream would round every
    layer's normed contribution (RMS 1) to steps of 0.25."""
    hidden, vocab = sizes["hidden_size"], sizes["vocab_slice"]
    dim = sizes["head_dim"]
    q_dim = sizes["num_attention_heads"] * dim
    kv_dim = sizes["num_key_value_heads"] * dim
    wide = sizes["intermediate_size"]
    width = sizes["moe_intermediate_size"]
    shared = sizes["moe_shared_expert_intermediate_size"]
    held, experts = sizes["experts_held"]["count"], sizes["n_routed_experts"]
    out = {"embedding": ((vocab, hidden), "kernel"),
           "final_scale": ((hidden,), "ones"),
           "head": ((hidden, vocab), "kernel")}
    attn = {"q_proj": ((hidden, q_dim), "kernel"),
            "k_proj": ((hidden, kv_dim), "kernel"),
            "v_proj": ((hidden, kv_dim), "kernel"),
            "gate_proj": ((hidden, q_dim), "kernel"),
            "q_norm_scale": ((dim,), "ones"),
            "k_norm_scale": ((dim,), "ones"),
            "o_proj": ((q_dim, hidden), "kernel")}
    kinds = {
        WINDOWED: attn,
        FULL: attn,
        "-": {"gate": ((hidden, wide), "kernel"),
              "up": ((hidden, wide), "kernel"),
              "down": ((wide, hidden), "kernel")},
        "E": {"router": ((hidden, experts), "kernel"),
              "score_bias": ((experts,), "score_bias"),
              "shared_gate": ((hidden, shared), "kernel"),
              "shared_up": ((hidden, shared), "kernel"),
              "shared_down": ((shared, hidden), "kernel"),
              "experts_gate": ((held, hidden, width), "kernel"),
              "experts_up": ((held, hidden, width), "kernel"),
              "experts_down": ((held, width, hidden), "kernel")},
    }
    for index, kind in enumerate(layer_kinds(sizes)):
        out[f"layer_{index}/scale"] = ((hidden,), "ones")
        out[f"layer_{index}/post_scale"] = ((hidden,), "ones")
        for name, entry in kinds[kind].items():
            out[f"layer_{index}/mixer/{name}"] = entry
    return out


def parameter_count(sizes):
    """Every number `spec` lists at `sizes`: the trainable parameters and
    the routers' expert biases (buffers: experts a router)."""
    return sum(math.prod(shape) for shape, _ in spec(sizes).values())


# ------------------------------------------------ operations and bytes


def window_work(sizes, batch, seq_len):
    """(operations, bytes) of ONE `sliding_attention` layer's scores and
    their product with the values, under `lm/attn/window_scores`, forward
    and backward by `attn_work`'s convention (three forward passes): the
    band and nothing else, `sum_i min(i + 1, window)` query-key pairs a
    head, two products of `2 d` operations a pair. It is the same count
    whatever implements the scope: a kernel that computes whole tiles
    reads what it wastes as a lower share. Bytes as `attn_work`'s: q, k,
    v and the output once each way in bfloat16."""
    q_heads, kv_heads = (sizes["num_attention_heads"],
                         sizes["num_key_value_heads"])
    dim = sizes["head_dim"]
    window = min(sizes["sliding_window"], seq_len)
    pairs = window * (window + 1) // 2 + (seq_len - window) * window
    forward = 2 * 2 * batch * q_heads * dim * pairs
    io = 2 * batch * seq_len * dim * (2 * q_heads + 2 * kv_heads)
    return 3 * forward, 3 * io


def work(sizes, batch, seq_len, held_assignments):
    """{scope family: [operations, bytes]} of a whole step, every layer
    that runs under the scope: `attn_scores` the `full_attention` layers'
    triangle (under `lm/attn/scores`), `attn_window` the
    `sliding_attention` layers' band (under `lm/attn/window_scores`).
    `held_assignments`: {layer index: rows that landed on the held
    experts}, as the step itself reported them."""
    kinds = layer_kinds(sizes)
    out = {
        "attn_scores": [n * kinds.count(FULL)
                        for n in attn_work(sizes, batch, seq_len)],
        "attn_window": [n * kinds.count(WINDOWED)
                        for n in window_work(sizes, batch, seq_len)],
        "moe_experts": None,
    }
    if held_assignments:
        out["moe_experts"] = [sum(n) for n in zip(*(
            expert_work(sizes, rows) for rows in held_assignments.values()))]
    return out


def step_flops(sizes, batch, seq_len, held_assignments):
    """Floating-point operations one training step needs (products only,
    recomputation not counted, three passes for a differentiated one).
    `held_assignments`: {layer index: rows that landed on the held
    experts}, as the step itself reported them."""
    hidden, tokens = sizes["hidden_size"], batch * seq_len
    q_dim = sizes["num_attention_heads"] * sizes["head_dim"]
    kv_dim = sizes["num_key_value_heads"] * sizes["head_dim"]
    projections = 2 * tokens * hidden * (3 * q_dim + 2 * kv_dim)
    forward = {WINDOWED: 0.0, FULL: 0.0, "-": 0.0, "E": 0.0,
               "head": 2.0 * tokens * hidden * sizes["vocab_slice"]}
    for index, kind in enumerate(layer_kinds(sizes)):
        if kind == WINDOWED:
            forward[kind] += (projections
                              + window_work(sizes, batch, seq_len)[0] / 3)
        elif kind == FULL:
            forward[kind] += (projections
                              + attn_work(sizes, batch, seq_len)[0] / 3)
        elif kind == "-":
            forward["-"] += 3 * 2 * tokens * hidden * sizes[
                "intermediate_size"]
        else:
            forward["E"] += (
                2 * tokens * hidden * sizes["n_routed_experts"]
                + 3 * 2 * tokens * hidden
                * sizes["moe_shared_expert_intermediate_size"]
                + expert_work(sizes, held_assignments[index])[0] / 3)
    return {"forward": forward, "iteration": 3.0 * sum(forward.values())}
