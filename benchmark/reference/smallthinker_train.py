"""Plain reference of the SmallThinker token model (`smallthinker`:
PowerInfer's SmallThinker-21BA3B-Instruct, arXiv:2507.20984) in training:
forward, loss, gradients, Adam. Plain `jax.numpy`, float32, every product
at HIGHEST precision, no kernel and no expert buffer (a published layer
stands under `jax.checkpoint`, and attention works by query blocks, only
so that the float32 step fits one chip at the published widths and
16,384 tokens: the arithmetic is the same); imports nothing of the
program (the norm, Adam and the rounding are `nemotron_h_train.py`'s, the
rotary turn and the gated experts' count of work `glm4_moe_lite_train.py`'s,
the band's count `afmoe_train.py`'s, which are this model's too).

`h_0 = E[ids]`; one published layer `l`, with two norms of their own
learned scales, is two letters of the pattern (`*E` the full layer of a
period, `WE` its three window layers):

    u1 = RMSNorm_1(h)
    z  = u1 W_r                    the router's logits, BEFORE attention
    h' = h + Attn_l(u1)
    u2 = RMSNorm_2(h')
    S  = the top k of z (by logit);  w = softmax(z[S])
    h''= h' + sum_{e in S} w_e W_down,e (relu(u2 W_gate,e) * u2 W_up,e)

logits `RMSNorm(h; w_f) W_head`; the loss is the mean next-token
cross-entropy over each sequence's L - 1 targets. No auxiliary loss.

  W, *  Grouped-query attention, `q, k, v = u W_q, u W_k, u W_v`, no head
     norm, no gate, no bias. `W` (`sliding_window_layout` and
     `rope_layout` 1): `q` and `k` turned by the rotary embedding over
     the whole head, pairs (i, i + d/2), angle `t theta^(-2i/d)`; query
     `i` sees keys `j` with `0 <= i - j < sliding_window` (the window
     counts the query itself). `*` (layout 0): no position embedding,
     every `j <= i`. Scores `q . k / sqrt(d)`, softmax over the keys seen
     (the window is a mask on the block's scores), `o = P v`, `y = o
     W_o`. Query head `h` reads key-value head `h // (Hq/Hkv)`.
  E  Mixture of experts with no shared expert, the router EARLY: its
     logits are a product of the attention layer's normed input `u1`,
     the experts read `u2`. Router in float32: the top k experts by
     logit, their weights the softmax over those k logits alone
     (`moe_primary_router_apply_softmax`; with `norm_topk_prob` the same
     as the softmax over all the experts renormed over the chosen); no
     bias, no factor. An expert is `W_down (relu(x W_gate) * x W_up)`;
     `out = sum over the selected experts HELD HERE of w_e f_e(u2)`, each
     held expert computed densely over all tokens and masked. The absent
     experts' terms are left out.

Departures and choices, each noted where it is made: the early router
(`moe_enable_early_router` in the family's public code; the catalog's row
has no key for it); only primary experts; the window counts the query
itself; rotary pairs are (i, i + d/2); documents are packed without
resets, so the window and the full layer reach through their boundaries.

`precision`: "float32" (the reference), "bfloat16" (a witness) or "float8"
(the control: what enters every product rounded to e4m3). The router, the
rotary turn, the norms and the loss are float32 in all three, as they are
the program's fp32 islands.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.afmoe_train import window_work  # noqa: F401
from benchmark.reference.glm4_moe_lite_train import (  # noqa: F401
    expert_work, rotary)
from benchmark.reference.nemotron_h_train import (  # noqa: F401
    HIGHEST, QUERY_BLOCK, adam, attn_work, product, rms_norm, split)

WINDOWED, FULL = "W", "*"
HEAD_BLOCK = 2048     # positions whose float32 logits stand at once


# ------------------------------------------------------------------ layers


def attention(p, prefix, sizes, u, precision, windowed):
    """A window layer (`windowed`: the turn, and the band) or a full one
    (neither)."""
    q_heads, kv_heads = (sizes["num_attention_heads"],
                         sizes["num_key_value_heads"])
    dim = sizes["head_dim"]
    bsz, length, _ = u.shape
    q = product("blh,hf->blf", u, p[prefix + "q_proj"], precision).reshape(
        bsz, length, q_heads, dim)
    k = product("blh,hf->blf", u, p[prefix + "k_proj"], precision).reshape(
        bsz, length, kv_heads, dim)
    v = product("blh,hf->blf", u, p[prefix + "v_proj"], precision).reshape(
        bsz, length, kv_heads, dim)
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
    return product("blf,fh->blh", out, p[prefix + "o_proj"], precision)


def window_attention(p, prefix, sizes, u, precision):
    return attention(p, prefix, sizes, u, precision, windowed=True)


def full_attention(p, prefix, sizes, u, precision):
    return attention(p, prefix, sizes, u, precision, windowed=False)


def routing(p, prefix, sizes, read):
    """(gate (T, experts): each token's weight on each expert, 0 on those
    it did not select; margin (T,): the gap between the last selected
    logit and the first rejected; edge (T, 2): those two experts) of the
    router's input `read` (T, hidden): the top k by logit, the softmax
    over the chosen logits. No gradient reaches the choice."""
    top_k = sizes["num_experts_per_tok"]
    logits = jnp.dot(read, p[prefix + "router"], precision=HIGHEST)
    values, chosen = lax.top_k(lax.stop_gradient(logits), top_k + 1)
    selected = jax.nn.one_hot(chosen[:, :top_k], logits.shape[-1],
                              dtype=logits.dtype).sum(1)
    # softmax over the chosen: exp(z - max) on the chosen, 0 elsewhere
    shifted = logits - lax.stop_gradient(values[:, :1])
    picked = jnp.exp(shifted) * selected
    gate = picked / picked.sum(-1, keepdims=True)
    return (gate, values[:, top_k - 1] - values[:, top_k],
            chosen[:, top_k - 1:])


def relu_gated(p, prefix, x, precision, expert):
    """`W_down (relu(x W_gate) * x W_up)` of expert `expert` of the stack
    `<prefix>gate`, `<prefix>up`, `<prefix>down`."""
    w_gate, w_up, w_down = (p[prefix + name][expert]
                            for name in ("gate", "up", "down"))
    hidden = (jax.nn.relu(product("th,hf->tf", x, w_gate, precision))
              * product("th,hf->tf", x, w_up, precision))
    return product("tf,fh->th", hidden, w_down, precision)


def moe(p, prefix, sizes, u, precision, tie_margin, router_input=None):
    """The held experts' part of the layer on `u`, routed by
    `router_input` (the attention layer's normed input; `u` itself where
    none is given, which is a model without the early router and is here
    for the tests that tell the two apart)."""
    held = sizes["experts_held"]
    first, count = held["first"], held["count"]
    x = u.reshape(-1, u.shape[-1])
    read = x if router_input is None else router_input.reshape(x.shape)
    gate, margin, edge = routing(p, prefix, sizes, read)
    out = jnp.zeros_like(x)
    for e in range(count):
        out = out + gate[:, first + e, None] * relu_gated(
            p, prefix + "experts_", x, precision, e)
    here = (edge >= first) & (edge < first + count)
    aux = {"held_assignments": jnp.sum(gate[:, first:first + count] > 0),
           # a token whose choice between a held expert and another (or
           # between two, one of them held) hangs on less than the margin
           "ties": jnp.sum((margin < tie_margin) & (here[:, 0] ^ here[:, 1]))}
    return out.reshape(u.shape), aux


_MIXERS = {WINDOWED: window_attention, FULL: full_attention}


def published_layers(sizes):
    """[(index of the attention letter, its kind)]: a published layer is
    an attention letter and the `E` behind it."""
    kinds = layer_kinds(sizes)
    if len(kinds) % 2 or set(kinds[1::2]) != {"E"} \
            or not set(kinds[0::2]) <= set(_MIXERS):
        raise ValueError(f"pattern {kinds!r} is not attention and expert "
                         "letters in turn")
    return list(enumerate(kinds))[0::2]


def loss(train, buffers, sizes, tokens, precision="float32",
         tie_margin=0.0):
    """(mean next-token cross-entropy, {layer index: routing counts}) of
    `tokens` (B, L) int32; `train` the trainable parameters, `buffers`
    empty (the model has no score-correction bias)."""
    p = {**train, **buffers}
    eps = sizes["norm_eps"]
    h = p["embedding"][tokens]
    aux = {}
    for index, kind in published_layers(sizes):

        def layer(h, p, kind=kind, index=index):
            u1 = rms_norm(h, p[f"layer_{index}/scale"], eps)
            h = h + _MIXERS[kind](p, f"layer_{index}/mixer/", sizes, u1,
                                  precision)
            u2 = rms_norm(h, p[f"layer_{index + 1}/scale"], eps)
            out, counts = moe(p, f"layer_{index + 1}/mixer/", sizes, u2,
                              precision, tie_margin, router_input=u1)
            return h + out, counts

        h, aux[index + 1] = jax.checkpoint(layer)(h, p)

    return head_loss(h, p, tokens, eps, precision), aux


def head_loss(h, p, tokens, eps, precision):
    """The mean cross-entropy of position t's logits `RMSNorm(h; w_f)
    W_head` against token t + 1 over each sequence's L - 1 targets, by
    blocks of `HEAD_BLOCK` positions under `jax.checkpoint`: at 16,384
    positions and 37,984 ids the logits whole are 2.5e9 bytes in float32,
    and the step keeps several such arrays (the first chip run did not
    compile: 19.78 of 15.75 GiB). The sum is the same."""
    h = rms_norm(h, p["final_scale"], eps)[:, :-1].reshape(-1, h.shape[-1])
    targets = tokens[:, 1:].reshape(-1)
    count = targets.shape[0]
    block = min(HEAD_BLOCK, count)
    pad = (-count) % block
    weight = jnp.pad(jnp.ones((count,), h.dtype), (0, pad))

    @jax.checkpoint
    def rows(inputs):
        hb, tb, wb = inputs
        logits = product("th,hv->tv", hb, p["head"], precision)
        picked = jnp.take_along_axis(logits, tb[:, None], -1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(logits, -1) - picked) * wb)

    blocks = (jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, block, h.shape[-1]),
              jnp.pad(targets, (0, pad)).reshape(-1, block),
              weight.reshape(-1, block))
    return jnp.sum(lax.map(rows, blocks)) / count


# ------------------------------------------------------------------- sizes


def layer_kinds(sizes):
    return sizes["pattern"]


def spec(sizes):
    """{name: (shape, kind)} of every parameter at `sizes` (the model has
    no buffer); the names are the program's paths below `params`."""
    hidden, vocab = sizes["hidden_size"], sizes["vocab_slice"]
    dim = sizes["head_dim"]
    q_dim = sizes["num_attention_heads"] * dim
    kv_dim = sizes["num_key_value_heads"] * dim
    width = sizes["moe_intermediate_size"]
    held, experts = sizes["experts_held"]["count"], sizes["n_routed_experts"]
    out = {"embedding": ((vocab, hidden), "embedding"),
           "final_scale": ((hidden,), "ones"),
           "head": ((hidden, vocab), "kernel")}
    attn = {"q_proj": ((hidden, q_dim), "kernel"),
            "k_proj": ((hidden, kv_dim), "kernel"),
            "v_proj": ((hidden, kv_dim), "kernel"),
            "o_proj": ((q_dim, hidden), "kernel")}
    kinds = {
        WINDOWED: attn,
        FULL: attn,
        "E": {"router": ((hidden, experts), "kernel"),
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


def work(sizes, batch, seq_len, held_assignments):
    """{scope family: [operations, bytes]} of a whole step, every layer
    that runs under the scope: `attn_scores` the full layers' triangle
    (under `lm/attn/scores`), `attn_window` the window layers' band and
    nothing else (under `lm/attn/window_scores`: `sum_i min(i + 1,
    window)` query-key pairs a head, 58,722,304 at 16,384 under 4,096,
    4 x 128 operations a pair forward, three passes), `moe_experts` the
    held experts' three products of hidden x width over the rows that
    landed, every column counted, those the gate's `relu` zeroes too (the
    program computes them). `held_assignments`: {layer index: rows that landed on the
    held experts}, as the step itself reported them."""
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
    projections = 2 * tokens * hidden * (2 * q_dim + 2 * kv_dim)
    scores = {WINDOWED: window_work, FULL: attn_work}
    forward = {WINDOWED: 0.0, FULL: 0.0, "E": 0.0,
               "head": 2.0 * tokens * hidden * sizes["vocab_slice"]}
    for index, kind in enumerate(layer_kinds(sizes)):
        if kind == "E":
            forward["E"] += (
                2 * tokens * hidden * sizes["n_routed_experts"]
                + expert_work(sizes, held_assignments[index])[0] / 3)
        else:
            forward[kind] += (projections
                              + scores[kind](sizes, batch, seq_len)[0] / 3)
    return {"forward": forward, "iteration": 3.0 * sum(forward.values())}
