"""Plain reference of the GLM-4.7-Flash token model (`glm4_moe_lite`) in
training: forward, two losses, gradients, Adam. Plain `jax.numpy`,
float32, every product at HIGHEST precision; imports nothing of the
program (the router, the norm, Adam and the rounding are
`nemotron_h_train.py`'s, which are this model's too).

`h_0 = E[ids]`; each layer of the pattern `h = h + Mixer(RMSNorm(h))`, a
transformer block being two letters (`*-` block 0, `*E` the others);
logits `RMSNorm(h; w_f) W_head`.

  *  Latent attention. `c_q = RMSNorm(x W_qa)`, `q = c_q W_qb`, a head
     `[q_nope | q_rope]`; `[c_kv | k_rope] = x W_kva`, `[k_nope | v]` a
     head `= RMSNorm(c_kv) W_kvb`; rotary turn R_t (pairs (i, i + 32),
     angle `t theta^(-2i/64)`) on each head's `q_rope` and on the one
     `k_rope`, which every head shares; causal softmax of `[q_nope | R_t
     q_rope] . [k_nope | R_t k_rope] / sqrt(256)` over `v`, by query blocks
     so that the scores fit; `out = concat(o_h) W_o`.
  -  Dense feed-forward `W_down (silu(x W_gate) * x W_up)`.
  E  Mixture of experts. Router in float32: `s = sigmoid(x W_r)`, the top
     k of `s + b`, weights `s_i / (sum of the selected s + 1e-20) *
     routed_scaling_factor`; every expert and the shared one gated as the
     dense layer is; `out = sum over the selected experts HELD HERE of w_i
     f_i(x) + f_shared(x)`, each held expert computed densely over all
     tokens and masked. The absent experts' terms are left out.

Multi-token prediction (one module, DeepSeek-V3's): for the L - 1
positions that have a next token, `m_i = W_eh [RMSNorm_e(E[t_{i+1}]) ;
RMSNorm_h(h^_i)]` with `h^` the main model's hidden state after its final
norm; the module's own `*E` block, causal over those L - 1 positions;
logits `RMSNorm(.; w_m) W_head` with `E` and `W_head` the main model's;
target `t_{i+2}`, L - 2 of them a sequence.
`loss = CE_main + nextn_loss_weight * CE_mtp`.

`precision`: "float32" (the reference), "bfloat16" (a witness) or "float8"
(the control: what enters every product rounded to e4m3). The router, the
rotary turn, the norms and the losses are float32 in all three, as they
are the program's fp32 islands.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.nemotron_h_train import (  # noqa: F401
    QUERY_BLOCK, adam, product, rms_norm, routing, split)


# ------------------------------------------------------------------ layers


def rotary(x, theta):
    """`x` (B, L, ..., d) turned pair by pair, (i, i + d/2) by the angle
    `t theta^(-2i/d)`, `t` the position along axis 1."""
    length, dim = x.shape[1], x.shape[-1]
    frequency = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * frequency
    angle = angle.reshape(1, length, *(1,) * (x.ndim - 3), dim // 2)
    first, second = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate(
        [first * jnp.cos(angle) - second * jnp.sin(angle),
         second * jnp.cos(angle) + first * jnp.sin(angle)], axis=-1)


def latent_attention(p, prefix, sizes, u, precision):
    heads, rank = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    theta, eps = sizes["rope_theta"], sizes["norm_eps"]
    bsz, length, _ = u.shape
    c_q = rms_norm(product("blh,hr->blr", u, p[prefix + "q_a_proj"],
                           precision), p[prefix + "q_a_scale"], eps)
    q = product("blr,rf->blf", c_q, p[prefix + "q_b_proj"],
                precision).reshape(bsz, length, heads, nope + rope)
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], theta)], -1)
    down = product("blh,hr->blr", u, p[prefix + "kv_a_proj"], precision)
    c_kv = rms_norm(down[..., :rank], p[prefix + "kv_a_scale"], eps)
    k_rope = rotary(down[..., rank:], theta)           # one for all heads
    kv = product("blr,rf->blf", c_kv, p[prefix + "kv_b_proj"],
                 precision).reshape(bsz, length, heads, -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.repeat(k_rope[:, :, None, :], heads, axis=2)], -1)
    v = kv[..., nope:]
    dim = nope + rope

    @jax.checkpoint
    def rows(inputs):
        qb, start = inputs
        s = product("bqhd,bkhd->bhqk", qb, k, precision) / math.sqrt(dim)
        pos = start + jnp.arange(qb.shape[1])[:, None]
        s = jnp.where(pos >= jnp.arange(length)[None, :], s, -jnp.inf)
        return product("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                       precision)

    # one block of query rows after another (a loop, so that one block's
    # scores stand at a time), each against all the keys, masked
    block = min(QUERY_BLOCK, length)
    pad = (-length) % block
    blocks = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        bsz, -1, block, heads, dim).swapaxes(0, 1)
    out = lax.map(rows, (blocks, jnp.arange(blocks.shape[0]) * block))
    out = out.swapaxes(0, 1).reshape(bsz, length + pad, -1)[:, :length]
    return product("blf,fh->blh", out, p[prefix + "o_proj"], precision)


def gated(p, prefix, x, precision, expert=None):
    """`W_down (silu(x W_gate) * x W_up)` of the kernels `<prefix>gate`,
    `<prefix>up`, `<prefix>down` (of expert `expert` of a stack)."""
    w_gate, w_up, w_down = (
        p[prefix + name] if expert is None else p[prefix + name][expert]
        for name in ("gate", "up", "down"))
    hidden = (jax.nn.silu(product("...h,hf->...f", x, w_gate, precision))
              * product("...h,hf->...f", x, w_up, precision))
    return product("...f,fh->...h", hidden, w_down, precision)


def dense(p, prefix, sizes, u, precision):
    return gated(p, prefix, u, precision)


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


_MIXERS = {"*": latent_attention, "-": dense}


def layers(p, sizes, h, kinds, first, precision, tie_margin):
    """`h` through the layers `kinds`, the first of them layer `first`;
    (h, {layer index: an expert layer's routing counts})."""
    aux = {}
    for index, kind in enumerate(kinds, first):
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
    return h, aux


def losses(train, buffers, sizes, tokens, precision="float32",
           tie_margin=0.0):
    """(CE_main, CE_mtp, {layer index: routing counts}) of `tokens`
    (B, L) int32."""
    p = {**train, **buffers}
    eps = sizes["norm_eps"]
    h, aux = layers(p, sizes, p["embedding"][tokens], sizes["pattern"], 0,
                    precision, tie_margin)
    h = rms_norm(h, p["final_scale"], eps)

    @jax.checkpoint
    def cross_entropy(h, w_head, targets):
        logits = product("blh,hv->blv", h, w_head, precision)
        picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)

    main = cross_entropy(h[:, :-1], p["head"], tokens[:, 1:])
    # the module, over the L - 1 positions that have a next token
    merged = product(
        "blf,fh->blh",
        jnp.concatenate(
            [rms_norm(p["embedding"][tokens[:, 1:]], p["mtp_embed_scale"],
                      eps),
             rms_norm(h[:, :-1], p["mtp_hidden_scale"], eps)], -1),
        p["mtp_merge"], precision)
    m, more = layers(p, sizes, merged, sizes["nextn_pattern"],
                     len(sizes["pattern"]), precision, tie_margin)
    m = rms_norm(m, p["mtp_final_scale"], eps)
    mtp = cross_entropy(m[:, :-1], p["head"], tokens[:, 2:])
    return main, mtp, {**aux, **more}


def loss(train, buffers, sizes, tokens, precision="float32",
         tie_margin=0.0):
    """(CE_main + nextn_loss_weight CE_mtp, routing counts)."""
    main, mtp, aux = losses(train, buffers, sizes, tokens, precision,
                            tie_margin)
    return main + sizes["nextn_loss_weight"] * mtp, aux


# ------------------------------------------------------------------- sizes


def layer_kinds(sizes):
    return sizes["pattern"] + sizes["nextn_pattern"]


def spec(sizes):
    """{name: (shape, kind)} of every parameter and buffer at `sizes`;
    the names are the program's paths below `params` / `buffers`."""
    hidden, vocab = sizes["hidden_size"], sizes["vocab_slice"]
    heads = sizes["num_attention_heads"]
    q_rank, kv_rank = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    v_dim = sizes["v_head_dim"]
    wide = sizes["intermediate_size"]
    width = sizes["moe_intermediate_size"]
    shared = sizes["moe_shared_expert_intermediate_size"]
    held, experts = sizes["experts_held"]["count"], sizes["n_routed_experts"]
    out = {"embedding": ((vocab, hidden), "embedding"),
           "final_scale": ((hidden,), "ones"),
           "head": ((hidden, vocab), "kernel"),
           "mtp_embed_scale": ((hidden,), "ones"),
           "mtp_hidden_scale": ((hidden,), "ones"),
           "mtp_merge": ((2 * hidden, hidden), "kernel"),
           "mtp_final_scale": ((hidden,), "ones")}
    kinds = {
        "*": {"q_a_proj": ((hidden, q_rank), "kernel"),
              "q_a_scale": ((q_rank,), "ones"),
              "q_b_proj": ((q_rank, heads * (nope + rope)), "kernel"),
              "kv_a_proj": ((hidden, kv_rank + rope), "kernel"),
              "kv_a_scale": ((kv_rank,), "ones"),
              "kv_b_proj": ((kv_rank, heads * (nope + v_dim)), "kernel"),
              "o_proj": ((heads * v_dim, hidden), "kernel")},
        "-": {"gate": ((hidden, wide), "kernel"),
              "up": ((hidden, wide), "kernel"),
              "down": ((wide, hidden), "kernel")},
        "E": {"router": ((hidden, experts), "kernel"),
              "score_bias": ((experts,), "score_bias"),
              "experts_gate": ((held, hidden, width), "kernel"),
              "experts_up": ((held, hidden, width), "kernel"),
              "experts_down": ((held, width, hidden), "kernel"),
              "shared_gate": ((hidden, shared), "kernel"),
              "shared_up": ((hidden, shared), "kernel"),
              "shared_down": ((shared, hidden), "kernel")},
    }
    for index, kind in enumerate(layer_kinds(sizes)):
        out[f"layer_{index}/scale"] = ((hidden,), "ones")
        for name, entry in kinds[kind].items():
            out[f"layer_{index}/mixer/{name}"] = entry
    return out


def parameter_count(sizes):
    return sum(math.prod(shape) for shape, _ in spec(sizes).values())


# ------------------------------------------------ operations and bytes


def attn_work(sizes, batch, seq_len):
    """(operations, bytes) of ONE attention layer's causal scores and
    their product with the values (not the projections), forward and
    backward, by `nemotron_h_train.attn_work`'s convention: three forward
    passes of products; q, k, v (a head each: the decompressed form has
    as many key-value heads as query heads) and the output once each way
    in bfloat16; the scores themselves are not counted."""
    heads = sizes["num_attention_heads"]
    dim = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    forward = 2 * 2 * batch * heads * dim * seq_len * (seq_len + 1) // 2
    io = 2 * batch * seq_len * dim * 4 * heads
    return 3 * forward, 3 * io


def latent_work(sizes, batch, seq_len):
    """(operations, bytes) of what ONE latent-attention layer does around
    its scores, under the scopes `lm/attn/q_latent`, `lm/attn/kv_latent`
    and `lm/attn/rope`, forward and backward (three passes): the four
    projections into and out of the two latents (the fifth, `W_o`, stands
    under `lm/attn/out`). Bytes in bfloat16: the layer's input read, both
    latents with the rotary key written and read, the per-head queries,
    keys (each built from its own part and the shared rotary key) and
    values written, the four kernels read."""
    hidden, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    q_rank, kv_rank = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    v_dim = sizes["v_head_dim"]
    tokens = batch * seq_len
    kernels = (hidden * q_rank + q_rank * heads * (nope + rope)
               + hidden * (kv_rank + rope) + kv_rank * heads * (nope + v_dim))
    forward = 2 * tokens * kernels
    io = 2 * tokens * (hidden + 2 * (q_rank + kv_rank + rope)
                       + heads * (2 * (nope + rope) + v_dim))
    return 3 * forward, 3 * (io + 2 * kernels)


def expert_work(sizes, held_assignments):
    """(operations, bytes) of ONE expert layer's three grouped products
    over the rows that really landed on the held experts, forward and
    backward: rows x hidden x width each; the rows in and out and the
    hidden activations once each way in bfloat16, the held experts'
    weights read twice (forward, gradient to the rows) and their gradient
    written."""
    hidden, width = sizes["hidden_size"], sizes["moe_intermediate_size"]
    held = sizes["experts_held"]["count"]
    forward = 3 * 2 * held_assignments * hidden * width
    rows = 2 * held_assignments * (2 * hidden + 2 * width)
    weights = 3 * 2 * held * hidden * width
    return 3 * forward, 3 * rows + 3 * weights


def work(sizes, batch, seq_len, held_assignments):
    """{scope family: [operations, bytes]} of a whole step, every layer
    that runs under the scope, the module's block included.
    `held_assignments`: {layer index: rows that landed on the held
    experts}, as the step itself reported them."""
    attention_layers = layer_kinds(sizes).count("*")
    out = {
        "attn_scores": [n * attention_layers
                        for n in attn_work(sizes, batch, seq_len)],
        "mla_latent": [n * attention_layers
                       for n in latent_work(sizes, batch, seq_len)],
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
    heads = sizes["num_attention_heads"]
    forward = {"*": 0.0, "-": 0.0, "E": 0.0,
               # two sets of logits over one head, and the module's merge
               "head": 2 * 2.0 * tokens * hidden * sizes["vocab_slice"],
               "merge": 2.0 * tokens * 2 * hidden * hidden}
    for index, kind in enumerate(layer_kinds(sizes)):
        if kind == "*":
            forward["*"] += (
                latent_work(sizes, batch, seq_len)[0] / 3
                + 2 * tokens * heads * sizes["v_head_dim"] * hidden    # W_o
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
