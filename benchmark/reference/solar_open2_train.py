"""Plain reference of the Solar-Open2 token model (`solar_open2`) in
training: forward, loss, gradients, Adam. Plain `jax.numpy`, float32,
every product at HIGHEST precision; imports nothing of the program (the
router, the norm, Adam and the rounding are `nemotron_h_train.py`'s, the
gated expert layer `glm4_moe_lite_train.py`'s, which are this model's too).

`h_0 = E[ids]`; each layer of the pattern `h = h + Mixer(RMSNorm(h))`, a
block being two letters (`*E` layer 0 of every four, `KE` the other three);
logits `RMSNorm(h; w_f) W_head`; the loss is the mean next-token
cross-entropy over each sequence's L - 1 targets.

  K  Kimi Delta Attention (Kimi Linear, arXiv:2510.26692), a head of size
     d: `q = l2norm(silu(conv(u W_q)))`, `k = l2norm(silu(conv(u W_k)))`,
     `v = silu(conv(u W_v))` (depthwise causal convolution, no bias);
     `a_t = -exp(A_log) softplus((u W_f1) W_f2 + dt_bias)` the log-decay
     of each key channel; `beta_t = 2 sigmoid(u W_b)`; per head the
     recurrence `S_t = (I - beta_t k_t k_t^T) Diag(exp(a_t)) S_{t-1} +
     beta_t k_t v_t^T`, `o_t = S_t^T q_t / sqrt(d)`, RUN STEP BY STEP
     (blocks of steps under `jax.checkpoint` so the saved states fit; the
     arithmetic is the recurrence's, elementwise, with no product to
     round); `y = (RMSNorm_head(o) * sigmoid((u W_g1) W_g2)) W_o`.
  *  Causal grouped-query attention, scale 1/sqrt(head size), no
     position embedding, by query blocks so that the scores fit, then
     `y = (attention * sigmoid(u W_gate)) W_o`, one gate value a head
     channel.
  E  Mixture of experts (`glm4_moe_lite_train.moe`): router in float32,
     `s = sigmoid(x W_r)`, the top k of `s + b`, weights normalised and
     scaled; gated `silu` experts, one shared; `out = sum over the
     selected experts HELD HERE of w_i f_i(x) + f_shared(x)`, a loop over
     the held experts, each computed densely over all tokens and masked.

A share of heads: `num_attention_heads`, `num_key_value_heads` and
`linear_attn_config.num_heads` count the heads held here; their rows of
`W_o` give this rank's part of the mixer's result, and that partial
result goes on, as the held experts' does.

`precision`: "float32" (the reference), "bfloat16" (a witness) or "float8"
(the control: what enters every product rounded to e4m3). The router, the
recurrence with its decays and beta, the norms and the loss are float32 in
all three, as they are the program's fp32 islands.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.glm4_moe_lite_train import (  # noqa: F401
    expert_work, moe)
from benchmark.reference.nemotron_h_train import (  # noqa: F401
    QUERY_BLOCK, STEP_BLOCK, adam, attn_work, product, rms_norm, split)


# ------------------------------------------------------------------ layers


def delta_rule(q, k, v, a, beta, block=STEP_BLOCK):
    """One sequence's gated delta rule, step by step. `q`, `k`, `v`, `a`
    (L, H, d), `beta` (L, H); returns o (L, H, d). The state (H, d_key,
    d_value) starts at zero."""
    length, heads, dim = q.shape
    pad = (-length) % block

    def padded(x):
        return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
            (length + pad) // block, block, *x.shape[1:])

    def step(state, inputs):
        q_t, k_t, v_t, a_t, b_t = inputs
        state = jnp.exp(a_t)[:, :, None] * state
        seen = jnp.sum(k_t[:, :, None] * state, axis=1)          # S^T k
        state = state + (b_t[:, None] * k_t)[:, :, None] * (
            v_t - seen)[:, None, :]
        return state, jnp.sum(q_t[:, :, None] * state, axis=1)

    @jax.checkpoint
    def steps(state, inputs):
        return lax.scan(step, state, inputs)

    state = jnp.zeros((heads, dim, dim), jnp.float32)
    _, out = lax.scan(steps, state,
                      tuple(padded(x) for x in (q, k, v, a, beta)))
    return out.reshape(-1, heads, dim)[:length] / math.sqrt(dim)


def l2_norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda(p, prefix, sizes, u, precision):
    linear = sizes["linear_attn_config"]
    heads, dim = linear["num_heads"], linear["head_dim"]
    bsz, length, _ = u.shape

    def conved(name):
        x = product("blh,hf->blf", u, p[prefix + name + "_proj"], precision)
        kernel = p[prefix + name + "_conv"]
        taps = kernel.shape[0]
        shifted = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
        conv = sum(shifted[:, i:i + length] * kernel[i] for i in range(taps))
        return jax.nn.silu(conv).reshape(bsz, length, heads, dim)

    def low_rank(name):
        down = product("blh,hr->blr", u, p[prefix + name + "_a_proj"],
                       precision)
        return product("blr,rf->blf", down, p[prefix + name + "_b_proj"],
                       precision)

    q, k, v = l2_norm(conved("q")), l2_norm(conved("k")), conved("v")
    a = (-jnp.exp(p[prefix + "A_log"])[:, None] * jax.nn.softplus(
        low_rank("f") + p[prefix + "dt_bias"]).reshape(
            bsz, length, heads, dim))
    beta = 2.0 * jax.nn.sigmoid(
        product("blh,hn->bln", u, p[prefix + "b_proj"], precision))
    out = jax.vmap(delta_rule)(q, k, v, a, beta)
    gate = jax.nn.sigmoid(low_rank("g")).reshape(out.shape)
    y = rms_norm(out, p[prefix + "gate_scale"], sizes["norm_eps"]) * gate
    return product("blf,fh->blh", y.reshape(bsz, length, -1),
                   p[prefix + "o_proj"], precision)


def gated_attention(p, prefix, sizes, u, precision):
    q_heads, kv_heads = (sizes["num_attention_heads"],
                         sizes["num_key_value_heads"])
    dim = sizes["head_dim"]
    bsz, length, _ = u.shape
    q = product("blh,hf->blf", u, p[prefix + "q_proj"], precision).reshape(
        bsz, length, kv_heads, q_heads // kv_heads, dim)
    k = product("blh,hf->blf", u, p[prefix + "k_proj"], precision).reshape(
        bsz, length, kv_heads, dim)
    v = product("blh,hf->blf", u, p[prefix + "v_proj"], precision).reshape(
        bsz, length, kv_heads, dim)

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
    gate = jax.nn.sigmoid(
        product("blh,hf->blf", u, p[prefix + "gate_proj"], precision))
    return product("blf,fh->blh", out * gate, p[prefix + "o_proj"],
                   precision)


_MIXERS = {"K": kda, "*": gated_attention}


def loss(train, buffers, sizes, tokens, precision="float32",
         tie_margin=0.0):
    """(mean next-token cross-entropy, {layer index: routing counts}) of
    `tokens` (B, L) int32; `train` the trainable parameters, `buffers`
    the routers' score-correction biases."""
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
    q_dim = sizes["num_attention_heads"] * sizes["head_dim"]
    kv_dim = sizes["num_key_value_heads"] * sizes["head_dim"]
    linear = sizes["linear_attn_config"]
    heads, dim = linear["num_heads"], linear["head_dim"]
    inner, taps = heads * dim, linear["short_conv_kernel_size"]
    width = sizes["moe_intermediate_size"]
    shared = sizes["moe_shared_expert_intermediate_size"]
    held, experts = sizes["experts_held"]["count"], sizes["n_routed_experts"]
    out = {"embedding": ((vocab, hidden), "embedding"),
           "final_scale": ((hidden,), "ones"),
           "head": ((hidden, vocab), "kernel")}
    kinds = {
        "K": {**{f"{n}_proj": ((hidden, inner), "kernel") for n in "qkv"},
              **{f"{n}_conv": ((taps, inner), "kernel") for n in "qkv"},
              # the decay's and the output gate's low-rank pairs
              "f_a_proj": ((hidden, dim), "kernel"),
              "f_b_proj": ((dim, inner), "kernel"),
              "dt_bias": ((inner,), "dt_bias"),
              "A_log": ((heads,), "a_log"),
              "b_proj": ((hidden, heads), "kernel"),
              "g_a_proj": ((hidden, dim), "kernel"),
              "g_b_proj": ((dim, inner), "kernel"),
              "gate_scale": ((dim,), "ones"),
              "o_proj": ((inner, hidden), "kernel")},
        "*": {"q_proj": ((hidden, q_dim), "kernel"),
              "k_proj": ((hidden, kv_dim), "kernel"),
              "v_proj": ((hidden, kv_dim), "kernel"),
              "gate_proj": ((hidden, q_dim), "kernel"),
              "o_proj": ((q_dim, hidden), "kernel")},
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


def kda_scan_work(sizes, batch, seq_len):
    """(operations, bytes) the chunked WY evaluation of ONE delta-rule
    layer's recurrence needs at the configuration's chunk, forward and
    backward (three passes of the forward's products), the same whatever
    implements the scope. A chunk of C steps, a head of size d: the two
    decayed (C x C x d) products `k k^T` and `q k^T` (2 C^2 d each), the
    unit-lower-triangular solve applied to `beta k e^c` and `beta v`
    (2 C^2 2d), `P U` (2 C^2 d), and the three products with the carried
    (d x d) state, `W S`, `(q e^c) S`, `(k e^(c_end - c))^T U` (2 C d^2
    each). Bytes: q, k, v and the output once each way in bfloat16, the
    log-decays in float32 and beta, and each chunk's carried state
    written and read once in float32."""
    linear = sizes["linear_attn_config"]
    heads, d = linear["num_heads"], linear["head_dim"]
    c = sizes["kda_chunk_size"]
    tokens = batch * seq_len
    chunks = tokens // c
    forward = tokens * heads * (10 * c * d + 6 * d * d)
    io = tokens * heads * (4 * 2 * d + 4 * d + 4)
    states = 2 * 4 * chunks * heads * d * d
    return 3 * forward, 3 * (io + states)


def work(sizes, batch, seq_len, held_assignments):
    """{scope family: [operations, bytes]} of a whole step, every layer
    that runs under the scope. `held_assignments`: {layer index: rows
    that landed on the held experts}, as the step itself reported them."""
    kinds = layer_kinds(sizes)
    out = {
        "attn_scores": [n * kinds.count("*")
                        for n in attn_work(sizes, batch, seq_len)],
        "kda_scan": [n * kinds.count("K")
                     for n in kda_scan_work(sizes, batch, seq_len)],
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
    linear = sizes["linear_attn_config"]
    heads, dim = linear["num_heads"], linear["head_dim"]
    inner = heads * dim
    forward = {"K": 0.0, "*": 0.0, "E": 0.0,
               "head": 2.0 * tokens * hidden * sizes["vocab_slice"]}
    for index, kind in enumerate(layer_kinds(sizes)):
        if kind == "K":
            projections = (
                2 * tokens * hidden * (4 * inner + 2 * dim + heads)
                + 2 * tokens * dim * 2 * inner             # W_f2, W_g2
                + 2 * tokens * 3 * inner * linear["short_conv_kernel_size"])
            forward["K"] += projections + kda_scan_work(
                sizes, batch, seq_len)[0] / 3
        elif kind == "*":
            forward["*"] += (2 * tokens * hidden * (3 * q_dim + 2 * kv_dim)
                             + attn_work(sizes, batch, seq_len)[0] / 3)
        else:
            forward["E"] += (
                2 * tokens * hidden * sizes["n_routed_experts"]
                + 3 * 2 * tokens * hidden
                * sizes["moe_shared_expert_intermediate_size"]
                + expert_work(sizes, held_assignments[index])[0] / 3)
    return {"forward": forward, "iteration": 3.0 * sum(forward.values())}
