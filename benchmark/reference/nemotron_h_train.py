"""Plain reference of the Nemotron-H hybrid token model in training:
forward, loss, gradients, Adam. Plain `jax.numpy`, float32, every product
at HIGHEST precision; imports nothing of the program.

`h_0 = E[ids]`; each layer of the pattern `h = h + Mixer(RMSNorm(h))`;
logits `RMSNorm(h) W_head`; the loss is the mean next-token cross-entropy
over each sequence's L - 1 targets.

  M  Mamba-2 mixer. `[z | xBC | dt] = u W_in`; `xBC = silu(causal
     depthwise conv(xBC) + bias)` split into x (H x P), B, C (G x N, head h
     reads group h // (H/G)); `dt = softplus(dt + dt_bias)`, `A =
     -exp(A_log)`; per head the recurrence `S_t = exp(dt_t A) S_{t-1} +
     dt_t x_t B_t^T`, `y_t = S_t C_t + D x_t`, RUN STEP BY STEP (blocks of
     steps under `jax.checkpoint` so the saved states fit; the arithmetic
     is the recurrence's); `y = GroupRMSNorm(y * silu(z))` over the G
     groups; `out = y W_out`.
  *  Causal grouped-query attention, scale 1/sqrt(head size), no
     position embedding, by query blocks so that the scores fit.
  E  Mixture of experts. Router in float32: `s = sigmoid(x W_r)`, the top
     k of `s + b`, weights `s_i / (sum of the selected s + 1e-20) *
     routed_scaling_factor`; an expert is `W_down relu(W_up x)^2`; `out =
     sum over the selected experts HELD HERE of w_i f_i(x) + f_shared(x)`,
     each held expert computed densely over all tokens and masked. The
     absent experts' terms are left out.

`precision`: "float32" (the reference), "bfloat16" (a witness: what
enters each product rounded to bfloat16) or "float8" (the control: rounded
to e4m3). The router, the recurrence, the norms and the loss are float32
in all three, as they are the program's fp32 islands.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
PRECISIONS = {"float32": None, "bfloat16": jnp.bfloat16,
              "float8": jnp.float8_e4m3fn}
STEP_BLOCK = 128      # steps of the recurrence under one checkpoint
QUERY_BLOCK = 512     # query rows of attention whose scores stand at once


def _rounded(x, precision):
    """x rounded to the precision's product type, gradient straight
    through. bfloat16 by `lax.reduce_precision`: the TPU compiler drops a
    float32 -> bfloat16 -> float32 round trip as excess precision it may
    keep (the witness read 0 on the v5e through `astype`, PERF.md PR 27)."""
    dtype = PRECISIONS[precision]
    if dtype is None:
        return x
    if dtype == jnp.bfloat16:
        low = lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    else:
        low = x.astype(dtype).astype(x.dtype)
    return x + lax.stop_gradient(low - x)


def product(spec, a, b, precision):
    return jnp.einsum(spec, _rounded(a, precision), _rounded(b, precision),
                      precision=HIGHEST)


def rms_norm(x, scale, eps, groups=1):
    shaped = x.reshape(*x.shape[:-1], groups, -1)
    var = jnp.mean(shaped * shaped, axis=-1, keepdims=True)
    return (shaped / jnp.sqrt(var + eps)).reshape(x.shape) * scale


def relu2(x):
    return jnp.maximum(x, 0.0) ** 2


# ------------------------------------------------------------------ layers


def recurrence(x, dt, a, b, c, block=STEP_BLOCK):
    """One sequence's Mamba-2 recurrence, step by step. `x` (L, H, P),
    `dt` (L, H), `a` (H,), `b`, `c` (L, G, N); returns y (L, H, P)."""
    length, heads, p = x.shape
    per = heads // b.shape[1]
    pad = (-length) % block

    def padded(v):
        return jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1)).reshape(
            (length + pad) // block, block, *v.shape[1:])

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        b_h = jnp.repeat(b_t, per, axis=0)              # (H, N)
        c_h = jnp.repeat(c_t, per, axis=0)
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        return state, jnp.sum(state * c_h[:, None, :], axis=-1)

    @jax.checkpoint
    def steps(state, inputs):
        return lax.scan(step, state, inputs)

    state = jnp.zeros((heads, p, b.shape[2]), jnp.float32)
    _, y = lax.scan(steps, state, tuple(padded(v) for v in (x, dt, b, c)))
    return y.reshape(-1, heads, p)[:length]


def mamba2(p, prefix, sizes, u, precision):
    heads, head_dim = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    groups, state = sizes["n_groups"], sizes["ssm_state_size"]
    inner = heads * head_dim
    conv_dim = inner + 2 * groups * state
    zxbcdt = product("blh,hf->blf", u, p[prefix + "in_proj"], precision)
    z, xbc, dt = jnp.split(zxbcdt, [inner, inner + conv_dim], -1)
    kernel = p[prefix + "conv_kernel"]
    taps, length = kernel.shape[0], u.shape[1]
    shifted = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(shifted[:, i:i + length] * kernel[i] for i in range(taps))
    xbc = jax.nn.silu(conv + p[prefix + "conv_bias"])
    x, b, c = jnp.split(xbc, [inner, inner + groups * state], -1)
    lead = x.shape[:2]
    x = x.reshape(*lead, heads, head_dim)
    dt = jax.nn.softplus(dt + p[prefix + "dt_bias"])
    a = -jnp.exp(p[prefix + "A_log"])
    y = jax.vmap(recurrence, in_axes=(0, 0, None, 0, 0))(
        x, dt, a, b.reshape(*lead, groups, state),
        c.reshape(*lead, groups, state))
    y = y + x * p[prefix + "D"][:, None]
    y = rms_norm(y.reshape(*lead, inner) * jax.nn.silu(z),
                 p[prefix + "gate_scale"], sizes["norm_eps"], groups)
    return product("blf,fh->blh", y, p[prefix + "out_proj"], precision)


def attention(p, prefix, sizes, u, precision):
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
    return product("blf,fh->blh", out, p[prefix + "o_proj"], precision)


def routing(p, prefix, sizes, x):
    """(gate (T, experts): each token's weight on each expert, 0 on those
    it did not select; margin (T,): the gap between the last selected and
    the first rejected of `s + b`; edge (T, 2): those two experts)."""
    top_k = sizes["num_experts_per_tok"]
    scores = jax.nn.sigmoid(jnp.dot(x, p[prefix + "router"],
                                    precision=HIGHEST))
    biased = lax.stop_gradient(scores + p[prefix + "score_bias"])
    values, chosen = lax.top_k(biased, top_k + 1)
    selected = jax.nn.one_hot(chosen[:, :top_k], scores.shape[-1],
                              dtype=scores.dtype).sum(1)
    picked = scores * selected
    gate = (picked / (picked.sum(-1, keepdims=True) + 1e-20)
            * sizes["routed_scaling_factor"])
    return (gate, values[:, top_k - 1] - values[:, top_k],
            chosen[:, top_k - 1:])


def moe(p, prefix, sizes, u, precision, tie_margin):
    held = sizes["experts_held"]
    first, count = held["first"], held["count"]
    x = u.reshape(-1, u.shape[-1])
    gate, margin, edge = routing(p, prefix, sizes, x)
    out = product("th,hf->tf", relu2(product(
        "th,hf->tf", x, p[prefix + "shared_up"], precision)),
        p[prefix + "shared_down"], precision)
    for e in range(count):
        hidden = relu2(product("th,hf->tf", x, p[prefix + "experts_up"][e],
                               precision))
        out = out + gate[:, first + e, None] * product(
            "tf,fh->th", hidden, p[prefix + "experts_down"][e], precision)
    here = (edge >= first) & (edge < first + count)
    aux = {"held_assignments": jnp.sum(gate[:, first:first + count] > 0),
           # a token whose choice between a held expert and another (or
           # between two, one of them held) hangs on less than the margin
           "ties": jnp.sum((margin < tie_margin) & (here[:, 0] ^ here[:, 1]))}
    return out.reshape(u.shape), aux


_MIXERS = {"M": mamba2, "*": attention}


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


def adam(params, grads, mu, nu, count, lr, b1, b2, eps=1e-8):
    """Adam with bias correction (no decay). Returns (params, mu, nu)."""
    count = count + 1
    new_mu = {k: b1 * mu[k] + (1.0 - b1) * grads[k] for k in grads}
    new_nu = {k: b2 * nu[k] + (1.0 - b2) * grads[k] ** 2 for k in grads}
    c1, c2 = 1.0 - b1 ** count, 1.0 - b2 ** count
    new_params = {k: params[k] - lr * (new_mu[k] / c1)
                  / (jnp.sqrt(new_nu[k] / c2) + eps) for k in grads}
    return new_params, new_mu, new_nu


def split(values):
    """(trainable, buffers) of the seed's values."""
    buffers = {k: v for k, v in values.items() if k.endswith("score_bias")}
    return {k: v for k, v in values.items() if k not in buffers}, buffers


# ------------------------------------------------------------------- sizes


def spec(sizes):
    """{name: (shape, kind)} of every parameter and buffer at `sizes`;
    the names are the program's paths below `params` / `buffers`."""
    hidden, vocab = sizes["hidden_size"], sizes["vocab_slice"]
    inner = sizes["mamba_num_heads"] * sizes["mamba_head_dim"]
    conv_dim = inner + 2 * sizes["n_groups"] * sizes["ssm_state_size"]
    q_dim = sizes["num_attention_heads"] * sizes["head_dim"]
    kv_dim = sizes["num_key_value_heads"] * sizes["head_dim"]
    width = sizes["moe_intermediate_size"]
    shared = sizes["moe_shared_expert_intermediate_size"]
    held, experts = sizes["experts_held"]["count"], sizes["n_routed_experts"]
    heads = sizes["mamba_num_heads"]
    out = {"embedding": ((vocab, hidden), "embedding"),
           "final_scale": ((hidden,), "ones"),
           "head": ((hidden, vocab), "kernel")}
    layers = {
        "M": {"in_proj": ((hidden, inner + conv_dim + heads), "kernel"),
              "conv_kernel": ((sizes["conv_kernel"], conv_dim), "kernel"),
              "conv_bias": ((conv_dim,), "bias"),
              "dt_bias": ((heads,), "dt_bias"),
              "A_log": ((heads,), "a_log"),
              "D": ((heads,), "ones"),
              "gate_scale": ((inner,), "ones"),
              "out_proj": ((inner, hidden), "kernel")},
        "*": {"q_proj": ((hidden, q_dim), "kernel"),
              "k_proj": ((hidden, kv_dim), "kernel"),
              "v_proj": ((hidden, kv_dim), "kernel"),
              "o_proj": ((q_dim, hidden), "kernel")},
        "E": {"router": ((hidden, experts), "kernel"),
              "score_bias": ((experts,), "score_bias"),
              "experts_up": ((held, hidden, width), "kernel"),
              "experts_down": ((held, width, hidden), "kernel"),
              "shared_up": ((hidden, shared), "kernel"),
              "shared_down": ((shared, hidden), "kernel")},
    }
    for index, kind in enumerate(sizes["pattern"]):
        out[f"layer_{index}/scale"] = ((hidden,), "ones")
        for name, entry in layers[kind].items():
            out[f"layer_{index}/mixer/{name}"] = entry
    return out


def parameter_count(sizes):
    return sum(math.prod(shape) for shape, _ in spec(sizes).values())


# ------------------------------------------------ operations and bytes


def scan_work(sizes, batch, seq_len):
    """(operations, bytes) the chunked state-space-dual evaluation of ONE
    Mamba-2 layer's recurrence needs, forward and backward (three passes
    of the forward's products): the masked C B^T product and its use
    within chunks, the chunk states and their read-out. Bytes: x, B, C, dt
    and y once each way in bfloat16, the (chunk x chunk) decay-weighted
    matrix per head written and read once in bfloat16, the chunk states
    in float32."""
    heads, p = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    groups, n = sizes["n_groups"], sizes["ssm_state_size"]
    q = sizes["chunk_size"]
    tokens = batch * seq_len
    chunks = tokens // q
    forward = 2 * tokens * (groups * q * n        # C B^T
                            + heads * q * p       # its product with x
                            + 2 * heads * p * n)  # chunk states, read-out
    io = 2 * tokens * (2 * heads * p + 2 * groups * n) + 4 * tokens * heads
    matrix = 2 * 2 * chunks * heads * q * q
    states = 2 * 4 * chunks * heads * p * n
    return 3 * forward, 3 * (io + matrix + states)


def expert_work(sizes, held_assignments):
    """(operations, bytes) of ONE expert layer's two grouped products
    over the rows that really landed on the held experts, forward and
    backward: rows x hidden x width each way; the rows in and out and the
    hidden activations in bfloat16, the held experts' weights read twice
    (forward, gradient to the rows) and their gradient written."""
    hidden, width = sizes["hidden_size"], sizes["moe_intermediate_size"]
    held = sizes["experts_held"]["count"]
    forward = 2 * 2 * held_assignments * hidden * width
    rows = 2 * held_assignments * (2 * hidden + 2 * width)
    weights = 2 * 2 * held * hidden * width
    return 3 * forward, 3 * rows + 3 * weights


def attn_work(sizes, batch, seq_len):
    """(operations, bytes) of ONE attention layer's causal scores and
    their product with the values (not the projections), forward and
    backward (the backward recomputes nothing here: 2.5 forward passes of
    products is flash attention's count, 3 the plain one; 3 is used).
    Bytes: q, k, v and the output once each way in bfloat16; the scores
    themselves are not counted, since a kernel need never write them."""
    q_heads, kv_heads = (sizes["num_attention_heads"],
                         sizes["num_key_value_heads"])
    dim = sizes["head_dim"]
    forward = 2 * 2 * batch * q_heads * dim * seq_len * (seq_len + 1) // 2
    io = 2 * batch * seq_len * dim * (2 * q_heads + 2 * kv_heads)
    return 3 * forward, 3 * io


def step_flops(sizes, batch, seq_len, held_assignments):
    """Floating-point operations one training step needs (products only,
    recomputation not counted, three passes for a differentiated one).
    `held_assignments`: {layer index: rows that landed on the held
    experts}, as the step itself reported them."""
    hidden, tokens = sizes["hidden_size"], batch * seq_len
    inner = sizes["mamba_num_heads"] * sizes["mamba_head_dim"]
    conv_dim = inner + 2 * sizes["n_groups"] * sizes["ssm_state_size"]
    q_dim = sizes["num_attention_heads"] * sizes["head_dim"]
    kv_dim = sizes["num_key_value_heads"] * sizes["head_dim"]
    forward = {"M": 0.0, "E": 0.0, "*": 0.0,
               "head": 2.0 * tokens * hidden * sizes["vocab_slice"]}
    for index, kind in enumerate(sizes["pattern"]):
        if kind == "M":
            projections = (
                2 * tokens * hidden * (inner + conv_dim
                                       + sizes["mamba_num_heads"])   # in
                + 2 * tokens * inner * hidden                        # out
                + 2 * tokens * sizes["conv_kernel"] * conv_dim)
            forward["M"] += projections + scan_work(sizes, batch,
                                                    seq_len)[0] / 3
        elif kind == "*":
            forward["*"] += (2 * tokens * hidden * (2 * q_dim + 2 * kv_dim)
                             + attn_work(sizes, batch, seq_len)[0] / 3)
        else:
            forward["E"] += (
                2 * tokens * hidden * sizes["n_routed_experts"]
                + 2 * 2 * tokens * hidden
                * sizes["moe_shared_expert_intermediate_size"]
                + expert_work(sizes, held_assignments[index])[0] / 3)
    return {"forward": forward, "iteration": 3.0 * sum(forward.values())}
