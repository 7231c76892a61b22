"""The gated delta rule with a decay a key channel (Kimi Delta Attention),
per head with state ``S`` (d, d), ``S_0 = 0``:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(a_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(d)

evaluated in chunks of ``chunk`` steps: within a chunk by the WY form (one
unit-lower-triangular inverse a chunk), across chunks by the carried
state. All of it runs in float32 with ``Precision.HIGHEST`` products: the
fp32 island ``delta_rule``.

One algorithm, two arms, and ``delta_rule`` picks between them from what
it can observe (``arm_of``), with no option:

  ``fused``   two Pallas TPU kernels, a forward and a backward sweep
              (``ops/pallas/delta_rule_kernel.py``): a chunk's operands
              and every head's (d, d) state stand in VMEM, the operands
              are read from the mixer's own (B, L, H, d) layout, and
              nothing (C, C) or (C, C, d) reaches HBM. Where
              the backend is a TPU, the head size a multiple of 128, the
              chunk a power of two of sub-blocks (and a multiple of 8)
              and the length a multiple of the chunk.
  ``chunks``  ``kda_scan``: the same arithmetic in plain ``jax.numpy``,
              ``KDA_CHUNKS_AT_ONCE`` chunks at a time under
              ``jax.checkpoint`` and a ``lax.scan`` over the chunks.
              Everywhere else: the CPU, where the tests run, ragged
              lengths, the unit-test YAML's head size of 16.

The fused arm's forward sweep names its output and the state each chunk
starts from ``KERNEL_RESIDUAL``: a block recomputed under
``optim/remat.py``'s ``blocks`` keeps the two and runs the backward
kernel, not the forward kernel a second time. ``TILES`` (chunks a grid
step, each sweep's) were chosen on a v5e chip at 8 heads of 128 and
8,192 positions (PERF.md, PR 43); they are not configuration.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from imaginaire_tpu.analysis import islands
from imaginaire_tpu.ops.attention import KERNEL_RESIDUAL
from imaginaire_tpu.ops.pallas import delta_rule_kernel as kernel

_HIGHEST = lax.Precision.HIGHEST
# rows of a sub-block of a chunk of the delta rule: only a sub-block
# against itself builds (rows, rows, head size) decays; a chunk that
# this does not divide is one sub-block
KDA_SUB_BLOCK = 16
# chunks of the delta rule whose sub-blocks' decays stand at once in the
# ``chunks`` arm (4.2 MB a chunk of 64 at 8 heads of 128); the whole
# step's temporaries rise with it (PERF.md, PR 35: 17 MB more at 16, 34
# MB at 32)
KDA_CHUNKS_AT_ONCE = 8


class Tiles(NamedTuple):
    """Chunks a grid step of the fused arm's forward and backward
    sweeps."""
    fwd: int
    bwd: int


TILES = Tiles(fwd=16, bwd=4)


def kda_sub_block(chunk):
    """Rows of the sub-blocks a chunk of ``chunk`` steps is cut into."""
    return KDA_SUB_BLOCK if chunk % KDA_SUB_BLOCK == 0 else chunk


def arm_of(head_dim, chunk, length):
    """``"fused"`` or ``"chunks"``: which arm ``delta_rule`` takes for a
    head size, a chunk and a length on this process's backend. The kernel
    merges the sub-blocks' inverses by halves, so a chunk is a power of
    two of them."""
    count = chunk // KDA_SUB_BLOCK
    fits = (head_dim % kernel.LANES == 0
            and chunk % KDA_SUB_BLOCK == 0 and chunk % 8 == 0
            and count & (count - 1) == 0
            and length % chunk == 0)
    return "fused" if jax.default_backend() == "tpu" and fits else "chunks"


def delta_rule(q, k, v, a, beta, chunk):
    """``q``, ``k``, ``v`` (B, L, H, d) in the compute dtype; the
    log-decays ``a`` (B, L, H, d), at most 0, and ``beta`` (B, L, H)
    float32. Returns ``o`` (B, L, H, d) in ``v``'s dtype."""
    if arm_of(q.shape[-1], chunk, q.shape[1]) == "fused":
        return fused_delta_rule(q, k, v, a, beta, chunk)
    return kda_scan(q, k, v, a, beta, chunk)


# ------------------------------------------------------- the ``chunks`` arm


def _substituted(a):
    """``(I + a)^-1`` of a strictly lower-triangular ``a`` (..., n, n) by
    forward substitution, row by row."""
    n = a.shape[-1]
    inv = jnp.broadcast_to(jnp.eye(n, dtype=a.dtype), a.shape)
    for i in range(1, n):
        inv = inv.at[..., i, :].add(-jnp.einsum(
            "...j,...jk->...k", a[..., i, :i], inv[..., :i, :],
            precision=_HIGHEST))
    return inv


def unit_lower_inverse(a):
    """``(I + a)^-1`` of a strictly lower-triangular ``a`` (..., n, n), in
    ``a``'s float32: forward substitution row by row up to
    ``KDA_SUB_BLOCK`` rows, and above that by halves, ``[[T, 0], [-B a_21
    T, B]]`` of the halves' inverses ``T`` and ``B``. Where the halves
    come down to sub-blocks of ``KDA_SUB_BLOCK`` rows (64 rows: four),
    those substitute as one batch, so the steps that run one after
    another are one sub-block's."""
    n, sub = a.shape[-1], KDA_SUB_BLOCK
    count = n // sub
    batched = None
    if n % sub == 0 and count > 1 and count & (count - 1) == 0:
        batched = _substituted(jnp.stack(
            [a[..., i:i + sub, i:i + sub] for i in range(0, n, sub)],
            axis=-3))

    def inverse(lo, hi):
        if hi - lo <= sub:
            return (_substituted(a[..., lo:hi, lo:hi]) if batched is None
                    else batched[..., lo // sub, :, :])
        mid = lo + (hi - lo) // 2
        top, bottom = inverse(lo, mid), inverse(mid, hi)
        corner = -jnp.matmul(jnp.matmul(bottom, a[..., mid:hi, lo:mid],
                                        precision=_HIGHEST),
                             top, precision=_HIGHEST)
        return jnp.concatenate([
            jnp.pad(top, [(0, 0)] * (a.ndim - 1) + [(0, hi - mid)]),
            jnp.concatenate([corner, bottom], axis=-1)], axis=-2)

    return inverse(0, n)


def _kda_within_chunks(q, k, v, a, beta):
    """What each chunk of the delta rule needs before the state carried
    into it is known; every operand (N, H, C, ...) float32, ``N`` chunks
    of ``C`` steps. With ``c`` the log-decay summed from the chunk's start
    and ``T = (I + strict_lower(beta_i sum_d k_id k_jd e^(c_id - c_jd)))^-1``
    (the WY form of the chunk's product of ``I - beta k k^T`` factors):
    ``W = T (beta k e^c)``, ``U0 = T (beta v)``, the causal ``P_ij = sum_d
    q_id k_jd e^(c_id - c_jd)``, ``q e^c``, ``k e^(c_end - c)`` and
    ``e^(c_end)``. A decay is always ``exp`` of a difference ``c_i - c_j``
    with ``i >= j``, at most 1; ``e^(-c_j)`` alone overflows on a fast
    channel.

    The two decayed products ``sum_d x_id k_jd e^(c_id - c_jd)`` (``x``
    the rows of ``beta k`` and of ``q``) are built by sub-blocks of ``s =
    KDA_SUB_BLOCK`` rows (a chunk that ``s`` does not divide is one
    sub-block). Every ``a <= 0``, so ``c`` never rises along the rows;
    ``r_I = c[s I]`` is the sum at sub-block ``I``'s first row. For a row
    ``i`` of sub-block ``I`` and a column ``j``:

    - ``j`` in the same sub-block: ``sum_d x_id k_jd exp(where(i >= j,
      c_id - c_jd, -inf))`` on (s, s, d), the sub-blocks of all chunks
      one batch axis: the only three-index tensor there is.
    - ``j`` in an earlier sub-block (``j < s I``): ``sum_d (x_id e^(c_id -
      r_Id)) (k_jd e^(r_Id - c_jd))``. Both exponents are at most 0 (``i
      >= s I > j``), so neither factor overflows, and one that underflows
      does so where the true product is smaller still. That is a plain
      product on the matrix unit: for each ``I >= 1`` the stacked rows
      ``[beta k; q]`` of the sub-block, (2 s, d), against the ``s I``
      earlier keys scaled for this ``I``.
    - ``j`` in a later sub-block: zero."""
    chunk, dim = q.shape[2:]
    sub = kda_sub_block(chunk)
    count = chunk // sub
    c = jnp.cumsum(a, axis=2)
    k_beta = k * beta[..., None]

    def blocks(x):      # (N, H, C, d) -> (N, H, C / s, s, d)
        return x.reshape(*x.shape[:2], count, sub, dim)

    c_sub, k_sub = blocks(c), blocks(k)
    causal = jnp.tril(jnp.ones((sub, sub), bool))
    decay = jnp.exp(jnp.where(
        causal[..., None], c_sub[..., :, None, :] - c_sub[..., None, :, :],
        -jnp.inf))

    def within_sub_block(rows):     # (N, H, C / s, s, s)
        return jnp.sum(blocks(rows)[..., :, None, :]
                       * k_sub[..., None, :, :] * decay, axis=-1)

    since_first = jnp.exp(c_sub - c_sub[..., :1, :])
    stacked = jnp.concatenate([blocks(k_beta) * since_first,
                               blocks(q) * since_first], axis=-2)

    def earlier(i):     # (N, H, 2 s, s i): sub-block i's rows, earlier keys
        keys = k[:, :, :sub * i] * jnp.exp(
            c_sub[:, :, i, :1] - c[:, :, :sub * i])
        return jnp.matmul(stacked[:, :, i], keys.swapaxes(-1, -2),
                          precision=_HIGHEST)

    before = [earlier(i) for i in range(count)]

    def lower(diagonal, rows):  # (N, H, C, C) of its sub-blocks
        return jnp.concatenate([
            jnp.pad(jnp.concatenate([before[i][:, :, rows],
                                     diagonal[:, :, i]], axis=-1),
                    [(0, 0)] * 3 + [(0, chunk - sub * (i + 1))])
            for i in range(count)], axis=-2)

    a_kk = lower(jnp.where(jnp.eye(sub, dtype=bool), 0.0,
                           within_sub_block(k_beta)), slice(0, sub))
    p_qk = lower(within_sub_block(q), slice(sub, None))
    solve = unit_lower_inverse(a_kk)
    from_start = jnp.exp(c)
    w = jnp.matmul(solve, k_beta * from_start, precision=_HIGHEST)
    u0 = jnp.matmul(solve, v * beta[..., None], precision=_HIGHEST)
    to_end = jnp.exp(c[:, :, -1:] - c)
    return (w, u0, p_qk, q * from_start, k * to_end,
            jnp.exp(c[:, :, -1]))


def kda_scan(q, k, v, a, beta, chunk):
    """The ``chunks`` arm: within a chunk by the WY form
    (``_kda_within_chunks``: one unit-lower-triangular inverse a chunk),
    ``KDA_CHUNKS_AT_ONCE`` chunks at a time under ``jax.checkpoint`` so
    that their sub-blocks' (rows, rows, d) decays never stand for the
    whole sequence; across chunks by the carried state, ``U = U0 - W S``, ``o =
    (q e^c) S + P U``, ``S <- Diag(e^(c_end)) S + (k e^(c_end - c))^T U``.
    ``q``, ``k``, ``v`` (B, L, H, d) in the compute dtype; the log-decays
    ``a`` (B, L, H, d), at most 0, and ``beta`` (B, L, H) float32. All of
    it runs in float32. Returns ``o`` (B, L, H, d) in ``v``'s dtype. A
    length that the chunk does not divide is padded with steps that
    leave the state as it is."""
    islands.guard("delta_rule", a=a, beta=beta)
    bsz, length, heads, dim = q.shape
    dtype = v.dtype
    pad = (-length) % chunk
    n = (length + pad) // chunk

    def chunked(x):     # (B, L, H, ...) -> (B n, H, C, ...)
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return x.reshape(bsz * n, chunk, *x.shape[2:]).swapaxes(1, 2)

    # the entry casts stand outside the island, as the exit cast does:
    # their gradients are casts down
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    with islands.scope("delta_rule"):
        operands = [chunked(x) for x in (q / math.sqrt(dim), k, v, a, beta)]
        at_once = math.gcd(bsz * n, KDA_CHUNKS_AT_ONCE)
        within = lax.map(
            jax.checkpoint(lambda xs: _kda_within_chunks(*xs)),
            [x.reshape(-1, at_once, *x.shape[1:]) for x in operands])
        # (n, B, H, ...): one step of the carry a chunk
        w, u0, p_qk, q_in, k_out, through = (
            x.reshape(bsz, n, *x.shape[2:]).swapaxes(0, 1) for x in within)

        def carry(state, inputs):
            w, u0, p_qk, q_in, k_out, through = inputs
            u = u0 - jnp.matmul(w, state, precision=_HIGHEST)
            out = (jnp.matmul(q_in, state, precision=_HIGHEST)
                   + jnp.matmul(p_qk, u, precision=_HIGHEST))
            state = through[..., None] * state + jnp.matmul(
                k_out.swapaxes(-1, -2), u, precision=_HIGHEST)
            return state, out

        _, out = lax.scan(carry, jnp.zeros((bsz, heads, dim, dim),
                                           jnp.float32),
                          (w, u0, p_qk, q_in, k_out, through))
    # (n, B, H, C, d) -> (B, L, H, d)
    out = out.transpose(1, 0, 3, 2, 4).reshape(bsz, n * chunk, heads, dim)
    return out[:, :length].astype(dtype)


# -------------------------------------------------------- the ``fused`` arm


def per_step(length, chunk, tile):
    """Chunks a grid step of a sweep at ``length``: the tile constant
    where it divides the sequence's chunks, else their common divisor."""
    return math.gcd(length // chunk, tile)


def residual_bytes(bsz, length, heads, head_dim, chunk):
    """Bytes of what the fused arm's forward sweep names
    ``KERNEL_RESIDUAL``, both float32: its output and the state each
    chunk starts from."""
    return bsz * heads * head_dim * 4 * (
        length + (length // chunk) * head_dim)


def fused_delta_rule(q, k, v, a, beta, chunk, tiles=TILES, interpret=False):
    """``kda_scan`` by the fused kernels. ``interpret`` runs them in
    Pallas's interpreter (the CPU tests). The kernels read and write
    float32; the casts of ``q``, ``k``, ``v`` and of the output stand
    here, outside the island, as ``kda_scan``'s do, where the compiler
    can drop a cast down that a cast up follows."""
    dtype = v.dtype
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    return _fused(q, k, v, a, beta, chunk, tiles, interpret).astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _fused(q, k, v, a, beta, chunk, tiles, interpret):
    return _sweep_forward(q, k, v, a, beta, chunk, tiles, interpret,
                          False)[0]


# One program a call site would trace and lower each sweep's kernel anew,
# a second and a half of every process's set-up a layer, compile cache or
# not. Under ``jax.jit`` the layers of one shape share one traced body and
# one lowered function, which the compiler inlines at each site under that
# site's ``op_name`` (as ``ops/grouped_matmul.py``'s kernels do).
_STATIC = ("heads", "chunk", "sub", "per_step", "scale", "interpret")
# lint: allow(bare-jit) -- inlined into the step program, never dispatched
_forward = jax.jit(kernel.forward, static_argnames=_STATIC + ("keep_states",))
# lint: allow(bare-jit) -- inlined into the step program, never dispatched
_backward = jax.jit(kernel.backward, static_argnames=_STATIC)


def _flat(x):
    return x.reshape(*x.shape[:2], -1)


def _sizes(q, chunk):
    return dict(heads=q.shape[2], chunk=chunk, sub=KDA_SUB_BLOCK,
                scale=1.0 / math.sqrt(q.shape[-1]))


def _sweep_forward(q, k, v, a, beta, chunk, tiles, interpret, keep_states):
    islands.guard("delta_rule", a=a, beta=beta)
    with islands.scope("delta_rule"):
        out, states = _forward(
            _flat(q), _flat(k), _flat(v), _flat(a), beta,
            per_step=per_step(q.shape[1], chunk, tiles.fwd),
            keep_states=keep_states, interpret=interpret, **_sizes(q, chunk))
    return out.reshape(v.shape), states


def _fused_fwd(q, k, v, a, beta, chunk, tiles, interpret):
    out, states = _sweep_forward(q, k, v, a, beta, chunk, tiles, interpret,
                                 True)
    # named before ``out`` is returned too: what follows reads ``out`` for
    # its own gradient, from the kept array
    out = checkpoint_name(out, KERNEL_RESIDUAL)
    states = checkpoint_name(states, KERNEL_RESIDUAL)
    return out, (q, k, v, a, beta, states)


def _fused_bwd(chunk, tiles, interpret, saved, d_out):
    q, k, v, a, beta, states = saved
    with islands.scope("delta_rule"):
        d_q, d_k, d_v, d_a, d_beta = _backward(
            _flat(q), _flat(k), _flat(v), _flat(a), beta, states,
            _flat(d_out), per_step=per_step(q.shape[1], chunk, tiles.bwd),
            interpret=interpret, **_sizes(q, chunk))
    return (d_q.reshape(q.shape), d_k.reshape(k.shape), d_v.reshape(v.shape),
            d_a.reshape(a.shape), d_beta)


_fused.defvjp(_fused_fwd, _fused_bwd)
