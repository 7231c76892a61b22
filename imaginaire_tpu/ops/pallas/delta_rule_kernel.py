"""The gated delta rule in chunks (the WY form), forward and backward, as
Pallas TPU kernels that keep a chunk's operands and the state on the chip.

Per head with state ``S`` (d, d), ``S_0 = 0``, log-decays ``a <= 0``:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(a_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t * scale

A chunk of ``C`` steps, with ``c`` the log-decay summed from the chunk's
start, ``A = strict_lower(beta_i sum_d k_id k_jd e^(c_id - c_jd))``, ``P_ij
= sum_d q_id k_jd e^(c_id - c_jd)`` (``i >= j``) and ``T = (I + A)^-1``:

    W = T (beta k e^c)      U = T (beta v) - W S
    o = (q e^c) S + P U     S <- Diag(e^(c_end)) S + (k e^(c_end - c))^T U

Two sweeps over (sequence, rows of ``TILES`` chunks, head), the head
innermost: every head's float32 state stands in VMEM scratch from the
first chunk to the last, and a grid step reads its rows of ``q``, ``k``,
``v``, ``a`` as a (rows, d) block of the model's own (B, L, H * d) layout
at column block ``h`` (no transpose on the way in or out) and ``beta`` as
the (rows, H) block all heads of those rows share.

  ``forward``   ``o`` in ``v``'s dtype (float32 as ``ops/delta_rule.py``
                calls it) and, where asked, the state each
                chunk starts from (the one thing of the forward sweep the
                backward sweep cannot rebuild from a chunk's operands)
  ``backward``  the chunks in reverse with ``dS`` carried in VMEM; a
                chunk's ``c``, ``T``, ``W``, ``U``, ``P`` are rebuilt in
                VMEM from its operands and its entry state, so nothing
                (C, C) or (C, C, d) ever reaches HBM; returns ``dq``,
                ``dk``, ``dv``, ``da``, ``dbeta``

Everything between the loads and the stores is float32 and every product
on the matrix unit ``Precision.HIGHEST``. A decay is only ever ``exp`` of
a difference that is at most 0, forward and backward: the two decayed
products are built by sub-blocks of ``sub`` rows (``_within``), a
sub-block against itself column by column on (sub, d) tiles with
``exp(c_i - c_j)`` masked to ``i >= j``, a sub-block against the earlier
ones as one product of rows scaled by ``e^(c_i - r)`` with keys scaled by
``e^(r - c_j)``, ``r`` the sum at the sub-block's first row; their
gradients by the same factoring (``_chunk_backward``). ``T`` is held
transposed: a column of ``A`` is then the coefficients of a row of a
diagonal sub-block's inverse (``sub - 1`` dependent steps, the chunk's
sub-blocks side by side), and the sub-blocks' inverses merge by halves as
``unit_lower_inverse`` merges them. The state is held transposed too (the
decay ``e^(c_end)`` then scales its lanes).

``ops/delta_rule.py`` holds the rule of the arm and the tile constants.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
_VMEM_LIMIT = 64 * 1024 * 1024


def _dot(a, b, dims=_NN):
    return lax.dot_general(a, b, dims, precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _stacked(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _lower_ones(chunk):
    """The (C, C) lower triangle of ones: ``_lower_ones @ a`` sums ``a``
    down its rows, its transpose back up them."""
    below = _iota((chunk, chunk), 0) >= _iota((chunk, chunk), 1)
    return below.astype(jnp.float32)


def _decays(a):
    """``c`` (C, d), ``e^c``, ``e^(c_end - c)`` and ``e^(c_end)`` (1, d)
    of a chunk's log-decays; the sum runs on the matrix unit."""
    chunk = a.shape[0]
    c = _dot(_lower_ones(chunk), a)
    c_end = c[chunk - 1:chunk]
    return c, jnp.exp(c), jnp.exp(c_end - c), jnp.exp(c_end)


def _sub_block_scales(c, lo, sub):
    """For the sub-block from row ``lo``: ``e^(c_i - r)`` of its rows
    (sub, d) and ``e^(r - c_j)`` of the chunk's rows, 0 from ``lo`` on
    (C, 1 or d): both exponents are at most 0."""
    first = c[lo:lo + 1]
    earlier = _iota((c.shape[0], 1), 0) < lo
    return (jnp.exp(c[lo:lo + sub] - first),
            jnp.exp(jnp.where(earlier, first - c, -jnp.inf)))


def _column_decay(c_rows, j):
    """``e^(c_i - c_j)`` of a sub-block's rows ``i >= j``, 0 above."""
    keep = _iota((c_rows.shape[0], 1), 0) >= j
    return jnp.exp(jnp.where(keep, c_rows - c_rows[j:j + 1], -jnp.inf))


def _within(q, k, kb, c, sub):
    """Of a chunk: ``A``'s sub-blocks below the diagonal ones (C, C),
    ``P`` (C, C), and the diagonal sub-blocks' ``(I + A_II)^-1``
    transposed, each in its place on the diagonal (C, C)."""
    chunk = q.shape[0]
    row = _iota((sub, 1), 0)
    lane = _iota((1, chunk), 1)
    below, causal, inverse = [], [], []
    for lo in range(0, chunk, sub):
        ci, ki, kbi, qi = (x[lo:lo + sub] for x in (c, k, kb, q))
        if lo:
            since, until = _sub_block_scales(c, lo, sub)
            both = _dot(jnp.concatenate([kbi * since, qi * since], axis=0),
                        k * until, _NT)
            a_i, p_i = both[:sub], both[sub:]
        else:
            a_i = p_i = jnp.zeros((sub, chunk), jnp.float32)
        inv_t = (lane == lo + row).astype(jnp.float32)
        for j in reversed(range(sub)):
            ke = _column_decay(ci, j) * ki[j:j + 1]
            p_i = jnp.where(lane == lo + j,
                            jnp.sum(qi * ke, axis=1, keepdims=True), p_i)
            if j < sub - 1:     # row j of the transposed inverse
                col = jnp.where(row > j,
                                jnp.sum(kbi * ke, axis=1, keepdims=True), 0.0)
                inv_t = jnp.where(
                    row == j,
                    inv_t - jnp.sum(col * inv_t, axis=0, keepdims=True),
                    inv_t)
        below.append(a_i)
        causal.append(p_i)
        inverse.append(inv_t)
    return _stacked(below), _stacked(causal), _stacked(inverse)


def _merged(inv_t, below, sub):
    """``T^T`` (C, C) from the diagonal sub-blocks' transposed inverses
    and ``A``'s sub-blocks below them, by halves: ``[[T, 0], [-B a_21 T,
    B]]`` of two halves' inverses, level by level."""
    chunk = inv_t.shape[0]
    row, col = _iota((chunk, chunk), 0), _iota((chunk, chunk), 1)
    half = sub
    while half < chunk:
        # the lower-left half-block of each pair of halves
        corner = ((row & half) != 0) & (((row ^ col) & -half) == half)
        picked = jnp.where(corner, below, 0.0)
        inv_t = inv_t - _dot(_dot(inv_t, picked, _NT), inv_t)
        half *= 2
    return inv_t


def _solved(solve_t, k_in, vb, state_t, q_in=None):
    """``W = T k_in``, ``U = T (beta v) - W S`` and, given ``q_in``, ``q_in
    S``: the two products with ``T`` as one, and the two with the state as
    one."""
    chunk, dim = k_in.shape
    solved = _dot(solve_t, jnp.concatenate([k_in, vb], axis=1), _TN)
    w = solved[:, :dim]
    if q_in is None:
        return w, solved[:, dim:] - _dot(w, state_t, _NT), None
    both = _dot(jnp.concatenate([w, q_in], axis=0), state_t, _NT)
    return w, solved[:, dim:] - both[:chunk], both[chunk:]


def _chunk_forward(q, k, v, a, beta, state_t, sub):
    """One chunk of one head: (``o`` (C, d), the state after it). ``q``
    scaled; ``beta`` (C, 1); ``state_t`` the state transposed (d, d)."""
    c, e_in, e_out, through = _decays(a)
    kb = k * beta
    below, causal, inv_t = _within(q, k, kb, c, sub)
    solve_t = _merged(inv_t, below, sub)
    w, u, from_state = _solved(solve_t, kb * e_in, v * beta, state_t,
                               q * e_in)
    out = from_state + _dot(causal, u)
    return out, state_t * through + _dot(u, k * e_out, _TN)


def _chunk_backward(q, k, v, a, beta, state_t, d_out, d_next, sub):
    """One chunk of one head, given its entry state, ``dO`` and the next
    chunk's ``dS`` (transposed): (``dq``, ``dk``, ``dv``, ``da`` (C, d),
    ``dbeta`` (C, 1), this chunk's ``dS``)."""
    chunk, dim = q.shape
    row = _iota((sub, 1), 0)
    rows, cols = _iota((chunk, 1), 0), _iota((1, chunk), 1)
    c, e_in, e_out, through = _decays(a)
    kb, vb = k * beta, v * beta
    below, causal, inv_t = _within(q, k, kb, c, sub)
    solve_t = _merged(inv_t, below, sub)
    k_in, q_in, k_out = kb * e_in, q * e_in, k * e_out
    w, u, _ = _solved(solve_t, k_in, vb, state_t)

    # the carry: o = q_in S + P U, S' = Diag(through) S + k_out^T U
    d_u = _dot(causal, d_out, _TN) + _dot(k_out, d_next, _NT)
    both = _dot(jnp.concatenate([d_u, d_out], axis=0), state_t)
    d_w, d_q_in = -both[:chunk], both[chunk:]
    d_state = d_next * through + _dot(
        jnp.concatenate([d_out, d_u], axis=0),
        jnp.concatenate([q_in, -w], axis=0), _TN)
    d_k_out = _dot(u, d_next)
    d_through = jnp.sum(state_t * d_next, axis=0, keepdims=True)
    d_causal = jnp.where(rows >= cols, _dot(d_out, u, _NT), 0.0)
    # W = T k_in, U0 = T (beta v), T = (I + A)^-1
    d_solved = jnp.concatenate([d_w, d_u], axis=1)
    d_solve = _dot(d_solved, jnp.concatenate([k_in, vb], axis=1), _NT)
    d_below = jnp.where(
        rows > cols, -_dot(_dot(solve_t, d_solve), solve_t), 0.0)
    d_both = _dot(solve_t, d_solved)
    d_k_in, d_vb = d_both[:, :dim], d_both[:, dim:]
    lost = d_k_out * k_out
    d_kb = d_k_in * e_in
    d_q = d_q_in * e_in
    d_k = d_k_out * e_out
    d_c = d_k_in * k_in + d_q_in * q_in - lost
    d_c_end = jnp.sum(lost, axis=0, keepdims=True) + d_through * through

    # A and P: sum_d x_id k_jd e^(c_id - c_jd), x the rows of beta k, of q
    d_k_blocks, d_kb_blocks, d_q_blocks, d_c_blocks = [], [], [], []
    for lo in range(0, chunk, sub):
        ci, ki, kbi, qi = (x[lo:lo + sub] for x in (c, k, kb, q))
        da_i, dp_i = d_below[lo:lo + sub], d_causal[lo:lo + sub]
        d_ki = jnp.zeros_like(ki)
        if lo:      # against the earlier sub-blocks: one product each way
            since, until = _sub_block_scales(c, lo, sub)
            scaled = jnp.concatenate([kbi * since, qi * since], axis=0)
            keys = k * until
            d_both = jnp.concatenate([da_i, dp_i], axis=0)
            d_scaled = _dot(d_both, keys)
            d_keys = _dot(d_both, scaled, _TN)
            d_kbi, d_qi = d_scaled[:sub] * since, d_scaled[sub:] * since
            moved = d_scaled * scaled
            moved = moved[:sub] + moved[sub:]
            kept = d_keys * keys
            d_k = d_k + d_keys * until
            d_c = d_c - kept
            d_first = (jnp.sum(kept, axis=0, keepdims=True)
                       - jnp.sum(moved, axis=0, keepdims=True))
            d_ci = moved + jnp.where(row == 0, d_first, 0.0)
        else:
            d_kbi, d_qi, d_ci = (jnp.zeros_like(ki) for _ in range(3))
        for j in range(sub):    # against itself, column by column
            decay = _column_decay(ci, j)
            kj = ki[j:j + 1]
            ke = decay * kj
            col_a = da_i[:, lo + j:lo + j + 1]
            col_p = dp_i[:, lo + j:lo + j + 1]
            pulled = col_a * kbi + col_p * qi
            d_kj = jnp.sum(pulled * decay, axis=0, keepdims=True)
            d_kbi = d_kbi + col_a * ke
            d_qi = d_qi + col_p * ke
            d_ci = d_ci + pulled * ke - jnp.where(row == j, kj * d_kj, 0.0)
            d_ki = jnp.where(row == j, d_kj, d_ki)
        d_k_blocks.append(d_ki)
        d_kb_blocks.append(d_kbi)
        d_q_blocks.append(d_qi)
        d_c_blocks.append(d_ci)
    d_kb = d_kb + _stacked(d_kb_blocks)
    d_q = d_q + _stacked(d_q_blocks)
    d_c = (d_c + _stacked(d_c_blocks)
           + jnp.where(rows == chunk - 1, d_c_end, 0.0))
    d_a = _dot(_lower_ones(chunk), d_c, _TN)
    d_beta = jnp.sum(d_kb * k + d_vb * v, axis=1, keepdims=True)
    d_k = d_k + _stacked(d_k_blocks) + d_kb * beta
    return d_q, d_k, d_vb * beta, d_a, d_beta, d_state


# ---------------------------------------------------------------- sweeps


def _head_column(block, h):
    """Column ``h`` of a (rows, H) block as (rows, 1)."""
    lanes = _iota(block.shape, 1)
    return jnp.sum(jnp.where(lanes == h, block, 0.0), axis=1, keepdims=True)


def _fwd_kernel(q_ref, k_ref, v_ref, a_ref, beta_ref, o_ref, *rest,
                chunk, sub, scale, keep_states):
    states_ref, state = rest if keep_states else (None, *rest)
    i, h = pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _():
        state[h] = jnp.zeros(state.shape[1:], state.dtype)

    def one_chunk(n, state_t):
        at = pl.ds(pl.multiple_of(n * chunk, chunk), chunk)
        if keep_states:
            states_ref[n] = state_t
        out, state_t = _chunk_forward(
            q_ref[at].astype(jnp.float32) * scale,
            k_ref[at].astype(jnp.float32), v_ref[at].astype(jnp.float32),
            a_ref[at], _head_column(beta_ref[at], h), state_t, sub)
        o_ref[at] = out.astype(o_ref.dtype)
        return state_t

    state[h] = lax.fori_loop(0, q_ref.shape[0] // chunk, one_chunk, state[h])


def _bwd_kernel(q_ref, k_ref, v_ref, a_ref, beta_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, da_ref, dbeta_ref, d_state,
                *, chunk, sub, scale):
    i, h = pl.program_id(1), pl.program_id(2)
    per_step = q_ref.shape[0] // chunk

    @pl.when(i == 0)
    def _():
        d_state[h] = jnp.zeros(d_state.shape[1:], d_state.dtype)

    def one_chunk(m, d_next):
        n = per_step - 1 - m
        at = pl.ds(pl.multiple_of(n * chunk, chunk), chunk)
        d_q, d_k, d_v, d_a, d_beta, d_next = _chunk_backward(
            q_ref[at].astype(jnp.float32) * scale,
            k_ref[at].astype(jnp.float32), v_ref[at].astype(jnp.float32),
            a_ref[at], _head_column(beta_ref[at], h), states_ref[n],
            do_ref[at].astype(jnp.float32), d_next, sub)
        dq_ref[at] = (d_q * scale).astype(dq_ref.dtype)
        dk_ref[at] = d_k.astype(dk_ref.dtype)
        dv_ref[at] = d_v.astype(dv_ref.dtype)
        da_ref[at] = d_a
        # this head's column of the block all heads of these rows share
        heads = _iota((chunk, dbeta_ref.shape[1]), 1)
        dbeta_ref[at] = jnp.where(heads == h, d_beta, dbeta_ref[at])
        return d_next

    d_state[h] = lax.fori_loop(0, per_step, one_chunk, d_state[h])


def _sweep(q, heads, chunk, per_step, reverse):
    """The grid (sequence, rows of ``per_step`` chunks, head) and the
    block specs of a (rows, d) operand, the rows' (rows, H) ``beta`` and
    their chunks' (per_step, d, d) entry states; ``reverse`` walks the
    rows from the last."""
    bsz, length, width = q.shape
    head_dim, rows = width // heads, per_step * chunk
    steps = length // rows

    def at(i):
        return steps - 1 - i if reverse else i

    return ((bsz, steps, heads),
            pl.BlockSpec((None, rows, head_dim),
                         lambda b, i, h: (b, at(i), h)),
            pl.BlockSpec((None, rows, heads), lambda b, i, h: (b, at(i), 0)),
            pl.BlockSpec((None, None, per_step, head_dim, head_dim),
                         lambda b, i, h: (b, h, at(i), 0, 0)))


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT)


def forward(q, k, v, a, beta, heads, chunk, sub, per_step, scale,
            keep_states=True, interpret=False):
    """``q``, ``k``, ``v`` (B, L, H * d), ``a`` (B, L, H * d) float32,
    ``beta`` (B, L, H) float32; ``L`` a multiple of ``per_step * chunk``.
    Returns ``o`` (B, L, H * d) in ``v``'s dtype and, with
    ``keep_states``, the transposed state each chunk starts from (B, H,
    L / chunk, d, d) float32."""
    bsz, length, width = q.shape
    head_dim = width // heads
    grid, rows_spec, beta_spec, states_spec = _sweep(
        q, heads, chunk, per_step, False)
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype)]
    out_specs = [rows_spec]
    if keep_states:
        out_shape.append(jax.ShapeDtypeStruct(
            (bsz, heads, length // chunk, head_dim, head_dim), jnp.float32))
        out_specs.append(states_spec)
    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, sub=sub, scale=scale,
                          keep_states=keep_states),
        grid=grid,
        in_specs=[rows_spec] * 4 + [beta_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, head_dim, head_dim),
                                   jnp.float32)],
        compiler_params=_PARAMS,
        name="delta_rule_fwd",
        interpret=interpret,
    )(q, k, v, a, beta)
    return tuple(outs) if keep_states else (outs[0], None)


def backward(q, k, v, a, beta, states, d_out, heads, chunk, sub, per_step,
             scale, interpret=False):
    """(``dq``, ``dk``, ``dv`` in their operands' dtypes, ``da``, ``dbeta``
    float32) from ``forward``'s operands, its entry states and ``d_out``
    (B, L, H * d)."""
    head_dim = q.shape[-1] // heads
    grid, rows_spec, beta_spec, states_spec = _sweep(
        q, heads, chunk, per_step, True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, sub=sub, scale=scale),
        grid=grid,
        in_specs=[rows_spec] * 4 + [beta_spec, states_spec, rows_spec],
        out_specs=[rows_spec] * 4 + [beta_spec],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (q, k, v, a, beta)],
        scratch_shapes=[pltpu.VMEM((heads, head_dim, head_dim),
                                   jnp.float32)],
        compiler_params=_PARAMS,
        name="delta_rule_bwd",
        interpret=interpret,
    )(q, k, v, a, beta, states, d_out)
