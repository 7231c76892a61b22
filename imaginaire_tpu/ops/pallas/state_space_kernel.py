"""The Mamba-2 recurrence in chunks (the state-space dual form), forward
and backward, as Pallas TPU kernels that keep a chunk's decay matrices and
every head's state on the chip.

Per head with state ``S`` (P, N), ``S_0 = 0``, step sizes ``dt >= 0`` and
a decay rate ``a <= 0``; head ``h`` reads ``b``, ``c`` of group ``h //
(H/G)``:

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t b_t^T,    y_t = S_t c_t

A chunk of ``Q`` steps, with ``cum`` the log-decay ``dt a`` summed from the
chunk's start and ``D_ls = exp(cum_l - cum_s)`` (``l >= s``, else 0):

    y = ((c b^T) * D) (dt x) + exp(cum) (c S^T)
    S <- exp(cum_end) S + (dt x exp(cum_end - cum))^T b

Two sweeps over (sequence, rows of ``TILES`` chunks, group), the group
innermost: every head's float32 state stands in VMEM scratch from the first
chunk to the last, transposed and with a group's heads side by side ((N,
H/G * P): the products that read and write it are then as wide as the
matrix unit). A grid step reads its rows of ``x`` as a (rows, H/G * P)
block of the model's own (B, L, H * P) layout at column block ``g``, ``b``
and ``c`` as (rows, N) blocks of (B, L, G * N), and ``dt`` as the (rows, H)
block all groups of those rows share: no transpose on the way in or out.
The log-decays are summed once a tile for every head by a float32 product
with a triangle of ones, and held as a column a head (Q, H) and, turned,
as a row a head (H, Q); a head's (Q, Q) decays are ``exp`` of a column
less a row, masked to ``l >= s``, so the exponent is never positive and
the diagonal is 1 to the bit.

  ``forward``   ``y`` in ``x``'s dtype and, where asked, the state each
                chunk starts from in float32 (the one thing of the forward
                sweep the backward sweep cannot rebuild from a chunk's
                operands)
  ``backward``  the chunks in reverse with ``dS`` carried in VMEM; a
                chunk's decays, ``c b^T``, weights, ``dt x`` are rebuilt in
                VMEM, so nothing (Q, Q) ever reaches HBM; returns ``dx``,
                ``db``, ``dc``, ``ddt`` and the gradient of ``dt a``
                (``da`` is its sum against ``dt``)

The rounding points are the ``chunks`` arm's (``ops/state_space.py``):
products on the matrix unit in the operands' dtype (bfloat16 as the model
calls it) with float32 accumulation; step sizes, decays, their sums and the
carried state float32. The weights, ``dt x``, its decayed copy and the
state that ``c`` reads are rounded to the operands' dtype before their
products, as there; a gradient that crosses such a rounding stays float32.

``ops/state_space.py`` holds the rule of the arm and the tile constants.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
_VMEM_LIMIT = 64 * 1024 * 1024


def _dot(a, b, dims=_NN):
    """A product in the operands' dtype, summed in float32."""
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _dot32(a, b, dims=_NN):
    """A float32 product that stays float32 on the matrix unit: the sums
    of log-decays and of their gradients."""
    return lax.dot_general(a, b, dims, precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _ones(chunk, upper):
    """The (Q, Q) lower triangle of ones (``@ v`` sums ``v`` down its
    rows) or the upper one (``@ v`` sums it back up them)."""
    rows, cols = _iota((chunk, chunk), 0), _iota((chunk, chunk), 1)
    return (rows <= cols if upper else rows >= cols).astype(jnp.float32)


def _summed(log_decay):
    """``cum`` of a chunk's log-decays (Q, H): a column a head (Q, H) and
    a row a head (H, Q)."""
    cum = _dot32(_ones(log_decay.shape[0], False), log_decay)
    return cum, cum.T


def _decays(cum_col, cum_row):
    """``exp(cum_l - cum_s)`` of one head, 0 above the diagonal: (Q, Q)
    from its column (Q, 1) and its row (1, Q)."""
    chunk = cum_col.shape[0]
    lower = _iota((chunk, chunk), 0) >= _iota((chunk, chunk), 1)
    return jnp.exp(jnp.where(lower, cum_col - cum_row, -jnp.inf))


def _head_of_lane(head_dim):
    """Which of a lane tile's heads a lane belongs to: (1, LANES)."""
    return _iota((1, LANES), 1) // head_dim


def _spread(cols, head_dim):
    """The (Q, 1) columns of a lane tile's heads, each over its head's
    lanes: (Q, LANES)."""
    which = _head_of_lane(head_dim)
    out = cols[-1]
    for k in reversed(range(len(cols) - 1)):
        out = jnp.where(which == k, cols[k], out)
    return jnp.broadcast_to(out, (cols[0].shape[0], LANES))


def _head_sums(v, head_dim):
    """``v`` (Q, LANES) summed over each of the lane tile's heads: a list
    of (Q, 1)."""
    count = LANES // head_dim
    if count == 1:
        return [jnp.sum(v, axis=1, keepdims=True)]
    which = _head_of_lane(head_dim)
    return [jnp.sum(jnp.where(which == k, v, 0.0), axis=1, keepdims=True)
            for k in range(count)]


def _lane_tiles(width):
    return [slice(lo, lo + LANES) for lo in range(0, width, LANES)]


def _joined(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _chunk_forward(x, b, c, dt_cols, cum_cols, cum_rows, state_t, head_dim):
    """One chunk of one group: (``y`` (Q, W) float32, the state after it).
    ``x`` (Q, W) the group's heads side by side, ``b``, ``c`` (Q, N);
    ``dt_cols``, ``cum_cols`` a (Q, 1) column a head, ``cum_rows`` (H/G,
    Q); ``state_t`` the group's states transposed (N, W)."""
    dtype = x.dtype
    chunk = x.shape[0]
    in_tile = LANES // head_dim
    which = _head_of_lane(head_dim)
    cb = _dot(c, b, _NT)
    read = _dot(c, state_t.astype(dtype))
    ys, decayed, through = [], [], []
    for t, tile in enumerate(_lane_tiles(x.shape[1])):
        heads = range(t * in_tile, (t + 1) * in_tile)
        dt = _spread([dt_cols[h] for h in heads], head_dim)
        cum = _spread([cum_cols[h] for h in heads], head_dim)
        last = cum[chunk - 1:chunk]
        xdt32 = x[:, tile].astype(jnp.float32) * dt
        xdt = xdt32.astype(dtype)
        decayed.append((xdt32 * jnp.exp(last - cum)).astype(dtype))
        through.append(jnp.exp(last))
        y = None
        for k, h in enumerate(heads):
            weights = (cb * _decays(cum_cols[h], cum_rows[h:h + 1]))
            part = _dot(weights.astype(dtype), xdt)
            y = part if y is None else jnp.where(which == k, part, y)
        ys.append(y + read[:, tile] * jnp.exp(cum))
    state_t = (state_t * _joined(through)
               + _dot(b, _joined(decayed), _TN))
    return _joined(ys), state_t


def _chunk_backward(x, b, c, d_y, dt_cols, cum_cols, cum_rows, state_t,
                    d_next, head_dim):
    """One chunk of one group, given its entry states, ``dy`` and the next
    chunk's ``dS`` (transposed, (N, W)): ``dx`` (Q, W), ``db``, ``dc`` (Q,
    N) float32; a head's share of the gradient of ``cum`` as a column
    (Q, 1) to add and a row (1, Q) to take away, and of ``dt``'s through
    ``dt x`` as a column (three lists, a head each); this chunk's ``dS``."""
    dtype = x.dtype
    chunk = x.shape[0]
    in_tile = LANES // head_dim
    which = _head_of_lane(head_dim)
    is_last = _iota((chunk, 1), 0) == chunk - 1
    cb = _dot(c, b, _NT)
    state_lo, d_next_lo = state_t.astype(dtype), d_next.astype(dtype)
    read = _dot(c, state_lo)
    d_decayed = _dot(b, d_next_lo)
    d_cb = jnp.zeros_like(cb)
    d_x, d_read, decayed, through = [], [], [], []
    d_cum_cols, d_cum_rows, d_dt_cols = [], [], []
    for t, tile in enumerate(_lane_tiles(x.shape[1])):
        heads = range(t * in_tile, (t + 1) * in_tile)
        dt = _spread([dt_cols[h] for h in heads], head_dim)
        cum = _spread([cum_cols[h] for h in heads], head_dim)
        last = cum[chunk - 1:chunk]
        to_end, from_start, decay_end = (jnp.exp(last - cum), jnp.exp(cum),
                                         jnp.exp(last))
        x32 = x[:, tile].astype(jnp.float32)
        xdt32 = x32 * dt
        xdt = xdt32.astype(dtype)
        decayed32 = xdt32 * to_end
        decayed.append(decayed32.astype(dtype))
        through.append(decay_end)
        d_y_lo = d_y[:, tile]
        d_y32 = d_y_lo.astype(jnp.float32)
        d_read32 = d_y32 * from_start
        d_read.append(d_read32.astype(dtype))
        # what the chunk's last sum gathers: exp(cum_end) on the state and
        # exp(cum_end - cum) on what the chunk adds to it
        lost = d_decayed[:, tile] * decayed32
        at_end = (jnp.sum(lost, axis=0, keepdims=True)
                  + jnp.sum(d_next[:, tile] * state_t[:, tile], axis=0,
                            keepdims=True) * decay_end)
        d_xdt32 = d_decayed[:, tile] * to_end
        d_within = None
        for k, h in enumerate(heads):
            decays = _decays(cum_cols[h], cum_rows[h:h + 1])
            weights32 = cb * decays
            mine = d_y_lo if in_tile == 1 else jnp.where(
                which == k, d_y_lo, jnp.zeros_like(d_y_lo))
            d_weights = _dot(mine, xdt, _NT)
            part = _dot(weights32.astype(dtype), d_y_lo, _TN)
            d_within = part if d_within is None else jnp.where(
                which == k, part, d_within)
            d_cb = d_cb + d_weights * decays
            moved = d_weights * weights32
            d_cum_rows.append(jnp.sum(moved, axis=0, keepdims=True))
            d_cum_cols.append(jnp.sum(moved, axis=1, keepdims=True))
        d_xdt32 = d_xdt32 + d_within
        d_x.append(d_xdt32 * dt)
        ends = _head_sums(at_end, head_dim)
        for k, (col, through_dt) in enumerate(zip(
                _head_sums(d_read32 * read[:, tile] - lost, head_dim),
                _head_sums(d_xdt32 * x32, head_dim))):
            at = t * in_tile + k
            d_cum_cols[at] = (d_cum_cols[at] + col
                              + jnp.where(is_last, ends[k], 0.0))
            d_dt_cols.append(through_dt)
    d_read, decayed = _joined(d_read), _joined(decayed)
    d_cb = d_cb.astype(dtype)
    d_c = _dot(d_read, state_lo, _NT) + _dot(d_cb, b)
    d_b = _dot(decayed, d_next_lo, _NT) + _dot(d_cb, c, _TN)
    d_state = d_next * _joined(through) + _dot(c, d_read, _TN)
    return (_joined(d_x), d_b, d_c, d_cum_cols, d_cum_rows, d_dt_cols,
            d_state)


# ---------------------------------------------------------------- sweeps


def _head_column(block, h):
    """Column ``h`` of a (rows, H) block as (rows, 1)."""
    lanes = _iota(block.shape, 1)
    return jnp.sum(jnp.where(lanes == h, block, 0.0), axis=1, keepdims=True)


def _group_columns(block, g, per):
    """The columns of group ``g``'s heads of a (rows, H) block: a list of
    (rows, 1)."""
    return [_head_column(block, g * per + j) for j in range(per)]


def _sum_decays(dt_ref, a_ref, cum_c, cum_r, chunk):
    """Every head's ``cum`` for the tile's chunks, both ways, into
    scratch."""
    def one_chunk(n, _):
        at = pl.ds(pl.multiple_of(n * chunk, chunk), chunk)
        cum_c[n], cum_r[n] = _summed(dt_ref[at] * a_ref[...])
        return 0

    lax.fori_loop(0, dt_ref.shape[0] // chunk, one_chunk, 0)


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, y_ref, *rest, chunk,
                head_dim, keep_states):
    states_ref, state, cum_c, cum_r = rest if keep_states else (None, *rest)
    i, g = pl.program_id(1), pl.program_id(2)
    per = x_ref.shape[1] // head_dim

    @pl.when(i == 0)
    def _():
        state[g] = jnp.zeros(state.shape[1:], state.dtype)

    @pl.when(g == 0)
    def _():
        _sum_decays(dt_ref, a_ref, cum_c, cum_r, chunk)

    def one_chunk(n, state_t):
        at = pl.ds(pl.multiple_of(n * chunk, chunk), chunk)
        if keep_states:
            states_ref[n] = state_t
        rows = cum_r[n, pl.ds(pl.multiple_of(g * per, SUBLANES), per), :]
        y, state_t = _chunk_forward(
            x_ref[at], b_ref[at], c_ref[at],
            _group_columns(dt_ref[at], g, per),
            _group_columns(cum_c[n], g, per), rows, state_t, head_dim)
        y_ref[at] = y.astype(y_ref.dtype)
        return state_t

    state[g] = lax.fori_loop(0, x_ref.shape[0] // chunk, one_chunk, state[g])


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, states_ref, dy_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, dla_ref,
                d_state, cum_c, cum_r, d_cum_c, d_cum_r, d_dt_c,
                *, chunk, head_dim):
    i, g = pl.program_id(1), pl.program_id(2)
    per = x_ref.shape[1] // head_dim
    count = x_ref.shape[0] // chunk
    heads = dt_ref.shape[1]

    @pl.when(i == 0)
    def _():
        d_state[g] = jnp.zeros(d_state.shape[1:], d_state.dtype)

    @pl.when(g == 0)
    def _():
        _sum_decays(dt_ref, a_ref, cum_c, cum_r, chunk)

    def one_chunk(m, d_next):
        n = count - 1 - m
        at = pl.ds(pl.multiple_of(n * chunk, chunk), chunk)
        mine = pl.ds(pl.multiple_of(g * per, SUBLANES), per)
        d_x, d_b, d_c, cols, rows, through_dt, d_next = _chunk_backward(
            x_ref[at], b_ref[at], c_ref[at], dy_ref[at],
            _group_columns(dt_ref[at], g, per),
            _group_columns(cum_c[n], g, per), cum_r[n, mine, :],
            states_ref[n], d_next, head_dim)
        dx_ref[at] = d_x.astype(dx_ref.dtype)
        db_ref[at] = d_b.astype(db_ref.dtype)
        dc_ref[at] = d_c.astype(dc_ref.dtype)
        # this group's columns of the (Q, H) sums all groups share, and
        # its rows of the (H, Q) one
        lane = _iota((chunk, heads), 1)
        d_cum, d_dt = d_cum_c[n], d_dt_c[n]
        for j in range(per):
            d_cum = jnp.where(lane == g * per + j, cols[j], d_cum)
            d_dt = jnp.where(lane == g * per + j, through_dt[j], d_dt)
        d_cum_c[n], d_dt_c[n] = d_cum, d_dt
        row = _iota((per, chunk), 0)
        d_rows = jnp.zeros((per, chunk), jnp.float32)
        for j in range(per):
            d_rows = jnp.where(row == j, rows[j], d_rows)
        d_cum_r[n, mine, :] = d_rows
        return d_next

    d_state[g] = lax.fori_loop(0, count, one_chunk, d_state[g])

    @pl.when(g == pl.num_programs(2) - 1)
    def _():
        # d(dt a): the gradient of ``cum`` summed back up the chunk
        def one_chunk(n, _):
            at = pl.ds(pl.multiple_of(n * chunk, chunk), chunk)
            upper = _ones(chunk, True)
            d_log = (_dot32(upper, d_cum_c[n])
                     - _dot32(upper, d_cum_r[n], _NT))
            dla_ref[at] = d_log
            ddt_ref[at] = d_log * a_ref[...] + d_dt_c[n]
            return 0

        lax.fori_loop(0, count, one_chunk, 0)


class _Sweep(NamedTuple):
    """A sweep's grid (sequence, rows of ``per_step`` chunks, group) and
    its block specs: ``rows(width)`` a group's (rows, width) columns of a
    (B, L, G * width) operand, ``dt`` the rows' (rows, H) step sizes all
    groups share, ``a`` the (1, H) rates, ``states(width)`` the group's
    (per_step, N, width) entry states of the rows' chunks."""
    grid: tuple
    rows: Callable
    dt: pl.BlockSpec
    a: pl.BlockSpec
    states: Callable


def _sweep(x, dt, groups, state_dim, chunk, per_step, reverse):
    """``reverse`` walks the rows from the last."""
    bsz, length, _ = x.shape
    heads = dt.shape[-1]
    rows = per_step * chunk
    steps = length // rows

    def at(i):
        return steps - 1 - i if reverse else i

    return _Sweep(
        grid=(bsz, steps, groups),
        rows=lambda width: pl.BlockSpec((None, rows, width),
                                        lambda s, i, g: (s, at(i), g)),
        dt=pl.BlockSpec((None, rows, heads), lambda s, i, g: (s, at(i), 0)),
        a=pl.BlockSpec((1, heads), lambda s, i, g: (0, 0)),
        states=lambda width: pl.BlockSpec(
            (None, per_step, state_dim, width),
            lambda s, i, g: (s, at(i), 0, g)))


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT)


def _scratch(x, dt, groups, state_dim, chunk, per_step, sums):
    """Every group's states (G, N, W) and ``sums`` pairs of a tile's (Q,
    H) and (H, Q) sums a chunk: ``cum`` and, backward, its gradient."""
    heads = dt.shape[-1]
    return [pltpu.VMEM((groups, state_dim, x.shape[-1] // groups),
                       jnp.float32)] + sums * [
        pltpu.VMEM((per_step, chunk, heads), jnp.float32),
        pltpu.VMEM((per_step, heads, chunk), jnp.float32)]


def forward(x, b, c, dt, a, groups, chunk, per_step, keep_states=True,
            interpret=False):
    """``x`` (B, L, H * P), ``b``, ``c`` (B, L, G * N) in the compute
    dtype, ``dt`` (B, L, H) and ``a`` (1, H) float32; ``L`` a multiple of
    ``per_step * chunk``. Returns ``y`` (B, L, H * P) in ``x``'s dtype
    and, with ``keep_states``, the transposed state each chunk starts from
    (B, L / chunk, N, H * P) float32."""
    bsz, length, inner = x.shape
    state_dim, width = b.shape[-1] // groups, inner // groups
    sweep = _sweep(x, dt, groups, state_dim, chunk, per_step, False)
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    out_specs = [sweep.rows(width)]
    if keep_states:
        out_shape.append(jax.ShapeDtypeStruct(
            (bsz, length // chunk, state_dim, inner), jnp.float32))
        out_specs.append(sweep.states(width))
    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk,
                          head_dim=inner // dt.shape[-1],
                          keep_states=keep_states),
        grid=sweep.grid,
        in_specs=[sweep.rows(width), sweep.rows(state_dim),
                  sweep.rows(state_dim), sweep.dt, sweep.a],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=_scratch(x, dt, groups, state_dim, chunk, per_step, 1),
        compiler_params=_PARAMS,
        name="ssd_scan_fwd",
        interpret=interpret,
    )(x, b, c, dt, a)
    return tuple(outs) if keep_states else (outs[0], None)


def backward(x, b, c, dt, a, states, d_y, groups, chunk, per_step,
             interpret=False):
    """(``dx``, ``db``, ``dc`` in their operands' dtypes, ``ddt`` and the
    gradient of ``dt a`` (B, L, H) float32) from ``forward``'s operands,
    its entry states and ``d_y`` (B, L, H * P)."""
    inner = x.shape[-1]
    state_dim, width = b.shape[-1] // groups, inner // groups
    sweep = _sweep(x, dt, groups, state_dim, chunk, per_step, True)
    wide, narrow = sweep.rows(width), sweep.rows(state_dim)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk,
                          head_dim=inner // dt.shape[-1]),
        grid=sweep.grid,
        in_specs=[wide, narrow, narrow, sweep.dt, sweep.a,
                  sweep.states(width), wide],
        out_specs=[wide, narrow, narrow, sweep.dt, sweep.dt],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype)
                   for v in (x, b, c, dt, dt)],
        # ``cum``, its gradient, and the gradient of ``dt`` through ``dt x``
        scratch_shapes=[
            *_scratch(x, dt, groups, state_dim, chunk, per_step, 2),
            pltpu.VMEM((per_step, chunk, dt.shape[-1]), jnp.float32)],
        compiler_params=_PARAMS,
        name="ssd_scan_bwd",
        interpret=interpret,
    )(x, b, c, dt, a, states, d_y)
