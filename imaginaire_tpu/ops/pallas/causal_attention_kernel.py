"""Causal grouped-query attention, forward and backward, as Pallas TPU
kernels that keep the scores on the chip.

Three passes, one tiling scheme. Each works on a (queries, keys) tile of
the scores in VMEM: the products take bfloat16 operands and accumulate in
float32, the scale, the mask and the softmax statistics are float32, the
probabilities are cast to the values' dtype for their product, and neither
scores nor probabilities are ever written to HBM.

  ``forward``       online softmax over the key tiles of a query tile (a
                    running row maximum and row sum, lane-replicated);
                    returns the output and the rows' log-sum-exp
  ``backward_dq``   the queries' gradient, the same sweep
  ``backward_dkv``  the keys' and values' gradients, on the transposed
                    tile (keys in rows), summed over the query heads of a
                    key-value head in VMEM

A tile wholly above the diagonal is not computed and not fetched (its
index map names the tile already held); one wholly below it skips the
mask. Without a window every tile on or below the diagonal is computed.

``window`` (static; None for none) is the number of keys a query sees,
itself counted: query ``i`` reads keys ``j`` with ``0 <= i - j < window``.
The scores are then a band, and a tile wholly below the band is as far
from the work as one above the diagonal: the innermost grid axis is only
as long as the most tiles the band crosses in one sweep, it starts at the
first tile the sweep reads (``_first_kv``, ``_first_q``) and its index map
clamps at the last (``_last_kv``, ``_last_q``), so no such tile is a grid
step, computed or fetched. A tile the band's lower edge crosses takes the
mask as one the diagonal crosses does (both edges in one comparison
pair). A row whose keys in the sweep's first tile are all masked leaves
that tile with the finite ``MASK_VALUE`` as its maximum; the next tile's
``exp(MASK_VALUE - m)`` is 0 and wipes what it summed. ``query_sweep_tiles``
and ``key_sweep_tiles`` list the tiles a pass computes, from the same
functions.

Layouts are the model's own: ``q`` (B, L, Hq*d), ``k``, ``v`` (B, L,
Hkv*d), head ``h`` the ``d`` columns from ``h*d``; query head ``h`` reads
key-value head ``h // (Hq/Hkv)``. No transpose on the way in or out.
``d`` and the tiles are multiples of 128, ``L`` a multiple of the tiles;
``ops/attention.py`` holds the rule and the tile sizes. ``scale`` is
``1/sqrt(d)`` unless the caller gives it: a head zero-padded to a lane
tile keeps the scale of the head it was.
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# finite, so a row whose tile is all above the diagonal gives exp(...) = 0
# against the maximum its earlier tiles left, never inf - inf
MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
# a kernel gets 16 MiB of VMEM unless it asks; a 1024 x 1024 tile's float32
# scores, probabilities and their gradients stand beside the operands
_VMEM_LIMIT = 32 * 1024 * 1024


def _last_kv(i, bq, bkv):
    """The last key tile a row of query tile ``i`` reads."""
    return ((i + 1) * bq - 1) // bkv


def _first_q(j, bq, bkv):
    """The first query tile a row of key tile ``j`` is read by."""
    return (j * bkv) // bq


def _first_kv(i, bq, bkv, window):
    """The first key tile a row of query tile ``i`` reads under a
    window: its first row's ``window - 1`` keys back."""
    if isinstance(i, int):
        return max(i * bq - window + 1, 0) // bkv
    return jnp.maximum(i * bq - window + 1, 0) // bkv


def _last_q(j, bq, bkv, window, q_tiles):
    """The last query tile a row of key tile ``j`` is read by under a
    window: its last key's ``window - 1`` queries on."""
    last = ((j + 1) * bkv + window - 2) // bq
    if isinstance(j, int):
        return min(last, q_tiles - 1)
    return jnp.minimum(last, q_tiles - 1)


def _crosses_diagonal(i, j, bq, bkv):
    """Whether tile (i, j) holds a key after one of its queries."""
    return (j + 1) * bkv - 1 > i * bq


def _crosses_edges(i, j, bq, bkv, window):
    """Whether tile (i, j) needs a mask: it holds a key after one of its
    queries or, under a window, a key ``window`` or more before one."""
    crosses = _crosses_diagonal(i, j, bq, bkv)
    if window is not None:
        crosses |= (i + 1) * bq - 1 - j * bkv >= window
    return crosses


def query_sweep_tiles(length, bq, bkv, window):
    """[(query tile, key tile)] that the forward and the dQ pass compute
    for one head; ``window`` None is every tile on or below the
    diagonal."""
    return [(i, j) for i in range(length // bq)
            for j in range(0 if window is None
                           else _first_kv(i, bq, bkv, window),
                           _last_kv(i, bq, bkv) + 1)]


def key_sweep_tiles(length, bq, bkv, window):
    """[(query tile, key tile)] that the dK/dV pass computes for one
    query head."""
    q_tiles = length // bq
    return [(i, j) for j in range(length // bkv)
            for i in range(_first_q(j, bq, bkv),
                           (q_tiles if window is None else
                            _last_q(j, bq, bkv, window, q_tiles) + 1))]


def _span(tiles, axis):
    """The most tiles of ``tiles`` that share their index on ``axis``:
    the length of a windowed sweep's innermost grid axis."""
    return max(collections.Counter(tile[axis] for tile in tiles).values())


def _scores(a_ref, b_ref, scale, row0, col0, masked, keys_in_rows,
            window=None):
    """``a b^T * scale`` in float32, masked to the causal part (the band,
    under a window) where the tile crosses an edge of it."""
    s = lax.dot_general(a_ref[...], b_ref[...], _NT,
                        preferred_element_type=jnp.float32) * scale
    if masked:
        rows = row0 + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = col0 + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = cols >= rows if keys_in_rows else rows >= cols
        if window is not None:
            keep &= (cols - rows if keys_in_rows else rows - cols) < window
        s = jnp.where(keep, s, MASK_VALUE)
    return s


def _sizes(q, q_heads, kv_heads, scale=None):
    """(head size, query heads a key-value head, scale) of flat ``q``;
    the scale ``1/sqrt(head size)`` where none is given."""
    head_dim = q.shape[-1] // q_heads
    return (head_dim, q_heads // kv_heads,
            float(scale or 1.0 / np.sqrt(head_dim)))


def _query_sweep(q, q_heads, kv_heads, bq, bkv, window):
    """What the forward and the dQ pass share: a grid (batch, query head,
    query tile, key tile), the key tiles innermost, and the block specs
    of a query tile, a key tile and a (1, bq) row of statistics. Under a
    window the innermost axis counts from the first key tile the query
    tile reads and is as long as the band's widest sweep."""
    bsz, length, _ = q.shape
    head_dim, group, _ = _sizes(q, q_heads, kv_heads)
    kv_steps = length // bkv
    if window is not None:
        kv_steps = _span(query_sweep_tiles(length, bq, bkv, window), 0)

    def q_map(b, h, i, j):
        return b, i, h

    def kv_map(b, h, i, j):
        if window is not None:
            j = j + _first_kv(i, bq, bkv, window)
        return b, jnp.minimum(j, _last_kv(i, bq, bkv)), h // group

    return ((bsz, q_heads, length // bq, kv_steps),
            pl.BlockSpec((None, bq, head_dim), q_map),
            pl.BlockSpec((None, bkv, head_dim), kv_map),
            pl.BlockSpec((None, None, 1, bq), lambda b, h, i, j: (b, h, 0, i)))


def _on_tiles(needed, crosses, tile):
    """Run ``tile(masked)`` where the tile is needed: with the mask where
    it crosses the diagonal, without it below."""
    pl.when(needed & crosses)(functools.partial(tile, True))
    pl.when(needed & jnp.logical_not(crosses))(functools.partial(tile, False))


# ------------------------------------------------------------------ forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, scale, bq, bkv, window):
    i, j = pl.program_id(2), pl.program_id(3)
    last = _last_kv(i, bq, bkv)
    head_dim = acc_ref.shape[-1]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if window is not None:      # the grid's step to the key tile it is at
        j = j + _first_kv(i, bq, bkv, window)

    def tile(masked):
        s = _scores(q_ref, k_ref, scale, i * bq, j * bkv, masked, False,
                    window)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_next = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - jnp.tile(m_next, (1, bkv // LANES)))
        alpha = jnp.exp(m_prev - m_next)
        m_ref[...] = m_next
        l_ref[...] = alpha * l_prev + p.sum(axis=-1, keepdims=True)
        pv = jnp.dot(p.astype(v_ref.dtype), v_ref[...],
                     preferred_element_type=jnp.float32)
        acc_ref[...] = (acc_ref[...] * jnp.tile(alpha, (1, head_dim // LANES))
                        + pv)

    _on_tiles(j <= last, _crosses_edges(i, j, bq, bkv, window), tile)

    @pl.when(j == last)
    def _():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] * jnp.tile(1.0 / l, (1, head_dim // LANES))
                      ).astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l)


def forward(q, k, v, q_heads, kv_heads, bq, bkv, interpret=False,
            scale=None, window=None):
    """(out (B, L, Hq*d) in ``q``'s dtype, lse (B, Hq, L) float32)."""
    bsz, length, _ = q.shape
    head_dim, _, scale = _sizes(q, q_heads, kv_heads, scale)
    grid, q_spec, kv_spec, _ = _query_sweep(q, q_heads, kv_heads, bq, bkv,
                                            window)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, bq=bq, bkv=bkv,
                          window=window),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec,
                   pl.BlockSpec((None, None, bq, LANES),
                                lambda b, h, i, j: (b, h, i, 0))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((bsz, q_heads, length, LANES),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, LANES), jnp.float32),
                        pltpu.VMEM((bq, LANES), jnp.float32),
                        pltpu.VMEM((bq, head_dim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="causal_gqa_fwd",
        interpret=interpret,
    )(q, k, v)
    return out, lse[..., 0]


# ----------------------------------------------------------------- backward


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, acc_ref,
               *, scale, bq, bkv, window):
    i, j = pl.program_id(2), pl.program_id(3)
    last = _last_kv(i, bq, bkv)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if window is not None:
        j = j + _first_kv(i, bq, bkv, window)

    def tile(masked):
        s = _scores(q_ref, k_ref, scale, i * bq, j * bkv, masked, False,
                    window)
        p = jnp.exp(s - jnp.expand_dims(lse_ref[0], -1))
        dp = lax.dot_general(do_ref[...], v_ref[...], _NT,
                             preferred_element_type=jnp.float32)
        ds = p * (dp - jnp.expand_dims(di_ref[0], -1))
        acc_ref[...] += jnp.dot(ds.astype(k_ref.dtype), k_ref[...],
                                preferred_element_type=jnp.float32)

    _on_tiles(j <= last, _crosses_edges(i, j, bq, bkv, window), tile)

    @pl.when(j == last)
    def _():
        dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def backward_dq(q, k, v, do, lse, di, q_heads, kv_heads, bq, bkv,
                interpret=False, scale=None, window=None):
    """The queries' gradient, (B, L, Hq*d). ``lse``, ``di`` (B, Hq, L)
    float32: the rows' log-sum-exp and ``sum(do * out)``."""
    head_dim, _, scale = _sizes(q, q_heads, kv_heads, scale)
    grid, q_spec, kv_spec, row_spec = _query_sweep(q, q_heads, kv_heads,
                                                   bq, bkv, window)
    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, bq=bq, bkv=bkv,
                          window=window),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, head_dim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="causal_gqa_dq",
        interpret=interpret,
    )(q, k, v, do, lse[:, :, None], di[:, :, None])


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, scale, bq, bkv, group, q_tiles, q_steps,
                window):
    j, r, i = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when((r == 0) & (i == 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    at_end = (r == group - 1) & (i == q_steps - 1)
    needed = i >= _first_q(j, bq, bkv)
    if window is not None:      # the grid's step to the query tile it is at
        i = i + _first_q(j, bq, bkv)
        needed = i <= _last_q(j, bq, bkv, window, q_tiles)

    def tile(masked):
        # keys in rows, queries in lanes: the rows' statistics broadcast
        # down the sublanes and no product needs a transposed operand
        s = _scores(k_ref, q_ref, scale, j * bkv, i * bq, masked, True,
                    window)
        p = jnp.exp(s - lse_ref[...])
        do = do_ref[...]
        dv_acc[...] += jnp.dot(p.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        dp = lax.dot_general(v_ref[...], do, _NT,
                             preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[...])
        dk_acc[...] += jnp.dot(ds.astype(q_ref.dtype), q_ref[...],
                               preferred_element_type=jnp.float32)

    _on_tiles(needed, _crosses_edges(i, j, bq, bkv, window), tile)

    @pl.when(at_end)
    def _():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _key_sweep(q, q_heads, kv_heads, bq, bkv, window):
    """The dK/dV pass's grid (batch, key-value head, key tile, query head
    of the group, query tile), the query tiles innermost, its steps along
    that axis, and the block specs of a query tile, a key tile and a
    (1, bq) row of statistics. Under a window the innermost axis counts
    from the first query tile that reads the key tile and is as long as
    the band's tallest sweep."""
    bsz, length, _ = q.shape
    head_dim, group, _ = _sizes(q, q_heads, kv_heads)
    q_tiles = q_steps = length // bq
    if window is not None:
        q_steps = _span(key_sweep_tiles(length, bq, bkv, window), 1)

    def first_q(i, j):
        # a tile above the diagonal names the first one that is not; one
        # below the band the last that is in it
        if window is not None:
            return jnp.minimum(i + _first_q(j, bq, bkv),
                               _last_q(j, bq, bkv, window, q_tiles))
        return jnp.maximum(i, _first_q(j, bq, bkv))

    def q_map(b, g, j, r, i):
        return b, first_q(i, j), g * group + r

    def kv_map(b, g, j, r, i):
        return b, j, g

    def row_map(b, g, j, r, i):
        return b, g * group + r, 0, first_q(i, j)

    return ((bsz, kv_heads, length // bkv, group, q_steps), q_steps,
            pl.BlockSpec((None, bq, head_dim), q_map),
            pl.BlockSpec((None, bkv, head_dim), kv_map),
            pl.BlockSpec((None, None, 1, bq), row_map))


def backward_dkv(q, k, v, do, lse, di, q_heads, kv_heads, bq, bkv,
                 interpret=False, scale=None, window=None):
    """The keys' and the values' gradients, (B, L, Hkv*d) each."""
    length = q.shape[1]
    head_dim, group, scale = _sizes(q, q_heads, kv_heads, scale)
    grid, q_steps, q_spec, kv_spec, row_spec = _key_sweep(
        q, q_heads, kv_heads, bq, bkv, window)
    return pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, bq=bq, bkv=bkv,
                          group=group, q_tiles=length // bq, q_steps=q_steps,
                          window=window),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bkv, head_dim), jnp.float32),
                        pltpu.VMEM((bkv, head_dim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="causal_gqa_dkv",
        interpret=interpret,
    )(q, k, v, do, lse[:, :, None], di[:, :, None])
