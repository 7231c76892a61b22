"""Causal grouped-query attention, forward and backward, as Pallas TPU
kernels that keep the scores on the chip.

Two passes, one tiling scheme. Each works on a (queries, keys) tile of
the scores in VMEM: the products take bfloat16 operands and accumulate in
float32, the scale, the mask and the softmax statistics are float32, the
probabilities are cast to the values' dtype for their product, and neither
scores nor probabilities are ever written to HBM.

  ``forward``   online softmax over the key tiles of a query tile (a
                running row maximum and row sum, lane-replicated);
                returns the output and the rows' log-sum-exp
  ``backward``  the three gradients from one sweep of the same tiles,
                five products a tile: the scores and ``do v^T`` once,
                then ``dv += p^T do``, ``dk += ds^T q``, ``dq += ds k``.
                The tile stands keys in rows (the rows' statistics then
                broadcast down the sublanes and four of the products
                need no turned operand). ``dq`` sums along the sweep
                into a tile of scratch; a key-value head's ``dk`` and
                ``dv`` sum across the sweeps of its query heads' query
                tiles and stand whole in VMEM in float32
                (``accumulator_bytes``: 16 MiB at 8,192 x 256 and at
                16,384 x 128), each tile written out once, after the
                last sum into it. Every sum runs in the order two
                separate passes would run it (key tiles ascending into
                ``dq``; query head, then query tile, ascending into
                ``dk``, ``dv``).

A tile wholly above the diagonal is not computed and not fetched (its
index map names a tile already held); one wholly below it skips the
mask. Without a window every tile on or below the diagonal is computed.

``window`` (static; None for none) is the number of keys a query sees,
itself counted: query ``i`` reads keys ``j`` with ``0 <= i - j < window``.
The scores are then a band, and a tile wholly below the band is as far
from the work as one above the diagonal: the innermost grid axis is only
as long as the most tiles the band crosses in one sweep (``_kv_steps``),
the sweep runs from the first tile it reads (``_first_kv``) to the last
(``_last_kv``) and a step it does not need names a tile it holds, so no
such tile is a grid step, computed or fetched. A tile the band's lower
edge crosses takes the
mask as one the diagonal crosses does (both edges in one comparison
pair). A row whose keys in the sweep's first tile are all masked leaves
that tile with the finite ``MASK_VALUE`` as its maximum; the next tile's
``exp(MASK_VALUE - m)`` is 0 and wipes what it summed.
``query_sweep_tiles`` lists the tiles a pass computes, from the same
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
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
# a kernel gets 16 MiB of VMEM unless it asks; the forward's 1024 x 1024
# tile of float32 scores and probabilities stands beside the operands
_VMEM_LIMIT = 32 * 1024 * 1024
# what the compiler lays out beside what ``backward_vmem_bytes`` counts
_VMEM_ROOM = 8 * 1024 * 1024
# the most a pass asks for, of a v5e core's 128 MiB
VMEM_BYTES = 112 * 1024 * 1024
# of a tile in the backward sweep: the scores, ``do v^T`` and the three
# gradients (``tests/test_attention_op.py`` counts them in the body)
BACKWARD_PRODUCTS = 5


def _last_kv(i, bq, bkv):
    """The last key tile a row of query tile ``i`` reads."""
    return ((i + 1) * bq - 1) // bkv


def _first_kv(i, bq, bkv, window):
    """The first key tile a row of query tile ``i`` reads under a
    window: its first row's ``window - 1`` keys back."""
    if isinstance(i, int):
        return max(i * bq - window + 1, 0) // bkv
    return jnp.maximum(i * bq - window + 1, 0) // bkv


def _whole_kv(i, bq, bkv, window, q_tiles, kv_tiles):
    """How many key tiles, from the first, no query tile after ``i``
    reads: every one after the last query tile, under a window those
    before the next query tile's first, none otherwise."""
    rest = 0 if window is None else _first_kv(i + 1, bq, bkv, window)
    return jnp.where(i == q_tiles - 1, kv_tiles, rest)


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
    """[(query tile, key tile)] that a pass computes for one query head,
    in its sweep's order; ``window`` None is every tile on or below the
    diagonal."""
    return [(i, j) for i in range(length // bq)
            for j in range(0 if window is None
                           else _first_kv(i, bq, bkv, window),
                           _last_kv(i, bq, bkv) + 1)]


def _kv_steps(length, bq, bkv, window):
    """The length of a sweep's innermost grid axis: every key tile, or
    under a window the most that one query tile reads."""
    if window is None:
        return length // bkv
    tiles = query_sweep_tiles(length, bq, bkv, window)
    return max(collections.Counter(i for i, _ in tiles).values())


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
    """The forward pass's grid (batch, query head, query tile, key tile),
    the key tiles innermost, and the block specs of a query tile and a
    key tile. Under a window the innermost axis counts from the first
    key tile the query tile reads and is as long as the band's widest
    sweep."""
    bsz, length, _ = q.shape
    head_dim, group, _ = _sizes(q, q_heads, kv_heads)

    def q_map(b, h, i, j):
        return b, i, h

    def kv_map(b, h, i, j):
        if window is not None:
            j = j + _first_kv(i, bq, bkv, window)
        return b, jnp.minimum(j, _last_kv(i, bq, bkv)), h // group

    return ((bsz, q_heads, length // bq, _kv_steps(length, bq, bkv, window)),
            pl.BlockSpec((None, bq, head_dim), q_map),
            pl.BlockSpec((None, bkv, head_dim), kv_map))


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
    grid, q_spec, kv_spec = _query_sweep(q, q_heads, kv_heads, bq, bkv,
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


def accumulator_bytes(length, head_dim):
    """Bytes of what stands in VMEM through a key-value head's sweep: its
    ``dk`` and ``dv`` whole, in float32."""
    return 2 * length * head_dim * 4


def backward_vmem_bytes(length, head_dim, bq, bkv, itemsize):
    """Bytes of VMEM the backward pass asks for: the accumulators, the
    pipeline's two copies of each block, the query tile's float32 ``dq``
    and what a tile's arithmetic stands on (the float32 scores,
    probabilities and their two gradients, and ``p``, ``ds`` and ``ds``
    turned in the operands' dtype)."""
    blocks = 2 * itemsize * head_dim * (3 * bq + 4 * bkv) + 2 * 2 * 8 * bq * 4
    tile = bq * bkv * (4 * 4 + 3 * itemsize)
    return (accumulator_bytes(length, head_dim) + blocks
            + bq * head_dim * 4 + tile + _VMEM_ROOM)


def _backward_tile(i, j, bq, bkv, window, kv_steps):
    """(the key tile that step ``j`` of query tile ``i``'s backward sweep
    is at, whether the step computes it). The sweep's tiles take its LAST
    steps: the steps a shorter sweep does not need come first and name
    its first tile, so everything a new query tile fetches is asked for
    during the last tile of the sweep before, behind that tile's
    arithmetic, and not behind a step that does nothing."""
    first = 0 if window is None else _first_kv(i, bq, bkv, window)
    idle = kv_steps - 1 - (_last_kv(i, bq, bkv) - first)
    return first + jnp.maximum(j - idle, 0), j >= idle


def _backward_sweep(q, q_heads, kv_heads, bq, bkv, window):
    """The backward pass's grid (batch, key-value head, query head of the
    group, query tile, key tile): the key tiles innermost and ascending,
    under a loop over the group's query heads, and the block specs of a
    query tile, a key tile, a (1, bq) row of statistics and a key tile
    of ``dk``, ``dv``.

    A key tile's gradients stand in VMEM until the last query tile that
    reads it has, in the group's last head (``_whole_kv``): its output
    block is named from that step on and tile 0's before, so each block
    is one run of grid steps that ends after its one write, and HBM is
    written once a tile."""
    bsz, length, _ = q.shape
    head_dim, group, _ = _sizes(q, q_heads, kv_heads)
    q_tiles, kv_steps = length // bq, _kv_steps(length, bq, bkv, window)

    def kv_tile(i, j):
        return _backward_tile(i, j, bq, bkv, window, kv_steps)[0]

    def q_map(b, g, r, i, j):
        return b, i, g * group + r

    def kv_map(b, g, r, i, j):
        return b, kv_tile(i, j), g

    def row_map(b, g, r, i, j):
        return b, g * group + r, 0, i

    def out_map(b, g, r, i, j):
        whole = _whole_kv(i, bq, bkv, window, q_tiles, length // bkv)
        at = jnp.minimum(kv_tile(i, j), jnp.maximum(whole - 1, 0))
        return b, jnp.where(r == group - 1, at, 0), g

    return ((bsz, kv_heads, group, q_tiles, kv_steps),
            pl.BlockSpec((None, bq, head_dim), q_map),
            pl.BlockSpec((None, bkv, head_dim), kv_map),
            pl.BlockSpec((None, None, 1, bq), row_map),
            pl.BlockSpec((None, bkv, head_dim), out_map))


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc,
                *, scale, bq, bkv, group, window):
    r, i, step = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    q_tiles, kv_steps = pl.num_programs(3), pl.num_programs(4)
    kv_tiles = dk_acc.shape[0]

    @pl.when((r == 0) & (i == 0) & (step == 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(step == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    j, needed = _backward_tile(i, step, bq, bkv, window, kv_steps)

    def tile(masked):
        # keys in rows, queries in lanes: the rows' statistics broadcast
        # down the sublanes, and of the five products only the queries'
        # gradient contracts over its left operand's rows
        s = _scores(k_ref, q_ref, scale, j * bkv, i * bq, masked, True,
                    window)
        p = jnp.exp(s - lse_ref[...])
        do = do_ref[...]
        dv_acc[j] += jnp.dot(p.astype(do.dtype), do,
                             preferred_element_type=jnp.float32)
        dp = lax.dot_general(v_ref[...], do, _NT,
                             preferred_element_type=jnp.float32)
        ds = (p * (dp - di_ref[...])).astype(q_ref.dtype)
        dk_acc[j] += jnp.dot(ds, q_ref[...],
                             preferred_element_type=jnp.float32)
        dq_acc[...] += lax.dot_general(ds, k_ref[...], _TN,
                                       preferred_element_type=jnp.float32)

    _on_tiles(needed, _crosses_edges(i, j, bq, bkv, window), tile)

    @pl.when(step == kv_steps - 1)       # the sweep's last tile, always
    def _():
        dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype)

    whole = _whole_kv(i, bq, bkv, window, q_tiles, kv_tiles)

    @pl.when(needed & (r == group - 1) & (j < whole))
    def _():
        dk_ref[...] = (dk_acc[j] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[j].astype(dv_ref.dtype)


def backward(q, k, v, do, lse, di, q_heads, kv_heads, bq, bkv,
             interpret=False, scale=None, window=None):
    """The three gradients, ``dq`` (B, L, Hq*d), ``dk``, ``dv`` (B, L,
    Hkv*d), from one sweep. ``lse``, ``di`` (B, Hq, L) float32: the rows'
    log-sum-exp and ``sum(do * out)``."""
    bsz, length, _ = q.shape
    head_dim, group, scale = _sizes(q, q_heads, kv_heads, scale)
    grid, q_spec, kv_spec, row_spec, out_spec = _backward_sweep(
        q, q_heads, kv_heads, bq, bkv, window)
    held = (length // bkv, bkv, head_dim)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, bq=bq, bkv=bkv,
                          group=group, window=window),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[q_spec, out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bq, head_dim), jnp.float32),
                        pltpu.VMEM(held, jnp.float32),
                        pltpu.VMEM(held, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary", "arbitrary"),
            vmem_limit_bytes=backward_vmem_bytes(
                length, head_dim, bq, bkv, q.dtype.itemsize)),
        name="causal_gqa_bwd",
        interpret=interpret,
    )(q, k, v, do, lse[:, :, None], di[:, :, None])
