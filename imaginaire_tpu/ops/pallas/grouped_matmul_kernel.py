"""Grouped matrix products as Pallas TPU kernels: the rows of ``lhs`` lie
sorted by group, ``group_sizes[g]`` of them belong to group ``g``, and
each group has a matrix of its own.

  ``rows``     ``out[r] = lhs[r] @ rhs[group of r]`` (or ``@ rhs[...]^T``:
               the gradient to the rows reads the same weights, contracted
               over their last axis, not a transposed copy)
  ``weights``  ``out[g] = lhs[rows of g]^T @ dout[rows of g]``: the
               gradient to the weights

The design is the one JAX ships as ``jax.experimental.pallas.ops.tpu.
megablox``: the groups' offsets and, for every visit of the grid, its
group and its row tile are computed outside and prefetched as scalars;
the grid runs over the row tiles that hold a group's rows, a tile that
straddles two groups once for each, its rows stored (or contracted) under
a row mask. The number of visits is a dynamic grid bound, so **row tiles
past the last group are not visited**: their rows of ``rows``'s output
are nobody's to write, and the time follows the filled rows, not the
buffer.

Products take the operands' dtype and accumulate in float32; the result
is in the operands' dtype, as ``lax.ragged_dot``'s is. The contraction
stands whole in VMEM, whatever its size (a group's weights are then
fetched once a width tile; cut into tiles it was slower at every shape
measured, PERF.md, PR 38), so the grid has no axis for it and nothing of
it to mask; a width the tile does not divide needs nothing, the columns
past the edge are dropped when the block is written back. No operand is
padded or transposed in HBM. ``ops/grouped_matmul.py`` holds the rule of
the arm and the tile sizes.
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
_MIB = 1024 * 1024


def visits(group_sizes, rows, tm, empty_groups):
    """The scalars a kernel's grid runs on. ``offsets`` (G + 1,): the row
    each group starts at; ``group_ids``, ``tile_ids`` (rows // tm + G -
    1,): the group and the row tile of each visit, the groups ascending
    and a group's tiles ascending; ``count``: how many visits there are.
    A group visits every tile that holds one of its rows; an empty one
    visits nothing, or one tile where ``empty_groups`` (``weights`` has
    its output to zero)."""
    groups = group_sizes.shape[0]
    tiles = rows // tm
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    starts = ends - group_sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = jnp.minimum(starts // tm, tiles - 1)
    of_group = jnp.where(group_sizes > 0, (ends + tm - 1) // tm - first,
                         1 if empty_groups else 0)
    visit_ends = jnp.cumsum(of_group)
    index = jnp.arange(tiles + groups - 1, dtype=jnp.int32)
    group_ids = jnp.minimum(
        (index[:, None] >= visit_ends[None, :]).sum(1), groups - 1)
    within = index - (visit_ends - of_group)[group_ids]
    tile_ids = jnp.clip(first[group_ids] + within, 0, tiles - 1)
    return (offsets, group_ids.astype(jnp.int32),
            tile_ids.astype(jnp.int32), visit_ends[-1])


def _rows_of_group(offsets, group, tile, tm):
    """(first, end) of the group's rows, counted from the tile's first,
    and whether they are the whole tile."""
    first, end = offsets[group] - tile * tm, offsets[group + 1] - tile * tm
    return first, end, (first <= 0) & (end >= tm)


def _in_rows(shape, first, end):
    rows = lax.broadcasted_iota(jnp.int32, shape, 0)
    return (rows >= first) & (rows < end)


def _vmem_limit(*blocks):
    """Bytes of VMEM to ask for: the blocks (shape, itemsize, copies),
    with room for the products' float32 results beside them."""
    need = sum(copies * itemsize * shape[0] * shape[1]
               for shape, itemsize, copies in blocks)
    return int(min(max(32 * _MIB, 2 * need), 100 * _MIB))


# --------------------------------------------------------------------- rows


def _rows_kernel(offsets, group_ids, tile_ids, lhs_ref, rhs_ref, out_ref, *,
                 tm, transposed):
    visit = pl.program_id(1)
    first, end, whole = _rows_of_group(offsets, group_ids[visit],
                                       tile_ids[visit], tm)
    acc = lax.dot_general(lhs_ref[...], rhs_ref[...],
                          _NT if transposed else _NN,
                          preferred_element_type=jnp.float32)

    @pl.when(whole)
    def _():
        out_ref[...] = acc.astype(out_ref.dtype)

    @pl.when(jnp.logical_not(whole))
    def _():
        # the other rows are another group's, stored by its own visit of
        # this tile (the block stays in VMEM between the two), or lie
        # past the last group
        out_ref[...] = jnp.where(_in_rows(acc.shape, first, end),
                                 acc.astype(out_ref.dtype), out_ref[...])


def rows(lhs, rhs, group_sizes, tile, transposed=False, name="grouped_rows",
         interpret=False):
    """``lhs`` (M, K) by each row's group's matrix: ``rhs`` (G, K, N), or
    (G, N, K) where ``transposed``. Returns (M, N) in ``lhs``'s dtype; the
    rows past the last group are not written. ``tile`` = (tm, tn) of the
    output; ``tm`` divides M."""
    tm, tn = tile
    m, k = lhs.shape
    n = rhs.shape[1] if transposed else rhs.shape[2]
    assert m % tm == 0, (m, tm)
    offsets, group_ids, tile_ids, count = visits(group_sizes, m, tm, False)

    def lhs_map(j, v, offsets, group_ids, tile_ids):
        return tile_ids[v], 0

    def rhs_map(j, v, offsets, group_ids, tile_ids):
        return (group_ids[v], j, 0) if transposed else (group_ids[v], 0, j)

    def out_map(j, v, offsets, group_ids, tile_ids):
        return tile_ids[v], j

    size = lhs.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_rows_kernel, tm=tm, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(n, tn), count),
            in_specs=[pl.BlockSpec((tm, k), lhs_map),
                      pl.BlockSpec((None, tn, k) if transposed
                                   else (None, k, tn), rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map)),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                ((tm, k), size, 2), ((k, tn), size, 2),
                ((tm, tn), size, 2), ((tm, tn), 4, 2))),
        name=name,
        interpret=interpret,
    )(offsets, group_ids, tile_ids, lhs, rhs)


# ------------------------------------------------------------------ weights


def _weights_kernel(offsets, group_ids, tile_ids, lhs_ref, dout_ref, out_ref,
                    acc_ref, *, tm):
    visit, last = pl.program_id(1), pl.num_programs(1) - 1
    group = group_ids[visit]
    first, end, whole = _rows_of_group(offsets, group, tile_ids[visit], tm)
    before = group_ids[jnp.maximum(visit - 1, 0)]
    after = group_ids[jnp.minimum(visit + 1, last)]

    @pl.when((visit == 0) | (before != group))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def add(masked):
        a, b = lhs_ref[...], dout_ref[...]
        if masked:
            a, b = (jnp.where(_in_rows(x.shape, first, end), x,
                              jnp.zeros_like(x)) for x in (a, b))
        acc_ref[...] += lax.dot_general(a, b, _TN,
                                        preferred_element_type=jnp.float32)

    filled = end > first
    pl.when(filled & whole)(functools.partial(add, False))
    pl.when(filled & jnp.logical_not(whole))(functools.partial(add, True))

    @pl.when((visit == last) | (after != group))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def weights(lhs, dout, group_sizes, tile, name="grouped_weights",
            interpret=False):
    """``out[g] = lhs[rows of g]^T @ dout[rows of g]``: ``lhs`` (M, K),
    ``dout`` (M, N) to (G, K, N) in ``lhs``'s dtype, zeros for an empty
    group. ``tile`` = (tm, tn): the rows contracted a visit and the tile
    of N; ``tm`` divides M."""
    tm, tn = tile
    m, k = lhs.shape
    n = dout.shape[1]
    groups = group_sizes.shape[0]
    assert m % tm == 0, (m, tm)
    offsets, group_ids, tile_ids, count = visits(group_sizes, m, tm, True)

    def lhs_map(j, v, offsets, group_ids, tile_ids):
        return tile_ids[v], 0

    def dout_map(j, v, offsets, group_ids, tile_ids):
        return tile_ids[v], j

    def out_map(j, v, offsets, group_ids, tile_ids):
        return group_ids[v], 0, j

    size = lhs.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_weights_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(n, tn), count),
            in_specs=[pl.BlockSpec((tm, k), lhs_map),
                      pl.BlockSpec((tm, tn), dout_map)],
            out_specs=pl.BlockSpec((None, k, tn), out_map),
            scratch_shapes=[pltpu.VMEM((k, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                ((tm, k), size, 2), ((tm, tn), size, 2),
                ((k, tn), size, 2), ((k, tn), 4, 2))),
        name=name,
        interpret=interpret,
    )(offsets, group_ids, tile_ids, lhs, dout)
