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
mask. Every tile on or below the diagonal is computed.

Layouts are the model's own: ``q`` (B, L, Hq*d), ``k``, ``v`` (B, L,
Hkv*d), head ``h`` the ``d`` columns from ``h*d``; query head ``h`` reads
key-value head ``h // (Hq/Hkv)``. No transpose on the way in or out.
``d`` and the tiles are multiples of 128, ``L`` a multiple of the tiles;
``ops/attention.py`` holds the rule and the tile sizes. ``scale`` is
``1/sqrt(d)`` unless the caller gives it: a head zero-padded to a lane
tile keeps the scale of the head it was.
"""

from __future__ import annotations

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


def _crosses_diagonal(i, j, bq, bkv):
    """Whether tile (i, j) holds a key after one of its queries."""
    return (j + 1) * bkv - 1 > i * bq


def _scores(a_ref, b_ref, scale, row0, col0, masked, keys_in_rows):
    """``a b^T * scale`` in float32, masked to the causal part where the
    tile crosses the diagonal."""
    s = lax.dot_general(a_ref[...], b_ref[...], _NT,
                        preferred_element_type=jnp.float32) * scale
    if masked:
        rows = row0 + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = col0 + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = cols >= rows if keys_in_rows else rows >= cols
        s = jnp.where(keep, s, MASK_VALUE)
    return s


def _sizes(q, q_heads, kv_heads, scale=None):
    """(head size, query heads a key-value head, scale) of flat ``q``;
    the scale ``1/sqrt(head size)`` where none is given."""
    head_dim = q.shape[-1] // q_heads
    return (head_dim, q_heads // kv_heads,
            float(scale or 1.0 / np.sqrt(head_dim)))


def _query_sweep(q, q_heads, kv_heads, bq, bkv):
    """What the forward and the dQ pass share: a grid (batch, query head,
    query tile, key tile), the key tiles innermost, and the block specs
    of a query tile, a key tile and a (1, bq) row of statistics."""
    bsz, length, _ = q.shape
    head_dim, group, _ = _sizes(q, q_heads, kv_heads)

    def q_map(b, h, i, j):
        return b, i, h

    def kv_map(b, h, i, j):
        return b, jnp.minimum(j, _last_kv(i, bq, bkv)), h // group

    return ((bsz, q_heads, length // bq, length // bkv),
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
                *, scale, bq, bkv):
    i, j = pl.program_id(2), pl.program_id(3)
    last = _last_kv(i, bq, bkv)
    head_dim = acc_ref.shape[-1]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(masked):
        s = _scores(q_ref, k_ref, scale, i * bq, j * bkv, masked, False)
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

    _on_tiles(j <= last, _crosses_diagonal(i, j, bq, bkv), tile)

    @pl.when(j == last)
    def _():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] * jnp.tile(1.0 / l, (1, head_dim // LANES))
                      ).astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l)


def forward(q, k, v, q_heads, kv_heads, bq, bkv, interpret=False,
            scale=None):
    """(out (B, L, Hq*d) in ``q``'s dtype, lse (B, Hq, L) float32)."""
    bsz, length, _ = q.shape
    head_dim, _, scale = _sizes(q, q_heads, kv_heads, scale)
    grid, q_spec, kv_spec, _ = _query_sweep(q, q_heads, kv_heads, bq, bkv)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, bq=bq, bkv=bkv),
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
               *, scale, bq, bkv):
    i, j = pl.program_id(2), pl.program_id(3)
    last = _last_kv(i, bq, bkv)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(masked):
        s = _scores(q_ref, k_ref, scale, i * bq, j * bkv, masked, False)
        p = jnp.exp(s - jnp.expand_dims(lse_ref[0], -1))
        dp = lax.dot_general(do_ref[...], v_ref[...], _NT,
                             preferred_element_type=jnp.float32)
        ds = p * (dp - jnp.expand_dims(di_ref[0], -1))
        acc_ref[...] += jnp.dot(ds.astype(k_ref.dtype), k_ref[...],
                                preferred_element_type=jnp.float32)

    _on_tiles(j <= last, _crosses_diagonal(i, j, bq, bkv), tile)

    @pl.when(j == last)
    def _():
        dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def backward_dq(q, k, v, do, lse, di, q_heads, kv_heads, bq, bkv,
                interpret=False, scale=None):
    """The queries' gradient, (B, L, Hq*d). ``lse``, ``di`` (B, Hq, L)
    float32: the rows' log-sum-exp and ``sum(do * out)``."""
    head_dim, _, scale = _sizes(q, q_heads, kv_heads, scale)
    grid, q_spec, kv_spec, row_spec = _query_sweep(q, q_heads, kv_heads,
                                                   bq, bkv)
    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, bq=bq, bkv=bkv),
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
                dk_acc, dv_acc, *, scale, bq, bkv, group, q_tiles):
    j, r, i = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when((r == 0) & (i == 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def tile(masked):
        # keys in rows, queries in lanes: the rows' statistics broadcast
        # down the sublanes and no product needs a transposed operand
        s = _scores(k_ref, q_ref, scale, j * bkv, i * bq, masked, True)
        p = jnp.exp(s - lse_ref[...])
        do = do_ref[...]
        dv_acc[...] += jnp.dot(p.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        dp = lax.dot_general(v_ref[...], do, _NT,
                             preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[...])
        dk_acc[...] += jnp.dot(ds.astype(q_ref.dtype), q_ref[...],
                               preferred_element_type=jnp.float32)

    _on_tiles(i >= _first_q(j, bq, bkv), _crosses_diagonal(i, j, bq, bkv),
              tile)

    @pl.when((r == group - 1) & (i == q_tiles - 1))
    def _():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def backward_dkv(q, k, v, do, lse, di, q_heads, kv_heads, bq, bkv,
                 interpret=False, scale=None):
    """The keys' and the values' gradients, (B, L, Hkv*d) each."""
    bsz, length, _ = q.shape
    head_dim, group, scale = _sizes(q, q_heads, kv_heads, scale)

    def first_q(i, j):
        # a tile above the diagonal names the first one that is not
        return jnp.maximum(i, _first_q(j, bq, bkv))

    def q_map(b, g, j, r, i):
        return b, first_q(i, j), g * group + r

    def kv_map(b, g, j, r, i):
        return b, j, g

    def row_map(b, g, j, r, i):
        return b, g * group + r, 0, first_q(i, j)

    q_spec = pl.BlockSpec((None, bq, head_dim), q_map)
    kv_spec = pl.BlockSpec((None, bkv, head_dim), kv_map)
    row_spec = pl.BlockSpec((None, None, 1, bq), row_map)
    return pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, bq=bq, bkv=bkv,
                          group=group, q_tiles=length // bq),
        grid=(bsz, kv_heads, length // bkv, group, length // bq),
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
