"""Causal grouped-query attention: scale ``1/sqrt(head size)``, no
position embedding, query head ``h`` on key-value head ``h // (Hq/Hkv)``;
with a ``window``, query ``i`` reads keys ``j`` with ``0 <= i - j <
window`` (a sliding window that counts the query itself) and the scores
are a band below the diagonal.

One algorithm, two arms, and ``attention`` picks between them from what
it can observe (``arm_of``), with no option:

  ``fused``   one Pallas TPU kernel of two passes, a forward and one
              backward sweep that gives all three gradients
              (``ops/pallas/causal_attention_kernel.py``): the scores of a
              tile stand in VMEM, the row maximum and row sum run along
              the key tiles in float32, scores and probabilities never
              reach HBM, and the tiles above the diagonal, and under a
              window those wholly below the band, are neither computed
              nor fetched (``visited_tiles`` counts the rest). Where the
              backend is a TPU, the head
              size a multiple of 128 or half of 128 (64: zero columns
              fill the lane tile, the kernel then runs at 128 under the
              scale of 64, which is exact, and the matrix unit's 128
              lanes were the head's to fill either way), the length
              a multiple of the kernel's largest tile, and a key-value
              head's ``dk`` and ``dv`` of that length small enough to
              stand in VMEM through the backward sweep (``arm_of``).
  ``blocks``  ``causal_attention``: query blocks in plain ``jax.numpy``,
              each against the keys up to its own end (from its first
              row's window on, under a window), the (Hq, block, keys)
              scores of one block in HBM at a time. Everywhere
              else: the CPU, where the tests run, ragged lengths and
              every other head size (none has been run padded).

The fused arm's forward pass names its output and the rows'
log-sum-exp ``KERNEL_RESIDUAL``: a block recomputed under
``optim/remat.py``'s ``blocks`` keeps the two, so its backward pass
rebuilds ``q``, ``k``, ``v`` and runs the backward kernel, not the
forward kernel a second time (PERF.md, PR 33).

Both take bfloat16 (the compute dtype's) operands, accumulate products in
float32, mask and take the softmax statistics in float32 and cast the
probabilities to the values' dtype for their product. The kernel's tiles
are constants here, chosen on a v5e chip at head size 128 with 32 query
heads on 2 (PERF.md, PR 30), found the best again at head size 256 with
20 on 20 (PR 31), and the backward sweep's swept again at both head sizes
and 16,384 positions (PR 42); they are not configuration.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from imaginaire_tpu.ops.pallas import causal_attention_kernel as kernel


class Tiles(NamedTuple):
    """(query rows, key rows) of a tile, for each pass of the kernel:
    the forward and the one backward sweep."""
    fwd: tuple
    bwd: tuple

    @property
    def largest(self):
        return max(*self.fwd, *self.bwd)


TILES = Tiles(fwd=(1024, 1024), bwd=(1024, 1024))

# the ``checkpoint_name`` of what a kernel's forward pass hands its
# backward pass and only that kernel can rebuild
KERNEL_RESIDUAL = "kernel_residual"
BACKWARD_PRODUCTS = kernel.BACKWARD_PRODUCTS
# the ``blocks`` arm's query rows a block: its (Hq, rows, keys) float32
# scores stand one block at a time; a shorter sequence is one block. No
# cell takes that arm on the chip, so the number was never tuned there.
QUERY_BLOCK = 512


def kernel_head_dim(head_dim):
    """The head size the kernel runs a head of ``head_dim`` at: the next
    multiple of its lanes."""
    return -(-head_dim // kernel.LANES) * kernel.LANES


def accumulator_bytes(length, head_dim):
    """Bytes of a key-value head's float32 ``dk`` and ``dv`` that stand in
    VMEM through the fused arm's backward sweep at ``length``."""
    return kernel.accumulator_bytes(length, kernel_head_dim(head_dim))


def arm_of(head_dim, length):
    """``"fused"`` or ``"blocks"``: which arm ``attention`` takes for a
    head size and a length on this process's backend.

    The last condition is on bytes: the backward sweep keeps a key-value
    head's ``dk`` and ``dv`` whole in VMEM (``8 x length x head size``
    bytes) beside its tiles, and the whole has to stay under
    ``kernel.VMEM_BYTES``. With ``TILES`` and two-byte operands that
    holds through 65,536 positions at head size 128 (and 64) and through
    32,768 at 256; from 131,072 and 65,536 on a length takes ``blocks``
    as a ragged one does. No configuration in the tree comes near."""
    on_tpu = jax.default_backend() == "tpu"
    at = kernel_head_dim(head_dim)
    fits = (at in (head_dim, 2 * head_dim)
            and length % TILES.largest == 0
            and kernel.backward_vmem_bytes(length, at, *TILES.bwd, 2)
            <= kernel.VMEM_BYTES)
    return "fused" if on_tpu and fits else "blocks"


def effective_window(window, length):
    """``window`` where it hides a key from a query of a sequence of
    ``length``, None where it does not (none given, or as long as the
    sequence): such a call is the causal one, to the bit."""
    return None if window is None or window >= length else int(window)


def attention(q, k, v, window=None):
    """``q`` (B, L, Hq, d), ``k``, ``v`` (B, L, Hkv, d) to (B, L, Hq*d).
    The plain arm's query block is ``QUERY_BLOCK``, the fused arm's tiles
    are ``TILES``. ``window``: the keys a query sees, itself counted;
    None for every key up to its own."""
    if arm_of(q.shape[-1], q.shape[1]) == "fused":
        return fused_causal_attention(q, k, v, TILES, False, window)
    return causal_attention(q, k, v, QUERY_BLOCK, window)


def visited_tiles(length, window=None, tiles=TILES):
    """{``fwd``, ``dq``, ``dkv``: (tiles the fused kernel computes for one
    head toward the output, the queries' gradient, the keys' and values'
    gradients; tiles on or below the diagonal)} at ``length`` under
    ``window``, from the kernel's own index arithmetic. One backward
    sweep computes all three gradients, so ``dq`` and ``dkv`` count the
    same tiles."""
    window = effective_window(window, length)
    passes = {"fwd": tiles.fwd, "dq": tiles.bwd, "dkv": tiles.bwd}
    return {name: (len(kernel.query_sweep_tiles(length, *tile, window)),
                   len(kernel.query_sweep_tiles(length, *tile, None)))
            for name, tile in passes.items()}


def causal_attention(q, k, v, block, window=None):
    """Causal grouped-query attention, scale ``1/sqrt(head size)``, no
    position embedding. ``q`` (B, L, Hq, d), ``k``, ``v`` (B, L, Hkv, d);
    query head ``h`` reads key-value head ``h // (Hq/Hkv)``. Query blocks
    of ``block`` rows, each against the keys up to its own end (and, under
    a ``window``, from the first key its first row sees), each under
    ``jax.checkpoint``: the (Hq, block, keys) scores of one block stand
    at a time, in float32."""
    bsz, length, q_heads, dim = q.shape
    kv_heads = k.shape[2]
    q = q.reshape(bsz, length, kv_heads, q_heads // kv_heads, dim)
    scale = 1.0 / math.sqrt(dim)
    window = effective_window(window, length)

    @jax.checkpoint
    def one(qb, kb, vb, start, first=None):
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qb, kb,
                       preferred_element_type=jnp.float32) * scale
        rows = start + jnp.arange(qb.shape[1])[:, None]
        cols = jnp.arange(kb.shape[1])[None, :]
        if first is None:
            keep = rows >= cols
        else:       # the keys from ``first`` on, and the band's lower edge
            cols = first + cols
            keep = (rows >= cols) & (rows - cols < window)
        s = jnp.where(keep, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(vb.dtype)
        return jnp.einsum("bgrqk,bkgd->bqgrd", p, vb)

    outs = []
    for start in range(0, length, block):
        end = min(start + block, length)
        if window is None:
            outs.append(one(q[:, start:end], k[:, :end], v[:, :end], start))
            continue
        first = max(start - window + 1, 0)
        outs.append(one(q[:, start:end], k[:, first:end], v[:, first:end],
                        start, first))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    return out.reshape(bsz, length, q_heads * dim)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_causal_attention(q, k, v, tiles=TILES, interpret=False,
                           window=None):
    """``causal_attention`` by the fused kernel. ``interpret`` runs the
    kernel in Pallas's interpreter (the CPU tests)."""
    return _fused_fwd(q, k, v, tiles, interpret, window)[0]


def residual_bytes(bsz, length, q_heads, head_dim, dtype):
    """Bytes of what the fused arm's forward pass names
    ``KERNEL_RESIDUAL``: its output in ``dtype`` and the rows' float32
    log-sum-exp."""
    return bsz * length * q_heads * (head_dim * jnp.dtype(dtype).itemsize + 4)


def _flat(x):
    """(B, L, H, d) to the kernel's (B, L, H * d'), each head zero-padded
    to the size the kernel runs it at: a zero column of ``q`` or ``k``
    adds nothing to a score, one of ``v`` or of the output's gradient is
    a zero column of the result, which ``_heads`` drops."""
    pad = kernel_head_dim(x.shape[-1]) - x.shape[-1]
    if pad:
        x = jnp.pad(x, ((0, 0),) * 3 + ((0, pad),))
    return x.reshape(*x.shape[:2], -1)


def _heads(flat, like):
    """The kernel's (B, L, H * d') back to ``like``'s (B, L, H, d)."""
    return flat.reshape(*like.shape[:3], -1)[..., :like.shape[-1]]


def _scale(q):
    return 1.0 / math.sqrt(q.shape[-1])


def _fused_fwd(q, k, v, tiles, interpret, window=None):
    window = effective_window(window, q.shape[1])
    out, lse = kernel.forward(_flat(q), _flat(k), _flat(v), q.shape[2],
                              k.shape[2], *tiles.fwd, interpret=interpret,
                              scale=_scale(q), window=window)
    out = _heads(out, q).reshape(*q.shape[:2], -1)
    # named before ``out`` is returned too: the product with ``W_o`` after
    # it reads ``out`` for its own gradient, from the kept array
    out = checkpoint_name(out, KERNEL_RESIDUAL)
    lse = checkpoint_name(lse, KERNEL_RESIDUAL)
    return out, (q, k, v, out, lse)


def _fused_bwd(tiles, interpret, window, saved, do):
    q, k, v, out, lse = saved
    window = effective_window(window, q.shape[1])
    heads = q.shape[2], k.shape[2]
    # the rows' sum(do * out), (B, Hq, L) as the log-sum-exp
    di = (do.astype(jnp.float32) * out.astype(jnp.float32)).reshape(
        q.shape).sum(-1).transpose(0, 2, 1)
    operands = (_flat(q), _flat(k), _flat(v), _flat(do.reshape(q.shape)),
                lse, di)
    dq, dk, dv = kernel.backward(*operands, *heads, *tiles.bwd,
                                 interpret=interpret, scale=_scale(q),
                                 window=window)
    return _heads(dq, q), _heads(dk, k), _heads(dv, v)


fused_causal_attention.defvjp(_fused_fwd, _fused_bwd)
