"""The Mamba-2 recurrence in its chunked state-space dual form, per head
with state ``S`` (P, N), ``S_0 = 0``:

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t b_t^T,    y_t = S_t c_t

evaluated in chunks of ``chunk`` steps: within a chunk by the masked ``c
b^T`` product, across chunks by the carried state. Step sizes, decays and
the carried state are the fp32 island ``ssm_scan``
(``analysis/islands.py``); the products between run in the compute dtype
and accumulate in float32.

One algorithm, two arms, and ``ssd_scan`` picks between them from what it
can observe (``arm_of``), with no option:

  ``fused``   two Pallas TPU kernels, a forward and a backward sweep
              (``ops/pallas/state_space_kernel.py``): a chunk's (chunk,
              chunk) decays, ``c b^T``, the weights and every head's (P, N)
              state stand in VMEM, the operands are read from the mixer's
              own (B, L, .) layout, and nothing (chunk, chunk) reaches HBM.
              Where the backend is a TPU, the head size 64 or 128, the
              state a multiple of 128, the chunk a multiple of 128 that
              divides the length, and a group has a multiple of 8 heads.
  ``chunks``  ``ssd_chunks``: the same arithmetic in plain ``jax.numpy``
              and one ``lax.scan`` over the chunks. Everywhere else: the
              CPU, where the tests run, ragged lengths, the unit-test
              YAML's head size of 16.

The fused arm's forward sweep names its output and the state each chunk
starts from ``KERNEL_RESIDUAL``: a block recomputed under
``optim/remat.py``'s ``blocks`` keeps the two and runs the backward
kernel, not the forward kernel a second time. ``TILES`` (chunks a grid
step, each sweep's) were chosen on a v5e chip at 64 heads of 64 in 8
groups, state 128 and 8,192 positions (PERF.md, PR 46); they are not
configuration.

The mixer that calls it (``hybrid_lm.Mamba2Mixer``) makes the step sizes
and decays (softplus, ``-exp(A_log)``) in the same island and stands the
call under ``lm/mamba2/ssd_scan``. Import it as a module,
``from imaginaire_tpu.ops import state_space``.
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
from imaginaire_tpu.ops.pallas import state_space_kernel as kernel


class Tiles(NamedTuple):
    """Chunks a grid step of the fused arm's forward and backward
    sweeps."""
    fwd: int
    bwd: int


TILES = Tiles(fwd=4, bwd=4)
# head sizes the kernels' lane tiles take: two heads or one to 128 lanes
HEAD_SIZES = (64, 128)


def arm_of(head_dim, state, chunk, length, heads, groups):
    """``"fused"`` or ``"chunks"``: which arm ``ssd_scan`` takes for these
    sizes on this process's backend. The kernels hold a group's heads side
    by side on whole lane tiles and read a group's rows of the (heads,
    chunk) sums as whole sublane tiles."""
    fits = (head_dim in HEAD_SIZES and state % kernel.LANES == 0
            and chunk % kernel.LANES == 0 and length % chunk == 0
            and heads % groups == 0
            and (heads // groups) % kernel.SUBLANES == 0)
    return "fused" if jax.default_backend() == "tpu" and fits else "chunks"


def ssd_scan(x, dt, a, b, c, chunk):
    """The Mamba-2 recurrence, per head with state ``S`` (P, N):

        S_t = exp(dt_t a) S_{t-1} + dt_t x_t b_t^T,    y_t = S_t c_t

    evaluated in chunks of ``chunk`` steps: within a chunk by the masked
    ``c b^T`` product, across chunks by the carried state. ``x``
    (B, L, H, P) and ``b``, ``c`` (B, L, G, N) in the compute dtype (head
    ``h`` reads group ``h // (H/G)``); ``dt`` (B, L, H) and ``a`` (H,)
    float32. Step sizes, decays and the carried state stay float32.
    Returns ``y`` (B, L, H, P) in ``x``'s dtype. A length that the chunk
    does not divide is padded with steps of size zero."""
    if arm_of(x.shape[3], b.shape[3], chunk, x.shape[1], x.shape[2],
              b.shape[2]) == "fused":
        return fused_ssd_scan(x, dt, a, b, c, chunk)
    return ssd_chunks(x, dt, a, b, c, chunk)


# ------------------------------------------------------- the ``chunks`` arm


def ssd_chunks(x, dt, a, b, c, chunk):
    """The ``chunks`` arm of ``ssd_scan``, operands and result as there."""
    islands.guard("ssm_scan", dt=dt, a=a)
    bsz, length, heads, _ = x.shape
    groups = b.shape[2]
    per = heads // groups
    pad = (-length) % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    n = (length + pad) // chunk
    dtype = x.dtype

    def chunked(v):
        return v.reshape(bsz, n, chunk, *v.shape[2:])

    x, dt, b, c = chunked(x), chunked(dt), chunked(b), chunked(c)
    x32 = x.astype(jnp.float32)
    with islands.scope("ssm_scan"):
        cum = jnp.cumsum(dt * a, axis=2).swapaxes(2, 3)   # (B, n, H, Q)
        # decay from step s to step l of one chunk, l >= s
        tril = jnp.tril(jnp.ones((chunk, chunk), bool))
        within = jnp.exp(jnp.where(
            tril, cum[..., :, None] - cum[..., None, :], -jnp.inf))
        to_end = jnp.exp(cum[..., -1:] - cum).swapaxes(2, 3)  # (B, n, Q, H)
        from_start = jnp.exp(cum).swapaxes(2, 3)              # (B, n, Q, H)
        chunk_decay = jnp.exp(cum[..., -1])                   # (B, n, H)
        xdt32 = x32 * dt[..., None]
        decayed32 = xdt32 * to_end[..., None]

    def grouped(v):                        # (B, n, Q, H, P) -> (.., G, per, P)
        return v.reshape(*v.shape[:3], groups, per, v.shape[-1])

    xdt = grouped(xdt32.astype(dtype))
    decayed = grouped(decayed32.astype(dtype))
    # within a chunk: (c_l . b_s) decay(l, s) dt_s x_s, summed over s <= l
    cb = jnp.einsum("bnlgk,bnsgk->bngls", c, b,
                    preferred_element_type=jnp.float32)
    within = within.reshape(bsz, n, groups, per, chunk, chunk)
    weights = (cb[:, :, :, None] * within).astype(dtype)
    y = jnp.einsum("bngrls,bnsgrp->bnlgrp", weights, xdt,
                   preferred_element_type=jnp.float32)
    # what each chunk adds to the state by its end
    added = jnp.einsum("bnsgrp,bnsgk->bngrpk", decayed, b,
                       preferred_element_type=jnp.float32)
    with islands.scope("ssm_scan"):
        def carry(state, inputs):
            decay, add = inputs
            return state * decay[..., None, None] + add, state

        added = added.reshape(bsz, n, heads, *added.shape[-2:])
        _, before = lax.scan(carry, jnp.zeros_like(added[:, 0]),
                             (chunk_decay.swapaxes(0, 1),
                              added.swapaxes(0, 1)))
        before = before.swapaxes(0, 1)                 # (B, n, H, P, N)
    # the state carried into the chunk, read at each of its steps
    before = before.reshape(bsz, n, groups, per, *before.shape[-2:])
    read = jnp.einsum("bnlgk,bngrpk->bnlgrp", c, before.astype(dtype),
                      preferred_element_type=jnp.float32)
    with islands.scope("ssm_scan"):
        y = y + read * from_start.reshape(bsz, n, chunk, groups, per, 1)
    y = y.reshape(bsz, n * chunk, heads, -1)[:, :length]
    return y.astype(dtype)


# -------------------------------------------------------- the ``fused`` arm


def per_step(length, chunk, tile):
    """Chunks a grid step of a sweep at ``length``: the tile constant
    where it divides the sequence's chunks, else their common divisor."""
    return math.gcd(length // chunk, tile)


def residual_bytes(bsz, length, heads, head_dim, state, chunk, dtype):
    """Bytes of what the fused arm's forward sweep names
    ``KERNEL_RESIDUAL``: its output in ``dtype`` and the float32 state
    each chunk starts from."""
    return bsz * heads * head_dim * (
        length * jnp.dtype(dtype).itemsize + (length // chunk) * state * 4)


def fused_ssd_scan(x, dt, a, b, c, chunk, tiles=TILES, interpret=False):
    """``ssd_chunks`` by the fused kernels. ``interpret`` runs them in
    Pallas's interpreter (the CPU tests)."""
    return _fused(x, dt, a, b, c, chunk, tiles, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _fused(x, dt, a, b, c, chunk, tiles, interpret):
    return _sweep_forward(x, dt, a, b, c, chunk, tiles, interpret, False)[0]


# One program a call site would trace and lower each sweep's kernel anew;
# under ``jax.jit`` the layers of one shape share one traced body and one
# lowered function, which the compiler inlines at each site under that
# site's ``op_name`` (as ``ops/delta_rule.py``'s kernels do).
_STATIC = ("groups", "chunk", "per_step", "interpret")
# lint: allow(bare-jit) -- inlined into the step program, never dispatched
_forward = jax.jit(kernel.forward, static_argnames=_STATIC + ("keep_states",))
# lint: allow(bare-jit) -- inlined into the step program, never dispatched
_backward = jax.jit(kernel.backward, static_argnames=_STATIC)


def _flat(v):
    return v.reshape(*v.shape[:2], -1)


def _sweep_forward(x, dt, a, b, c, chunk, tiles, interpret, keep_states):
    islands.guard("ssm_scan", dt=dt, a=a)
    with islands.scope("ssm_scan"):
        y, states = _forward(
            _flat(x), _flat(b), _flat(c), dt, a[None], groups=b.shape[2],
            chunk=chunk, per_step=per_step(x.shape[1], chunk, tiles.fwd),
            keep_states=keep_states, interpret=interpret)
    return y.reshape(x.shape), states


def _fused_fwd(x, dt, a, b, c, chunk, tiles, interpret):
    y, states = _sweep_forward(x, dt, a, b, c, chunk, tiles, interpret, True)
    # named before ``y`` is returned too: what follows reads ``y`` for its
    # own gradient, from the kept array
    y = checkpoint_name(y, KERNEL_RESIDUAL)
    states = checkpoint_name(states, KERNEL_RESIDUAL)
    return y, (x, dt, a, b, c, states)


def _fused_bwd(chunk, tiles, interpret, saved, d_y):
    x, dt, a, b, c, states = saved
    with islands.scope("ssm_scan"):
        d_x, d_b, d_c, d_dt, d_log = _backward(
            _flat(x), _flat(b), _flat(c), dt, a[None], states, _flat(d_y),
            groups=b.shape[2], chunk=chunk,
            per_step=per_step(x.shape[1], chunk, tiles.bwd),
            interpret=interpret)
        # the decay of a step is ``dt a``
        d_a = jnp.sum(d_log * dt, axis=(0, 1))
    return (d_x.reshape(x.shape), d_dt, d_a, d_b.reshape(b.shape),
            d_c.reshape(c.shape))


_fused.defvjp(_fused_fwd, _fused_bwd)
