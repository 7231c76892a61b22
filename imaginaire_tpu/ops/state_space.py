"""The Mamba-2 recurrence in its chunked state-space dual form
(``ssd_scan``): within a chunk the masked ``c b^T`` product, across chunks
the carried state, in plain ``jax.numpy`` and one ``lax.scan``. Step
sizes, decays and the carried state are the fp32 island ``ssm_scan``
(``analysis/islands.py``); the products between run in the compute dtype
and accumulate in float32.

Not a reference op and no ``implementation``: one arm, on every backend.
The mixer that calls it (``hybrid_lm.Mamba2Mixer``) makes the step sizes
and decays (softplus, ``-exp(A_log)``) in the same island and stands the
call under ``lm/mamba2/ssd_scan``. Import it as a module,
``from imaginaire_tpu.ops import state_space``.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from imaginaire_tpu.analysis import islands


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
