"""Named rematerialization policies — ONE registry for every family.

ISSUE 10: the SPADE-only ``gen.remat`` knob becomes a uniform per-block
``jax.checkpoint`` policy surface across every generator and
discriminator (spade/vid2vid/fs_vid2vid, pix2pixHD, UNIT/MUNIT,
FUNIT/COCO-FUNIT). Configs name a policy; models resolve it here —
one error message, one registry:

  ``none``           no remat: every block activation stays live for the
                     backward pass (the fp32 seed behavior).
  ``blocks``         ``jax.checkpoint`` around each block: recompute
                     the block forward during backward; keep what a
                     kernel's forward pass handed its backward passes
                     (the arrays a block names
                     ``ops.attention.KERNEL_RESIDUAL``: the fused
                     attention kernel's output and log-sum-exp, which
                     only a second run of that kernel would give back)
                     and where an expert layer's routing found its rows
                     (``ROUTING_PLAN``: integers, which only a second
                     sort of the step's assignments would give back).
                     A block that names nothing keeps nothing: every
                     GAN family's, and a token model's off the fused
                     arm and without an expert layer. The historical
                     spade knob value.
  ``dots_saveable``  checkpoint each block but let XLA keep matmul/conv
                     outputs (``jax.checkpoint_policies.dots_saveable``)
                     — recompute only the cheap elementwise tail, the
                     middle ground on MXU-heavy blocks.
  ``save_nothing``   explicit ``nothing_saveable`` — the offload-style
                     maximally-frugal policy: keeps nothing at all, a
                     kernel's residuals neither (``blocks``' residency
                     wherever a block names none).

``training`` must be a STATIC positional argument under remat: a traced
kwarg bool breaks the blocks' Python control flow (norm mode switches,
dropout). The wrappers here put ``training`` FIRST — ``__call__(self,
training, x, *cond)`` with ``static_argnums=(1,)`` — so one fixed index
covers blocks with any conditional-input arity (vid2vid's up blocks take
one or two cond maps depending on the flow curriculum). The wrapped
block keeps the same flax ``name``, so the parameter tree is IDENTICAL
across policies and the knob can toggle mid-training.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
from flax import linen as nn

from imaginaire_tpu.ops.attention import KERNEL_RESIDUAL
from imaginaire_tpu.ops.held_experts import ROUTING_PLAN


class RematPolicy(NamedTuple):
    """A resolved registry entry. ``enabled`` False means no checkpoint
    wrapping at all; ``policy`` is the jax.checkpoint saveable-filter
    (None = the checkpoint default: save nothing);
    ``keeps_kernel_residuals`` says whether a block under it holds the
    arrays it names ``KERNEL_RESIDUAL`` from its forward to its backward
    pass (what ``policy`` does to them; the ``attn_impl`` meta reads it)."""

    name: str
    enabled: bool
    policy: Any
    keeps_kernel_residuals: bool = False


POLICIES = {
    "none": RematPolicy("none", False, None, keeps_kernel_residuals=True),
    "blocks": RematPolicy(
        "blocks", True,
        jax.checkpoint_policies.save_only_these_names(
            KERNEL_RESIDUAL, ROUTING_PLAN),
        keeps_kernel_residuals=True),
    "dots_saveable": RematPolicy(
        "dots_saveable", True, jax.checkpoint_policies.dots_saveable),
    "save_nothing": RematPolicy(
        "save_nothing", True, jax.checkpoint_policies.nothing_saveable),
}


def resolve_policy(name, where="remat"):
    """Resolve a policy name (or pass through a ``RematPolicy``); the
    single validation point for every family's remat knob. Raises at
    trace/init time so a bad config fails loudly before any step runs."""
    if isinstance(name, RematPolicy):
        return name
    key = "none" if name is None else str(name)
    try:
        return POLICIES[key]
    except KeyError:
        raise ValueError(
            f"{where}={name!r} is not a known remat policy; use one of "
            + ", ".join(repr(k) for k in POLICIES)) from None


# wrapped-class cache: nn.remat creates a new class; reusing it keeps
# repeated block construction cheap and class identities stable
_WRAPPED = {}


def remat_block_cls(block_cls, policy, where="remat"):
    """The Module class implementing ``policy`` over ``block_cls``.

    ``none`` returns ``block_cls`` unchanged (kwarg calling convention);
    enabled policies return an ``nn.remat``-lifted subclass whose
    ``__call__(training, x, *cond)`` is all-positional with ``training``
    static. Use :func:`call_block` to call either uniformly, or
    :func:`remat_block` for a closure with the uniform kwarg signature.
    """
    pol = resolve_policy(policy, where=where)
    if not pol.enabled:
        return block_cls
    key = (block_cls, pol.name)
    if key not in _WRAPPED:
        class _Positional(block_cls):
            _remat_positional = True

            def __call__(self, training, x, *cond):  # noqa: D102
                return block_cls.__call__(self, x, *cond, training=training)

        _Positional.__name__ = block_cls.__name__
        _Positional.__qualname__ = block_cls.__qualname__
        _WRAPPED[key] = nn.remat(_Positional, static_argnums=(1,),
                                 policy=pol.policy)
    return _WRAPPED[key]


def remat_hyper_block_cls(block_cls, policy, where="remat"):
    """Variant for hyper blocks (fs_vid2vid's ``HyperRes2dBlock``) whose
    per-sample predicted ``conv_weights``/``norm_weights`` ride the call
    as traced pytrees: ``__call__(training, conv_weights, norm_weights,
    x, *cond)``, everything but ``training`` traced."""
    pol = resolve_policy(policy, where=where)
    if not pol.enabled:
        return block_cls
    key = (block_cls, pol.name, "hyper")
    if key not in _WRAPPED:
        class _PositionalHyper(block_cls):
            _remat_positional = True
            _remat_hyper = True

            def __call__(self, training, conv_weights, norm_weights,
                         x, *cond):  # noqa: D102
                return block_cls.__call__(
                    self, x, *cond, conv_weights=conv_weights,
                    norm_weights=norm_weights, training=training)

        _PositionalHyper.__name__ = block_cls.__name__
        _PositionalHyper.__qualname__ = block_cls.__qualname__
        _WRAPPED[key] = nn.remat(_PositionalHyper, static_argnums=(1,),
                                 policy=pol.policy)
    return _WRAPPED[key]


def is_positional(blk):
    """True when ``blk`` came out of an enabled-policy wrapper and uses
    the training-first positional convention."""
    return bool(getattr(blk, "_remat_positional", False))


def call_block(blk, x, *cond, training=False):
    """Call a block built from :func:`remat_block_cls` with the uniform
    ``(x, *cond, training=...)`` convention, whatever the policy."""
    if is_positional(blk):
        return blk(training, x, *cond)
    return blk(x, *cond, training=training)


def call_hyper_block(blk, x, *cond, conv_weights=None, norm_weights=None,
                     training=False):
    """:func:`call_block` for :func:`remat_hyper_block_cls` blocks."""
    if is_positional(blk):
        return blk(training, conv_weights, norm_weights, x, *cond)
    return blk(x, *cond, conv_weights=conv_weights,
               norm_weights=norm_weights, training=training)


def remat_block(block_cls, policy, where="remat", **block_kw):
    """Compact-style convenience: build the block under ``policy`` and
    return a callable with the uniform ``(x, *cond, training=...)``
    signature. ``block_kw`` must carry ``name=`` so the parameter tree
    is policy-invariant."""
    cls = remat_block_cls(block_cls, policy, where=where)
    blk = cls(**block_kw)
    return lambda x, *cond, training=False: call_block(
        blk, x, *cond, training=training)
