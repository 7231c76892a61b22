"""Fromage / Madam optimizers and the config-driven factory.

ref: imaginaire/optimizers/fromage.py:11-44, madam.py:9-62,
imaginaire/utils/trainer.py:219-306 (factory + lr policies).
"""

from __future__ import annotations

import math
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

from imaginaire_tpu.config import cfg_get


def fromage(lr: float):
    """Fromage (arXiv:2002.03432): norm-rescaled step + 1/sqrt(1+lr^2)
    shrink (ref: fromage.py:20-44). Stateless."""

    shrink = 1.0 / math.sqrt(1.0 + lr ** 2)

    def init_fn(params):
        del params
        return optax.EmptyState()

    def update_fn(grads, state, params=None):
        if params is None:
            raise ValueError("fromage requires params")

        def upd(g, p):
            g_norm = jnp.linalg.norm(g)
            p_norm = jnp.linalg.norm(p)
            scaled = jnp.where((p_norm > 0.0) & (g_norm > 0.0),
                               g * (p_norm / jnp.maximum(g_norm, 1e-30)), g)
            new_p = (p - lr * scaled) * shrink
            return new_p - p

        return jax.tree_util.tree_map(upd, grads, params), state

    return optax.GradientTransformation(init_fn, update_fn)


class MadamState(NamedTuple):
    step: jnp.ndarray
    exp_avg_sq: optax.Updates
    p_max: optax.Updates


def madam(lr: float, scale: float = 3.0, g_bound: Optional[float] = None):
    """Madam (arXiv:2006.14560): multiplicative update clamped to a
    scale-of-init bound (ref: madam.py:20-62)."""

    def init_fn(params):
        return MadamState(
            step=jnp.zeros((), jnp.int32),
            exp_avg_sq=jax.tree_util.tree_map(jnp.zeros_like, params),
            p_max=jax.tree_util.tree_map(
                lambda p: scale * jnp.sqrt(jnp.mean(p * p)), params),
        )

    def update_fn(grads, state, params=None):
        if params is None:
            raise ValueError("madam requires params")
        step = state.step + 1
        bias_correction = 1.0 - 0.999 ** step.astype(jnp.float32)

        def upd(g, p, avg_sq, p_max):
            new_avg = 0.999 * avg_sq + 0.001 * g * g
            g_normed = g / jnp.sqrt(new_avg / bias_correction)
            g_normed = jnp.nan_to_num(g_normed, nan=0.0)
            if g_bound is not None:
                g_normed = jnp.clip(g_normed, -g_bound, g_bound)
            new_p = p * jnp.exp(-lr * g_normed * jnp.sign(p))
            new_p = jnp.clip(new_p, -p_max, p_max)
            return new_p - p, new_avg

        flat = jax.tree_util.tree_map(upd, grads, params, state.exp_avg_sq, state.p_max)
        updates = jax.tree_util.tree_map(lambda t: t[0], flat,
                                         is_leaf=lambda t: isinstance(t, tuple))
        new_avg_sq = jax.tree_util.tree_map(lambda t: t[1], flat,
                                            is_leaf=lambda t: isinstance(t, tuple))
        return updates, MadamState(step=step, exp_avg_sq=new_avg_sq, p_max=state.p_max)

    return optax.GradientTransformation(init_fn, update_fn)


def get_scheduler(cfg_opt, iters_per_epoch: int = 1) -> Callable[[int], float]:
    """lr-policy -> multiplier(step). 'step' decays by gamma every
    step_size EPOCHS like torch StepLR (ref: utils/trainer.py:219-240);
    steps are converted via iters_per_epoch. 'constant' -> 1.0."""
    policy = cfg_get(cfg_opt, "lr_policy", None) or {}
    ptype = cfg_get(policy, "type", "constant")
    if ptype == "constant":
        return lambda step: 1.0
    # iteration_mode counts optimizer steps directly; epoch mode converts
    # via iters_per_epoch (ref: utils/trainer.py:219-258)
    iteration_mode = cfg_get(policy, "iteration_mode", False)
    if ptype == "step":
        step_size = policy["step_size"]
        gamma = policy["gamma"]

        def sched(step):
            unit = step if iteration_mode else step // max(iters_per_epoch, 1)
            return gamma ** (unit // step_size)

        return sched
    if ptype == "linear":
        # constant until decay_start, then linear to 0 at decay_end
        # (ref scheduler family)
        decay_start = cfg_get(policy, "decay_start", 0)
        decay_end = cfg_get(policy, "decay_end", decay_start + 1)

        def sched(step):
            # trace-safe: called with a traced step inside the jitted update
            import jax.numpy as jnp

            unit = step if iteration_mode else step // max(iters_per_epoch, 1)
            frac = (unit - decay_start) / max(decay_end - decay_start, 1)
            return jnp.clip(1.0 - frac, 0.0, 1.0)

        return sched
    raise NotImplementedError(f"Learning rate policy {ptype} not implemented.")


def init_optimizer_state(tx, params, plan=None):
    """``tx.init(params)``, materialized under a partition plan.

    With an active ``PartitionPlan`` (parallel/partition.py) the init
    runs as a jitted program whose ``out_shardings`` are the plan's
    cross-replica update-state specs (arXiv:2004.13336): every moment
    leaf is *born* as its 1/N data-axis shard (+ model-axis channel
    shard where the rules match), so the full replicated moment tree —
    2x param bytes for adam, the single biggest entry of the train
    state — never exists on any chip, not even
    transiently at init. Scalar bookkeeping leaves (adam ``count``,
    madam ``step``/``p_max``) resolve to replicated. Without a plan
    this is exactly ``tx.init(params)``.
    """
    if plan is None or not getattr(plan, "active", False):
        return tx.init(params)
    import jax
    from jax.sharding import NamedSharding

    shapes = jax.eval_shape(tx.init, params)
    specs = plan.update_state_specs(shapes)
    mesh = plan.mesh
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: type(s).__name__ == "PartitionSpec")
    # lint: allow(bare-jit) -- one-shot sharded optimizer-state init at t=0; out_shardings placement, never re-dispatched
    return jax.jit(tx.init, out_shardings=shardings)(params)


def get_optimizer_for_params(cfg_opt, sched: Optional[Callable[[int], float]] = None):
    """Build the optax chain for one network (ref: utils/trainer.py:261-306).

    Returns GradientTransformation; lr schedule (if any) multiplies the
    base lr per step.
    """
    opt_type = cfg_get(cfg_opt, "type", "adam")
    lr = cfg_get(cfg_opt, "lr", 1e-4)
    if sched is not None:
        lr_sched = lambda step: lr * sched(step)  # noqa: E731
    else:
        lr_sched = lr

    if opt_type == "adam":
        return optax.adam(
            learning_rate=lr_sched,
            b1=cfg_get(cfg_opt, "adam_beta1", 0.9),
            b2=cfg_get(cfg_opt, "adam_beta2", 0.999),
            eps=cfg_get(cfg_opt, "eps", 1e-8),
        )
    if opt_type == "rmsprop":
        base = optax.rmsprop(
            learning_rate=lr_sched,
            eps=cfg_get(cfg_opt, "eps", 1e-8),
        )
        wd = cfg_get(cfg_opt, "weight_decay", 0)
        if wd:
            return optax.chain(optax.add_decayed_weights(wd), base)
        return base
    if opt_type == "sgd":
        return optax.sgd(
            learning_rate=lr_sched,
            momentum=cfg_get(cfg_opt, "momentum", 0) or None,
        )
    if opt_type == "fromage":
        # fromage's shrink couples lr into the update; schedules would
        # change the contraction factor — keep static lr like the reference.
        return fromage(lr)
    if opt_type == "madam":
        return madam(lr, scale=cfg_get(cfg_opt, "scale", 3.0),
                     g_bound=cfg_get(cfg_opt, "g_bound", None))
    raise NotImplementedError(f"Optimizer {opt_type} is not yet implemented.")
