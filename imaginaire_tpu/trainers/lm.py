"""Token-model trainer: one step program, no discriminator.

``gen_forward`` is the mean next-token cross-entropy the model returns
over its vocabulary slice (``lm``) and, where the model has a
multi-token-prediction module, that module's (``mtp``, weighed by
``gen.nextn_loss_weight``); the step is ``BaseTrainer._gen_step_fn`` as
it is. The expert layers' routing counts ride the step's losses, so the
loop reads them without a device sync of its own: telemetry's flush hook
turns the newest step's into the ``moe/<layer>/*`` counters, its losses
into ``lm/main`` and ``lm/mtp``, and the window's tokens into
``perf/tokens_per_sec``.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from imaginaire_tpu import telemetry
from imaginaire_tpu.config import as_attrdict, cfg_get
from imaginaire_tpu.models.generators import hybrid_lm
from imaginaire_tpu.ops import (attention, delta_rule, grouped_matmul,
                                held_experts, state_space)
from imaginaire_tpu.optim.remat import resolve_policy
from imaginaire_tpu.trainers.base import BaseTrainer

COUNTERS = ("held_assignments", "load_max_over_mean", "buffer_occupancy",
            "compact", "moved_rows")
# the step's unweighted losses -> the counters the flush hook gives them
LOSS_COUNTERS = {"lm": "lm/main", "mtp": "lm/mtp"}


def attn_impl(gen_cfg, tokens_shape):
    """The ``attn_impl`` meta of a (batch, length) step: the head size the
    scores run at, the size the fused arm's kernel runs such a head at (a
    head under a lane tile is zero-padded to one inside that arm), the
    arm each attention layer's scores take at it and
    this length on this backend (``ops/attention.py`` decides; nothing
    here does), the fused arm's tiles, and the bytes each layer's block
    keeps of the kernel's forward pass for its backward passes (the
    output and the log-sum-exp: ``gen.remat``'s policy decides; 0 where
    the backward pass runs the forward kernel again, and on the plain
    arm, which has no kernel). Where a layer is fused: the products a
    tile of the kernel's one backward sweep computes
    (``backward_products``) and the bytes of a key-value head's ``dk``
    and ``dv`` that stand in VMEM through it
    (``vmem_accumulator_bytes``). A model with sliding-window layers adds
    each such layer's ``windows`` entry (the keys a query sees) and
    ``visited_tiles`` where the tiles divide the length: toward the
    output and toward each gradient, the tiles a head's sweep computes
    under the window
    over those on or below the diagonal, whichever arm the layer takes
    on this backend."""
    bsz, length = (int(n) for n in tokens_shape)
    g = hybrid_lm.model_settings(gen_cfg)
    head_dim = hybrid_lm.attention_head_dim(g)
    kinds = dict(enumerate(hybrid_lm.layer_kinds(g)))
    arms = {str(i): attention.arm_of(head_dim, length)
            for i, kind in kinds.items() if kind in "*W"}
    keeps = resolve_policy(g.remat).keeps_kernel_residuals
    a_layer = attention.residual_bytes(
        bsz, length, g.num_attention_heads, head_dim, g.compute_dtype)
    meta = dict(length=length, head_dim=head_dim,
                kernel_head_dim=attention.kernel_head_dim(head_dim),
                tiles=attention.TILES._asdict(), layers=arms,
                kept_bytes={i: a_layer if keeps and arm == "fused" else 0
                            for i, arm in arms.items()})
    if "fused" in arms.values():
        meta.update(
            backward_products=attention.BACKWARD_PRODUCTS,
            vmem_accumulator_bytes=attention.accumulator_bytes(
                length, head_dim))
    windowed = [str(i) for i, kind in kinds.items() if kind == "W"]
    if windowed:
        meta["windows"] = dict.fromkeys(windowed, g.sliding_window)
    if windowed and length % attention.TILES.largest == 0:
        visited = {name: list(counts) for name, counts in
                   attention.visited_tiles(length, g.sliding_window).items()}
        meta["visited_tiles"] = dict.fromkeys(windowed, visited)
    return meta


def kda_impl(gen_cfg, tokens_shape):
    """The ``kda_impl`` meta of a (batch, length) step: the delta-rule
    layers of the pattern, the heads held here (how many the whole layer
    has is the deployment's to say, not the program's) at their size, the
    chunk of the WY form, the rows of the sub-blocks its decayed products
    are built by (the whole chunk where ``KDA_SUB_BLOCK`` does not divide
    it), how many chunks' decays stand at once on the ``chunks`` arm, the
    arm each layer takes at this length on this backend
    (``ops/delta_rule.py`` decides; nothing here does), the fused arm's
    tiles (chunks a grid step of each sweep) and the bytes each layer's
    block keeps of the kernel's forward sweep for its backward sweep (the
    output and the chunks' entry states: ``gen.remat``'s policy decides;
    0 on the ``chunks`` arm, which has no kernel); None for a model
    without such a layer."""
    g = hybrid_lm.model_settings(gen_cfg)
    layers = [i for i, kind in enumerate(hybrid_lm.layer_kinds(g))
              if kind == "K"]
    if not layers:
        return None
    bsz, length = (int(n) for n in tokens_shape)
    arm = delta_rule.arm_of(g.kda_head_dim, g.kda_chunk_size, length)
    kept = (delta_rule.residual_bytes(
        bsz, length, g.kda_num_heads, g.kda_head_dim, g.kda_chunk_size)
            if arm == "fused"
            and resolve_policy(g.remat).keeps_kernel_residuals else 0)
    return dict(layers=layers, heads=g.kda_num_heads,
                head_dim=g.kda_head_dim, chunk=g.kda_chunk_size,
                sub_block=delta_rule.kda_sub_block(g.kda_chunk_size),
                chunks_at_once=delta_rule.KDA_CHUNKS_AT_ONCE,
                arm={str(i): arm for i in layers},
                tiles=delta_rule.TILES._asdict(),
                kept_bytes={str(i): kept for i in layers})


def ssd_impl(gen_cfg, tokens_shape):
    """The ``ssd_impl`` meta of a (batch, length) step: the Mamba-2 layers
    of the pattern, their heads at their size, the groups that share
    ``b`` and ``c``, the state's size, the chunk of the dual form, the arm
    each layer's scan takes at this length on this backend
    (``ops/state_space.py`` decides; nothing here does), the fused arm's
    tiles (chunks a grid step of each sweep) and the bytes each layer's
    block keeps of the kernel's forward sweep for its backward sweep (the
    output and the chunks' entry states: ``gen.remat``'s policy decides;
    0 on the ``chunks`` arm, which has no kernel); None for a model
    without such a layer."""
    g = hybrid_lm.model_settings(gen_cfg)
    layers = [i for i, kind in enumerate(hybrid_lm.layer_kinds(g))
              if kind == "M"]
    if not layers:
        return None
    bsz, length = (int(n) for n in tokens_shape)
    sizes = dict(heads=g.mamba_num_heads, head_dim=g.mamba_head_dim,
                 state=g.ssm_state_size, chunk=g.chunk_size)
    arm = state_space.arm_of(length=length, groups=g.n_groups, **sizes)
    kept = (state_space.residual_bytes(
        bsz, length, dtype=g.compute_dtype, **sizes)
            if arm == "fused"
            and resolve_policy(g.remat).keeps_kernel_residuals else 0)
    return dict(layers=layers, groups=g.n_groups, **sizes,
                arm={str(i): arm for i in layers},
                tiles=state_space.TILES._asdict(),
                kept_bytes={str(i): kept for i in layers})


def moe_impl(gen_cfg, tokens_shape):
    """The ``moe_impl`` meta of a (batch, length) step: the expert layers
    of the pattern with the arm their grouped products take on this
    backend (``ops/grouped_matmul.py`` decides; one arm where every tier
    takes the same, else each tier's in the tiers' order), the held
    experts' hidden size and width, the tiers of rows a step computes a
    layer on, and the kernel's (rows, width) output tiles, forward (the
    weights' gradient's too) and in the gradient to the rows, for the
    product up into the width and the one down out of it; what each
    layer's router reads (``own_norm``: the layer's own normed input;
    ``attention_input``: the normed input of the attention layer before
    it, ``use_early_router``), how it scores (``sigmoid``, or
    ``softmax_of_chosen``), the experts' ``hidden_act`` and the rows of
    the buffer a step's held assignments are sorted into; None for a
    model without such a layer."""
    g = hybrid_lm.model_settings(gen_cfg)
    layers = [i for i, kind in enumerate(hybrid_lm.layer_kinds(g))
              if kind == "E"]
    if not layers:
        return None
    bsz, length = (int(n) for n in tokens_shape)
    hidden, width = g.hidden_size, g.moe_intermediate_size
    tiers = held_experts.expert_tiers(
        bsz * length, g.num_experts_per_tok, g.held_count,
        g.n_routed_experts, g.expert_buffer_rows)
    arms = list(dict.fromkeys(
        grouped_matmul.arm_of(rows, *shape) for rows in tiers
        for shape in ((hidden, width), (width, hidden))))
    reads = "attention_input" if g.use_early_router else "own_norm"
    return dict(layers={str(i): "/".join(arms) for i in layers},
                hidden=hidden, width=width, held=g.held_count,
                tiers=list(tiers),
                router_input=dict.fromkeys(map(str, layers), reads),
                scoring=("softmax_of_chosen"
                         if g.moe_primary_router_apply_softmax
                         else "sigmoid"),
                activation=g.hidden_act,
                buffer_rows=g.expert_buffer_rows,
                tiles={"up": grouped_matmul.tiles_of(hidden, width)._asdict(),
                       "down": grouped_matmul.tiles_of(width,
                                                       hidden)._asdict()})


class Trainer(BaseTrainer):
    def __init__(self, cfg, *args, **kwargs):
        cfg = as_attrdict(cfg)
        # the default tree's dummy discriminator: a token model has none
        cfg["dis"] = None
        mp = cfg_get(cfg.trainer, "mixed_precision", None) or {}
        cfg.gen["compute_dtype"] = (
            str(cfg_get(mp, "compute_dtype", "bfloat16"))
            if cfg_get(mp, "enabled", False)
            else str(cfg_get(cfg.trainer, "compute_dtype", "float32")))
        super().__init__(cfg, *args, **kwargs)
        self._last_losses = None
        self._tokens_since_flush = 0
        self._flush_t0 = None
        tm = telemetry.get()
        if tm.enabled:
            tm.flush_hooks.append(self._flush_counters)

    def _init_loss(self, cfg):
        self.weights["lm"] = 1.0
        if cfg_get(cfg.gen, "nextn_pattern", None):
            self.weights["mtp"] = float(cfg.gen.nextn_loss_weight)

    def _to_compute_dtype(self, tree):
        """Nothing is cast here: the model casts each layer's kernels
        where it uses them (``gen.compute_dtype``, set above from
        ``trainer.mixed_precision``), and token ids have no float."""
        return tree

    def _audit_health(self, ok, grad_norm, step_counter, grads, params,
                      updates, spectral=None, ema=None):
        """The base's health summary with its norms taken every step and
        zeroed off the cadence, not under ``lax.cond``: a cond's operands
        are materialized, and at two thirds of a billion parameters the
        ``updates`` tree is 2.7 GB that otherwise fuses into the Adam
        pass (the compiler's memory analysis: 7.3 GB of temporaries
        against 4.9). The norms read what that pass reads anyway."""
        if ok is None:
            return {}
        from imaginaire_tpu.diagnostics import audit

        pred = (step_counter % self.diag.every_n) == 0
        health = {k: jnp.where(pred, v, 0.0) for k, v in audit.module_health(
            grads, params, updates, grad_norm_total=grad_norm).items()}
        health.update(finite=ok, audited=pred, rng_step=step_counter)
        return health

    def gen_forward(self, vars_G, vars_D, loss_params, data, rng,
                    training=True):
        out = self.net_G.apply(vars_G, data, training=training)
        overflow = sum(v for k, v in out.items() if k.endswith("/overflow"))
        # an assignment the buffer had no row for fails the step's finite
        # flag (the update does not land) rather than vanishing
        losses = {"lm": out["loss"] + jnp.where(overflow > 0, jnp.nan, 0.0)}
        if "mtp_loss" in out:
            losses["mtp"] = out["mtp_loss"]
        losses.update({k: v for k, v in out.items() if k.startswith("moe/")})
        return losses, {}

    def gen_update(self, data):
        if self._flush_t0 is None:
            self._flush_t0 = time.perf_counter()
            self._note_attn_impl(data["tokens"].shape)
        losses = super().gen_update(data)
        self._last_losses = losses
        self._tokens_since_flush += int(data["tokens"].size)
        return losses

    def _note_attn_impl(self, tokens_shape):
        """One ``attn_impl`` meta as the step is first built, one
        ``kda_impl`` where the model has delta-rule layers, one
        ``ssd_impl`` where it has Mamba-2 layers and one ``moe_impl``
        where it has expert layers."""
        tm = telemetry.get()
        if not tm.enabled:
            return
        tm.meta("attn_impl", **attn_impl(self.cfg.gen, tokens_shape))
        for name, impl in (("kda_impl", kda_impl), ("ssd_impl", ssd_impl),
                           ("moe_impl", moe_impl)):
            meta = impl(self.cfg.gen, tokens_shape)
            if meta:
                tm.meta(name, **meta)

    def _flush_counters(self, tm, step):
        """At telemetry's flush, behind its fence: tokens a second over
        the flush window, and the newest step's routing counts."""
        now = time.perf_counter()
        if self._tokens_since_flush and now > (self._flush_t0 or now):
            tm.counter("perf/tokens_per_sec",
                       self._tokens_since_flush / (now - self._flush_t0),
                       step=step)
        self._flush_t0, self._tokens_since_flush = now, 0
        if self._last_losses is None:
            return
        wanted = {k: v for k, v in self._last_losses.items()
                  if k.rsplit("/", 1)[-1] in COUNTERS}
        wanted.update({name: self._last_losses[k]
                       for k, name in LOSS_COUNTERS.items()
                       if k in self._last_losses})
        # lint: allow(host-sync) -- flush cadence, behind the flush's own fence
        for name, value in jax.device_get(wanted).items():
            tm.counter(name, float(value), step=step)
