"""GAN training loop skeleton (ref: imaginaire/trainers/base.py).

The reference BaseTrainer owns: a loss registry (criteria + weights),
alternating D/G updates with AMP, EMA model averaging, checkpointing,
image snapshots, FID scheduling, and speed-benchmark timers
(ref: base.py:27-829).

TPU-first redesign:
  - Training state is an explicit pytree
    ``{vars_G, vars_D, opt_G, opt_D, ema_G, num_ema_updates, step, rng_G,
    rng_D, loss_params}`` threaded through two jitted step functions
    (gen_step / dis_step). No wrapper nesting, no .module chains
    (contrast ref: base.py:58-63).
  - The whole update — forward, losses, backward, optimizer, EMA — is one
    XLA program per step type. The reference's per-phase CUDA-sync timers
    (base.py:723-787) map to whole-step wall times under
    ``block_until_ready`` (phases inside one fused program are not
    separable, by design).
  - bf16 is a compute-dtype policy instead of AMP loss scaling (bf16 has
    fp32's exponent range, so no scaler is needed).
  - Data parallelism: batches arrive sharded over the 'data' mesh axis;
    jit partitions the step SPMD-style and inserts gradient all-reduces
    (replaces DDP, ref: utils/trainer.py:193-216).
  - RNG: per-step keys are fold_in(stream, step) — deterministic resume,
    distinct noise per step; per-shard noise diversity comes from XLA
    partitioning the random op itself.
"""

from __future__ import annotations

import functools
import math
import os
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from imaginaire_tpu import telemetry
from imaginaire_tpu.config import as_attrdict, cfg_get
from imaginaire_tpu.telemetry import podview
from imaginaire_tpu.optim import (
    get_optimizer_for_params,
    get_scheduler,
    init_optimizer_state,
)
from imaginaire_tpu.parallel.mesh import is_master, master_only_print as print  # noqa: A001
from imaginaire_tpu.parallel.partition import PartitionPlan
from imaginaire_tpu.registry import resolve
from imaginaire_tpu.utils import checkpoint as ckpt_lib
from imaginaire_tpu.utils.meters import Meter
from imaginaire_tpu.utils.model_average import ema_init, ema_update

MUTABLE = ("batch_stats", "spectral")


class BaseTrainer:
    """Lifecycle: start_of_epoch / start_of_iteration / dis_update /
    gen_update / end_of_iteration / end_of_epoch / save_checkpoint /
    load_checkpoint / test (ref: base.py:267-405, 594-670)."""

    def __init__(self, cfg, net_G=None, net_D=None,
                 train_data_loader=None, val_data_loader=None):
        self.cfg = cfg = as_attrdict(cfg)
        self.train_data_loader = train_data_loader
        self.val_data_loader = val_data_loader

        if net_G is None:
            net_G = resolve(cfg.gen.type, "Generator")(cfg.gen, cfg.data)
        if net_D is None and cfg_get(cfg, "dis", None) is not None:
            net_D = resolve(cfg.dis.type, "Discriminator")(cfg.dis, cfg.data)
        self.net_G = net_G
        self.net_D = net_D

        iters_per_epoch = len(train_data_loader) if train_data_loader is not None else 1
        self.tx_G = get_optimizer_for_params(
            cfg.gen_opt, get_scheduler(cfg.gen_opt, iters_per_epoch))
        # no discriminator, no optimizer for one (a config without ``dis``
        # need not carry a ``dis_opt``)
        self.tx_D = None if net_D is None else get_optimizer_for_params(
            cfg.dis_opt, get_scheduler(cfg.dis_opt, iters_per_epoch))

        tcfg = cfg_get(cfg, "trainer", None) or {}
        self.model_average = cfg_get(tcfg, "model_average", False)
        self.model_average_beta = cfg_get(tcfg, "model_average_beta", 0.9999)
        self.model_average_start = cfg_get(tcfg, "model_average_start_iteration", 1000)
        self.model_average_remove_sn = cfg_get(tcfg, "model_average_remove_sn", True)
        self.clip_grad_norm_G = cfg_get(cfg_get(cfg, "gen_opt", {}), "clip_grad_norm", None)
        self.clip_grad_norm_D = cfg_get(cfg_get(cfg, "dis_opt", {}), "clip_grad_norm", None)
        self.speed_benchmark = cfg_get(tcfg, "speed_benchmark", False)
        # bf16 compute policy — the XLA-native replacement for apex AMP
        # (ref: utils/trainer.py:152-154). Master params stay fp32; the
        # forward/backward runs in compute_dtype (the cast is differentiable,
        # so grads accumulate back into fp32). bf16 shares fp32's exponent
        # range, so no loss scaler is needed. fp32 islands survive the
        # cast: norm statistics (activation_norm), SN power iteration
        # ('spectral' collection), loss accumulation, and audit norms.
        # cfg.trainer.mixed_precision is the structured knob; the legacy
        # scalar cfg.trainer.compute_dtype still works when it is absent
        # or disabled.
        mp = as_attrdict(cfg_get(tcfg, "mixed_precision", None) or {})
        if cfg_get(mp, "enabled", False):
            self.compute_dtype = jnp.dtype(
                cfg_get(mp, "compute_dtype", "bfloat16"))
        else:
            self.compute_dtype = jnp.dtype(
                cfg_get(tcfg, "compute_dtype", "float32"))
        self.mixed_precision = self.compute_dtype != jnp.float32

        # Loss registry (ref: base.py:163-197): subclasses fill weights in
        # _init_loss; loss values come from gen_forward/dis_forward.
        self.weights: Dict[str, float] = {}
        self._init_loss(cfg)

        self.current_epoch = 0
        self.current_iteration = 0
        # bit-exact resume bookkeeping (resilience/, ISSUE 7): the
        # epoch-relative batches-consumed offset rides the checkpoint's
        # runstate sidecar; on resume the train loop fast-forwards the
        # loader by ``resume_batch_in_epoch`` instead of replaying the
        # epoch from batch 0.
        self._epoch_start_iteration = 0
        self.resume_batch_in_epoch = 0
        self.state: Optional[dict] = None
        self.meters: Dict[str, Meter] = {}
        self.time_iteration = None
        self.time_epoch = None
        self._step_flops_probed = False
        # Training-health diagnostics (diagnostics/): the step programs
        # compute a fixed-size health summary at diagnostics.every_n_steps
        # cadence and guard non-finite updates in-graph; the monitor
        # polls with one-step lag so the loop stays fence-free.
        from imaginaire_tpu.diagnostics import HealthMonitor

        self.diag = HealthMonitor(cfg)
        # 2-D (data x model) partition plan (parallel/partition.py):
        # inactive (the seed's replicated-state semantics, byte-identical
        # programs) unless cfg.parallel opted in via mesh_shape/enabled.
        # When active, init_state commits the train state under the
        # plan's NamedShardings — wide conv channels over 'model',
        # optimizer/EMA trees over 'data' (arXiv:2004.13336) — and the
        # step programs constrain their output state to the same
        # layout, so warm steps keep one stable fingerprint.
        self.partition = PartitionPlan(cfg)
        self._state_shardings = None
        # --debug-nans repro runs disable donation: jax_debug_nans
        # re-runs the op eagerly, which would read already-invalidated
        # donated buffers (see train.py)
        self._donate = ((0,) if cfg_get(tcfg, "donate_step_buffers", True)
                        else ())
        # step programs dispatch through the compile ledger
        # (telemetry/xla_obs.py): the same compile that runs the step
        # records memory_analysis/cost_analysis and arms the recompile
        # tripwire; a disabled cfg.xla_obs degrades to plain jax.jit
        from imaginaire_tpu.telemetry import xla_obs

        self._jit_gen_step = xla_obs.compiled_program(
            "gen_step", self._gen_step_fn, donate_argnums=self._donate)
        self._jit_dis_step = xla_obs.compiled_program(
            "dis_step", self._dis_step_fn, donate_argnums=self._donate)

    # ------------------------------------------------------------------ setup

    def _init_loss(self, cfg):
        raise NotImplementedError

    def init_loss_params(self, key):
        """Parameters of loss networks (e.g. VGG); frozen, stored in state."""
        return {}

    def _init_data(self, data):
        """The example batch as the modules' inits take it: on the
        device, through ``_on_device`` (a no-op for a batch
        ``start_of_iteration`` already returned)."""
        from imaginaire_tpu.utils.misc import to_device

        return self._on_device(to_device(dict(data)))

    def _fake_output_for_init(self, data):
        """Shape-example generator output used to init the discriminator
        (unpaired trainers override: their D consumes images_ab/ba)."""
        return {"fake_images": jnp.zeros_like(data["images"])}

    def init_state(self, key, data):
        """Build and place the full train-state pytree from one example
        batch, under the ``init_state`` span (a large part of set-up at
        zoo width). Families override ``_init_state``."""
        with telemetry.span("init_state"):
            return self._init_state(key, data)

    def _init_state(self, key, data):
        """The Flax inits run under jit: eager init dispatches every op
        separately (minutes on CPU for a full generator); one traced
        program initializes in seconds.
        """
        from imaginaire_tpu.utils.misc import numeric_only

        data = self._init_data(numeric_only(data))
        k_g, k_d, k_loss, k_noise, k_rg, k_rd = jax.random.split(key, 6)
        # lint: allow(bare-jit) -- one-shot flax init at t=0, before the ledger's first step program
        vars_G = jax.jit(lambda rngs, d: self.net_G.init(rngs, d, training=True))(
            {"params": k_g, "noise": k_noise}, data)
        vars_G = dict(vars_G)
        state: Dict[str, Any] = {
            "vars_G": vars_G,
            "opt_G": init_optimizer_state(self.tx_G, vars_G["params"],
                                          self.partition),
            "step": jnp.zeros((), jnp.int32),
            "rng_G": k_rg,
            "rng_D": k_rd,
            "loss_params": self.init_loss_params(k_loss),
        }
        if self.net_D is not None:
            fake_out = self._fake_output_for_init(data)
            # lint: allow(bare-jit) -- one-shot flax init at t=0
            vars_D = dict(jax.jit(
                lambda rngs, d, f: self.net_D.init(rngs, d, f, training=True))(
                {"params": k_d, "dropout": k_d}, data, fake_out))
            state["vars_D"] = vars_D
            state["opt_D"] = init_optimizer_state(self.tx_D,
                                                  vars_D["params"],
                                                  self.partition)
            # Separate D step counter: with cfg.trainer.dis_step > 1 each
            # sub-step must draw distinct randomness (the G step only
            # advances 'step' once per iteration).
            state["step_D"] = jnp.zeros((), jnp.int32)
        if self.model_average:
            state["ema_G"] = ema_init(
                vars_G["params"], vars_G.get("spectral"),
                remove_sn=self.model_average_remove_sn)
            state["num_ema_updates"] = jnp.zeros((), jnp.int32)
        self.state = self._place_state(state, data)
        return self.state

    def _place_state(self, state, data=None):
        """Commit the state pytree under the partition plan's shardings
        (no-op without an active plan): params model-sharded per the
        rules, optimizer/EMA trees cross-replica sharded over 'data',
        everything committed BEFORE the first step so the compiled
        programs see their final layout from call one — no
        ``sharding_commit`` re-specialization, ``xla/recompiles`` 0.

        Multi-process without a partition plan (ISSUE 8): the state
        commits REPLICATED over the pod-global mesh. Leaving it on
        per-host local devices (the old behavior) silently compiled N
        independent single-host programs — each host trained its own
        replica with no gradient all-reduce at all. Committing globally
        makes the jitted step one SPMD program over every host's
        devices, with XLA inserting the cross-process collectives."""
        if self.partition.active:
            state, self._state_shardings = self.partition.place_state(
                state)
            return state
        if jax.process_count() > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from imaginaire_tpu.parallel.mesh import get_mesh
            from imaginaire_tpu.parallel.sharding import assemble_global

            return assemble_global(state,
                                   NamedSharding(get_mesh(), P()))
        if data is not None and self._feed_commits_batches(data):
            # the steps will come back from the first call with the
            # state committed (replicated) to the mesh their batches are
            # committed to: place it there now, or every step program
            # compiles twice, once for the uncommitted state and once
            # for the committed one (the ledger's ``sharding_commit``;
            # a zoo-width SPADE D step is minutes and gigabytes of host
            # memory to compile, PR 22)
            from jax.sharding import NamedSharding, PartitionSpec as P

            from imaginaire_tpu.parallel.mesh import peek_mesh

            return jax.device_put(state, NamedSharding(peek_mesh(), P()))
        return state

    def _feed_commits_batches(self, data):
        """Whether the training loop will hand the steps committed,
        sharded batches: a train loader behind the device prefetcher,
        and a batch the process mesh's data axis divides."""
        from imaginaire_tpu.data.device_prefetch import prefetch_settings
        from imaginaire_tpu.parallel.sharding import batch_commits

        return (self.train_data_loader is not None
                and prefetch_settings(self.cfg)[0]
                and batch_commits(data))

    def _constrain_state(self, state):
        """Pin a step program's output state to the placement layout
        (traced; no-op without an active plan). Keeping outputs on the
        exact input shardings is what makes the update-state sharding a
        steady state: moments stay 1/N-resident across steps, donation
        aliases input buffers, and the recompile tripwire stays
        quiet."""
        if not self.partition.active or self._state_shardings is None:
            return state
        return self.partition.constrain_state(state, self._state_shardings)

    # ------------------------------------------------------- subclass hooks

    def gen_forward(self, vars_G, vars_D, loss_params, data, rng, training=True):
        """Return (loss_dict, new_mutables_G). Traced under jit."""
        raise NotImplementedError

    def dis_forward(self, vars_G, vars_D, loss_params, data, rng, training=True):
        """Return (loss_dict, new_mutables_D). Traced under jit."""
        raise NotImplementedError

    def _get_outputs(self, net_D_output, real=True):
        """Relativistic GAN support: difference of D outputs
        (ref: base.py:498-536)."""
        relativistic = cfg_get(cfg_get(self.cfg, "trainer", {}), "gan_relativistic", False)

        def diff(a, b):
            return [diff(x, y) if isinstance(x, list) else x - y
                    for x, y in zip(a, b)]

        if real:
            if relativistic:
                return diff(net_D_output["real_outputs"], net_D_output["fake_outputs"])
            return net_D_output["real_outputs"]
        if relativistic:
            return diff(net_D_output["fake_outputs"], net_D_output["real_outputs"])
        return net_D_output["fake_outputs"]

    def _to_compute_dtype(self, tree):
        """Cast fp32 leaves to the compute dtype (identity for fp32 policy)."""
        if self.compute_dtype == jnp.float32:
            return tree
        dt = self.compute_dtype
        return jax.tree_util.tree_map(
            lambda x: x.astype(dt)
            if hasattr(x, "dtype") and x.dtype == jnp.float32 else x, tree)

    def _cast_net_vars(self, variables):
        """Compute-dtype view of a network's variables: cast ONLY the
        ``params`` collection. The fp32 islands — ``batch_stats`` running
        moments and the SN ``spectral`` u vectors — keep their dtype so
        statistics/power-iteration stay full-precision under bf16."""
        if variables is None or self.compute_dtype == jnp.float32:
            return variables
        return dict(variables,
                    params=self._to_compute_dtype(variables["params"]))

    def _total(self, losses):
        """Weighted sum over registered losses (ref: base.py:698-714)."""
        total = jnp.zeros(())
        for name, w in self.weights.items():
            if name in losses:
                total = total + losses[name].astype(jnp.float32) * w
        return total

    # --------------------------------------------------------- jitted steps

    def _audit_guard(self, losses, grads, state, net_key, opt_key,
                     new_params, new_opt, new_mut):
        """Diagnostics seam shared by the G/D step fns: compute the
        per-step finite flag, guard the update in-graph (a non-finite
        update never lands — params/opt/mutables keep their previous
        finite values), and hand back the guarded trees plus the
        (flag, grad-norm) pair the health summary reuses. Traced into
        the step programs; a no-op returning ``None`` flags when
        diagnostics are off."""
        if not self.diag.enabled:
            return new_params, new_opt, new_mut, None, None
        from imaginaire_tpu.diagnostics import audit

        grad_norm = audit.tree_norm(grads)
        ok = audit.finite_flag(losses["total"], grad_norm)
        old_vars = state[net_key]
        new_params = audit.select_finite(ok, new_params, old_vars["params"])
        new_opt = audit.select_finite(ok, new_opt, state[opt_key])
        new_mut = {k: (audit.select_finite(ok, v, old_vars[k])
                       if k in old_vars else v)
                   for k, v in new_mut.items()}
        return new_params, new_opt, new_mut, ok, grad_norm

    def _audit_health(self, ok, grad_norm, step_counter, grads, params,
                      updates, spectral=None, ema=None):
        """The step program's health summary: per-module norms under the
        cadence cond, plus the per-step control flags the monitor polls.
        Returns {} when diagnostics are off (stable step-fn arity)."""
        if ok is None:
            return {}
        from imaginaire_tpu.diagnostics import audit

        pred = (step_counter % self.diag.every_n) == 0
        health = audit.health_at_cadence(pred, grads, params, updates,
                                         spectral=spectral, ema=ema,
                                         grad_norm_total=grad_norm)
        health["finite"] = ok
        health["audited"] = pred
        health["rng_step"] = step_counter
        return health

    def _gen_step_fn(self, state, data):
        step0 = state["step"]
        rng = jax.random.fold_in(state["rng_G"], step0)

        def loss_fn(params_G):
            with jax.named_scope("step/cast"):
                vars_G = dict(state["vars_G"],
                              params=self._to_compute_dtype(params_G))
                vars_D = self._cast_net_vars(state.get("vars_D"))
                batch = self._to_compute_dtype(data)
            losses, new_mut = self.gen_forward(
                vars_G, vars_D, state["loss_params"], batch, rng)
            losses = {k: v.astype(jnp.float32) for k, v in losses.items()}
            total = self._total(losses)
            return total, (dict(losses, total=total), new_mut)

        (_, (losses, new_mut)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["vars_G"]["params"])
        if self.clip_grad_norm_G:
            with jax.named_scope("step/clip"):
                grads, _ = optax.clip_by_global_norm(self.clip_grad_norm_G).update(grads, optax.EmptyState())
        with jax.named_scope("step/optim"):
            updates, new_opt = self.tx_G.update(
                grads, state["opt_G"], state["vars_G"]["params"])
            new_params = optax.apply_updates(state["vars_G"]["params"],
                                             updates)
        with jax.named_scope("step/guard"):
            new_params, new_opt, new_mut, ok, grad_norm = self._audit_guard(
                losses, grads, state, "vars_G", "opt_G",
                new_params, new_opt, new_mut)
        new_vars_G = dict(state["vars_G"], params=new_params, **new_mut)
        state = dict(state, vars_G=new_vars_G, opt_G=new_opt,
                     step=step0 + 1)
        if self.model_average:
            n = state["num_ema_updates"] + 1
            with jax.named_scope("step/ema"):
                state["ema_G"] = ema_update(
                    state["ema_G"], new_params, n,
                    beta=self.model_average_beta,
                    start_iteration=self.model_average_start,
                    spectral=new_vars_G.get("spectral"),
                    remove_sn=self.model_average_remove_sn)
            state["num_ema_updates"] = n
        with jax.named_scope("step/health"):
            health = self._audit_health(
                ok, grad_norm, step0, grads, new_params, updates,
                spectral=new_vars_G.get("spectral"),
                ema=state.get("ema_G") if self.model_average else None)
        return self._constrain_state(state), losses, health

    def _dis_step_fn(self, state, data):
        step0 = state["step_D"]
        rng = jax.random.fold_in(state["rng_D"], step0)

        def loss_fn(params_D):
            with jax.named_scope("step/cast"):
                vars_D = dict(state["vars_D"],
                              params=self._to_compute_dtype(params_D))
                vars_G = self._cast_net_vars(state["vars_G"])
                batch = self._to_compute_dtype(data)
            losses, new_mut = self.dis_forward(
                vars_G, vars_D, state["loss_params"], batch, rng)
            losses = {k: v.astype(jnp.float32) for k, v in losses.items()}
            total = self._total(losses)
            return total, (dict(losses, total=total), new_mut)

        (_, (losses, new_mut)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["vars_D"]["params"])
        if self.clip_grad_norm_D:
            with jax.named_scope("step/clip"):
                grads, _ = optax.clip_by_global_norm(self.clip_grad_norm_D).update(grads, optax.EmptyState())
        with jax.named_scope("step/optim"):
            updates, new_opt = self.tx_D.update(
                grads, state["opt_D"], state["vars_D"]["params"])
            new_params = optax.apply_updates(state["vars_D"]["params"],
                                             updates)
        with jax.named_scope("step/guard"):
            new_params, new_opt, new_mut, ok, grad_norm = self._audit_guard(
                losses, grads, state, "vars_D", "opt_D",
                new_params, new_opt, new_mut)
        new_vars_D = dict(state["vars_D"], params=new_params, **new_mut)
        state = dict(state, vars_D=new_vars_D,
                     opt_D=new_opt, step_D=step0 + 1)
        with jax.named_scope("step/health"):
            health = self._audit_health(
                ok, grad_norm, step0, grads, new_params, updates,
                spectral=new_vars_D.get("spectral"))
        return self._constrain_state(state), losses, health

    # ------------------------------------------------------------ lifecycle

    def gen_update(self, data):
        """(ref: base.py:594-632)."""
        t0 = time.time() if self.speed_benchmark else None
        from imaginaire_tpu.utils.misc import numeric_only

        batch = numeric_only(data)
        # the span is the DISPATCH: the host's time to fingerprint the
        # arguments and enqueue the program, not its time on the device
        with telemetry.span("gen_step", step=self.current_iteration):
            self.state, losses, health = self._jit_gen_step(self.state,
                                                            batch)
        # polls the PREVIOUS step's finite flag (already complete — no
        # pipeline stall) and triggers triage/skip/halt on non-finite
        self.diag.observe(self, "G", losses, health, batch,
                          self.current_iteration)
        if self.speed_benchmark:
            # lint: allow(host-sync) -- speed_benchmark timing fence, opt-in flag only
            jax.block_until_ready(self.state["vars_G"]["params"])
            self._meter("time/gen_step").write(time.time() - t0)
        self._log_losses("gen_update", losses)
        return losses

    def dis_update(self, data):
        """(ref: base.py:638-666)."""
        if self.net_D is None:
            return None
        t0 = time.time() if self.speed_benchmark else None
        from imaginaire_tpu.utils.misc import numeric_only

        batch = numeric_only(data)
        with telemetry.span("dis_step", step=self.current_iteration):
            self.state, losses, health = self._jit_dis_step(self.state,
                                                            batch)
        self.diag.observe(self, "D", losses, health, batch,
                          self.current_iteration)
        if self.speed_benchmark:
            # lint: allow(host-sync) -- speed_benchmark timing fence
            jax.block_until_ready(self.state["vars_D"]["params"])
            self._meter("time/dis_step").write(time.time() - t0)
        self._log_losses("dis_update", losses)
        return losses

    def start_of_epoch(self, current_epoch):
        self._start_of_epoch(current_epoch)
        self.current_epoch = current_epoch
        self.start_epoch_time = time.time()
        # epoch-relative batch accounting: normally this epoch starts at
        # the current iteration; on the first epoch after a mid-epoch
        # resume, ``resume_batch_in_epoch`` batches were already
        # consumed before the kill (the train loop fast-forwards the
        # loader past them), so the epoch's true start lies behind us.
        offset = int(self.resume_batch_in_epoch or 0)
        self._epoch_start_iteration = self.current_iteration - offset
        self.resume_batch_in_epoch = 0

    def start_of_iteration(self, data, current_iteration):
        from imaginaire_tpu.data.device_prefetch import PrefetchedBatch

        # host hook + H2D placement for a batch that was not prefetched;
        # near zero for one that was. The wait on the feed is the train
        # loop's own ``data_wait`` span, around ``next(feed)``.
        with telemetry.span("start_of_iteration", step=current_iteration):
            prefetched = isinstance(data, PrefetchedBatch)
            if not prefetched:
                data = self._start_of_iteration(data, current_iteration)
            self.current_iteration = current_iteration
            self.start_iteration_time = time.time()
            if prefetched:
                # a DevicePrefetcher already ran the host hook,
                # committed the numeric leaves as sharded device arrays
                # and ran ``_on_device`` — re-running any of them would
                # drag them back through the host
                return data
            from imaginaire_tpu.utils.misc import to_device

            return self._on_device(to_device(data))

    def _on_device(self, data):
        """What every dataset batch passes right after it is placed,
        whichever path placed it (the prefetcher, the synchronous arm,
        evaluation, the inits, the visualizations): a dataset's index
        map ``label`` becomes the float32 channel stack, so the step
        programs, the inference forward and the serving engine take the
        arrays a host-encoded batch gives. Device work only, enqueued
        and not waited for; a trainer whose hook reads the stack's
        channels extends this (pix2pixHD)."""
        from imaginaire_tpu.data.device_prefetch import expand_index_labels

        # a config without label types has no stack to build
        channels = self._label_channels
        return expand_index_labels(data, channels) if channels else data

    @functools.cached_property
    def _label_channels(self):
        """Channels of the label stack the config's data section names
        (0 without label types); read once, ``_on_device`` runs on every
        batch."""
        from imaginaire_tpu.utils.data import (
            get_paired_input_label_channel_number,
        )

        return get_paired_input_label_channel_number(self.cfg.data)

    def data_prefetcher(self, loader, iteration_of=None):
        """Wrap ``loader`` in a DevicePrefetcher honoring the
        ``data.device_prefetch`` knob; the loader comes back unchanged
        when prefetch is off (the synchronous to_device path) or the
        loader is already wrapped.

        ``iteration_of``: optional ``index -> current_iteration``
        mapping handed to the host-side ``_start_of_iteration`` hook
        (the train loop's epoch-relative counter); metric/test sweeps
        omit it and the hook sees -1, the side-effect-free mode.
        """
        from imaginaire_tpu.data.device_prefetch import (
            DevicePrefetcher,
            prefetch_settings,
        )

        enabled, depth = prefetch_settings(self.cfg)
        if not enabled or loader is None \
                or isinstance(loader, DevicePrefetcher):
            return loader

        def host_preprocess(batch, index):
            it = iteration_of(index) if iteration_of is not None else -1
            return self._start_of_iteration(batch, it)

        return DevicePrefetcher(loader, host_preprocess=host_preprocess,
                                depth=depth, on_device=self._on_device)

    def write_data_meters(self, stats):
        """Record drained DevicePrefetcher stats ({meter: [floats]}) —
        flushed with the loss meters on logging_iter, never a device
        sync (values are already host floats)."""
        for name, values in (stats or {}).items():
            meter = self._meter(name)
            for value in values:
                meter.write(value)

    def _eval_preprocess(self, data):
        """Side-effect-free per-batch prep for metric sweeps: host hook
        + transfer, skipped when a DevicePrefetcher already did both.
        ISSUE 18: the transfer is the committed data-axis placement, so
        the eval generator forward shards over the mesh exactly like a
        training step instead of running replicated."""
        from imaginaire_tpu.data.device_prefetch import PrefetchedBatch

        if isinstance(data, PrefetchedBatch):
            return data
        from imaginaire_tpu.parallel.sharding import place_committed_batch

        return self._on_device(
            place_committed_batch(self._start_of_iteration(data, -1)))

    def end_of_iteration(self, data, current_epoch, current_iteration):
        """(ref: base.py:294-373)."""
        # the span carries the iteration the batch was started with (the
        # callers count one further before they call), so one
        # iteration's spans share a step
        with telemetry.span("end_of_iteration",
                            step=self.current_iteration):
            self.current_epoch = current_epoch
            self.current_iteration = current_iteration
            self._end_of_iteration(data, current_epoch, current_iteration)
            self.time_iteration = time.time() - self.start_iteration_time
            tm = telemetry.get()
            if tm.enabled:
                self._register_step_flops(data)
                # heartbeat + ring-buffer accounting; the fence only runs
                # at the flush interval (never a per-step device sync)
                tm.step_complete(
                    current_iteration, items=self._batch_items(data),
                    dur_s=self.time_iteration,
                    # lint: allow(host-sync) -- heartbeat fence, runs only at the telemetry flush interval
                    fence=lambda: jax.block_until_ready(self.state))
                # pod digest (podview.py, ISSUE 17): publish/aggregate at
                # the digest cadence; inert null object single-process
                podview.get().on_step(current_iteration)
            cfg = self.cfg
            if current_iteration % cfg_get(cfg, "logging_iter", 100) == 0:
                self._meter("time/iteration").write(self.time_iteration)
                self._flush_meters(current_iteration)
                if cfg_get(cfg.trainer, "log_weight_stats", False):
                    self._write_weight_stats(current_iteration)
            if current_iteration % cfg_get(cfg, "snapshot_save_iter",
                                           10000) == 0:
                self.save_checkpoint(current_epoch, current_iteration)
                self.write_metrics()
            if current_iteration % cfg_get(cfg, "image_save_iter",
                                           10000) == 0:
                self.save_image(self._image_path(current_iteration), data)
            # continuous eval (ISSUE 18): mid-training FID/KID sweeps at
            # the cfg.evaluation.every_n_iter cadence, through the sharded
            # plane + reference store — quality lands in the same jsonl
            # the throughput counters do
            eval_every = cfg_get(cfg_get(cfg, "evaluation", {}) or {},
                                 "every_n_iter", None)
            if eval_every and current_iteration % int(eval_every) == 0:
                self.continuous_eval(current_iteration)

    def end_of_epoch(self, data, current_epoch, current_iteration):
        """(ref: base.py:375-405)."""
        self.current_epoch = current_epoch
        self.current_iteration = current_iteration
        # the last step's health entry is still pending (the monitor
        # polls with one-step lag); the epoch boundary is a safe place
        # to block on it
        self.diag.drain(self)
        self._end_of_epoch(data, current_epoch, current_iteration)
        self.time_epoch = time.time() - self.start_epoch_time
        print(f"Epoch: {current_epoch}, total time: {self.time_epoch:6f}.")
        if current_epoch % cfg_get(self.cfg, "snapshot_save_epoch", 20) == 0:
            self.save_checkpoint(current_epoch, current_iteration)
            self.write_metrics()

    @staticmethod
    def _batch_items(data):
        """Samples in a batch (``perf/imgs_per_sec`` accounting): leading
        dim of the first array leaf; video batches count frames (B*T). A
        token batch (B, L) counts its B packed sequences, not their
        tokens (``perf/tokens_per_sec`` is the token trainer's own)."""
        try:
            leaves = [v for v in (data or {}).values()
                      if hasattr(v, "shape") and getattr(v, "ndim", 0) >= 1]
            if not leaves:
                return 0
            lead = leaves[0]
            if getattr(lead, "ndim", 0) >= 5:  # (B, T, H, W, C)
                return int(lead.shape[0]) * int(lead.shape[1])
            return int(lead.shape[0])
        except Exception:  # noqa: BLE001 — accounting must never raise
            return 0

    def _register_step_flops(self, data):
        """Register per-iteration FLOPs with telemetry ONCE, from the
        compile ledger's cost analysis of the two step programs
        (recorded by the SAME compile that runs the step — no duplicate
        lower/compile),
        weighted by the dis_step/gen_step multipliers. Also emits the
        one-shot static memory-budget report (executable footprints +
        state tree sizes). Falls back to an explicit lower/compile when
        the ledger is disabled. Guarded by ``telemetry.mfu``; failures
        degrade to a debug log (MFU simply stays absent). Trainers
        whose update is not the base two-program step (vid2vid's
        per-frame rollout) override this to a no-op."""
        tm = telemetry.get()
        if self._step_flops_probed or not (tm.enabled and tm.wants_mfu) \
                or tm.step_flops is not None:
            return
        self._step_flops_probed = True
        from imaginaire_tpu.telemetry import xla_obs

        programs = [("gen_step", self._jit_gen_step,
                     cfg_get(self.cfg.trainer, "gen_step", 1))]
        if self.net_D is not None:
            programs.append(("dis_step", self._jit_dis_step,
                             cfg_get(self.cfg.trainer, "dis_step", 1)))
        ledger_flops = xla_obs.ledger_flops()
        total = 0.0
        try:
            for label, fn, mult in programs:
                flops = ledger_flops.get(label)
                if flops is None:
                    # ledger disabled/passthrough: the one-time
                    # explicit compile the ledger otherwise replaces
                    from imaginaire_tpu.utils.misc import numeric_only

                    with telemetry.span("cost_analysis"):
                        cost = fn.lower(self.state,
                                        numeric_only(data)).compile() \
                            .cost_analysis()
                    if isinstance(cost, list):
                        cost = cost[0]
                    flops = (cost or {}).get("flops")
                if flops is None or not math.isfinite(float(flops)):
                    return
                total += float(flops) * mult
        except Exception as e:  # noqa: BLE001 — MFU is best-effort
            import logging

            logging.getLogger(__name__).debug(
                "step cost analysis unavailable: %s", e)
            return
        tm.set_step_flops(total)
        # both step executables exist by now: report whether the run
        # fits (per-executable memory_analysis + param/opt/EMA bytes)
        xla_obs.emit_budget_report(self.state, tm=tm)

    def _write_weight_stats(self, step):
        """Spectral-norm σ/weight-norm stats per logging interval
        (ref: utils/meters.py:19-51, get_weight_stats — the reference
        ships it unwired; enable via trainer.log_weight_stats)."""
        from imaginaire_tpu.utils.meters import write_weight_stats

        for net_key, prefix in (("vars_G", "weights/G"),
                                ("vars_D", "weights/D")):
            tree = (self.state or {}).get(net_key)
            if tree and tree.get("spectral"):
                write_weight_stats(
                    prefix,
                    # lint: allow(host-sync) -- logging-cadence stat dump
                    jax.device_get(tree["params"]),
                    # lint: allow(host-sync) -- logging-cadence stat dump
                    jax.device_get(tree["spectral"]), step)

    # subclass extension points (ref: base.py:481-585)
    def _start_of_epoch(self, current_epoch):
        pass

    def _start_of_iteration(self, data, current_iteration):
        return data

    def _end_of_iteration(self, data, current_epoch, current_iteration):
        pass

    def _end_of_epoch(self, data, current_epoch, current_iteration):
        pass

    def _get_visualizations(self, data):
        return None

    def _fid_extractor(self):
        """Cached Inception-v3 feature extractor for FID
        (ref: evaluation/fid.py:16-58); fails loudly without ported
        weights unless trainer.fid_random_init."""
        if getattr(self, "_cached_fid_extractor", None) is None:
            from imaginaire_tpu.evaluation import inception

            variables = inception.load_params(
                random_init=cfg_get(cfg_get(self.cfg, "trainer", {}),
                                    "fid_random_init", False))
            self._cached_fid_extractor = inception.make_extractor(variables)
        return self._cached_fid_extractor

    def _compute_fid(self):
        return None

    def _extra_metric_activations(self, extractor):
        """Return (act_real, act_fake) Inception activations for KID/PRDC,
        or None when the trainer family doesn't support them. Image
        trainers use get_activations over the val loader; video trainers
        the pinned-sequence rollout (get_video_activations)."""
        return None

    def _cached_real_activations(self, cache_name, compute):
        """Real-set activations are identical across a checkpoint sweep —
        cache them beside the logdir like the FID real stats (tagged with
        the inception feature-graph version so a changed extractor
        recomputes). Random-init extractors (tests) never cache: their
        features change per process."""
        import os

        import numpy as np

        from imaginaire_tpu.evaluation.fid import FEATURE_GRAPH_VERSION

        if cfg_get(cfg_get(self.cfg, "trainer", {}), "fid_random_init",
                   False):
            return compute()
        path = os.path.join(cfg_get(self.cfg, "logdir", "."), cache_name)
        if os.path.exists(path):
            npz = np.load(path)
            if int(npz.get("graph_version", 0)) == FEATURE_GRAPH_VERSION:
                return npz["acts"]
        acts = compute()
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        np.savez(path, acts=acts, graph_version=FEATURE_GRAPH_VERSION)
        return acts

    def compute_extra_metrics(self, metrics):
        """KID / PRDC -> {name: value} — metrics the reference ships as
        library code (evaluation/kid.py, prdc.py) but never wires into
        its evaluate sweep; here evaluate.py --metrics does. The trainer
        family supplies activations via _extra_metric_activations; one
        (real, fake) pass feeds both metrics."""
        out = {}
        metrics = {str(m).lower() for m in (metrics or ())}
        unknown = metrics - {"kid", "prdc"}
        if unknown:
            print(f"Unknown extra metrics ignored: {sorted(unknown)}")
        metrics &= {"kid", "prdc"}
        if not metrics or self.val_data_loader is None:
            return out
        try:
            extractor = self._fid_extractor()
        except FileNotFoundError as e:
            print(f"extra metrics skipped: {e}")
            return out
        with telemetry.span("eval", step=self.current_iteration):
            acts = self._extra_metric_activations(extractor)
        if acts is None:
            return out
        act_real, act_fake = acts

        from imaginaire_tpu.evaluation.kid import kid_from_activations
        from imaginaire_tpu.evaluation.prdc import prdc_from_activations

        if "kid" in metrics:
            out["KID"] = float(kid_from_activations(act_real, act_fake))
        if "prdc" in metrics:
            prdc = prdc_from_activations(act_real, act_fake)
            out.update({f"PRDC_{k}": float(v) for k, v in prdc.items()})
        for name, value in out.items():
            self._meter(name).write(value)
        self._flush_meters(self.current_iteration)
        return out

    def write_metrics(self):
        """FID + best-FID tracking (ref: base.py:467-479)."""
        with telemetry.span("eval", step=self.current_iteration):
            fid = self._compute_fid()
        telemetry.get().heartbeat(self.current_iteration)
        if fid is not None:
            if getattr(self, "best_fid", None) is None or fid < self.best_fid:
                self.best_fid = fid
            self._meter("FID").write(float(fid))
            self._meter("best_FID").write(float(self.best_fid))
            self._flush_meters(self.current_iteration)

    # -------------------------------------------- quality plane (ISSUE 18)

    def eval_plane(self):
        """The trainer's quality-observability plane (lazy: the store
        directory and sentinel state live for the whole run, so sweep N
        hits the reference shard sweep 1 wrote and the EWMA trend spans
        the run)."""
        if getattr(self, "_eval_plane", None) is None:
            from imaginaire_tpu.evaluation.plane import EvalPlane

            self._eval_plane = EvalPlane(
                self.cfg, logdir=cfg_get(self.cfg, "logdir", "."))
        return self._eval_plane

    def _eval_resolution(self):
        """The eval-time resolution tag riding the reference-store key
        (from the val pipeline's deterministic sizing knobs; 'native'
        when none constrain it)."""
        data_cfg = cfg_get(self.cfg, "data", {}) or {}
        for group in (cfg_get(data_cfg, "val", None) or {}, data_cfg):
            aug = cfg_get(group, "augmentations", None) or {}
            for key in ("center_crop_h_w", "resize_h_w",
                        "random_crop_h_w"):
                value = cfg_get(aug, key, None)
                if value:
                    return str(value).replace(" ", "").replace(",", "x")
            side = cfg_get(aug, "resize_smallest_side", None)
            if side:
                return f"ss{int(side)}"
        return "native"

    def run_quality_sweep(self, step=None, metrics=None, max_batches=None):
        """One sweep through the sharded eval plane: reference acts via
        the content-addressed store, fake acts via the instrumented
        mesh-placed loop, FID (+KID) with ``eval/*`` counters and the
        regression sentinel. The single entry point continuous eval
        (``continuous_eval``) and offline ``evaluate.py`` share, so
        both emit one schema. Returns the plane's results dict or None
        (no val loader / no image-family generator closure / missing
        inception weights)."""
        if self.val_data_loader is None:
            return None
        make_gen = getattr(self, "_make_eval_gen_fn", None)
        vars_g = (self.state or {}).get("vars_G") \
            if isinstance(self.state, dict) else None
        if make_gen is None or vars_g is None:
            return None
        plane = self.eval_plane()
        extractor_tag = None
        if plane.settings.get("extractor") == "patch":
            # CI smoke extractor: the whole plane (placement, ledger,
            # store, sentinel) at negligible cost; tagged so its shards
            # never collide with real inception features
            from imaginaire_tpu.evaluation.plane import make_patch_extractor

            if getattr(self, "_cached_patch_extractor", None) is None:
                self._cached_patch_extractor = make_patch_extractor()
            extractor = self._cached_patch_extractor
            extractor_tag = "patch-v1:g8"
            random_init = False
        else:
            try:
                extractor = self._fid_extractor()
            except FileNotFoundError as e:
                print(f"quality sweep skipped: {e}")
                return None
            random_init = cfg_get(cfg_get(self.cfg, "trainer", {}),
                                  "fid_random_init", False)
        dataset_name = cfg_get(cfg_get(self.cfg, "data", {}) or {},
                               "name", "data")
        val_loader = self.data_prefetcher(self.val_data_loader)
        return plane.run_sweep(
            val_loader, "images", "fake_images", extractor,
            make_gen(vars_g),
            step=self.current_iteration if step is None else step,
            dataset_name=dataset_name, resolution=self._eval_resolution(),
            random_init=random_init, max_batches=max_batches,
            metrics=metrics, extractor_tag=extractor_tag)

    def continuous_eval(self, step, metrics=None):
        """The ``cfg.evaluation.every_n_iter`` cadence hook: a full
        quality sweep inside the watchdog-exempt eval span (sweeps are
        legitimately step-shaped-free time; the heartbeat re-arms from
        span exit), feeding the FID/best_FID meters like the
        snapshot-time ``write_metrics`` path does. ``evaluate.py``
        calls it per checkpoint with an explicit metrics list."""
        with telemetry.span("eval", step=step):
            result = self.run_quality_sweep(step=step, metrics=metrics)
        telemetry.get().heartbeat(step)
        if result is None:
            return None
        fid = result["fid"]
        if getattr(self, "best_fid", None) is None or fid < self.best_fid:
            self.best_fid = fid
        self._meter("FID").write(float(fid))
        self._meter("best_FID").write(float(self.best_fid))
        if "kid" in result:
            self._meter("KID").write(float(result["kid"]))
        self._flush_meters(step)
        return result

    # --------------------------------------------------------- persistence

    def _pre_save_checkpoint(self):
        """Hook run before checkpoint serialization (ref: base.py:408-414,
        e.g. pix2pixHD computes K-means cluster centers here)."""
        pass

    def save_checkpoint(self, current_epoch, current_iteration,
                        emergency=False):
        """(ref: base.py:790-829).

        ``emergency``: the preemption-guard path — forces a synchronous
        commit (the process is about to exit; an async save would race
        the teardown) and stamps the run state so resume is bit-exact.
        """
        from imaginaire_tpu import resilience

        self._pre_save_checkpoint()
        logdir = cfg_get(self.cfg, "logdir", ".")
        rset = resilience.resilience_settings(self.cfg)
        meta = {"epoch": current_epoch, "iteration": current_iteration}
        path = ckpt_lib.save_checkpoint(
            logdir, {"state": self.state, "meta": meta},
            current_epoch, current_iteration,
            max_to_keep=cfg_get(self.cfg, "checkpoints_to_keep", None),
            async_save=(not emergency
                        and bool(cfg_get(self.cfg.trainer,
                                         "async_checkpoint", False))),
            # Partition descriptor sidecar: restore compares it against
            # the live plan and reshards (jax.device_put) on any
            # mesh-shape / sharding-policy change instead of crashing or
            # silently replicating (see load_checkpoint). ISSUE 7: the
            # per-leaf checksums ride the same sidecar.
            partition_descriptor=(self.partition.describe()
                                  if self.partition.active else None),
            checksum=rset["checksum"])
        # Run-state sidecar (resilience/runstate.py): the host-side half
        # of a bit-exact resume — mid-epoch data position plus the
        # HealthMonitor and telemetry-ring state the pointer-file
        # restart used to silently reset.
        resilience.write_runstate(path, resilience.build_runstate(
            current_epoch, current_iteration,
            current_iteration - self._epoch_start_iteration,
            monitor=self.diag.state_dict(),
            telemetry_state=telemetry.get().state_dict()))
        # Recalibrated EMA BN stats ride alongside (a sibling file keeps
        # the state tree's structure stable across checkpoint versions);
        # the reference persists them inside the averaged model's buffers.
        if getattr(self, "_ema_batch_stats", None) is not None \
                and is_master():
            import pickle

            with open(path + ".ema_bn.pkl", "wb") as f:
                # lint: allow(host-sync) -- checkpoint serialization path
                pickle.dump(jax.device_get(self._ema_batch_stats), f)
        print(f"Save checkpoint to {path}")
        return path

    def load_checkpoint(self, checkpoint_path=None, resume=None,
                        fallback=False):
        """(ref: base.py:210-265): explicit path = weights-only unless
        resume=True; pointer-file discovery = resume.

        The discovery path verifies checksums and falls back: a corrupt
        / truncated pointed checkpoint is quarantined and the newest
        verifiable one restores instead (``ckpt_lib.load_latest_verified``).
        An explicit path never falls back by default — the caller asked
        for that exact checkpoint, so corruption raises; serving entry
        points (inference.py) pass ``fallback=True`` to quarantine the
        bad checkpoint and restore the newest verifiable sibling
        instead (ISSUE 8: serving must never deserialize a checkpoint
        training would refuse)."""
        from imaginaire_tpu import resilience

        logdir = cfg_get(self.cfg, "logdir", ".")
        verify = resilience.resilience_settings(self.cfg)["verify_on_load"]
        # restore-structure donor: the live state, or — after an
        # elastic rebind dropped it — the abstract template captured
        # from it (ISSUE 11). Orbax only needs per-leaf shape/dtype
        # plus the tree structure; without a donor the no-target path
        # returns nested dicts and the optimizer NamedTuples are lost.
        template = self.state if self.state is not None else getattr(
            self, "_elastic_state_template", None)
        target = ({"state": template,
                   "meta": {"epoch": 0, "iteration": 0}}
                  if template is not None else None)
        # an in-flight async save must commit before we read anything back
        ckpt_lib.wait_for_pending_checkpoint()
        if checkpoint_path is None:
            payload, checkpoint_path, fallbacks = \
                ckpt_lib.load_latest_verified(logdir, target=target,
                                              verify=verify)
            # Pod resume agreement (ISSUE 8): every host verified its
            # own candidate above; the cluster restores ONE checkpoint
            # (min over verified) or a host that disagreed follows it.
            payload, checkpoint_path = self._consensus_restore(
                payload, checkpoint_path, logdir, target, verify)
            if payload is None:
                print("No checkpoint found.")
                return False
            if fallbacks:
                print(f"Checkpoint fallback: restored {checkpoint_path} "
                      f"after quarantining {fallbacks} corrupt "
                      f"checkpoint(s)")
            resume = True if resume is None else resume
        else:
            try:
                payload = ckpt_lib.load_checkpoint(checkpoint_path,
                                                   target=target,
                                                   verify=verify)
            except Exception as e:  # noqa: BLE001 — corrupt/truncated
                if not fallback:
                    raise
                # serving fallback (ISSUE 8 satellite): quarantine the
                # named checkpoint and restore the newest one in its
                # directory that training itself would accept — a
                # server must never deserialize bytes the training
                # integrity layer refuses
                from imaginaire_tpu.resilience import (
                    quarantine_checkpoint,
                )

                print(f"WARNING: checkpoint {checkpoint_path} failed "
                      f"to restore ({type(e).__name__}: {str(e)[:200]});"
                      f" falling back to the newest verifiable "
                      f"checkpoint in its directory")
                quarantine_checkpoint(checkpoint_path,
                                      reason=f"serving restore failed: "
                                             f"{type(e).__name__}")
                ckpt_dir = os.path.dirname(
                    os.path.abspath(str(checkpoint_path)))
                payload, checkpoint_path, fallbacks = \
                    ckpt_lib.load_latest_verified(ckpt_dir,
                                                  target=target,
                                                  verify=verify)
                if payload is None:
                    raise RuntimeError(
                        f"no verifiable fallback checkpoint in "
                        f"{ckpt_dir} (no pointer file)") from e
                print(f"Serving fallback: restored {checkpoint_path}")
        restored = payload["state"]
        if resume:
            self.state = restored
            self.current_epoch = int(payload["meta"]["epoch"])
            self.current_iteration = int(payload["meta"]["iteration"])
            self._restore_runstate(checkpoint_path)
        elif self.state is None:
            # weights-only load before init_state: adopt the restored
            # state wholesale (counters stay at 0).
            self.state = restored
        else:
            # weights only
            self.state["vars_G"] = restored["vars_G"]
            if "vars_D" in restored and "vars_D" in self.state:
                self.state["vars_D"] = restored["vars_D"]
            if "ema_G" in restored:
                self.state["ema_G"] = restored["ema_G"]
        self._elastic_state_template = None  # structure donor consumed
        if resume:
            # mixed redistribution plan (ISSUE 13): leaves the
            # RedistributionPlanner routed "gather" were carried live
            # across the resize — overwrite the restored copies before
            # the re-commit so the carried bytes (bit-identical to the
            # emergency checkpoint by the planner's iteration guard)
            # are what lands under the new shardings
            self._apply_elastic_carry()
        self._reshard_restored_state(checkpoint_path)
        bn_path = str(checkpoint_path) + ".ema_bn.pkl"
        if os.path.exists(bn_path):
            import pickle

            with open(bn_path, "rb") as f:
                self._ema_batch_stats = pickle.load(f)
        print(f"Done with loading the checkpoint (resume={bool(resume)}).")
        return True

    def _consensus_restore(self, payload, checkpoint_path, logdir,
                           target, verify):
        """Pod resume agreement (ISSUE 8): every host publishes the
        iteration of the newest checkpoint IT verified; the cluster
        restores the min over verified. A host whose local candidate
        was newer (its copy of the consensus target verified, a peer's
        did not) — or whose own verification failed where a peer's
        succeeded — follows the consensus instead of silently training
        from different weights than the rest of the pod. A host that
        cannot restore the agreed checkpoint at all raises
        ``ClusterDesyncError`` (diverging silently is the one
        unacceptable outcome; ``resilience/resume_divergence`` stays
        fatal in the health gate). Single-process: identity."""
        from imaginaire_tpu.resilience import cluster

        if not cluster.is_active():
            return payload, checkpoint_path
        if cluster.membership_epoch() > 0:
            # post-resize membership (ISSUE 13): the checkpoint to
            # resume from was already agreed cluster-wide by the
            # ResizePlan, and restores are now legitimately asymmetric
            # — survivors on the live-gather route never call
            # load_checkpoint, so a joiner voting here would wait on
            # peers that are already training and desync the pod
            return payload, checkpoint_path
        it_local = (ckpt_lib.parse_checkpoint_name(checkpoint_path)[1]
                    if checkpoint_path else -1)
        name_local = (os.path.basename(str(checkpoint_path))
                      if checkpoint_path else None)
        consensus, votes = cluster.agree_min("resume", it_local,
                                             extra=name_local)
        if consensus < 0 or it_local == consensus:
            # nobody has a checkpoint, or this host already holds the
            # agreed one
            return payload, checkpoint_path
        name = next((x for v, x in votes.values()
                     if v == consensus and x), None)
        tm = telemetry.get()
        if tm.enabled:
            tm.meta("resilience/consensus_resume",
                    local_iteration=it_local, consensus=consensus,
                    consensus_checkpoint=name,
                    votes={str(p): v for p, (v, _) in votes.items()})
            tm.counter("resilience/consensus_overrides", 1)
        print(f"Pod resume consensus: this host verified iteration "
              f"{it_local if it_local >= 0 else '<none>'} but the "
              f"cluster agreed on {consensus} ({name}); following the "
              f"consensus")
        path = os.path.join(logdir, name)
        try:
            payload = ckpt_lib.load_checkpoint(path, target=target,
                                               verify=verify)
        except Exception as e:  # noqa: BLE001
            raise cluster.ClusterDesyncError(
                f"process {cluster.process_index()} cannot restore the "
                f"cluster-agreed checkpoint {path} "
                f"({type(e).__name__}: {str(e)[:300]}); refusing to "
                f"resume divergent — restart the pod after repairing "
                f"the checkpoint directory") from e
        return payload, path

    def _restore_runstate(self, checkpoint_path):
        """Replay the checkpoint's host-side run state (runstate
        sidecar): mid-epoch data position, HealthMonitor history, and
        the telemetry ring. A sidecar whose counters disagree with the
        checkpoint's own meta emits a ``resilience/resume_divergence``
        meta event — ``check_run_health`` fails any run that carries
        one (a stale or cross-wired sidecar would desynchronize the
        data stream from the RNG/step state)."""
        from imaginaire_tpu import resilience

        runstate = resilience.read_runstate(checkpoint_path)
        tm = telemetry.get()
        if runstate is None:
            # legacy checkpoint: coarse resume (epoch restarts at batch
            # 0, monitor/telemetry state fresh) — still correct weights,
            # just not bit-exact against an uninterrupted run
            self.resume_batch_in_epoch = 0
            if tm.enabled:
                tm.meta("resilience/resume", checkpoint=str(checkpoint_path),
                        iteration=self.current_iteration,
                        runstate=False)
            return
        if (int(runstate.get("iteration", -1)) != self.current_iteration
                or int(runstate.get("epoch", -1)) != self.current_epoch):
            if tm.enabled:
                tm.meta("resilience/resume_divergence",
                        checkpoint=str(checkpoint_path),
                        checkpoint_iteration=self.current_iteration,
                        runstate_iteration=runstate.get("iteration"),
                        checkpoint_epoch=self.current_epoch,
                        runstate_epoch=runstate.get("epoch"))
            import logging

            logging.getLogger(__name__).error(
                "runstate sidecar disagrees with checkpoint meta "
                "(ckpt epoch/iter %s/%s vs runstate %s/%s); ignoring "
                "the sidecar — resume will be coarse, not bit-exact",
                self.current_epoch, self.current_iteration,
                runstate.get("epoch"), runstate.get("iteration"))
            self.resume_batch_in_epoch = 0
            return
        self.resume_batch_in_epoch = int(runstate.get("batch_in_epoch",
                                                      0) or 0)
        try:
            self.diag.load_state_dict(runstate.get("monitor") or {})
        except Exception as e:  # noqa: BLE001 — observability only
            import logging

            logging.getLogger(__name__).warning(
                "health-monitor state restore failed: %s", e)
        try:
            tm.load_state_dict(runstate.get("telemetry") or {})
        except Exception as e:  # noqa: BLE001
            import logging

            logging.getLogger(__name__).warning(
                "telemetry state restore failed: %s", e)
        if tm.enabled:
            tm.meta("resilience/resume", checkpoint=str(checkpoint_path),
                    iteration=self.current_iteration,
                    batch_in_epoch=self.resume_batch_in_epoch,
                    runstate=True)

    def emergency_checkpoint(self, current_epoch, current_iteration,
                             guard=None):
        """Preemption drain: synchronous checkpoint + run-state sidecar
        under the ``ckpt_emergency`` span; disarms the guard's deadline
        timer once the commit lands. Returns the checkpoint path."""
        import time as _time

        t0 = _time.perf_counter()
        with telemetry.span("ckpt_emergency", step=current_iteration):
            path = self.save_checkpoint(current_epoch, current_iteration,
                                        emergency=True)
        ckpt_lib.wait_for_pending_checkpoint()
        dur_ms = (_time.perf_counter() - t0) * 1e3
        tm = telemetry.get()
        if tm.enabled:
            tm.counter("resilience/emergency_ckpt_ms", dur_ms,
                       step=current_iteration)
            tm.meta("resilience/emergency_checkpoint", path=str(path),
                    iteration=current_iteration, dur_ms=round(dur_ms, 2))
        if guard is not None:
            guard.disarm()
        print(f"Emergency checkpoint committed in {dur_ms:.0f}ms -> "
              f"{path}")
        return path

    def _reshard_restored_state(self, checkpoint_path):
        """Re-place a restored state under the CURRENT partition plan.

        ``load_checkpoint`` hands back host arrays (layout-agnostic by
        design), so a checkpoint written on one mesh shape loads on any
        other: here they are committed under the live plan's
        NamedShardings via ``jax.device_put`` — orbax never sees a
        spec mismatch, nothing silently replicates, and the step
        programs meet their expected layout on the first post-restore
        call. A saved-vs-current descriptor difference (mesh shape,
        sharding knobs, plan on/off) is surfaced as a ``ckpt/reshard``
        telemetry meta event."""
        saved = ckpt_lib.read_partition_sidecar(checkpoint_path)
        current = self.partition.describe() if self.partition.active \
            else None
        if saved != current and (saved is not None
                                 or current is not None):
            telemetry.get().meta("ckpt/reshard", saved=saved,
                                 current=current,
                                 checkpoint=str(checkpoint_path))
            print(f"Resharding restored checkpoint: saved partition "
                  f"{saved} -> current {current}")
        if self.partition.active or jax.process_count() > 1:
            # the pod resume re-commits under the global mesh
            # (replicated when no plan is active) — the same placement
            # init_state produced, so the warm step programs keep their
            # fingerprint
            self.state = self._place_state(self.state)
        else:
            # the restored leaves are host numpy (load_checkpoint is
            # layout-agnostic by design); commit them to device arrays
            # jax OWNS before the first post-restore step. A plain
            # ``device_put`` is not enough: on the CPU backend it
            # zero-copy-aliases an aligned numpy buffer, and the step
            # programs DONATE their state argument — freeing a buffer
            # numpy still owns is a use-after-free. ``jnp.array``
            # (copy=True by default) guarantees an owned buffer.
            import jax.numpy as jnp

            self.state = jax.tree_util.tree_map(jnp.array, self.state)

    def set_elastic_carry(self, carry):
        """Stash the gather-routed leaves a ``RedistributionPlanner``
        snapshot carried across the resize; the next resuming
        ``load_checkpoint`` splices them over the restored tree."""
        self._elastic_carry = dict(carry) if carry else None

    def _apply_elastic_carry(self):
        """Overwrite restored leaves with their carried live values
        (keyed by ``jax.tree_util.keystr`` path). Returns the number of
        leaves spliced. One-shot: the carry is consumed either way."""
        carry = getattr(self, "_elastic_carry", None)
        self._elastic_carry = None
        if not carry or self.state is None:
            return 0
        applied = [0]

        def _splice(path, leaf):
            key = jax.tree_util.keystr(path)
            if key in carry:
                applied[0] += 1
                return carry[key]
            return leaf

        self.state = jax.tree_util.tree_map_with_path(_splice, self.state)
        return applied[0]

    def elastic_recommit(self, carry, iteration, epoch):
        """All-gather elastic restore (ISSUE 13): every state leaf was
        carried across the resize as an owned host copy — rebuild the
        tree from the rebind template's STRUCTURE and commit it under
        the new world's shardings without touching the checkpoint (the
        downtime win the RedistributionPlanner exists for). The
        partition sidecar + runstate still come from the pointed
        checkpoint so batch-offset resume and reshard telemetry match
        the checkpoint route bit for bit."""
        template = getattr(self, "_elastic_state_template", None)
        if template is None:
            raise RuntimeError(
                "elastic_recommit needs the rebind template — call "
                "elastic_rebind() first")

        def _rebuild(path, leaf):
            key = jax.tree_util.keystr(path)
            if key not in carry:
                raise KeyError(
                    f"elastic_recommit: leaf {key} missing from the "
                    f"carry — the planner routed it 'gather' but no "
                    f"snapshot landed")
            return carry[key]

        self.state = jax.tree_util.tree_map_with_path(_rebuild, template)
        self.current_iteration = int(iteration)
        self.current_epoch = int(epoch)
        self._elastic_state_template = None
        checkpoint_path = ckpt_lib.latest_checkpoint_path(
            cfg_get(self.cfg, "logdir", "."))
        if checkpoint_path is not None:
            self._restore_runstate(checkpoint_path)
        self._reshard_restored_state(checkpoint_path)
        print(f"Done with the elastic re-commit (iteration "
              f"{self.current_iteration}, no checkpoint round-trip).")
        return True

    def elastic_rebind(self):
        """Rebind the trainer to a freshly resized pod (ISSUE 11).

        Called by the supervise loop AFTER ``elastic.apply`` tore the
        old distributed runtime down and the new mesh is installed. The
        old state arrays lived on backends that no longer exist, so
        ``self.state`` drops to None — an abstract shape/dtype template
        keeps its tree structure so the next ``load_checkpoint``
        restores into it (host numpy, layout-agnostic) and
        ``_reshard_restored_state`` commits the optimizer/EMA shards
        under the new world's NamedShardings (the PR-6 reshard-on-load,
        not a second reshard path). Every ledgered step program is
        retraced under ``retrace('elastic_resize')``: the executables
        baked the dead world's device ids into their bindings, and the
        named retrace keeps the recompile tripwire quiet."""
        from imaginaire_tpu.telemetry import xla_obs

        self.partition = PartitionPlan(self.cfg)
        self._state_shardings = None
        # the state's tree STRUCTURE must survive the rebind: the
        # no-target restore hands back plain nested dicts, and optax
        # update() needs its NamedTuples (ScaleByAdamState.mu) back.
        # An abstract shape/dtype template costs no memory and reads
        # only aval metadata — safe even though the arrays' backend is
        # already gone.
        self._elastic_state_template = jax.tree_util.tree_map(
            lambda x: (jax.ShapeDtypeStruct(x.shape, x.dtype)
                       if hasattr(x, "shape") and hasattr(x, "dtype")
                       else x),
            self.state) if self.state is not None else None
        self.state = None
        self._ema_batch_stats = None  # device arrays of the dead world
        retraced = []
        for name, value in vars(self).items():
            if isinstance(value, xla_obs.CompiledProgram):
                value.retrace("elastic_resize")
                retraced.append(value.label)
        return retraced

    # ------------------------------------------------------------ inference

    def inference_params(self):
        """EMA params when model averaging is on (ref: base.py:674-678);
        recalibrated BN stats when they have been estimated."""
        if self.model_average:
            variables = dict(self.state["vars_G"],
                             params=self.state["ema_G"])
            if getattr(self, "_ema_batch_stats", None) is not None:
                variables["batch_stats"] = self._ema_batch_stats
            return variables
        return self.state["vars_G"]

    def recalculate_model_average_batch_norm_statistics(self,
                                                        data_loader=None):
        """Re-estimate the EMA model's BN running stats as the
        cumulative mean of per-batch statistics over
        ``model_average_batch_norm_estimation_iteration`` training
        batches (ref: trainers/base.py:415-443 momentum=1/(n+1) loop,
        utils/model_average.py:9-33). The per-batch statistic is
        recovered from flax's linear running update
        (new = m*old + (1-m)*batch, m=0.9 — the layer default)."""
        if data_loader is None:
            data_loader = self.train_data_loader
        if not self.model_average or data_loader is None:
            return
        if getattr(self, "_ema_bn_recal_iter", None) == \
                self.current_iteration:
            return  # already estimated this iteration (FID + image save)
        n_iters = cfg_get(self.cfg.trainer,
                          "model_average_batch_norm_estimation_iteration",
                          30)
        old_stats = self.state["vars_G"].get("batch_stats")
        if not n_iters or old_stats is None or not jax.tree_util.tree_leaves(
                old_stats):
            return
        from imaginaire_tpu.utils.misc import numeric_only, to_device

        momentum = 0.9
        ema_vars = dict(self.state["vars_G"], params=self.state["ema_G"])
        mean_stats = None
        count = 0
        rng = jax.random.PRNGKey(1234)
        for it, data in enumerate(data_loader):
            if it >= n_iters:
                break
            # side-effect-free preprocessing: start_of_iteration would
            # reset the iteration's timers mid-metrics
            data = self._on_device(to_device(self._start_of_iteration(
                data, self.current_iteration)))
            _, new_mut = self._apply_G(ema_vars, numeric_only(data),
                                       jax.random.fold_in(rng, it),
                                       training=True)
            new_stats = new_mut.get("batch_stats")
            if new_stats is None:
                return
            batch_stat = jax.tree_util.tree_map(
                lambda new, old: (new - momentum * old) / (1 - momentum),
                new_stats, old_stats)
            count += 1
            if mean_stats is None:
                mean_stats = batch_stat
            else:
                mean_stats = jax.tree_util.tree_map(
                    lambda m, b: m + (b - m) / count, mean_stats,
                    batch_stat)
        if mean_stats is not None:
            self._ema_batch_stats = mean_stats
            self._ema_bn_recal_iter = self.current_iteration

    def inference_forward(self, variables, data, rng,
                          inference_args=None):
        """One inference forward of net_G. Routed through the attached
        serving engine when one is present (``ServingEngine.attach``) —
        the one-shot entry points then inherit the ledgered warm
        executables and serve/* SLO telemetry for free — else the
        legacy eager apply (byte-for-byte the seed behavior)."""
        engine = getattr(self, "_serving_engine", None)
        if engine is not None:
            return engine.forward(variables, data, rng,
                                  inference_args=inference_args)
        return self.net_G.apply(
            variables, data, training=False, rngs={"noise": rng},
            method=self.net_G.inference, **(inference_args or {}))

    def test(self, data_loader, output_dir, inference_args=None):
        """(ref: base.py:672-696)."""
        from imaginaire_tpu.utils.visualization import tensor2im, save_image_grid

        os.makedirs(output_dir, exist_ok=True)
        inference_args = inference_args or {}
        variables = self.inference_params()
        # overlap the next batch's host load + H2D with this batch's
        # generate (start_of_iteration skips re-prep for wrapped batches)
        data_loader = self.data_prefetcher(data_loader)
        tm = telemetry.get()
        for it, data in enumerate(tm.timed_iter(data_loader, "data_wait")):
            tm.heartbeat()
            data = self.start_of_iteration(data, current_iteration=-1)
            with tm.span("eval"):
                images = self.inference_forward(
                    variables, data, jax.random.PRNGKey(it),
                    inference_args=inference_args)
            keys = data.get("key", [f"{it:06d}_{i}" for i in range(images.shape[0])])
            if isinstance(keys, (str, bytes)):
                keys = [keys]
            for img, name in zip(np.asarray(images), keys):
                path = os.path.join(output_dir, f"{name}.jpg")
                os.makedirs(os.path.dirname(path), exist_ok=True)
                save_image_grid([tensor2im(img)], path)

    def save_image(self, path, data):
        """Visualization snapshot (ref: base.py:445-465)."""
        if not is_master():
            return
        vis = self._get_visualizations(data)
        if vis is None:
            return
        from imaginaire_tpu.utils.visualization import save_tensor_strip

        os.makedirs(os.path.dirname(path), exist_ok=True)
        save_tensor_strip(vis, path)
        print(f"Save output images to {path}")

    # -------------------------------------------------------------- meters

    def _meter(self, name):
        if name not in self.meters:
            self.meters[name] = Meter(name)
        return self.meters[name]

    def _log_losses(self, update_type, losses):
        # values stay on device; Meter.flush materializes them at
        # logging_iter so the step loop never blocks on a host sync.
        for name, value in losses.items():
            self._meter(f"{update_type}/{name}").write(value)

    def _flush_meters(self, step):
        for meter in self.meters.values():
            meter.flush(step)

    def _image_path(self, iteration):
        return os.path.join(cfg_get(self.cfg, "logdir", "."), "images",
                            f"{iteration:09d}.jpg")
