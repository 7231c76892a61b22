"""vid2vid trainer (ref: imaginaire/trainers/vid2vid.py:30-766).

Training is an interleaved per-frame rollout: for each frame t of the
sequence, one discriminator update then one generator update, feeding
the generator its own (detached) previous outputs
(ref: vid2vid.py:238-288). The sequence-length curriculum starts at a
single frame and doubles every ``num_epochs_temporal_step`` epochs
(ref: vid2vid.py:162-204).

TPU-first: each (prev-frame-count, active-temporal-scale) combination
is one jitted step program; jax.jit's structure cache handles the
variants (bounded: prev counts ≤ num_frames_G-1, scale activations ≤
num_scales). Temporal-discriminator inputs come from host-threaded
device ring buffers sliced with static strides (the reference's
get_skipped_frames bookkeeping, discriminators/fs_vid2vid.py:225-256) —
no dynamic shapes inside any step.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

from imaginaire_tpu import telemetry
from imaginaire_tpu.config import as_attrdict, cfg_get
from imaginaire_tpu.losses import (
    PerceptualLoss,
    dis_accuracy,
    feature_matching_loss,
    gan_loss,
)
from imaginaire_tpu.losses.flow import masked_l1_loss
from imaginaire_tpu.model_utils.fs_vid2vid import concat_frames, skip_stride_span
from imaginaire_tpu.optim import init_optimizer_state
from imaginaire_tpu.trainers.base import MUTABLE, BaseTrainer
from imaginaire_tpu.utils.misc import numeric_only, to_device
from imaginaire_tpu.utils.model_average import ema_init, ema_update


class Trainer(BaseTrainer):
    def __init__(self, cfg, *args, **kwargs):
        super().__init__(cfg, *args, **kwargs)
        self.num_frames_G = cfg_get(cfg.data, "num_frames_G", 3)
        self.num_frames_D = cfg_get(cfg.data, "num_frames_D", 3)
        self.has_fg = cfg_get(cfg.data, "has_foreground", False)
        self.sequence_length = 1
        self.sequence_length_max = cfg_get(
            cfg_get(cfg.data, "train", {}) or {}, "max_sequence_length", 16)
        if self.train_data_loader is not None:
            ds = getattr(self.train_data_loader, "dataset", None)
            if ds is not None and hasattr(ds, "sequence_length_max"):
                self.sequence_length_max = min(self.sequence_length_max,
                                               ds.sequence_length_max)
        # per-frame programs ride the compile ledger like the base step
        # programs; allow_shape_growth: the sequence-length curriculum
        # and ring-buffer warm-up legitimately re-specialize on new
        # shapes (same dtypes/shardings), which must not trip the
        # recompile tripwire
        from imaginaire_tpu.telemetry import xla_obs

        self._jit_vid_dis = xla_obs.compiled_program(
            "vid_dis_step", self._vid_dis_step_fn,
            donate_argnums=self._donate, allow_shape_growth=True)
        self._jit_vid_gen = xla_obs.compiled_program(
            "vid_gen_step", self._vid_gen_step_fn,
            donate_argnums=self._donate, allow_shape_growth=True)

    # ---------------------------------------------------------------- loss

    def _init_loss(self, cfg):
        """(ref: trainers/vid2vid.py:89-157)."""
        tcfg = cfg.trainer
        lw = tcfg.loss_weight
        self.gan_mode = cfg_get(tcfg, "gan_mode", "hinge")
        self.weights["GAN"] = lw.gan
        self.weights["FeatureMatching"] = lw.feature_matching
        self.perceptual = None
        if cfg_get(tcfg, "perceptual_loss", None) is not None:
            p = tcfg.perceptual_loss
            self.perceptual = PerceptualLoss(
                network=p.mode, layers=list(p.layers),
                weights=list(cfg_get(p, "weights", None) or []) or None,
                weights_path=cfg_get(p, "weights_path", None),
                allow_random_init=cfg_get(p, "allow_random_init", False))
            self.weights["Perceptual"] = lw.perceptual
        if cfg_get(lw, "L1", 0) > 0:
            self.weights["L1"] = lw.L1
        self.use_flow = cfg_get(cfg.gen, "flow", None) is not None
        self.flow_net_wrapper = None
        self.flow_cache = None
        if self.use_flow:
            self.weights["Flow"] = lw.flow
            # Full FlowLoss with a frozen FlowNet2 teacher when
            # cfg.flow_network is configured and weights resolve
            # (ref: trainers/vid2vid.py:147-152, third_party flow_net);
            # otherwise the fork's warp-consistency masked L1.
            fn_cfg = cfg_get(cfg, "flow_network", None)
            if fn_cfg is not None:
                from imaginaire_tpu.flow import FlowNet

                try:
                    self.flow_net_wrapper = FlowNet(
                        weights_path=cfg_get(fn_cfg, "weights_path", None),
                        allow_random_init=cfg_get(fn_cfg,
                                                  "allow_random_init", False))
                    self.flow_net_wrapper.init_params(jax.random.PRNGKey(0))
                    self.weights["Flow_L1"] = self.weights["Flow_Warp"] = \
                        self.weights["Flow_Mask"] = lw.flow
                except FileNotFoundError as e:
                    import logging

                    msg = (f"FlowNet2 teacher unavailable ({e}); using "
                           "warp-consistency flow loss.")
                    logging.getLogger(__name__).warning(msg)
                    # mirror into the run JSONL so a post-hoc reader can
                    # tell a teacherless run from a teacher-supervised one
                    telemetry.get().meta("flow_teacher_unavailable",
                                         reason=str(e), fallback="warp_"
                                         "consistency_masked_l1")
                    self.flow_net_wrapper = None
        if self.flow_net_wrapper is not None:
            # teacher amortization (flow/cache.py): run the frozen
            # teacher OFF the step program — in the prefetch producer
            # thread, with an optional on-disk canonical-resolution
            # cache — so the compiled D/G steps carry no FlowNet2
            # params. flow_cache.enabled: false keeps the reference's
            # in-graph teacher.
            from imaginaire_tpu.flow.cache import (
                TeacherFlowCache,
                flow_cache_settings,
                resolve_cache_dir,
            )

            settings = flow_cache_settings(cfg)
            if settings.enabled:
                self.flow_cache = TeacherFlowCache(
                    self.flow_net_wrapper, settings,
                    cache_dir=resolve_cache_dir(cfg))
        self.num_temporal_scales = cfg_get(
            cfg_get(cfg.dis, "temporal", {}) or {}, "num_scales", 0)
        for s in range(self.num_temporal_scales):
            self.weights[f"GAN_T{s}"] = cfg_get(lw, "temporal_gan", 0)
            self.weights[f"FeatureMatching_T{s}"] = lw.feature_matching
        # Per-region additional discriminators: each carries its own
        # loss_weight (ref: trainers/vid2vid.py:120-129, configs'
        # additional_discriminators blocks).
        add_cfg = cfg_get(cfg.dis, "additional_discriminators", None)
        add_cfg = as_attrdict(add_cfg) if add_cfg else {}
        self.add_dis_names = sorted(add_cfg.keys())
        for name in self.add_dis_names:
            self.weights[f"GAN_{name}"] = cfg_get(add_cfg[name],
                                                  "loss_weight", 1.0)
            self.weights[f"FeatureMatching_{name}"] = lw.feature_matching

    def init_loss_params(self, key):
        params = {}
        if self.perceptual is not None:
            params["perceptual"] = self.perceptual.init_params(key)
        if self.flow_net_wrapper is not None and self.flow_cache is None:
            # with the flow cache active the teacher runs off-step and
            # its 162M-param tree must NOT enter the step programs —
            # the gen executable shrinks and never re-ships the cascade
            params["flownet"] = self.flow_net_wrapper.params
        return params

    # ---------------------------------------------------------- data hooks

    def _start_of_iteration(self, data, current_iteration):
        """DensePose preprocessing for pose datasets
        (ref: trainers/vid2vid.py:206-233 pre_process), plus the
        off-step teacher: under the device-prefetch pipeline this hook
        runs in the producer thread, so the FlowNet2 forward overlaps
        the main step and its (flow, conf) outputs ride the prefetch
        queue as committed sharded arrays."""
        if self.flow_cache is not None and current_iteration >= 0:
            # eval/test sweeps (current_iteration == -1) never consume
            # flow supervision — don't pay the teacher for them
            data = self.flow_cache.attach(dict(data))
        elif isinstance(data, dict) and "_flow_cache" in data:
            # dataset-side payloads with no consumer (cache disabled at
            # the trainer after the dataset attached them) must not
            # reach the jit boundary
            data = dict(data)
            data.pop("_flow_cache")
        pose_cfg = cfg_get(self.cfg.data, "for_pose_dataset", None)
        if pose_cfg is not None and \
                "pose_maps-densepose" in (cfg_get(self.cfg.data,
                                                  "input_labels", []) or []):
            from imaginaire_tpu.model_utils.fs_vid2vid import (
                pre_process_densepose,
            )

            data = dict(data)
            data["label"] = pre_process_densepose(
                pose_cfg, np.asarray(data["label"]),
                is_infer=current_iteration < 0)
            if "ref_labels" in data:
                # few-shot reference labels share the scale; never drop
                # parts from them (ref preprocesses few_shot_label with
                # is_infer=True)
                data["ref_labels"] = pre_process_densepose(
                    pose_cfg, np.asarray(data["ref_labels"]), is_infer=True)
        return data

    # --------------------------------------------------------------- state

    def _frame0(self, data):
        label = data["label"]
        images = data["images"]
        if label.ndim == 5:
            label = label[:, 0]
        if images.ndim == 5:
            images = images[:, 0]
        return {"label": label, "image": images}

    def _init_state(self, key, data):
        """All generator submodules (temporal path included) and all
        temporal discriminator scales materialize here — the curriculum
        only flips static flags later."""
        data = to_device(numeric_only(dict(data)))
        data_t = self._frame0(data)
        k_g, k_d, k_loss, k_noise, k_rg, k_rd = jax.random.split(key, 6)
        # lint: allow(bare-jit) -- one-shot flax init at t=0
        vars_G = dict(jax.jit(
            lambda rngs, d: self.net_G.init(rngs, d, training=True,
                                            init_all=True))(
            {"params": k_g, "noise": k_noise}, data_t))
        state: Dict[str, Any] = {
            "vars_G": vars_G,
            "opt_G": init_optimizer_state(self.tx_G, vars_G["params"],
                                          self.partition),
            "step": jnp.zeros((), jnp.int32),
            "rng_G": k_rg,
            "rng_D": k_rd,
            "loss_params": self.init_loss_params(k_loss),
        }
        b, h, w, _ = data_t["label"].shape
        c_img = data_t["image"].shape[-1]
        fake_out = {"fake_images": jnp.zeros_like(data_t["image"]),
                    "fake_raw_images": jnp.zeros_like(data_t["image"])}
        tD = self.num_frames_D
        stacks = {f"s{s}": (jnp.zeros((b, tD - 1, h, w, c_img)),
                            jnp.zeros((b, tD - 1, h, w, c_img)))
                  for s in range(self.num_temporal_scales)}
        # lint: allow(bare-jit) -- one-shot flax init at t=0
        vars_D = dict(jax.jit(
            lambda rngs, d, f, st: self.net_D.init(
                rngs, d, f, past_stacks=st, training=True))(
            {"params": k_d, "dropout": k_d}, data_t, fake_out,
            self._stacks_list(stacks)))
        state["vars_D"] = vars_D
        state["opt_D"] = init_optimizer_state(self.tx_D, vars_D["params"],
                                              self.partition)
        state["step_D"] = jnp.zeros((), jnp.int32)
        if self.model_average:
            state["ema_G"] = ema_init(
                vars_G["params"], vars_G.get("spectral"),
                remove_sn=self.model_average_remove_sn)
            state["num_ema_updates"] = jnp.zeros((), jnp.int32)
        # 2-D partition plan (parallel/partition.py): commit the state
        # under its shardings before the first per-frame program compiles
        self.state = self._place_state(state)
        return self.state

    def _stacks_list(self, stacks):
        """dict {'s0': (real, fake)} -> list indexed by scale, None when
        absent (the discriminator's past_stacks contract)."""
        return [stacks.get(f"s{s}") for s in range(self.num_temporal_scales)]

    # ------------------------------------------------------------ forwards

    def _apply_G(self, vars_G, data_t, rng, training):
        return self.net_G.apply(vars_G, data_t, training=training,
                                rngs={"noise": rng}, mutable=list(MUTABLE))

    def _apply_D(self, vars_D, data_t, out, stacks, training, mutable=False):
        kwargs = dict(past_stacks=self._stacks_list(stacks),
                      training=training)
        if mutable:
            return self.net_D.apply(vars_D, data_t, out,
                                    mutable=list(MUTABLE), **kwargs)
        return self.net_D.apply(vars_D, data_t, out, **kwargs)

    def _gan_fm_losses(self, d_out_part, dis_update, sample_weight=None):
        """(ref: trainers/vid2vid.py:609-635). ``sample_weight`` carries
        the region-validity mask of additional discriminators."""
        fake = d_out_part["pred_fake"]
        real = d_out_part["pred_real"]
        if dis_update:
            gan = 0.5 * (
                gan_loss(fake["outputs"], False, self.gan_mode, True,
                         sample_weight=sample_weight)
                + gan_loss(real["outputs"], True, self.gan_mode, True,
                           sample_weight=sample_weight))
            return gan, None
        gan = gan_loss(fake["outputs"], True, self.gan_mode, False,
                       sample_weight=sample_weight)
        fm = feature_matching_loss(fake["features"], real["features"],
                                   sample_weight=sample_weight)
        return gan, fm

    def _region_d_losses(self, d_out, losses, dis_update):
        """Collect per-region (face/hand) GAN/FM losses; the validity
        mask of fixed-shape region crops weights out absent regions
        (ref: trainers/vid2vid.py additional-D loss collection)."""
        for name in self.add_dis_names:
            if name in d_out:
                gan_r, fm_r = self._gan_fm_losses(
                    d_out[name], dis_update=dis_update,
                    sample_weight=d_out[name].get("valid"))
                losses[f"GAN_{name}"] = gan_r
                if not dis_update:
                    losses[f"FeatureMatching_{name}"] = fm_r
        return losses

    def _split_data_t(self, data):
        data = dict(data)
        stacks = data.pop("past_stacks", {})
        return data, stacks

    def gen_forward(self, vars_G, vars_D, loss_params, data, rng,
                    training=True):
        """Per-frame G losses (ref: trainers/vid2vid.py:469-553)."""
        data_t, stacks = self._split_data_t(data)
        out, new_mut = self._apply_G(vars_G, data_t, rng, training)
        d_out = self._apply_D(vars_D, data_t, out, stacks, training)

        losses = {}
        losses["GAN"], losses["FeatureMatching"] = self._gan_fm_losses(
            d_out["indv"], dis_update=False)
        if self.perceptual is not None:
            losses["Perceptual"] = self.perceptual(
                loss_params["perceptual"], out["fake_images"],
                data_t["image"])
        if "L1" in self.weights:
            losses["L1"] = jnp.mean(jnp.abs(out["fake_images"]
                                            - data_t["image"]))
        if "raw" in d_out:
            raw_gan, raw_fm = self._gan_fm_losses(d_out["raw"],
                                                  dis_update=False)
            losses["GAN"] = losses["GAN"] + raw_gan
            losses["FeatureMatching"] = losses["FeatureMatching"] + raw_fm
            if self.perceptual is not None:
                from imaginaire_tpu.model_utils.fs_vid2vid import get_fg_mask

                fg = get_fg_mask(data_t["label"], self.has_fg)
                losses["Perceptual"] = losses["Perceptual"] + self.perceptual(
                    loss_params["perceptual"],
                    out["fake_raw_images"] * fg, data_t["image"] * fg)
        if self.use_flow and out.get("warped_images") is not None:
            cached_gt = data_t.get("flow_gt") is not None
            if self.flow_net_wrapper is not None and \
                    (cached_gt or
                     data_t.get("real_prev_image") is not None):
                from imaginaire_tpu.losses.flow import FlowLoss

                if cached_gt:
                    # amortized teacher: (flow, conf) arrived with the
                    # batch (flow/cache.py) — the step program contains
                    # no FlowNet2 cascade
                    flow_loss = FlowLoss(None, has_fg=self.has_fg)
                    loss_data = {"image": data_t["image"],
                                 "flow_gt": data_t["flow_gt"],
                                 "conf_gt": data_t["conf_gt"]}
                else:
                    fn_params = loss_params["flownet"]
                    flow_loss = FlowLoss(
                        lambda a, b: self.flow_net_wrapper._flow_fn(
                            fn_params, a, b),
                        has_fg=self.has_fg)
                    loss_data = {"image": data_t["image"],
                                 "real_prev_image":
                                     data_t["real_prev_image"]}
                l1, warp, mask_l = flow_loss(loss_data, out)
                losses["Flow_L1"] = l1
                losses["Flow_Warp"] = warp
                losses["Flow_Mask"] = mask_l
            else:
                # fork semantics: warp-consistency masked L1; stop-grad the
                # occlusion mask (it weights its own loss — a learnable
                # weight has a degenerate mask->0 optimum)
                losses["Flow"] = masked_l1_loss(
                    out["fake_images"], out["warped_images"],
                    jax.lax.stop_gradient(out["fake_occlusion_masks"]))
        for s in range(self.num_temporal_scales):
            if f"temporal_{s}" in d_out:
                gan_t, fm_t = self._gan_fm_losses(d_out[f"temporal_{s}"],
                                                  dis_update=False)
                losses[f"GAN_T{s}"] = gan_t
                losses[f"FeatureMatching_T{s}"] = fm_t
        losses = self._region_d_losses(d_out, losses, dis_update=False)
        return losses, new_mut, out

    def dis_forward(self, vars_G, vars_D, loss_params, data, rng,
                    training=True):
        """Per-frame D losses (ref: trainers/vid2vid.py:555-599)."""
        data_t, stacks = self._split_data_t(data)
        out, _ = self._apply_G(vars_G, data_t, rng, training)
        out = jax.lax.stop_gradient(
            {k: v for k, v in out.items() if v is not None})
        d_out, new_mut_D = self._apply_D(vars_D, data_t, out, stacks,
                                         training, mutable=True)
        losses = {}
        losses["GAN"], _ = self._gan_fm_losses(d_out["indv"], dis_update=True)
        # GAN-balance diagnostics: per-frame D accuracy on the image D
        # (unweighted keys never enter the total)
        losses["D_real_acc"], losses["D_fake_acc"] = dis_accuracy(
            d_out["indv"]["pred_real"]["outputs"],
            d_out["indv"]["pred_fake"]["outputs"], self.gan_mode)
        if "raw" in d_out:
            raw_gan, _ = self._gan_fm_losses(d_out["raw"], dis_update=True)
            losses["GAN"] = losses["GAN"] + raw_gan
        for s in range(self.num_temporal_scales):
            if f"temporal_{s}" in d_out:
                gan_t, _ = self._gan_fm_losses(d_out[f"temporal_{s}"],
                                               dis_update=True)
                losses[f"GAN_T{s}"] = gan_t
        losses = self._region_d_losses(d_out, losses, dis_update=True)
        return losses, new_mut_D

    # --------------------------------------------------------- jitted steps

    def _vid_gen_step_fn(self, state, data):
        step0 = state["step"]
        rng = jax.random.fold_in(state["rng_G"], step0)

        def loss_fn(params_G):
            vars_G = dict(state["vars_G"],
                          params=self._to_compute_dtype(params_G))
            losses, new_mut, out = self.gen_forward(
                vars_G, self._cast_net_vars(state["vars_D"]),
                state["loss_params"], self._to_compute_dtype(data), rng)
            losses = {k: v.astype(jnp.float32) for k, v in losses.items()}
            total = self._total(losses)
            return total, (dict(losses, total=total), new_mut,
                           out["fake_images"])

        (_, (losses, new_mut, fake)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["vars_G"]["params"])
        if self.clip_grad_norm_G:
            grads, _ = optax.clip_by_global_norm(
                self.clip_grad_norm_G).update(grads, optax.EmptyState())
        updates, new_opt = self.tx_G.update(
            grads, state["opt_G"], state["vars_G"]["params"])
        new_params = optax.apply_updates(state["vars_G"]["params"], updates)
        new_params, new_opt, new_mut, ok, grad_norm = self._audit_guard(
            losses, grads, state, "vars_G", "opt_G",
            new_params, new_opt, new_mut)
        new_vars_G = dict(state["vars_G"], params=new_params, **new_mut)
        state = dict(state, vars_G=new_vars_G, opt_G=new_opt,
                     step=step0 + 1)
        if self.model_average:
            n = state["num_ema_updates"] + 1
            state["ema_G"] = ema_update(
                state["ema_G"], new_params, n,
                beta=self.model_average_beta,
                start_iteration=self.model_average_start,
                spectral=new_vars_G.get("spectral"),
                remove_sn=self.model_average_remove_sn)
            state["num_ema_updates"] = n
        health = self._audit_health(
            ok, grad_norm, step0, grads, new_params, updates,
            spectral=new_vars_G.get("spectral"),
            ema=state.get("ema_G") if self.model_average else None)
        return (self._constrain_state(state), losses,
                jax.lax.stop_gradient(fake), health)

    def _vid_dis_step_fn(self, state, data):
        step0 = state["step_D"]
        rng = jax.random.fold_in(state["rng_D"], step0)

        def loss_fn(params_D):
            vars_D = dict(state["vars_D"],
                          params=self._to_compute_dtype(params_D))
            losses, new_mut = self.dis_forward(
                self._cast_net_vars(state["vars_G"]), vars_D,
                state["loss_params"], self._to_compute_dtype(data), rng)
            losses = {k: v.astype(jnp.float32) for k, v in losses.items()}
            total = self._total(losses)
            return total, (dict(losses, total=total), new_mut)

        (_, (losses, new_mut)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["vars_D"]["params"])
        if self.clip_grad_norm_D:
            grads, _ = optax.clip_by_global_norm(
                self.clip_grad_norm_D).update(grads, optax.EmptyState())
        updates, new_opt = self.tx_D.update(
            grads, state["opt_D"], state["vars_D"]["params"])
        new_params = optax.apply_updates(state["vars_D"]["params"], updates)
        new_params, new_opt, new_mut, ok, grad_norm = self._audit_guard(
            losses, grads, state, "vars_D", "opt_D",
            new_params, new_opt, new_mut)
        new_vars_D = dict(state["vars_D"], params=new_params, **new_mut)
        state = dict(state, vars_D=new_vars_D,
                     opt_D=new_opt, step_D=step0 + 1)
        health = self._audit_health(
            ok, grad_norm, step0, grads, new_params, updates,
            spectral=new_vars_D.get("spectral"))
        return self._constrain_state(state), losses, health

    # ------------------------------------------------------------- rollout

    def _get_data_t(self, data, t, prev_labels, prev_images):
        """(ref: trainers/vid2vid.py:637-668)."""
        label = data["label"][:, t] if data["label"].ndim == 5 \
            else data["label"]
        image = data["images"][:, t] if data["images"].ndim == 5 \
            else data["images"]
        data_t = {"label": label, "image": image}
        if prev_images is not None:
            data_t["prev_labels"] = prev_labels
            data_t["prev_images"] = prev_images
        if t > 0 and data["images"].ndim == 5:
            # real previous frame for the FlowNet2 teacher's GT flow
            data_t["real_prev_image"] = data["images"][:, t - 1]
            if data.get("flow_gt") is not None:
                # amortized teacher output (flow/cache.py):
                # flow_gt[:, t-1] supervises frame t against frame t-1
                data_t["flow_gt"] = data["flow_gt"][:, t - 1]
                data_t["conf_gt"] = data["conf_gt"][:, t - 1]
        return data_t

    def _past_stacks(self, past_real, past_fake):
        """Per-scale strided past stacks from the ring buffers
        (ref: discriminators/fs_vid2vid.py:225-256); the current frame is
        appended inside the discriminator so G gradients reach it."""
        stacks = {}
        if past_real is None:
            return stacks
        tD = self.num_frames_D
        L = past_real.shape[1]
        for s in range(self.num_temporal_scales):
            # buffer here EXCLUDES the current frame (the discriminator
            # appends it so G gradients reach it), hence >= t_span where
            # get_skipped_frames (current included) uses > t_span
            t_step, t_span = skip_stride_span(tD, s)
            if L >= t_span:
                stacks[f"s{s}"] = (past_real[:, -t_span::t_step],
                                   past_fake[:, -t_span::t_step])
        return stacks

    def gen_update(self, data):
        """Interleaved per-frame D/G rollout (ref: vid2vid.py:238-288)."""
        # the gen_step span covers the whole rollout (per-frame dis_step
        # spans nest inside it — D updates happen here, dis_update is a
        # no-op for this family)
        with telemetry.span("gen_step", step=self.current_iteration):
            return self._gen_update_rollout(data)

    def _gen_update_rollout(self, data):
        if self.flow_cache is not None and isinstance(data, dict) \
                and "flow_gt" not in data \
                and getattr(data.get("images"), "ndim", 0) == 5:
            # safety net for callers that skip start_of_iteration
            # (direct gen_update in tests): the amortized teacher must
            # still supply the supervision the cached step program
            # expects
            data = self.flow_cache.attach(dict(data))
        data = numeric_only(data)
        seq_len = (data["images"].shape[1] if data["images"].ndim == 5
                   else 1)
        tD = self.num_frames_D
        max_prev = (tD ** max(self.num_temporal_scales - 1, 0)) * (tD - 1)
        prev_labels = prev_images = None
        past_real = past_fake = None
        t0 = time.time() if self.speed_benchmark else None
        d_hist, g_hist = [], []
        for t in range(seq_len):
            data_t = self._get_data_t(data, t, prev_labels, prev_images)
            fake = self._frame_override(data_t)
            if fake is None:
                data_t["past_stacks"] = self._past_stacks(past_real,
                                                          past_fake)
                # keys starting with '_' carry host-side objects (e.g.
                # wc-vid2vid point clouds) and must not cross the jit
                # boundary
                data_jit = {k: v for k, v in data_t.items()
                            if not k.startswith("_")}
                with telemetry.span("dis_step",
                                    step=self.current_iteration):
                    self.state, d_losses, d_health = \
                        self._jit_vid_dis(self.state, data_jit)
                # per-frame health hooks: each frame's D and G update
                # reports its own summary/finite flag (the monitor's
                # cadence runs on the per-frame step counters)
                self.diag.observe(self, "D", d_losses, d_health,
                                  data_jit, self.current_iteration)
                self.state, g_losses, fake, g_health = \
                    self._jit_vid_gen(self.state, data_jit)
                self.diag.observe(self, "G", g_losses, g_health,
                                  data_jit, self.current_iteration)
                d_hist.append(d_losses)
                g_hist.append(g_losses)
                if self.num_temporal_scales > 0:
                    past_real = concat_frames(past_real, data_t["image"],
                                              max_prev)
                    past_fake = concat_frames(past_fake, fake, max_prev)
            self._after_gen_frame(data_t, fake)
            prev_labels = concat_frames(prev_labels, data_t["label"],
                                        self.num_frames_G - 1)
            prev_images = concat_frames(prev_images, fake,
                                        self.num_frames_G - 1)
        if self.speed_benchmark:
            # lint: allow(host-sync) -- speed_benchmark timing fence
            jax.block_until_ready(self.state["vars_G"]["params"])
            self._meter("time/gen_step").write(time.time() - t0)

        def mean_losses(hist):
            # a key averages over the frames that report it (the
            # temporal scales' losses appear once their stacks fill)
            out = {}
            for k in set().union(*(h.keys() for h in hist)):
                values = [h[k] for h in hist if k in h]
                out[k] = sum(values) / len(values)
            return out

        d_losses = mean_losses(d_hist)
        g_losses = mean_losses(g_hist)
        self._log_losses("dis_update", d_losses)
        self._log_losses("gen_update", g_losses)
        return g_losses

    def _end_of_iteration(self, data, current_epoch, current_iteration):
        """Flush the amortized-teacher stats into the meters (the
        DevicePrefetcher drain_stats pattern): flow_cache/hit_rate and
        flow_cache/compute_ms land beside the loss meters on
        logging_iter, never a device sync."""
        if self.flow_cache is not None:
            self.write_data_meters(self.flow_cache.drain_stats())

    def _after_gen_frame(self, data_t, fake):
        """Hook after each frame's G step (wc-vid2vid colors its point
        cloud here). Default: no-op."""
        pass

    def _frame_override(self, data_t):
        """Hook: return a replacement fake frame for ``data_t``, or None
        to run the normal D/G steps. Override frames skip both updates
        and the temporal-D past stacks but still feed the prev-frame
        history (ref: trainers/vid2vid.py:264-284, the
        ``fake_images_source == 'pretrained'`` gating; wc-vid2vid's
        frozen single-image takeover lives here). Default: None."""
        return None

    def _start_of_test_sequence(self, data):
        """Hook before generating a test sequence (wc-vid2vid resets its
        renderer here, ref: trainers/wc_vid2vid.py:70-87). No-op."""
        pass

    def recalculate_model_average_batch_norm_statistics(self,
                                                        data_loader=None):
        """No-op for the video family: the base implementation feeds
        whole loader batches into _apply_G, which here takes per-frame
        data_t — and the reference likewise never recalibrates EMA BN
        stats for its video trainers (only spade/pix2pixHD do,
        ref: trainers/spade.py:196)."""
        return

    def reset(self):
        """Reset per-sequence rollout state before generating a new test
        sequence (ref: trainers/vid2vid.py:298-312). The sequence
        counter keeps advancing so each sequence draws distinct noise."""
        self._test_prev_labels = None
        self._test_prev_images = None
        self._test_t = 0
        self._test_seq = getattr(self, "_test_seq", -1) + 1

    def _generate_frame(self, data, t):
        """Generate frame ``t`` of ``data`` carrying the stored rollout
        history; advances the history buffers."""
        data_t = self._get_data_t(data, t,
                                  getattr(self, "_test_prev_labels", None),
                                  getattr(self, "_test_prev_images", None))
        fake = self._frame_override(data_t)
        if fake is None:
            out, _ = self._apply_G(
                self.inference_params(),
                {k: v for k, v in data_t.items() if not k.startswith("_")},
                jax.random.PRNGKey(getattr(self, "_test_seq", 0) * 100003
                                   + getattr(self, "_test_t", 0)),
                training=False)
            fake = out["fake_images"]
        self._after_gen_frame(data_t, fake)
        self._test_prev_labels = concat_frames(
            getattr(self, "_test_prev_labels", None), data_t["label"],
            self.num_frames_G - 1)
        self._test_prev_images = concat_frames(
            getattr(self, "_test_prev_images", None), fake,
            self.num_frames_G - 1)
        self._test_t = getattr(self, "_test_t", 0) + 1
        return fake

    def test_single(self, data):
        """Generate the next frame of the current test sequence — the
        per-frame entry the video FID/eval harness drives
        (ref: trainers/vid2vid.py:419-467, evaluation/common.py:79-158).
        Call reset() at each sequence start."""
        data = to_device(self._start_of_iteration(
            numeric_only(dict(data)), -1))
        return {"fake_images": self._generate_frame(data, 0)}

    def test(self, data_loader, output_dir, inference_args=None):
        """Frame-by-frame video generation (ref: trainers/vid2vid.py:
        330-417). With a sequence-pinning dataset, every inference
        sequence is rolled out frame by frame; direct batch iterables
        (tests, ad-hoc data) roll out each batch's time axis."""
        inference_args = dict(inference_args or {})
        dataset = getattr(data_loader, "dataset", None)
        if dataset is not None \
                and getattr(dataset, "is_inference", False) \
                and hasattr(dataset, "set_inference_sequence_idx"):
            return self._test_sequences(dataset, output_dir,
                                        inference_args)
        return self._test_batches(data_loader, output_dir)

    def _inference_sequence_indices(self, dataset, inference_args):
        # sequences shard round-robin per process, mirroring the video
        # FID harness (evaluation/common.py), so multi-host inference
        # neither duplicates rollouts nor races on output files
        return list(range(dataset.num_inference_sequences()))[
            jax.process_index()::jax.process_count()]

    def _frame_loader(self, dataset):
        """Batch-1 unsharded loader over a pinned sequence's frames —
        the strictly-sequential contract test_single/_generate_frame
        require (frames of one sequence must never rank-shard)."""
        from imaginaire_tpu.data.loader import DataLoader

        return DataLoader(dataset, batch_size=1, shuffle=False,
                          drop_last=False, shard_by_process=False)

    def _pin_inference_sequence(self, dataset, seq_idx, inference_args):
        dataset.set_inference_sequence_idx(seq_idx)

    def _save_test_frame(self, output_dir, key, t, fake):
        import os

        from imaginaire_tpu.utils.visualization import (
            save_image_grid,
            tensor2im,
        )

        path = os.path.join(output_dir, str(key), f"{t:04d}.jpg")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        save_image_grid(
            # lint: allow(host-sync) -- offline inference image dump
            [tensor2im(np.asarray(jax.device_get(fake))[0])], path)

    def _test_sequences(self, dataset, output_dir, inference_args):
        """(ref: trainers/vid2vid.py:339-417): pin each sequence, build
        a batch-1 unsharded frame loader, roll out with carried
        generated history."""
        import os

        os.makedirs(output_dir, exist_ok=True)
        frame_loader = self._frame_loader(dataset)
        for seq_idx in self._inference_sequence_indices(dataset,
                                                        inference_args):
            self._pin_inference_sequence(dataset, seq_idx, inference_args)
            self.reset()
            started = False
            for t, data in enumerate(frame_loader):
                data = self.start_of_iteration(data, current_iteration=-1)
                data = numeric_only(data)
                if not started:
                    self._start_of_test_sequence(data)
                    started = True
                fake = self._generate_frame(data, 0)
                self._save_test_frame(output_dir, f"seq{seq_idx:04d}", t,
                                      fake)

    def _test_batches(self, data_loader, output_dir):
        import os

        os.makedirs(output_dir, exist_ok=True)
        for it, data in enumerate(data_loader):
            data = self.start_of_iteration(data, current_iteration=-1)
            key = data.get("key", f"{it:06d}")
            if isinstance(key, (list, tuple)):
                key = key[0]
            if not isinstance(key, (str, bytes)):
                key = f"{it:06d}"
            data = numeric_only(data)
            self.reset()
            self._start_of_test_sequence(data)
            seq_len = (data["images"].shape[1]
                       if data["images"].ndim == 5 else 1)
            for t in range(seq_len):
                fake = self._generate_frame(data, t)
                self._save_test_frame(output_dir, str(key), t, fake)

    def _compute_fid(self):
        """Video FID over generated sequences
        (ref: trainers/vid2vid.py:697-757): shard the validation
        sequences, reset + roll out per sequence via test_single, gather
        Inception activations."""
        if self.val_data_loader is None:
            return None
        dataset = getattr(self.val_data_loader, "dataset", None)
        if dataset is None or not hasattr(dataset,
                                          "set_inference_sequence_idx"):
            print("Video FID skipped: val dataset has no sequence "
                  "pinning (set_inference_sequence_idx).")
            return None
        import os

        from imaginaire_tpu.evaluation import compute_fid

        try:
            extractor = self._fid_extractor()
        except FileNotFoundError as e:
            print(f"FID skipped: {e}")
            return None
        logdir = cfg_get(self.cfg, "logdir", ".")
        data_name = cfg_get(cfg_get(self.cfg, "data", {}), "name", "data")
        fid_path = os.path.join(logdir,
                                f"real_stats_video_{data_name}.npz")
        sample_size = cfg_get(self.cfg.trainer, "num_videos_to_test", 64)
        return float(compute_fid(
            fid_path, self._frame_loader(dataset), extractor, None,
            trainer=self, is_video=True, sample_size=sample_size))

    def _extra_metric_activations(self, extractor):
        """Video-family activations for KID/PRDC (base template at
        trainers/base.py::compute_extra_metrics): the same pinned-sequence
        rollout as video FID (``get_video_activations``); real-set
        activations are cached across a checkpoint sweep
        (ref: evaluation/kid.py:29, prdc.py)."""
        dataset = getattr(self.val_data_loader, "dataset", None)
        if dataset is None or not hasattr(dataset,
                                          "set_inference_sequence_idx"):
            print("Video KID/PRDC skipped: val dataset has no sequence "
                  "pinning (set_inference_sequence_idx).")
            return None

        from imaginaire_tpu.evaluation.common import get_video_activations

        sample_size = cfg_get(self.cfg.trainer, "num_videos_to_test", 64)
        frame_loader = self._frame_loader(dataset)
        act_fake = get_video_activations(frame_loader, "images",
                                         "fake_images", self, extractor,
                                         sample_size=sample_size)
        data_name = cfg_get(cfg_get(self.cfg, "data", {}), "name", "data")
        act_real = self._cached_real_activations(
            f"real_acts_video_{data_name}.npz",
            lambda: get_video_activations(frame_loader, "images",
                                          "fake_images", None, extractor,
                                          sample_size=sample_size))
        return act_real, act_fake

    def dis_update(self, data):
        """D updates happen inside gen_update's rollout
        (ref: trainers/vid2vid.py:290-296)."""
        return None

    def _register_step_flops(self, data):
        """No-op: the video families step through per-frame programs,
        not the base two-program step — lowering those unused programs
        here would trigger pointless compiles. No ``perf/mfu`` for this
        family."""
        return None

    # ----------------------------------------------------------- curriculum

    def _start_of_epoch(self, current_epoch):
        """Sequence-length curriculum (ref: trainers/vid2vid.py:162-204)."""
        cfg = self.cfg
        dataset = getattr(self.train_data_loader, "dataset", None)
        single_frame_epoch = cfg_get(cfg, "single_frame_epoch", 0)
        if current_epoch < single_frame_epoch:
            if dataset is not None:
                dataset.set_sequence_length(1)
            self.sequence_length = 1
            return
        if current_epoch == single_frame_epoch:
            self.init_temporal_network()
        temp_epoch = current_epoch - single_frame_epoch
        if temp_epoch > 0:
            initial = cfg_get(cfg_get(cfg.data, "train", {}) or {},
                              "initial_sequence_length", 4)
            step = cfg_get(cfg, "num_epochs_temporal_step", 1)
            seq = min(initial * (2 ** (temp_epoch // step)),
                      self.sequence_length_max)
            if seq > self.sequence_length:
                self.sequence_length = seq
                if dataset is not None:
                    dataset.set_sequence_length(seq)
                print(f"------- Updating sequence length to {seq} -------")

    def init_temporal_network(self):
        """(ref: trainers/vid2vid.py:194-204). Params already exist (built
        at init); only the data curriculum changes."""
        self.sequence_length = cfg_get(
            cfg_get(self.cfg.data, "train", {}) or {},
            "initial_sequence_length", 4)
        self.sequence_length = min(self.sequence_length,
                                   self.sequence_length_max)
        dataset = getattr(self.train_data_loader, "dataset", None)
        if dataset is not None:
            dataset.set_sequence_length(self.sequence_length)
        print(f"------ Now start training {self.sequence_length} frames "
              "-------")

    # -------------------------------------------------------- visualization

    def _get_visualizations(self, data):
        """Rollout the sequence with the inference params
        (ref: trainers/vid2vid.py:672-716)."""
        data = to_device(numeric_only(dict(data)))
        variables = self.inference_params()
        seq_len = (data["images"].shape[1] if data["images"].ndim == 5
                   else 1)
        prev_labels = prev_images = None
        fakes = []
        for t in range(seq_len):
            data_t = self._get_data_t(data, t, prev_labels, prev_images)
            fake = self._frame_override(data_t)
            if fake is None:
                out, _ = self._apply_G(variables, data_t,
                                       jax.random.PRNGKey(0),
                                       training=False)
                fake = out["fake_images"]
            fakes.append(fake)
            prev_labels = concat_frames(prev_labels, data_t["label"],
                                        self.num_frames_G - 1)
            prev_images = concat_frames(prev_images, fake,
                                        self.num_frames_G - 1)
        label = data["label"][:, -1] if data["label"].ndim == 5 \
            else data["label"]
        image = data["images"][:, -1] if data["images"].ndim == 5 \
            else data["images"]
        return [image, label[..., :3], fakes[-1]]
