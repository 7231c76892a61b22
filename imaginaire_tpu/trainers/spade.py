"""SPADE trainer (ref: imaginaire/trainers/spade.py).

Losses: GAN(hinge) + Perceptual(VGG19 5-layer pyramid) + FeatureMatching +
GaussianKL (ref: spade.py:56-81). Video batches fold previous frames into
the label channels (ref: spade.py:97-126); input H/W are rounded to the
generator's base multiple (ref: spade.py:297-312).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from imaginaire_tpu.config import cfg_get
from imaginaire_tpu.losses import (
    PerceptualLoss,
    dis_accuracy,
    feature_matching_loss,
    gan_loss,
    gaussian_kl_loss,
)
from imaginaire_tpu.trainers.base import MUTABLE, BaseTrainer
from imaginaire_tpu.utils.misc import to_device


class Trainer(BaseTrainer):
    def __init__(self, cfg, *args, **kwargs):
        super().__init__(cfg, *args, **kwargs)
        self.video_mode = str(cfg_get(cfg.data, "type", "")).endswith("paired_videos")
        try:
            from imaginaire_tpu.utils.data import get_crop_or_resize_h_w

            # same crop-else-resize sizing the generator uses — trainer
            # input rounding and the generator ladder must agree on base
            crop_h, crop_w = get_crop_or_resize_h_w(
                cfg.data.train.augmentations)
            self.base = {256: 16, 512: 32, 1024: 64}.get(min(crop_h, crop_w), 32)
        except (AttributeError, KeyError, ValueError):
            self.base = 32  # size-less config: tests feed 256-class inputs

    def _init_loss(self, cfg):
        """(ref: trainers/spade.py:56-81)."""
        tcfg = cfg.trainer
        self.gan_mode = cfg_get(tcfg, "gan_mode", "hinge")
        self.weights["GAN"] = tcfg.loss_weight.gan
        self.weights["FeatureMatching"] = tcfg.loss_weight.feature_matching
        if cfg_get(tcfg.loss_weight, "kl", None) is not None:
            self.weights["GaussianKL"] = tcfg.loss_weight.kl
        self.perceptual = None
        if cfg_get(tcfg, "perceptual_loss", None) is not None:
            p = tcfg.perceptual_loss
            self.perceptual = PerceptualLoss(
                network=p.mode, layers=list(p.layers),
                weights=list(cfg_get(p, "weights", None) or []) or None,
                weights_path=cfg_get(p, "weights_path", None),
                allow_random_init=cfg_get(p, "allow_random_init", False))
            self.weights["Perceptual"] = tcfg.loss_weight.perceptual

    def init_loss_params(self, key):
        if self.perceptual is None:
            return {}
        return {"perceptual": self.perceptual.init_params(key)}

    # ------------------------------------------------------------ forwards

    def _apply_G(self, vars_G, data, rng, training, random_style=False):
        out, new_mut = self.net_G.apply(
            vars_G, data, training=training, random_style=random_style,
            rngs={"noise": rng}, mutable=list(MUTABLE))
        return out, new_mut

    def _apply_D(self, vars_D, data, net_G_output, training, mutable=False):
        if mutable:
            return self.net_D.apply(vars_D, data, net_G_output,
                                    training=training, mutable=list(MUTABLE))
        return self.net_D.apply(vars_D, data, net_G_output, training=training)

    def gen_forward(self, vars_G, vars_D, loss_params, data, rng, training=True):
        """(ref: trainers/spade.py:128-163)."""
        with jax.named_scope("gan/G"):
            net_G_output, new_mut = self._apply_G(vars_G, data, rng, training)
        with jax.named_scope("gan/D"):
            net_D_output = self._apply_D(vars_D, data, net_G_output, training)

        losses = {}
        with jax.named_scope("gan/loss/adversarial"):
            output_fake = self._get_outputs(net_D_output, real=False)
            losses["GAN"] = gan_loss(output_fake, True, self.gan_mode,
                                     dis_update=False)
            losses["FeatureMatching"] = feature_matching_loss(
                net_D_output["fake_features"], net_D_output["real_features"])
            if net_G_output.get("mu") is not None:
                losses["GaussianKL"] = gaussian_kl_loss(
                    net_G_output["mu"], net_G_output["logvar"])
            else:
                losses["GaussianKL"] = jnp.zeros(())
        if self.perceptual is not None:
            with jax.named_scope("gan/loss/perceptual"):
                losses["Perceptual"] = self.perceptual(
                    loss_params["perceptual"], net_G_output["fake_images"],
                    data["images"])
        return losses, new_mut

    def dis_forward(self, vars_G, vars_D, loss_params, data, rng, training=True):
        """(ref: trainers/spade.py:165-187)."""
        with jax.named_scope("gan/G"):
            net_G_output, _ = self._apply_G(vars_G, data, rng, training)
            net_G_output = jax.lax.stop_gradient(
                {"fake_images": net_G_output["fake_images"]})
        # D runs with mutable spectral/batch_stats so the power-iteration
        # vector u advances every dis step (torch spectral_norm updates
        # weight_u on every training forward, ref: layers/weight_norm.py).
        with jax.named_scope("gan/D"):
            net_D_output, new_mut_D = self._apply_D(
                vars_D, data, net_G_output, training, mutable=True)

        with jax.named_scope("gan/loss/adversarial"):
            fake_loss = gan_loss(self._get_outputs(net_D_output, real=False),
                                 False, self.gan_mode, dis_update=True)
            true_loss = gan_loss(self._get_outputs(net_D_output, real=True),
                                 True, self.gan_mode, dis_update=True)
            losses = {"GAN/fake": fake_loss, "GAN/true": true_loss,
                      "GAN": fake_loss + true_loss}
            # GAN-balance diagnostics: D real/fake accuracy rides the loss
            # dict (unweighted keys never enter the total — _total only
            # sums registered weights) so it reaches the meters and the
            # health monitor without an extra forward
            losses["D_real_acc"], losses["D_fake_acc"] = dis_accuracy(
                net_D_output["real_outputs"], net_D_output["fake_outputs"],
                self.gan_mode)
        return losses, new_mut_D

    # ---------------------------------------------------------- data hooks

    def _start_of_iteration(self, data, current_iteration):
        """Fold 5-D video batches into label channels
        (ref: trainers/spade.py:97-126); NHWC: (N,T,H,W,C)."""
        import numpy as np

        label = np.asarray(data["label"])
        if label.ndim == 5:
            images = np.asarray(data["images"])
            prev_images = images[:, :-1]
            n, tm1, h, w, c = prev_images.shape
            label_image = prev_images.transpose(0, 2, 3, 1, 4).reshape(n, h, w, tm1 * c)
            t = label.shape[1]
            label_flat = label.transpose(0, 2, 3, 1, 4).reshape(
                n, h, w, t * label.shape[-1])
            data = dict(data)
            data["label"] = np.concatenate([label_flat, label_image], axis=-1)
            data["images"] = images[:, -1]
        return self._resize_data(data)

    def _resize_data(self, data):
        """Round H/W down to the generator base multiple
        (ref: trainers/spade.py:297-312)."""
        import numpy as np

        base = self.base
        out = dict(data)
        # label_float rides alongside the dataset's index-map label and
        # must stay spatially aligned for the feed's device concat
        for key in ("label", "images", "label_float"):
            if key in out:
                arr = np.asarray(out[key])
                h, w = arr.shape[1:3]
                h2, w2 = (h // base) * base, (w // base) * base
                if (h2, w2) != (h, w):
                    out[key] = arr[:, :h2, :w2]
        return out

    # ------------------------------------------------------------------ FID

    def _make_eval_gen_fn(self, variables):
        """Validation-set generator closure shared by FID/KID/PRDC.
        Uses the side-effect-free _start_of_iteration (the full hook
        would clobber current_iteration/timers mid-metrics)."""
        def gen_fn(data):
            data = self._eval_preprocess(data)
            out, _ = self._apply_G(variables, data, jax.random.PRNGKey(0),
                                   training=False)
            return out["fake_images"]
        return gen_fn

    def _extra_metric_activations(self, extractor):
        """Image-family activations for KID/PRDC (base template at
        trainers/base.py::compute_extra_metrics); real-set activations
        are cached across a checkpoint sweep."""
        from imaginaire_tpu.evaluation.common import get_activations

        gen_fn = self._make_eval_gen_fn(self.inference_params())
        # device-prefetch the sweep: the next batch transfers while the
        # extractor chews on this one (gen_fn skips re-prep for wrapped
        # batches)
        val_loader = self.data_prefetcher(self.val_data_loader)
        act_fake = get_activations(val_loader, "images",
                                   "fake_images", extractor,
                                   generator_fn=gen_fn)
        data_name = cfg_get(cfg_get(self.cfg, "data", {}), "name", "data")
        act_real = self._cached_real_activations(
            f"real_acts_{data_name}.npz",
            lambda: get_activations(val_loader, "images",
                                    "fake_images", extractor))
        return act_real, act_fake

    def _compute_fid(self):
        """FID for the regular and (if enabled) EMA generator
        (ref: trainers/spade.py:264-295)."""
        if self.val_data_loader is None:
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
        fid_path = os.path.join(logdir, f"real_stats_{data_name}.npz")

        val_loader = self.data_prefetcher(self.val_data_loader)
        fid = compute_fid(fid_path, val_loader, extractor,
                          self._make_eval_gen_fn(self.state["vars_G"]))
        if self.model_average:
            self.recalculate_model_average_batch_norm_statistics()
            fid_ema = compute_fid(
                fid_path, val_loader, extractor,
                self._make_eval_gen_fn(self.inference_params()))
            self._meter("FID_ema").write(float(fid_ema))
        return fid

    def _get_visualizations(self, data):
        """(input, label-viz, fake, [ema-fake]) strip
        (ref: trainers/spade.py:189-215)."""
        data = self._on_device(to_device(dict(data)))
        rng = jax.random.PRNGKey(0)
        out, _ = self._apply_G(self.state["vars_G"], data, rng,
                               training=False, random_style=True)
        vis = [data["images"][..., :3],
               data["label"][..., :1],
               out["fake_images"][..., :3]]
        if self.model_average:
            # the EMA copy's BN stats are re-estimated over training
            # batches first (ref: trainers/spade.py:189-215, base 415-443)
            self.recalculate_model_average_batch_norm_statistics()
            ema_out, _ = self._apply_G(self.inference_params(), data, rng,
                                       training=False, random_style=True)
            vis.append(ema_out["fake_images"][..., :3])
        return vis
