"""pix2pixHD trainer (ref: imaginaire/trainers/pix2pixHD.py:17-203).

Losses: GAN + FeatureMatching + Perceptual — SPADE's set minus the
style-VAE GaussianKL (ref: pix2pixHD.py:49-73). Preprocessing replaces
the label's trailing instance-map channel with an edge map and exposes
the raw ids as ``instance_maps`` (ref: pix2pixHD.py:135-157). Before a
checkpoint is written, instance features are K-means clustered so
multi-modal inference can sample cluster centers
(ref: pix2pixHD.py:159-173, model_utils/pix2pixHD.py:17-71).

TPU-first: the edge map is pure jnp shifts (no host loop), computed on
the device right after the feed has placed the batch and expanded its
label (``_on_device``); the cluster pass reuses the jitted encoder apply.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from imaginaire_tpu.config import cfg_get
from imaginaire_tpu.model_utils.pix2pixHD import cluster_features, get_edges
from imaginaire_tpu.trainers.spade import Trainer as SPADETrainer


class Trainer(SPADETrainer):
    def __init__(self, cfg, *args, **kwargs):
        super().__init__(cfg, *args, **kwargs)
        # Flax setup() attributes are only visible inside apply, so derive
        # the instance-map flag from the config exactly as the generator
        # does (models/generators/pix2pixHD.py:203-205).
        input_labels = list(cfg_get(cfg.data, "input_labels", []) or [])
        self.contain_instance_map = (
            bool(input_labels) and input_labels[-1] == "instance_maps")

    # _init_loss: SPADE's (spade.py:36-51) registers the KL weight only
    # when cfg.trainer.loss_weight.kl exists, so pix2pixHD configs get
    # exactly GAN + FeatureMatching + Perceptual (ref: pix2pixHD.py:49-73).

    # ------------------------------------------------------- preprocessing

    def pre_process(self, data):
        """Swap the trailing instance channel for an edge map
        (ref: trainers/pix2pixHD.py:135-157). jnp-traced; safe both
        host-side and under jit. Idempotent: a batch that already carries
        ``instance_maps`` passes through (end_of_iteration re-feeds the
        preprocessed batch to the visualization path)."""
        if not self.contain_instance_map or "instance_maps" in data:
            return data
        data = dict(data)
        label = jnp.asarray(data["label"])
        inst = label[..., -1:]
        # int32: ids must survive the bf16 compute-dtype cast (packed
        # Cityscapes ids like 26001/26002 collide in bf16's 8-bit mantissa);
        # _to_compute_dtype only touches float32 leaves.
        data["instance_maps"] = inst.astype(jnp.int32)
        data["label"] = jnp.concatenate([label[..., :-1], get_edges(inst)],
                                        axis=-1)
        return data

    def _on_device(self, data):
        # reads the stack's channels, so it follows the expansion; the
        # host hook stays SPADE's crop, on the compact batch
        return self.pre_process(super()._on_device(data))

    # --------------------------------------------------------- checkpoints

    def _has_encoder(self):
        enc_cfg = cfg_get(self.cfg.gen, "enc", None)
        return (enc_cfg is not None and self.contain_instance_map
                and cfg_get(enc_cfg, "num_feat_channels", 0) > 0)

    def _init_state(self, key, data):
        """Reserve the cluster-center leaf up front so the state pytree
        structure never changes mid-training (a late insert would force the
        jitted steps to recompile and break orbax resume targets)."""
        state = super()._init_state(key, data)
        if self._has_encoder():
            from imaginaire_tpu.utils.data import (
                get_paired_input_label_channel_number,
            )

            enc_cfg = self.cfg.gen.enc
            state["cluster_centers"] = jnp.zeros(
                (get_paired_input_label_channel_number(self.cfg.data),
                 cfg_get(enc_cfg, "num_clusters", 10),
                 enc_cfg.num_feat_channels), jnp.float32)
            # the partition shardings super() computed predate the new
            # leaf — rebuild them so the plan's structure matches
            self.state = self._place_state(state)
            return self.state
        return state

    def _pre_save_checkpoint(self):
        """K-means over encoder instance features → state['cluster_centers']
        (ref: trainers/pix2pixHD.py:159-173). The reference writes the
        centers into encoder buffers; our state pytree keeps them beside
        the params so they ride the same checkpoint."""
        if not self._has_encoder() or self.val_data_loader is None:
            return
        enc_cfg = self.cfg.gen.enc
        feat_nc = enc_cfg.num_feat_channels
        from imaginaire_tpu.utils.data import (
            get_paired_input_label_channel_number,
        )

        label_nc = get_paired_input_label_channel_number(self.cfg.data)
        variables = self.inference_params()
        from imaginaire_tpu.telemetry import xla_obs

        # ledgered (and graph-audited) like every compile site; the
        # variables ride as an argument so they never bake into the
        # executable as constants
        def encode(variables, images, instance_maps):
            return self.net_G.apply(
                variables, images, instance_maps, training=False,
                method=lambda mdl, im, inst, training: mdl.encoder(
                    im, inst, training=training))

        encode_program = xla_obs.compiled_program(
            "pix2pixHD_encode", encode, allow_shape_growth=True)

        def encode_fn(data):
            return encode_program(variables, data["images"],
                                  data["instance_maps"])

        preprocessed = (self._init_data(dict(d)) for d in self.val_data_loader)
        centers = cluster_features(
            encode_fn, preprocessed, label_nc, feat_nc,
            n_clusters=cfg_get(enc_cfg, "num_clusters", 10),
            is_cityscapes=cfg_get(self.cfg.gen, "is_cityscapes", False))
        self.state["cluster_centers"] = jnp.asarray(centers)

    # ------------------------------------------------------ visualizations

    def _get_visualizations(self, data):
        """(input, label-viz, fake) strip — pix2pixHD has no style path."""
        data = self._init_data(dict(data))
        out, _ = self._apply_G(self.state["vars_G"], data,
                               jax.random.PRNGKey(0), training=False)
        vis = [data["images"][..., :3], data["label"][..., :1],
               out["fake_images"][..., :3]]
        if self.model_average:
            ema_vars = dict(self.state["vars_G"], params=self.state["ema_G"])
            ema_out, _ = self._apply_G(ema_vars, data, jax.random.PRNGKey(0),
                                       training=False)
            vis.append(ema_out["fake_images"][..., :3])
        return vis
