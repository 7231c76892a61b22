"""Trace the repo's real ledgered programs for offline auditing.

``scripts/lint_graph.py --families`` and tests/test_graph_audit.py need
the closed jaxpr of every family's step programs WITHOUT paying a
compile or touching an accelerator: build the trainer from its
unit-test config, ``jax.eval_shape`` the init to get a state
ShapeDtypeStruct tree (no compute), and ``jit.trace`` each registered
``CompiledProgram`` on SDS inputs. Closures that must be concrete
(inception variables, flow-teacher params) are zero-filled from their
eval_shape — allocation, never computation.
"""

import os
import tempfile

import numpy as np

FAMILIES = ("spade", "pix2pixHD", "unit", "munit", "funit", "coco_funit",
            "vid2vid", "fs_vid2vid", "wc_vid2vid")
VIDEO_FAMILIES = ("vid2vid", "fs_vid2vid", "wc_vid2vid")
AUX_PROGRAMS = ("flow_teacher", "inception_extractor")

_CONFIG_FILES = {
    "vid2vid": "vid2vid_street.yaml",
}


def _repo_root():
    return os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", ".."))


def config_path(family):
    return os.path.join(_repo_root(), "configs", "unit_test",
                        _CONFIG_FILES.get(family, f"{family}.yaml"))


def _rng():
    return np.random.RandomState(0)


def family_batch(family, h=64, w=64):
    """A one-sample numpy batch shaped like the family's unit-test
    datasets (tests/test_* helpers are the reference)."""
    rng = _rng()

    def img(*shape):
        return rng.rand(*shape).astype(np.float32) * 2 - 1

    def seg(*shape):
        return (rng.rand(*shape) > 0.9).astype(np.float32)

    if family == "spade":
        return {"images": img(1, 256, 256, 3),
                "label": seg(1, 256, 256, 14)}
    if family == "pix2pixHD":
        lab = np.concatenate(
            [seg(1, 128, 128, 8),
             rng.randint(0, 5, (1, 128, 128, 1)).astype(np.float32)],
            axis=-1)
        return {"images": img(1, 128, 128, 3), "label": lab}
    if family in ("unit", "munit"):
        return {"images_a": img(1, h, w, 3), "images_b": img(1, h, w, 3)}
    if family in ("funit", "coco_funit"):
        return {"images_content": img(1, h, w, 3),
                "images_style": img(1, h, w, 3),
                "labels_content": np.asarray([1], np.int32),
                "labels_style": np.asarray([0], np.int32)}
    if family in ("vid2vid", "fs_vid2vid", "wc_vid2vid"):
        t = 3 if family != "fs_vid2vid" else 2
        data = {"images": img(1, t, h, w, 3),
                "label": seg(1, t, h, w, 12)}
        if family == "fs_vid2vid":
            data["ref_images"] = img(1, 1, h, w, 3)
            data["ref_labels"] = seg(1, 1, h, w, 12)
        if family == "wc_vid2vid":
            infos = []
            for ti in range(t):
                n = 50
                infos.append(np.stack(
                    [rng.randint(0, h, n), rng.randint(0, w, n),
                     rng.randint(0, 500, n)], axis=1))
            data["unprojection"] = [infos]
        return data
    raise KeyError(f"unknown family {family!r}")


def _sds(tree):
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype)
        if not isinstance(a, jax.ShapeDtypeStruct) else a, tree)


def build_trainer(family, logdir=None):
    from imaginaire_tpu.config import Config
    from imaginaire_tpu.registry import resolve

    cfg = Config(config_path(family))
    cfg.logdir = logdir or tempfile.mkdtemp(prefix=f"audit_{family}_")
    return resolve(cfg.trainer.type, "Trainer")(cfg)


def _state_sds(trainer, batch):
    """State ShapeDtypeStruct tree via eval_shape'd init (no compute).
    eval_shape leaves SDS in trainer.state — reset it."""
    import jax

    sds = jax.eval_shape(
        lambda k, b: trainer.init_state(k, b),
        jax.ShapeDtypeStruct((2,), np.uint32), batch)
    trainer.state = None
    return sds


def _video_data_t(trainer, data):
    """Steady-state per-frame data_t (full-size history buffers), jit
    keys only — mirrors gen_update's per-frame path."""
    n_prev = trainer.num_frames_G - 1
    t_dis = trainer.num_frames_D
    scales = trainer.num_temporal_scales
    max_prev = (t_dis ** max(scales - 1, 0)) * (t_dis - 1)
    t_steady = max(n_prev, max_prev if scales > 0 else 0, 1)
    seq_len = data["images"].shape[1]
    t = min(t_steady, seq_len - 1)
    b, _, h, w, _ = data["images"].shape
    n_lab = data["label"].shape[-1]
    prev_labels = np.zeros((b, max(n_prev, 1), h, w, n_lab), np.float32)
    prev_images = np.zeros((b, max(n_prev, 1), h, w, 3), np.float32)
    if hasattr(trainer, "reset_renderer"):
        trainer.reset_renderer(False)  # wc point cloud host state
    data_t = trainer._get_data_t(data, t, prev_labels, prev_images)
    if scales > 0:
        past_real = np.zeros((b, max_prev, h, w, 3), np.float32)
        past_fake = np.zeros((b, max_prev, h, w, 3), np.float32)
        data_t["past_stacks"] = trainer._past_stacks(past_real, past_fake)
    else:
        data_t["past_stacks"] = {}
    return {k: v for k, v in data_t.items()
            if not str(k).startswith("_")}


def trace_family_programs(family, logdir=None):
    """[(label, Traced)] for the family's ledgered step programs —
    trace-only, no compile, no compute."""
    trainer = build_trainer(family, logdir=logdir)
    batch = family_batch(family)
    traced = []
    if family in VIDEO_FAMILIES:
        data_t = _video_data_t(trainer, batch)
        state = _sds(_state_sds(trainer, batch))
        args = (state, _sds(data_t))
        traced.append(("vid_dis_step",
                       trainer._jit_vid_dis._jit.trace(*args)))
        traced.append(("vid_gen_step",
                       trainer._jit_vid_gen._jit.trace(*args)))
        if family == "wc_vid2vid" and trainer.single_image_model \
                is not None:
            import jax

            sid = {"label": batch["label"][:, 0],
                   "images": batch["images"][:, 0]}
            vars_sds = jax.eval_shape(
                lambda k, d: trainer.single_image_model.init(
                    {"params": k, "noise": k}, d, random_style=True,
                    training=False),
                jax.ShapeDtypeStruct((2,), np.uint32), _sds(sid))
            traced.append(("wc_single_image",
                           trainer._jit_single._jit.trace(
                               vars_sds, _sds(sid),
                               jax.ShapeDtypeStruct((2,), np.uint32))))
    else:
        if family == "pix2pixHD":
            # edge/instance preprocessing happens in start_of_iteration
            batch = trainer.start_of_iteration(batch, 1)
        state = _sds(_state_sds(trainer, batch))
        args = (state, _sds(batch))
        traced.append(("dis_step",
                       trainer._jit_dis_step._jit.trace(*args)))
        traced.append(("gen_step",
                       trainer._jit_gen_step._jit.trace(*args)))
    return traced


def trace_aux_programs():
    """[(label, Traced)] for the shared non-trainer programs: the
    FlowNet2 teacher and the FID/KID inception extractor (zero-filled
    concrete closures — no init compute)."""
    import jax
    import jax.numpy as jnp

    traced = []
    from imaginaire_tpu.flow.flow_net import FlowNet

    net = FlowNet(allow_random_init=True)
    params_sds = jax.eval_shape(
        lambda k: net.model.init(k, jnp.zeros((1, 2, 64, 64, 3)))
        ["params"], jax.ShapeDtypeStruct((2,), np.uint32))
    im = jax.ShapeDtypeStruct((1, 64, 64, 3), np.float32)
    traced.append(("flow_teacher", net._jit_flow._jit.trace(
        params_sds, im, im)))

    from imaginaire_tpu.evaluation.inception import (
        InceptionV3, make_extractor,
    )

    model = InceptionV3()
    vars_sds = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 299, 299, 3))),
        jax.ShapeDtypeStruct((2,), np.uint32))
    extractor = make_extractor(vars_sds)
    traced.append(("inception_extractor", extractor.program._jit.trace(
        vars_sds, jax.ShapeDtypeStruct((2, 299, 299, 3), np.float32))))
    return traced


def audit_family(family, *, const_bytes_limit=None, logdir=None):
    """label -> audit dict (see analysis.audit_program), trace-only."""
    from . import audit_program

    out = {}
    for label, traced in trace_family_programs(family, logdir=logdir):
        out[label] = audit_program(
            f"{family}/{label}", traced=traced,
            const_bytes_limit=const_bytes_limit, include_hlo=False)
    return out
