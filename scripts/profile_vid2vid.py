#!/usr/bin/env python
"""Per-frame attribution for the vid2vid bench leg (VERDICT r3 #5).

Times, on the real chip, the cityscapes bf16.yaml recipe at 256x512
(512x1024 did not compile on the installation of 2026-08-01; not
retried): the per-frame D and G step programs and the G
apply alone, across three variants — base (FlowNet2 teacher in-graph),
a no-teacher twin (teacher cost = base - noteacher), and a
temporal-D-enabled twin (temporal-D marginal). Writes VIDPROFILE.json;
the narrative lives in PROFILE.md.

Method: the same two-K dispatch-slope timing as profile_bench.py (the
device queue serializes; the constant dispatch cost cancels).
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

REPEATS = 3
K_SMALL, K_LARGE = 2, 6


def _fence(out):
    jax.block_until_ready(out)


def measure(call):
    times = {}
    for k in (K_SMALL, K_LARGE):
        samples = []
        for _ in range(1 + REPEATS):
            t0 = time.perf_counter()
            out = None
            for _ in range(k):
                out = call()
            _fence(out)
            samples.append((time.perf_counter() - t0) * 1e3)
        times[k] = statistics.median(samples[1:])
    return max(0.0, (times[K_LARGE] - times[K_SMALL]) / (K_LARGE - K_SMALL))


def build(with_temporal=False, flow_teacher=True):
    import bench

    # 256x512 (VIDBENCH.json leg): 512x1024 programs did not compile on
    # the installation of 2026-08-01; not retried
    trainer, label_ch = bench.build_vid2vid(flow_teacher=flow_teacher,
                                            hw=(256, 512))
    if with_temporal:
        cfg = trainer.cfg
        cfg.dis.temporal = {"num_scales": 1, "num_filters": 64,
                            "max_num_filters": 512, "num_discriminators": 1,
                            "num_layers": 3, "weight_norm_type": "none",
                            "activation_norm_type": "instance"}
        cfg.trainer.loss_weight.temporal_gan = 1.0
        from imaginaire_tpu.registry import resolve

        trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
    return trainer, label_ch


def warped_frame_data(trainer, data):
    """data_t for a steady-state (full prev history) frame + past stacks."""
    t = data["images"].shape[1] - 1
    nG = trainer.num_frames_G
    prev_labels = data["label"][:, t - (nG - 1):t]
    prev_images = data["images"][:, t - (nG - 1):t]  # stand-in history
    data_t = trainer._get_data_t(data, t, prev_labels, prev_images)
    data_t["past_stacks"] = {}
    if trainer.num_temporal_scales > 0:
        tD = trainer.num_frames_D
        b, _, h, w, c = data["images"].shape
        data_t["past_stacks"] = {
            "s0": (data["images"][:, -(tD - 1):],
                   data["images"][:, -(tD - 1):])}
    return data_t


def main():
    results = {}
    # flow-teacher cost is attributed by SUBTRACTION (base - noteacher):
    # a standalone teacher-forward probe hung the device on the
    # installation of 2026-08-01; not retried
    for variant, with_temporal, flow_teacher in (
            ("base", False, True),
            ("noteacher", False, False),
            ("temporalD", True, True)):
        try:
            main_variant(variant, with_temporal, flow_teacher, results)
        except Exception as e:  # noqa: BLE001 - one bad variant
            print(f"[{variant}] failed entirely: {e!s:.150}", flush=True)
            results.setdefault(variant, {})
    finish(results)


def main_variant(variant, with_temporal, flow_teacher, results):
    import bench

    trainer, label_ch = build(with_temporal, flow_teacher)
    bs, seq = 2, 4
    data = jax.device_put(jax.tree_util.tree_map(
        np.asarray, bench.vid2vid_batch(bs, seq, label_ch,
                                        h=256, w=512)))
    jax.block_until_ready(data)
    trainer.init_state(jax.random.PRNGKey(0), data)
    data_t = warped_frame_data(trainer, data)
    print(f"[{variant}] profiling at bs={bs} 256x512 on "
          f"{jax.devices()[0]}", flush=True)

    def dis_frame():
        trainer.state, _, _h = trainer._jit_vid_dis(trainer.state, data_t)
        return trainer.state["vars_D"]["params"]

    def gen_frame():
        trainer.state, _, fake, _h = trainer._jit_vid_gen(trainer.state,
                                                          data_t)
        return fake

    rng = jax.random.PRNGKey(1)

    @jax.jit  # lint: allow(bare-jit) -- profiler harness measures the raw jit path on purpose
    def g_apply(vars_G, d):
        out, _ = trainer._apply_G(vars_G, d, rng, training=True)
        return out["fake_images"]

    comp_data = trainer._to_compute_dtype(
        {k: v for k, v in data_t.items() if k != "past_stacks"})
    vars_G = trainer._cast_net_vars(trainer.state["vars_G"])

    cases = [("dis_frame_step", dis_frame),
             ("gen_frame_step", gen_frame),
             ("g_apply_forward", lambda: g_apply(vars_G, comp_data))]

    out = {}
    for name, call in cases:
        try:
            ms = measure(call)
            out[name] = round(ms, 2)
            print(f"  {name}: {ms:.2f} ms", flush=True)
        except Exception as e:  # noqa: BLE001
            out[name] = None
            print(f"  {name}: failed ({e!s:.100})", flush=True)
    results[variant] = out
    trainer.state = None


def finish(results):
    base = results.get("base", {})
    noteacher = results.get("noteacher", {})
    temp = results.get("temporalD", {})
    derived = {}
    if all((base.get("gen_frame_step"), base.get("dis_frame_step"),
            temp.get("gen_frame_step"), temp.get("dis_frame_step"))):
        derived["temporal_D_marginal_ms (gen+dis, temporalD - base)"] = round(
            (temp["gen_frame_step"] + temp["dis_frame_step"])
            - (base["gen_frame_step"] + base["dis_frame_step"]), 2)
    if base.get("gen_frame_step") and noteacher.get("gen_frame_step"):
        derived["flownet2_teacher_marginal_ms (base - noteacher gen)"] = \
            round(base["gen_frame_step"] - noteacher["gen_frame_step"], 2)
    if base.get("gen_frame_step") and base.get("g_apply_forward"):
        derived["gen_backward+opt_ms (step - apply)"] = round(
            base["gen_frame_step"] - base["g_apply_forward"], 2)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    payload = {"device": str(jax.devices()[0]), "batch_size": 2,
               "shape": "256x512", "components_ms": results,
               "derived": derived}
    with open(os.path.join(root, "VIDPROFILE.json"), "w") as f:
        json.dump(payload, f, indent=1)
    print(json.dumps(payload, indent=1))


if __name__ == "__main__":
    main()
