"""Benchmark: SPADE training throughput on the real TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Measures steady-state imgs/sec of the full alternating D+G SPADE training
step (both updates per batch, reference semantics) at 256x256 using the
shipped zoo config ``configs/projects/spade/cocostuff/base128_bs4.yaml``
verbatim — num_filters 128 G and D, kernel-5 separate-projection
sync-batch SPADE norms, spectral norm, model average, bf16 — the exact
budget behind the reference's published 2-3-week training run. Pass
``--width unit`` for the reference's nf=64 unit-test width (the number
benched in rounds 1-2; reported for continuity in README).

vs_baseline derivation: the reference documents only "~2-3 weeks" for
400 epochs of COCO-Stuff (~118,287 train images) on 8x V100
(projects/spade/README.md:24-25, MODELZOO.md:10) with this same nf=128
config. Taking 17.5 days: 400*118287 / (17.5*86400) / 8 = 3.91 imgs/sec
per V100. vs_baseline is our imgs/sec/chip divided by that —
apples-to-apples at --width zoo (the default).

Component attribution for this number lives in PROFILE.md
(scripts/profile_bench.py).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

V100_IMGS_PER_SEC = 3.91


def _bench_telemetry():
    """In-memory telemetry for bench legs: spans/ring buffers on, no
    sinks, no auto-flush — window_summary() is read per leg so bench
    rounds and training telemetry share one schema (DATABENCH/VIDBENCH
    carry the same step p50/p99 + data_wait share a run's
    telemetry.jsonl does)."""
    from imaginaire_tpu import telemetry

    return telemetry.configure(enabled=True, sinks=[],
                               flush_every_n_steps=0, mfu=False)


def _leg_summary(tm, xla_mark=None, trainer=None):
    """Slim window_summary for the bench JSON sidecars. With an
    ``xla_mark`` (a ledger snapshot from the leg's start), the summary
    also carries the leg's compile cost, recompile count, and the peak
    HBM watermark (ISSUE 5: every bench leg answers 'what did compiles
    cost and did anything re-specialize'). With a ``trainer``, the
    summary records the precision/remat configuration the leg actually
    ran under (ISSUE 10: a bench number is meaningless without the
    compute dtype + checkpointing policy that produced it)."""
    s = tm.window_summary()
    keep = ("duration_s", "steps", "step_ms_p50", "step_ms_p99",
            "data_wait_share_pct", "imgs_per_sec")
    out = {k: s[k] for k in keep if k in s}
    out["phase_total_ms"] = {name: row["total_ms"]
                             for name, row in s.get("phases", {}).items()}
    if xla_mark is not None:
        out["xla"] = _xla_leg(xla_mark)
    if trainer is not None:
        out["precision"] = _precision_leg(trainer)
    out["ops"] = _ops_leg()
    out["resilience"] = _resilience_leg()
    out.update(_pipeline_leg(tm))
    out["pod"] = _pod_leg(tm)
    out["eval"] = _eval_leg(tm)
    out["serving"] = _serving_leg(tm)
    return out


def _ops_leg():
    """The resolved ops implementation map for one bench leg (ISSUE 16):
    what ``implementation='auto'`` dispatched to for every native op
    (``{spade_modulation: fused, correlation: mxu, ...}``), so BENCH
    rows are attributable to kernel choices."""
    try:
        from imaginaire_tpu import ops

        return ops.resolved_implementations()
    except Exception:  # noqa: BLE001 — bench accounting is best-effort
        return None


def _pipeline_leg(tm):
    """{pipeline_depth, overlap_ratio, dispatch_gap_ms} for one bench
    leg (ISSUE 14) — the LAST rollout's counters from the software
    pipeline's instrument (parallel/pipeline.py; the sequential path
    reports depth 0 from the same meter). All None for image-family
    legs, which never emit the counters."""
    latest = {}
    try:
        with tm._lock:
            events = list(tm._events)
        for ev in events:
            if ev.get("kind") == "counter" and \
                    str(ev.get("name", "")).startswith("pipeline/"):
                latest[ev["name"]] = ev.get("value")
    except Exception:  # noqa: BLE001 — bench accounting is best-effort
        pass
    depth = latest.get("pipeline/depth")
    return {
        "pipeline_depth": int(depth) if depth is not None else None,
        "overlap_ratio": latest.get("pipeline/overlap_ratio"),
        "dispatch_gap_ms": latest.get("pipeline/dispatch_gap_ms"),
    }


def _eval_leg(tm):
    """{fid, time_to_fid_ms, ref_cache_hit_rate} for one bench leg
    (ISSUE 18) — the quality plane's verdict when the leg ran eval
    sweeps (latest FID, latest sweep's wall-clock, and the share of
    sweeps whose reference activations came from the content-addressed
    store). None for legs that never evaluated."""
    fid = ttf = None
    hits = []
    try:
        with tm._lock:
            events = list(tm._events)
        for ev in events:
            if ev.get("kind") != "counter":
                continue
            name = str(ev.get("name", ""))
            if name == "eval/fid":
                fid = ev.get("value")
            elif name == "eval/time_to_fid_ms":
                ttf = ev.get("value")
            elif name == "eval/ref_cache_hit":
                hits.append(int(ev.get("value") or 0))
    except Exception:  # noqa: BLE001 — bench accounting is best-effort
        pass
    if fid is None and not hits:
        return None
    return {
        "fid": fid,
        "time_to_fid_ms": ttf,
        "ref_cache_hit_rate": (sum(hits) / len(hits)) if hits else None,
    }


def _serving_leg(tm):
    """{p50_ms, p99_ms, requests, bucket_hit_rate, pad_waste_frac} for
    one bench leg (ISSUE 19) — the serving engine's latest SLO counters
    when the leg pushed requests through the warm executable pool.
    None for legs that never served. ISSUE 20 adds the error-budget
    gauges (burn rate, remaining budget, breach/shed counts) and the
    leg's trace volume."""
    latest = {}
    traces = 0
    keep = ("serve/p50_ms", "serve/p99_ms", "serve/requests",
            "serve/bucket_hit_rate", "serve/pad_waste_frac",
            "serve/queue_depth", "serve/slo/burn_rate",
            "serve/slo/budget_remaining_frac", "serve/slo/breaches",
            "serve/slo/rejected")
    try:
        with tm._lock:
            events = list(tm._events)
        for ev in events:
            if ev.get("kind") == "counter" and ev.get("name") in keep:
                latest[ev["name"]] = ev.get("value")
            elif (ev.get("kind") == "trace"
                  and ev.get("name") == "trace/request"):
                traces += 1
    except Exception:  # noqa: BLE001 — bench accounting is best-effort
        pass
    if not latest:
        return None
    return {
        "p50_ms": latest.get("serve/p50_ms"),
        "p99_ms": latest.get("serve/p99_ms"),
        "requests": latest.get("serve/requests"),
        "bucket_hit_rate": latest.get("serve/bucket_hit_rate"),
        "pad_waste_frac": latest.get("serve/pad_waste_frac"),
        "queue_depth": latest.get("serve/queue_depth"),
        "slo_burn_rate": latest.get("serve/slo/burn_rate"),
        "slo_budget_remaining_frac":
            latest.get("serve/slo/budget_remaining_frac"),
        "slo_breaches": latest.get("serve/slo/breaches"),
        "slo_rejected": latest.get("serve/slo/rejected"),
        "traces": traces,
    }


def _pod_leg(tm):
    """{step_skew_ms_p50, straggler_process, straggler_span,
    divergence_count} for one bench leg (ISSUE 17) — the podview
    plane's verdict over the leg's digest rounds, so the PODBENCH
    localhost-contention framing is measurable instead of prose. All
    None/0 for single-process legs, which never emit the counters."""
    skews = []
    straggler_meta = None
    divergence = 0
    try:
        with tm._lock:
            events = list(tm._events)
        for ev in events:
            name = str(ev.get("name", ""))
            if ev.get("kind") == "counter":
                if name == "pod/step_skew_ms":
                    skews.append(float(ev.get("value") or 0.0))
                elif name == "pod/divergence":
                    divergence = int(ev.get("value") or 0)
            elif ev.get("kind") == "meta" and name == "pod/straggler":
                straggler_meta = ev
    except Exception:  # noqa: BLE001 — bench accounting is best-effort
        pass
    p50 = None
    if skews:
        ordered = sorted(skews)
        p50 = round(ordered[len(ordered) // 2], 3)
    return {
        "step_skew_ms_p50": p50,
        "straggler_process": (straggler_meta or {}).get("process"),
        "straggler_span": (straggler_meta or {}).get("span"),
        "divergence_count": divergence,
    }


def _precision_leg(trainer):
    """{compute_dtype, remat_policy, temp_bytes} for one bench leg
    (ISSUE 10). temp_bytes is the worst per-executable XLA temp
    allocation the compile ledger saw (gen_step/dis_step and friends) —
    None on backends that don't expose memory_analysis (CPU)."""
    import jax.numpy as jnp

    from imaginaire_tpu.config import cfg_get
    from imaginaire_tpu.telemetry import xla_obs

    temp = None
    try:
        for mem in xla_obs.ledger().label_memory.values():
            t = mem.get("temp_bytes")
            if t is not None:
                temp = max(int(t), temp or 0)
    except Exception:  # noqa: BLE001 — bench accounting is best-effort
        pass
    return {
        "compute_dtype": str(jnp.dtype(trainer.compute_dtype).name),
        "remat_policy": str(cfg_get(getattr(trainer.cfg, "gen", None),
                                    "remat", "none")),
        "temp_bytes": temp,
    }


def _resilience_leg():
    """Fault-tolerance counters for a bench leg (ISSUE 7): retries,
    checkpoint fallbacks/quarantines and corrupt flow shards observed
    during the leg. All zero on a healthy leg — the point of recording
    them is that a regression (flaky store, corrupt cache) shows up in
    the bench JSON instead of hiding in warning logs."""
    counters = {}
    try:
        from imaginaire_tpu import telemetry as _tm

        with _tm.get()._lock:
            events = list(_tm.get()._events)
        for ev in events:
            name = str(ev.get("name", ""))
            if ev.get("kind") == "counter" and (
                    name.startswith("resilience/")
                    or name == "flow_cache/corrupt_shards"):
                counters[name] = ev.get("value")
    except Exception:  # noqa: BLE001 — bench accounting is best-effort
        pass
    return {
        "retries": sum(int(v or 0) for k, v in counters.items()
                       if k.startswith("resilience/retry/")),
        "ckpt_fallbacks": int(counters.get("resilience/ckpt_fallbacks",
                                           0) or 0),
        "ckpt_quarantined": int(
            counters.get("resilience/ckpt_quarantined", 0) or 0),
        "corrupt_flow_shards": int(
            counters.get("flow_cache/corrupt_shards", 0) or 0),
        # pod coordination (ISSUE 8): which topology the leg ran in and
        # whether any timed rendezvous expired — a desync in a bench
        # leg means the numbers measured a half-dead pod
        "process_count": _process_count(),
        "cluster_desyncs": int(
            counters.get("resilience/cluster_desyncs", 0) or 0),
        # elastic resizes (ISSUE 13): a bench leg that reshaped its pod
        # mid-run measured TWO topologies — the resize count, the total
        # downtime, and the redistributed state bytes must ride the
        # JSON next to the throughput
        "resizes": int(
            counters.get("elastic/resizes", 0) or 0),
        "resize_downtime_ms": float(
            counters.get("elastic/downtime_ms", 0) or 0),
        "redistributed_bytes": int(
            counters.get("elastic/redistributed_bytes", 0) or 0),
    }


def _process_count():
    try:
        import jax

        return int(jax.process_count())
    except Exception:  # noqa: BLE001
        return 1


def _parallel_leg(trainer=None):
    """{mesh_shape, state_bytes_per_chip, update_state_bytes} for a
    bench leg (ISSUE 6): which mesh the leg ran on and what the train
    state actually costs PER CHIP under the active partition plan —
    equal to the global tree size when state is replicated, 1/shard of
    opt/EMA under cfg.parallel's cross-replica update-state sharding."""
    from imaginaire_tpu.parallel.mesh import peek_mesh
    from imaginaire_tpu.parallel.partition import (
        per_device_tree_bytes,
        state_bytes_report,
    )

    mesh = peek_mesh()
    out = {"mesh_shape": {str(k): int(v)
                          for k, v in dict(mesh.shape).items()}
           if mesh is not None else None}
    state = getattr(trainer, "state", None) if trainer is not None else None
    if state:
        out["state_bytes_per_chip"] = per_device_tree_bytes(state)
        out["update_state_bytes"] = state_bytes_report(state)
    return out


def _xla_mark():
    """Ledger snapshot at a bench leg's start (before its compiles)."""
    from imaginaire_tpu.telemetry import xla_obs

    return xla_obs.ledger().snapshot()


def _xla_leg(mark):
    """{compiles, compile_s, recompile_count, cache_hits,
    peak_hbm_bytes, graph_violations, dead_donations, collective_bytes}
    for one leg (peak_hbm_bytes is None on CPU). The graph-audit triple
    is the static verdict over the leg's fresh compiles — a bench leg
    that introduces a dead donated arg or an island cast shows it here
    even when its timings look fine."""
    from imaginaire_tpu.telemetry import xla_obs

    delta = xla_obs.snapshot_delta(mark)
    delta["recompile_count"] = delta.pop("recompiles")
    return delta
ZOO_CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "configs", "projects", "spade", "cocostuff",
                          "base128_bs4.yaml")


def build_zoo():
    """The faithful zoo-width trainer, built from the shipped YAML."""
    from imaginaire_tpu.config import Config
    from imaginaire_tpu.registry import resolve

    cfg = Config(ZOO_CONFIG)
    # no pretrained VGG in this environment; random weights cost the same
    cfg.trainer.perceptual_loss.allow_random_init = True
    trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
    # label channels: 183 seg + dont-care + 1 edge map (cfg.data input_types)
    from imaginaire_tpu.utils.data import get_paired_input_label_channel_number

    return trainer, get_paired_input_label_channel_number(cfg.data)


def build_unit():
    """The reference unit-test width (nf=64, kernel-3 instance-norm SPADE)."""
    from imaginaire_tpu.config import Config
    from imaginaire_tpu.registry import resolve

    cfg = Config()
    cfg.trainer.type = "imaginaire_tpu.trainers.spade"
    cfg.trainer.gan_mode = "hinge"
    cfg.trainer.loss_weight = {"gan": 1.0, "feature_matching": 10.0,
                               "kl": 0.05, "perceptual": 10.0}
    cfg.trainer.perceptual_loss = {
        "mode": "vgg19",
        "layers": ["relu_1_1", "relu_2_1", "relu_3_1", "relu_4_1", "relu_5_1"],
        "weights": [0.03125, 0.0625, 0.125, 0.25, 1.0],
        "allow_random_init": True}
    cfg.trainer.model_average = True
    cfg.trainer.compute_dtype = "bfloat16"
    cfg.gen = {
        "type": "imaginaire_tpu.models.generators.spade",
        "style_dims": 256, "num_filters": 64, "kernel_size": 3,
        "weight_norm_type": "spectral",
        "global_adaptive_norm_type": "instance",
        "activation_norm_params": {"num_filters": 128, "kernel_size": 3,
                                   "activation_norm_type": "instance",
                                   "weight_norm_type": "none",
                                   "separate_projection": False},
        "style_enc": {"num_filters": 64, "kernel_size": 3},
    }
    cfg.dis = {
        "type": "imaginaire_tpu.models.discriminators.spade",
        "num_filters": 64, "max_num_filters": 512, "num_discriminators": 2,
        "num_layers": 5, "weight_norm_type": "spectral",
    }
    n_seg = 183
    cfg.data = {
        "name": "bench", "type": "imaginaire_tpu.data.paired_images",
        "input_types": [
            {"images": {"num_channels": 3, "normalize": True}},
            {"seg_maps": {"num_channels": n_seg, "is_mask": True,
                          "use_dont_care": True, "interpolator": "NEAREST"}},
        ],
        "input_image": ["images"],
        "input_labels": ["seg_maps"],
        "train": {"batch_size": 1,
                  "augmentations": {"random_crop_h_w": "256, 256"}},
    }
    cfg.gen_opt.lr = 1e-4
    cfg.dis_opt.lr = 4e-4
    trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
    return trainer, n_seg + 1


def build_vid2vid(flow_teacher=True, hw=(512, 1024), rollout_scan=False,
                  flow_cache=None, pipeline=None):
    """The shipped cityscapes vid2vid recipe (512x1024, bs2, interleaved
    per-frame D+G rollout with flow warp + multi-SPADE combine).
    ``hw`` below (512, 1024) is the fallback size for programs that did
    not compile on the installation of 2026-08-01; not retried (metric
    name flags it)."""
    from imaginaire_tpu.config import Config
    from imaginaire_tpu.registry import resolve
    from imaginaire_tpu.utils.data import get_paired_input_label_channel_number

    cfg = Config(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "configs", "projects", "vid2vid", "cityscapes",
                              "bf16.yaml"))
    cfg.trainer.rollout_scan = rollout_scan
    if pipeline is not None:
        # software-pipelined dispatch A/B (ISSUE 14): e.g.
        # {"enabled": False} for the sequential baseline leg
        cfg.trainer.pipeline = dict(pipeline)
    if flow_cache is not None:
        # teacher-amortization A/B legs (run_teacher_ab): e.g.
        # {"enabled": True, "mode": "disk", "dir": ...}
        cfg.flow_cache = dict(flow_cache)
    # no pretrained VGG / FlowNet2 weights in this environment; random
    # weights cost the same (the FlowNet2 teacher stays in the graph)
    cfg.trainer.perceptual_loss.allow_random_init = True
    cfg.trainer.perceptual_loss.pop("weights_path", None)
    if flow_teacher:
        cfg.flow_network.allow_random_init = True
        cfg.flow_network.pop("weights_path", None)
    else:
        # fallback leg: the fork's warp-consistency flow loss instead of
        # the FlowNet2 teacher (the teacher's 512x1024 cascade did not
        # compile on the installation of 2026-08-01; not retried)
        cfg.pop("flow_network", None)
    if hw != (512, 1024):
        # the generator statically sizes from the config augmentations
        hw_str = f"{hw[0]}, {hw[1]}"
        for split in ("train", "val"):
            aug = cfg.data[split].augmentations
            aug.pop("resize_smallest_side", None)
            for key in ("random_crop_h_w", "center_crop_h_w",
                        "resize_h_w"):
                if key in aug:
                    aug.pop(key)
            aug.resize_h_w = hw_str
    trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
    return trainer, get_paired_input_label_channel_number(cfg.data)


def vid2vid_batch(bs, t, label_ch, h=512, w=1024):
    rng = np.random.RandomState(0)
    lab = np.zeros((bs, t, h, w, label_ch), np.float32)
    idx = rng.randint(0, label_ch, (bs, t, h, w))
    np.put_along_axis(lab, idx[..., None], 1.0, axis=-1)
    return {
        "images": rng.rand(bs, t, h, w, 3).astype(np.float32) * 2 - 1,
        "label": lab,
    }


def _merge_vidbench(extra):
    """Merge keys into VIDBENCH.json without clobbering the tracked
    metric time series."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "VIDBENCH.json")
    book = {}
    if os.path.exists(path):
        with open(path) as f:
            book = json.load(f)
    book.update(extra)
    with open(path, "w") as f:
        json.dump(book, f, indent=1)


def run_teacher_ab(width="zoo", hw=(256, 512), bs=2, seq_len=4, iters=4):
    """Teacher-amortization A/B (ISSUE 4 satellite): the same vid2vid
    step driven three ways — FlowNet2 teacher in-graph (the reference
    semantics), amortized producer-mode cold (teacher recomputed
    off-step every iteration), and cache-warm (on-disk hit, ~zero
    teacher cost) — recording ``teacher_cache_speedup_pct`` and
    ``flow_cache_hit_rate`` into VIDBENCH.json as first-class
    regression metrics. ``--width unit`` runs the 64x64 unit-test
    recipe (CPU-feasible smoke); ``zoo`` the cityscapes recipe at the
    bench operating point."""
    import tempfile

    import jax
    import jax.numpy as jnp

    cache_dir = tempfile.mkdtemp(prefix="flow_cache_ab_")
    leg_cache_cfg = {
        "in_graph": {"enabled": False},
        "producer_cold": {"enabled": True, "mode": "producer"},
        "cache_warm": {"enabled": True, "mode": "disk", "dir": cache_dir},
    }

    def build(leg):
        if width == "unit":
            from imaginaire_tpu.config import Config
            from imaginaire_tpu.registry import resolve

            cfg = Config(os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "configs",
                "unit_test", "vid2vid_street.yaml"))
            cfg.flow_network = {"allow_random_init": True}
            cfg.flow_cache = dict(leg_cache_cfg[leg])
            trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
            rng = np.random.RandomState(0)
            t = 3
            data = {
                "images": rng.rand(1, t, 64, 64, 3).astype(
                    np.float32) * 2 - 1,
                "label": (rng.rand(1, t, 64, 64, 12) > 0.9).astype(
                    np.float32),
            }
            return trainer, data, t
        trainer, label_ch = build_vid2vid(True, hw,
                                          flow_cache=leg_cache_cfg[leg])
        data = vid2vid_batch(bs, seq_len, label_ch, h=hw[0], w=hw[1])
        return trainer, data, bs * seq_len

    rates, hit_rate = {}, None
    for leg in ("in_graph", "producer_cold", "cache_warm"):
        jax.clear_caches()
        trainer, data, n_units = build(leg)
        first = trainer.start_of_iteration(dict(data), 0)
        trainer.init_state(jax.random.PRNGKey(0), first)

        def sync():
            # the last step's outputs: the state both updates wrote
            jax.block_until_ready(trainer.state)

        for i in range(2):  # compile + warm (and populate the store)
            batch = trainer.start_of_iteration(dict(data), i)
            trainer.dis_update(batch)
            trainer.gen_update(batch)
        sync()
        t0 = time.time()
        for i in range(iters):
            batch = trainer.start_of_iteration(dict(data), i)
            trainer.dis_update(batch)
            trainer.gen_update(batch)
        sync()
        rates[leg] = n_units * iters / (time.time() - t0)
        if leg == "cache_warm" and trainer.flow_cache is not None:
            hit_rate = trainer.flow_cache.hit_rate()
            assert "flownet" not in (trainer.state["loss_params"] or {}), \
                "flow cache active but the step program still carries " \
                "the FlowNet2 param tree"
        trainer.state = None

    speedup_pct = (rates["cache_warm"] / rates["in_graph"] - 1.0) * 100.0
    payload = {
        "teacher_cache_speedup_pct": round(speedup_pct, 2),
        "flow_cache_hit_rate": (round(hit_rate, 4)
                                if hit_rate is not None else None),
        "teacher_ab": {
            "width": width,
            "platform": jax.devices()[0].platform,
            "in_graph_fps": round(rates["in_graph"], 3),
            "producer_cold_fps": round(rates["producer_cold"], 3),
            "cache_warm_fps": round(rates["cache_warm"], 3),
            "iters": iters,
        },
    }
    _merge_vidbench(payload)
    print(json.dumps({
        "metric": "vid2vid_teacher_cache_speedup_pct",
        "value": round(speedup_pct, 2),
        "unit": "pct",
        "vs_baseline": None,
    }))
    return payload


def _merge_evalbench(extra):
    """Merge keys into EVALBENCH.json without clobbering existing rows."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "EVALBENCH.json")
    book = {}
    if os.path.exists(path):
        with open(path) as f:
            book = json.load(f)
    book.update(extra)
    with open(path, "w") as f:
        json.dump(book, f, indent=1)


def run_eval_ab(batches=8, bs=8, hw=(64, 64)):
    """Reference-store cold-vs-warm A/B (ISSUE 18 acceptance record):
    the same quality sweep driven twice through the eval plane — cold
    (reference activations computed and published to the
    content-addressed store) and warm (reference shard read back) —
    recording both legs' time-to-FID and the warm speedup into
    EVALBENCH.json. Runs the patch smoke extractor (the store A/B is
    about the REFERENCE side's recompute-vs-read, which is
    extractor-agnostic; inception on CPU would bury the signal under
    minutes of network forward). Multi-device processes (real chips, or
    XLA_FLAGS=--xla_force_host_platform_device_count=N on CPU) set the
    all-device data mesh first, so the sweep's batches genuinely shard
    — the recorded ``devices`` field says which regime a row measured."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from imaginaire_tpu.evaluation import EvalPlane, make_patch_extractor

    tm = _bench_telemetry()
    devices = len(jax.devices())
    if devices > 1:
        from imaginaire_tpu.parallel.mesh import mesh_from_config, set_mesh

        set_mesh(mesh_from_config({}))
    rng = np.random.RandomState(0)
    loader = [{"images": rng.rand(bs, hw[0], hw[1], 3).astype(
        np.float32) * 2 - 1} for _ in range(batches)]

    def gen_fn(data):
        return jnp.clip(jnp.asarray(np.asarray(
            data["images"])) * 0.7 + 0.05, -1.0, 1.0)

    store_dir = tempfile.mkdtemp(prefix="eval_ab_store_")
    plane = EvalPlane(cfg={"evaluation": {"extractor": "patch"}},
                      store_dir=store_dir)
    extractor = make_patch_extractor()
    # compile outside the timed legs: cold must measure the reference
    # RECOMPUTE, not XLA compile time
    np.asarray(extractor(jnp.zeros((bs, 299, 299, 3), jnp.float32)))

    legs = {}
    for leg, step in (("cold", 1), ("warm", 2)):
        r = plane.run_sweep(loader, "images", "fake_images", extractor,
                            gen_fn, step=step, dataset_name="bench_synth",
                            resolution=f"{hw[0]}x{hw[1]}",
                            extractor_tag="patch-v1:g8")
        legs[leg] = {"fid": round(r["fid"], 4),
                     "time_to_fid_ms": round(r["time_to_fid_ms"], 2),
                     "ref_cache_hit": r["ref_cache_hit"]}
    assert legs["warm"]["ref_cache_hit"] and \
        not legs["cold"]["ref_cache_hit"], \
        "warm leg missed the reference store (or cold leg hit a stale one)"
    speedup_pct = (legs["cold"]["time_to_fid_ms"]
                   / max(legs["warm"]["time_to_fid_ms"], 1e-6)
                   - 1.0) * 100.0
    payload = {
        "time_to_fid_warm_ms": legs["warm"]["time_to_fid_ms"],
        "eval_ab": {
            "platform": jax.devices()[0].platform,
            "devices": devices,
            "extractor": "patch",
            "batches": batches,
            "batch_size": bs,
            "resolution": f"{hw[0]}x{hw[1]}",
            "cold": legs["cold"],
            "warm": legs["warm"],
            "warm_speedup_pct": round(speedup_pct, 2),
            "leg": _eval_leg(tm),
        },
    }
    _merge_evalbench(payload)
    print(json.dumps({
        "metric": "eval_ref_store_warm_speedup_pct",
        "value": round(speedup_pct, 2),
        "unit": "pct",
        "vs_baseline": None,
    }))
    return payload


def _merge_servebench(extra):
    """Merge keys into SERVEBENCH.json without clobbering existing rows."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "SERVEBENCH.json")
    book = {}
    if os.path.exists(path):
        with open(path) as f:
            book = json.load(f)
    book.update(extra)
    with open(path, "w") as f:
        json.dump(book, f, indent=1)


def run_serving_ab(hw_buckets=((64, 64), (96, 96)), batch_sizes=(1, 4)):
    """Serving cold-vs-warm A/B (ISSUE 19 acceptance record): the same
    bucketed request trace driven through TWO ServingEngine pools —
    cold (first request pays the jit compile, later buckets compile
    mid-trace) and warm (``engine.warm()`` AOT-compiles the full
    (bucket x batch-size) table first) — recording both legs' TTFI
    (time-to-first-image), sustained p50/p99, bucket_hit_rate and
    pad_waste_frac into SERVEBENCH.json. The tiny SPADE width keeps
    the leg CPU-feasible; the speedup is compile-vs-dispatch, which
    the width only scales in the cold leg's favor."""
    import time as _time

    import jax

    from imaginaire_tpu.config import Config
    from imaginaire_tpu.registry import resolve
    from imaginaire_tpu.serving import ServeRequest, ServingEngine

    tm = _bench_telemetry()
    cfg = Config()
    cfg.trainer.type = "imaginaire_tpu.trainers.spade"
    cfg.trainer.gan_mode = "hinge"
    cfg.trainer.loss_weight = {"gan": 1.0, "feature_matching": 10.0,
                               "kl": 0.05, "perceptual": 10.0}
    cfg.trainer.perceptual_loss = {
        "mode": "vgg19", "layers": ["relu_1_1", "relu_2_1"],
        "weights": [0.5, 1.0], "allow_random_init": True}
    cfg.gen = {
        "type": "imaginaire_tpu.models.generators.spade",
        "style_dims": 16, "num_filters": 4, "kernel_size": 3,
        "weight_norm_type": "spectral",
        "global_adaptive_norm_type": "instance",
        "activation_norm_params": {"num_filters": 4, "kernel_size": 3,
                                   "activation_norm_type": "instance",
                                   "weight_norm_type": "none",
                                   "separate_projection": False},
        "style_enc": {"num_filters": 4, "kernel_size": 3},
    }
    cfg.dis = {
        "type": "imaginaire_tpu.models.discriminators.spade",
        "num_filters": 4, "max_num_filters": 16, "num_discriminators": 2,
        "num_layers": 2, "weight_norm_type": "spectral",
    }
    cfg.data = {
        "name": "serve_bench", "type": "imaginaire_tpu.data.paired_images",
        "input_types": [
            {"images": {"num_channels": 3, "normalize": True}},
            {"seg_maps": {"num_channels": 4, "is_mask": True,
                          "use_dont_care": True,
                          "interpolator": "NEAREST"}},
        ],
        "input_image": ["images"],
        "input_labels": ["seg_maps"],
        "train": {"batch_size": 1,
                  "augmentations": {"random_crop_h_w": "256, 256"}},
    }
    cfg.serving.buckets = [list(hw) for hw in hw_buckets]
    cfg.serving.batch_sizes = list(batch_sizes)

    trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
    rng0 = np.random.RandomState(0)
    h0, w0 = hw_buckets[0]
    init_batch = {
        "images": rng0.rand(1, h0, w0, 3).astype(np.float32) * 2 - 1,
        "label": (rng0.rand(1, h0, w0, 5) > 0.8).astype(np.float32),
    }
    example = trainer.start_of_iteration(dict(init_batch), 0)

    def req(rng, seed, hw):
        h, w = hw
        return ServeRequest(
            data={"label": rng.rand(1, h, w, 5).astype(np.float32),
                  "images": np.zeros((1, h, w, 3), np.float32)},
            seed=seed)

    # mixed trace: both buckets, full bs=4 chunks, bs=1 remainders and
    # padded partials — the bucketing/padding story, not one hot lane
    rounds = [(hw_buckets[0], 4), (hw_buckets[1], 2), (hw_buckets[0], 3),
              (hw_buckets[1], 4), (hw_buckets[0], 1), (hw_buckets[1], 3),
              (hw_buckets[0], 4), (hw_buckets[1], 1)]
    n_requests = sum(k for _, k in rounds)

    legs = {}
    for leg in ("cold", "warm"):
        engine = ServingEngine(cfg, trainer=trainer)
        engine.register_example(example)
        engine.initialize(example_batch=init_batch)
        warm_s = None
        if leg == "warm":
            t0 = _time.perf_counter()
            engine.warm()
            warm_s = _time.perf_counter() - t0
        rng = np.random.RandomState(19)
        # TTFI: one bs=1 request; cold pays the jit compile here
        t0 = _time.perf_counter()
        engine.serve([req(rng, 0, hw_buckets[0])])
        ttfi_ms = (_time.perf_counter() - t0) * 1e3
        seed = 1
        for hw, k in rounds:
            batch = [req(rng, seed + i, hw) for i in range(k)]
            seed += k
            engine.serve(batch)
        st = engine.stats()
        legs[leg] = {
            "ttfi_ms": round(ttfi_ms, 2),
            "warm_table_s": round(warm_s, 2) if warm_s else None,
            "p50_ms": round(st["p50_ms"], 2),
            "p99_ms": round(st["p99_ms"], 2),
            "bucket_hit_rate": st["bucket_hit_rate"],
            "pad_waste_frac": round(st["pad_waste_frac"], 4),
        }
    speedup = legs["cold"]["ttfi_ms"] / max(legs["warm"]["ttfi_ms"], 1e-6)
    assert speedup >= 5.0, (
        f"warm pool must beat cold first-request compile >=5x, got "
        f"{speedup:.1f}x ({legs})")
    payload = {
        "serving_warm_ttfi_ms": legs["warm"]["ttfi_ms"],
        "serving_ab": {
            "platform": jax.devices()[0].platform,
            "devices": len(jax.devices()),
            "width": "tiny-nf4",
            "buckets": [f"{h}x{w}" for h, w in hw_buckets],
            "batch_sizes": list(batch_sizes),
            "requests": 1 + n_requests,
            "cold": legs["cold"],
            "warm": legs["warm"],
            "warm_ttfi_speedup_x": round(speedup, 1),
            "leg": _serving_leg(tm),
        },
    }
    _merge_servebench(payload)
    print(json.dumps({
        "metric": "serving_warm_ttfi_speedup_x",
        "value": round(speedup, 1),
        "unit": "x",
        "vs_baseline": None,
    }))
    return payload


def run_pipeline_ab(width="unit", hw=(256, 512), bs=1, seq_len=4, iters=4):
    """Software-pipelined dispatch A/B (ISSUE 14 acceptance record):
    the same vid2vid recipe driven three ways — sequential per-frame
    loop (trainer.pipeline disabled; the depth-0 meter still runs so
    the before/after dispatch-gap table shares one instrument),
    pipelined dispatch (depth 2, loop invariants hoisted), and the
    demoted whole-rollout scan — recording every variant's frames/s
    plus both dispatch-gap/overlap meters into VIDBENCH.json under
    ``pipelined_ab``. ``--width unit`` runs the 64x64 unit-test recipe
    (CPU-feasible smoke; on a single local device the rollout is
    compute-bound, so parity is the expected result and the meters are
    the signal); ``zoo`` the cityscapes recipe (run_vid2vid wires the
    same A/B into the headline leg at the bench operating point, where
    the host's dispatch latency is the cost being hidden)."""
    import jax
    import jax.numpy as jnp

    tm = _bench_telemetry()
    leg_knobs = {
        "sequential": {"pipeline": {"enabled": False}},
        "pipelined": {"pipeline": {"enabled": True, "depth": 2,
                                   "overlap_collectives": True}},
        "rollout_scan": {"pipeline": {"enabled": False},
                         "rollout_scan": True},
    }

    def build(leg):
        knobs = leg_knobs[leg]
        if width == "unit":
            from imaginaire_tpu.config import Config
            from imaginaire_tpu.registry import resolve

            cfg = Config(os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "configs",
                "unit_test", "vid2vid_street.yaml"))
            cfg.trainer.perceptual_loss.layers = ["relu_1_1", "relu_2_1"]
            cfg.trainer.perceptual_loss.weights = [0.5, 1.0]
            cfg.dis.image.num_discriminators = 1
            cfg.trainer.rollout_scan = bool(knobs.get("rollout_scan"))
            cfg.trainer.pipeline = dict(knobs["pipeline"])
            trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
            rng = np.random.RandomState(0)
            data = {
                "images": rng.rand(bs, seq_len, 64, 64, 3).astype(
                    np.float32) * 2 - 1,
                "label": (rng.rand(bs, seq_len, 64, 64, 12) > 0.9).astype(
                    np.float32),
            }
            return trainer, data
        trainer, label_ch = build_vid2vid(
            True, hw, rollout_scan=bool(knobs.get("rollout_scan")),
            pipeline=knobs["pipeline"])
        return trainer, vid2vid_batch(bs, seq_len, label_ch,
                                      h=hw[0], w=hw[1])

    rates, meters = {}, {}
    for leg in ("sequential", "pipelined", "rollout_scan"):
        jax.clear_caches()
        trainer, data = build(leg)
        trainer.init_state(jax.random.PRNGKey(0), data)

        def sync():
            # the last step's outputs: the state both updates wrote
            jax.block_until_ready(trainer.state)

        for i in range(2):  # compile both per-frame programs + warm
            batch = trainer.start_of_iteration(dict(data), i)
            trainer.dis_update(batch)
            trainer.gen_update(batch)
        sync()
        tm.reset_window()
        t0 = time.time()
        for i in range(iters):
            batch = trainer.start_of_iteration(dict(data), i)
            trainer.dis_update(batch)
            trainer.gen_update(batch)
            tm.step_complete(i, items=bs * seq_len)
        sync()
        rates[leg] = bs * seq_len * iters / (time.time() - t0)
        meters[leg] = _pipeline_leg(tm)  # this leg's LAST rollout
        trainer.state = None

    speedup_pct = (rates["pipelined"] / rates["sequential"] - 1.0) * 100.0
    payload = {"pipelined_ab": {
        "width": width,
        "platform": jax.devices()[0].platform,
        "sequential_fps": round(rates["sequential"], 3),
        "pipelined_fps": round(rates["pipelined"], 3),
        "rollout_scan_fps": round(rates["rollout_scan"], 3),
        "pipelined_vs_sequential_pct": round(speedup_pct, 2),
        "winning_variant": max(rates, key=rates.get),
        "sequential_dispatch_gap_ms":
            meters["sequential"]["dispatch_gap_ms"],
        "pipelined_dispatch_gap_ms":
            meters["pipelined"]["dispatch_gap_ms"],
        "sequential_overlap_ratio": meters["sequential"]["overlap_ratio"],
        "pipelined_overlap_ratio": meters["pipelined"]["overlap_ratio"],
        "pipeline_depth": meters["pipelined"]["pipeline_depth"],
        "iters": iters,
    }}
    _merge_vidbench(payload)
    print(json.dumps({
        "metric": "vid2vid_pipelined_vs_sequential_speedup_pct",
        "value": round(speedup_pct, 2),
        "unit": "pct",
        "vs_baseline": None,
    }))
    return payload


def run_vid2vid(seq_len=4):
    """Steady-state frames/sec of the interleaved per-frame rollout.

    The reference publishes no vid2vid throughput numbers, so
    vs_baseline is null; the number is tracked round-over-round
    (BASELINE.json tracked-config list; ref timer semantics
    trainers/base.py:723-787). Legs sweep (bs, flow-teacher); the
    ``_noteacher`` metric marks the warp-consistency fallback used when
    the FlowNet2 teacher cascade does not compile."""
    import jax
    import jax.numpy as jnp

    tm = _bench_telemetry()
    last_error = None
    trainer = data = None
    # the full 512x1024 shape is tried first; no 512x1024 vid2vid
    # program compiled on the installation of 2026-08-01 (not retried),
    # so the sweep degrades to 256x512 with an honest metric suffix
    # rather than reporting nothing
    legs = ((2, True, (512, 1024)), (2, True, (256, 512)),
            (1, True, (256, 512)), (2, False, (256, 512)),
            (1, False, (256, 512)))
    for bs, flow_teacher, hw in legs:
        try:
            # drop the previous leg's device state BEFORE building the
            # next trainer — otherwise old + new HBM must coexist and a
            # smaller batch can OOM spuriously
            if trainer is not None:
                trainer.state = None
            trainer = data = None
            jax.clear_caches()
            # sequential per-frame baseline first (pipeline disabled):
            # the A/B reference the pipelined variant must beat, and the
            # headline stays intact if the pipelined leg fails
            trainer, label_ch = build_vid2vid(flow_teacher, hw,
                                              pipeline={"enabled": False})
            xla_mark = _xla_mark()
            data = jax.device_put(jax.tree_util.tree_map(
                np.asarray,
                vid2vid_batch(bs, seq_len, label_ch, h=hw[0], w=hw[1])))
            jax.block_until_ready(data)
            trainer.init_state(jax.random.PRNGKey(0), data)

            def sync():
                # the last step's outputs: the state both updates wrote
                jax.block_until_ready(trainer.state)

            for _ in range(2):  # compile both per-frame programs + warm
                trainer.dis_update(data)
                g_losses = trainer.gen_update(data)
            sync()
            bad = [k for k, v in g_losses.items()
                   if not np.isfinite(float(jnp.asarray(v)))]
            if bad:
                raise SystemExit(f"non-finite losses at bs={bs}: {bad}")
            iters = 4
            tm.reset_window()
            t0 = time.time()
            for i in range(iters):
                trainer.dis_update(data)
                trainer.gen_update(data)
                tm.step_complete(i, items=bs * seq_len)
            sync()
            dt = time.time() - t0
            leg_telemetry = _leg_summary(tm, xla_mark, trainer=trainer)
            frames_per_sec = bs * seq_len * iters / dt
            # software-pipelined dispatch A/B (ISSUE 14): same recipe,
            # same programs, deferred completion polls. Measured second
            # so a pipeline-side failure can't cost the baseline number.
            pipelined_frames_per_sec = None
            pipelined_telemetry = None
            try:
                trainer.state = None
                trainer = None
                jax.clear_caches()
                tm.reset_window()
                trainer, _ = build_vid2vid(
                    flow_teacher, hw,
                    pipeline={"enabled": True, "depth": 2,
                              "overlap_collectives": True})
                trainer.init_state(jax.random.PRNGKey(0), data)
                for _ in range(2):
                    trainer.dis_update(data)
                    trainer.gen_update(data)
                sync()
                tm.reset_window()
                t0 = time.time()
                for i in range(iters):
                    trainer.dis_update(data)
                    trainer.gen_update(data)
                    tm.step_complete(i, items=bs * seq_len)
                sync()
                pipelined_frames_per_sec = bs * seq_len * iters / (
                    time.time() - t0)
                pipelined_telemetry = _leg_summary(tm, trainer=trainer)
            except Exception as e:
                print(f"# pipelined leg failed: {e!r}", flush=True)
            # same recipe with the whole-rollout scan tail
            # (trainer.rollout_scan) for the head-to-head record;
            # measured last so a scan-side failure can't cost the
            # baseline number (PROFILE.md Round 5: the known loser,
            # kept in the record)
            scan_frames_per_sec = None
            try:
                trainer.state = None
                trainer = None
                jax.clear_caches()
                trainer, _ = build_vid2vid(flow_teacher, hw,
                                           rollout_scan=True,
                                           pipeline={"enabled": False})
                trainer.init_state(jax.random.PRNGKey(0), data)
                for _ in range(2):
                    trainer.dis_update(data)
                    trainer.gen_update(data)
                sync()
                t0 = time.time()
                for _ in range(iters):
                    trainer.dis_update(data)
                    trainer.gen_update(data)
                sync()
                scan_frames_per_sec = bs * seq_len * iters / (
                    time.time() - t0)
            except Exception as e:
                print(f"# rollout_scan leg failed: {e!r}", flush=True)

            # the metric key stays stable round-over-round (ADVICE r5:
            # a _scan rename would break the tracked time series); the
            # winning variant is a separate field, both raw fps recorded
            metric = (f"vid2vid_{hw[0]}x{hw[1]}_train_frames_per_sec"
                      "_per_chip")
            if not flow_teacher:
                metric += "_noteacher"
            best = frames_per_sec
            winning_variant = "per_frame_loop"
            if pipelined_frames_per_sec and pipelined_frames_per_sec > best:
                best = pipelined_frames_per_sec
                winning_variant = "pipelined"
            if scan_frames_per_sec and scan_frames_per_sec > best:
                best = scan_frames_per_sec
                winning_variant = "rollout_scan"
            payload = {
                "metric": metric,
                "value": round(best, 3),
                "unit": "frames/sec/chip",
                "vs_baseline": None,
            }
            with open(os.path.join(os.path.dirname(
                    os.path.abspath(__file__)), "VIDBENCH.json"), "w") as f:
                json.dump(dict(payload, batch_size=bs, seq_len=seq_len,
                               flow_teacher=flow_teacher,
                               winning_variant=winning_variant,
                               per_frame_loop_fps=round(frames_per_sec, 3),
                               pipelined_fps=(
                                   round(pipelined_frames_per_sec, 3)
                                   if pipelined_frames_per_sec else None),
                               pipelined_telemetry=pipelined_telemetry,
                               rollout_scan_fps=(
                                   round(scan_frames_per_sec, 3)
                                   if scan_frames_per_sec else None),
                               per_frame_step_ms=round(
                                   dt * 1e3 / (bs * seq_len * iters), 2),
                               leg_duration_s=round(dt, 3),
                               leg_telemetry=leg_telemetry),
                          f, indent=1)
            print(json.dumps(payload))
            # teacher-amortization A/B at the winning operating point
            # (best-effort: an A/B failure must not cost the headline)
            if flow_teacher:
                try:
                    trainer.state = None
                    trainer = None
                    jax.clear_caches()
                    run_teacher_ab(width="zoo", hw=hw, bs=bs,
                                   seq_len=seq_len)
                except Exception as e:  # noqa: BLE001
                    print(f"# teacher A/B legs failed: {e!r}", flush=True)
            return
        except Exception as e:  # OOM / compiler cap -> next leg
            last_error = e
            continue
    raise SystemExit(f"vid2vid bench failed at all batch sizes: "
                     f"{last_error}")


def run_diag_ab(width="unit", iters=10):
    """Diagnostics-overhead A/B (ISSUE 3 acceptance): the same SPADE
    training loop with training-health auditing on (the shipping
    default: every_n_steps=10, in-graph non-finite guard + finite-flag
    poll) vs fully off. Prints one JSON line with the overhead pct and
    records both raw rates in DIAGBENCH.json. Separate trainers per arm:
    the step *programs* differ (the audit is traced in), so this is the
    honest comparison — program + host-side monitor cost together."""
    import jax
    import jax.numpy as jnp

    build = build_unit if width == "unit" else build_zoo
    rates = {}
    for arm, enabled in (("diag_on", True), ("diag_off", False)):
        jax.clear_caches()
        trainer, label_ch = build()
        trainer.cfg.diagnostics.enabled = enabled
        from imaginaire_tpu.diagnostics import HealthMonitor

        trainer.diag = HealthMonitor(trainer.cfg)
        bs = 8
        data = jax.device_put(
            jax.tree_util.tree_map(np.asarray, batch_of(bs, label_ch)))
        jax.block_until_ready(data)
        trainer.init_state(jax.random.PRNGKey(0), data)

        def sync():
            # the last step's outputs: the state both updates wrote
            jax.block_until_ready(trainer.state)

        for _ in range(2):
            trainer.dis_update(data)
            trainer.gen_update(data)
        sync()
        t0 = time.time()
        for _ in range(iters):
            trainer.dis_update(data)
            trainer.gen_update(data)
        sync()
        rates[arm] = bs * iters / (time.time() - t0)
        trainer.state = None
    overhead_pct = (rates["diag_off"] / rates["diag_on"] - 1.0) * 100.0
    payload = {
        "metric": f"spade_diagnostics_overhead_pct_{width}",
        "value": round(overhead_pct, 2),
        "unit": "pct",
        "vs_baseline": None,
    }
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "DIAGBENCH.json"), "w") as f:
        json.dump(dict(payload,
                       imgs_per_sec_diag_on=round(rates["diag_on"], 3),
                       imgs_per_sec_diag_off=round(rates["diag_off"], 3),
                       every_n_steps=10, iters=iters), f, indent=1)
    print(json.dumps(payload))


def batch_of(bs, label_ch):
    # int label map, one-hot expanded on device inside the jitted step —
    # ships ~KB/img to the chip instead of ~48MB of one-hot floats.
    rng = np.random.RandomState(0)
    return {
        "images": rng.rand(bs, 256, 256, 3).astype(np.float32) * 2 - 1,
        "label": rng.randint(0, label_ch, (bs, 256, 256)).astype(np.int32),
    }


def _onehot_label(rng, shape, label_ch):
    lab = np.zeros(shape + (label_ch,), np.float32)
    idx = rng.randint(0, label_ch, shape)
    np.put_along_axis(lab, idx[..., None], 1.0, axis=-1)
    return lab


def _sidecar(model, payload, extra):
    """Record the winning leg in FAMILYBENCH.json keyed by model."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "FAMILYBENCH.json")
    book = {}
    if os.path.exists(path):
        with open(path) as f:
            book = json.load(f)
    book[model] = dict(payload, **extra)
    with open(path, "w") as f:
        json.dump(book, f, indent=1)


def _project_cfg(rel, hw=None, hw_keys=("random_crop_h_w",
                                        "center_crop_h_w", "resize_h_w")):
    """Load a shipped project config with random-init weight escapes and
    an optional spatial override (metric names flag non-native sizes)."""
    from imaginaire_tpu.config import Config, cfg_get

    cfg = Config(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "configs", "projects", rel))
    if cfg_get(cfg.trainer, "perceptual_loss", None) is not None:
        cfg.trainer.perceptual_loss.allow_random_init = True
        cfg.trainer.perceptual_loss.pop("weights_path", None)
    if cfg_get(cfg, "flow_network", None) is not None:
        cfg.flow_network.allow_random_init = True
        cfg.flow_network.pop("weights_path", None)
    if hw is not None:
        hw_str = f"{hw[0]}, {hw[1]}"
        for split in ("train", "val"):
            aug = cfg.data[split].augmentations
            aug.pop("resize_smallest_side", None)
            for key in hw_keys:
                aug.pop(key, None)
            aug.resize_h_w = hw_str
        if cfg_get(cfg.data, "output_h_w", None) is not None:
            cfg.data.output_h_w = hw_str
    return cfg


def _family_time(trainer, data, iters):
    """Warm both step programs, guard finiteness, return seconds/iter."""
    import jax
    import jax.numpy as jnp

    for _ in range(2):
        trainer.dis_update(data)
        g_losses = trainer.gen_update(data)
    jax.block_until_ready(trainer.state)
    bad = [k for k, v in g_losses.items()
           if not np.isfinite(float(jnp.asarray(v)))]
    if bad:
        raise SystemExit(f"non-finite losses: {bad}")
    t0 = time.time()
    for _ in range(iters):
        trainer.dis_update(data)
        trainer.gen_update(data)
    jax.block_until_ready(trainer.state)
    return (time.time() - t0) / iters


def run_family(model):
    """Tracked-config bench legs beyond spade/vid2vid (BASELINE.json:
    pix2pixHD Cityscapes, MUNIT AFHQ, fs_vid2vid FaceForensics). Each
    sweeps (bs, hw) down from the faithful recipe shape to what
    compiles; the metric name carries the actual shape. One JSON line;
    winning leg recorded in FAMILYBENCH.json."""
    import jax
    from imaginaire_tpu.registry import resolve
    from imaginaire_tpu.utils.data import get_paired_input_label_channel_number

    rng = np.random.RandomState(0)
    if model == "pix2pixHD":
        rel = "pix2pixHD/cityscapes/bf16.yaml"
        legs = ((2, (512, 1024)), (1, (512, 1024)), (2, (256, 512)),
                (1, (256, 512)))

        def make(bs, hw):
            cfg = _project_cfg(rel, hw if hw != (512, 1024) else None)
            trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
            n = get_paired_input_label_channel_number(cfg.data)
            data = {"images": rng.rand(bs, *hw, 3).astype(
                        np.float32) * 2 - 1,
                    "label": _onehot_label(rng, (bs,) + hw, n)}
            return trainer, data, bs
    elif model == "munit":
        rel = "munit/afhq_dog2cat/bf16.yaml"
        legs = ((4, (256, 256)), (2, (256, 256)), (1, (256, 256)))

        def make(bs, hw):
            cfg = _project_cfg(rel)  # native 256 crop
            trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
            data = {"images_a": rng.rand(bs, *hw, 3).astype(
                        np.float32) * 2 - 1,
                    "images_b": rng.rand(bs, *hw, 3).astype(
                        np.float32) * 2 - 1}
            return trainer, data, bs
    elif model == "funit":
        rel = "funit/animal_faces/base64_bs8_class119.yaml"
        legs = ((8, (256, 256)), (4, (256, 256)), (1, (256, 256)))

        def make(bs, hw):
            cfg = _project_cfg(rel)  # native 256 crop
            trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
            n_cls = int(cfg.dis.num_classes)
            data = {"images_content": rng.rand(bs, *hw, 3).astype(
                        np.float32) * 2 - 1,
                    "images_style": rng.rand(bs, *hw, 3).astype(
                        np.float32) * 2 - 1,
                    "labels_content": rng.randint(
                        0, n_cls, (bs,)).astype(np.int32),
                    "labels_style": rng.randint(
                        0, n_cls, (bs,)).astype(np.int32)}
            return trainer, data, bs
    elif model == "fs_vid2vid":
        rel = "fs_vid2vid/faceForensics/bf16.yaml"
        seq, K = 4, 1
        legs = ((3, (512, 512)), (1, (512, 512)), (3, (256, 256)),
                (1, (256, 256)))

        def make(bs, hw):
            cfg = _project_cfg(rel, hw if hw != (512, 512) else None)
            trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
            n = get_paired_input_label_channel_number(cfg.data)
            lab = _onehot_label(rng, (bs, seq) + hw, n)
            data = {"images": rng.rand(bs, seq, *hw, 3).astype(
                        np.float32) * 2 - 1,
                    "label": lab,
                    "ref_images": rng.rand(bs, K, *hw, 3).astype(
                        np.float32) * 2 - 1,
                    "ref_labels": lab[:, :K]}
            return trainer, data, bs * seq
    else:
        raise SystemExit(f"unknown family {model}")

    last_error = None
    trainer = None
    for bs, hw in legs:
        try:
            if trainer is not None:
                trainer.state = None
            trainer = None
            jax.clear_caches()
            trainer, data, units = make(bs, hw)
            data = jax.device_put(jax.tree_util.tree_map(np.asarray, data))
            jax.block_until_ready(data)
            trainer.init_state(jax.random.PRNGKey(0), data)
            dt = _family_time(trainer, data, iters=6)
            unit = ("frames/sec/chip" if model == "fs_vid2vid"
                    else "imgs/sec/chip")
            payload = {
                "metric": f"{model}_{hw[0]}x{hw[1]}_train_"
                          f"{unit.split('/')[0]}_per_sec_per_chip",
                "value": round(units / dt, 3),
                "unit": unit,
                "vs_baseline": None,
            }
            _sidecar(model, payload,
                     {"batch_size": bs, "step_ms": round(dt * 1e3, 2)})
            print(json.dumps(payload))
            return
        except Exception as e:  # OOM / compiler cap -> next leg
            last_error = e
            continue
    raise SystemExit(f"{model} bench failed at all legs: {last_error}")


def _ensure_packed_fixture(n_imgs=64, side=288):
    """The COCO-Stuff-shaped packed fixture (data/fixtures.py), built
    once per process cache under the temp directory."""
    import tempfile

    from imaginaire_tpu.data.fixtures import make_packed_cocostuff_fixture

    return make_packed_cocostuff_fixture(
        os.path.join(tempfile.gettempdir(), "imaginaire_tpu_bench_data"),
        n_imgs=n_imgs, side=side)


class _EpochCycler:
    """Infinite re-iterable over a loader, advancing ``set_epoch`` at
    each wrap — lets the device prefetcher read ahead across epoch
    boundaries so small bench fixtures never starve the timed window."""

    def __init__(self, loader):
        self.loader = loader
        self.epoch = 0

    def __iter__(self):
        while True:
            self.loader.set_epoch(self.epoch)
            for item in self.loader:
                yield item
            self.epoch += 1


def _pipeline_cfg(bs=None):
    from imaginaire_tpu.config import Config

    packed = _ensure_packed_fixture()
    cfg = Config(ZOO_CONFIG)
    cfg.trainer.perceptual_loss.allow_random_init = True
    cfg.trainer.perceptual_loss.pop("weights_path", None)
    for split in ("train", "val"):
        cfg.data[split].roots = [packed]
        cfg.data[split].is_packed = True
    if bs is not None:
        cfg.data.train.batch_size = int(bs)
    return cfg


def _pipeline_ab(cfg, iters=10):
    """One A/B pass at cfg's batch size: the SPADE zoo step fed three
    ways in one run — synchronous pipeline (per-iteration blocking
    to_device, the pre-prefetch baseline), device-prefetched pipeline
    (data.device_prefetch, the shipped default), and the synthetic
    device-resident twin. Returns the rates + prefetcher meters."""
    import jax
    import jax.numpy as jnp

    from imaginaire_tpu.data.device_prefetch import prefetch_settings
    from imaginaire_tpu.data.loader import get_train_and_val_dataloader
    from imaginaire_tpu.registry import resolve
    from imaginaire_tpu.utils.data import get_paired_input_label_channel_number

    bs = int(cfg.data.train.batch_size)
    label_ch = get_paired_input_label_channel_number(cfg.data)
    trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
    xla_mark = _xla_mark()  # all three feed legs share one program set
    train_loader, _ = get_train_and_val_dataloader(cfg)
    cycler = _EpochCycler(train_loader)

    def steps(data, n, sync=True):
        for _ in range(n):
            trainer.dis_update(data)
            g_losses = trainer.gen_update(data)
        if sync:
            jax.block_until_ready(trainer.state)
        return g_losses

    tm = _bench_telemetry()

    def measure(feed_iter, warm=2):
        first = trainer.start_of_iteration(next(feed_iter), 0)
        if trainer.state is None:
            trainer.init_state(jax.random.PRNGKey(0), first)
        g_losses = steps(first, warm)  # compile + warm
        bad = [k for k, v in g_losses.items()
               if not np.isfinite(float(jnp.asarray(v)))]
        if bad:
            raise SystemExit(f"non-finite losses (pipeline leg): {bad}")
        tm.reset_window()
        t0 = time.time()
        for i in range(iters):
            with tm.span("data_wait"):
                batch = next(feed_iter)
            steps(trainer.start_of_iteration(batch, 0), 1, sync=False)
            tm.step_complete(i, items=bs)
        jax.block_until_ready(trainer.state)
        return (bs * iters / (time.time() - t0),
                _leg_summary(tm, trainer=trainer))

    # leg 1 — synchronous pipeline feed (device_prefetch off: raw loader
    # batches through start_of_iteration's blocking to_device)
    sync_iter = iter(cycler)
    sync_rate, sync_tm = measure(sync_iter)
    sync_iter.close()

    # leg 2 — device-prefetched feed: host decode + H2D of the next
    # batches overlap the running step programs
    prefetcher = trainer.data_prefetcher(cycler)
    if prefetcher is cycler:  # data.device_prefetch off in the config
        prefetch_rate, meters, prefetch_tm = sync_rate, {}, sync_tm
    else:
        prefetcher.drain_stats()
        pf_iter = iter(prefetcher)
        prefetch_rate, prefetch_tm = measure(pf_iter, warm=2)
        meters = {name: round(sum(vals) / max(len(vals), 1), 3)
                  for name, vals in prefetcher.drain_stats().items()}
        pf_iter.close()

    # leg 3 — synthetic twin: pre-built device-resident batch (the
    # headline bench's feeding mode, the zero-input-cost ceiling)
    data = jax.device_put(
        jax.tree_util.tree_map(np.asarray, batch_of(bs, label_ch)))
    jax.block_until_ready(data)
    steps(data, 2)
    tm.reset_window()
    t0 = time.time()
    steps(data, iters)
    synth_rate = bs * iters / (time.time() - t0)
    synth_tm = _leg_summary(tm, trainer=trainer)

    parallel_leg = _parallel_leg(trainer)
    trainer.state = None
    _, depth = prefetch_settings(cfg)
    return {
        "batch_size": bs,
        # mesh + per-chip state residency (ISSUE 6)
        "parallel": parallel_leg,
        "pipeline_sync_imgs_per_sec": round(sync_rate, 3),
        "pipeline_prefetch_imgs_per_sec": round(prefetch_rate, 3),
        "synthetic_imgs_per_sec": round(synth_rate, 3),
        "pipeline_overhead_pct": round(
            (synth_rate - prefetch_rate) / synth_rate * 100.0, 2),
        "pipeline_overhead_sync_pct": round(
            (synth_rate - sync_rate) / synth_rate * 100.0, 2),
        "prefetch_depth": depth,
        "data_meters_mean": meters,
        # per-leg wall duration + telemetry summary — the same
        # step-p50/p99 / data_wait-share schema a training run's
        # telemetry.jsonl carries (ISSUE 2 satellite)
        "leg_telemetry": {"sync": sync_tm, "prefetch": prefetch_tm,
                          "synthetic": synth_tm},
        # compile ledger totals for the whole A/B (one shared program
        # set; ISSUE 5) — recompile_count past warmup should be 0
        "xla": _xla_leg(xla_mark),
    }


def run_pipeline_fed():
    """SPADE zoo step fed by the REAL input pipeline — packed-shard
    backend -> augmentor -> threaded loader -> device prefetcher — vs
    the synthetic pre-built-batch twin at the same batch size
    (VERDICT r4 #3), in ONE run: DATABENCH.json tracks
    ``pipeline_overhead_pct`` (prefetch-fed vs synthetic) as a
    first-class regression metric, with the synchronous-feed rate kept
    alongside as the before/after evidence for the transfer overlap.

    Uses the zoo config's own data section (8 workers, is_packed,
    resize/scale/flip/crop augmentations): the dataset ships (B,256,256)
    int seg maps + (B,256,256,1) edge maps and the feed one-hot expands
    on the device (the 48MB/img host one-hot would otherwise dominate
    the loader and the host-to-device link). A second bs8 leg
    records the pipeline-fed number at the throughput-optimum batch
    (PROFILE.md round 4); its failure (compiler cap) degrades to the
    bs4-only record rather than failing the bench."""
    import jax

    from imaginaire_tpu.parallel.mesh import create_mesh, peek_mesh, set_mesh

    # train.py sets the process mesh before its loop; mirror it so the
    # prefetcher commits batches with the real NamedSharding spec
    # instead of its uncommitted no-mesh fallback
    if peek_mesh() is None:
        set_mesh(create_mesh(("data",)))

    base = _pipeline_ab(_pipeline_cfg())

    # bs8: the on-chip throughput optimum (PROFILE.md r4 headline) —
    # a fresh trainer/program set, measured after the bs4 state is freed
    bs8 = None
    try:
        jax.clear_caches()
        bs8 = _pipeline_ab(_pipeline_cfg(bs=8))
    except Exception as e:  # OOM / compiler cap -> bs4-only
        print(f"# bs8 pipeline leg failed: {e!r}", flush=True)

    pipe_rate = base["pipeline_prefetch_imgs_per_sec"]
    payload = {
        "metric": "spade_256_train_imgs_per_sec_per_chip_pipeline_fed",
        "value": pipe_rate,
        "unit": "imgs/sec/chip",
        "vs_baseline": round(pipe_rate / V100_IMGS_PER_SEC, 3),
    }
    cfg = _pipeline_cfg()
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "DATABENCH.json"), "w") as f:
        json.dump(dict(payload, **base,
                       num_workers=int(cfg.data.num_workers),
                       bs8_headline=bs8), f, indent=1)
    print(json.dumps(payload))


def run(trainer, label_ch, batch_sizes, metric):
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    last_error = None
    for bs in batch_sizes:
        try:
            xla_mark = _xla_mark()
            # commit the batch to device once: steady-state throughput is
            # measured on-device (in real training the device prefetcher
            # overlaps H2D with the step; see data/device_prefetch.py
            # and the --data packed A/B)
            data = jax.device_put(
                jax.tree_util.tree_map(np.asarray, batch_of(bs, label_ch)))
            jax.block_until_ready(data)
            trainer.init_state(jax.random.PRNGKey(0), data)

            def sync():
                # the last step's outputs: the state both updates wrote
                jax.block_until_ready(trainer.state)

            # warmup: compile both steps + 1 extra for stabilization
            for _ in range(2):
                d_losses = trainer.dis_update(data)
                g_losses = trainer.gen_update(data)
            sync()
            # a bench number over NaN losses would be meaningless
            bad = [k for k, v in {**(d_losses or {}), **g_losses}.items()
                   if not np.isfinite(float(jnp.asarray(v)))]
            if bad:
                raise SystemExit(
                    f"non-finite losses at bs={bs}: {bad}")
            iters = 10
            t0 = time.time()
            for _ in range(iters):
                trainer.dis_update(data)
                trainer.gen_update(data)
            sync()
            dt = time.time() - t0
            imgs_per_sec = bs * iters / dt
            print(json.dumps({
                "metric": metric,
                "value": round(imgs_per_sec, 3),
                "unit": "imgs/sec/chip",
                "vs_baseline": round(imgs_per_sec / V100_IMGS_PER_SEC, 3),
                "device": {"platform": dev.platform,
                           "kind": dev.device_kind,
                           "count": jax.device_count()},
                "batch_size": bs,
                # per-leg compile cost + recompile tripwire + peak HBM
                # (ISSUE 5); recompile_count must stay 0 post-warmup
                "xla": _xla_leg(xla_mark),
                # mesh + per-chip state residency (ISSUE 6)
                "parallel": _parallel_leg(trainer),
            }))
            return
        except Exception as e:  # OOM etc. -> next smaller batch
            print(f"# bs{bs} leg failed on {dev.device_kind}: {e!r}",
                  flush=True)
            last_error = e
            continue
    raise SystemExit(f"bench failed at all batch sizes: {last_error}")


def _pod_spade_cfg():
    """Tiny spade recipe for the pod-scaling legs: the pod harness runs
    on localhost CPUs (one virtual device per process), so the workload
    must be dryrun-sized — the leg measures multi-process scaling of the
    REAL distributed stack (gloo collectives, global batch assembly),
    not chip throughput."""
    from imaginaire_tpu.config import Config

    cfg = Config()
    cfg.trainer.type = "imaginaire_tpu.trainers.spade"
    cfg.trainer.gan_mode = "hinge"
    cfg.trainer.loss_weight = {"gan": 1.0, "feature_matching": 10.0,
                               "kl": 0.05, "perceptual": 10.0}
    cfg.trainer.perceptual_loss = {
        "mode": "vgg19", "layers": ["relu_1_1", "relu_2_1"],
        "weights": [0.5, 1.0], "allow_random_init": True}
    cfg.gen = {
        "type": "imaginaire_tpu.models.generators.spade",
        "style_dims": 16, "num_filters": 4, "kernel_size": 3,
        "weight_norm_type": "spectral",
        "global_adaptive_norm_type": "instance",
        "activation_norm_params": {"num_filters": 4, "kernel_size": 3,
                                   "activation_norm_type": "instance",
                                   "weight_norm_type": "none",
                                   "separate_projection": False},
        "style_enc": {"num_filters": 4, "kernel_size": 3},
    }
    cfg.dis = {
        "type": "imaginaire_tpu.models.discriminators.spade",
        "num_filters": 4, "max_num_filters": 16, "num_discriminators": 2,
        "num_layers": 2, "weight_norm_type": "spectral",
    }
    cfg.data = {
        "name": "podbench", "type": "imaginaire_tpu.data.paired_images",
        "input_types": [
            {"images": {"num_channels": 3, "normalize": True}},
            {"seg_maps": {"num_channels": 4, "is_mask": True,
                          "use_dont_care": True,
                          "interpolator": "NEAREST"}},
        ],
        "input_image": ["images"],
        "input_labels": ["seg_maps"],
        "train": {"batch_size": 1,
                  "augmentations": {"random_crop_h_w": "256, 256"}},
    }
    cfg.gen_opt.lr = 1e-4
    cfg.dis_opt.lr = 4e-4
    return cfg


def run_pod_child(model, iters=4, warmup=2):
    """One pod process of a pod-scaling leg (``--pod-child``, spawned by
    ``launch_local_pod.py --bench``): join the coordination service,
    build the dryrun-sized workload on the pod-wide 'data' mesh, run the
    real sharded train step, and have rank 0 print ONE JSON row the
    harness folds into its leg-summary JSON."""
    from imaginaire_tpu.parallel import mesh as pmesh

    # must run before the backend initializes — it consumes the
    # harness's IMAGINAIRE_DIST_* contract
    pmesh.maybe_init_distributed_from_env()
    import jax
    import jax.numpy as jnp

    from imaginaire_tpu.parallel.mesh import create_mesh, set_mesh
    from imaginaire_tpu.parallel.sharding import place_committed_batch
    from imaginaire_tpu.registry import resolve

    mesh = create_mesh(("data",))
    set_mesh(mesh)
    n_dev = jax.device_count()
    local_bs = jax.local_device_count()
    rng = np.random.RandomState(jax.process_index())
    seq_len = 1
    if model == "vid2vid":
        from imaginaire_tpu.config import Config

        cfg = Config(os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "configs",
            "unit_test", "vid2vid_street.yaml"))
        cfg.trainer.perceptual_loss.layers = ["relu_1_1", "relu_2_1"]
        cfg.trainer.perceptual_loss.weights = [0.5, 1.0]
        cfg.dis.image.num_discriminators = 1
        trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
        seq_len = 3
        h = w = 64
        lab = (rng.rand(local_bs, seq_len, h, w, 12) > 0.9)
        local = {
            "images": rng.rand(local_bs, seq_len, h, w, 3).astype(
                np.float32) * 2 - 1,
            "label": lab.astype(np.float32),
        }
        unit = "frames/sec"
    else:
        cfg = _pod_spade_cfg()
        trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
        h = w = 256  # the spade up-ladder's minimum generation size
        lab = np.zeros((local_bs, h, w, 5), np.float32)
        idx = rng.randint(0, 5, (local_bs, h, w))
        np.put_along_axis(lab, idx[..., None], 1.0, axis=-1)
        local = {
            "images": rng.rand(local_bs, h, w, 3).astype(np.float32) * 2 - 1,
            "label": lab,
        }
        unit = "imgs/sec"
    # podview over the bench loop (ISSUE 17): every iteration digests
    # (publish + aggregate over the real coordination KV), so the row
    # carries measured skew/straggler/divergence instead of prose
    from imaginaire_tpu.telemetry import podview

    tm = _bench_telemetry()
    podview.configure({
        "enabled": jax.process_count() > 1,
        "digest_every_n_steps": 1,
        "history": 8,
        "divergence": "crc",
        "ewma_rel_threshold": 0.05,
        "stale_after_s": 0.0,  # bench legs never gate on staleness
    })
    with mesh:
        # delegates to place_process_local_batch when multi-process:
        # each process contributes its local rows to the global batch
        data = place_committed_batch(local, mesh=mesh)
        trainer.init_state(jax.random.PRNGKey(0), data)

        def sync():
            # the last step's outputs: the state both updates wrote
            jax.block_until_ready(trainer.state)

        for _ in range(warmup):
            trainer.dis_update(data)
            trainer.gen_update(data)
        sync()
        t0 = time.time()
        for it in range(1, iters + 1):
            t_it = time.time()
            with tm.span("dis_step", step=it):
                trainer.dis_update(data)
            with tm.span("gen_step", step=it):
                trainer.gen_update(data)
            tm.step_complete(it, items=n_dev * seq_len,
                             dur_s=time.time() - t_it)
            podview.get().on_step(it)
        sync()
        dt = time.time() - t0
    items = n_dev * seq_len * iters
    if jax.process_index() == 0:
        pod = _pod_leg(tm)
        print(json.dumps({
            "model": model,
            "value": round(items / dt, 3),
            "unit": unit,
            "process_count": jax.process_count(),
            "device_count": n_dev,
            "iters": iters,
            "step_ms": round(dt * 1e3 / iters, 2),
            "step_skew_ms_p50": pod["step_skew_ms_p50"],
            "straggler_process": pod["straggler_process"],
            "straggler_span": pod["straggler_span"],
            "divergence_count": pod["divergence_count"],
        }), flush=True)


def run_pod_scaling(host_counts=(1, 2, 3), timeout=900.0,
                    models=("spade", "vid2vid")):
    """First real multi-host throughput rows (ISSUE 14): imgs/s (spade)
    and frames/s (vid2vid) vs host count, via the pod harness's clean
    ``--bench`` mode. Each leg spawns N localhost processes with one
    virtual CPU device each — real coordination service, real gloo
    collectives, real global-batch assembly — and records the harness's
    leg-summary JSON. Rows print as JSON lines (-> BENCH tail) and the
    full record lands in PODBENCH.json. Best-effort per leg: a wedged
    pod times out (the harness kills it) and the remaining legs still
    run."""
    import subprocess

    here = os.path.dirname(os.path.abspath(__file__))
    harness = os.path.join(here, "scripts", "launch_local_pod.py")
    book = {"host_counts": list(host_counts), "legs": []}
    # partial reruns (models subset) keep the other models' rows: merge
    # into the existing book rather than clobbering it
    pod_path = os.path.join(here, "PODBENCH.json")
    if os.path.exists(pod_path):
        try:
            with open(pod_path) as f:
                prior = json.load(f)
            book["legs"] = [leg for leg in prior.get("legs", [])
                            if leg.get("model") not in models]
        except (ValueError, OSError):
            pass
    for model in models:
        for n in host_counts:
            cmd = [sys.executable, harness, "--bench",
                   "--num-processes", str(n), "--timeout", str(timeout),
                   "--", "bench.py", "--pod-child", model]
            try:
                res = subprocess.run(
                    cmd, cwd=here, capture_output=True, text=True,
                    timeout=timeout + 120)
                summary = None
                for line in reversed(res.stdout.splitlines()):
                    if line.lstrip().startswith("{"):
                        try:
                            obj = json.loads(line)
                        except ValueError:
                            continue
                        if "pod_bench" in obj:
                            summary = obj["pod_bench"]
                            break
                if summary is None:
                    raise RuntimeError(
                        f"no pod_bench summary (rc={res.returncode}, "
                        f"tail={res.stdout[-300:]!r})")
                rows = summary.get("rows") or []
                rate = rows[0].get("value") if rows else None
                unit = rows[0].get("unit") if rows else None
                leg = {"model": model, "process_count": n,
                       "exit_codes": summary.get("exit_codes"),
                       "wall_s": summary.get("wall_s"),
                       "value": rate, "unit": unit,
                       "rows": rows}
                if rows:
                    # podview verdict (ISSUE 17): skew/straggler/
                    # divergence measured over the leg's digest rounds
                    for key in ("step_skew_ms_p50", "straggler_process",
                                "straggler_span", "divergence_count"):
                        leg[key] = rows[0].get(key)
                book["legs"].append(leg)
                print(json.dumps({
                    "metric": f"pod_scaling_{model}_"
                              f"{'frames' if model == 'vid2vid' else 'imgs'}"
                              "_per_sec",
                    "value": rate,
                    "unit": unit,
                    "vs_baseline": None,
                    "process_count": n,
                    "exit_codes": summary.get("exit_codes"),
                }), flush=True)
            except Exception as e:  # noqa: BLE001 — one leg, not the bench
                print(f"# pod-scaling leg {model} x{n} failed: {e!r}",
                      flush=True)
                book["legs"].append({"model": model, "process_count": n,
                                     "error": repr(e)})
    book["legs"].sort(key=lambda leg: (leg.get("model", ""),
                                       leg.get("process_count", 0)))
    with open(pod_path, "w") as f:
        json.dump(book, f, indent=1)


def main():
    from imaginaire_tpu.utils import compile_cache

    compile_cache.configure()
    parser = argparse.ArgumentParser()
    parser.add_argument("--width", choices=("zoo", "unit"), default="zoo",
                        help="zoo = faithful nf=128 base128_bs4.yaml budget "
                             "(headline); unit = nf=64 unit-test width")
    parser.add_argument("--data", choices=("synthetic", "packed"),
                        default="synthetic",
                        help="synthetic = pre-built device batch (headline); "
                             "packed = feed the SPADE zoo step from the "
                             "real packed-shard backend->augmentor->loader "
                             "pipeline and record the delta (DATABENCH.json)")
    parser.add_argument("--model",
                        choices=("spade", "vid2vid", "pix2pixHD", "munit",
                                 "funit", "fs_vid2vid"),
                        default="spade",
                        help="spade = headline image bench (default); "
                             "vid2vid = cityscapes interleaved rollout "
                             "(VIDBENCH.json); pix2pixHD/munit/"
                             "fs_vid2vid = remaining BASELINE-tracked "
                             "families (FAMILYBENCH.json)")
    parser.add_argument("--diag-ab", action="store_true",
                        help="measure the training-health diagnostics "
                             "overhead (on vs off) on the SPADE step "
                             "at --width and record DIAGBENCH.json")
    parser.add_argument("--teacher-ab", action="store_true",
                        help="vid2vid teacher-amortization A/B only "
                             "(in-graph vs producer-cold vs cache-warm) "
                             "-> VIDBENCH.json teacher_cache_speedup_pct; "
                             "--width unit runs the CPU-feasible 64x64 "
                             "smoke, zoo the cityscapes recipe")
    parser.add_argument("--pipeline-ab", action="store_true",
                        help="vid2vid software-pipelined dispatch A/B "
                             "only (sequential vs pipelined vs "
                             "rollout_scan) -> VIDBENCH.json "
                             "pipelined_ab; --width unit runs the "
                             "CPU-feasible 64x64 smoke, zoo the "
                             "cityscapes recipe")
    parser.add_argument("--eval-ab", action="store_true",
                        help="reference-store cold-vs-warm quality-sweep "
                             "A/B only (ISSUE 18): two identical sweeps "
                             "through the eval plane, first computing the "
                             "reference activations, second reading the "
                             "content-addressed shard back -> "
                             "EVALBENCH.json eval_ab + "
                             "time_to_fid_warm_ms")
    parser.add_argument("--serving-ab", action="store_true",
                        help="serving cold-vs-warm A/B only (ISSUE 19): "
                             "the same bucketed request trace through a "
                             "cold executable pool (first request pays "
                             "the compile) and an AOT-warmed one -> "
                             "SERVEBENCH.json serving_ab + "
                             "serving_warm_ttfi_ms")
    parser.add_argument("--pod-scaling", action="store_true",
                        help="run ONLY the pod-scaling legs (ISSUE 14): "
                             "imgs/s + frames/s at 1/2/3 localhost pod "
                             "processes via launch_local_pod.py --bench "
                             "-> PODBENCH.json")
    parser.add_argument("--pod-child", default=None,
                        choices=("spade", "vid2vid"),
                        help="internal: run as one pod-scaling child "
                             "process (spawned by launch_local_pod.py "
                             "--bench; expects IMAGINAIRE_DIST_* env)")
    args = parser.parse_args()
    if args.pod_child:
        run_pod_child(args.pod_child)
        return
    if args.pod_scaling:
        run_pod_scaling()
        return
    if args.serving_ab:
        run_serving_ab()
        return
    if args.eval_ab:
        run_eval_ab()
        return
    if args.pipeline_ab:
        run_pipeline_ab(width=args.width if args.width == "unit" else "zoo")
        return
    if args.teacher_ab:
        run_teacher_ab(width=args.width if args.width == "unit" else "zoo",
                       hw=(256, 512))
        return
    if args.diag_ab:
        run_diag_ab(width=args.width)
        return
    if args.data == "packed":
        if args.model != "spade":
            raise SystemExit("--data packed is the SPADE pipeline leg")
        run_pipeline_fed()
        return
    if args.model == "vid2vid":
        run_vid2vid()
        return
    if args.model in ("pix2pixHD", "munit", "funit", "fs_vid2vid"):
        run_family(args.model)
        return
    if args.width == "zoo":
        # pod-scaling rows FIRST (ISSUE 14: the first real multi-host
        # throughput numbers in BENCH) so the headline metric stays the
        # LAST JSON line — the tracked time series must not change its
        # anchor. Best-effort: the localhost pod legs run on CPU and a
        # failure must never cost the chip headline.
        try:
            run_pod_scaling()
        except Exception as e:  # noqa: BLE001
            print(f"# pod-scaling legs failed: {e!r}", flush=True)
        trainer, label_ch = build_zoo()
        # nf=128 is ~4x the unit-width FLOPs; sweep down on OOM
        run(trainer, label_ch, (16, 8, 4, 2, 1),
            "spade_256_train_imgs_per_sec_per_chip")
    else:
        trainer, label_ch = build_unit()
        # measured on v5e: throughput flat in bs (compute-bound); 24 optimum
        run(trainer, label_ch, (24, 16, 8, 4, 2, 1),
            "spade_256_train_imgs_per_sec_per_chip_nf64")


if __name__ == "__main__":
    main()
