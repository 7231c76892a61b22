#!/usr/bin/env python
"""Closed/open-loop serving load harness CLI (ISSUE 20).

Drives the tiny-SPADE serving engine with Poisson offered load at a
sweep of rates, plus an optional closed-loop capacity point and a
streaming burst, and prints the offered-load-vs-latency curve under
``"loadgen"``:

    per point: offered_rps, achieved_rps, p50_ms, p99_ms,
               queue_depth_max/mean, rejected, slo_burn_rate

The engine runs with tracing on (sample_rate 1.0 by default) and the
SLO budget armed, so the run's in-memory telemetry carries ``trace/``
records and ``serve/slo/*`` counters; ``--telemetry-out`` dumps them
to a jsonl for ``scripts/telemetry_report.py --serving`` /
``scripts/check_run_health.py --max-slo-burn-rate``.

Usage:
    python scripts/serving_loadgen.py                      # default sweep
    python scripts/serving_loadgen.py --rates 2,6,12 --duration 4
    python scripts/serving_loadgen.py --slo-p99-ms 150 --streams 2
    python scripts/serving_loadgen.py --telemetry-out /tmp/t.jsonl
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _tiny_spade_cfg(hw_buckets, batch_sizes, slo_p99_ms, availability,
                    window, sample_rate, max_queue):
    """A tiny SPADE width (nf 4) with the ISSUE-20 serving knobs
    (trace sampling + SLO budget) set."""
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
        "name": "serve_loadgen",
        "type": "imaginaire_tpu.data.paired_images",
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
    cfg.serving.trace_sample_rate = float(sample_rate)
    if max_queue is not None:
        cfg.serving.max_queue = int(max_queue)
    if slo_p99_ms is not None:
        cfg.serving.slo.p99_ms = float(slo_p99_ms)
        cfg.serving.slo.availability = float(availability)
        cfg.serving.slo.window = int(window)
    return cfg


def build_engine(hw_buckets, batch_sizes, slo_p99_ms=None,
                 availability=0.999, window=256, sample_rate=1.0,
                 max_queue=None):
    """Warm tiny-SPADE ServingEngine + the {(H, W) -> lane data} map
    the loadgen mixes requests over."""
    from imaginaire_tpu.registry import resolve
    from imaginaire_tpu.serving import ServingEngine

    cfg = _tiny_spade_cfg(hw_buckets, batch_sizes, slo_p99_ms,
                          availability, window, sample_rate, max_queue)
    trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
    rng0 = np.random.RandomState(0)
    h0, w0 = hw_buckets[0]
    init_batch = {
        "images": rng0.rand(1, h0, w0, 3).astype(np.float32) * 2 - 1,
        "label": (rng0.rand(1, h0, w0, 5) > 0.8).astype(np.float32),
    }
    example = trainer.start_of_iteration(dict(init_batch), 0)
    engine = ServingEngine(cfg, trainer=trainer)
    engine.register_example(example)
    engine.initialize(example_batch=init_batch)
    engine.warm()
    lanes = {}
    for h, w in hw_buckets:
        lanes[(h, w)] = {
            "label": rng0.rand(1, h, w, 5).astype(np.float32),
            "images": np.zeros((1, h, w, 3), np.float32),
        }
    return engine, lanes


def main():
    ap = argparse.ArgumentParser(
        description="Offered-load sweep against the tiny-SPADE serving "
                    "engine")
    ap.add_argument("--rates", default="2,6,12",
                    help="comma-separated offered rates (requests/s) "
                         "for the open-loop sweep, lowest first")
    ap.add_argument("--duration", type=float, default=4.0,
                    help="seconds of offered load per sweep point")
    ap.add_argument("--buckets", default="64x64,96x96",
                    help="comma-separated HxW resolution buckets "
                         "(the request mix is uniform over them)")
    ap.add_argument("--batch-sizes", default="1,4",
                    help="comma-separated micro-batch sizes")
    ap.add_argument("--closed-concurrency", type=int, default=0,
                    help="when >0, also run one closed-loop point at "
                         "this concurrency (capacity reference)")
    ap.add_argument("--closed-requests", type=int, default=32,
                    help="total requests for the closed-loop point")
    ap.add_argument("--streams", type=int, default=0,
                    help="when >0, also run a streaming burst with this "
                         "many interleaved StreamSessions")
    ap.add_argument("--frames", type=int, default=4,
                    help="frames per stream in the streaming burst")
    ap.add_argument("--slo-p99-ms", type=float, default=250.0,
                    help="arm the SLO budget at this latency objective "
                         "(<=0 disables the budget)")
    ap.add_argument("--availability", type=float, default=0.999)
    ap.add_argument("--slo-window", type=int, default=256)
    ap.add_argument("--sample-rate", type=float, default=1.0,
                    help="trace sample rate (breaches always emit)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="admission queue bound (overflow = shed load)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--telemetry-out", default=None,
                    help="dump the run's telemetry events (trace/ "
                         "records, serve/slo/* counters) to this jsonl")
    args = ap.parse_args()

    import jax

    from imaginaire_tpu import telemetry
    from imaginaire_tpu.serving import (run_closed_loop, run_load_sweep,
                                        run_stream_burst)

    tm = telemetry.configure(enabled=True, sinks=[],
                             flush_every_n_steps=0, mfu=False)
    hw_buckets = tuple(tuple(int(d) for d in b.split("x"))
                       for b in args.buckets.split(","))
    batch_sizes = tuple(int(b) for b in args.batch_sizes.split(","))
    rates = [float(r) for r in args.rates.split(",")]
    slo_p99 = args.slo_p99_ms if args.slo_p99_ms > 0 else None

    t0 = time.perf_counter()
    engine, lanes = build_engine(
        hw_buckets, batch_sizes, slo_p99_ms=slo_p99,
        availability=args.availability, window=args.slo_window,
        sample_rate=args.sample_rate, max_queue=args.max_queue)
    warm_s = time.perf_counter() - t0

    points = run_load_sweep(engine, rates, args.duration, lanes,
                            seed=args.seed)
    if args.closed_concurrency > 0:
        engine.reset_stats()
        points.append(run_closed_loop(engine, args.closed_concurrency,
                                      args.closed_requests, lanes,
                                      seed=args.seed + len(points)))
    streams = None
    if args.streams > 0:
        sids = [f"loadgen-s{i}" for i in range(args.streams)]
        hw = hw_buckets[0]
        outs = run_stream_burst(engine, sids, args.frames,
                                lanes[hw], seed=args.seed)
        streams = {"streams": len(sids), "frames_each": args.frames,
                   "frames_total": sum(len(v) for v in outs.values())}

    payload = {
        "loadgen": {
            "platform": jax.devices()[0].platform,
            "devices": len(jax.devices()),
            "width": "tiny-nf4",
            "buckets": [f"{h}x{w}" for h, w in hw_buckets],
            "batch_sizes": list(batch_sizes),
            "duration_s_per_point": args.duration,
            "warm_table_s": round(warm_s, 2),
            "slo_p99_ms": slo_p99,
            "curve": points,
            "streams": streams,
        },
    }
    if args.telemetry_out:
        with tm._lock:
            events = list(tm._events)
        with open(args.telemetry_out, "w") as f:
            for ev in events:
                f.write(json.dumps(ev, default=str) + "\n")
        payload["loadgen"]["telemetry_jsonl"] = args.telemetry_out
    print(json.dumps(payload, indent=1, default=str))
    return payload


if __name__ == "__main__":
    main()
