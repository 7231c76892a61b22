#!/usr/bin/env python
"""Perf lab for the SPADE zoo-width training step (VERDICT r3 #1).

Measures, on the real chip:
  - D+G step time and imgs/sec across batch sizes
  - XLA-reported FLOPs of the two step programs (cost analysis)
  - MFU vs the chip's peak bf16 throughput

Usage: python scripts/perf_lab.py [--bs 4,8,16] [--remat none|blocks]
Writes nothing; prints a table. bench.py stays the official number.
"""

from __future__ import annotations

import argparse
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from imaginaire_tpu.telemetry import resolve_peak_flops


def fence(tree):
    jax.block_until_ready(tree)


def time_step(trainer, data, iters=8):
    for _ in range(2):
        trainer.dis_update(data)
        trainer.gen_update(data)
    fence(trainer.state["vars_G"]["params"])
    t0 = time.perf_counter()
    for _ in range(iters):
        trainer.dis_update(data)
        trainer.gen_update(data)
    fence(trainer.state["vars_G"]["params"])
    return (time.perf_counter() - t0) / iters


def step_flops(trainer, data):
    """XLA cost analysis of the jitted D and G step programs."""
    out = {}
    for name, fn in (("dis", trainer._jit_dis_step),
                     ("gen", trainer._jit_gen_step)):
        try:
            lowered = fn.lower(trainer.state, data)
            cost = lowered.compile().cost_analysis()
            if isinstance(cost, list):
                cost = cost[0]
            out[name] = float(cost.get("flops", float("nan")))
        except Exception as e:  # noqa: BLE001
            out[name] = None
            print(f"cost_analysis({name}) failed: {e!s:.100}")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bs", default="4,8,16")
    ap.add_argument("--remat", default=None,
                    help="override cfg.gen.remat; any name in "
                         "imaginaire_tpu.optim.remat.POLICIES "
                         "(none|blocks|dots_saveable|save_nothing)")
    ap.add_argument("--flops-bs", type=int, default=4,
                    help="batch size for the cost-analysis/MFU report")
    args = ap.parse_args()

    import bench

    def build(remat):
        from imaginaire_tpu.config import Config
        from imaginaire_tpu.registry import resolve
        from imaginaire_tpu.utils.data import (
            get_paired_input_label_channel_number,
        )

        cfg = Config(bench.ZOO_CONFIG)
        cfg.trainer.perceptual_loss.allow_random_init = True
        if remat:
            cfg.gen.remat = remat
        trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
        return trainer, get_paired_input_label_channel_number(cfg.data)

    print(f"device: {jax.devices()[0]}", flush=True)
    results = []
    for bs in [int(b) for b in args.bs.split(",")]:
        trainer, label_ch = build(args.remat)
        data = jax.device_put(jax.tree_util.tree_map(
            np.asarray, bench.batch_of(bs, label_ch)))
        jax.block_until_ready(data)
        try:
            trainer.init_state(jax.random.PRNGKey(0), data)
            dt = time_step(trainer, data)
            imgs = bs / dt
            row = (bs, dt * 1e3, imgs)
            print(f"bs={bs}: step={dt * 1e3:.1f} ms  "
                  f"imgs/s={imgs:.2f}", flush=True)
            if bs == args.flops_bs:
                fl = step_flops(trainer, data)
                if all(v is not None for v in fl.values()):
                    total = sum(fl.values())
                    peak, source = resolve_peak_flops()
                    print(f"  flops: dis={fl['dis']:.3e} "
                          f"gen={fl['gen']:.3e} "
                          f"total={total:.3e}/step -> "
                          + (f"MFU={total / dt / peak * 100:.1f}% of "
                             f"{peak / 1e12:.0f} TF/s ({source})"
                             if peak else source), flush=True)
            results.append(row)
        except Exception as e:  # noqa: BLE001
            print(f"bs={bs}: failed ({e!s:.120})", flush=True)
        finally:
            trainer.state = None
    if results:
        best = max(results, key=lambda r: r[2])
        print(f"best: bs={best[0]} imgs/s={best[2]:.2f}")


if __name__ == "__main__":
    main()
