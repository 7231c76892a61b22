#!/usr/bin/env python
"""Microbenchmark: pallas vs jnp/XLA for the native ops, on the device
it runs on.

Prints per (op, impl, shape) median latency and the winner per op, and
with ``--json PATH`` also writes them there. Every ``AUTO_IMPLEMENTATION``
in ops/{resample2d,channelnorm,correlation,spade_modulation}.py is pinned
to the XLA formulation; a pin changes only on rows from a chip run (see
ops/__init__.py).

Shapes are the vid2vid operating points (ref: the reference runs FlowNet2
on 512x1024 cityscapes frames; FlowNetC's cost volume runs at 1/8 res
with 256 channels, third_party/flow_net/flownet2/networks/flownet_c.py).

Timing: each measurement jits a k-iteration chain of ``sum(op(...))``,
waits for it with ``jax.block_until_ready`` and takes the slope between
two chain lengths, so the host's dispatch constant cancels.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

WARMUP = 2
REPEATS = 7
K_SMALL, K_LARGE = 2, 12


def _looped(fn, k):
    """Run ``fn`` k times serialized by a data dependence, so the chain
    can't be parallelized or folded away; returns the accumulated sum.
    Ledgered so the bench compiles carry compile-time counters and the
    graph audit like every other compile site."""
    from imaginaire_tpu.telemetry import xla_obs

    def run(*args):
        def body(_, acc):
            out = fn(args[0] + acc * 1e-30, *args[1:])
            return acc + jnp.sum(out.astype(jnp.float32))

        return jax.lax.fori_loop(0, k, body, jnp.float32(0.0))

    label = getattr(fn, "__name__", None) or "op"
    return xla_obs.compiled_program(f"opsbench/{label}x{k}", run)


def measure(fn, *args):
    """Per-call latency with the host-dispatch constant cancelled: time
    K_SMALL- and K_LARGE-iteration loops and take the slope."""
    times = {}
    for k in (K_SMALL, K_LARGE):
        wrapped = _looped(fn, k)
        for _ in range(WARMUP):
            jax.block_until_ready(wrapped(*args))
        samples = []
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            jax.block_until_ready(wrapped(*args))
            samples.append((time.perf_counter() - t0) * 1e3)
        times[k] = statistics.median(samples)
    # noise can push the slope of a near-free op below zero; a latency
    # can't be negative, and winner sums must not be credited for noise
    return max(0.0, (times[K_LARGE] - times[K_SMALL]) / (K_LARGE - K_SMALL))


def _run_case(cases, op, impl, shape, thunk, *args, extras=None):
    try:
        ms = measure(thunk, *args)
        row = {"op": op, "impl": impl, "shape": list(shape),
               "ms": round(ms, 4)}
        if extras is not None:
            row.update(extras())
    except Exception as e:  # noqa: BLE001 - record compile failures as data
        cases.append({"op": op, "impl": impl, "shape": list(shape),
                      "error": (str(e).splitlines() or [""])[0][:200]})
    else:
        cases.append(row)
    print(cases[-1], flush=True)


def _grad_program_temp_bytes(fn, *args):
    """XLA temp allocation of the op's training-path program
    (fwd + grad wrt every input), from AOT memory_analysis — the axis a
    residual-policy op actually trades on. Latency cannot separate
    implementations whose forward math is identical (spade_modulation
    'jnp' vs 'fused'); their difference is what the backward keeps."""
    def loss(*a):
        return jnp.sum(fn(*a).astype(jnp.float32) ** 2)

    from imaginaire_tpu.telemetry import xla_obs

    grad = xla_obs.compiled_program(
        "opsbench/grad_temp",
        jax.grad(loss, argnums=tuple(range(len(args)))))
    ma = grad.lower(*args).compile().memory_analysis()
    return int(ma.temp_size_in_bytes)


def bench_resample2d(cases):
    from imaginaire_tpu.ops.resample2d import resample2d

    rng = np.random.RandomState(0)
    for shape in ((4, 256, 512, 3), (2, 512, 1024, 3), (4, 64, 128, 128)):
        x = jnp.asarray(rng.rand(*shape), jnp.float32)
        flow = jnp.asarray(rng.randn(*shape[:3], 2) * 8, jnp.float32)
        _run_case(cases, "resample2d", "jnp", shape,
                  lambda a, f: resample2d(a, f, implementation="jnp"),
                  x, flow)


def bench_channelnorm(cases):
    from imaginaire_tpu.ops.channelnorm import channelnorm

    rng = np.random.RandomState(0)
    for shape in ((2, 512, 1024, 3), (4, 256, 512, 2), (4, 64, 128, 256)):
        x = jnp.asarray(rng.rand(*shape), jnp.float32)
        for impl in ("jnp", "pallas"):
            _run_case(cases, "channelnorm", impl, shape,
                      lambda a, i=impl: channelnorm(a, implementation=i), x)


def bench_correlation(cases):
    from imaginaire_tpu.ops.correlation import correlation

    rng = np.random.RandomState(0)
    # 1/8-res FlowNetC features: 512x1024 frame -> 64x128; smaller probe too
    for shape in ((1, 64, 128, 256), (1, 32, 64, 256)):
        x1 = jnp.asarray(rng.rand(*shape), jnp.float32)
        x2 = jnp.asarray(rng.rand(*shape), jnp.float32)
        for impl in ("jnp", "mxu"):
            _run_case(cases, "correlation", impl, shape,
                      lambda a, b, i=impl: correlation(a, b, implementation=i),
                      x1, x2)


def bench_spade_modulation(cases):
    from imaginaire_tpu.ops.spade_modulation import spade_modulation

    rng = np.random.RandomState(0)
    # SPADE generator epilogue operating points at 512^2 synthesis: the
    # deep low-res blocks (bs4 x 32^2 x 1024), the mid blocks and the
    # wide near-output block; plus the 2-condition accumulation case
    # (spade.py feeds seg + edge maps). Measured on the TRAINING path
    # (grad of sum-of-squares wrt every input): the op exists to change
    # what the backward keeps, and its rows carry the grad program's
    # AOT temp bytes alongside latency — pick_winners orders
    # temp-annotated ops by (temp, then ms).
    shapes = (((4, 32, 32, 1024), 1), ((4, 128, 128, 256), 1),
              ((2, 256, 256, 128), 1), ((4, 64, 64, 512), 2))
    for shape, n_pairs in shapes:
        x = jnp.asarray(rng.rand(*shape), jnp.float32)
        gs = tuple(jnp.asarray(rng.randn(*shape) * 0.1, jnp.float32)
                   for _ in range(n_pairs))
        bs = tuple(jnp.asarray(rng.randn(*shape) * 0.1, jnp.float32)
                   for _ in range(n_pairs))
        for impl in ("jnp", "fused", "pallas"):
            def op(x_, *gb, i=impl):
                return spade_modulation(
                    x_, gb[:len(gb) // 2], gb[len(gb) // 2:],
                    implementation=i)

            def grad_dx(x_, *gb):
                # dx chains through _looped's data dependence; the full
                # pytree grad would not
                return jax.grad(
                    lambda a: jnp.sum(op(a, *gb) ** 2))(x_)

            grad_dx.__name__ = f"spade_modulation_{impl}_grad"
            _run_case(cases, "spade_modulation", impl,
                      shape + (n_pairs,), grad_dx, x, *gs, *bs,
                      extras=lambda: {"temp_bytes":
                                      _grad_program_temp_bytes(
                                          op, x, *gs, *bs)})


BENCHES = {
    "resample2d": bench_resample2d,
    "channelnorm": bench_channelnorm,
    "correlation": bench_correlation,
    "spade_modulation": bench_spade_modulation,
}


def pick_winners(cases, op_names):
    """Per-op default from the measured rows. Ordering: if every
    qualifying implementation's rows carry ``temp_bytes`` (residual-
    policy ops benched on the grad path, e.g. spade_modulation), the
    winner is min by (sum temp_bytes, sum ms) — implementations with
    identical forward math differ in what the backward materializes,
    not in latency, so temp is the decision axis and latency only
    breaks ties. Otherwise min by sum ms as before."""
    winners = {}
    for op in op_names:
        op_cases = [item for item in cases if item["op"] == op]
        shapes = {tuple(item["shape"]) for item in op_cases}
        rows, failed = {}, set()
        for item in op_cases:
            if "ms" in item:
                rows.setdefault(item["impl"], []).append(item)
            else:
                failed.add(item["impl"])
        # only an impl that ran EVERY shape cleanly can be the default;
        # then all qualifying sums cover the identical shape set
        ran = {impl: rs for impl, rs in rows.items()
               if impl not in failed and len(rs) == len(shapes)}
        if not ran:
            winners[op] = "jnp"
            continue
        if all("temp_bytes" in r for rs in ran.values() for r in rs):
            key = {impl: (sum(r["temp_bytes"] for r in rs),
                          sum(r["ms"] for r in rs))
                   for impl, rs in ran.items()}
        else:
            key = {impl: sum(r["ms"] for r in rs)
                   for impl, rs in ran.items()}
        winners[op] = min(key, key=key.get)
    return winners


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ops", default=",".join(BENCHES),
                    help="comma list of ops to bench")
    ap.add_argument("--json", default=None,
                    help="also write the report to this path")
    args = ap.parse_args(argv)
    op_names = [o.strip() for o in args.ops.split(",") if o.strip()]
    unknown = [o for o in op_names if o not in BENCHES]
    if unknown:
        ap.error(f"unknown ops {unknown}; choose from " + ",".join(BENCHES))

    dev = jax.devices()[0]
    print("device:", dev, flush=True)
    cases = []
    for op in op_names:
        BENCHES[op](cases)

    out = {"device": str(dev), "platform": dev.platform,
           "device_kind": dev.device_kind,
           "method": f"slope between {K_SMALL}- and {K_LARGE}-iteration "
                     f"fori_loop chains, median of {REPEATS}",
           "cases": cases, "winners": pick_winners(cases, op_names)}
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps({"platform": dev.platform,
                      "device_kind": dev.device_kind,
                      "winners": out["winners"]}))


if __name__ == "__main__":
    main()
