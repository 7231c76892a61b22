#!/usr/bin/env python
"""What an expert layer does around its grouped products, alone, on the
chip: finding the held experts' rows (``held_experts.route_held``'s sort) and
moving rows into the buffer and out of it (``held_experts_part`` and its
backward), at the token cells' shapes.

  python scripts/sweep_expert_movement.py [--out chiprun_out/sweep_expert_movement.json]

Two families of variants:

  ``order``  the first rows of the stable order of N assignments by held
             expert, N the cells' 32,768, 49,152 and 65,536, 8 held
             experts, the cells' held shares: ``argsort`` (the stable
             two-operand ``jnp.argsort``, the program's), ``key_sort``
             (one key an assignment, the expert above the index's bits,
             through a one-operand sort) and ``running_count`` (positions
             from the experts'
             counts and a running count over the assignments, then a
             scatter of the indices); and the experts' counts beside it,
             ``counts_bincount`` (``jnp.bincount``, a scatter-add of ones)
             and ``counts_compare`` (a comparison with each expert,
             summed)
  ``move``   at each cell's short tier x hidden, with the cell's usual
             count of filled rows and then the whole tier filled (the
             count is data: one compile serves both): ``gather`` (rows
             into the buffer), ``add`` (the weighted float32 add out of
             it) and their transposes ``gather_t`` (the rows' gradient
             added into the input's) and ``add_t`` (the rows' and the
             weights' cotangents), ``add_halves`` (the add in two halves
             of the columns, each with an accumulator of half the size),
             each ``whole`` (the whole tier
             gathered, masked and scatter-added, and ``jax.vjp`` of that:
             the form before ISSUE 40) and ``seg<rows>`` (the program's
             loop over the filled segments, at the segment it derives
             and at others)

It measures a TPU and nothing else: without one it exits 2
(``tests/test_hybrid_lm_layers.py`` holds the forms against each other on
the CPU). The numbers are DEVICE milliseconds: the median over the calls
of the jitted function's event on the trace's ``XLA Modules`` line, read
as ``scripts/sweep_grouped_products.py`` reads them.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from imaginaire_tpu.ops import held_experts

CALLS, GROUPS = 5, 8
SHARES = (0.21, 0.06, 0.17, 0.11, 0.02, 0.19, 0.09, 0.15)
# assignments a layer -> the share of them on the held experts
ORDERS = {32768: 8 / 64, 49152: 8 / 128, 65536: 8 / 32}
# cell -> (tokens, the short tier's rows, hidden, filled rows a layer)
CELLS = {"lfm2": (16384, 32768, 2048, 17049),
         "solar": (8192, 8192, 4096, 2092),
         "nemotron": (8192, 8192, 2688, 2890),
         "glm": (8192, 8192, 2048, 3099)}
SEGMENTS = (256, 512, 1024, 2048, 4096)


# -------------------------------------------------------------- the order


def order_argsort(local, rows):
    return jnp.argsort(local, stable=True)[:rows]


def order_key_sort(local, rows):
    n = local.shape[0]
    bits = (n - 1).bit_length()
    keys = lax.sort((local << bits) | jnp.arange(n, dtype=jnp.int32))
    return keys[:rows] & ((1 << bits) - 1)


def order_running_count(local, rows):
    n = local.shape[0]
    one_hot = (local[:, None] == jnp.arange(GROUPS + 1)).astype(jnp.int32)
    sizes = one_hot.sum(0)
    before = jnp.take_along_axis(jnp.cumsum(one_hot, axis=0),
                                 local[:, None], axis=1)[:, 0] - 1
    position = (jnp.cumsum(sizes) - sizes)[local] + before
    return jnp.zeros((n,), jnp.int32).at[position].set(
        jnp.arange(n, dtype=jnp.int32), mode="promise_in_bounds")[:rows]


def counts_bincount(local, rows):
    return jnp.bincount(local, length=GROUPS + 1)[:GROUPS]


def counts_compare(local, rows):
    return (local[:, None] == jnp.arange(GROUPS)).sum(0)


def order_variants():
    out = []
    for n, share in ORDERS.items():
        key = jax.random.PRNGKey(n)
        held = jax.random.uniform(key, (n,)) < share
        local = jnp.where(held, jax.random.randint(key, (n,), 0, GROUPS),
                          GROUPS).astype(jnp.int32)
        for name, fn in (("order_argsort", order_argsort),
                         ("order_key_sort", order_key_sort),
                         ("order_running_count", order_running_count),
                         ("counts_bincount", counts_bincount),
                         ("counts_compare", counts_compare)):
            out.append((f"{name}_{n}",
                        lambda local, fn=fn, n=n: fn(local, n), (local,),
                        None))
    return out


# ----------------------------------------------------------- the movement


def placed(tokens, rows, filled, seed):
    """token (rows,) as ``route_held`` lays it: ``filled`` rows in
    ``GROUPS`` groups of uneven sizes, tokens ascending inside a group,
    other assignments' tokens past them."""
    rng = np.random.default_rng(seed)
    sizes = [int(share * filled) for share in SHARES]
    sizes[-1] += filled - sum(sizes)
    token = [np.sort(rng.choice(tokens, size, replace=False))
             for size in sizes]
    token.append(rng.integers(0, tokens, rows - filled))
    return jnp.asarray(np.concatenate(token), jnp.int32)


def gather_whole(x, token, filled):
    mask = (jnp.arange(token.shape[0]) < filled)[:, None]
    return jnp.where(mask, x[token], 0)


def add_whole(out, weight, token, filled, tokens):
    mask = (jnp.arange(token.shape[0]) < filled)[:, None]
    out = jnp.where(mask, out, 0).astype(jnp.float32) * weight[:, None]
    return jnp.zeros((tokens, out.shape[1]), jnp.float32).at[token].add(
        out).astype(jnp.bfloat16)


def move_variants():
    out = []
    for cell, (tokens, rows, hidden, usual) in CELLS.items():
        keys = jax.random.split(jax.random.PRNGKey(hidden + rows), 4)
        x = jax.random.normal(keys[0], (tokens, hidden), jnp.bfloat16)
        buf = jax.random.normal(keys[1], (rows, hidden), jnp.bfloat16)
        weight = jax.random.uniform(keys[2], (rows,), jnp.float32)
        fills = (usual, rows)
        token = [placed(tokens, rows, filled, hidden) for filled in fills]

        def whole(which, tokens=tokens):
            if which == "gather":
                return lambda x, buf, weight, token, filled: gather_whole(
                    x, token, filled)
            if which == "gather_t":
                return lambda x, buf, weight, token, filled: jax.vjp(
                    lambda x: gather_whole(x, token, filled), x)[1](buf)
            if which == "add":
                return lambda x, buf, weight, token, filled: add_whole(
                    buf, weight, token, filled, tokens)
            return lambda x, buf, weight, token, filled: jax.vjp(
                lambda buf, weight: add_whole(buf, weight, token, filled,
                                              tokens), buf, weight)[1](x)

        def by_segments(which, segment, tokens=tokens, rows=rows):
            def fn(x, buf, weight, token, filled):
                with mock.patch.object(
                        held_experts, "segment_rows", lambda rows: segment
                ), mock.patch.object(held_experts, "SEGMENTED_SUM_BYTES",
                                     float("inf")):
                    if which == "gather":
                        return held_experts.gather_rows(x, token, filled)
                    if which == "gather_t":
                        return held_experts.add_rows(
                            buf, token, filled, tokens,
                            2 * rows).astype(x.dtype)
                    if which == "add":
                        return held_experts.add_rows(
                            buf, token, filled, tokens, 2 * rows,
                            weight).astype(x.dtype)
                    if which == "add_halves":
                        half = buf.shape[1] // 2
                        return jnp.concatenate([held_experts.add_rows(
                            part, token, filled, tokens, 2 * rows, weight)
                            for part in (buf[:, :half], buf[:, half:])],
                            axis=1).astype(x.dtype)
                    return held_experts.weighted_rows_bwd(
                        x, buf, weight, token, filled)
            return fn

        derived = held_experts.segment_rows(rows)
        segments = sorted({s for s in SEGMENTS if s <= rows} | {derived})
        for which in ("gather", "add", "add_halves", "gather_t", "add_t"):
            if which != "add_halves":
                out.append((f"move_{cell}_{which}_whole", whole(which),
                            (x, buf, weight), (token, fills)))
            for segment in segments if which != "add_halves" else (derived,):
                tag = f"seg{segment}" + ("*" if segment == derived else "")
                out.append((f"move_{cell}_{which}_{tag}",
                            by_segments(which, segment),
                            (x, buf, weight), (token, fills)))
    return out


def main(argv=None):
    from sweep_grouped_products import device_times

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--out",
                        default="chiprun_out/sweep_expert_movement.json")
    args = parser.parse_args(argv)
    if jax.default_backend() != "tpu":
        print(f"the sweep measures a TPU; this backend is "
              f"{jax.default_backend()}", file=sys.stderr)
        return 2
    todo = order_variants() + move_variants()
    compiled, results = {}, {}
    for label, fn, fixed, data in todo:
        name = label.replace("*", "")
        fn.__name__ = name
        # lint: allow(bare-jit) -- a timing probe of one movement
        compiled[label] = jax.jit(fn)
        first = () if data is None else (data[0][0], jnp.int32(data[1][0]))
        jax.block_until_ready(compiled[label](*fixed, *first))
        results[label] = {}
    for fill in range(2):
        trace_dir = tempfile.mkdtemp(prefix="sweep_movement_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        for label, fn, fixed, data in todo:
            if data is None and fill:
                continue
            rest = () if data is None else (data[0][fill],
                                            jnp.int32(data[1][fill]))
            for _ in range(CALLS):
                jax.block_until_ready(compiled[label](*fixed, *rest))
        jax.profiler.stop_trace()
        device = device_times(
            trace_dir, {label.replace("*", "") for label in compiled})
        for label, fn, fixed, data in todo:
            found = device.get(label.replace("*", ""))
            if found and not (data is None and fill):
                filled = "all" if data is None else str(data[1][fill])
                results[label][filled] = statistics.median(found[0])
    device0 = jax.devices()[0]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": {"platform": device0.platform,
                              "kind": device0.device_kind},
                   "clock": "device", "calls": CALLS, "cells": CELLS,
                   "orders": {str(k): v for k, v in ORDERS.items()},
                   "results": results}, f, indent=1)
    print("| variant (* the program's segment) | filled rows: device ms |")
    print("| --- | --- |")
    for label, by_fill in results.items():
        print(f"| {label} | " + ", ".join(
            f"{filled}: {ms:.3f}" for filled, ms in by_fill.items()) + " |")
    print(json.dumps({"ok": True, "out": args.out, "clock": "device",
                      "device": {"platform": device0.platform,
                                 "kind": device0.device_kind},
                      "variants": len(results)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
