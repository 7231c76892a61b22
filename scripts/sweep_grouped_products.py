#!/usr/bin/env python
"""One expert layer's grouped products alone, on the chip: what
``lax.ragged_dot``, the grouped matmul JAX ships (``jax.experimental.
pallas.ops.tpu.megablox``) and this repo's kernel (``ops/pallas/
grouped_matmul_kernel.py``) take for the forward product, the gradient to
the rows and the gradient to the weights, at the token cells' widths.

  python scripts/sweep_grouped_products.py [--cells lfm2,...] [--out chiprun_out/sweep_grouped_products.json]

8,192 rows, 8 groups of uneven sizes, 3,200 and then 8,192 rows filled
(the group sizes are data: one compile serves both), bfloat16 operands;
16,384 rows with 8,192 and then 16,384 filled at the shape of the cell
that steps on two sequences (``ROWS_OF``).
Four families of variants, each in both directions of a layer (hidden x
width, the up product; width x hidden, the down product):

  ``ragged_dot``  at the three cells' shapes
  ``padded``      ``lax.ragged_dot`` at Nemotron's shapes with the width,
                  or hidden size and width, zero-padded to a lane multiple
  ``kernel``      the kernel at the tiles the program picks (``tiles_of``)
                  and with the row tile and the width tile moved one at a
                  time (the whole grid at Nemotron's shapes)
  ``megablox``    the shipped ``gmm`` (``transpose_rhs`` for the gradient
                  to the rows) and ``tgmm`` at the kernel's tiles, at the
                  contraction (``tgmm``: the weights' rows) cut to 1024 and
                  512, which fit the scoped VMEM the shipped calls do not
                  raise, and at (512, 1024, 1024)

It measures a TPU and nothing else: without one it exits 2 (the kernels
against ``lax.ragged_dot`` in Pallas's interpreter are ``tests/
test_grouped_matmul_op.py``'s). Every variant runs under one profiler
trace a fill and the numbers are DEVICE milliseconds: the median over the
calls of the jitted function's event on the trace's ``XLA Modules`` line
(``module_ms``) and, of that, the part in the product's own kernels
(``kernel_ms``: ``ragged-dot*`` events and the Pallas calls; the rest is
layout copies and the kernels' scalars). ``ops`` names the events of a
variant's first call, so that what a trace calls the shipped kernels can
be read off.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

from imaginaire_tpu.ops import grouped_matmul
from imaginaire_tpu.ops.pallas import grouped_matmul_kernel as kernel

ROWS, GROUPS, CALLS = 8192, 8, 5
FILLS = (3200, 8192)
SHARES = (0.21, 0.06, 0.17, 0.11, 0.02, 0.19, 0.09, 0.15)
# hidden x width of a held expert in each token cell
CELLS = {"nemotron": (2688, 1856), "glm": (2048, 1536),
         "solar": (4096, 1280), "lfm2": (2048, 1792)}
# the rows of a cell's row-a-token tier where it is not ``ROWS``
ROWS_OF = {"lfm2": 16384}
PADDED = ((2688, 1920), (2688, 2048), (3072, 2048))
# the Pallas calls' events: this repo's by name, the shipped by their jit's
PALLAS_NAMES = ("grouped_", "gmm")


def group_sizes(filled):
    sizes = [int(share * filled) for share in SHARES]
    sizes[-1] += filled - sum(sizes)
    return jnp.asarray(sizes, jnp.int32)


def tile_grid(width, wide):
    """(tm, tn) to try for a product of ``width`` columns: the program's
    first, then one of the two moved at a time."""
    grid = [(grouped_matmul.ROW_TILE, grouped_matmul.width_tile(width)),
            (128, 512), (512, 512)]
    if wide:
        grid += [(256, 512), (1024, 512), (128, 256), (128, 384), (128, 640),
                 (128, 768), (128, 896), (128, 1024), (512, 1024)]
    return list(dict.fromkeys(grid))


def megablox_grid(contracted, width):
    """(tm, tk, tn) to try the shipped kernels at; ``tk`` cuts the
    contraction in ``gmm`` and the weights' rows in ``tgmm``."""
    tn = grouped_matmul.width_tile(width)
    grid = [(grouped_matmul.ROW_TILE, contracted, tn), (128, 1024, tn),
            (128, 512, tn), (512, 1024, 1024)]
    return [t for t in dict.fromkeys(grid) if t[1] <= contracted]


def rows_of(k, n):
    """The rows a product of this shape is timed at."""
    return next((ROWS_OF[cell] for cell, shape in CELLS.items()
                 if cell in ROWS_OF and shape in ((k, n), (n, k))), ROWS)


def fills_of(k, n):
    """The two counts of filled rows a product of this shape is timed
    at."""
    rows = rows_of(k, n)
    return FILLS if rows == ROWS else (rows // 2, rows)


def variants(cells=None):
    """[(label, family, pass, contracted, width, function of (lhs, rhs,
    dout, sizes))]: ``lhs`` (rows, k), ``rhs`` (groups, k, n), ``dout``
    (rows, n); of the ``cells`` named, or of all with the padded
    shapes."""
    out = []

    def ragged(family, k, n):
        out.append((f"{family}_fwd_{k}x{n}", family, "fwd", k, n,
                    lambda lhs, rhs, dout, sizes:
                    lax.ragged_dot(lhs, rhs, sizes)))
        out.append((f"{family}_dlhs_{k}x{n}", family, "dlhs", k, n,
                    lambda lhs, rhs, dout, sizes: jax.linear_transpose(
                        lambda x: lax.ragged_dot(x, rhs, sizes), lhs)(dout)))
        out.append((f"{family}_drhs_{k}x{n}", family, "drhs", k, n,
                    lambda lhs, rhs, dout, sizes: jax.linear_transpose(
                        lambda w: lax.ragged_dot(lhs, w, sizes), rhs)(dout)))

    def kernels(k, n, wide):
        for t in tile_grid(n, wide):
            tag = "x".join(map(str, t))
            out.append((f"kernel_fwd_{k}x{n}_t{tag}", "kernel", "fwd", k, n,
                        lambda lhs, rhs, dout, sizes, t=t: kernel.rows(
                            lhs, rhs, sizes, t, name="grouped_rows_fwd")))
            out.append((f"kernel_drhs_{k}x{n}_t{tag}", "kernel", "drhs", k, n,
                        lambda lhs, rhs, dout, sizes, t=t: kernel.weights(
                            lhs, dout, sizes, t,
                            name="grouped_weights_drhs")))
        for t in tile_grid(k, wide):
            tag = "x".join(map(str, t))
            out.append((f"kernel_dlhs_{k}x{n}_t{tag}", "kernel", "dlhs", k, n,
                        lambda lhs, rhs, dout, sizes, t=t: kernel.rows(
                            dout, rhs, sizes, t, transposed=True,
                            name="grouped_rows_dlhs")))

    def shipped(k, n):
        bf16 = jnp.bfloat16
        for t in megablox_grid(k, n):
            tag = "x".join(map(str, t))
            out.append((f"megablox_fwd_{k}x{n}_t{tag}", "megablox", "fwd", k,
                        n, lambda lhs, rhs, dout, sizes, t=t: gmm(
                            lhs, rhs, sizes, bf16, t)))
            # ``tgmm`` takes the rows transposed and transposes them
            # back: under one jit no copy is left
            out.append((f"megablox_drhs_{k}x{n}_t{tag}", "megablox", "drhs",
                        k, n, lambda lhs, rhs, dout, sizes, t=t: tgmm(
                            lhs.swapaxes(0, 1), dout, sizes, bf16, t)))
        for t in megablox_grid(n, k):
            tag = "x".join(map(str, t))
            out.append((f"megablox_dlhs_{k}x{n}_t{tag}", "megablox", "dlhs",
                        k, n, lambda lhs, rhs, dout, sizes, t=t: gmm(
                            dout, rhs, sizes, bf16, t, transpose_rhs=True)))

    for cell, (hidden, width) in CELLS.items():
        if cells and cell not in cells:
            continue
        for k, n in ((hidden, width), (width, hidden)):
            ragged("ragged_dot", k, n)
            kernels(k, n, cell == "nemotron")
            shipped(k, n)
    for hidden, width in () if cells else PADDED:
        for k, n in ((hidden, width), (width, hidden)):
            ragged("padded", k, n)
    return out


def operands(k, n):
    rows = rows_of(k, n)
    keys = jax.random.split(jax.random.PRNGKey(k * 7919 + n), 3)
    lhs = jax.random.normal(keys[0], (rows, k), jnp.bfloat16)
    rhs = (jax.random.normal(keys[1], (GROUPS, k, n)) * k ** -0.5
           ).astype(jnp.bfloat16)
    dout = jax.random.normal(keys[2], (rows, n), jnp.bfloat16)
    return lhs, rhs, dout


def agreement(k, n, filled):
    """The kernel's three passes, at the program's tiles, against
    ``lax.ragged_dot``'s at one shape: the largest difference over the
    filled rows, as a share of the largest value there."""
    lhs, rhs, dout = operands(k, n)
    sizes = group_sizes(filled)
    dout = dout.at[filled:].set(0)
    want, vjp = jax.vjp(lambda a, b: lax.ragged_dot(a, b, sizes), lhs, rhs)
    want = (want, *vjp(dout))
    tiles = grouped_matmul.tiles_of(k, n)
    got = (kernel.rows(lhs, rhs, sizes, tiles.fwd),
           kernel.rows(dout, rhs, sizes, tiles.dlhs, transposed=True),
           kernel.weights(lhs, dout, sizes, tiles.fwd))
    out = {}
    for which, a, b in zip(("fwd", "dlhs", "drhs"), got, want):
        a, b = (x.astype(jnp.float32) for x in (a, b))
        if which != "drhs":
            a, b = a[:filled], b[:filled]
        out[which] = float(jnp.abs(a - b).max() / jnp.abs(b).max())
    return out


def device_times(trace_dir, labels):
    """{label: (module ms of each call, product kernels' ms of each call,
    the events' names in the first call)} from the newest trace under
    ``trace_dir``."""
    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                      "*.xplane.pb")), key=os.path.getmtime)
    modules, ops = [], []
    for plane in ProfileData.from_file(path).planes:
        if not (plane.name.startswith("/device:") and "TPU" in plane.name):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                modules += [(ev.start_ns, ev.duration_ns, ev.name)
                            for ev in line.events]
            elif line.name == "XLA Ops":
                ops += [(ev.start_ns, ev.duration_ns, ev.name)
                        for ev in line.events]
    ops.sort()
    starts = [op[0] for op in ops]
    found = {}
    for start, duration, name in sorted(modules):
        label = name.split("(")[0].strip().removeprefix("jit_")
        if label not in labels:
            continue
        inside = ops[bisect.bisect_left(starts, start):
                     bisect.bisect_left(starts, start + duration)]
        names = [n.split("=")[0].strip().lstrip("%") for _, _, n in inside]
        own = sum(d for (_, d, _), n in zip(inside, names)
                  if n.startswith("ragged-dot")
                  or any(part in n for part in PALLAS_NAMES))
        module_ms, kernel_ms, _ = found.setdefault(label, ([], [], names))
        module_ms.append(duration / 1e6)
        kernel_ms.append(own / 1e6)
    return found


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--out",
                        default="chiprun_out/sweep_grouped_products.json")
    parser.add_argument("--cells", default="",
                        help="comma-separated names of CELLS to sweep "
                             "alone (default: all, and the padded shapes)")
    args = parser.parse_args(argv)
    cells = [c for c in args.cells.split(",") if c]
    unknown = sorted(set(cells) - set(CELLS))
    if unknown:
        parser.error(f"--cells {unknown}: known {sorted(CELLS)}")
    if jax.default_backend() != "tpu":
        print(f"the sweep measures a TPU; this backend is "
              f"{jax.default_backend()}", file=sys.stderr)
        return 2
    todo = variants(cells)
    by_shape = {}
    for v in todo:
        by_shape.setdefault((v[3], v[4]), []).append(v)
    results = {label: {"family": family, "pass": which, "contracted": k,
                       "width": n, "fills": {}}
               for label, family, which, k, n, _ in todo}
    agree = {}
    for k, n in [kn for cell, (h, w) in CELLS.items()
                 if not cells or cell in cells for kn in ((h, w), (w, h))]:
        agree[f"{k}x{n}"] = agreement(k, n, fills_of(k, n)[0])
        print("agreement", f"{k}x{n}", agree[f"{k}x{n}"], flush=True)
    compiled = {}
    for (k, n), group in by_shape.items():
        args_kn = operands(k, n)
        for label, _, _, _, _, fn in group:
            fn.__name__ = label
            started = time.perf_counter()
            try:
                # lint: allow(bare-jit) -- a timing probe of one product
                jitted = jax.jit(fn)
                jax.block_until_ready(jitted(*args_kn, group_sizes(
                    fills_of(k, n)[0])))
                compiled[label] = jitted
            except Exception as e:  # a tile the compiler refuses is a row
                lines = (str(e) or repr(e)).splitlines()
                results[label]["error"] = next(
                    (line for line in lines if "vmem" in line.lower()),
                    lines[0])[:300]
            results[label]["first_call_s"] = round(
                time.perf_counter() - started, 2)
        del args_kn
    for fill in range(len(FILLS)):
        trace_dir = tempfile.mkdtemp(prefix="sweep_grouped_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        for (k, n), group in by_shape.items():
            args_kn = operands(k, n)
            sizes = group_sizes(fills_of(k, n)[fill])
            for label, *_ in group:
                for _ in range(CALLS if label in compiled else 0):
                    jax.block_until_ready(compiled[label](*args_kn, sizes))
            del args_kn
        jax.profiler.stop_trace()
        device = device_times(trace_dir, set(compiled))
        if not device:
            raise SystemExit("the trace holds no module of the sweep")
        for label, (module_ms, kernel_ms, names) in device.items():
            filled = fills_of(results[label]["contracted"],
                              results[label]["width"])[fill]
            results[label]["fills"][str(filled)] = {
                "module_ms": statistics.median(module_ms),
                "kernel_ms": statistics.median(kernel_ms),
                "calls": len(module_ms)}
            results[label]["ops"] = sorted(set(names))
    device0 = jax.devices()[0]
    report = {"device": {"platform": device0.platform,
                         "kind": device0.device_kind},
              "clock": "device", "rows": ROWS, "rows_of": ROWS_OF,
              "groups": GROUPS, "fills": list(FILLS), "shares": SHARES,
              "calls": CALLS,
              "agreement": agree, "results": results}
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print("| variant | rows filled | "
          f"{' | '.join('module_ms' for _ in FILLS)} | of it kernels |")
    print("| --- |" + " --- |" * (len(FILLS) + 2))
    for label, r in results.items():
        if "error" in r:
            print(f"| {label} | refused: {r['error']} |")
            continue
        counts = fills_of(r["contracted"], r["width"])
        fills = [r["fills"].get(str(f), {}) for f in counts]
        module = [f"{f.get('module_ms', float('nan')):.3f}" for f in fills]
        own = [f"{f.get('kernel_ms', float('nan')):.3f}" for f in fills]
        print(f"| {label} | {' / '.join(map(str, counts))} | "
              f"{' | '.join(module)} | {' / '.join(own)} |")
    print(json.dumps({"ok": True, "out": args.out, "clock": report["clock"],
                      "device": report["device"],
                      "variants": len(results)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
