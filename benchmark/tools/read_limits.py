"""Readings a limit of `correct` is set from, many seeds in one process.

    python benchmark/tools/read_limits.py --workload <cell> \
        --seeds 11,12,13 --seconds 4 [--control-seeds 11,12,13]

For each seed: the seed's weights go into the one engine, a short window
runs at the cell's own load, and the checked requests' images are compared
with the reference in float32 (`program`: what sound runs read). For each
control seed the reference in bfloat16, and with its products' operands in
float8 e4m3, is compared with the float32 reference on the same requests
(`control_*`: what has to fail; PERF.md says which of the two can). One JSON line per seed, also appended to
chiprun_out/limits.jsonl. Needs the chip.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import harness, labels, weights  # noqa: E402


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--seconds", type=float, default=4.0)
    args = p.parse_args()
    loaded, _, _ = harness.start(args.workload)
    from benchmark.drivers import serve_open
    from benchmark.lib import program

    config, traffic = loaded["config"], loaded["workload"]["traffic"]
    reference = program.load_reference(config, "serve")
    sizes = config["sizes"]
    seeds = [int(s) for s in args.seeds.split(",")]
    control = set(int(s) for s in args.control_seeds.split(",") if s)
    engine, _ = serve_open.build_engine(config, seeds[0])
    spec = reference.spec(sizes)
    out_dir = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    warmed = False
    programs = {}
    for seed in seeds:
        values = weights.make(spec, seed)
        serve_open.install_weights(engine.trainer, values)
        engine.refresh_weights()
        pool = labels.label_pool(seed, int(traffic["label_pool"]),
                                 sizes["image_size"], sizes["num_labels"])
        if not warmed:
            serve_open.warm(engine, pool)
            warmed = True
        schedule, request_seeds, keep = serve_open.plan(traffic, args.seconds,
                                                        seed)
        engine.reset_stats()
        window = serve_open.offer(engine, schedule, pool, request_seeds, keep)
        kept = window["kept"]
        row = {"seed": seed, "offered": window["offered"],
               "failed": window["failed"]}
        refs = serve_open.reference_images(
            reference, values, sizes, pool, request_seeds, sorted(kept),
            programs=programs)
        row["program"] = [serve_open.rel_err(kept[i], refs[i])
                          for i in sorted(kept)]
        if seed in control:
            for precision in ("bfloat16", "float8"):
                low = serve_open.reference_images(
                    reference, values, sizes, pool, request_seeds,
                    sorted(kept), precision=precision, programs=programs)
                row["control_" + precision] = [
                    serve_open.rel_err(low[i], refs[i]) for i in sorted(kept)]
        line = json.dumps(row)
        print(line, flush=True)
        with open(os.path.join(out_dir, "limits.jsonl"), "a") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
