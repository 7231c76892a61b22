"""The serving sweep: one engine, one open-loop point per offered rate.

    python benchmark/tools/sweep_serve.py --workload <cell> --seed <n> \
        --seconds <s per point> --rates 10,20,30,40

Prints one JSON line per point. The knee is the highest rate at which no
request is shed and the backlog does not grow: the last quarter's median
latency stays near the first quarter's. Repeat a rate (`--rates 12,12,12`)
to read how steady its latencies are from window to window. Needs the chip;
procedure in README.md, the points it gave in PERF.md.
"""

import argparse
import json
import os
import sys
import time

_T0 = time.time()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import harness, labels, stats  # noqa: E402


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--rates", required=True)
    p.add_argument("--traffic", default="{}",
                   help="JSON merged over the cell's traffic block")
    args = p.parse_args()
    loaded, _, _ = harness.start(args.workload)
    from benchmark.drivers import serve_open

    config, traffic = loaded["config"], dict(loaded["workload"]["traffic"])
    traffic.update(json.loads(args.traffic))
    sizes = config["sizes"]
    engine, _ = serve_open.build_engine(config, args.seed)
    pool = labels.label_pool(args.seed, int(traffic["label_pool"]),
                             sizes["image_size"], sizes["num_labels"])
    serve_open.warm(engine, pool)
    print(json.dumps({"setup_s": time.time() - _T0}), flush=True)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        traffic["rate_rps"] = rate
        schedule, seeds, _ = serve_open.plan(traffic, args.seconds,
                                             args.seed + k)
        engine.reset_stats()
        w = serve_open.offer(engine, schedule, pool, seeds, keep=set())
        lat = w["latencies_ms"]
        quarter = max(len(lat) // 4, 1)
        print(json.dumps({
            "rate_rps": rate, "offered": w["offered"], "failed": w["failed"],
            "window_s": w["window_s"],
            "achieved_rps": len(lat) / w["window_s"],
            "p50_ms": stats.percentile(lat, 0.5),
            "p95_ms": stats.percentile(lat, 0.95),
            "max_ms": max(lat) if lat else None,
            "first_quarter_p50_ms": stats.percentile(lat[:quarter], 0.5),
            "last_quarter_p50_ms": stats.percentile(lat[-quarter:], 0.5),
            "late_p95_ms": stats.percentile(
                [x for x in w["late_ms"] if x is not None], 0.95),
            "pad_share": (engine._lane_padded / engine._lane_total
                          if engine._lane_total else None)}), flush=True)


if __name__ == "__main__":
    main()
