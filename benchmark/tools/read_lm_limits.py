"""Readings a token-model training cell's limits of `correct` are set
from, many seeds through one trainer in one process.

    python benchmark/tools/read_lm_limits.py --workload <cell> \
        --seeds 11,12,13 [--control-seeds 11,12] [--witness-seeds 11]

First the program: for each seed its weights go into the one trainer
(moments and counters zeroed), three iterations run through the loop the
window uses, and what they left is reduced to the comparison's numbers at
once (the seed's initial weights are made again on the device beside the
idle trainer: 2.7 GB next to its 8). Then the program is freed and the
reference follows each seed's three steps on the recorded batches in
float32 (`program`: the numbers a sound run reads). For a control seed the
reference also runs in the nearest precision below the configuration's
bfloat16 (what enters every product rounded to float8 e4m3) and is
compared with the float32 reference in the program's place (`control`:
what has to fail). For a witness seed it runs in the configuration's own
bfloat16 (`witness`: what sound arithmetic in the program's precision
reads against float32, whatever the program does). One JSON line per
seed, appended to chiprun_out/lm_limits.jsonl. Needs the chip.
"""

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import harness, lm_weights  # noqa: E402
from benchmark.lib.program import load_reference  # noqa: E402


def emit(row):
    line = json.dumps(row)
    print(line, flush=True)
    out_dir = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "lm_limits.jsonl"), "a") as f:
        f.write(line + "\n")


def seed_list(text):
    return [int(s) for s in text.split(",") if s]


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--control-precision", default="float8")
    p.add_argument("--witness-seeds", default="")
    args = p.parse_args()
    loaded, _, _ = harness.start(args.workload)
    import jax

    from benchmark.drivers import train_fed, train_lm

    config, workload = loaded["config"], loaded["workload"]
    sizes = config["sizes"]
    seeds = seed_list(args.seeds)
    extra = {"control": (set(seed_list(args.control_seeds)),
                         args.control_precision),
             "witness": (set(seed_list(args.witness_seeds)), "bfloat16")}
    reference = load_reference(config, "train")
    spec = reference.spec(sizes)
    trainer, loop, tm = train_lm.build(config, workload, args.workload,
                                       seeds[0])
    recorded = {}
    for n, seed in enumerate(seeds):
        if n:
            train_fed.reset_state(trainer)
            train_lm.install_weights(trainer, lm_weights.make(spec, seed))
        recorder = train_lm.Recorder(sizes)
        for _ in range(train_lm.CHECKED_STEPS):
            _, gen = loop.step(capture=recorder.capture)
            recorder.after_step(trainer, gen)
        recorded[seed] = (recorder.batches,
                          recorder.numbers(lm_weights.make(spec, seed)))
        print(json.dumps({"seed": seed, "program_losses": recorder.losses}),
              flush=True)
    peak = harness.describe_devices(jax.devices()[:1])["memory_peak_bytes"]
    loop.close()
    tm.shutdown()
    trainer.state = None
    del trainer, loop
    gc.collect()

    tie_margin = float(workload["tie_margin"])
    for seed in seeds:
        batches, numbers = recorded.pop(seed)

        def follow(precision):
            return train_lm.reference_steps(
                reference, lm_weights.make(spec, seed), sizes, batches,
                precision, tie_margin)

        ref = follow("float32")
        got, where = train_lm.compare(numbers, ref)
        row = {"seed": seed, "program": got, "program_leaves": where,
               "losses": {"program": numbers["losses"],
                          "reference": ref["losses"]},
               "held": {"program": numbers["held"][0],
                        "reference": ref["held"][0], "ties": ref["ties"][0]}}
        for name, (which, precision) in extra.items():
            if seed not in which:
                continue
            other = follow(precision)
            row[name], row[name + "_leaves"] = train_lm.compare(other, ref)
            row["losses"][name] = other["losses"]
        emit(row)
    print(json.dumps({"memory_peak_bytes": peak}))


if __name__ == "__main__":
    main()
