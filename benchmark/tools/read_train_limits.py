"""Readings a training cell's limits of `correct` are set from, many
seeds through one trainer in one process.

    python benchmark/tools/read_train_limits.py --workload <cell> \
        --seeds 11,12,13 [--control-seeds 11,12,13]

First the program: for each seed its weights go into the one trainer
(moments and counters zeroed), three iterations run through the loop the
window uses, and what they consumed and left is kept on the host. Then the
program is freed and the reference follows each seed's three steps in
float32 (`program`: the numbers a sound run reads). For each control seed
the reference also runs in the nearest precision below the configuration's
bfloat16 (what enters every product rounded to float8 e4m3) and is
compared with the float32 reference in the program's place (`control`:
what has to fail). For each witness seed it runs in the configuration's own
bfloat16 as well (`witness`: what sound arithmetic in the program's precision
reads against float32, whatever the program does; it told the later steps'
noise from a fault in PR 23). Each row carries every step's loss gap
(`loss_gaps_by_step`). One JSON line per seed, appended to
chiprun_out/train_limits.jsonl. Needs the chip. In a process that compiles
the step programs itself the host holds some 30 GiB, and six seeds' batches
on top met the machine's 40 GiB (PR 23): run a cell once first, so that the
cache is warm, or read the control alone (`--control-only`).
"""

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import harness, program, weights  # noqa: E402


class LoaderBatches:
    """Three batches straight from the cell's loader, for the control,
    which needs the reference alone and no trainer."""

    def __init__(self, loader_iter, steps):
        import numpy as np

        self.batches = [{k: np.asarray(b[k]) for k in ("images", "label")}
                        for b in (next(loader_iter) for _ in range(steps))]


def control_only(args, loaded, seeds):
    """The control without the program: the reference in float32 and in
    the control's precision on batches from the cell's own loader."""
    import jax.numpy as jnp

    from benchmark.drivers import train_fed
    from imaginaire_tpu.data import get_train_and_val_dataloader

    config, workload = loaded["config"], loaded["workload"]
    sizes = config["sizes"]
    reference = program.load_reference(config, "train")
    spec = reference.spec(sizes)
    cfg = train_fed.program_config(config, workload, args.workload)
    loader, _ = get_train_and_val_dataloader(cfg, seed=seeds[0])
    batches = iter(loader)
    for seed in seeds:
        recorded = LoaderBatches(batches, train_fed.CHECKED_STEPS)
        runs = {precision: train_fed.reference_steps(
            reference, weights.make(spec, seed), sizes, recorded, seed,
            precision, jnp.bfloat16)
            for precision in ("float32", args.control_precision)}
        numbers, where = train_fed.compare(runs[args.control_precision],
                                           runs["float32"])
        emit({"seed": seed, "control": numbers, "control_leaves": where,
              "losses": {k: v["losses"] for k, v in runs.items()}})


def emit(row):
    line = json.dumps(row)
    print(line, flush=True)
    out_dir = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "train_limits.jsonl"), "a") as f:
        f.write(line + "\n")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--control-precision", default="float8")
    p.add_argument("--witness-seeds", default="")
    p.add_argument("--control-only", action="store_true",
                   help="read the control alone, on batches from the "
                        "cell's loader, without building the trainer")
    args = p.parse_args()
    loaded, _, _ = harness.start(args.workload)
    import jax
    import jax.numpy as jnp

    from benchmark.drivers import train_fed

    if args.control_only:
        control_only(args, loaded, [int(s) for s in args.seeds.split(",")])
        return

    config, workload = loaded["config"], loaded["workload"]
    sizes = config["sizes"]
    seeds = [int(s) for s in args.seeds.split(",")]
    control = set(int(s) for s in args.control_seeds.split(",") if s)
    witness = set(int(s) for s in args.witness_seeds.split(",") if s)
    reference = program.load_reference(config, "train")
    spec = reference.spec(sizes)
    trainer, loop, tm = train_fed.build(config, workload, args.workload,
                                        seeds[0])
    noise_dtype = (jnp.bfloat16 if trainer.compute_dtype == jnp.bfloat16
                   else jnp.float32)
    recorded = {}
    for n, seed in enumerate(seeds):
        if n:
            train_fed.reset_state(trainer)
            train_fed.install_weights(trainer, weights.make(spec, seed), seed)
        recorder = train_fed.Recorder(sizes)
        for _ in range(train_fed.CHECKED_STEPS):
            dis, gen = loop.step(capture=recorder.capture)
            recorder.after_step(trainer, dis, gen)
        recorded[seed] = (recorder, recorder.numbers(weights.make(spec, seed)))
        print(json.dumps({"seed": seed, "program_losses": recorder.losses}),
              flush=True)
    loop.close()
    tm.shutdown()
    trainer.state = None
    del trainer, loop
    gc.collect()

    for seed in seeds:
        recorder, numbers = recorded[seed]
        ref = train_fed.reference_steps(
            reference, weights.make(spec, seed), sizes, recorder, seed,
            "float32", noise_dtype)
        got, where = train_fed.compare(numbers, ref)
        row = {"seed": seed, "program": got, "program_leaves": where,
               "loss_gaps_by_step": {
                   "program": train_fed.loss_gaps(numbers, ref)},
               "losses": {"program": numbers["losses"],
                          "reference": ref["losses"]}}
        if seed in witness:
            same = train_fed.reference_steps(
                reference, weights.make(spec, seed), sizes, recorder, seed,
                "bfloat16", noise_dtype)
            row["witness"], row["witness_leaves"] = train_fed.compare(same,
                                                                      ref)
            row["loss_gaps_by_step"]["witness"] = train_fed.loss_gaps(same,
                                                                      ref)
            row["losses"]["witness"] = same["losses"]
        if seed in control:
            low = train_fed.reference_steps(
                reference, weights.make(spec, seed), sizes, recorder, seed,
                args.control_precision, noise_dtype)
            row["control"], row["control_leaves"] = train_fed.compare(low, ref)
            row["loss_gaps_by_step"]["control"] = train_fed.loss_gaps(low, ref)
            row["losses"]["control"] = low["losses"]
        emit(row)
        del recorded[seed]
    print(json.dumps({"memory_peak_bytes": harness.describe_devices(
        jax.devices()[:1])["memory_peak_bytes"]}))


if __name__ == "__main__":
    main()
