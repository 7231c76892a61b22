"""Driver of a token model's training cell whose reference counts the
step's work itself.

`train_lm.run` builds `observed["work"]` from the reference's `scan_work`
and from the pattern's `*` times one layer's `attn_work`: it cannot count a
layer outside the pattern (a multi-token-prediction module's block) or a
scope family of another model. Here the reference's `work(sizes, batch,
seq_len, held_assignments)` gives the whole dictionary, and the step's own
two losses give the module's share. Everything else is `train_lm`'s and
`train_fed`'s: the loop, the trainer's build with the seed's weights, what
the first iterations record, the reference's three steps, the comparison.
Folding the two drivers into one is a `benchmark` issue's (PERF.md
section 7).
"""

from __future__ import annotations

import gc
import os
import shutil
import sys
import time

import numpy as np

from benchmark.drivers.train_fed import (CHECKED_STEPS, TRACED_SECONDS,
                                         WARM_STEPS)
from benchmark.drivers.train_lm import (Recorder, build, compare,
                                        held_counts, reference_steps)
from benchmark.lib import harness, lm_weights
from benchmark.lib.program import load_reference


def traced(trainer, trace_dir):
    """(observed's "trace", its "scopes", the result line's breakdown)
    of the trace under `trace_dir`; the step's optimized HLO is kept
    beside it for tools/describe_scopes.py."""
    from benchmark.lib import scope_times, trace_reduce

    profile = trace_reduce.load(trace_reduce.newest_xplane(trace_dir))
    reduced = trace_reduce.reduce(
        profile, trace_reduce.host_marks(profile, "bench/"))
    if reduced is None:
        raise harness.BenchmarkError(
            "the trace holds no operation on a device")
    hlo_text = trainer._jit_gen_step.executables()[-1].as_text()
    with open(os.path.join(trace_dir, "gen_step.hlo.txt"), "w") as f:
        f.write(hlo_text)
    scopes = scope_times.reduce(profile, hlo_text)
    return reduced, scopes, {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"],
                             "scopes": scopes}


def run(loaded, seed, seconds, trace, devices, peaks, clock, shrunk=False):
    import jax

    config, workload = loaded["config"], loaded["workload"]
    cell, spec = loaded["cell"], loaded["spec"]
    sizes = config["sizes"]
    if not os.path.exists(os.path.join(harness.ROOT,
                                       config["program_yaml"])):
        # a program older than the configuration (the benchmark's files
        # laid over a parent commit): the cell cannot run there
        print(f"benchmark: cannot load cell {cell['name']!r}: the program "
              f"has no {config['program_yaml']}", file=sys.stderr)
        sys.exit(2)
    watch = harness.CompileWatch()
    trainer, loop, tm = build(config, workload, cell["name"], seed,
                              shrunk=shrunk)
    batch, seq_len = int(sizes["batch_seqs"]), int(sizes["seq_len"])

    # the first iterations: through the window's own call and feed
    recorder = Recorder(sizes)
    for _ in range(CHECKED_STEPS):
        _, gen = loop.step(capture=recorder.capture)
        recorder.after_step(trainer, gen)
    for _ in range(WARM_STEPS - CHECKED_STEPS):
        loop.step()
    jax.block_until_ready(trainer.state)

    trace_dir = os.path.join(harness.CACHE_DIR, "trace")
    tracing = False
    before = watch.snapshot()
    wait_before, iterations_before = loop.wait_s, loop.iteration
    del loop.host_wait_ms[:]
    window_losses = []
    setup_s = clock.since_start()
    t_begin = time.perf_counter()
    try:
        while True:
            now = time.perf_counter() - t_begin
            if now >= seconds:
                break
            if trace and not tracing and now >= seconds - TRACED_SECONDS:
                shutil.rmtree(trace_dir, ignore_errors=True)
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir,
                                         profiler_options=options)
                tracing = True
            # the step's own outputs, kept as the device's: read after
            # the window, so the loop waits for none of them
            window_losses.append(loop.step()[1])
        # every iteration the window started is finished before it closes
        jax.block_until_ready(trainer.state)
        window_s = time.perf_counter() - t_begin
    except Exception:
        # the program halted (a non-finite step): what each step of the
        # window reported goes to standard error before the traceback
        for index, step in enumerate(jax.device_get(window_losses)):
            print(f"window step {index}: " + " ".join(
                f"{k}={float(v):.6g}" for k, v in sorted(step.items())),
                file=sys.stderr)
        raise
    finally:
        if tracing:
            jax.profiler.stop_trace()
    after = watch.snapshot()
    iterations = loop.iteration - iterations_before

    device = harness.describe_devices(devices)
    reference = load_reference(config, "train")
    window_losses = jax.device_get(window_losses)
    routed = [held_counts(step) for step in window_losses]
    failed = sum(1 for step in window_losses
                 if not np.isfinite(step["total"]))
    layers = sorted(routed[0]) if routed else []
    mean_held = {k: float(np.mean([r[k] for r in routed])) for k in layers}
    weight = float(sizes["nextn_loss_weight"])
    shares = [weight * float(step["mtp"]) / float(step["total"])
              for step in window_losses if "mtp" in step]
    observed = {
        "setup_s": setup_s, "window_s": window_s,
        "iterations": iterations, "images": iterations * batch,
        "tokens": iterations * batch * seq_len,
        "chips": len(devices),
        "feed_wait_s": loop.wait_s - wait_before,
        "host_wait_ms": list(loop.host_wait_ms),
        "step_flops": reference.step_flops(sizes, batch, seq_len, mean_held),
        "memory_peak_bytes": device["memory_peak_bytes"],
        "peaks": peaks["kinds"].get(device["kind"]),
        "held_assignments": mean_held,
        "load_max_over_mean": [
            float(v) for step in window_losses for k, v in step.items()
            if k.endswith("/load_max_over_mean")],
        "mtp_loss_shares": shares,
        "work": reference.work(sizes, batch, seq_len, mean_held),
    }
    breakdown = None
    if trace:
        observed["trace"], observed["scopes"], breakdown = traced(
            trainer, trace_dir)
        device["busy_s"] = observed["trace"]["busy_s"]
        device["window_s"] = observed["trace"]["window_s"]

    # the program goes before the reference runs: the peak above is the
    # program's own, and the reference needs the whole chip
    loop.close()
    tm.shutdown()
    trainer.state = None
    del trainer, loop
    gc.collect()

    recorded = recorder.numbers(lm_weights.make(reference.spec(sizes), seed))
    ref = reference_steps(
        reference, lm_weights.make(reference.spec(sizes), seed), sizes,
        recorder.batches, "float32", float(workload["tie_margin"]))
    numbers, where = compare(recorded, ref)
    compared = {name: {"value": value, "limit": workload["limits"][name]}
                for name, value in numbers.items()}
    compared["compiles_in_window"] = {
        "value": after["compiles"] - before["compiles"], "limit": 0}
    group = "per_layer" if trace else "end_to_end"
    metrics = harness.read_metrics(
        harness.metrics_of(spec, cell["name"], group), observed,
        loaded["bench_dir"])
    return {"correct": harness.verdict(compared), "attempted": iterations,
            "failed": failed, "metrics": metrics, "device": device,
            "compared": compared, "breakdown": breakdown,
            "extra": {"cache": after, "setup_s": setup_s,
                      "window_s": window_s, "worst_leaves": where,
                      "losses": {"program": recorder.losses,
                                 "reference": ref["losses"]},
                      "mtp_loss_share": (float(np.mean(shares))
                                         if shares else None),
                      "held_assignments": {
                          "program": recorder.held, "reference": ref["held"],
                          "ties": ref["ties"], "window_mean": mean_held,
                          "window_first": routed[0] if routed else None,
                          "window_last": routed[-1] if routed else None}}}
