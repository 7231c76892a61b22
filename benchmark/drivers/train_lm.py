"""Driver of the pipeline-fed training cells of a token model.

The loop is `train_fed`'s (`train.py`'s body: the loader behind
`trainer.data_prefetcher`, `start_of_iteration`, `dis_update` (nothing
here: no discriminator), `gen_update`, the data meters,
`end_of_iteration`, telemetry configured as `train.py` configures it).
Set-up builds one trainer with the seed's weights and drives it through
its first iterations by the same call the window uses, recording the
batches, each step's loss and routing counts, the first gradient per leaf
and the parameters after three steps. After the window the program's
state is freed and the plain float32 reference follows the same three
steps on the same batches.
"""

from __future__ import annotations

import gc
import os
import sys
import time

import numpy as np

from benchmark.drivers.train_fed import (CHECKED_STEPS, TRACED_SECONDS,
                                         WARM_STEPS, Loop, change_norms,
                                         leaf_readings, leaf_readings_of,
                                         worst_leaf_gap)
from benchmark.lib import harness, lm_program, lm_weights, token_fixture
from benchmark.lib.program import (flatten, graft, load_reference,
                                   require_all_used)

HELD = "/held_assignments"


def program_config(config, workload, cell_name, shrunk=False):
    """The program's config for the cell: the benchmark's token fixture
    (made once per checkout) as its data, its logs under the cache."""
    cfg = lm_program.load_config(config, shrunk=shrunk)
    traffic = dict(workload["traffic"])
    sizes = config["sizes"]
    if shrunk:   # the rehearsal's corpus follows its small sizes
        traffic.update(seq_len=sizes["seq_len"],
                       token_ids=dict(traffic["token_ids"],
                                      ids=sizes["vocab_slice"]))
    elif (traffic["seq_len"], traffic["batch_seqs"],
          traffic["token_ids"]["ids"]) != (
              sizes["seq_len"], sizes["batch_seqs"], sizes["vocab_slice"]):
        raise harness.BenchmarkError(
            "the cell's traffic and the configuration's sizes disagree on "
            "seq_len, batch_seqs or the ids' range")
    packed = token_fixture.packed_tokens(
        os.path.join(harness.CACHE_DIR, "fixtures", cell_name), traffic)
    for split in ("train", "val"):
        cfg.data[split].roots = [packed]
    cfg.logdir = os.path.join(harness.CACHE_DIR, "logs", cell_name)
    os.makedirs(cfg.logdir, exist_ok=True)
    return cfg


def install_weights(trainer, values):
    """The seed's arrays into the trainer's state, every leaf where
    `init_state` had placed it (or the step would compile again). The
    step donates its state, so the program owns these arrays from here."""
    import jax

    state = dict(trainer.state)
    placed = jax.tree_util.tree_map(lambda x: x.sharding, trainer.state)
    unlisted = sorted({name for tree in state["vars_G"].values()
                       for name in flatten(tree)} - set(values))
    if unlisted:
        raise harness.BenchmarkError(
            f"the program holds {unlisted[:3]}, which the reference does "
            "not list")
    used = set()
    state["vars_G"] = {collection: graft(tree, values, used)
                       for collection, tree in state["vars_G"].items()}
    require_all_used(values, used)
    trainer.state = jax.tree_util.tree_map(jax.device_put, state, placed)


def build(config, workload, cell_name, seed, shrunk=False):
    """(trainer, loop, tm): the trainer as `train.py` builds it, on the
    benchmark's fixture, holding the seed's weights."""
    from imaginaire_tpu import telemetry
    from imaginaire_tpu.data import get_train_and_val_dataloader
    from imaginaire_tpu.parallel.mesh import mesh_from_config, set_mesh
    from imaginaire_tpu.registry import resolve

    cfg = program_config(config, workload, cell_name, shrunk=shrunk)
    set_mesh(mesh_from_config(cfg))
    tm = telemetry.configure(cfg, logdir=cfg.logdir)
    train_loader, val_loader = get_train_and_val_dataloader(
        cfg, seed=int(seed) & 0x7FFFFFFF)
    trainer = resolve(cfg.trainer.type, "Trainer")(
        cfg, train_data_loader=train_loader, val_data_loader=val_loader)
    sample = next(iter(train_loader))
    sample = trainer.start_of_iteration(sample, 0)
    trainer.init_state(lm_weights.seed_key(seed), sample)
    del sample
    reference = load_reference(config, "train")
    install_weights(trainer, lm_weights.make(
        reference.spec(config["sizes"]), seed))
    return trainer, Loop(trainer, train_loader, tm), tm


def held_counts(losses):
    """{layer index: assignments that landed on the held experts} of one
    step's losses, as floats."""
    return {int(k.split("/")[1]): float(v) for k, v in losses.items()
            if k.endswith(HELD)}


class Recorder:
    """What the first iterations leave for the comparison."""

    def __init__(self, sizes):
        self.sizes = sizes
        self.batches = []
        self.losses = []
        self.held = []
        self.first_gradient_norms = None
        self.first_gradient_projections = None
        self.params_after = None

    def capture(self, data):
        self.batches.append(np.asarray(data["tokens"]))

    def after_step(self, trainer, gen):
        self.losses.append(float(gen["total"]))
        self.held.append(held_counts(gen))
        state = trainer.state
        if len(self.losses) == 1:
            # Adam's first moment after one step is (1 - beta1) times the
            # gradient the optimizer was given
            scale = 1.0 - float(self.sizes["adam_beta1"])
            norms, projections = leaf_readings_of(state["opt_G"][0].mu)
            self.first_gradient_norms = {k: v / scale
                                         for k, v in norms.items()}
            self.first_gradient_projections = {
                k: v / scale for k, v in projections.items()}
        if len(self.losses) == CHECKED_STEPS:
            self.params_after = {
                k: np.asarray(v) for k, v in flatten(
                    state["vars_G"]["params"]).items()}

    def numbers(self, values):
        """What `compare` takes, once the seed's initial `values` are at
        hand again; the parameters' host copy is dropped."""
        out = {"losses": self.losses, "held": self.held,
               "first_gradient_norms": self.first_gradient_norms,
               "first_gradient_projections": self.first_gradient_projections,
               "param_change_norms": change_norms(self.params_after, values)}
        self.params_after = None
        return out


def reference_steps(reference, values, sizes, batches, precision,
                    tie_margin):
    """The reference's losses, routing counts, first gradient readings and
    parameters' change over the recorded steps, on the recorded batches.
    `values` is consumed."""
    import jax
    import jax.numpy as jnp

    train, buffers = reference.split(values)
    del values
    initial = {k: np.asarray(x) for k, x in train.items()}
    mu = {k: jnp.zeros_like(x) for k, x in train.items()}
    nu = {k: jnp.zeros_like(x) for k, x in train.items()}

    def step(train, mu, nu, buffers, tokens, count):
        (loss, aux), grads = jax.value_and_grad(reference.loss, has_aux=True)(
            train, buffers, sizes, tokens, precision, tie_margin)
        readings = leaf_readings(grads)
        train, mu, nu = reference.adam(
            train, grads, mu, nu, count, sizes["gen_lr"],
            sizes["adam_beta1"], sizes["adam_beta2"])
        return loss, aux, train, mu, nu, readings

    program = None
    losses, held, ties, first = [], [], [], None
    for index, tokens in enumerate(batches):
        args = (train, mu, nu, buffers, jnp.asarray(tokens),
                jnp.int32(index))
        if program is None:
            program = harness.compile_reference(step, *args,
                                                donate_argnums=(0, 1, 2))
        loss, aux, train, mu, nu, readings = program(*args)
        losses.append(float(loss))
        held.append({k: float(v["held_assignments"])
                     for k, v in aux.items()})
        ties.append({k: float(v["ties"]) for k, v in aux.items()})
        if first is None:
            first = tuple({k: float(x) for k, x in r.items()}
                          for r in readings)
    return {"losses": losses, "held": held, "ties": ties,
            "first_gradient_norms": first[0],
            "first_gradient_projections": first[1],
            "param_change_norms": change_norms(train, initial)}


def compare(recorded, ref):
    """The numbers of `correct`, {name: value}, and the leaves at fault:
    the first step's loss (relative), the first gradient's norm by the
    worst leaf, how far apart the two first gradients are by the median
    leaf (a fixed +-1 projection of each), the parameters' change after
    the checked steps by the worst leaf, and the first step's count of
    assignments on the held experts: the two sides' gap in the worst
    expert layer, over the reference's count there of tokens whose choice
    hangs on less than the tie margin (at or under 1: ties explain it)."""
    out, where = {}, {}
    out["loss_first_rel"] = (abs(recorded["losses"][0] - ref["losses"][0])
                             / max(abs(ref["losses"][0]), 1e-6))
    gap, leaf = worst_leaf_gap(recorded["first_gradient_norms"],
                               ref["first_gradient_norms"])
    out["first_gradient_norm_worst_leaf"] = gap
    where["first_gradient_norm_worst_leaf"] = leaf
    norms = ref["first_gradient_norms"]
    median = float(np.median(list(norms.values())))
    apart = [abs(recorded["first_gradient_projections"][k]
                 - ref["first_gradient_projections"][k])
             / max(norms[k], median) for k in norms]
    out["first_gradient_apart_median_leaf"] = float(np.median(apart))
    gap, leaf = worst_leaf_gap(recorded["param_change_norms"],
                               ref["param_change_norms"])
    out["param_change_norm_worst_leaf"] = gap
    where["param_change_norm_worst_leaf"] = leaf
    ours, theirs, ties = recorded["held"][0], ref["held"][0], ref["ties"][0]
    out["held_assignments_gap_over_ties"], layer = max(
        (abs(ours[k] - theirs[k]) / max(ties[k], 1.0), k) for k in theirs)
    where["held_assignments_gap_over_ties"] = f"layer_{layer}"
    return out, where


def run(loaded, seed, seconds, trace, devices, peaks, clock, shrunk=False):
    import jax

    config, workload = loaded["config"], loaded["workload"]
    cell, spec = loaded["cell"], loaded["spec"]
    sizes = config["sizes"]
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
                import shutil

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
        "work": {
            "ssd_scan": [n * sizes["pattern"].count("M") for n in
                         reference.scan_work(sizes, batch, seq_len)],
            "attn_scores": [n * sizes["pattern"].count("*") for n in
                            reference.attn_work(sizes, batch, seq_len)],
            "moe_experts": [sum(n) for n in zip(*(
                reference.expert_work(sizes, mean_held[k])
                for k in layers))] if layers else None,
        },
    }
    breakdown = None
    if trace:
        from benchmark.lib import scope_times, trace_reduce

        profile = trace_reduce.load(trace_reduce.newest_xplane(trace_dir))
        reduced = trace_reduce.reduce(
            profile, trace_reduce.host_marks(profile, "bench/"))
        if reduced is None:
            raise harness.BenchmarkError(
                "the trace holds no operation on a device")
        observed["trace"] = reduced
        # the step's optimized HLO names each instruction's scope; kept
        # beside the trace for tools/describe_scopes.py
        hlo_text = trainer._jit_gen_step.executables()[-1].as_text()
        with open(os.path.join(trace_dir, "gen_step.hlo.txt"), "w") as f:
            f.write(hlo_text)
        observed["scopes"] = scope_times.reduce(profile, hlo_text)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"],
                     "scopes": observed["scopes"]}

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
    correct = harness.verdict(compared)
    group = "per_layer" if trace else "end_to_end"
    metrics = harness.read_metrics(
        harness.metrics_of(spec, cell["name"], group), observed,
        loaded["bench_dir"])
    return {"correct": correct, "attempted": iterations, "failed": failed,
            "metrics": metrics, "device": device, "compared": compared,
            "breakdown": breakdown,
            "extra": {"cache": after, "setup_s": setup_s,
                      "window_s": window_s, "worst_leaves": where,
                      "losses": {"program": recorder.losses,
                                 "reference": ref["losses"]},
                      "held_assignments": {
                          "program": recorder.held, "reference": ref["held"],
                          "ties": ref["ties"], "window_mean": mean_held,
                          "window_first": routed[0] if routed else None,
                          "window_last": routed[-1] if routed else None}}}
