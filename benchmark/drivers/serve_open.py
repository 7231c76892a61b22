"""Driver of the open-loop serving cells.

Builds a `ServingEngine` as `inference.py` builds it (trainer, state from
an example batch, the engine's shipped settings), puts the seed's weights
in place of a restore, warms the two lane executables by running them, and
offers requests on a schedule fixed by the cell's traffic file, through
`submit` and `pump`, from one thread. Each request is timed from its
scheduled arrival to its image in host memory. After the window a sample
of the answers, drawn from the seed, is compared with the plain reference.
"""

from __future__ import annotations

import gc
import json
import os
import time

import numpy as np

from benchmark.lib import arrivals, harness, labels, program, weights

TRACED_SECONDS = 5.0


def install_weights(trainer, values):
    """Put the seed's arrays into the trainer's state where serving reads
    them: the averaged generator, its spectral-norm vectors and its
    batch-norm running statistics. Every name of the reference must land."""
    used = set()
    state = trainer.state
    vars_g = dict(state["vars_G"])
    state["ema_G"] = program.graft(state["ema_G"], values, used)
    for collection in ("spectral", "batch_stats"):
        vars_g[collection] = program.graft(vars_g[collection], values, used)
    state["vars_G"] = vars_g
    program.require_all_used(values, used)


def build_engine(config, seed, shrunk=False):
    """(engine, values): the engine as `inference.py` builds it, serving
    the seed's weights; `values` are those weights by reference name."""
    import jax

    from imaginaire_tpu.parallel.mesh import mesh_from_config, set_mesh
    from imaginaire_tpu.registry import resolve
    from imaginaire_tpu.serving import ServingEngine

    cfg = program.load_config(config, shrunk=shrunk)
    reference = program.load_reference(config, "serve")
    sizes = config["sizes"]
    set_mesh(mesh_from_config(cfg))
    trainer = resolve(cfg.trainer.type, "Trainer")(cfg)
    side = sizes["image_size"]
    sample = {"images": np.zeros((1, side, side, 3), np.float32),
              "label": np.zeros((1, side, side, sizes["num_labels"]),
                                np.float32)}
    sample = trainer.start_of_iteration(sample, 0)
    trainer.init_state(weights.seed_key(seed), sample)
    values = weights.make(reference.spec(sizes), seed)
    install_weights(trainer, values)
    engine = ServingEngine(cfg, trainer=trainer,
                           logdir=os.path.join(harness.CACHE_DIR, "logs"))
    engine.register_example({"label": np.asarray(sample["label"])})
    engine.refresh_weights()
    jax.block_until_ready(engine._variables)
    return engine, values


def warm(engine, pool):
    """Compile the shipped lane executables and run each once, so that
    the window meets only programs that have already run."""
    from imaginaire_tpu.serving.engine import ServeRequest

    engine.warm()
    for bs in sorted(engine.settings["batch_sizes"]):
        engine.serve([ServeRequest(data={"label": pool[i % len(pool)]},
                                   seed=i) for i in range(bs)])
    engine.reset_stats()


def plan(traffic, seconds, seed):
    """(schedule, request seeds, checked request indices) of a window: all
    drawn from the seed, before the window opens."""
    schedule = arrivals.schedule(traffic, seconds, seed)
    rng = np.random.default_rng([int(seed), 0x5EED])
    request_seeds = rng.integers(0, 1 << 31, size=len(schedule))
    checked = int(min(traffic["checked_requests"], len(schedule)))
    keep = set(int(i) for i in
               rng.choice(len(schedule), size=checked, replace=False))
    return schedule, request_seeds, keep


def offer(engine, schedule, pool, request_seeds, keep, at_offset=None):
    """The measured window. Submits request i at `schedule[i]` seconds,
    stamped with that scheduled time, pumping the engine meanwhile; a
    request that is refused counts as failed. `at_offset` is an optional
    (seconds, callable): the callable runs once when the window reaches
    that offset (the traced run starts its profiler there). Returns
    per-request records and the answers of the requests in `keep`."""
    from jax.profiler import TraceAnnotation

    from imaginaire_tpu.serving.engine import ServeRequest, ServingError

    n = len(schedule)
    due = [None] * n          # scheduled arrival, perf_counter clock
    late_ms = [None] * n      # actual submit minus scheduled
    done = [None] * n         # answer in host memory
    kept = {}
    by_id = {}
    failed = 0

    def collect(results):
        now = time.perf_counter()
        for rid, image in results.items():
            i = by_id.pop(rid)
            done[i] = now
            if i in keep:
                kept[i] = np.array(image, copy=True)

    t0 = time.perf_counter()
    for i in range(n):
        target = t0 + float(schedule[i])
        if at_offset is not None and schedule[i] >= at_offset[0]:
            at_offset[1]()
            at_offset = None
        while True:
            now = time.perf_counter()
            if now >= target:
                break
            with TraceAnnotation("bench/pump"):
                results = engine.pump(now=now)
            if results:
                collect(results)
            else:
                with TraceAnnotation("bench/no_request_due"):
                    time.sleep(min(target - now, 5e-4))
        req = ServeRequest(data={"label": pool[i % len(pool)]},
                           seed=int(request_seeds[i]))
        req.t_submit = target
        due[i] = target
        late_ms[i] = (time.perf_counter() - target) * 1e3
        try:
            engine.submit(req)
            by_id[req.id] = i
        except ServingError:
            failed += 1
        with TraceAnnotation("bench/pump"):
            collect(engine.pump())
    with TraceAnnotation("bench/pump"):
        collect(engine.flush())
    t_end = time.perf_counter()
    latencies = [(done[i] - due[i]) * 1e3 for i in range(n)
                 if done[i] is not None]
    return {"offered": n, "failed": failed + len(by_id),
            "latencies_ms": latencies, "late_ms": late_ms,
            "window_s": t_end - t0, "kept": kept}


def reference_images(reference, values, sizes, pool, request_seeds, indices,
                     precision="float32", programs=None):
    """The reference's image for each request of `indices`, one at a time
    so that it fits beside nothing else. `programs` keeps the compiled
    forward from call to call (the tools that read many seeds pass one)."""
    import jax
    import jax.numpy as jnp

    key = (reference.__name__, precision, json.dumps(sizes, sort_keys=True))
    label0 = jnp.asarray(pool[0])
    z0 = reference.style_noise(0, sizes["style_dims"])
    programs = {} if programs is None else programs
    if key not in programs:
        programs[key] = harness.compile_reference(
            lambda v, label, z: reference.forward(v, sizes, label, z,
                                                  precision),
            values, label0, z0)
    fwd = programs[key]
    out = {}
    for i in indices:
        z = reference.style_noise(int(request_seeds[i]), sizes["style_dims"])
        out[i] = np.asarray(fwd(values, jnp.asarray(pool[i % len(pool)]), z))
    return out


def rel_err(served, ref):
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(served - ref) / np.linalg.norm(ref))


def run(loaded, seed, seconds, trace, devices, peaks, clock, shrunk=False):
    import jax

    config, workload = loaded["config"], loaded["workload"]
    cell, spec = loaded["cell"], loaded["spec"]
    traffic = workload["traffic"]
    sizes = config["sizes"]
    watch = harness.CompileWatch()

    engine, values = build_engine(config, seed, shrunk=shrunk)
    pool = labels.label_pool(seed, int(traffic["label_pool"]),
                             sizes["image_size"], sizes["num_labels"])
    schedule, request_seeds, keep = plan(traffic, seconds, seed)
    warm(engine, pool)

    request_traces = []
    if trace:
        emit = engine.tracer.emit

        def record(trace_):
            request_traces.append({"spans": list(trace_.spans),
                                   "fields": dict(trace_.fields)})
            return emit(trace_)

        engine.tracer.emit = record
    trace_dir = os.path.join(harness.CACHE_DIR, "trace")
    at_offset = None
    if trace:
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # it alone makes the host late
        # the profiler takes the window's last seconds: starting it costs
        # some tens of ms, stopping it seconds, which would shed requests
        at_offset = (max(seconds - TRACED_SECONDS, 0.0),
                     lambda: jax.profiler.start_trace(
                         trace_dir, profiler_options=options))
    before = watch.snapshot()
    setup_s = clock.since_start()
    try:
        window = offer(engine, schedule, pool, request_seeds, keep,
                       at_offset=at_offset)
    finally:
        if trace:
            jax.profiler.stop_trace()
    after = watch.snapshot()

    device = harness.describe_devices(devices)
    observed = {
        "setup_s": setup_s,
        "latencies_ms": window["latencies_ms"],
        "late_ms": window["late_ms"],
        "window_s": window["window_s"],
        "request_traces": request_traces,
        "lanes_run": engine._lane_total,
        "lanes_padded": engine._lane_padded,
        "memory_peak_bytes": device["memory_peak_bytes"],
        "peaks": peaks["kinds"].get(device["kind"]),
    }
    breakdown = None
    if trace:
        from benchmark.lib import trace_reduce

        profile = trace_reduce.load(trace_reduce.newest_xplane(trace_dir))
        reduced = trace_reduce.reduce(
            profile, trace_reduce.host_marks(profile, "bench/"))
        if reduced is None:
            raise harness.BenchmarkError(
                "the trace holds no operation on a device")
        observed["trace"] = reduced
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}

    # the program's state goes before the reference runs: the peak above
    # is the program's own, and the reference needs the room
    kept = window.pop("kept")
    engine.trainer.state = None
    engine._variables = None
    engine.pool._programs.clear()
    del engine
    gc.collect()

    reference = program.load_reference(config, "serve")
    refs = reference_images(reference, values, sizes, pool, request_seeds,
                            sorted(kept))
    errs = [rel_err(kept[i], refs[i]) for i in sorted(kept)]
    compared = {"image_rel_err_max": {
        "value": max(errs) if len(errs) == len(keep) else None,
        "limit": workload["limits"]["image_rel_err_max"]}}
    compared.update({
        "compiles_in_window": {
            "value": after["compiles"] - before["compiles"], "limit": 0},
        "requests_unanswered": {"value": window["failed"], "limit": 0},
    })
    correct = harness.verdict(compared)
    group = "per_layer" if trace else "end_to_end"
    metrics = harness.read_metrics(
        harness.metrics_of(spec, cell["name"], group), observed,
        loaded["bench_dir"])
    return {"correct": correct, "attempted": window["offered"],
            "failed": window["failed"], "metrics": metrics,
            "device": device, "compared": compared, "breakdown": breakdown,
            "extra": {"cache": after, "setup_s": setup_s,
                      "window_s": window["window_s"]}}
