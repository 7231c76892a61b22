"""Driver of the pipeline-fed training cells.

Runs the loop body of `train.py`: the loader behind
`trainer.data_prefetcher`, `start_of_iteration`, `dis_update`,
`gen_update`, the data meters, `end_of_iteration`, with telemetry
configured as `train.py` configures it. Set-up builds one trainer with
the seed's weights, drives it through its first iterations by the same
call the window uses (recording what the feed handed it, the losses, the
first gradients and the parameters after three steps), and hands that
same object to the window. After the window the program's state is freed
and the plain reference follows the same three steps on the same batches.
"""

from __future__ import annotations

import gc
import hashlib
import os
import time

import numpy as np

from benchmark.lib import fixtures, harness, program, weights

CHECKED_STEPS = 3
WARM_STEPS = 5          # iterations before the window, the checked three among them
TRACED_SECONDS = 5.0


class Loop:
    """`train.py`'s epoch and iteration loop, one iteration per `step`."""

    def __init__(self, trainer, train_loader, tm):
        self.trainer = trainer
        self.loader = train_loader
        self.tm = tm
        self.iteration = 0
        self.epoch = 0
        self.wait_s = 0.0
        self.host_wait_ms = []
        self._epoch_base = [0]
        self.feed = trainer.data_prefetcher(
            train_loader,
            iteration_of=lambda index: self._epoch_base[0] + index)
        self.prefetching = self.feed is not train_loader
        self._timed = None
        self._data = None
        self._start_epoch()

    def _start_epoch(self):
        self.loader.set_epoch(self.epoch)
        self.trainer.start_of_epoch(self.epoch)
        self._epoch_base[0] = self.iteration
        self._timed = iter(self.tm.timed_iter(
            self.feed, "data_wait",
            step_of=lambda index: self._epoch_base[0] + index))

    def step(self, capture=None):
        """One iteration; returns (dis losses, gen losses) as the program
        hands them back (device scalars, not waited for)."""
        from jax.profiler import TraceAnnotation

        trainer = self.trainer
        t0 = time.perf_counter()
        with TraceAnnotation("bench/next_feed"):
            try:
                data = next(self._timed)
            except StopIteration:
                trainer.end_of_epoch(self._data, self.epoch, self.iteration)
                self.epoch += 1
                self._start_epoch()
                data = next(self._timed)
        self.wait_s += time.perf_counter() - t0
        with TraceAnnotation("bench/dispatch_steps"):
            data = trainer.start_of_iteration(data, self.iteration)
            if capture is not None:
                capture(data)
            dis = trainer.dis_update(data)
            gen = trainer.gen_update(data)
        self.iteration += 1
        with TraceAnnotation("bench/end_of_iteration"):
            if self.prefetching:
                stats = self.feed.drain_stats()
                self.host_wait_ms.extend(stats.get("data/host_wait_ms", []))
                trainer.write_data_meters(stats)
            trainer.end_of_iteration(data, self.epoch, self.iteration)
        self._data = data
        return dis, gen

    def close(self):
        if self._timed is not None:
            self._timed.close()
            self._timed = None


def reset_state(trainer):
    """Optimizer moments and step counters back to zero, for the tool that
    reads many seeds through one trainer."""
    import jax
    import jax.numpy as jnp

    state = dict(trainer.state)
    for key in ("opt_G", "opt_D", "step", "step_D", "num_ema_updates"):
        if key in state:
            state[key] = jax.tree_util.tree_map(jnp.zeros_like, state[key])
    trainer.state = state


def install_weights(trainer, values, seed):
    """The seed's arrays into the trainer's state: both networks'
    parameters and spectral-norm vectors, the loss network, the averaged
    generator as a copy, and the two noise streams' keys. The steps donate
    their state, so the program owns these arrays from here on."""
    import jax
    import jax.numpy as jnp

    used = set()
    state = dict(trainer.state)
    placed = jax.tree_util.tree_map(lambda x: x.sharding, trainer.state)
    for net in ("vars_G", "vars_D"):
        tree = dict(state[net])
        for collection in ("params", "spectral"):
            tree[collection] = program.graft(tree[collection], values, used)
        state[net] = tree
    state["loss_params"] = program.graft(state["loss_params"], values, used)
    program.require_all_used(values, used)
    if "ema_G" in state:
        state["ema_G"] = jax.tree_util.tree_map(
            lambda x: jnp.array(x, copy=True), state["vars_G"]["params"])
    keys = stream_keys(seed)
    state["rng_G"] = jnp.array(np.asarray(keys["G"]))
    state["rng_D"] = jnp.array(np.asarray(keys["D"]))
    # every leaf where `init_state` had placed it, or the steps would
    # compile again for the new placement
    trainer.state = jax.tree_util.tree_map(jax.device_put, state, placed)


def stream_keys(seed):
    import jax

    root = weights.seed_key(seed)
    return {"G": jax.random.fold_in(root, 0x6E01),
            "D": jax.random.fold_in(root, 0x6E02)}


def program_config(config, workload, cell_name, shrunk=False):
    """The program's config for a training cell: the benchmark's fixture
    (made once per checkout) as its data, its logs under the cache."""
    cfg = program.load_config(config, shrunk=shrunk)
    traffic = workload["traffic"]
    packed = fixtures.packed_cocostuff(
        os.path.join(harness.CACHE_DIR, "fixtures", config["name"]),
        n_imgs=int(traffic["fixture_samples"]),
        side=int(traffic["fixture_side"]))
    for split in ("train", "val"):
        cfg.data[split].roots = [packed]
    cfg.logdir = os.path.join(harness.CACHE_DIR, "logs", cell_name)
    os.makedirs(cfg.logdir, exist_ok=True)
    return cfg


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
    trainer.init_state(weights.seed_key(seed), sample)
    del sample
    reference = program.load_reference(config, "train")
    install_weights(trainer, weights.make(reference.spec(config["sizes"]),
                                          seed), seed)
    return trainer, Loop(trainer, train_loader, tm), tm


def _leaf_key(name):
    return int.from_bytes(hashlib.sha1(name.encode()).digest()[:4], "big")


def leaf_readings(flat):
    """(norms, projections) of {name: array}, traced: each leaf's l2 norm,
    and its inner product with a fixed vector of +-1 drawn from the leaf's
    name. The projection of a difference is as long as the difference, up
    to a random factor near one, so two sides' projections tell how far
    their gradients are apart without either side holding the other's."""
    import jax
    import jax.numpy as jnp

    norms, projections = {}, {}
    for name, value in flat.items():
        value = value.astype(jnp.float32)
        signs = jax.random.rademacher(
            jax.random.PRNGKey(_leaf_key(name)), value.shape, jnp.float32)
        norms[name] = jnp.linalg.norm(value)
        projections[name] = jnp.vdot(value, signs)
    return norms, projections


def leaf_readings_of(tree):
    """`leaf_readings` of a program tree, computed on the device in one
    program, fetched as floats."""
    import jax

    # lint: allow(bare-jit) -- the benchmark's own small reduction
    norms, projections = jax.jit(leaf_readings)(program.flatten(tree))
    return ({k: float(v) for k, v in norms.items()},
            {k: float(v) for k, v in projections.items()})


class Recorder:
    """What the first iterations leave for the comparison."""

    def __init__(self, sizes):
        self.sizes = sizes
        self.batches = []
        self.losses = []
        self.first_gradient_norms = None
        self.first_gradient_projections = None
        self.params_after = None

    def capture(self, data):
        self.batches.append({k: np.asarray(data[k])
                             for k in ("images", "label")})

    def after_step(self, trainer, dis, gen):
        self.losses.append({"D": float(dis["total"]),
                            "G": float(gen["total"])})
        state = trainer.state
        if len(self.losses) == 1:
            # Adam's first moment after one step is (1 - beta1) times the
            # gradient the optimizer was given
            scale = 1.0 - float(self.sizes["adam_beta1"])
            norms, projections = {}, {}
            for opt in ("opt_D", "opt_G"):
                n, p = leaf_readings_of(state[opt][0].mu)
                norms.update(n)
                projections.update(p)
            self.first_gradient_norms = {k: v / scale
                                         for k, v in norms.items()}
            self.first_gradient_projections = {
                k: v / scale for k, v in projections.items()}
        if len(self.losses) == CHECKED_STEPS:
            flat = {}
            for net in ("vars_D", "vars_G"):
                flat.update(program.flatten(state[net]["params"]))
            self.params_after = {k: np.asarray(v) for k, v in flat.items()}

    def numbers(self, values):
        """What `compare` takes, once the seed's initial `values` are at
        hand again; the parameters' host copy is dropped."""
        out = {"losses": self.losses,
               "first_gradient_norms": self.first_gradient_norms,
               "first_gradient_projections": self.first_gradient_projections,
               "param_change_norms": change_norms(self.params_after, values)}
        self.params_after = None
        return out


def reference_steps(reference, values, sizes, recorder, seed, precision,
                    noise_dtype):
    """The reference's losses, first gradient norms and parameters after
    the recorded steps, on the recorded batches."""
    import jax
    import jax.numpy as jnp

    initial = {k: np.asarray(x) for k, x in values.items()
               if not k.endswith("/u")}
    g, d, v = reference.split(values)
    g_train, g_u = reference._trainable(g), reference._vectors(g)
    d_train, d_u = reference._trainable(d), reference._vectors(d)
    nu_g = {k: jnp.zeros_like(x) for k, x in g_train.items()}
    nu_d = {k: jnp.zeros_like(x) for k, x in d_train.items()}

    def d_step(d_train, d_u, nu, g_all, batch, eps, count):
        (loss, new_u), grads = jax.value_and_grad(
            reference.d_loss, has_aux=True)(
                d_train, d_u, g_all, sizes, batch, eps, precision)
        new_p, new_nu = reference.adam(d_train, grads, nu, count,
                                       sizes["dis_lr"], sizes["adam_beta2"])
        return loss, new_p, new_u, new_nu, leaf_readings(grads)

    def g_step(g_train, g_u, nu, d_all, v, batch, eps, count):
        (loss, (terms, new_u)), grads = jax.value_and_grad(
            reference.g_loss, has_aux=True)(
                g_train, g_u, d_all, v, sizes, batch, eps, precision)
        new_p, new_nu = reference.adam(g_train, grads, nu, count,
                                       sizes["gen_lr"], sizes["adam_beta2"])
        # the total's terms have both signs and can all but cancel: its
        # gap is held against their weighted magnitudes
        weights_ = sizes["loss_weights"]
        scale = (abs(weights_["gan"] * terms["GAN"])
                 + abs(weights_["feature_matching"] * terms["FeatureMatching"])
                 + abs(weights_["kl"] * terms["GaussianKL"])
                 + abs(weights_["perceptual"] * terms["Perceptual"]))
        return (loss, scale), new_p, new_u, new_nu, leaf_readings(grads)

    def as_float32(tree):
        # a lower-precision control hands its vectors back in its own type
        return {k: x.astype(jnp.float32) for k, x in tree.items()}

    keys = stream_keys(seed)
    shape = (recorder.batches[0]["images"].shape[0], sizes["style_dims"])
    losses, first_norms, first_projections = [], {}, {}
    d_program = g_program = None
    for step, host_batch in enumerate(recorder.batches):
        batch = {k: jnp.asarray(x) for k, x in host_batch.items()}
        eps = {net: jax.random.normal(
            reference.noise_key(keys[net], step), shape,
            noise_dtype).astype(jnp.float32) for net in ("D", "G")}
        d_args = (d_train, d_u, nu_d, {**g_train, **g_u}, batch, eps["D"],
                  jnp.int32(step))
        if d_program is None:
            d_program = harness.compile_reference(d_step, *d_args,
                                                  donate_argnums=(0, 2))
        loss_d, d_train, new_u, nu_d, read_d = d_program(*d_args)
        d_u = {**d_u, **as_float32(new_u)}
        g_args = (g_train, g_u, nu_g, {**d_train, **d_u}, v, batch,
                  eps["G"], jnp.int32(step))
        if g_program is None:
            g_program = harness.compile_reference(g_step, *g_args,
                                                  donate_argnums=(0, 2))
        (loss_g, scale_g), g_train, new_u, nu_g, read_g = g_program(*g_args)
        g_u = {**g_u, **as_float32(new_u)}
        losses.append({"D": float(loss_d), "G": float(loss_g),
                       "G_scale": float(scale_g)})
        if step == 0:
            first_norms = {k: float(x)
                           for k, x in {**read_d[0], **read_g[0]}.items()}
            first_projections = {
                k: float(x) for k, x in {**read_d[1], **read_g[1]}.items()}
    return {"losses": losses, "first_gradient_norms": first_norms,
            "first_gradient_projections": first_projections,
            "param_change_norms": change_norms({**d_train, **g_train},
                                               initial)}


def change_norms(after, initial):
    """{name: l2 norm of the parameter's change} on the host, in float64."""
    return {k: float(np.linalg.norm(np.asarray(after[k], np.float64)
                                    - np.asarray(initial[k], np.float64)))
            for k in after}


def worst_leaf_gap(ours, theirs):
    """Largest gap over the leaves between our norm and the reference's,
    measured against the reference's norm of that leaf or of the median
    leaf, whichever is larger. Returns (gap, leaf)."""
    median = float(np.median(list(theirs.values())))
    return max((abs(ours[k] - theirs[k]) / max(theirs[k], median), k)
               for k in theirs)


def loss_gaps(recorded, ref):
    """{"D": [...], "G": [...]}: each step's gap between the two sides'
    loss, relative to the reference's loss, or to the summed magnitudes of
    its weighted terms where the reference gives them (`G_scale`) and they
    are larger."""
    return {net: [abs(a[net] - b[net])
                  / max(abs(b[net]), b.get(net + "_scale", 0.0), 1e-6)
                  for a, b in zip(recorded["losses"], ref["losses"])]
            for net in ("D", "G")}


def compare(recorded, ref):
    """The numbers of `correct`, {name: value}, and the leaves at fault.
    Both sides give `losses`, `first_gradient_norms`,
    `first_gradient_projections` and `param_change_norms`. Of the losses
    the first step's is compared: the later steps' gaps (`loss_gaps`, in
    the result line beside the losses) swing with the seed, G's third from
    0.03 % to 12 % between sound runs and to 5 % for the reference itself
    in bfloat16 (PERF.md, PR 23), since two sign-like Adam steps in
    bfloat16 stand between them and the seeded weights. The later steps
    are held by the parameters' change after the three."""
    out, where = {}, {}
    for net, gaps in loss_gaps(recorded, ref).items():
        out[f"loss_{net}_first_rel"] = gaps[0]
    gap, leaf = worst_leaf_gap(recorded["first_gradient_norms"],
                               ref["first_gradient_norms"])
    out["first_gradient_norm_worst_leaf"] = gap
    where["first_gradient_norm_worst_leaf"] = leaf
    # how far apart the two first gradients are, by the median leaf: the
    # norms above hardly feel rounding noise, which averages out of a norm
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
    return out, where


def run(loaded, seed, seconds, trace, devices, peaks, clock, shrunk=False):
    import jax
    import jax.numpy as jnp

    config, workload = loaded["config"], loaded["workload"]
    cell, spec = loaded["cell"], loaded["spec"]
    sizes = config["sizes"]
    watch = harness.CompileWatch()
    trainer, loop, tm = build(config, workload, cell["name"], seed,
                              shrunk=shrunk)
    noise_dtype = (jnp.bfloat16 if trainer.compute_dtype == jnp.bfloat16
                   else jnp.float32)
    batch_size = int(sizes["train_batch_size"])

    # the first iterations: through the window's own call and feed
    recorder = Recorder(sizes)
    for _ in range(CHECKED_STEPS):
        dis, gen = loop.step(capture=recorder.capture)
        recorder.after_step(trainer, dis, gen)
    for _ in range(WARM_STEPS - CHECKED_STEPS):
        loop.step()
    jax.block_until_ready(trainer.state)

    trace_dir = os.path.join(harness.CACHE_DIR, "trace")
    tracing = False
    before = watch.snapshot()
    wait_before, iterations_before = loop.wait_s, loop.iteration
    del loop.host_wait_ms[:]
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
            loop.step()
        # every iteration the window started is finished before it closes
        jax.block_until_ready(trainer.state)
        window_s = time.perf_counter() - t_begin
    finally:
        if tracing:
            jax.profiler.stop_trace()
    after = watch.snapshot()
    iterations = loop.iteration - iterations_before

    device = harness.describe_devices(devices)
    reference = program.load_reference(config, "train")
    observed = {
        "setup_s": setup_s, "window_s": window_s,
        "iterations": iterations, "images": iterations * batch_size,
        "chips": len(devices),
        "feed_wait_s": loop.wait_s - wait_before,
        "host_wait_ms": list(loop.host_wait_ms),
        "step_flops": reference.step_flops(sizes, batch_size),
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

    # the program goes before the reference runs: the peak above is the
    # program's own, and the reference needs the whole chip
    loop.close()
    tm.shutdown()
    trainer.state = None
    del trainer, loop
    gc.collect()

    values = weights.make(reference.spec(sizes), seed)
    recorded = recorder.numbers(values)
    ref = reference_steps(reference, values, sizes, recorder, seed,
                          "float32", noise_dtype)
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
    return {"correct": correct, "attempted": iterations, "failed": 0,
            "metrics": metrics, "device": device, "compared": compared,
            "breakdown": breakdown,
            "extra": {"cache": after, "setup_s": setup_s,
                      "window_s": window_s, "worst_leaves": where,
                      "losses": {"program": recorder.losses,
                                 "reference": ref["losses"]},
                      "loss_gaps_by_step": loss_gaps(recorded, ref)}}
