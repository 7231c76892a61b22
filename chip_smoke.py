#!/usr/bin/env python
"""Chip smoke: zoo-width SPADE trains, checkpoints and serves on a TPU.

    python chip_smoke.py              one chip: train.py then inference.py
    python chip_smoke.py --chips 4    data-parallel training on four chips
                                      against the same run on one

Default mode drives ``configs/projects/spade/cocostuff/base128_bs4.yaml``
as shipped (nf 128, 256x256, 185 label channels, bf16, bs 4) through the
normal entry points in this one process: ``train.main()`` for 6
pipeline-fed iterations and a checkpoint, then ``inference.main()`` on
that checkpoint through the serving engine. Data is a COCO-Stuff-shaped
packed fixture made from ``--seed``; weights are random. There is no CPU
mode: without a TPU the script exits non-zero. The CPU rehearsal of the
same flow is tests/test_chip_smoke.py.

Every line but the last is an observation from one short run, not a
metric. The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import glob
import io
import json
import math
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ZOO_CONFIG = os.path.join(ROOT, "configs", "projects", "spade", "cocostuff",
                          "base128_bs4.yaml")
TRAIN_ITERS = 6
MIN_SERVED_IMAGES = 8
# the global batch of the four-chip comparison, one image per chip. AOT
# for the described chip (PR 22, PERF.md) says one v5e chip holds 8 at
# zoo width with ``remat: blocks`` (G step 13.03 GiB) and not 16 (16.32
# GB of 15.75); 4 keeps the two cold compiles of each layout, which are
# most of this mode's time, as small as they get.
DP_GLOBAL_BATCH = 4
# what both layouts compute from the same parameters and the same batch
# differs only by the order of reduction
LOSS_RTOL = 1e-2


def fail(message):
    raise SystemExit(f"chip_smoke: FAIL: {message}")


_T0 = time.time()


def say(message):
    print(f"chip_smoke: [{time.time() - _T0:6.1f} s] {message}", flush=True)


def require_tpu(n_chips):
    """The devices to run on, or exit non-zero: there is no CPU mode."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but JAX found platform "
            f"{devices[0].platform!r} ({devices[0].device_kind}); nothing "
            f"was run")
    if len(devices) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} chip(s), JAX found "
                         f"{len(devices)}")
    return devices


# ---------------------------------------------------------------- config


def derive_config(base_yaml, out_dir, seed, mesh_devices, name,
                  global_batch=None, same_samples=False, n_imgs=64):
    """Write the fixture and a config that differs from ``base_yaml``
    only in where the data is (``data.*.roots``, ``is_packed``, a
    ``test_data`` block that is the file's own val split),
    ``perceptual_loss.allow_random_init``, the
    iteration/snapshot counters and a mesh of ``mesh_devices`` devices.
    ``global_batch`` and ``same_samples`` are the four-chip comparison's:
    both of its runs train at one global batch, on the file's own
    deterministic val augmentations, so that they see the same samples.
    Returns the derived file's path."""
    import copy

    import yaml

    from imaginaire_tpu.data.fixtures import make_packed_cocostuff_fixture

    with open(base_yaml) as f:
        cfg = yaml.safe_load(f)
    data = cfg["data"]
    n_classes = next(spec["seg_maps"]["num_channels"]
                     for spec in data["input_types"] if "seg_maps" in spec)
    packed = make_packed_cocostuff_fixture(
        os.path.join(out_dir, "data"), n_imgs=n_imgs, seed=seed,
        n_classes=n_classes)
    for split in ("train", "val"):
        data[split]["roots"] = [packed]
        data[split]["is_packed"] = True
        data[split].pop("is_lmdb", None)
    if global_batch is not None:
        data["train"]["batch_size"] = int(global_batch)
    if same_samples:
        data["train"]["augmentations"] = copy.deepcopy(
            data["val"]["augmentations"])
    test_data = {k: copy.deepcopy(v) for k, v in data.items()
                 if k not in ("train", "val")}
    test_data["test"] = copy.deepcopy(data["val"])
    cfg["test_data"] = test_data
    perceptual = cfg["trainer"]["perceptual_loss"]
    perceptual["allow_random_init"] = True
    perceptual.pop("weights_path", None)
    # every iteration logs its losses; nothing but the final checkpoint
    # is saved inside the run (--max_iter ends it)
    cfg["logging_iter"] = 1
    cfg["image_save_iter"] = 10 ** 9
    cfg["snapshot_save_iter"] = 10 ** 9
    cfg["snapshot_save_epoch"] = 10 ** 9
    cfg.setdefault("runtime", {})["mesh"] = {
        "axes": ["data"], "shape": [int(mesh_devices)]}
    path = os.path.join(out_dir, f"{name}.yaml")
    with open(path, "w") as f:
        yaml.safe_dump(cfg, f)
    return path


def describe_config(cfg_path):
    from imaginaire_tpu.config import Config
    from imaginaire_tpu.utils.data import (
        get_paired_input_label_channel_number,
    )

    cfg = Config(cfg_path)
    crop = str(cfg.data.train.augmentations.get(
        "random_crop_h_w", cfg.data.train.augmentations.get(
            "center_crop_h_w")))
    mp = cfg.trainer.get("mixed_precision", None) or {}
    dtype = mp.get("compute_dtype", "float32") if mp.get("enabled") \
        else "float32"
    return {"gen_num_filters": int(cfg.gen.num_filters),
            "dis_num_filters": int(cfg.dis.num_filters),
            "crop_h_w": crop.replace(" ", ""),
            "label_channels": int(
                get_paired_input_label_channel_number(cfg.data)),
            "batch_size": int(cfg.data.train.batch_size),
            "compute_dtype": str(dtype),
            "remat": str(cfg.gen.get("remat", "none"))}


# ---------------------------------------------------------------- phases


@contextlib.contextmanager
def _argv(*args):
    old = sys.argv
    sys.argv = [str(a) for a in args]
    try:
        yield
    finally:
        sys.argv = old


class _Stamped(io.StringIO):
    """Stdout of an entry point: passed on line by line with the
    script's clock in front, and kept."""

    def __init__(self, out):
        super().__init__()
        self._out = out
        self._at_line_start = True

    def write(self, text):
        for piece in text.splitlines(keepends=True):
            if self._at_line_start:
                self._out.write(f"[{time.time() - _T0:6.1f} s] ")
            self._out.write(piece)
            self._at_line_start = piece.endswith("\n")
        self._out.flush()
        return super().write(text)


def _run_entry_point(module_main, *argv):
    stamped = _Stamped(sys.stdout)
    with _argv(*argv), contextlib.redirect_stdout(stamped):
        result = module_main()
    return result, stamped.getvalue()


def run_train(cfg_path, logdir, seed, max_iter=TRAIN_ITERS):
    """``train.py --config <derived> --logdir <dir> --max_iter N``, in
    this process. Returns the trainer ``train.main`` ended with."""
    import train

    trainer, _ = _run_entry_point(
        train.main, "train.py", "--config", cfg_path, "--logdir", logdir,
        "--max_iter", max_iter, "--seed", seed)
    return trainer


def run_serve(cfg_path, logdir, output_dir, seed):
    """``inference.py --config <derived> --logdir <dir> --output_dir
    <out>``, in this process. Returns what it printed."""
    import inference

    _, printed = _run_entry_point(
        inference.main, "inference.py", "--config", cfg_path, "--logdir",
        logdir, "--output_dir", output_dir, "--seed", seed)
    return printed


# ---------------------------------------------------------------- checks


def _events(logdir):
    from imaginaire_tpu.telemetry.report import load_events

    return load_events(os.path.join(logdir, "telemetry.jsonl"))


def _ledger(logdir):
    with open(os.path.join(logdir, "compile_ledger.jsonl")) as f:
        return [json.loads(line) for line in f if line.strip()]


def losses_by_iteration(events):
    """{iteration: {name: value}} of the logged D and G losses."""
    out = {}
    for e in events:
        if e.get("kind") == "counter" and e["name"].split("/")[0] in (
                "dis_update", "gen_update"):
            out.setdefault(int(e["step"]), {})[e["name"]] = float(
                e["value"])
    return out


def first_step_evidence(events):
    """What the first iteration computes BEFORE any optimiser step of
    this run lies between the parameters and the number: the D step's
    loss, the norms of its gradient (whole and for each sub-network;
    fp32, taken after the data axis has reduced them), and the terms of
    the G loss that do not go through the discriminator."""
    out = {}
    for e in events:
        if e.get("kind") != "counter":
            continue
        name, step = e["name"], int(e.get("step") or 0)
        if (step == 0 and name.startswith("health/D/grad_norm/")) or (
                step == 1 and name in ("dis_update/total",
                                       "gen_update/Perceptual")):
            out.setdefault(name, float(e["value"]))
    return out


def check_training_run(logdir, iters=TRAIN_ITERS, since=0.0):
    """Fails unless every logged loss of every iteration is finite, each
    step program compiled once and ``check_run_health --max-recompiles
    0`` passes. ``since``: when the run began (the compile ledger is the
    process's, and replays an earlier run's records into a later run's
    logdir). Returns the run's observations."""
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    import check_run_health

    events = _events(logdir)
    losses = losses_by_iteration(events)
    if sorted(losses) != list(range(1, iters + 1)):
        fail(f"losses were logged for iterations {sorted(losses)}, "
             f"expected 1..{iters}")
    for it, row in sorted(losses.items()):
        if not {"dis_update/total", "gen_update/total"} <= set(row):
            fail(f"iteration {it} logged no D or no G total: {sorted(row)}")
        bad = {k: v for k, v in row.items() if not math.isfinite(v)}
        if bad:
            fail(f"non-finite losses at iteration {it}: {bad}")
    compiles = {}
    for rec in _ledger(logdir):
        if rec.get("kind") == "compile" and rec["t"] >= since:
            compiles.setdefault(rec["label"], []).append(rec)
    for label in ("dis_step", "gen_step"):
        recs = compiles.get(label, ())
        if len(recs) != 1 or recs[0].get("counted_recompile"):
            fail(f"{label} compiled {len(recs)} times, expected once: "
                 f"{[r.get('expected') or 'first' for r in recs]}")
    # the gate is recompiles; random weights on a noise fixture sit
    # outside the D/G-ratio band a real run is held to, and the graph
    # auditor's findings are printed below, not gated here
    rc = check_run_health.main([logdir, "--max-recompiles", "0",
                                "--max-dg-breaches", str(iters),
                                "--max-graph-violations", str(10 ** 6)])
    if rc != 0:
        fail(f"check_run_health {logdir} --max-recompiles 0 exited {rc}")
    iter_ms = [float(e["value"]) * 1e3 for e in events
               if e.get("kind") == "counter"
               and e["name"] == "time/iteration"]
    counters = {e["name"]: e["value"] for e in events
                if e.get("kind") == "counter"}
    return {
        "losses": losses,
        "first_step": first_step_evidence(events),
        "compile_s": {label: [round((r["lower_ms"] + r["compile_ms"])
                                    / 1e3, 2) for r in recs]
                      for label, recs in sorted(compiles.items())},
        "compile_reasons": {label: [r.get("expected") or "first"
                                    for r in recs]
                            for label, recs in sorted(compiles.items())},
        # host clock, losses read back every iteration; the first two
        # iterations compile and settle
        "step_ms_p50_after_warmup": (round(statistics.median(iter_ms[2:]), 2)
                                     if len(iter_ms) > 2 else None),
        "graph_violations": counters.get("xla/graph_violations"),
        "recompiles": counters.get("xla/recompiles"),
    }


def check_device_evidence(logdir, device_kind):
    """Fails unless the run's telemetry shows the device: ``perf/mfu``
    computed from the peak table's row for this ``device_kind``, and the
    ``mem/*`` counters that only a backend with ``memory_stats()``
    gives (None on the CPU)."""
    events = _events(logdir)
    counters = {e["name"] for e in events if e.get("kind") == "counter"}
    meta = next((e for e in events if e.get("kind") == "meta"
                 and e["name"] == "step_flops"), None)
    if "perf/mfu" not in counters or meta is None:
        fail(f"no perf/mfu in {logdir}: "
             f"{(meta or {}).get('peak_source', 'no step_flops meta')}")
    if not str(meta.get("peak_source", "")).startswith(
            f"device_kind:{device_kind}"):
        fail(f"perf/mfu was not computed from {device_kind}'s own row: "
             f"{meta.get('peak_source')}")
    mem = sorted(c for c in counters if c.startswith("mem/"))
    if not any(c.endswith("/peak_bytes_in_use") for c in mem):
        fail(f"no mem/*/peak_bytes_in_use counter in {logdir}: {mem}")
    return {"peak_flops": meta["peak_flops"],
            "peak_source": meta["peak_source"], "mem_counters": len(mem)}


def check_served(logdir, output_dir, printed, checkpoint,
                 min_images=MIN_SERVED_IMAGES):
    """Fails unless the checkpoint was really loaded, at least
    ``min_images`` images came out finite and not constant, and every
    serving executable compiled once."""
    import cv2
    import numpy as np

    if "fresh weights" in printed:
        fail("inference.py found no checkpoint and served fresh weights")
    verified = [e for e in _events(logdir) if e.get("kind") == "meta"
                and e["name"] == "ckpt/verified"]
    if not any(os.path.basename(str(e.get("checkpoint")))
               == os.path.basename(checkpoint) and e.get("verified")
               for e in verified):
        fail(f"no verified restore of {checkpoint} in the serve telemetry: "
             f"{verified}")
    paths = sorted(p for p in glob.glob(
        os.path.join(output_dir, "**", "*"), recursive=True)
        if p.lower().endswith((".jpg", ".jpeg", ".png")))
    if len(paths) < min_images:
        fail(f"{len(paths)} image(s) under {output_dir}, expected at least "
             f"{min_images}")
    for p in paths:
        img = cv2.imread(p, cv2.IMREAD_UNCHANGED)
        if img is None:
            fail(f"{p} is not a readable image")
        img = img.astype(np.float32)
        if not np.isfinite(img).all() or float(img.std()) < 1e-3:
            fail(f"{p} is constant or not finite (std {img.std()})")
    serve = {}
    for rec in _ledger(logdir):
        if rec.get("kind") == "compile" and rec["label"].startswith(
                "serve/"):
            serve.setdefault(rec["label"], []).append(rec)
    if not serve:
        fail("no serve/* program in the compile ledger: the engine path "
             "was not taken")
    for label, recs in serve.items():
        if len(recs) != 1 or any(r.get("counted_recompile") for r in recs):
            fail(f"{label} compiled {len(recs)} times, expected once")
    return {"images": len(paths), "shape": list(img.shape),
            "compile_s": {label: round((recs[0]["lower_ms"]
                                        + recs[0]["compile_ms"]) / 1e3, 2)
                          for label, recs in sorted(serve.items())}}


def check_data_parallel_layout(trainer, n_devices):
    """Fails unless the state is laid out as the partition plan says:
    a batch leaf sharded over ``n_devices`` devices, a parameter
    replicated on all of them."""
    import jax

    feed = iter(trainer.data_prefetcher(trainer.train_data_loader))
    try:
        images = next(feed)["images"]
    finally:
        feed.close()
    shards = images.addressable_shards
    if len({s.device for s in shards}) != n_devices or any(
            s.data.shape[0] * n_devices != images.shape[0] for s in shards):
        fail(f"batch leaf images{images.shape} is not sharded over "
             f"{n_devices} devices: {[s.data.shape for s in shards]}")
    param = jax.tree_util.tree_leaves(
        trainer.state["vars_G"]["params"])[0]
    pshards = param.addressable_shards
    if len({s.device for s in pshards}) != n_devices or any(
            s.data.shape != param.shape for s in pshards):
        fail(f"parameter{param.shape} is not replicated on {n_devices} "
             f"devices: {[s.data.shape for s in pshards]}")
    return {"batch_leaf": f"images{tuple(images.shape)} as {len(shards)} x "
                          f"{tuple(shards[0].data.shape)}",
            "parameter": f"{tuple(param.shape)} on {len(pshards)} devices"}


def check_collectives(trainer):
    """Fails unless the compiled D and G steps hold the all-reduces of
    the gradients (emitted in the backward pass) and, where the config
    has sync-batch norms, of their statistics (emitted in the forward
    pass, under the norm's own scope). Reads ``compiled.as_text()``."""
    import re

    from imaginaire_tpu.config import cfg_get

    anp = cfg_get(trainer.cfg.gen, "activation_norm_params", None) or {}
    sync_batch = "sync_batch" in (
        str(cfg_get(anp, "activation_norm_type", "")),
        str(cfg_get(trainer.cfg.gen, "global_adaptive_norm_type", "")))
    out = {}
    for label, program in (("dis_step", trainer._jit_dis_step),
                           ("gen_step", trainer._jit_gen_step)):
        compiled = program.executables()[-1]  # the committed layout's
        names = [(re.search(r'op_name="([^"]*)"', line) or [None, ""])[1]
                 for line in compiled.as_text().splitlines()
                 if re.search(r"\ball-reduce(-start)?\(", line)]
        grads = [n for n in names if "transpose(jvp(" in n]
        stats = [n for n in names
                 if "BatchNorm" in n and "transpose(" not in n]
        if not grads:
            fail(f"{label}: no all-reduce of gradients in the compiled "
                 f"step ({len(names)} all-reduce ops)")
        if sync_batch and not stats:
            fail(f"{label}: no all-reduce of sync-batch statistics in the "
                 f"compiled step ({len(names)} all-reduce ops)")
        out[label] = {"all_reduce_ops": len(names),
                      "of_gradients": len(grads),
                      "of_sync_batch_statistics": len(stats)}
    return out


# ----------------------------------------------------------------- modes


def _versions():
    from importlib.metadata import PackageNotFoundError, version

    out = {}
    for pkg in ("jax", "jaxlib", "libtpu", "flax", "orbax-checkpoint"):
        try:
            out[pkg] = version(pkg)
        except PackageNotFoundError:
            out[pkg] = "not installed"
    return out


def _peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _release(trainer):
    """The next phase builds a trainer of its own and needs the memory
    this one holds: its state on the device, and on the host the copy of
    every leaf that the checkpoint's checksum pass left on the arrays,
    the step executables, and what the allocator kept of the compiles
    (PR 22, measured for the described v5e: one zoo-width G-step compile
    leaves 15.7 GiB resident, 12.0 after ``malloc_trim``, 8.6 once the
    executable is gone too; the one-chip host has 40 GiB)."""
    import gc

    import jax

    from imaginaire_tpu.telemetry import xla_obs

    trainer.state = None
    trainer._jit_dis_step = trainer._jit_gen_step = None
    jax.clear_caches()
    gc.collect()
    xla_obs.trim_host_heap()
    charged = _charged_gib()
    say(f"released the trainer: host RSS now {_host_rss_gib()} GiB"
        + (f", {charged:.2f} GiB charged" if charged is not None else ""))


def _host_rss_gib():
    with open("/proc/self/status") as f:
        kib = next(int(line.split()[1]) for line in f
                   if line.startswith("VmRSS"))
    return round(kib / 2 ** 20, 2)


_CGROUP_USAGE = ("/sys/fs/cgroup/memory.current",
                 "/sys/fs/cgroup/memory/memory.usage_in_bytes")
_charged_peak = [None]


def _charged_gib():
    """What the machine's memory cgroup charges right now, or None
    where there is none to read."""
    for path in _CGROUP_USAGE:
        try:
            with open(path) as f:
                return int(f.read()) / 2 ** 30
        except (OSError, ValueError):
            continue
    return None


def _watch_charged_memory(period_s=1.0):
    """Keep the peak of ``_charged_gib`` (the cgroup files here keep
    none themselves). It is what the machine's limit is held against;
    the resident set is not: once the TPU runtime is up this process
    has 13.2 GiB resident of which 5.0 are charged, the rest being the
    runtime's mappings of the device (my chip run, PR 22)."""
    import threading

    def watch():
        while True:
            now = _charged_gib()
            if now is not None:
                _charged_peak[0] = max(_charged_peak[0] or 0.0, now)
            time.sleep(period_s)

    if _charged_gib() is not None:
        threading.Thread(target=watch, daemon=True,
                         name="chip-smoke-memory").start()


def _host_peak_gib():
    """'<peak resident set> GiB resident, <peak> GiB charged': this
    process's peak resident set so far, and the peak of what the
    machine charged for it (the chip machine gives one chip's host 40
    GiB)."""
    import resource

    rss = round(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
                / 2 ** 20, 2)
    charged = _charged_peak[0]
    return (f"{rss} GiB resident, "
            + (f"{charged:.2f} GiB charged to the machine's cgroup"
               if charged is not None else "no cgroup usage to read"))


def smoke_one_chip(out, seed, devices):
    """Train 6 iterations through train.py, then serve from that
    checkpoint through inference.py's engine path. One device."""
    from imaginaire_tpu.utils import checkpoint as ckpt_lib

    device = devices[0]
    cfg_path = derive_config(ZOO_CONFIG, out, seed, mesh_devices=1,
                             name="one_chip")
    say(f"config {cfg_path}: {json.dumps(describe_config(cfg_path))}")
    logdir = os.path.join(out, "run")
    t0 = time.time()
    _release(run_train(cfg_path, logdir, seed))
    train_s = time.time() - t0
    seen = check_training_run(logdir)
    say(f"train: {TRAIN_ITERS} pipeline-fed iterations in {train_s:.1f} s "
        f"(compile included), compile seconds {seen['compile_s']} "
        f"{seen['compile_reasons']}, "
        f"step ms p50 after warm-up {seen['step_ms_p50_after_warmup']}, "
        f"recompiles {seen['recompiles']}, graph-audit findings "
        f"{seen['graph_violations']}, host peak {_host_peak_gib()}")
    for it, row in sorted(seen["losses"].items()):
        say(f"  iteration {it}: D {row['dis_update/total']:.5g}  "
            f"G {row['gen_update/total']:.5g}")
    say(f"device evidence: "
        f"{json.dumps(check_device_evidence(logdir, device.device_kind))}")
    checkpoint = ckpt_lib.latest_checkpoint_path(logdir)
    if checkpoint is None:
        fail(f"train.py left no checkpoint under {logdir}")
    say(f"checkpoint {os.path.basename(checkpoint)}")
    output_dir = os.path.join(out, "served")
    t0 = time.time()
    printed = run_serve(cfg_path, logdir, output_dir, seed)
    serve_s = time.time() - t0
    served = check_served(logdir, output_dir, printed, checkpoint)
    say(f"serve: {served['images']} images {served['shape']} in "
        f"{serve_s:.1f} s (compile included), compile seconds "
        f"{served['compile_s']}, host peak {_host_peak_gib()}")
    say(f"peak bytes in use on {device.device_kind}: {_peak_bytes(device)}")


def smoke_data_parallel(out, seed, devices):
    """The same 6 iterations, same seed and samples, on a ``data: N``
    mesh and on one device, both at one global batch."""
    n = len(devices)
    global_batch = DP_GLOBAL_BATCH
    n_imgs = max(64, 8 * global_batch)
    runs = {}
    for name, mesh_devices in (("data_parallel", n), ("one_device", 1)):
        cfg_path = derive_config(ZOO_CONFIG, out, seed, mesh_devices,
                                 name=name, global_batch=global_batch,
                                 same_samples=True, n_imgs=n_imgs)
        say(f"{name}: mesh data:{mesh_devices}, config {cfg_path}: "
            f"{json.dumps(describe_config(cfg_path))}")
        logdir = os.path.join(out, f"run_{name}")
        t0 = time.time()
        trainer = run_train(cfg_path, logdir, seed)
        wall = time.time() - t0
        seen = check_training_run(logdir, since=t0)
        say(f"{name}: {TRAIN_ITERS} iterations in {wall:.1f} s (compile "
            f"included), compile seconds {seen['compile_s']} "
            f"{seen['compile_reasons']}, step ms p50 after warm-up "
            f"{seen['step_ms_p50_after_warmup']}")
        if mesh_devices > 1:
            say(f"{name} layout: "
                f"{json.dumps(check_data_parallel_layout(trainer, n))}")
            say(f"{name} collectives: "
                f"{json.dumps(check_collectives(trainer))}")
        say(f"{name}: peak bytes in use on device 0: "
            f"{_peak_bytes(devices[0])}, host peak {_host_peak_gib()}")
        runs[name] = seen
        _release(trainer)
        del trainer
    compare_layouts(runs["data_parallel"], runs["one_device"])


def compare_layouts(dp, one):
    """Gated at ``LOSS_RTOL``: ``first_step_evidence``, the numbers both
    layouts compute from the same parameters and the same batch, with
    only the order of reduction (sync-batch statistics, the gradients'
    all-reduce) between them. They move when the layouts do not see
    the same batch: with one sample of the four seen twice the D
    gradient's norm is 6.6 % off (11 % for one sub-network) and the
    perceptual term 0.3 % (on the CPU at zoo width, PR 22); the first D
    loss alone does not move (hinge loss on a fresh D's near-zero
    logits is 2 for any batch). The G loss's KL term is zero to bf16
    rounding at initialisation and has no relative tolerance to hold.

    Printed, not gated: everything downstream of an optimiser step, the
    first G loss's GAN and feature-matching terms included, which go
    through the D that has just made its first Adam step. With
    ``adam_beta1`` 0 that step is ``lr * sign(gradient)`` on every
    weight, so a weight whose gradient the two reduction orders round
    to different signs lands ``2 lr`` apart whatever the gradient's
    size. On four v5e chips in bf16 the first G totals were 12.6 % apart
    (PR 22); on four virtual CPU devices at the same width they are 6.4
    % apart with 1.4 % of D's weights moved in opposite directions, 0.24
    % apart in fp32, and 0.006 % apart with SGD in place of Adam
    (PERF.md, PR 22)."""
    names = sorted(set(dp["first_step"]) | set(one["first_step"]))
    if not any(n.startswith("health/D/grad_norm/") for n in names) \
            or "dis_update/total" not in names:
        fail(f"the first step left no D loss or no D gradient norms in "
             f"the telemetry to compare: {names}")
    broken = []
    for name in names:
        a, b = dp["first_step"].get(name), one["first_step"].get(name)
        if a is None or b is None:
            fail(f"first step: {name} was logged by one layout only "
                 f"(data-parallel {a}, one-device {b})")
        rel = abs(a - b) / max(abs(b), 1e-12)
        say(f"  first step, gated: {name} {a:.6g} vs {b:.6g} "
            f"(rel {rel:.2e})")
        if not rel <= LOSS_RTOL:
            broken.append(f"{name}: data-parallel {a} vs one-device {b}, "
                          f"relative difference {rel:.3e} > {LOSS_RTOL}")
    for it in range(1, TRAIN_ITERS + 1):
        row = []
        for key in sorted(set(dp["losses"][it]) & set(one["losses"][it])):
            if key.endswith("_acc"):
                continue
            a, b = dp["losses"][it][key], one["losses"][it][key]
            rel = abs(a - b) / max(abs(b), 1e-12)
            row.append(f"{key.replace('_update', '')} {a:.5g} vs {b:.5g} "
                       f"(rel {rel:.1e})")
        say(f"  iteration {it}, printed: " + "; ".join(row))
    if broken:
        fail("the layouts disagree on the first step: " + "; ".join(broken))


def result_line(device, count):
    return json.dumps({"ok": True,
                       "device": {"platform": device.platform,
                                  "kind": device.device_kind,
                                  "count": count}})


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=os.path.join(ROOT, "chip_smoke_out"),
                    help="fixture, configs, logdirs and served images go "
                         "here (git-ignored)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the data-parallel training comparison")
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)[:args.chips]

    from imaginaire_tpu.utils import compile_cache

    cache_dir = compile_cache.configure()
    cache_events = compile_cache.count_events()
    os.makedirs(args.out, exist_ok=True)
    _watch_charged_memory()
    say(f"versions {json.dumps(_versions())}")
    say(f"device {devices[0].device_kind} x{len(devices)}, compile cache "
        f"{cache_dir}")
    if args.chips == 1:
        smoke_one_chip(args.out, args.seed, devices)
    else:
        smoke_data_parallel(args.out, args.seed, devices)
    entries = [os.path.getsize(p) for p in glob.glob(
        os.path.join(cache_dir, "*-cache"))]
    say(f"persistent compile cache: {cache_events['hits']} hits, "
        f"{cache_events['misses']} misses; {len(entries)} entries, "
        f"{sum(entries) / 2 ** 20:.1f} MiB in {cache_dir} "
        f"(JAX_COMPILATION_CACHE_MAX_SIZE="
        f"{os.environ.get('JAX_COMPILATION_CACHE_MAX_SIZE', 'unset')}: "
        f"JAX evicts least-recently-used entries beyond it)")
    sys.stdout.flush()
    print(result_line(devices[0], len(devices)), flush=True)


if __name__ == "__main__":
    try:
        import imaginaire_tpu  # noqa: F401
    except ImportError as e:
        raise SystemExit(f"chip_smoke: needs the repository around it "
                         f"({e}); nothing was run")
    main()
