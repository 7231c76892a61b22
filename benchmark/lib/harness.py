"""What every driver shares: the cell's files, the device check, the
compile cache's place, the watch on compilation, and the result line.

The harness is driven by data. `BENCHMARK.json` names a cell; its
configuration is `configs/<config>.json`, its driver and traffic are
`workloads/<cell>.json`, each metric is `metrics/<metric>.py`. A later PR
adds a configuration, a cell or a metric by adding such files.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, ".cache")
NO_CHIP_EXIT = 3


class BenchmarkError(RuntimeError):
    """The run cannot be a measurement; no result line is printed."""


def read_json(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_spec(root=ROOT, bench_dir=None):
    """`BENCHMARK.json`, with the cells and metrics of
    `benchmark/candidates.json` after its own: cells that were measured but
    are not admitted to the driver's check load like any other."""
    bench_dir = bench_dir or os.path.join(root, "benchmark")
    spec = read_json(os.path.join(root, "BENCHMARK.json"))
    candidates = os.path.join(bench_dir, "candidates.json")
    if os.path.exists(candidates):
        extra = read_json(candidates)
        for group in ("workloads", "end_to_end", "per_layer"):
            have = {e["name"] for e in spec[group]}
            spec[group] = spec[group] + [e for e in extra.get(group, [])
                                         if e["name"] not in have]
    return spec


def load_cell(name, root=ROOT, bench_dir=None):
    """{"cell", "config", "workload", "spec"} of the cell `name`, read
    from `BENCHMARK.json` (or the candidates) and the files it points at."""
    bench_dir = bench_dir or os.path.join(root, "benchmark")
    spec = load_spec(root, bench_dir)
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise BenchmarkError(
            f"no cell {name!r} in BENCHMARK.json (has: {sorted(cells)})")
    cell = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = read_json(os.path.join(root, entry["file"]))
    workload = read_json(os.path.join(bench_dir, "workloads",
                                      name + ".json"))
    return {"cell": cell, "config": config, "workload": workload,
            "spec": spec, "bench_dir": bench_dir}


def metrics_of(spec, cell_name, group):
    """The metrics of `group` ("end_to_end" or "per_layer") that the cell
    `cell_name` reports: those without a `workloads` key, and those whose
    key lists the cell."""
    return [m for m in spec[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def load_by_path(path, name):
    module_spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module


def load_driver(name):
    return importlib.import_module(f"benchmark.drivers.{name}")


def read_metrics(metrics, observed, bench_dir=BENCH_DIR):
    """{name: {"value", "unit"}} from each metric's own reader. A reader
    that finds nothing returns None and the metric is left out."""
    out = {}
    for m in metrics:
        reader = load_by_path(
            os.path.join(bench_dir, "metrics", m["name"] + ".py"),
            "benchmark_metric_" + m["name"].replace(".", "_"))
        value = reader.read(observed)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ------------------------------------------------------------- the device


def place_caches():
    """Before JAX is imported: the persistent compilation cache at one
    fixed place inside the checkout, and no cap on its size (the chip
    machine's 192 MiB cap evicts programs this benchmark needs again).
    The program reads the same variable and sets no directory of its own."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE_DIR, "jax")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.makedirs(os.path.join(CACHE_DIR, "jax"), exist_ok=True)


def require_chips(chips, peaks):
    """The devices of this run, or exit: a measurement needs a TPU of a
    kind in the table of peaks, and as many chips as the cell asks for."""
    try:
        import jax

        devices = jax.devices()
    except RuntimeError as e:
        print(f"benchmark: no accelerator: {e}", file=sys.stderr)
        raise SystemExit(NO_CHIP_EXIT)
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"benchmark: platform {dev.platform!r} is not a TPU; a run "
              "here is no measurement", file=sys.stderr)
        raise SystemExit(NO_CHIP_EXIT)
    if dev.device_kind not in peaks["kinds"]:
        print(f"benchmark: device kind {dev.device_kind!r} has no row in "
              "benchmark/peaks.json", file=sys.stderr)
        raise SystemExit(NO_CHIP_EXIT)
    if len(devices) < chips:
        print(f"benchmark: {len(devices)} chips found, the cell asks for "
              f"{chips}", file=sys.stderr)
        raise SystemExit(NO_CHIP_EXIT)
    return devices[:chips]


def start(cell_name):
    """What the command and every tool begin with: the cell's files, the
    caches' place (before JAX is imported), the table of peaks and the
    chips. Returns (loaded, peaks, devices)."""
    loaded = load_cell(cell_name)
    place_caches()
    peaks = read_json(os.path.join(BENCH_DIR, "peaks.json"))
    return loaded, peaks, require_chips(int(loaded["cell"]["chips"]), peaks)


def describe_devices(devices):
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


# compile the benchmark's own reference programs for a short compile, not
# a fast run: they run a handful of times, and at the default effort the
# float32 training steps take ten minutes to compile (PERF.md, PR 23)
REFERENCE_COMPILE_OPTIONS = {"exec_time_optimization_effort": -1.0}


def compile_reference(fn, *args, donate_argnums=()):
    """`fn` compiled ahead of time for `args`, through the persistent
    cache, with the reference programs' compile options."""
    import jax

    # lint: allow(bare-jit) -- the benchmark's own reference program
    return jax.jit(fn, donate_argnums=donate_argnums).lower(*args).compile(
        compiler_options=REFERENCE_COMPILE_OPTIONS)


# ------------------------------------------------------ compilation watch


class CompileWatch:
    """Counts what JAX compiles or fetches from its persistent cache, so
    that a window can show that it built no program. `hits` and `misses`
    are the persistent cache's; `compiles` counts every backend compile
    request, cached or not."""

    _EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
               "/jax/compilation_cache/cache_misses": "misses",
               "/jax/compilation_cache/compile_requests_use_cache":
                   "compiles"}

    def __init__(self):
        import jax

        self.counts = {"hits": 0, "misses": 0, "compiles": 0}
        jax.monitoring.register_event_listener(self._listen)

    def _listen(self, event, **kwargs):
        key = self._EVENTS.get(event)
        if key:
            self.counts[key] += 1

    def snapshot(self):
        return dict(self.counts)


# ------------------------------------------------------------ result line


def verdict(compared):
    """Whether every number of `compared` ({name: {"value", "limit"}}) is
    there and at or under its limit."""
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in compared.values())


def emit(correct, attempted, failed, metrics, device, compared,
         breakdown=None, extra=None):
    """The last line of standard output, and before it, on standard
    error, each number compared beside its limit."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    line.update(extra or {})
    line["compared"] = compared
    sys.stdout.flush()
    for name, c in compared.items():
        print(f"compared {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line))
    sys.stdout.flush()


class Clock:
    """Seconds since the process began (as near as Python can say)."""

    def __init__(self, t0=None):
        self.t0 = time.time() if t0 is None else t0

    def since_start(self):
        return time.time() - self.t0
