"""What the benchmark reads of the program's own spans.

Two sources. Durations come from the program's in-memory phase table
(`telemetry.get().window_summary()["phases"]`, which survives the
program's `shutdown()`): medians, counts and totals over the process's
iterations, the warm-up's among them. The split of the device's idle time
comes from the `imaginaire/<name>` events that every `telemetry.span`
leaves on the host plane of the run's own `.xplane.pb`, on the clock of
the device's operations; the file is parsed once for all its readers.

A program without such a span or event (the parent of the PR that added
them) gives `None`, and the metric is left out.
"""

from __future__ import annotations

import functools
import os

from benchmark.lib import harness, trace_reduce

PREFIX = "imaginaire/"


def phase_table():
    """{span name: {"count", "total_ms", "p50_ms", "p99_ms"}} of the
    process so far."""
    from imaginaire_tpu import telemetry

    return telemetry.get().window_summary().get("phases") or {}


def median_ms(name):
    return (phase_table().get(name) or {}).get("p50_ms")


def count(name):
    return (phase_table().get(name) or {}).get("count") or 0


def total_s(name):
    total = (phase_table().get(name) or {}).get("total_ms")
    return None if total is None else total / 1e3


def build_seconds(labels):
    """Seconds the compile ledger spent lowering and compiling (or loading
    from the persistent cache) the programs `labels`; None if it holds
    none of them."""
    from imaginaire_tpu.telemetry import xla_obs

    records = [r for r in xla_obs.ledger().records if r["label"] in labels]
    if not records:
        return None
    return sum(r["lower_ms"] + r["compile_ms"] for r in records) / 1e3


def merged(intervals):
    """The union of (start, end) intervals as sorted disjoint ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap_ns(gaps, cover):
    """Length of the part of the disjoint `gaps` that the disjoint, sorted
    `cover` intervals cover."""
    total = 0
    for gs, ge in gaps:
        for cs, ce in cover:
            if cs >= ge:
                break
            total += max(0, min(ge, ce) - max(gs, cs))
    return total


def idle_split(profile):
    """{"window_s", "idle_s", "covered_s"} of a profile: the traced part
    (first to last device operation), the time in it with no operation
    running on a device (averaged over the devices, as `trace_reduce`
    has it), and the part of that idle time during which a `data_wait`
    span of the program was open. None without device operations or
    without a single such span."""
    cover = merged((s, e) for s, e, name
                   in trace_reduce.host_marks(profile, PREFIX)
                   if name == "data_wait")
    per_plane = [[(s, e) for s, e, _ in
                  trace_reduce._events(plane, trace_reduce.OP_LINES)]
                 for plane in trace_reduce.device_planes(profile)]
    per_plane = [ops for ops in per_plane if ops]
    if not per_plane or not cover:
        return None
    lo = min(s for ops in per_plane for s, _ in ops)
    hi = max(e for ops in per_plane for _, e in ops)
    idle = covered = 0
    for ops in per_plane:
        gaps = trace_reduce.gaps(ops, lo, hi)
        idle += sum(e - s for s, e in gaps)
        covered += overlap_ns(gaps, cover)
    n = len(per_plane) * 1e9
    return {"window_s": (hi - lo) / 1e9, "idle_s": idle / n,
            "covered_s": covered / n}


@functools.lru_cache(maxsize=1)
def _idle_split_of_file(path, mtime):
    return idle_split(trace_reduce.load(path))


def traced_idle_split(observed):
    """`idle_split` of the run's own trace (the newest under the harness's
    trace directory), parsed once; None for a run that was not traced."""
    if not observed.get("trace"):
        return None
    try:
        path = trace_reduce.newest_xplane(
            os.path.join(harness.CACHE_DIR, "trace"))
    except FileNotFoundError:
        return None
    return _idle_split_of_file(path, os.path.getmtime(path))
