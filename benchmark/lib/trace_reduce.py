"""Reduction of a profiler trace (`.xplane.pb`) to device numbers.

Busy time is the union of the intervals in which an operation ran on a
device, averaged over the devices used; idle share is one minus busy over
the traced window. `device_ops` and `idle_gaps` are the breakdown the
ledger keeps. Read with nothing but JAX (`jax.profiler.ProfileData`).
"""

from __future__ import annotations

import glob
import os

# lines of a TPU device plane that hold single operations; the others
# ("Steps", "XLA Modules", "XLA TraceMe") hold whole programs or host marks
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


def newest_xplane(trace_dir):
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def device_planes(profile):
    return [p for p in profile.planes
            if p.name.startswith("/device:") and "TPU" in p.name.upper()]


def _events(plane, line_names):
    out = []
    for line in plane.lines:
        if line.name in line_names:
            for ev in line.events:
                out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                            ev.name))
    return out


def short_op_name(name):
    """'%fusion.35 = f32[256,8,33,3]{...} fusion(...)' to
    'fusion.35 f32[256,8,33,3]': the operation and what it yields."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:80]
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{head.lstrip('%')} {shape}"[:80]


def union_seconds(intervals):
    """Total length of the union of (start_ns, end_ns) intervals, in s."""
    total = 0
    end = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total / 1e9


def gaps(intervals, lo_ns, hi_ns):
    """(start_ns, end_ns) of the stretches of [lo, hi] no interval covers."""
    out = []
    cursor = lo_ns
    for s, e in sorted(intervals):
        if s > cursor:
            out.append((cursor, min(s, hi_ns)))
        cursor = max(cursor, e)
        if cursor >= hi_ns:
            break
    if cursor < hi_ns:
        out.append((cursor, hi_ns))
    return out


def reduce(profile, host_marks=None):
    """{"busy_s", "window_s", "device_ops", "idle_gaps", "modules"}.

    `host_marks` is a list of (start_ns, end_ns, name) on the trace's
    clock naming what the host was doing (the driver's own annotations,
    read from the host plane by name): each idle gap is attributed to the
    mark that covers most of it.
    """
    planes = device_planes(profile)
    if not planes:
        return None
    busy = []
    lo = hi = None
    per_op = {}
    modules = {}
    all_gaps = []
    for plane in planes:
        events = _events(plane, OP_LINES)
        if not events:
            continue
        spans = [(s, e) for s, e, _ in events]
        busy.append(union_seconds(spans))
        p_lo, p_hi = min(s for s, _ in spans), max(e for _, e in spans)
        lo = p_lo if lo is None else min(lo, p_lo)
        hi = p_hi if hi is None else max(hi, p_hi)
        for s, e, name in events:
            name = short_op_name(name)
            per_op[name] = per_op.get(name, 0.0) + (e - s) / 1e9
        for s, e, name in _events(plane, MODULE_LINES):
            modules.setdefault(name, []).append((e - s) / 1e9)
        all_gaps.extend(gaps(spans, p_lo, p_hi))
    if not busy:
        return None
    window_s = (hi - lo) / 1e9
    by_what = {}
    for s, e in all_gaps:
        name = _covering(host_marks or [], s, e)
        by_what[name] = by_what.get(name, 0.0) + (e - s) / 1e9 / len(busy)
    top = lambda d: [[k, v] for k, v in  # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"busy_s": sum(busy) / len(busy), "window_s": window_s,
            "device_ops": top({k: v / len(busy) for k, v in per_op.items()}),
            "idle_gaps": top(by_what), "modules": modules}


def _covering(marks, s, e):
    best, best_len = "unattributed", 0
    for ms, me, name in marks:
        overlap = min(e, me) - max(s, ms)
        if overlap > best_len:
            best, best_len = name, overlap
    return best


def host_marks(profile, prefix):
    """The driver's `TraceAnnotation`s (names starting with `prefix`) from
    the host planes, as (start_ns, end_ns, name-without-prefix)."""
    out = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name[len(prefix):]))
    return out


def describe(profile, limit=6):
    """Planes, lines and a few events: what to look at by hand first."""
    rows = []
    for plane in profile.planes:
        rows.append(f"plane {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            rows.append(f"  line {line.name!r}: {len(events)} events")
            for ev in events[:limit]:
                rows.append(f"    {ev.name[:80]!r} start={ev.start_ns} "
                            f"dur={ev.duration_ns}")
    return "\n".join(rows)
