"""What the program's threads were in while the device idled, from the
newest trace a `--trace 1` run of a training cell left:

    python benchmark/tools/span_breakdown.py [trace_dir]

Prints one JSON object: the size of the `.xplane.pb`, each host thread
that holds `imaginaire/` events with its spans' counts, the idle time's
split by `data_wait`, the seconds of the feed-starved and of the
host-busy gaps that each span of the program covers (spans of different
threads overlap, so a row's seconds can add up past the gaps'), and each
gap of 20 ms or more with where it lies and what covered it.
"""

from __future__ import annotations

import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark.lib import harness, program_spans, trace_reduce  # noqa: E402

LONG_GAP_NS = 20e6


def threads(profile):
    """[{span name: count}] for each host line with `imaginaire/` events."""
    out = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            names = {}
            for ev in line.events:
                if ev.name.startswith(program_spans.PREFIX):
                    name = ev.name[len(program_spans.PREFIX):]
                    names[name] = names.get(name, 0) + 1
            if names:
                out.append(names)
    return out


def intersect(a, b):
    """The parts of the disjoint sorted intervals `a` inside those of `b`."""
    out = []
    for s, e in a:
        for bs, be in b:
            if bs >= e:
                break
            if be > s:
                out.append((max(s, bs), min(e, be)))
    return out


def breakdown(profile):
    marks = trace_reduce.host_marks(profile, program_spans.PREFIX)
    by_name = {}
    for s, e, name in marks:
        by_name.setdefault(name, []).append((s, e))
    by_name = {k: program_spans.merged(v) for k, v in by_name.items()}
    planes = trace_reduce.device_planes(profile)
    ops = [(s, e) for plane in planes for s, e, _ in
           trace_reduce._events(plane, trace_reduce.OP_LINES)]
    out = {"threads": threads(profile),
           "idle_split": program_spans.idle_split(profile)}
    if len(planes) != 1 or not ops or "data_wait" not in by_name:
        return out
    lo = min(s for s, _ in ops)
    gaps = trace_reduce.gaps(ops, lo, max(e for _, e in ops))
    starved = intersect(gaps, by_name["data_wait"])
    length = lambda part: sum(e - s for s, e in part)  # noqa: E731
    out["feed_starved_s"] = {"_total": length(starved) / 1e9}
    out["host_busy_s"] = {"_total": (length(gaps) - length(starved)) / 1e9}
    for name, cover in sorted(by_name.items()):
        in_gaps = program_spans.overlap_ns(gaps, cover)
        in_starved = program_spans.overlap_ns(starved, cover)
        out["feed_starved_s"][name] = in_starved / 1e9
        out["host_busy_s"][name] = (in_gaps - in_starved) / 1e9
    out["long_gaps"] = [
        {"at_ms": round((s - lo) / 1e6, 1),
         "ms": round((e - s) / 1e6, 1),
         "in": {name: round(program_spans.overlap_ns([(s, e)], cover) / 1e6,
                            1)
                for name, cover in sorted(by_name.items())
                if program_spans.overlap_ns([(s, e)], cover) >= 1e6}}
        for s, e in gaps if e - s >= LONG_GAP_NS]
    return out


def main(argv):
    trace_dir = argv[1] if len(argv) > 1 else os.path.join(
        harness.CACHE_DIR, "trace")
    path = trace_reduce.newest_xplane(trace_dir)
    out = {"xplane": os.path.relpath(path, trace_dir),
           "xplane_bytes": os.path.getsize(path)}
    out.update(breakdown(trace_reduce.load(path)))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
