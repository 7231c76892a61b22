"""Print what the newest trace under benchmark/.cache/trace says of the
program's `lm/...` scopes: the stats the first device events carry (is the
`op_name` among them?), and the device seconds under each scope.

    python benchmark/tools/describe_scopes.py [trace_dir] [hlo_text_file]

Without an HLO text only the events' own stats can name a scope.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import harness, scope_times, trace_reduce  # noqa: E402

if __name__ == "__main__":
    trace_dir = (sys.argv[1] if len(sys.argv) > 1
                 else os.path.join(harness.CACHE_DIR, "trace"))
    hlo_text = open(sys.argv[2]).read() if len(sys.argv) > 2 else None
    profile = trace_reduce.load(trace_reduce.newest_xplane(trace_dir))
    for plane in trace_reduce.device_planes(profile):
        print("plane", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            for ev in events[:4]:
                print(f"    {ev.name[:100]!r} dur={ev.duration_ns}")
                for key, value in ev.stats:
                    print(f"      {key} = {str(value)[:160]!r}")
    reduced = scope_times.reduce(profile, hlo_text)
    if reduced is None:
        print("no device operation or no step program in the trace")
        sys.exit(1)
    print(f"steps {reduced['steps']} busy_s {reduced['busy_s']:.4f} "
          f"matched_s {reduced['matched_s']:.4f}")
    for scope, seconds in sorted(reduced["seconds"].items(),
                                 key=lambda kv: -kv[1]):
        print(f"  {scope:<28} {1e3 * seconds:9.3f} ms a step")
