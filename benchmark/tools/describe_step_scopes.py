"""Print the device time of the step programs by program, scope and pass
from a trace and the `scopes.json` beside it (the compile ledger's
{program: {instruction: op_name}}: a traced run of the benchmark leaves
one under benchmark/.cache/trace, `telemetry.trace_at_step` one under
`<logdir>/trace`).

    python benchmark/tools/describe_step_scopes.py [trace_dir]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import harness, step_scopes, trace_reduce  # noqa: E402

if __name__ == "__main__":
    trace_dir = (sys.argv[1] if len(sys.argv) > 1
                 else os.path.join(harness.CACHE_DIR, "trace"))
    op_names = harness.read_json(os.path.join(trace_dir, "scopes.json"))
    profile = trace_reduce.load(trace_reduce.newest_xplane(trace_dir))
    reduced = step_scopes.reduce(profile, op_names)
    if reduced is None:
        print("no whole execution of a step program that scopes.json "
              f"names ({sorted(op_names)}) in the trace")
        sys.exit(1)
    for program, n in sorted(reduced["executions"].items()):
        busy, matched = (reduced[k][program] for k in ("busy_s",
                                                       "matched_s"))
        print(f"{program}: {n} whole executions, busy {1e3 * busy:.3f} ms "
              f"an execution, under a scope {1e3 * matched:.3f} "
              f"({matched / busy:.4f})")
    print(f"{'program':<9} {'scope':<24} " + " ".join(
        f"{p:>10}" for p in step_scopes.PASSES) + f" {'ms':>10}")
    for program, scope, *ms in step_scopes.table(reduced):
        print(f"{program:<9} {scope:<24} " + " ".join(
            f"{v:10.3f}" for v in ms))
