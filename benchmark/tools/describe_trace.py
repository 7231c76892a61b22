"""Print the planes, lines and first events of the newest trace under
benchmark/.cache/trace: what to look at by hand before trusting the
reduction. Usage: python benchmark/tools/describe_trace.py [trace_dir]"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import harness, trace_reduce  # noqa: E402

if __name__ == "__main__":
    trace_dir = (sys.argv[1] if len(sys.argv) > 1
                 else os.path.join(harness.CACHE_DIR, "trace"))
    profile = trace_reduce.load(trace_reduce.newest_xplane(trace_dir))
    print(trace_reduce.describe(profile))
