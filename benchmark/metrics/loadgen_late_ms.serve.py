"""95th percentile of how late the generator submitted a request after
its scheduled arrival: a starved generator must not read as a fast
server. (The lateness is charged to the request's latency either way.)"""

from benchmark.lib.stats import percentile


def read(observed):
    late = [x for x in observed.get("late_ms") or [] if x is not None]
    return percentile(late, 0.95)
