"""Time an iteration's loop thread is blocked on the device for the
previous program's health flags: the program's `health_poll` spans'
total over its iterations. (A mean, not a median: the poll after the D
step reads the G step's flags of the iteration before, long there, the
poll after the G step reads the D step just enqueued, so the spans are of
two kinds and their median is one kind's.)"""

from benchmark.lib import program_spans


def read(observed):
    total = program_spans.total_s("health_poll")
    iterations = program_spans.count("gen_step")
    if total is None or not iterations:
        return None
    return 1e3 * total / iterations
