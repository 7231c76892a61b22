"""Share of the traced part of the window in which no operation ran on
the device while the loop thread was in a `data_wait` span (the
program's own annotation on the trace's host plane)."""

from benchmark.lib import program_spans


def read(observed):
    split = program_spans.traced_idle_split(observed)
    if not split:
        return None
    return 100.0 * split["covered_s"] / split["window_s"]
