"""Seconds in the program's `init_state` span: building and placing the
train state."""

from benchmark.lib import program_spans


def read(observed):
    return program_spans.total_s("init_state")
