"""Seconds the program's compile ledger spent lowering and compiling, or
loading from the persistent cache, the two step programs."""

from benchmark.lib import program_spans

LABELS = ("dis_step", "gen_step")


def read(observed):
    return program_spans.build_seconds(LABELS)
