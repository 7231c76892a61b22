"""Roofline share of `lm/moe/experts`: the reference's `expert_work` (operations
and bytes, every layer of the kind, forward and backward) against the
device time under the scope (`benchmark/lib/roofline.py`)."""

from benchmark.lib import roofline


def read(observed):
    return roofline.share(observed, "moe_experts", "lm/moe/experts")
