"""Roofline share of `lm/attn/scores`: the reference's `attn_work` (operations
and bytes, every layer of the kind, forward and backward) against the
device time under the scope (`benchmark/lib/roofline.py`)."""

from benchmark.lib import roofline


def read(observed):
    return roofline.share(observed, "attn_scores", "lm/attn/scores")
