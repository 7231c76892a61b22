"""Roofline share of `lm/attn/window_scores`: the reference's `attn_window`
work (the band's operations and nothing else, `sum_i min(i + 1, window)`
query-key pairs a head, every sliding-window layer, forward and backward)
against the device time under the scope (`benchmark/lib/roofline.py`). A
kernel that computes whole tiles the band only crosses reads what it
wastes as a lower share."""

from benchmark.lib import roofline


def read(observed):
    return roofline.share(observed, "attn_window", "lm/attn/window_scores")
