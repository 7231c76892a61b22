"""Shared by the benchmark's CPU rehearsals: the repo root on the path,
and a cell loaded at a width a test run can hold."""

import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY = dict(num_filters=8, style_dims=16, spade_num_filters=8,
            style_enc_num_filters=4, dis_num_filters=8,
            dis_max_num_filters=32)


def tiny_cell(name, cache_dir, **traffic):
    """The cell `name` with its widths cut for the CPU and the harness's
    cache directory moved to `cache_dir` (a temporary one)."""
    from benchmark.lib import harness

    harness.CACHE_DIR = str(cache_dir)
    loaded = harness.load_cell(name)
    loaded["config"] = copy.deepcopy(loaded["config"])
    loaded["config"]["sizes"].update(TINY)
    loaded["workload"] = copy.deepcopy(loaded["workload"])
    loaded["workload"]["traffic"].update(traffic)
    return loaded
