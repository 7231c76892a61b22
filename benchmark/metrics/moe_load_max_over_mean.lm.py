"""The fullest held expert's rows over the held experts' mean, the
median over the window's steps and expert layers (the step's own
`moe/<layer>/load_max_over_mean`): 1 is even routing."""

from benchmark.lib.stats import percentile


def read(observed):
    return percentile(observed.get("load_max_over_mean") or [], 0.50)
