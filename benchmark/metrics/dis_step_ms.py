"""Median device duration of the discriminator step program's executions
in the traced part of the window (the trace's `XLA Modules` line)."""

from benchmark.lib.stats import percentile

MODULE = "dis_step"


def read(observed):
    modules = (observed.get("trace") or {}).get("modules") or {}
    durations = [d * 1e3 for name, ds in modules.items()
                 if MODULE in name for d in ds]
    return percentile(durations, 0.50)
