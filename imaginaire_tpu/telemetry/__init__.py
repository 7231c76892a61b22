"""Structured run telemetry (ISSUE 2): pluggable metric sinks,
step-phase spans, live MFU/throughput counters, and a hang watchdog.

Entry points call ``telemetry.configure(cfg, logdir=...)``; everything
else reports through the module-level singleton:

    from imaginaire_tpu import telemetry

    with telemetry.span("gen_step", step=it):
        ...
    telemetry.get().step_complete(it, items=batch, fence=drain)

See ``core.py`` for the event model, ``sinks.py`` for where events go,
``watchdog.py`` for the hang dumper, and ``report.py`` /
``scripts/telemetry_report.py`` for rendering a run's JSONL into the
phase table.
"""

from imaginaire_tpu.telemetry.core import (  # noqa: F401
    Telemetry,
    configure,
    get,
    resolve_peak_flops,
    span,
    telemetry_settings,
)
from imaginaire_tpu.telemetry.sinks import (  # noqa: F401
    ConsoleSink,
    JsonlSink,
    Sink,
    TensorBoardSink,
    make_sinks,
)
