#!/usr/bin/env python
"""Render a run's telemetry.jsonl into a per-phase attribution table (counts, totals, p50/p99, share of wall) plus the
derived counters (imgs/sec, MFU, step percentiles), the training-health
section (grad-norm / update-ratio trends, D real/fake accuracy, D/G
loss-ratio EWMA with breach counts, non-finite triage events), the
"## quality" section (ISSUE 18: per-sweep FID/KID trend table,
reference-store hit rate, regression-sentinel events), and hang
dumps. ``--json`` includes every counter plus the full ``health`` block
(health counter series, nonfinite events) — the machine-readable feed
``scripts/check_run_health.py`` gates on.

Usage:
    python scripts/telemetry_report.py logs/<run>/telemetry.jsonl
    python scripts/telemetry_report.py logs/<run>            # dir works too
    python scripts/telemetry_report.py <path> --json         # machine-readable
    python scripts/telemetry_report.py logs/<run> --pod      # pod timeline
    python scripts/telemetry_report.py logs/<run> --serving  # trace/SLO view

``--pod`` (ISSUE 17) merges every per-process ``telemetry.jsonl.p<i>``
of the run into one clock-aligned pod timeline — per-host lanes,
per-step skew histogram, span-level straggler table — instead of the
single-file phase report; with ``--json`` it dumps the merged
structure.

``--serving`` (ISSUE 20) renders the request-scoped serving view from
the run's ``trace/`` records and ``serve/slo/*`` counters: the span
cost table (where request time goes, stage by stage), the SLO error-
budget history, breach attribution grouped by dominant span, and the
slowest sampled traces; with ``--json`` it dumps the serving summary
block (traces + slo) that ``check_run_health`` gates on.

The MFU shown is reproducible from the JSONL alone: the ``step_flops``
meta event records the XLA cost analysis (and the peak-FLOPs source),
and ``perf/mfu`` counters record flops*steps / (fenced-window-wall *
peak) at each flush.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from imaginaire_tpu.telemetry.report import (  # noqa: E402
    load_events,
    render_report,
    summarize,
)


def main():
    ap = argparse.ArgumentParser(
        description="Per-phase report from a telemetry.jsonl")
    ap.add_argument("path", help="telemetry.jsonl (or a run dir "
                                 "containing one)")
    ap.add_argument("--json", action="store_true",
                    help="dump the aggregated summary as JSON instead "
                         "of the table")
    ap.add_argument("--pod", action="store_true",
                    help="merge all per-process telemetry files into "
                         "one clock-aligned pod timeline (per-host "
                         "lanes, skew histogram, straggler table)")
    ap.add_argument("--serving", action="store_true",
                    help="render the request-scoped serving view "
                         "(span cost table, SLO budget history, "
                         "breach attribution, slowest traces)")
    args = ap.parse_args()
    path = args.path
    if args.serving:
        from imaginaire_tpu.telemetry.report import render_serving_report

        if os.path.isdir(path):
            path = os.path.join(path, "telemetry.jsonl")
        if not os.path.exists(path):
            raise SystemExit(f"no telemetry.jsonl at {path}")
        summary = summarize(load_events(path))
        serving = summary.get("serving") or {}
        if not serving.get("present"):
            raise SystemExit(f"no serve/* or trace/ events in {path} — "
                             f"did the run use the serving engine with "
                             f"telemetry enabled?")
        if args.json:
            print(json.dumps(serving, indent=1, default=str))
        else:
            print(render_serving_report(path))
        return
    if args.pod:
        from imaginaire_tpu.telemetry.podview import (
            merge_pod_timeline,
            render_pod_timeline,
        )

        merged = merge_pod_timeline(path)
        if not merged["hosts"]:
            raise SystemExit(f"no pod/digest events under {path} — "
                             f"was the run multi-process with "
                             f"telemetry.pod enabled?")
        if args.json:
            print(json.dumps(merged, indent=1, default=str))
        else:
            print(render_pod_timeline(merged))
        return
    if os.path.isdir(path):
        path = os.path.join(path, "telemetry.jsonl")
    if not os.path.exists(path):
        raise SystemExit(f"no telemetry.jsonl at {path}")
    if args.json:
        summary = summarize(load_events(path))
        summary["counters"] = {k: {"value": v, "step": s}
                               for k, (v, s) in summary["counters"].items()}
        print(json.dumps(summary, indent=1, default=str))
    else:
        print(render_report(path))


if __name__ == "__main__":
    main()
