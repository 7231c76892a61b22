"""Render a run's ``telemetry.jsonl`` into a per-phase attribution
table, plus the derived counters (imgs/sec, MFU,
step percentiles) and any hang dumps.

Library half of ``scripts/telemetry_report.py``; also run by the
``__graft_entry__`` dryrun so every dryrun prints a phase breakdown.
"""

from __future__ import annotations

import json


def load_events(path):
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                events.append(json.loads(line))
            except ValueError:
                continue  # a torn final line from a killed run
    return events


def _percentile(samples, q):
    ordered = sorted(samples)
    idx = min(int(q * (len(ordered) - 1) + 0.5), len(ordered) - 1)
    return ordered[idx]


def summarize(events):
    """Aggregate events into {phases, counters, meta, hangs, wall_s}.

    Span events nested under a same-named parent are skipped (they are
    the same wall time measured twice). Phases can still legitimately
    nest under *different* names (vid2vid's per-frame ``dis_step`` runs
    inside ``gen_step``, ``ckpt`` inside ``end_of_iteration``), so phase
    shares may sum past 100%.
    """
    phases = {}
    counters = {}
    health_series = {}
    flow_cache_series = {}
    compact_series = {}
    nonfinite_events = []
    recompile_events = []
    oom_events = []
    fallback_events = []
    quarantine_events = []
    resume_events = []
    divergence_events = []
    preempt_events = []
    chaos_events = []
    gc_events = []
    retry_exhausted = []
    desync_events = []
    consensus_events = []
    resize_events = []
    remap_events = []
    graph_events = []
    pod_skew_series = []
    pod_straggler_events = []
    pod_divergence_events = []
    pod_digest_count = 0
    eval_series = {}
    eval_sweep_events = []
    regression_events = []
    trace_records = []
    stream_trace_events = []
    slo_series = {}
    slo_breach_events = []
    meta = {}
    hangs = []
    t_min = t_max = None
    for ev in events:
        kind = ev.get("kind")
        t = ev.get("t")
        if isinstance(t, (int, float)):
            t_end = t + (ev.get("dur_ms", 0) or 0) / 1e3
            t_min = t if t_min is None else min(t_min, t)
            t_max = t_end if t_max is None else max(t_max, t_end)
        if kind == "span":
            if ev.get("parent") == ev.get("name"):
                continue
            entry = phases.setdefault(ev["name"], [])
            entry.append(float(ev.get("dur_ms", 0) or 0))
        elif kind == "counter":
            counters[ev["name"]] = (ev.get("value"), ev.get("step"))
            if str(ev["name"]).startswith("health/"):
                # full series for health counters: trends (grad norms
                # rising, D/G ratio drifting) are the signal, the
                # latest value alone is not
                health_series.setdefault(ev["name"], []).append(
                    [ev.get("step"), ev.get("value")])
            elif str(ev["name"]).startswith("flow_cache/"):
                flow_cache_series.setdefault(ev["name"], []).append(
                    float(ev.get("value") or 0.0))
            elif (str(ev["name"]).startswith("moe/")
                  and str(ev["name"]).endswith("/compact")):
                # full series: each flush carries its newest step's 0 or
                # 1, and the table shows the share of them on the prefix
                compact_series.setdefault(
                    ev["name"].split("/")[1], []).append(
                    float(ev.get("value") or 0.0))
            elif ev["name"] == "pod/step_skew_ms":
                # full series: the gate thresholds the p50, not the
                # latest value
                pod_skew_series.append(
                    [ev.get("step"), float(ev.get("value") or 0.0)])
            elif str(ev["name"]).startswith("eval/"):
                # full series for quality counters (ISSUE 18): the
                # report renders the per-sweep trend, not the latest
                eval_series.setdefault(ev["name"], []).append(
                    [ev.get("step"), ev.get("value")])
            elif str(ev["name"]).startswith("serve/slo/"):
                # full series for the error budget (ISSUE 20): the
                # burn-rate gate thresholds the series MAX — a budget
                # that burned and recovered still burned
                slo_series.setdefault(ev["name"], []).append(
                    [ev.get("step"), ev.get("value")])
        elif kind == "meta":
            name = ev.get("name")
            if name == "nonfinite":
                nonfinite_events.append(ev)
            elif name == "xla_recompile":
                recompile_events.append(ev)
            elif name == "oom":
                oom_events.append(ev)
            elif name == "ckpt/fallback":
                fallback_events.append(ev)
            elif name == "ckpt/quarantined":
                quarantine_events.append(ev)
            elif name == "ckpt/gc":
                gc_events.append(ev)
            elif name == "resilience/resume":
                resume_events.append(ev)
            elif name == "resilience/resume_divergence":
                divergence_events.append(ev)
            elif name in ("resilience/preempt_signal",
                          "resilience/preempt_deadline_expired",
                          "resilience/preempt_remote",
                          "resilience/preempt_remote_trigger"):
                preempt_events.append(ev)
            elif name == "resilience/retry_exhausted":
                retry_exhausted.append(ev)
            elif name == "resilience/cluster_desync":
                desync_events.append(ev)
            elif name == "resilience/consensus_resume":
                consensus_events.append(ev)
            elif name == "elastic/resize":
                resize_events.append(ev)
            elif name == "resilience/runstate_remap":
                remap_events.append(ev)
            elif name == "graph_violation":
                graph_events.append(ev)
            elif name == "pod/digest":
                pod_digest_count += 1
            elif name == "pod/straggler":
                pod_straggler_events.append(ev)
            elif name == "pod/divergence":
                pod_divergence_events.append(ev)
            elif name == "eval/sweep":
                eval_sweep_events.append(ev)
            elif name == "eval/regression":
                regression_events.append(ev)
            elif name == "serve/slo/breach":
                slo_breach_events.append(ev)
            elif str(name).startswith("chaos/"):
                chaos_events.append(ev)
            meta[ev.get("name", "?")] = ev
        elif kind == "trace":
            # request-scoped serving traces (ISSUE 20): per-request
            # span records vs stream lifecycle transitions
            if ev.get("name") == "trace/stream":
                stream_trace_events.append(ev)
            else:
                trace_records.append(ev)
        elif kind == "hang":
            hangs.append(ev)
    wall_s = (t_max - t_min) if t_min is not None else 0.0
    table = {}
    for name, durs in phases.items():
        table[name] = {
            "count": len(durs),
            "total_ms": sum(durs),
            "mean_ms": sum(durs) / len(durs),
            "p50_ms": _percentile(durs, 0.50),
            "p99_ms": _percentile(durs, 0.99),
            "share_pct": (sum(durs) / (wall_s * 1e3) * 100.0)
            if wall_s > 0 else 0.0,
        }
    health = {
        "has_health_counters": bool(health_series),
        "series": health_series,
        "nonfinite_events": nonfinite_events,
        "nonfinite_event_count": int(
            counters.get("health/nonfinite_events", (0, None))[0] or 0)
        or len(nonfinite_events),
        "nonfinite_skipped": int(
            counters.get("health/nonfinite_skipped", (0, None))[0] or 0),
        "dg_ratio_ewma": counters.get("health/dg_loss_ratio_ewma",
                                      (None, None))[0],
        "dg_ratio_breaches": len(
            health_series.get("health/dg_ratio_breach", [])),
    }
    # amortized-teacher health (informational — never gated on): the
    # hit rate tells a cold epoch from a warm one, compute_ms how much
    # producer-thread time the teacher takes
    flow_cache = {"present": bool(flow_cache_series)}
    if flow_cache_series.get("flow_cache/hit_rate"):
        flow_cache["hit_rate"] = flow_cache_series[
            "flow_cache/hit_rate"][-1]
    if flow_cache_series.get("flow_cache/compute_ms"):
        series = flow_cache_series["flow_cache/compute_ms"]
        flow_cache["compute_ms_mean"] = sum(series) / len(series)
    # XLA compile ledger + HBM watermarks (ISSUE 5): per-label compile
    # counts from the counters, recompile tripwire events from meta,
    # and the worst peak/limit fraction across devices (None on CPU,
    # where no mem/* counters exist)
    compiles = {}
    for name, (value, _) in counters.items():
        m = str(name)
        if m.startswith("xla/compile/") and m.endswith("/count"):
            compiles[m[len("xla/compile/"):-len("/count")]] = \
                int(value or 0)
    mem_peak_frac = None
    for name, (value, _) in counters.items():
        m = str(name)
        if m.startswith("mem/") and m.endswith("/peak_bytes_in_use"):
            dev = m[len("mem/"):-len("/peak_bytes_in_use")]
            limit = counters.get(f"mem/{dev}/bytes_limit",
                                 (None, None))[0]
            if value and limit:
                frac = float(value) / float(limit)
                if mem_peak_frac is None or frac > mem_peak_frac:
                    mem_peak_frac = frac
    xla = {
        "present": bool(compiles) or "xla/recompiles" in counters,
        "compiles": compiles,
        "recompiles": int(
            counters.get("xla/recompiles", (0, None))[0] or 0)
        or len([e for e in recompile_events]),
        "recompile_events": recompile_events,
        "mem_peak_frac": mem_peak_frac,
        "oom_events": oom_events,
    }
    # fault-tolerance accounting (ISSUE 7): fallbacks/quarantines are
    # gated by check_run_health --max-fallbacks; any resume-divergence
    # event fails the gate outright. Counters are cumulative, so the
    # latest value is the run total.
    retries = sum(int(v or 0) for name, (v, _) in counters.items()
                  if str(name).startswith("resilience/retry/"))
    resilience = {
        "present": bool(fallback_events or quarantine_events
                        or resume_events or preempt_events
                        or chaos_events or retries or resize_events
                        or any(str(n).startswith(("resilience/",
                                                  "elastic/"))
                               for n in counters)),
        "fallbacks": int(counters.get("resilience/ckpt_fallbacks",
                                      (0, None))[0] or 0)
        or len(fallback_events),
        "quarantined": len(quarantine_events),
        "retries": retries,
        "retry_exhausted": retry_exhausted,
        "preemptions": int(counters.get("resilience/preemptions",
                                        (0, None))[0] or 0),
        "emergency_ckpt_ms": counters.get("resilience/emergency_ckpt_ms",
                                          (None, None))[0],
        "corrupt_flow_shards": int(
            counters.get("flow_cache/corrupt_shards", (0, None))[0] or 0),
        "gc_deleted": int(counters.get("resilience/ckpt_gc_deleted",
                                       (0, None))[0] or 0),
        "resume_events": resume_events,
        "divergence_events": divergence_events,
        "fallback_events": fallback_events,
        "chaos_events": chaos_events,
        "gc_events": gc_events,
        # pod coordination (ISSUE 8): desyncs gate check_run_health;
        # consensus overrides are informational (a host following the
        # cluster's agreed checkpoint is the machinery WORKING)
        "cluster_desyncs": int(
            counters.get("resilience/cluster_desyncs", (0, None))[0]
            or 0) or len(desync_events),
        "desync_events": desync_events,
        "consensus_events": consensus_events,
        # elastic pods (ISSUE 13): in-process mesh resizes — counted
        # (check_run_health --max-resizes gates on this) and
        # carried in full so the report can render old -> new shape
        # plus the downtime + redistribution breakdown per event
        "elastic_resizes": int(
            counters.get("elastic/resizes", (0, None))[0]
            or 0) or len(resize_events),
        "resize_downtime_ms": counters.get(
            "elastic/downtime_ms", (None, None))[0],
        "redistributed_bytes": counters.get(
            "elastic/redistributed_bytes", (None, None))[0],
        "resize_events": resize_events,
        "runstate_remap_events": remap_events,
    }
    # graph audit (ISSUE 12): per-program static-analysis verdicts from
    # the compile ledger (xla/graph/<label>/* counters hold the LATEST
    # audit per program; xla/graph_violations is the cross-program sum)
    graph_programs = {}
    for name, (value, _) in counters.items():
        m = str(name)
        if not m.startswith("xla/graph/"):
            continue
        label, _, key = m[len("xla/graph/"):].rpartition("/")
        if label and key in ("violations", "dead_donations",
                             "collective_bytes"):
            graph_programs.setdefault(label, {})[key] = int(value or 0)
    graph = {
        "present": bool(graph_programs)
        or "xla/graph_violations" in counters,
        "programs": graph_programs,
        "violations": int(
            counters.get("xla/graph_violations", (0, None))[0] or 0)
        or sum(p.get("violations", 0) for p in graph_programs.values()),
        "dead_donations": sum(p.get("dead_donations", 0)
                              for p in graph_programs.values()),
        "collective_bytes": sum(p.get("collective_bytes", 0)
                                for p in graph_programs.values()),
        "violation_events": graph_events,
    }
    # pod observability plane (ISSUE 17): cross-host step skew, the
    # persistent-straggler attribution, and the SPMD divergence
    # sentinel — check_run_health --hosts gates on skew p50 /
    # divergence count / straggler share
    straggler_counters = {}
    for name, (value, _) in counters.items():
        m = str(name)
        if m.startswith("pod/straggler/"):
            straggler_counters[m[len("pod/straggler/"):]] = \
                int(value or 0)
    skew_vals = [v for _, v in pod_skew_series]
    pod = {
        "present": bool(pod_skew_series or pod_digest_count
                        or "pod/divergence" in counters),
        "digest_count": pod_digest_count,
        "skew_series": pod_skew_series,
        "step_skew_ms_p50": _percentile(skew_vals, 0.50)
        if skew_vals else None,
        "step_skew_ms_max": max(skew_vals) if skew_vals else None,
        "divergence_count": int(
            counters.get("pod/divergence", (0, None))[0] or 0)
        or len(pod_divergence_events),
        "divergence_events": pod_divergence_events,
        "straggler_counters": straggler_counters,
        "straggler_events": pod_straggler_events,
    }
    if straggler_counters:
        total = sum(straggler_counters.values())
        leader = max(straggler_counters, key=straggler_counters.get)
        span = next((ev.get("span")
                     for ev in reversed(pod_straggler_events)
                     if f"p{ev.get('process')}" == leader), None)
        pod["straggler"] = {
            "process": leader,
            "rounds": straggler_counters[leader],
            "share": straggler_counters[leader] / max(total, 1),
            "span": span,
        }
    # quality observability plane (ISSUE 18): full eval/* counter
    # series (FID/KID trend over sweeps), the per-sweep meta events,
    # and the regression sentinel's firings — check_run_health
    # --max-fid / --max-quality-regressions gate on these
    fid_series = eval_series.get("eval/fid", [])
    fid_vals = [v for _, v in fid_series
                if isinstance(v, (int, float))]
    ref_hits = [int(v or 0) for _, v in
                eval_series.get("eval/ref_cache_hit", [])]
    quality = {
        "present": bool(eval_series or eval_sweep_events
                        or regression_events),
        "series": eval_series,
        "sweeps": eval_sweep_events,
        "sweep_count": max(len(fid_series), len(eval_sweep_events)),
        "fid_latest": fid_vals[-1] if fid_vals else None,
        "fid_best": min(fid_vals) if fid_vals else None,
        "regressions": int(
            counters.get("eval/regressions", (0, None))[0] or 0)
        or len(regression_events),
        "regression_events": regression_events,
        "ref_cache_hits": sum(ref_hits),
        "ref_cache_misses": len(ref_hits) - sum(ref_hits),
        "store_corrupt": int(
            counters.get("eval/store_corrupt", (0, None))[0] or 0),
    }
    # serving SLO plane (ISSUE 19): top-level serve/* counters are the
    # engine's cumulative request-latency percentiles and queue state;
    # deeper serve/<family>/.../{p50_ms,p99_ms,count} names are the
    # per-executable bucket series — check_run_health
    # --max-p99-latency-ms / --max-queue-depth gate on the former
    serve_buckets = {}
    for name, (value, _) in counters.items():
        m = str(name)
        if not m.startswith("serve/"):
            continue
        label, _, stat = m.rpartition("/")
        if stat in ("p50_ms", "p99_ms", "count") and \
                label.count("/") >= 2:
            serve_buckets.setdefault(label, {})[stat] = value
    # request-scoped traces (ISSUE 20): per-span aggregate table over
    # every trace/request record, plus breach/eviction attribution —
    # the "why was THIS request slow" plane rendered aggregate-side
    span_durs = {}
    trace_breaches = 0
    trace_evict_recompiles = 0
    trace_sampled = 0
    for rec in trace_records:
        if rec.get("slo_breach"):
            trace_breaches += 1
        if rec.get("evict_recompile"):
            trace_evict_recompiles += 1
        if rec.get("sampled"):
            trace_sampled += 1
        for sp in rec.get("spans") or []:
            span_durs.setdefault(str(sp.get("name")), []).append(
                float(sp.get("dur_ms") or 0.0))
    span_table = {}
    for name, durs in span_durs.items():
        span_table[name] = {
            "count": len(durs),
            "total_ms": sum(durs),
            "mean_ms": sum(durs) / len(durs),
            "p50_ms": _percentile(durs, 0.50),
            "p99_ms": _percentile(durs, 0.99),
        }
    traces = {
        "present": bool(trace_records or stream_trace_events),
        "count": len(trace_records),
        "sampled": trace_sampled,
        "breaches": trace_breaches,
        "evict_recompiles": trace_evict_recompiles,
        "spans": span_table,
        "records": trace_records,
        "stream_events": stream_trace_events,
        "stream_ids": sorted(
            {str(rec["stream_id"]) for rec in trace_records
             if rec.get("stream_id") is not None}
            | {str(ev["stream_id"]) for ev in stream_trace_events
               if ev.get("stream_id") is not None}),
    }
    # SLO error budget (ISSUE 20): check_run_health
    # --max-slo-burn-rate / --min-slo-budget-frac threshold the series
    # extremes, the breach metas carry the dominant-span attribution
    burn_series = slo_series.get("serve/slo/burn_rate", [])
    budget_series = slo_series.get("serve/slo/budget_remaining_frac",
                                   [])
    burn_vals = [float(v) for _, v in burn_series
                 if isinstance(v, (int, float))]
    budget_vals = [float(v) for _, v in budget_series
                   if isinstance(v, (int, float))]
    slo = {
        "present": bool(slo_series or slo_breach_events
                        or "serve/slo/config" in meta),
        "config": meta.get("serve/slo/config"),
        "burn_rate_latest": burn_vals[-1] if burn_vals else None,
        "burn_rate_max": max(burn_vals) if burn_vals else None,
        "budget_remaining_frac": (budget_vals[-1] if budget_vals
                                  else None),
        "budget_remaining_min": (min(budget_vals) if budget_vals
                                 else None),
        "breaches": int(
            counters.get("serve/slo/breaches", (0, None))[0] or 0)
        or len(slo_breach_events),
        "rejected": int(
            counters.get("serve/slo/rejected", (0, None))[0] or 0),
        "breach_events": slo_breach_events,
        "series": slo_series,
    }
    serving = {
        "present": any(str(n).startswith("serve/") for n in counters)
        or any(str(n).startswith("serve/") for n in meta)
        or traces["present"],
        "p50_ms": counters.get("serve/p50_ms", (None, None))[0],
        "p99_ms": counters.get("serve/p99_ms", (None, None))[0],
        "requests": int(counters.get("serve/requests", (0, None))[0]
                        or 0),
        "queue_depth": counters.get("serve/queue_depth",
                                    (None, None))[0],
        "bucket_hit_rate": counters.get("serve/bucket_hit_rate",
                                        (None, None))[0],
        "pad_waste_frac": counters.get("serve/pad_waste_frac",
                                       (None, None))[0],
        "hbm_headroom_frac": counters.get("serve/hbm_headroom_frac",
                                          (None, None))[0],
        "buckets": serve_buckets,
        "weights_meta": meta.get("serve/weights"),
        "traces": traces,
        "slo": slo,
    }
    return {"phases": table, "counters": counters, "meta": meta,
            "hangs": hangs, "wall_s": wall_s, "health": health,
            "flow_cache": flow_cache, "xla": xla,
            "resilience": resilience, "graph": graph, "pod": pod,
            "quality": quality, "serving": serving,
            "experts_compact_share": {
                layer: sum(series) / len(series)
                for layer, series in compact_series.items()}}


def _trend(series):
    """'first -> last (xN)' for a [[step, value], ...] counter series."""
    vals = [v for _, v in series if isinstance(v, (int, float))]
    if not vals:
        return None
    if len(vals) == 1:
        return f"{vals[0]:.4g}"
    ratio = vals[-1] / vals[0] if vals[0] else float("inf")
    return f"{vals[0]:.4g} -> {vals[-1]:.4g} (x{ratio:.2f})"


def _health_section(s):
    """Markdown lines for the Health section: grad-norm trends, GAN
    balance, non-finite events. Empty when the run carried no health
    counters (diagnostics disabled)."""
    h = s.get("health") or {}
    if not h.get("has_health_counters") and not h.get("nonfinite_events"):
        return []
    series = h.get("series", {})
    lines = ["", "## health"]
    for kind in ("G", "D"):
        for stat, label in (("grad_norm/_total", "grad norm"),
                            ("update_ratio/_total", "update/param ratio"),
                            ("sn_sigma/max", "sn sigma max"),
                            ("ema_drift", "ema drift")):
            trend = _trend(series.get(f"health/{kind}/{stat}", []))
            if trend is not None:
                lines.append(f"- {kind} {label}: {trend}")
    for name, label in (("health/D/real_acc", "D real acc"),
                        ("health/D/fake_acc", "D fake acc")):
        trend = _trend(series.get(name, []))
        if trend is not None:
            lines.append(f"- {label}: {trend}")
    if h.get("dg_ratio_ewma") is not None:
        lines.append(f"- D/G loss-ratio EWMA: {h['dg_ratio_ewma']:.4g} "
                     f"(threshold breaches: {h.get('dg_ratio_breaches', 0)})")
    n_bad = h.get("nonfinite_event_count", 0)
    if n_bad:
        lines.append(f"!! {n_bad} non-finite event(s), "
                     f"{h.get('nonfinite_skipped', 0)} skipped:")
        for ev in h.get("nonfinite_events", []):
            lines.append(
                f"  - step {ev.get('step')} ({ev.get('update')}): terms "
                f"{ev.get('culprit_terms')}, modules "
                f"{ev.get('culprit_modules')}, action {ev.get('action')}"
                + (f", report {ev.get('report')}" if ev.get("report")
                   else ""))
    else:
        lines.append("- non-finite events: 0")
    return lines


def _fmt_bytes(n):
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0
    return f"{n:.1f}TiB"


def _xla_section(s):
    """Markdown lines for the compile-ledger/HBM section. Empty when
    the run carried no xla/* counters (observability disabled)."""
    x = s.get("xla") or {}
    if not x.get("present"):
        return []
    lines = ["", "## xla compile ledger"]
    for label in sorted(x.get("compiles", {})):
        count = x["compiles"][label]
        detail = ""
        compile_meta = s["meta"].get(f"xla_compile/{label}")
        if compile_meta:
            mem = compile_meta.get("memory") or {}
            parts = [f"compile {compile_meta.get('compile_ms', 0):.0f}ms"]
            if mem.get("total_bytes"):
                parts.append(f"footprint {_fmt_bytes(mem['total_bytes'])}"
                             f" (temp {_fmt_bytes(mem.get('temp_bytes', 0))})")
            if compile_meta.get("flops"):
                parts.append(f"{compile_meta['flops']:.3g} flops")
            if compile_meta.get("scoped_instructions") is not None:
                # 0 for a program whose source has named scopes: the
                # executable came from a cache another build filled
                parts.append(f"{compile_meta['scoped_instructions']} "
                             "instructions under named scopes")
            detail = " — " + ", ".join(parts)
        lines.append(f"- {label}: {count} compile(s){detail}")
    n_re = x.get("recompiles", 0)
    if n_re:
        lines.append(f"!! {n_re} post-warmup recompile(s):")
        for ev in x.get("recompile_events", []):
            diff = ev.get("diff") or {}
            changed = sorted((diff.get("changed") or {})) \
                + sorted((diff.get("added") or {})) \
                + sorted((diff.get("removed") or {}))
            lines.append(f"  - {ev.get('label')}: changed leaves "
                         f"{changed[:4]}")
    else:
        lines.append("- post-warmup recompiles: 0")
    if x.get("mem_peak_frac") is not None:
        lines.append(f"- peak HBM watermark: "
                     f"{x['mem_peak_frac'] * 100:.1f}% of bytes_limit")
    budget = s["meta"].get("mem_budget")
    if budget and budget.get("budget_frac") is not None:
        lines.append(f"- static budget (worst executable + state): "
                     f"{budget['budget_frac'] * 100:.1f}% of limit")
    for ev in x.get("oom_events", []):
        lines.append(f"!! OOM in {ev.get('context')}: forensics at "
                     f"{ev.get('report')}")
    return lines


def _graph_section(s):
    """Markdown lines for the static graph-audit section. Empty when
    the run carried no xla/graph/* counters (audit disabled)."""
    g = s.get("graph") or {}
    if not g.get("present"):
        return []
    lines = ["", "## graph audit"]
    for label in sorted(g.get("programs", {})):
        row = g["programs"][label]
        lines.append(
            f"- {label}: {row.get('violations', 0)} violation(s), "
            f"{row.get('dead_donations', 0)} dead donation(s), "
            f"collective bytes "
            f"{_fmt_bytes(row.get('collective_bytes', 0))}")
    total = g.get("violations", 0)
    if total:
        lines.append(f"!! {total} graph violation(s):")
        for ev in g.get("violation_events", []):
            for v in (ev.get("violations") or [])[:8]:
                lines.append(f"  - {ev.get('label')}: {v.get('rule')} at "
                             f"{v.get('path')} — {v.get('message')}")
    else:
        lines.append("- graph violations: 0")
    return lines


def _resilience_section(s):
    """Markdown lines for the fault-tolerance section. Empty when the
    run carried no resilience events (the common, healthy case)."""
    r = s.get("resilience") or {}
    if not r.get("present"):
        return []
    lines = ["", "## resilience"]
    if r.get("preemptions"):
        ms = r.get("emergency_ckpt_ms")
        lines.append(f"- preemptions: {r['preemptions']}"
                     + (f" (emergency checkpoint {ms:.0f}ms)"
                        if ms is not None else ""))
    if r.get("fallbacks") or r.get("quarantined"):
        lines.append(f"!! checkpoint fallbacks: {r.get('fallbacks', 0)} "
                     f"(quarantined: {r.get('quarantined', 0)})")
        for ev in r.get("fallback_events", []):
            lines.append(f"  - skipped {ev.get('skipped')}: "
                         f"{str(ev.get('error'))[:120]}")
    for ev in r.get("divergence_events", []):
        lines.append(
            f"!! resume divergence: checkpoint iter "
            f"{ev.get('checkpoint_iteration')} vs runstate "
            f"{ev.get('runstate_iteration')} ({ev.get('checkpoint')})")
    for ev in r.get("resume_events", []):
        lines.append(f"- resumed from {ev.get('checkpoint')} at iter "
                     f"{ev.get('iteration')} "
                     f"(runstate: {ev.get('runstate')}, batch offset "
                     f"{ev.get('batch_in_epoch', 0)})")
    for ev in r.get("desync_events", []):
        lines.append(f"!! cluster desync: barrier {ev.get('barrier')} "
                     f"absent process(es) {ev.get('absent')} "
                     f"(observed by p{ev.get('process')})")
    for ev in r.get("consensus_events", []):
        lines.append(f"- resume consensus override: local iter "
                     f"{ev.get('local_iteration')} -> cluster "
                     f"{ev.get('consensus')} "
                     f"({ev.get('consensus_checkpoint')})")
    if r.get("retries"):
        lines.append(f"- transient-IO retries: {r['retries']}"
                     + (f" (!! {len(r['retry_exhausted'])} exhausted)"
                        if r.get("retry_exhausted") else ""))
    if r.get("corrupt_flow_shards"):
        lines.append(f"- corrupt flow-cache shards quarantined: "
                     f"{r['corrupt_flow_shards']}")
    if r.get("gc_deleted"):
        lines.append(f"- checkpoint GC deleted: {r['gc_deleted']}")
    for ev in r.get("chaos_events", []):
        lines.append(f"- chaos injected: {ev.get('name')} at step "
                     f"{ev.get('step')}")
    return lines


def _elasticity_section(s):
    """Markdown lines for the elastic-pod section (ISSUE 13): resize
    count, cumulative downtime, redistributed state bytes, and the per
    -event old -> new topology with the phase + redistribution
    breakdown. Empty when the run never resized."""
    r = s.get("resilience") or {}
    if not (r.get("resize_events") or r.get("elastic_resizes")):
        return []
    lines = ["", "## elasticity"]
    lines.append(f"- resizes: {r.get('elastic_resizes', 0)}")
    if r.get("resize_downtime_ms") is not None:
        lines.append(f"- cumulative downtime: "
                     f"{float(r['resize_downtime_ms']):.0f}ms")
    if r.get("redistributed_bytes") is not None:
        lines.append(f"- redistributed state bytes: "
                     f"{_fmt_bytes(r['redistributed_bytes'])}")
    for ev in r.get("resize_events", []):
        phases = ev.get("phases") or {}
        breakdown = ", ".join(f"{k} {float(v):.0f}ms"
                              for k, v in phases.items()
                              if isinstance(v, (int, float)))
        lines.append(
            f"- resize (gen {ev.get('generation')}, "
            f"{ev.get('reason')}): world {ev.get('old_world')} -> "
            f"{ev.get('new_world')}, mesh {ev.get('old_shape')} -> "
            f"{ev.get('new_shape')} at iter {ev.get('iteration')}, "
            f"downtime {float(ev.get('downtime_ms') or 0):.0f}ms"
            + (f" ({breakdown})" if breakdown else ""))
        redist = ev.get("redistribution") or {}
        if redist.get("redistributed_bytes"):
            lines.append(
                f"  - moved {_fmt_bytes(redist['redistributed_bytes'])}"
                f": {redist.get('gather_leaves', 0)} leaf/leaves "
                f"({_fmt_bytes(redist.get('gather_bytes', 0))}) via "
                f"live gather, {redist.get('checkpoint_leaves', 0)} "
                f"({_fmt_bytes(redist.get('checkpoint_bytes', 0))}) "
                f"via checkpoint reshard")
    for ev in r.get("runstate_remap_events", []):
        lines.append(
            f"- runstate remap: wanted {ev.get('wanted')}, used "
            f"{ev.get('used')} (epoch {ev.get('membership_epoch')}, "
            f"p{ev.get('process_index')})")
    return lines


def _quality_section(s):
    """Markdown lines for the quality observability section (ISSUE
    18): the per-sweep FID/KID trend table, reference-store hit
    accounting, and the regression sentinel's verdict. Empty when the
    run ran no eval sweeps."""
    q = s.get("quality") or {}
    if not q.get("present"):
        return []
    series = q.get("series", {})
    lines = ["", "## quality"]
    fid = {step: v for step, v in series.get("eval/fid", [])}
    kid = {step: v for step, v in series.get("eval/kid", [])}
    ttf = {step: v for step, v in
           series.get("eval/time_to_fid_ms", [])}
    hit = {step: v for step, v in
           series.get("eval/ref_cache_hit", [])}
    steps = [step for step, _ in series.get("eval/fid", [])]
    if steps:
        lines.append("| sweep | step | fid | kid | time-to-fid ms "
                     "| ref hit |")
        lines.append("|---|---|---|---|---|---|")
        for i, step in enumerate(steps):
            kid_v = kid.get(step)
            ttf_v = ttf.get(step)
            lines.append(
                f"| {i + 1} | {step} | {fid.get(step, 0):.3f} "
                f"| {f'{kid_v:.5f}' if kid_v is not None else '-'} "
                f"| {f'{ttf_v:.0f}' if ttf_v is not None else '-'} "
                f"| {'yes' if hit.get(step) else 'no'} |")
    hits, misses = q.get("ref_cache_hits", 0), q.get("ref_cache_misses", 0)
    if hits or misses:
        lines.append(f"- reference store: {hits} hit(s), {misses} "
                     f"miss(es)"
                     + (f", !! {q['store_corrupt']} corrupt shard(s) "
                        f"quarantined" if q.get("store_corrupt") else ""))
    if q.get("fid_best") is not None:
        lines.append(f"- fid: best {q['fid_best']:.3f}, latest "
                     f"{q['fid_latest']:.3f} over "
                     f"{q.get('sweep_count', 0)} sweep(s)")
    n_reg = q.get("regressions", 0)
    if n_reg:
        lines.append(f"!! quality regressions: {n_reg}")
        for ev in q.get("regression_events", [])[:5]:
            lines.append(
                f"  - {ev.get('metric')} {ev.get('value')} vs baseline "
                f"{ev.get('baseline')} (+{100 * float(ev.get('delta') or 0):.1f}%"
                f", {ev.get('streak')} consecutive) at step "
                f"{ev.get('step')}")
    else:
        lines.append("- quality regressions: 0")
    return lines


def _pod_section(s):
    """Markdown lines for the pod observability section (ISSUE 17):
    cross-host step skew, straggler attribution, and the divergence
    sentinel's verdict. Empty when the run published no pod digests
    (single-process)."""
    p = s.get("pod") or {}
    if not p.get("present"):
        return []
    lines = ["", "## pod"]
    lines.append(f"- digests published: {p.get('digest_count', 0)}")
    if p.get("step_skew_ms_p50") is not None:
        lines.append(
            f"- step skew: p50 {p['step_skew_ms_p50']:.1f}ms, max "
            f"{p['step_skew_ms_max']:.1f}ms over "
            f"{len(p.get('skew_series') or [])} round(s)")
    straggler = p.get("straggler")
    if straggler:
        lines.append(
            f"- straggler: {straggler['process']} (slowest in "
            f"{straggler['rounds']} round(s), "
            f"{straggler['share'] * 100:.0f}% share, dominant span "
            f"{straggler.get('span') or 'n/a'})")
    div = p.get("divergence_count", 0)
    if div:
        lines.append(f"- !! divergence sentinel: {div} event(s)")
        for ev in p.get("divergence_events", [])[:5]:
            if ev.get("mode") == "crc":
                lines.append(f"  - step {ev.get('step')}: loss crcs "
                             f"disagree ({ev.get('crcs')})")
            else:
                lines.append(
                    f"  - step {ev.get('step')}: p{ev.get('process')} "
                    f"rel delta EWMA {ev.get('ewma')} over "
                    f"{ev.get('threshold')}")
    else:
        lines.append("- divergence sentinel: 0 events")
    return lines


def _serving_section(s):
    """Markdown lines for the serving SLO section (ISSUE 19): the
    engine's request-latency percentiles, queue/bucketing efficiency,
    and the per-executable bucket latency table. Empty when the run
    served no requests."""
    sv = s.get("serving") or {}
    if not sv.get("present"):
        return []
    lines = ["", "## serving"]
    if sv.get("p50_ms") is not None:
        lines.append(
            f"- request latency: p50 {sv['p50_ms']:.1f}ms, p99 "
            f"{sv['p99_ms']:.1f}ms over {sv.get('requests', 0)} "
            f"request(s)")
    if sv.get("bucket_hit_rate") is not None:
        lines.append(
            f"- bucketing: hit rate "
            f"{sv['bucket_hit_rate'] * 100:.0f}%, pad waste "
            f"{(sv.get('pad_waste_frac') or 0) * 100:.1f}% of lanes, "
            f"queue depth {sv.get('queue_depth') or 0:.0f}")
    if sv.get("hbm_headroom_frac") is not None:
        lines.append(f"- hbm headroom: "
                     f"{sv['hbm_headroom_frac'] * 100:.0f}%")
    wm = sv.get("weights_meta") or {}
    if wm:
        verified = wm.get("verified")
        lines.append(f"- weights: {wm.get('checkpoint', '?')} "
                     f"({'verified restore' if verified else '!! UNVERIFIED'})")
    buckets = sv.get("buckets") or {}
    if buckets:
        lines.append("| executable | exec p50 ms | exec p99 ms | batches |")
        lines.append("|---|---|---|---|")
        for label in sorted(buckets):
            b = buckets[label]
            p50, p99 = b.get("p50_ms"), b.get("p99_ms")
            lines.append(
                f"| {label} "
                f"| {f'{p50:.1f}' if p50 is not None else '-'} "
                f"| {f'{p99:.1f}' if p99 is not None else '-'} "
                f"| {int(b.get('count') or 0)} |")
    lines.extend(_trace_lines(sv))
    lines.extend(_slo_lines(sv))
    return lines


def _trace_lines(sv):
    """Span-breakdown lines from the request-scoped traces (ISSUE 20):
    where the aggregate request latency actually goes, stage by stage,
    plus eviction-recompile attribution and stream lifecycle counts."""
    tr = sv.get("traces") or {}
    if not tr.get("present"):
        return []
    lines = [
        f"- traces: {tr.get('count', 0)} request(s) recorded "
        f"({tr.get('breaches', 0)} SLO breach(es), "
        f"{tr.get('evict_recompiles', 0)} evict-recompile(s))"]
    spans = tr.get("spans") or {}
    if spans:
        lines.append("| span | count | total ms | mean ms | p50 ms "
                     "| p99 ms |")
        lines.append("|---|---|---|---|---|---|")
        # pipeline order, then anything unexpected alphabetically
        order = ("admit", "queue_wait", "bucket/pad", "h2d_transfer",
                 "execute", "d2h/slice", "respond")
        names = [n for n in order if n in spans] \
            + sorted(n for n in spans if n not in order)
        for name in names:
            row = spans[name]
            lines.append(
                f"| {name} | {row['count']} | {row['total_ms']:.2f} "
                f"| {row['mean_ms']:.3f} | {row['p50_ms']:.3f} "
                f"| {row['p99_ms']:.3f} |")
    stream_ids = tr.get("stream_ids") or []
    if stream_ids or tr.get("stream_events"):
        lines.append(
            f"- streams: {len(stream_ids)} stream(s) traced, "
            f"{len(tr.get('stream_events') or [])} lifecycle event(s)")
    return lines


def _slo_lines(sv):
    """Error-budget lines (ISSUE 20): burn-rate extremes over the run
    and the dominant-span attribution of each breach."""
    slo = sv.get("slo") or {}
    if not slo.get("present"):
        return []
    cfg = slo.get("config") or {}
    lines = []
    if cfg:
        lines.append(
            f"- slo: p99 target {cfg.get('p99_ms')}ms at "
            f"{cfg.get('availability')} availability "
            f"(window {cfg.get('window')})")
    if slo.get("burn_rate_max") is not None:
        lines.append(
            f"- error budget: burn rate latest "
            f"{slo['burn_rate_latest']:.3f} / max "
            f"{slo['burn_rate_max']:.3f}, budget remaining "
            f"{(slo.get('budget_remaining_frac') or 0) * 100:.1f}% "
            f"(min {(slo.get('budget_remaining_min') or 0) * 100:.1f}%)")
    n = slo.get("breaches", 0)
    if n:
        lines.append(f"!! slo breaches: {n} "
                     f"({slo.get('rejected', 0)} shed at admission)")
        by_span = {}
        for ev in slo.get("breach_events") or []:
            by_span.setdefault(ev.get("dominant_span") or "rejected",
                               []).append(ev)
        for span in sorted(by_span, key=lambda k: -len(by_span[k])):
            evs = by_span[span]
            worst = max((float(e.get("e2e_ms") or 0) for e in evs),
                        default=0.0)
            lines.append(f"  - dominant span {span}: {len(evs)} "
                         f"breach(es), worst e2e {worst:.1f}ms")
    else:
        lines.append("- slo breaches: 0")
    return lines


def render_serving_report(path_or_events):
    """Standalone '## serving' deep-dive (the ``telemetry_report.py
    --serving`` flag, matching the ``--pod`` pattern): span breakdown
    table, SLO budget history, and the slowest sampled traces."""
    events = (load_events(path_or_events)
              if isinstance(path_or_events, str) else path_or_events)
    s = summarize(events)
    sv = s.get("serving") or {}
    if not sv.get("present"):
        return "# serving\n(no serving telemetry in this run)"
    lines = ["# serving"]
    lines.extend(_serving_section(s)[2:])  # drop the blank + "## serving"
    slo = sv.get("slo") or {}
    budget_series = (slo.get("series") or {}).get(
        "serve/slo/budget_remaining_frac", [])
    if budget_series:
        lines.append("")
        lines.append("budget history (step, remaining frac):")
        step_width = max(12, len(budget_series))
        stride = max(len(budget_series) // step_width, 1)
        for step, value in budget_series[::stride]:
            bar = "#" * int(round(float(value or 0) * 20))
            lines.append(f"  {step:>6} {float(value or 0):.3f} {bar}")
    records = (sv.get("traces") or {}).get("records") or []
    slowest = sorted(records,
                     key=lambda r: -float(r.get("e2e_ms") or 0))[:5]
    if slowest:
        lines.append("")
        lines.append("slowest traces:")
        for rec in slowest:
            spans = ", ".join(
                f"{sp['name']} {float(sp.get('dur_ms') or 0):.1f}ms"
                for sp in rec.get("spans") or [])
            flags = []
            if rec.get("slo_breach"):
                flags.append("BREACH")
            if rec.get("evict_recompile"):
                flags.append("evict-recompile")
            if not rec.get("warm_hit", True):
                flags.append("cold")
            lines.append(
                f"- {rec.get('trace_id')} "
                f"e2e {float(rec.get('e2e_ms') or 0):.1f}ms on "
                f"{rec.get('executable', '?')}"
                + (f" [{' '.join(flags)}]" if flags else ""))
            lines.append(f"    {spans}")
    return "\n".join(lines)


def _window_note(attn, layer):
    """What the ``attn_impl`` line says after a layer's arm: its window
    and, where the meta counts them, the tiles the kernel computes
    toward its output and toward each gradient over those on or below
    the diagonal; nothing for a layer
    without a window."""
    window = (attn.get("windows") or {}).get(layer)
    if window is None:
        return ""
    visited = (attn.get("visited_tiles") or {}).get(layer)
    if not visited:
        return f" (window {window})"
    return f" (window {window}: " + ", ".join(
        f"{name} {done} of {below}"
        for name, (done, below) in visited.items()) + " tiles a head)"


def _experts_section(s):
    """Routing of a token model's expert layers: the latest
    ``moe/<layer>/*`` counters (``trainers/lm.py``'s flush hook), the
    rows a pass over the layer's buffer moved over the rows it held
    (``moe/<layer>/moved_rows``: 1 where the movement ends with the held
    rows, the tier over them where it moves the tier whole), and of
    the flushes' newest steps the share that computed on the filled
    prefix of the buffer (``moe/<layer>/compact``); "n/a" in a run from
    before either counter."""
    layers = {}
    for name, (value, _) in s["counters"].items():
        parts = name.split("/")
        if len(parts) == 3 and parts[0] == "moe":
            layers.setdefault(parts[1], {})[parts[2]] = value
    if not layers:
        return []
    share = s.get("experts_compact_share") or {}
    lines = ["", "## experts",
             "| layer | held assignments | fullest over mean "
             "| buffer occupancy | moved over held | on the prefix |",
             "|---|---|---|---|---|---|"]
    for layer in sorted(layers, key=lambda k: (len(k), k)):
        row = layers[layer]
        held = row.get("held_assignments", float("nan"))
        lines.append(
            f"| {layer} | {held:.0f} "
            f"| {row.get('load_max_over_mean', float('nan')):.2f} "
            f"| {row.get('buffer_occupancy', float('nan')) * 100:.1f}% "
            + (f"| {row['moved_rows'] / held:.2f} "
               if "moved_rows" in row and held > 0 else "| n/a ")
            + (f"| {share[layer] * 100:.0f}% |" if layer in share
               else "| n/a |"))
    return lines


def render_report(path_or_events):
    """Markdown-ish report for a telemetry.jsonl path or a pre-loaded
    event list."""
    events = (load_events(path_or_events)
              if isinstance(path_or_events, str) else path_or_events)
    s = summarize(events)
    lines = ["# telemetry phase breakdown",
             f"wall: {s['wall_s']:.3f}s over {len(events)} events", "",
             "| phase | count | total ms | mean ms | p50 ms | p99 ms "
             "| % of wall |",
             "|---|---|---|---|---|---|---|"]
    order = sorted(s["phases"].items(),
                   key=lambda kv: -kv[1]["total_ms"])
    for name, row in order:
        lines.append(
            f"| {name} | {row['count']} | {row['total_ms']:.2f} "
            f"| {row['mean_ms']:.2f} | {row['p50_ms']:.2f} "
            f"| {row['p99_ms']:.2f} | {row['share_pct']:.1f}% |")
    if not s["phases"]:
        lines.append("| (no spans recorded) | | | | | | |")
    lines.append("")
    lines.append("phases nest (vid2vid dis_step runs inside gen_step); "
                 "durations are dispatch times on async backends — wall "
                 "and imgs/sec are fenced at flush intervals.")

    # perf/*, and a token model's two losses (lm/main, lm/mtp)
    perf = {k: v for k, v in s["counters"].items()
            if k.startswith(("perf/", "lm/"))}
    if perf:
        lines.append("")
        lines.append("derived counters (latest):")
        for name, (value, step) in sorted(perf.items()):
            if name == "perf/mfu":
                lines.append(f"- {name}: {value * 100:.2f}% "
                             f"(step {step})")
            else:
                lines.append(f"- {name}: {value:.4g} (step {step})")
    flops_meta = s["meta"].get("step_flops")
    if flops_meta:
        peak = flops_meta.get("peak_flops")
        lines.append(f"- step_flops: {flops_meta.get('flops'):.4g} "
                     f"({flops_meta.get('source')}, "
                     + (f"peak {peak:.4g} FLOP/s via " if peak else "")
                     + f"{flops_meta.get('peak_source')})")
    attn = s["meta"].get("attn_impl")
    if attn:
        tiles = attn.get("tiles") or {}
        layers = attn.get("layers") or {}
        padded = ("fused" in layers.values() and attn.get("kernel_head_dim")
                  not in (None, attn.get("head_dim")))
        lines.append(
            f"- attn_impl at length {attn.get('length')}"
            + (f", head size {attn['head_dim']}" if "head_dim" in attn
               else "") + ": "
            + ", ".join(f"layer {i} {arm}" + _window_note(attn, i)
                        for i, arm in sorted(
                layers.items(), key=lambda kv: int(kv[0])))
            + "; fused "
            + (f"at head size {attn['kernel_head_dim']} (zero-padded), "
               if padded else "")
            + "tiles (queries x keys) "
            + ", ".join(f"{k} {'x'.join(map(str, v))}"
                        for k, v in tiles.items())
            + (f"; the blocks keep {sum(attn['kept_bytes'].values())} bytes "
               "of the kernel's forward passes for its backward passes"
               if "kept_bytes" in attn else "")
            + (f"; one backward sweep of {attn['backward_products']} "
               f"products a tile, {attn.get('vmem_accumulator_bytes')} "
               "bytes of a key-value head's dk and dv standing in VMEM"
               if "backward_products" in attn else ""))
    kda = s["meta"].get("kda_impl")
    if kda:
        lines.append(
            f"- kda_impl: layers {', '.join(map(str, kda.get('layers')))}; "
            f"{kda.get('heads')} heads of {kda.get('head_dim')} held; "
            f"chunks of {kda.get('chunk')} steps in sub-blocks of "
            f"{kda.get('sub_block')}, {kda.get('chunks_at_once')} at once"
            + ("; " + ", ".join(
                f"layer {i} {arm}" for i, arm in sorted(
                    kda["arm"].items(), key=lambda kv: int(kv[0])))
               + "; fused tiles (chunks a grid step) "
               + ", ".join(f"{k} {v}" for k, v in
                           (kda.get("tiles") or {}).items())
               + f"; the blocks keep {sum(kda['kept_bytes'].values())} "
               "bytes of the kernel's forward sweeps for its backward "
               "sweeps" if "arm" in kda else ""))
    ssd = s["meta"].get("ssd_impl")
    if ssd:
        lines.append(
            f"- ssd_impl: layers {', '.join(map(str, ssd.get('layers')))}; "
            f"{ssd.get('heads')} heads of {ssd.get('head_dim')} in "
            f"{ssd.get('groups')} groups, state {ssd.get('state')}, chunks "
            f"of {ssd.get('chunk')} steps; "
            + ", ".join(f"layer {i} {arm}" for i, arm in sorted(
                (ssd.get("arm") or {}).items(), key=lambda kv: int(kv[0])))
            + "; fused tiles (chunks a grid step) "
            + ", ".join(f"{k} {v}" for k, v in
                        (ssd.get("tiles") or {}).items())
            + "; the blocks keep "
            f"{sum((ssd.get('kept_bytes') or {}).values())} bytes of the "
            "kernel's forward sweeps for its backward sweeps")
    moe = s["meta"].get("moe_impl")
    if moe:
        lines.append(
            "- moe_impl: "
            + ", ".join(f"layer {i} {arm}" for i, arm in sorted(
                (moe.get("layers") or {}).items(), key=lambda kv: int(kv[0])))
            + f"; {moe.get('held')} held experts of {moe.get('hidden')} x "
            f"{moe.get('width')} on tiers of "
            f"{', '.join(map(str, moe.get('tiers')))} rows; kernel tiles "
            "(rows x width) "
            + "; ".join(
                f"{way} " + ", ".join(f"{k} {'x'.join(map(str, v))}"
                                      for k, v in tiles.items())
                for way, tiles in (moe.get("tiles") or {}).items())
            + ("; routers read " + ", ".join(
                f"layer {i} {read}" for i, read in sorted(
                    moe["router_input"].items(), key=lambda kv: int(kv[0])))
               + f", scored by {moe.get('scoring')}; experts "
               f"{moe.get('activation')}, a buffer of "
               f"{moe.get('buffer_rows')} rows"
               if "router_input" in moe else ""))
    lines.extend(_experts_section(s))
    lines.extend(_health_section(s))
    lines.extend(_xla_section(s))
    lines.extend(_graph_section(s))
    lines.extend(_resilience_section(s))
    lines.extend(_elasticity_section(s))
    lines.extend(_quality_section(s))
    lines.extend(_pod_section(s))
    lines.extend(_serving_section(s))
    if s["hangs"]:
        lines.append("")
        lines.append(f"!! {len(s['hangs'])} hang dump(s) recorded:")
        for hang in s["hangs"]:
            threads = ", ".join(sorted(hang.get("stacks", {})))
            lines.append(f"- step {hang.get('step')}: "
                         f"{hang.get('reason')} [threads: {threads}]")
    return "\n".join(lines)
