"""Structured run telemetry: spans, counters, live throughput/MFU, and
a hang watchdog, fanned out to pluggable sinks.

Why this exists (ISSUE 2): the training loop previously emitted nothing
but loss scalars through the torch-TensorBoard ``Meter`` — no live
throughput, no per-step phase attribution, no way to tell a hung
prefetcher from a slow compile. This module is the process-wide event
bus the whole stack reports into:

- ``span(name)``       — context manager timing one phase of one step.
  Every span is also a ``jax.profiler.TraceAnnotation`` named
  ``imaginaire/<name>`` on the thread it runs on: a flag test while no
  profiler session is active, and while one is (``trace_at_step``, a
  benchmark's own ``start_trace``) an event on the host plane of the
  same ``.xplane.pb`` that holds the device's operations, on the
  profiler's clock, so a device gap can be laid against what the host
  was in. The training path's spans, by layer boundary; the spans of one
  iteration on the loop thread carry the iteration ``start_of_iteration``
  was given:

  - ``data_wait`` (loop thread): blocked in ``next(feed)``, and nothing
    else.
  - ``start_of_iteration`` (loop): host hook and placement of a batch
    that was not prefetched; near zero for one that was.
  - ``dis_step`` / ``gen_step`` (loop): the host's time to ENQUEUE the
    step program, pytree fingerprint included: the dispatch, not the
    step's time on the device.
  - ``health_poll`` (loop): blocked on the device for the previous
    program's flags, the one place the loop thread waits for the device.
  - ``end_of_iteration`` (loop): meters, ``step_complete``, the flush
    and its fence when due; ``ckpt`` and ``eval`` nest under it.
  - ``prefetch_host`` / ``prefetch_preprocess`` / ``prefetch_transfer``
    / ``prefetch_put`` (``device-prefetch`` thread): waiting for the
    loader's next batch; the trainer's host hook; placement, to the
    batch being ON the device (the H2D time); blocked on a full queue
    (the feed is ahead).
  - ``loader_fetch`` (a loader worker, one per sample): read, decode,
    augment, label encoding. ``loader_collate`` (the loader's producer
    thread): stacking one batch.
  - ``init_state`` (caller): building and placing the train state.

  A ``block_until_ready`` fence runs only at the flush interval
  (``step_complete(..., fence=...)``), so window wall-clock — and
  therefore imgs/sec and MFU — is device-true while per-span overhead
  stays at two ``perf_counter`` calls and one inactive annotation.
- derived counters     — imgs/sec over the fenced window, step-time EWMA
  and p50/p99 over a bounded ring buffer, and MFU from the XLA cost
  analysis registered once at jit time
  (``BaseTrainer._register_step_flops``).
- hang watchdog        — if no ``step_complete`` heartbeat lands within
  ``telemetry.hang_timeout_s``, every Python thread's stack (prefetcher
  producer and checkpoint pointer thread included) is dumped to the
  sinks and stderr (see ``watchdog.py``).
- on-demand tracing    — ``telemetry.trace_at_step`` captures a
  ``jax.profiler`` trace for steps ``[N, N + trace_num_steps)`` into
  ``<logdir>/trace``: the one profiler starter the program has.

The module-level singleton starts disabled (a no-op whose ``span`` hands
back a shared null context manager); entry points opt in via
``configure(cfg, logdir=...)``. Nothing here ever raises into the
training loop: telemetry failures degrade to logged warnings.
"""

from __future__ import annotations

import atexit
import logging
import sys
import threading
import time
import traceback
from collections import deque

from imaginaire_tpu.config import cfg_get

logger = logging.getLogger(__name__)

# bf16 peak FLOP/s per chip by device kind (prefix-matched), each with
# the source of the figure. A device that is not here has no peak, and
# a run on it no ``perf/mfu``: set telemetry.peak_flops to give one.
_CLOUD_TPU_DOCS = "Google Cloud TPU documentation, system architecture"
_PEAK_FLOPS_BY_KIND = (
    ("TPU v6", 918e12, f"{_CLOUD_TPU_DOCS}, 'TPU v6e'"),
    ("TPU v5p", 459e12, f"{_CLOUD_TPU_DOCS}, 'TPU v5p'"),
    # a v5e chip reports device_kind 'TPU v5 lite'
    ("TPU v5 lite", 197e12, f"{_CLOUD_TPU_DOCS}, 'TPU v5e'"),
    ("TPU v5e", 197e12, f"{_CLOUD_TPU_DOCS}, 'TPU v5e'"),
    ("TPU v4", 275e12, f"{_CLOUD_TPU_DOCS}, 'TPU v4'"),
)


def resolve_peak_flops(override=None):
    """(peak_flops, source) — config override > the table row of the
    device's own ``device_kind``. A kind the table does not know (the
    CPU among them) gets ``(None, why)``: no peak is assumed for it."""
    if override:
        return float(override), "config:telemetry.peak_flops"
    import jax

    kind = str(jax.devices()[0].device_kind)
    for prefix, peak, source in _PEAK_FLOPS_BY_KIND:
        if kind.startswith(prefix):
            return peak, f"device_kind:{kind} ({source})"
    return None, (f"no peak known for device_kind={kind}: perf/mfu not "
                  "computed (set telemetry.peak_flops to give one)")


class _NullSpan:
    """Shared no-op context manager: the disabled-telemetry fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()

# every span's name on the profiler's host plane
ANNOTATION_PREFIX = "imaginaire/"


class _Span:
    __slots__ = ("_tm", "name", "step", "parent", "_t0", "_wall",
                 "_annotation")

    def __init__(self, tm, name, step):
        self._tm = tm
        self.name = name
        self.step = step

    def __enter__(self):
        stack = self._tm._span_stack()
        self.parent = stack[-1].name if stack else None
        stack.append(self)
        if self.name in self._tm.watchdog_exempt:
            # suspend the hang watchdog for the span's duration: a long
            # FID/KID eval sweep completes no training steps by design
            # and must not read as a stall (entering the span IS
            # progress, so refresh the heartbeat too)
            with self._tm._lock:
                self._tm._exempt_depth += 1
            self._tm.last_heartbeat = self._tm._clock()
        import jax

        self._annotation = jax.profiler.TraceAnnotation(
            ANNOTATION_PREFIX + self.name)
        self._annotation.__enter__()
        self._wall = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur_s = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        stack = self._tm._span_stack()
        if stack and stack[-1] is self:
            stack.pop()
        if self.name in self._tm.watchdog_exempt:
            with self._tm._lock:
                self._tm._exempt_depth = max(self._tm._exempt_depth - 1, 0)
            # re-arm from NOW — the stall clock must not include the
            # exempt span's duration, or the watchdog fires the instant
            # a long eval returns
            self._tm.last_heartbeat = self._tm._clock()
        self._tm._record_span(self, dur_s)
        return False


class Telemetry:
    """Process-wide telemetry aggregator. Thread-safe: spans/counters may
    arrive from the prefetcher producer, the checkpoint pointer thread,
    and the watchdog concurrently with the main step loop."""

    def __init__(self, enabled=False, sinks=(), flush_every_n_steps=50,
                 ring_size=512, hang_timeout_s=0.0, trace_at_step=None,
                 trace_num_steps=5, logdir=None, peak_flops=None,
                 mfu=True, watchdog_exempt_spans=("eval",)):
        self.enabled = bool(enabled)
        self.watchdog_exempt = frozenset(watchdog_exempt_spans or ())
        self._exempt_depth = 0
        self.logdir = logdir
        self.sinks = list(sinks)
        self.flush_every_n_steps = int(flush_every_n_steps or 0)
        self.ring_size = max(int(ring_size), 8)
        self.hang_timeout_s = float(hang_timeout_s or 0.0)
        self.trace_at_step = trace_at_step
        self.trace_num_steps = int(trace_num_steps or 5)
        self.wants_mfu = bool(mfu)
        self.step_flops = None
        self.peak_flops = None
        self.peak_source = None
        if self.enabled and self.wants_mfu:
            self.peak_flops, self.peak_source = resolve_peak_flops(
                peak_flops)

        self._lock = threading.RLock()
        self._local = threading.local()
        # flush-cadence callbacks (tm, step) — xla_obs installs its
        # ledger-counter + HBM-watermark sampler here so memory is
        # sampled exactly when the window is fenced anyway
        self.flush_hooks = []
        self._events = []
        self._clock = time.monotonic
        self._ring = deque(maxlen=self.ring_size)
        self._phases = {}  # name -> [count, total_s, deque(samples)]
        self._ewma = None
        self._steps_since_flush = 0
        self._window_t0 = self._clock() if self.enabled else None
        self._window_steps = 0
        self._window_items = 0
        self.last_step = None
        self.last_heartbeat = self._clock()
        self._tracing_until = None
        self._closed = False

        self._watchdog = None
        if self.enabled and self.hang_timeout_s > 0:
            from imaginaire_tpu.telemetry.watchdog import HangWatchdog

            self._watchdog = HangWatchdog(self, self.hang_timeout_s)
            self._watchdog.start()

    # ----------------------------------------------------------- spans

    def _span_stack(self):
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name, step=None):
        """Time one phase. Cheap no-op when telemetry is disabled."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, step)

    def _record_span(self, span, dur_s):
        event = {
            "kind": "span",
            "name": span.name,
            "step": span.step if span.step is not None else self.last_step,
            "t": span._wall,
            "dur_ms": round(dur_s * 1e3, 4),
            "parent": span.parent,
            "thread": threading.current_thread().name,
        }
        with self._lock:
            self._events.append(event)
            # a span nested under a same-named span is the same wall
            # time measured twice: it must not double-count in the
            # phase totals
            if span.parent != span.name:
                phase = self._phases.get(span.name)
                if phase is None:
                    phase = self._phases[span.name] = [
                        0, 0.0, deque(maxlen=self.ring_size)]
                phase[0] += 1
                phase[1] += dur_s
                phase[2].append(dur_s)

    def timed_iter(self, iterable, name, step_of=None):
        """Yield from ``iterable`` with each ``next()`` wrapped in a
        ``span(name)`` — how the train loop attributes ``data_wait``."""
        it = iter(iterable)
        index = 0
        while True:
            step = step_of(index) if step_of is not None else None
            with self.span(name, step=step):
                try:
                    item = next(it)
                except StopIteration:
                    return
            yield item
            index += 1

    # -------------------------------------------------------- counters

    def counter(self, name, value, step=None):
        """Record a scalar. Returns True when a TensorBoardSink is
        configured (``meters.write_summary`` uses this to avoid writing
        the same scalar to TB twice)."""
        if not self.enabled:
            return False
        event = {"kind": "counter", "name": name, "value": float(value),
                 "step": step if step is not None else self.last_step,
                 "t": time.time()}
        with self._lock:
            self._events.append(event)
        from imaginaire_tpu.telemetry.sinks import TensorBoardSink

        return any(isinstance(s, TensorBoardSink) for s in self.sinks)

    def meta(self, name, **fields):
        if not self.enabled:
            return
        event = dict({"kind": "meta", "name": name, "t": time.time()},
                     **fields)
        with self._lock:
            self._events.append(event)

    def trace(self, name, **fields):
        """Record a request-scoped trace (ISSUE 20): a ``kind="trace"``
        event carrying a span list + attribution fields for ONE serving
        request (``trace/request``) or stream lifecycle transition
        (``trace/stream``). Distinct from ``span`` (aggregate phase
        timing) and ``meta`` (one-off annotations) so the report can
        collect traces without sniffing field shapes."""
        if not self.enabled:
            return
        event = dict({"kind": "trace", "name": name, "t": time.time()},
                     **fields)
        with self._lock:
            self._events.append(event)

    def set_step_flops(self, flops, source="cost_analysis"):
        """Register FLOPs per training iteration (D+G, multipliers
        included) — computed ONCE, at jit time, from
        ``lowered.compile().cost_analysis()['flops']``. MFU counters
        derive from this and the fenced window wall-clock."""
        if not self.enabled or flops is None:
            return
        self.step_flops = float(flops)
        self.meta("step_flops", flops=self.step_flops, source=source,
                  peak_flops=self.peak_flops, peak_source=self.peak_source)

    # --------------------------------------------------- step lifecycle

    def record_step(self, dur_s, items=0, step=None):
        """Account one completed step (the testable seam under
        ``step_complete``): ring buffer + EWMA + window totals."""
        if not self.enabled:
            return
        with self._lock:
            if self._window_t0 is None:
                self._window_t0 = self._clock()
            if dur_s is not None:
                self._ring.append(float(dur_s))
                self._ewma = (float(dur_s) if self._ewma is None
                              else 0.9 * self._ewma + 0.1 * float(dur_s))
            self._window_steps += 1
            self._window_items += int(items or 0)
            self._steps_since_flush += 1
            if step is not None:
                self.last_step = step

    def step_complete(self, step, items=0, dur_s=None, fence=None):
        """Heartbeat: one training iteration finished. Feeds the
        watchdog, the ring-buffer stats, the trace-at-step knob, and —
        every ``flush_every_n_steps`` — triggers the fenced flush."""
        if not self.enabled:
            return
        self.record_step(dur_s, items=items, step=step)
        self.last_heartbeat = self._clock()
        self._maybe_trace(step)
        if (self.flush_every_n_steps > 0
                and self._steps_since_flush >= self.flush_every_n_steps):
            self.flush(step=step, fence=fence)

    def heartbeat(self, step=None):
        """Liveness-only heartbeat for long non-step phases (eval,
        checkpoint commit) so the watchdog doesn't cry wolf."""
        if step is not None:
            self.last_step = step
        self.last_heartbeat = self._clock()

    def watchdog_suspended(self):
        """True while a watchdog-exempt span (``eval`` by default; see
        ``telemetry.watchdog_exempt_spans``) is open on any thread —
        the watchdog skips firing instead of flagging a long metric
        sweep as a hang."""
        return self._exempt_depth > 0

    # ---------------------------------------------------------- tracing

    def _maybe_trace(self, step):
        if self.trace_at_step is None or step is None:
            return
        start = int(self.trace_at_step)
        try:
            import jax

            if self._tracing_until is None and step == start:
                path = (self.logdir or ".") + "/trace"
                jax.profiler.start_trace(path)
                self._tracing_until = start + self.trace_num_steps
                self.meta("trace_started", step=step, path=path)
                logger.info("telemetry: jax.profiler trace started -> %s "
                            "(steps [%d, %d))", path, start,
                            self._tracing_until)
            elif self._tracing_until is not None \
                    and step >= self._tracing_until:
                jax.profiler.stop_trace()
                self.meta("trace_stopped", step=step,
                          scopes=self._write_trace_scopes())
                logger.info("telemetry: jax.profiler trace stopped at "
                            "step %d", step)
                self._tracing_until = None
        except Exception as e:  # noqa: BLE001 — tracing must not kill runs
            logger.warning("telemetry trace capture failed: %s", e)
            self._tracing_until = None
            self.trace_at_step = None

    def _write_trace_scopes(self):
        """``<logdir>/trace/scopes.json`` beside the trace just stopped:
        the compile ledger's {label: {instruction: op_name}}, by which a
        device event is laid under the program's named scopes wherever
        the trace is read. Returns the labels written."""
        from imaginaire_tpu.telemetry import xla_obs

        return xla_obs.write_op_names(
            (self.logdir or ".") + "/trace/scopes.json")

    # ------------------------------------------------------- aggregates

    @staticmethod
    def _percentile(samples, q):
        if not samples:
            return None
        ordered = sorted(samples)
        idx = min(int(q * (len(ordered) - 1) + 0.5), len(ordered) - 1)
        return ordered[idx]

    def _stat_counters(self, now):
        """Derived counters for the current window (caller holds lock)."""
        out = {}
        ring = list(self._ring)
        if ring:
            out["perf/step_time_ms_p50"] = self._percentile(ring, 0.50) * 1e3
            out["perf/step_time_ms_p99"] = self._percentile(ring, 0.99) * 1e3
            out["perf/step_time_ms_mean"] = sum(ring) / len(ring) * 1e3
        if self._ewma is not None:
            out["perf/step_time_ms_ewma"] = self._ewma * 1e3
        elapsed = (now - self._window_t0) if self._window_t0 is not None \
            else 0.0
        if elapsed > 0 and self._window_steps > 0:
            out["perf/steps_per_sec"] = self._window_steps / elapsed
            if self._window_items > 0:
                out["perf/imgs_per_sec"] = self._window_items / elapsed
            if self.step_flops and self.peak_flops:
                out["perf/mfu"] = (self.step_flops * self._window_steps
                                   / (elapsed * self.peak_flops))
        return out

    def flush(self, step=None, fence=None):
        """Emit derived counters, push buffered events to the sinks, and
        reset the window. ``fence`` (e.g. ``block_until_ready`` on the
        train state) runs HERE — the only device sync telemetry ever
        causes — so window wall-clock reflects device completion, not
        dispatch."""
        if not self.enabled:
            return
        if fence is not None:
            t0 = time.perf_counter()
            try:
                fence()
            except Exception as e:  # noqa: BLE001
                logger.warning("telemetry flush fence failed: %s", e)
            self.counter("perf/device_drain_ms",
                         (time.perf_counter() - t0) * 1e3, step=step)
            self.last_heartbeat = self._clock()
        for hook in list(self.flush_hooks):
            try:
                hook(self, step)
            except Exception as e:  # noqa: BLE001 — hooks never kill runs
                logger.warning("telemetry flush hook %s failed: %s",
                               getattr(hook, "__name__", hook), e)
        now = self._clock()
        with self._lock:
            stats = self._stat_counters(now)
        for name, value in stats.items():
            self.counter(name, value, step=step)
        with self._lock:
            self._window_t0 = now
            self._window_steps = 0
            self._window_items = 0
            self._steps_since_flush = 0
        self._push_to_sinks()

    def _push_to_sinks(self):
        with self._lock:
            events, self._events = self._events, []
        for sink in self.sinks:
            try:
                for event in events:
                    sink.emit(event)
                sink.flush()
            except Exception as e:  # noqa: BLE001 — sinks never kill runs
                logger.warning("telemetry sink %s failed: %s",
                               type(sink).__name__, e)

    def window_summary(self):
        """Snapshot of the current window for bench legs: wall duration,
        step p50/p99, per-phase totals, and the data_wait share. Phase
        durations are dispatch times on async backends; the wall
        duration is honest whenever the caller fenced before asking."""
        now = self._clock()
        with self._lock:
            elapsed = (now - self._window_t0) \
                if self._window_t0 is not None else 0.0
            ring = list(self._ring)
            phases = {}
            for name, (count, total_s, samples) in sorted(
                    self._phases.items()):
                entry = {"count": count,
                         "total_ms": round(total_s * 1e3, 3)}
                p50 = self._percentile(list(samples), 0.50)
                p99 = self._percentile(list(samples), 0.99)
                if p50 is not None:
                    entry["p50_ms"] = round(p50 * 1e3, 3)
                    entry["p99_ms"] = round(p99 * 1e3, 3)
                phases[name] = entry
            data_wait_s = self._phases.get("data_wait", [0, 0.0, ()])[1]
            items = self._window_items
            steps = self._window_steps
        summary = {
            "duration_s": round(elapsed, 3),
            "steps": steps,
            "phases": phases,
        }
        p50 = self._percentile(ring, 0.50)
        p99 = self._percentile(ring, 0.99)
        if p50 is not None:
            summary["step_ms_p50"] = round(p50 * 1e3, 3)
            summary["step_ms_p99"] = round(p99 * 1e3, 3)
        if elapsed > 0:
            summary["data_wait_share_pct"] = round(
                data_wait_s / elapsed * 100.0, 2)
            if items:
                summary["imgs_per_sec"] = round(items / elapsed, 3)
        return summary

    # -------------------------------------------------------- run state

    def state_dict(self):
        """JSON-serializable telemetry accounting for the checkpoint's
        runstate sidecar (resilience/, ISSUE 7): step-time ring + EWMA
        + last step, so a resumed run's p50/p99 and EWMA counters
        continue the killed run's series instead of re-warming from
        empty. Window totals are deliberately NOT captured — a resume
        starts a fresh throughput window (wall-clock across processes
        is meaningless)."""
        if not self.enabled:
            return {}
        with self._lock:
            return {"ring": [float(x) for x in self._ring],
                    "ewma": self._ewma,
                    "last_step": self.last_step}

    def load_state_dict(self, state):
        if not self.enabled or not state:
            return
        with self._lock:
            ring = state.get("ring") or []
            self._ring.clear()
            self._ring.extend(float(x) for x in ring)
            if state.get("ewma") is not None:
                self._ewma = float(state["ewma"])
            if state.get("last_step") is not None:
                self.last_step = state["last_step"]

    def reset_window(self):
        """Zero every accumulator (bench legs A/B the same process)."""
        with self._lock:
            self._ring.clear()
            self._phases.clear()
            self._ewma = None
            self._window_t0 = self._clock()
            self._window_steps = 0
            self._window_items = 0
            self._steps_since_flush = 0

    # ----------------------------------------------------- hang dumping

    @staticmethod
    def _process_identity():
        """"p<i>/<n>" for the dump header — which HOST's dump this is
        (ISSUE 8 satellite: the per-process jsonl suffix carried the
        index, the dump header did not; aggregating pod dumps without
        it meant guessing)."""
        try:
            import jax

            return f"p{jax.process_index()}/{jax.process_count()}"
        except Exception:  # noqa: BLE001 — no backend yet
            return "p0/1"

    @staticmethod
    def _cluster_liveness():
        """(header line, stalled indices) from the cross-host heartbeat
        record, or (None, []) single-process — a distributed hang dump
        should name the stalled PROCESS, not just show local threads
        parked in a collective."""
        try:
            from imaginaire_tpu.resilience import cluster

            status = cluster.peer_status()
            if not status:
                return None, []
            stalled = [i for i, rec in sorted(status.items())
                       if rec["stalled"]]
            parts = []
            for i, rec in sorted(status.items()):
                if rec["t"] is None:
                    parts.append(f"p{i}: no heartbeat")
                else:
                    parts.append(f"p{i}: {rec['age_s']:.0f}s ago "
                                 f"(step {rec['step']})"
                                 + (" STALLED" if rec["stalled"] else ""))
            return "peer heartbeats: " + "; ".join(parts), stalled
        except Exception:  # noqa: BLE001 — liveness is best-effort
            return None, []

    @staticmethod
    def _pod_skew_line():
        """Pod-skew header line (ISSUE 17): every peer's last digest
        step + wall age from the podview plane, next to the heartbeat
        line — a hung-pod stack dump should name the step laggard, not
        just the heartbeat laggard."""
        try:
            from imaginaire_tpu.telemetry import podview

            return podview.get().status_line()
        except Exception:  # noqa: BLE001 — best-effort
            return None

    def dump_stacks(self, reason):
        """Dump every Python thread's stack to the sinks and stderr —
        the watchdog's payload, also callable on demand. The header
        names this process's index/count and, on multi-process runs,
        every peer's last heartbeat (the stalled process index is the
        first thing a pod hang investigation needs)."""
        names = {t.ident: t.name for t in threading.enumerate()}
        stacks = {}
        for ident, frame in sys._current_frames().items():
            name = names.get(ident, f"thread-{ident}")
            stacks[name] = traceback.format_stack(frame)
        proc = self._process_identity()
        liveness, stalled = self._cluster_liveness()
        pod_line = self._pod_skew_line()
        event = {"kind": "hang", "t": time.time(), "reason": reason,
                 "step": self.last_step, "process": proc,
                 "stacks": stacks}
        if liveness is not None:
            event["peer_heartbeats"] = liveness
            event["stalled_processes"] = stalled
        if pod_line is not None:
            event["pod_skew"] = pod_line
        with self._lock:
            self._events.append(event)
        lines = [f"=== telemetry hang dump [{proc}]: {reason} "
                 f"(last step {self.last_step}) ==="]
        if liveness is not None:
            lines.append(liveness)
            if stalled:
                lines.append(f"!! likely stalled process(es): {stalled}")
        if pod_line is not None:
            lines.append(pod_line)
        for name, frames in stacks.items():
            lines.append(f"--- thread {name} ---")
            lines.extend(f.rstrip("\n") for f in frames)
        sys.stderr.write("\n".join(lines) + "\n")
        sys.stderr.flush()
        # immediate flush: the evidence must land before the process is
        # killed by whatever supervises the hung run
        self._push_to_sinks()

    # ---------------------------------------------------------- teardown

    def shutdown(self):
        """Final flush + sink close. Idempotent; atexit-registered."""
        if self._closed:
            return
        self._closed = True
        if self._watchdog is not None:
            self._watchdog.stop()
            self._watchdog.join(timeout=5)
            self._watchdog = None
        if not self.enabled:
            return
        if self._tracing_until is not None:
            try:
                import jax

                jax.profiler.stop_trace()
                self._write_trace_scopes()
            except Exception:  # noqa: BLE001
                pass
            self._tracing_until = None
        self.flush()
        for sink in self.sinks:
            try:
                sink.close()
            except Exception as e:  # noqa: BLE001
                logger.warning("telemetry sink close failed: %s", e)
        # a shut-down instance must not keep buffering events nobody
        # will ever flush
        self.enabled = False


# -------------------------------------------------- module-level singleton

_TELEMETRY = Telemetry(enabled=False)
_ATEXIT_REGISTERED = False


def get():
    """The process telemetry singleton (a disabled no-op until an entry
    point calls ``configure``)."""
    return _TELEMETRY


def span(name, step=None):
    """Module-level convenience: ``telemetry.span('ckpt')``."""
    return _TELEMETRY.span(name, step=step)


def telemetry_settings(cfg):
    """Parse the ``telemetry`` config section into Telemetry kwargs."""
    tcfg = cfg_get(cfg or {}, "telemetry", None) or {}
    return {
        "enabled": bool(cfg_get(tcfg, "enabled", True)),
        "sinks": list(cfg_get(tcfg, "sinks", ["jsonl", "tensorboard"])),
        "flush_every_n_steps": int(cfg_get(tcfg, "flush_every_n_steps",
                                           50)),
        "ring_size": int(cfg_get(tcfg, "ring_size", 512)),
        "hang_timeout_s": float(cfg_get(tcfg, "hang_timeout_s", 0) or 0),
        "trace_at_step": cfg_get(tcfg, "trace_at_step", None),
        "trace_num_steps": int(cfg_get(tcfg, "trace_num_steps", 5)),
        "peak_flops": cfg_get(tcfg, "peak_flops", None),
        "mfu": bool(cfg_get(tcfg, "mfu", True)),
        "watchdog_exempt_spans": tuple(
            cfg_get(tcfg, "watchdog_exempt_spans", None) or ("eval",)),
    }


def configure(cfg=None, logdir=None, **overrides):
    """Install the process telemetry singleton from a config tree plus
    keyword overrides. Replaces (and shuts down) any previous instance;
    returns the new one. ``sinks`` may be sink names (built via
    ``make_sinks``) or already-constructed Sink objects."""
    global _TELEMETRY, _ATEXIT_REGISTERED
    settings = telemetry_settings(cfg)
    settings.update(overrides)
    if logdir is not None:
        settings["logdir"] = logdir
    sinks = settings.pop("sinks", [])
    if sinks and not all(hasattr(s, "emit") for s in sinks):
        from imaginaire_tpu.telemetry.sinks import make_sinks

        sinks = make_sinks(sinks, settings.get("logdir"))
    old, _TELEMETRY = _TELEMETRY, Telemetry(sinks=sinks, **settings)
    old.shutdown()
    # XLA observability (xla_obs.py) rides the same configure call:
    # adopt cfg.xla_obs, replay compiles that predate this instance
    # into its sinks, and install the flush-cadence memory sampler
    try:
        from imaginaire_tpu.telemetry import xla_obs

        xla_obs.on_telemetry_configured(cfg, _TELEMETRY)
    except Exception as e:  # noqa: BLE001 — observability is best-effort
        logger.warning("xla_obs configure failed: %s", e)
    # pod observability plane (podview.py, ISSUE 17) rides it too:
    # cross-host digest exchange + straggler/divergence sentinels,
    # active exactly when the cluster layer is
    try:
        from imaginaire_tpu.telemetry import podview

        podview.on_telemetry_configured(cfg, _TELEMETRY)
    except Exception as e:  # noqa: BLE001 — observability is best-effort
        logger.warning("podview configure failed: %s", e)
    if not _ATEXIT_REGISTERED:
        atexit.register(lambda: _TELEMETRY.shutdown())
        _ATEXIT_REGISTERED = True
    return _TELEMETRY
