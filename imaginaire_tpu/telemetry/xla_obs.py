"""XLA compile ledger + device-memory observability (ISSUE 5).

The two failure modes that actually kill TPU runs are invisible to the
span/counter telemetry of ISSUE 2/3: silent recompilation storms (a
dtype or sharding drift re-specializes the step program every iteration
and the run quietly gets 100x slower) and HBM exhaustion (the OOM
message names an allocation, not what was resident). Three coupled
subsystems, all reporting through the existing ``Telemetry`` sinks:

- **Compile ledger** — every labeled program (``dis_step`` /
  ``gen_step``, the vid2vid per-frame programs, the flow-cache teacher,
  the inception extractor) registers through
  ``compiled_program(label, fn)``. The wrapper dispatches through its
  own fingerprint -> AOT-executable table, so the *same* compile that
  runs the step also yields ``memory_analysis()`` (temp/argument/
  output/generated-code bytes) and ``cost_analysis()`` FLOPs — the
  ``BaseTrainer._register_step_flops`` lower/compile duplicate is gone.
  Each compile is timed (lowering and XLA compile separately), written
  to ``logs/<run>/compile_ledger.jsonl``, emitted as
  ``xla/compile/<label>/*`` counters + an ``xla_compile/<label>`` meta
  event, and announces itself via an open "compiling <label>" record
  the hang watchdog names in its dump header.
- **Recompile tripwire** — per wrapper, inputs are fingerprinted by
  (pytree path, dtype, shape, sharding). Any compile after the first is
  a recompile: the structural diff against the previous fingerprint is
  logged naming the changed leaf, ``xla/recompiles`` increments, and
  under ``xla_obs.strict_recompile`` a ``RecompileError`` raises.
  Legitimate re-specialization stays silent: shape-polymorphic labels
  (vid2vid's growing-sequence rollout) register with
  ``allow_shape_growth`` and dtype/sharding-stable shape changes —
  including leaves APPEARING as the conditioning ring buffers fill over
  the first frames — don't count; deliberate re-jits (fs_vid2vid
  finetune swaps the optimizer)
  call ``retrace(reason)`` or appear in
  ``xla_obs.expected_recompiles``.
- **HBM accounting + OOM forensics** — per-device ``memory_stats()``
  watermarks (``mem/<dev>/bytes_in_use|peak_bytes_in_use|
  largest_alloc_size``) sample on the telemetry flush cadence and feed
  a bounded history ring; ``live_array_census()`` groups
  ``jax.live_arrays()`` by shape/dtype; ``static_budget_report()``
  combines executable footprints with param/opt/EMA tree sizes. A
  ``RESOURCE_EXHAUSTED`` escaping a wrapped program (or an explicit
  ``with oom_forensics(...)`` block) dumps
  ``logs/<run>/oom_report.json`` — watermark history, census,
  per-executable footprints, parsed requested allocation — before
  re-raising. Everything degrades gracefully to no-ops on CPU, where
  ``memory_stats()`` is ``None``.

Nothing here ever raises into the step loop except the opt-in
``strict_recompile`` tripwire: ledger/memory failures degrade to logged
warnings, and a failed AOT dispatch falls back to the plain jit path.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import re
import threading
import time
from collections import deque
from contextlib import contextmanager

from imaginaire_tpu.config import cfg_get

logger = logging.getLogger(__name__)

_MEM_FIELDS = (
    ("temp_bytes", "temp_size_in_bytes"),
    ("argument_bytes", "argument_size_in_bytes"),
    ("output_bytes", "output_size_in_bytes"),
    ("alias_bytes", "alias_size_in_bytes"),
    ("generated_code_bytes", "generated_code_size_in_bytes"),
)

# memory_stats() keys worth a counter per device (TPU allocator names)
_MEM_STAT_KEYS = ("bytes_in_use", "peak_bytes_in_use",
                  "largest_alloc_size", "bytes_limit")


class RecompileError(RuntimeError):
    """A post-warmup recompile under ``xla_obs.strict_recompile``."""


class _Settings:
    """Module-wide knobs (``cfg.xla_obs``), installed by ``configure``.

    The module starts with permissive defaults so programs built before
    the entry point configures telemetry (the dryrun warms its step
    programs first) are still ledgered — their records replay into the
    telemetry instance when it arrives.
    """

    def __init__(self):
        self.enabled = True
        self.strict_recompile = False
        self.expected_recompiles = ()
        self.ledger_file = True
        self.mem_sample = True
        self.mem_budget_frac = 0.9
        self.census_top = 20
        self.oom_report = True
        self.logdir = None
        # graph audit (imaginaire_tpu/analysis): every compile's jaxpr
        # + HLO are statically checked and the verdict rides the ledger
        self.graph_audit = True
        self.audit_hlo = True
        self.audit_const_bytes = 4 << 20


_SETTINGS = _Settings()


def settings():
    return _SETTINGS


def xla_obs_settings(cfg):
    """Parse the ``xla_obs`` config section into settings kwargs."""
    ocfg = cfg_get(cfg or {}, "xla_obs", None) or {}
    return {
        "enabled": bool(cfg_get(ocfg, "enabled", True)),
        "strict_recompile": bool(cfg_get(ocfg, "strict_recompile", False)),
        "expected_recompiles": tuple(
            cfg_get(ocfg, "expected_recompiles", None) or ()),
        "ledger_file": bool(cfg_get(ocfg, "ledger_file", True)),
        "mem_sample": bool(cfg_get(ocfg, "mem_sample", True)),
        "mem_budget_frac": float(cfg_get(ocfg, "mem_budget_frac", 0.9)),
        "census_top": int(cfg_get(ocfg, "census_top", 20)),
        "oom_report": bool(cfg_get(ocfg, "oom_report", True)),
        "graph_audit": bool(cfg_get(ocfg, "graph_audit", True)),
        "audit_hlo": bool(cfg_get(ocfg, "audit_hlo", True)),
        "audit_const_bytes": int(cfg_get(ocfg, "audit_const_bytes",
                                         4 << 20)),
    }


# ------------------------------------------------------------ fingerprints


def _leaf_spec(x):
    """(dtype, shape, sharding) identity of one pytree leaf.

    Sharding collapses to three classes: ``host`` (numpy / scalars),
    ``single`` (any single-device array — the default-device layouts
    XLA treats identically), or the NamedSharding spec + mesh shape.
    Finer distinctions would split fingerprints that compile to the
    same executable; coarser ones would hand an AOT executable inputs
    it must reject (the dispatch path catches that and falls back).
    """
    shape = tuple(int(s) for s in getattr(x, "shape", ()))
    dtype = str(getattr(x, "dtype", type(x).__name__))
    sharding = getattr(x, "sharding", None)
    if sharding is None:
        kind = "host"
    else:
        try:
            from jax.sharding import NamedSharding

            if isinstance(sharding, NamedSharding):
                kind = (f"{sharding.spec}@"
                        f"{tuple(sorted(dict(sharding.mesh.shape).items()))}")
            else:
                kind = "single"
        except Exception:  # noqa: BLE001
            kind = "single"
    return (dtype, shape, kind)


def fingerprint(args):
    """{path: (dtype, shape, sharding)} over the call's pytree leaves,
    plus a stable 12-hex digest of it."""
    import jax

    leaves = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(args)[0]:
        leaves[jax.tree_util.keystr(path)] = _leaf_spec(leaf)
    digest = hashlib.md5(
        repr(sorted(leaves.items())).encode()).hexdigest()[:12]
    return digest, leaves


def _spec_str(spec):
    dtype, shape, kind = spec
    return f"{dtype}[{','.join(str(s) for s in shape)}]:{kind}"


def fingerprint_diff(old, new):
    """Structural diff naming every changed/added/removed leaf.

    ``sharding_settle_only`` marks the one benign transition every
    training loop makes: freshly-initialized uncommitted state
    (``host``/``single``) comes back from the first step as committed
    ``NamedSharding`` arrays, and the second step re-specializes —
    plain ``jax.jit`` pays the same recompile. Settling is expected;
    the reverse direction or a spec change still counts.
    """
    changed = {p: [_spec_str(old[p]), _spec_str(new[p])]
               for p in old if p in new and old[p] != new[p]}
    added = {p: _spec_str(new[p]) for p in new if p not in old}
    removed = {p: _spec_str(old[p]) for p in old if p not in new}
    shape_only = (not added and not removed and all(
        old[p][0] == new[p][0] and old[p][2] == new[p][2]
        for p in changed))
    # growth_only: leaves APPEAR (none removed, dtype/sharding of the
    # survivors stable) — the ring-buffer warm-up shape of growth, where
    # vid2vid's conditioning stacks (past_stacks, prev_images) fill over
    # the first frames. Same legitimacy as pure shape growth; gated by
    # the same per-label allow_shape_growth opt-in.
    growth_only = (bool(added) and not removed and all(
        old[p][0] == new[p][0] and old[p][2] == new[p][2]
        for p in changed))
    settle_only = (not added and not removed and bool(changed) and all(
        old[p][0] == new[p][0] and old[p][1] == new[p][1]
        and old[p][2] in ("host", "single")
        and new[p][2] not in ("host", "single")
        for p in changed))
    return {"changed": changed, "added": added, "removed": removed,
            "shape_only": bool(changed) and shape_only,
            "growth_only": growth_only,
            "sharding_settle_only": settle_only}


# --------------------------------------------------------------- the ledger


class CompileLedger:
    """Process-wide record of every labeled compile. Thread-safe: the
    flow-teacher compiles in the prefetcher producer thread while the
    step programs compile on the main thread."""

    def __init__(self):
        self._lock = threading.RLock()
        self.records = []          # every compile entry, in order
        self.recompiles = 0        # post-warmup, unexpected only
        self.cache_hits = {}       # label -> warm-dispatch count
        self.compile_counts = {}   # label -> compile count
        self.label_flops = {}      # label -> latest cost_analysis flops
        self.label_memory = {}     # label -> latest memory_analysis dict
        self.label_op_names = {}   # label -> latest {instruction: op_name}
        self._active = []          # open (label, t_start) compile stack
        self._written = 0          # records already in the jsonl file

    # -------------------------------------------------- compile lifecycle

    def begin(self, label):
        with self._lock:
            self._active.append((label, time.time()))

    def end(self, label):
        with self._lock:
            for i in range(len(self._active) - 1, -1, -1):
                if self._active[i][0] == label:
                    del self._active[i]
                    break

    def active_compile_label(self):
        """Label of the most recently opened in-flight compile, or
        None — the watchdog's 'what is the main thread stuck on'."""
        with self._lock:
            return self._active[-1][0] if self._active else None

    def hit(self, label):
        with self._lock:
            self.cache_hits[label] = self.cache_hits.get(label, 0) + 1

    def record(self, entry):
        """Append one compile entry; emit counters/meta + jsonl line."""
        op_names = entry.pop("op_names", None)
        with self._lock:
            self.records.append(entry)
            label = entry["label"]
            if op_names is not None:
                self.label_op_names[label] = op_names
            self.compile_counts[label] = \
                self.compile_counts.get(label, 0) + 1
            if entry.get("flops") is not None:
                self.label_flops[label] = entry["flops"]
            if entry.get("memory"):
                self.label_memory[label] = entry["memory"]
            if entry.get("counted_recompile"):
                self.recompiles += 1
        self._emit(entry)
        self._append_file()

    def _emit(self, entry, tm=None):
        tm = tm or _telemetry()
        label = entry["label"]
        tm.counter(f"xla/compile/{label}/count",
                   self.compile_counts.get(label, 0))
        tm.counter(f"xla/compile/{label}/lower_ms", entry["lower_ms"])
        tm.counter(f"xla/compile/{label}/compile_ms", entry["compile_ms"])
        for key, value in (entry.get("memory") or {}).items():
            tm.counter(f"xla/compile/{label}/{key}", value)
        tm.meta(f"xla_compile/{label}",
                **{k: v for k, v in entry.items() if k != "kind"})
        audit = entry.get("audit") or {}
        if audit and "error" not in audit:
            tm.counter(f"xla/graph/{label}/violations",
                       audit.get("violation_count", 0))
            tm.counter(f"xla/graph/{label}/dead_donations",
                       (audit.get("donation") or {}).get("dead_count", 0))
            tm.counter(f"xla/graph/{label}/collective_bytes",
                       (audit.get("collectives") or {}).get("bytes", 0))
            if audit.get("violation_count"):
                tm.meta("graph_violation", label=label,
                        count=audit["violation_count"],
                        violations=audit["violations"][:8])
        if entry.get("counted_recompile"):
            tm.counter("xla/recompiles", self.recompiles)
            tm.meta("xla_recompile", label=label, diff=entry.get("diff"),
                    fingerprint=entry.get("fingerprint"))

    def _append_file(self):
        if not (_SETTINGS.ledger_file and _SETTINGS.logdir):
            return
        path = os.path.join(_SETTINGS.logdir, "compile_ledger.jsonl")
        try:
            with self._lock:
                pending = self.records[self._written:]
                self._written = len(self.records)
            if not pending:
                return
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            with open(path, "a") as f:
                for entry in pending:
                    f.write(json.dumps(entry, default=str) + "\n")
        except Exception as e:  # noqa: BLE001 — the ledger never kills runs
            logger.warning("compile ledger write failed: %s", e)

    # ---------------------------------------------------------- replays

    def replay_into(self, tm):
        """Re-emit every recorded compile into a (newly configured)
        telemetry instance — programs compiled before the entry point
        called ``telemetry.configure`` still land in its jsonl."""
        with self._lock:
            records = list(self.records)
        for entry in records:
            self._emit(entry, tm=tm)

    def flush_counters(self, tm, step=None):
        """Cadence counters: cumulative recompiles + per-label warm
        hits (cheap scalars; emitted from the telemetry flush hook)."""
        with self._lock:
            recompiles = self.recompiles
            hits = dict(self.cache_hits)
        tm.counter("xla/recompiles", recompiles, step=step)
        tm.counter("xla/graph_violations", self._graph_totals()[0],
                   step=step)
        for label, count in hits.items():
            tm.counter(f"xla/compile/{label}/cache_hits", count,
                       step=step)

    def _graph_totals(self):
        """(violations, dead_donations, collective_bytes) summed over
        the LATEST audit per label — recompiles of one program replace
        its verdict instead of double-counting it."""
        with self._lock:
            records = list(self.records)
        latest = {}
        for record in records:
            audit = record.get("audit")
            if audit and "error" not in audit:
                latest[record["label"]] = audit
        violations = sum(a.get("violation_count", 0)
                         for a in latest.values())
        dead = sum((a.get("donation") or {}).get("dead_count", 0)
                   for a in latest.values())
        coll = sum((a.get("collectives") or {}).get("bytes", 0)
                   for a in latest.values())
        return violations, dead, coll

    def snapshot(self):
        """Cumulative totals for bench-leg deltas."""
        violations, dead, coll = self._graph_totals()
        with self._lock:
            return {
                "compiles": len(self.records),
                "compile_s": round(sum(
                    (r["lower_ms"] + r["compile_ms"]) / 1e3
                    for r in self.records), 3),
                "recompiles": self.recompiles,
                "cache_hits": sum(self.cache_hits.values()),
                "graph_violations": violations,
                "dead_donations": dead,
                "collective_bytes": coll,
            }


_LEDGER = CompileLedger()


def ledger():
    return _LEDGER


def active_compile_label():
    return _LEDGER.active_compile_label()


def ledger_flops():
    """label -> latest compiled-program FLOPs (cost_analysis)."""
    return dict(_LEDGER.label_flops)


def snapshot_delta(mark=None):
    """Ledger totals since ``mark`` (a previous ``snapshot()``), plus
    the current cross-device peak HBM watermark (None on CPU)."""
    now = _LEDGER.snapshot()
    if mark:
        now = {k: round(now[k] - mark.get(k, 0), 3) for k in now}
    now["peak_hbm_bytes"] = peak_hbm_bytes()
    return now


def _telemetry():
    from imaginaire_tpu.telemetry import core

    return core.get()


# --------------------------------------------------------- wrapped programs


def trim_host_heap():
    """Hand the heap's freed pages back to the OS (glibc only; a no-op
    elsewhere). The compiler allocates and frees gigabytes of host
    memory per step program and glibc keeps them, so the next compile's
    peak lands on top of the last one's leftovers: at zoo-width SPADE
    the G-step compile peaked at 44 GiB resident on a TPU v5e host that
    has 40 (PR 22), with 6 GiB of the D step's still held."""
    import ctypes

    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass


class CompiledProgram:
    """Ledger-dispatching drop-in for ``jax.jit(fn)``.

    Calls dispatch through a fingerprint -> AOT-executable table: a
    fresh fingerprint pays one timed ``lower().compile()`` whose
    memory/cost analyses go to the ledger, warm fingerprints call the
    cached executable directly. The plain jitted function survives as
    ``.lower()`` and as the fallback when observability is
    off, ``jax_debug_nans`` is on (the eager re-run needs jit's
    dispatch path), or an AOT call rejects an input the fingerprint
    collapsed (weak-type corners) — correctness never depends on the
    ledger.
    """

    def __init__(self, label, fn, donate_argnums=(),
                 allow_shape_growth=False, out_shardings=None):
        self.label = label
        self._fn = fn
        self._donate_argnums = donate_argnums
        self._out_shardings = out_shardings
        self._jit = self._build_jit()
        self._allow_shape_growth = bool(allow_shape_growth)
        self._executables = {}
        self._fingerprints = {}
        self._last_fp = None
        self._pending_reason = None
        self._passthrough = not _SETTINGS.enabled

    def _build_jit(self):
        import jax

        # out_shardings=None is jit's own default: the compiler decides
        kwargs = {} if self._out_shardings is None else {
            "out_shardings": self._out_shardings}
        return jax.jit(self._fn, donate_argnums=self._donate_argnums,
                       **kwargs)

    # jax.jit surface the rest of the repo relies on
    def lower(self, *args, **kwargs):
        return self._jit.lower(*args, **kwargs)

    def executables(self):
        """The AOT executables compiled so far (``as_text()``,
        ``memory_analysis()``), in compile order."""
        return list(self._executables.values())

    def _cache_size(self):
        if self._passthrough:
            return self._jit._cache_size()
        return len(self._executables)

    def retrace(self, reason):
        """Deliberate re-jit (the fn's closure changed — fs_vid2vid's
        finetune swaps the optimizer): drop every cached executable and
        mark the next compile expected under ``reason``, so the ledger
        records it and the tripwire stays silent."""
        self._executables.clear()
        self._fingerprints.clear()
        self._last_fp = None
        self._pending_reason = str(reason)
        # jax's trace cache is keyed on the underlying callable, so a
        # rebuilt jax.jit(fn) would still serve the STALE jaxpr (old
        # closure baked in as constants) — clear_cache() is the only
        # invalidation that actually retraces
        try:
            self._jit.clear_cache()
        except Exception as e:  # noqa: BLE001 — older jax spellings
            logger.warning("retrace(%s): clear_cache failed (%s); "
                           "rebuilding the jit wrapper", self.label, e)
            self._jit = self._build_jit()
        _telemetry().meta("xla_retrace", label=self.label, reason=reason)

    def aot_compile(self, *args):
        """Compile (and ledger) the program for these args WITHOUT
        executing it — the capacity-planning entry
        (scripts/partition_budget.py): args may be ``ShapeDtypeStruct``
        trees carrying ``NamedSharding``s, so a shape that does not fit
        a chip can still be lowered/compiled and its
        ``memory_analysis`` recorded. Returns the ledger's memory dict
        for this label ({} when the compile failed/passthrough)."""
        try:
            digest, leaves = fingerprint(args)
        except Exception as e:  # noqa: BLE001
            logger.warning("aot_compile fingerprint failed for %s: %s",
                           self.label, e)
            return {}
        if digest not in self._executables:
            self._compile(digest, leaves, args)
        return dict(_LEDGER.label_memory.get(self.label, {}))

    def _debug_nans_on(self):
        try:
            import jax

            return bool(jax.config.jax_debug_nans)
        except Exception:  # noqa: BLE001
            return False

    def __call__(self, *args):
        if self._passthrough or self._debug_nans_on():
            return self._jit(*args)
        try:
            digest, leaves = fingerprint(args)
        except Exception as e:  # noqa: BLE001 — never break dispatch
            logger.warning("xla_obs fingerprint failed for %s: %s",
                           self.label, e)
            return self._jit(*args)
        compiled = self._executables.get(digest)
        if compiled is None:
            compiled = self._compile(digest, leaves, args)
            if compiled is None:
                return self._call_fallback(args)
        else:
            _LEDGER.hit(self.label)
        try:
            with oom_forensics(context=f"program:{self.label}"):
                return compiled(*args)
        except (TypeError, ValueError) as e:
            # an aval corner the fingerprint collapsed (e.g. weak
            # types): stay correct on the jit path and stop serving
            # this executable for that fingerprint
            logger.warning(
                "xla_obs: AOT dispatch of %s rejected its input (%s); "
                "falling back to the jit path for this fingerprint",
                self.label, str(e).split("\n")[0][:200])
            self._executables.pop(digest, None)
            return self._call_fallback(args)

    def _call_fallback(self, args):
        with oom_forensics(context=f"program:{self.label}"):
            return self._jit(*args)

    def _compile(self, digest, leaves, args):
        """Timed lower+compile, ledger entry, tripwire evaluation."""
        is_recompile = bool(self._fingerprints)
        reason, diff = None, None
        if is_recompile:
            reason = self._expected_reason()
            if reason is None and self._last_fp is not None:
                diff = fingerprint_diff(self._fingerprints[self._last_fp],
                                        leaves)
                if diff["sharding_settle_only"]:
                    # uncommitted init state settling into committed
                    # device arrays after step 1 — every label makes
                    # this transition exactly once
                    reason = "sharding_commit"
                elif self._allow_shape_growth and (
                        diff["shape_only"] or diff["growth_only"]):
                    reason = "shape_growth"
        elif self._pending_reason is not None:
            # post-retrace: the table is empty by design, but the
            # compile is still an expected re-jit worth naming
            reason, self._pending_reason = self._pending_reason, None
            is_recompile = True
        counted = is_recompile and reason is None
        _LEDGER.begin(self.label)
        trim_host_heap()
        try:
            t0 = time.perf_counter()
            # trace explicitly so the graph auditor gets the closed
            # jaxpr the lowering consumed — lower() alone discards it
            traced = self._jit.trace(*args)
            lowered = traced.lower()
            t1 = time.perf_counter()
            compiled = lowered.compile()
            t2 = time.perf_counter()
        except Exception as e:  # noqa: BLE001 — AOT path must not be fatal
            _LEDGER.end(self.label)
            logger.warning("xla_obs: lower/compile of %s failed (%s); "
                           "using the plain jit path", self.label, e)
            self._passthrough = True
            return None
        _LEDGER.end(self.label)
        entry = {
            "kind": "compile",
            "label": self.label,
            "t": time.time(),
            "fingerprint": digest,
            "lower_ms": round((t1 - t0) * 1e3, 3),
            "compile_ms": round((t2 - t1) * 1e3, 3),
            "recompile": is_recompile,
            "expected": reason,
            "counted_recompile": counted,
            "memory": _memory_dict(compiled),
            "flops": _flops_of(compiled),
        }
        if counted and diff is not None:
            entry["diff"] = diff
        # the optimized module's text, once: the graph audit reads it,
        # and with telemetry on the ledger keeps its instructions' names
        keep_names = _telemetry().enabled
        hlo_text = None
        if keep_names or (_SETTINGS.graph_audit and _SETTINGS.audit_hlo):
            hlo_text = _hlo_text(self.label, compiled)
        if _SETTINGS.graph_audit:
            entry["audit"] = _run_audit(self.label, traced, lowered,
                                        compiled, hlo_text)
        if keep_names and hlo_text is not None:
            entry["op_names"] = instruction_op_names(hlo_text)
            entry["scoped_instructions"] = scoped_instructions(
                entry["op_names"])
        _LEDGER.record(entry)
        if counted:
            text = _diff_text(diff)
            logger.warning(
                "xla_obs: post-warmup RECOMPILE of %s (#%d this process)"
                " — %s", self.label, _LEDGER.recompiles, text)
            if _SETTINGS.strict_recompile:
                raise RecompileError(
                    f"post-warmup recompile of {self.label}: {text}")
        self._fingerprints[digest] = leaves
        self._last_fp = digest
        self._executables[digest] = compiled
        return compiled

    def _expected_reason(self):
        if self._pending_reason is not None:
            reason, self._pending_reason = self._pending_reason, None
            return reason
        if self.label in _SETTINGS.expected_recompiles:
            return "xla_obs.expected_recompiles"
        return None


def compiled_program(label, fn, donate_argnums=(),
                     allow_shape_growth=False, out_shardings=None):
    """Register ``fn`` as the labeled program ``label`` (see
    ``CompiledProgram``). The drop-in for ``jax.jit(fn,
    donate_argnums=..., out_shardings=...)`` at every named compile
    site."""
    return CompiledProgram(label, fn, donate_argnums=donate_argnums,
                           allow_shape_growth=allow_shape_growth,
                           out_shardings=out_shardings)


def _hlo_text(label, compiled):
    """The executable's optimized HLO module as text, or None."""
    try:
        return compiled.as_text()
    except Exception as e:  # noqa: BLE001 — never fatal to a compile
        logger.warning("xla_obs: no HLO text of %s (%s)", label, e)
        return None


def _run_audit(label, traced, lowered, compiled, hlo_text=None):
    """Graph audit (imaginaire_tpu/analysis) for one fresh compile —
    strictly best-effort: a broken audit is a ledger note, never a
    failed program."""
    try:
        from imaginaire_tpu import analysis

        audit = analysis.audit_program(
            label, traced=traced, lowered=lowered, compiled=compiled,
            const_bytes_limit=_SETTINGS.audit_const_bytes,
            include_hlo=_SETTINGS.audit_hlo, hlo_text=hlo_text)
    except Exception as e:  # noqa: BLE001
        return {"error": f"{type(e).__name__}: {e}"}
    if audit.get("violation_count"):
        logger.warning(
            "graph audit: %d violation(s) in %s — %s",
            audit["violation_count"], label,
            "; ".join(f"{v['rule']} at {v['path']}"
                      for v in audit["violations"][:4]))
    return audit


# ------------------------------------------------------ instruction names

# The profiler names a device event by its HLO instruction
# (``%fusion.35 = ...``) and nothing else; the instruction's ``op_name``
# holds the name stack it was traced under, ``jax.named_scope``s and
# passes (``jvp``, ``transpose``, ``checkpoint``) alike. Keeping
# {instruction: op_name} a program lets a trace be read by scope after
# the trainer and its executables are gone.

# `  %name = shape op(...)`: the spaces tell an instruction's head from an
# attribute (`op_name="..."`) at the start of a continuation line
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# what the programs' own ``jax.named_scope``s begin with: the token
# model's layers, the GAN trainers' passes, the step's tail
PROGRAM_SCOPES = ("lm/", "gan/", "step/")


def instruction_op_names(hlo_text):
    """{instruction name: op_name} of an optimized HLO module's text, for
    the instructions that carry one. An instruction is read to its
    closing brace: a ``frontend_attributes`` that holds a JSON string (a
    ``pallas_call`` given ``metadata=``) is printed over several lines
    and its ``metadata={op_name=...}`` stands on the last."""
    out = {}

    def close(name, lines):
        found = _OP_NAME.search(" ".join(lines))
        if found:
            out[name] = found.group(1)

    name, lines, depth = None, [], 0
    for line in hlo_text.splitlines():
        head = _INSTRUCTION.match(line)
        if head:
            if name is not None:
                # a stray brace in a string: the next instruction's head
                # closes this one all the same
                close(name, lines)
            name, lines, depth = head.group(1), [], 0
        elif name is None:
            continue
        lines.append(line)
        depth += line.count("{") - line.count("}")
        if depth <= 0:
            close(name, lines)
            name = None
    if name is not None:
        close(name, lines)
    return out


def scoped_instructions(op_names):
    """How many instructions' name stack holds a scope of the program's
    own. 0 for a program with such scopes in its source says the
    executable was served with another build's names (a persistent
    cache keys a program without them)."""
    return sum(1 for op_name in op_names.values()
               if any(scope in op_name for scope in PROGRAM_SCOPES))


def write_op_names(path):
    """The labelled programs' {label: {instruction: op_name}} as JSON at
    ``path`` (beside a trace, so that it reads by scope wherever it is
    opened). Returns the labels written."""
    with _LEDGER._lock:
        # a label's map is replaced whole, never written to
        maps = dict(_LEDGER.label_op_names)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(maps, f)
    return sorted(maps)


def _diff_text(diff):
    if not diff:
        return "no prior fingerprint to diff"
    parts = [f"{p}: {old} -> {new}"
             for p, (old, new) in sorted(diff["changed"].items())]
    parts += [f"+{p}: {s}" for p, s in sorted(diff["added"].items())]
    parts += [f"-{p}: {s}" for p, s in sorted(diff["removed"].items())]
    return "; ".join(parts[:8]) + \
        (f" (+{len(parts) - 8} more leaves)" if len(parts) > 8 else "")


def _memory_dict(compiled):
    """``memory_analysis()`` -> plain bytes dict ({} when the backend
    doesn't report one)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001
        return {}
    if ma is None:
        return {}
    out = {}
    for name, attr in _MEM_FIELDS:
        value = getattr(ma, attr, None)
        if value is not None:
            out[name] = int(value)
    if out:
        out["total_bytes"] = sum(
            out.get(k, 0) for k in
            ("temp_bytes", "argument_bytes", "output_bytes"))
    return out


def _flops_of(compiled):
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0] if cost else {}
        flops = (cost or {}).get("flops")
        if flops is None:
            return None
        flops = float(flops)
        return flops if flops == flops and flops not in (
            float("inf"), float("-inf")) else None
    except Exception:  # noqa: BLE001
        return None


def expect_recompile(*labels, reason="expected"):
    """Config-free allowlist extension: future recompiles of ``labels``
    are expected (ledgered with ``reason``, never counted)."""
    _SETTINGS.expected_recompiles = tuple(
        set(_SETTINGS.expected_recompiles) | set(labels))
    _telemetry().meta("xla_expect_recompile", labels=list(labels),
                      reason=reason)


# ----------------------------------------------------------- HBM accounting

_WATERMARKS = deque(maxlen=256)


def device_memory_stats():
    """{device_label: memory_stats dict} — empty on backends (CPU)
    whose ``memory_stats()`` is None."""
    out = {}
    try:
        import jax

        for dev in jax.local_devices():
            stats = dev.memory_stats()
            if stats:
                out[f"{dev.platform}{dev.id}"] = dict(stats)
    except Exception as e:  # noqa: BLE001
        logger.debug("device_memory_stats unavailable: %s", e)
    return out


def peak_hbm_bytes():
    """Max peak_bytes_in_use across local devices, or None (CPU)."""
    peaks = [s.get("peak_bytes_in_use") for s in
             device_memory_stats().values() if s.get("peak_bytes_in_use")]
    return max(peaks) if peaks else None


def sample_memory(tm=None, step=None):
    """Watermark sample: one ``mem/<dev>/<stat>`` counter set per
    device plus a history-ring entry (the OOM report's time axis).
    No-op where ``memory_stats()`` is None."""
    stats = device_memory_stats()
    if not stats:
        return {}
    tm = tm or _telemetry()
    entry = {"t": time.time(), "step": step, "devices": {}}
    for dev, s in stats.items():
        row = {k: int(s[k]) for k in _MEM_STAT_KEYS if k in s}
        entry["devices"][dev] = row
        for key, value in row.items():
            tm.counter(f"mem/{dev}/{key}", value, step=step)
    _WATERMARKS.append(entry)
    return stats


def tree_bytes(tree):
    """Total array bytes in a pytree (params/opt/EMA sizing)."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        size = getattr(leaf, "size", None)
        dtype = getattr(leaf, "dtype", None)
        if size is not None and dtype is not None:
            try:
                total += int(size) * int(dtype.itemsize)
            except Exception:  # noqa: BLE001
                continue
    return total


def live_array_census(top=None):
    """``jax.live_arrays()`` grouped by (dtype, shape): the 'what is
    actually resident' view for budget checks and the OOM report."""
    import jax

    groups = {}
    try:
        arrays = jax.live_arrays()
    except Exception as e:  # noqa: BLE001
        logger.debug("live_arrays unavailable: %s", e)
        return []
    for arr in arrays:
        try:
            key = (str(arr.dtype), tuple(int(s) for s in arr.shape))
            nbytes = int(arr.size) * int(arr.dtype.itemsize)
        except Exception:  # noqa: BLE001 — deleted/donated stragglers
            continue
        row = groups.setdefault(key, {"dtype": key[0],
                                      "shape": list(key[1]),
                                      "count": 0, "total_bytes": 0})
        row["count"] += 1
        row["total_bytes"] += nbytes
    census = sorted(groups.values(), key=lambda r: -r["total_bytes"])
    top = top or _SETTINGS.census_top
    return census[:top] if top else census


def static_budget_report(state=None):
    """Combine the ledger's per-executable footprints with the train
    state's tree sizes into one 'does this fit' report. ``budget_frac``
    appears only where the backend reports ``bytes_limit``."""
    report = {"executables": dict(_LEDGER.label_memory)}
    if state:
        sizes = {key: tree_bytes(sub) for key, sub in state.items()}
        sizes = {k: v for k, v in sizes.items() if v}
        sizes["_total"] = sum(sizes.values())
        report["state_bytes"] = sizes
    stats = device_memory_stats()
    limits = [s.get("bytes_limit") for s in stats.values()
              if s.get("bytes_limit")]
    if limits:
        limit = min(limits)
        worst_exec = max(
            (m.get("total_bytes", 0)
             for m in report["executables"].values()), default=0)
        state_total = (report.get("state_bytes") or {}).get("_total", 0)
        report["bytes_limit"] = int(limit)
        report["budget_frac"] = round(
            (worst_exec + state_total) / limit, 4)
    return report


def emit_budget_report(state=None, tm=None):
    """One-shot ``mem_budget`` meta event (+ ``mem/budget_frac``
    counter where a limit exists) — trainers call this once the step
    programs have compiled."""
    tm = tm or _telemetry()
    try:
        report = static_budget_report(state)
    except Exception as e:  # noqa: BLE001
        logger.warning("static budget report failed: %s", e)
        return None
    tm.meta("mem_budget", **report)
    if report.get("budget_frac") is not None:
        tm.counter("mem/budget_frac", report["budget_frac"])
    return report


# ------------------------------------------------------------ OOM forensics


def is_resource_exhausted(exc):
    text = f"{type(exc).__name__}: {exc}"
    return ("RESOURCE_EXHAUSTED" in text
            or "Resource exhausted" in text
            or "out of memory" in text.lower())


_UNITS = {"b": 1, "kb": 1e3, "kib": 2**10, "mb": 1e6, "mib": 2**20,
          "gb": 1e9, "gib": 2**30, "tb": 1e12, "tib": 2**40,
          "bytes": 1, "byte": 1}


def parse_requested_bytes(message):
    """Best-effort parse of the allocation size an XLA OOM names
    ('Attempting to allocate 1.51GiB', '... allocating 123456 bytes')."""
    m = re.search(r"allocat\w*\s+(\d+(?:\.\d+)?)\s*"
                  r"([KMGT]i?B|bytes?|B)?", str(message), re.IGNORECASE)
    if not m:
        return None
    value = float(m.group(1))
    unit = (m.group(2) or "bytes").lower()
    return int(value * _UNITS.get(unit, 1))


def write_oom_report(error=None, context=None, path=None):
    """Dump the forensics bundle: what was resident, what each
    executable needs, and what the failed allocation asked for."""
    logdir = _SETTINGS.logdir or "."
    path = path or os.path.join(logdir, "oom_report.json")
    report = {
        "t": time.time(),
        "context": context,
        "error": str(error)[:4000] if error is not None else None,
        "requested_bytes": parse_requested_bytes(error)
        if error is not None else None,
        "device_memory": device_memory_stats(),
        "watermark_history": list(_WATERMARKS),
        "live_array_census": live_array_census(),
        "executables": dict(_LEDGER.label_memory),
        "budget": static_budget_report(),
        "recompiles": _LEDGER.recompiles,
    }
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(report, f, indent=1, default=str)
    except Exception as e:  # noqa: BLE001 — forensics must not mask the OOM
        logger.warning("oom report write failed: %s", e)
        return None
    tm = _telemetry()
    tm.meta("oom", context=context, report=path,
            requested_bytes=report["requested_bytes"])
    try:
        tm.dump_stacks(f"RESOURCE_EXHAUSTED in {context or 'unknown'} — "
                       f"forensics at {path}") if tm.enabled else None
    except Exception:  # noqa: BLE001
        pass
    logger.error("RESOURCE_EXHAUSTED in %s — forensics written to %s",
                 context, path)
    return path


@contextmanager
def oom_forensics(context=None):
    """Wrap a step/eval dispatch: a RESOURCE_EXHAUSTED escaping the
    block writes ``oom_report.json`` and re-raises."""
    try:
        yield
    except Exception as e:  # noqa: BLE001 — filtered below, always re-raised
        if _SETTINGS.oom_report and is_resource_exhausted(e):
            write_oom_report(error=e, context=context)
        raise


# -------------------------------------------------------------- installing


def _flush_hook(tm, step=None):
    _LEDGER.flush_counters(tm, step=step)
    if _SETTINGS.mem_sample:
        sample_memory(tm, step=step)


def on_telemetry_configured(cfg, tm):
    """Called by ``telemetry.configure`` with the new instance: adopt
    the config knobs, replay the ledger so pre-configure compiles reach
    the new sinks, and install the flush-cadence sampler."""
    for key, value in xla_obs_settings(cfg).items():
        setattr(_SETTINGS, key, value)
    if tm.logdir:
        _SETTINGS.logdir = tm.logdir
        with _LEDGER._lock:
            _LEDGER._written = 0  # re-write the full ledger per logdir
    if not _SETTINGS.enabled:
        return
    _LEDGER.replay_into(tm)
    _LEDGER._append_file()
    if _flush_hook not in tm.flush_hooks:
        tm.flush_hooks.append(_flush_hook)


def _reset_for_tests():
    """Test isolation: fresh ledger + default settings."""
    global _LEDGER, _SETTINGS
    _LEDGER = CompileLedger()
    _SETTINGS = _Settings()
    _WATERMARKS.clear()
