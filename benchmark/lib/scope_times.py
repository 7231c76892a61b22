"""Device time under the program's `lm/...` named scopes, from a trace.

The v5e trace's `XLA Ops` events are named by their HLO instruction
(`%fusion.35 = ...`) and, where the profiler kept it, carry the
instruction's `op_name` among their stats; otherwise the name is looked
up in the step program's optimized HLO text (`metadata={op_name="..."}`),
which the compile ledger's executable gives. An instruction belongs to the
last `lm/...` scope on its name stack, whichever pass put it there
(forward, recompute, backward). A scope's time is the union of its events'
intervals (a `while` and the operations of its body overlap), summed over
the devices and divided by the step program's executions in the trace.

`benchmark/lib/trace_reduce.py` keeps ten operations and the modules; this
is the reduction by scope beside it.
"""

from __future__ import annotations

import re

from benchmark.lib import trace_reduce

SCOPE = re.compile(
    r"lm/(?:embed|head_loss|mamba2/\w+|attn/\w+|moe/\w+)")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# the v5e compiler turns `lax.ragged_dot` into kernels of its own
# (`%ragged-dot-none.31`, `%ragged-dot-metadata.11`) whose `op_name` is
# that name and no more; the program's only ragged products are the held
# experts'
BY_PREFIX = {"ragged-dot": "lm/moe/experts"}


def scope_of(op_name):
    """The last `lm/...` scope of an `op_name`, or None."""
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else None


def instruction_scopes(hlo_text):
    """{instruction name: scope} of an optimized HLO module's text, for
    the instructions whose `op_name` lies under a scope."""
    out = {}
    for line in (hlo_text or "").splitlines():
        head = _INSTRUCTION.match(line)
        meta = _OP_NAME.search(line)
        if not head or not meta:
            continue
        scope = scope_of(meta.group(1))
        if scope:
            out[head.group(1)] = scope
    return out


def _event_scope(ev, by_instruction):
    try:
        for key, value in ev.stats:
            if isinstance(value, str) and "lm/" in value:
                scope = scope_of(value)
                if scope:
                    return scope
    except (AttributeError, TypeError, ValueError):
        pass
    head = _INSTRUCTION.match(ev.name)
    name = head.group(1) if head else ev.name.lstrip("%").split(" ", 1)[0]
    scope = by_instruction.get(name)
    if scope is None:
        scope = next((s for prefix, s in BY_PREFIX.items()
                      if name.startswith(prefix)), None)
    return scope


def reduce(profile, hlo_text=None, module="gen_step"):
    """{"steps", "seconds": {scope: device seconds a step}, "matched_s",
    "busy_s"} or None without device operations or executions of
    `module`. `matched_s` over `busy_s` says how much of the device's busy
    time the scopes account for."""
    by_instruction = instruction_scopes(hlo_text)
    intervals, busy, steps = {}, [], 0
    for plane in trace_reduce.device_planes(profile):
        for line in plane.lines:
            if line.name in trace_reduce.MODULE_LINES:
                steps += sum(1 for ev in line.events if module in ev.name)
            if line.name not in trace_reduce.OP_LINES:
                continue
            for ev in line.events:
                span = (ev.start_ns, ev.start_ns + ev.duration_ns)
                busy.append(span)
                scope = _event_scope(ev, by_instruction)
                if scope:
                    intervals.setdefault(scope, []).append(span)
    if not busy or not steps:
        return None
    seconds = {scope: trace_reduce.union_seconds(spans) / steps
               for scope, spans in intervals.items()}
    everything = [s for spans in intervals.values() for s in spans]
    return {"steps": steps, "seconds": seconds,
            "matched_s": trace_reduce.union_seconds(everything),
            "busy_s": trace_reduce.union_seconds(busy)}


def under(observed, prefix):
    """Device milliseconds a step under the scopes that start with
    `prefix`, from `observed["scopes"]`; None where there is none."""
    seconds = (observed.get("scopes") or {}).get("seconds") or {}
    found = [v for k, v in seconds.items() if k.startswith(prefix)]
    return 1e3 * sum(found) if found else None
