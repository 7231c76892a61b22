"""Device time of the step programs by named scope and pass, from a trace.

`scope_times.py` reads one program (`gen_step`) under the token model's
`lm/...` scopes and needs the step's optimized HLO text from the trainer.
This reader reads both step programs under every scope the program sets
(`lm/...`, `gan/...`, `step/...`), after the trainer is gone: the
program's compile ledger keeps {instruction: op_name} a labelled program
(`xla_obs.ledger().label_op_names`).

- Each `XLA Ops` event is laid into the execution of `gen_step` or
  `dis_step` on the `XLA Modules` line that contains it and named by THAT
  program's map: instruction names repeat across programs.
- An instruction belongs to the last scope on its name stack, and to a
  pass by that stack: `rematted_computation` is a block's recompute,
  else `transpose(` is backward, else forward. The compiler's own
  `ragged-dot` kernels carry no stack: `scope_times.BY_PREFIX` names
  their scope, and their pass is the enclosing event's or, at the top
  of a branch, that of the last event before them that has one.
- Events nest (a `while` holds its body's events inside its interval):
  every instant goes to the innermost event that has a scope, so a
  program's scopes and its unscoped part add up to its busy time.
- Whole executions only: a step that the trace's start cut gives neither
  its time nor itself to the count.

A program whose ledger keeps no names (the parent of the PR that added
them) gives `None`, and the metrics are left out.
"""

from __future__ import annotations

import functools
import os
import re

from benchmark.lib import harness, scope_times, trace_reduce

PROGRAMS = ("gen_step", "dis_step")
SCOPE = re.compile(
    r"lm/(?:embed|head_loss|final_norm"
    r"|(?:mamba2|attn|moe|mlp|mtp|block)/\w+)"
    r"|gan/(?:[GD](?!\w)|loss/\w+)"
    r"|step/\w+")
PASSES = ("forward", "recompute", "backward")
# a module event that begins within this of the plane's first event began
# before the trace did
_CUT_NS = 1000


def scope_of(op_name):
    """The last scope on an `op_name`'s name stack, or None."""
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else None


def pass_of(op_name):
    """The pass an `op_name`'s name stack says, or None where it has no
    stack (one bare name)."""
    if "/" not in (op_name or ""):
        return None
    if "rematted_computation" in op_name:
        return "recompute"
    return "backward" if "transpose(" in op_name else "forward"


def label_of(event_name, op_names):
    """(scope or None, pass or None) of a trace event of a program whose
    {instruction: op_name} is `op_names`."""
    head = scope_times._INSTRUCTION.match(event_name)
    name = (head.group(1) if head
            else event_name.lstrip("%").split(" ", 1)[0])
    op_name = op_names.get(name)
    scope = scope_of(op_name)
    if scope is None:
        scope = next((s for prefix, s in scope_times.BY_PREFIX.items()
                      if name.startswith(prefix)), None)
    return scope, pass_of(op_name)


def self_times(events):
    """{(scope, pass): ns} and the busy ns of one execution's events,
    (start_ns, end_ns, scope or None, pass or None) each. An instant
    covered by several events goes to the one that started last; an
    event without a scope takes the scope and pass of the event it
    started inside; one with a scope and no pass (a kernel without a
    name stack) takes that event's pass, else the pass of the last event
    before it that states one. The values add up to the union of the
    intervals, `busy`; the time under no scope stands under `None`."""
    out, busy = {}, 0
    stack = []          # (end, label): open events, the innermost last
    cursor = None
    latest = "forward"  # the pass of the last event that stated one

    def advance(until):
        nonlocal cursor, busy
        while stack:
            end, label = stack[-1]
            if end <= cursor:
                stack.pop()
                continue
            stop = min(end, until)
            if stop > cursor:
                out[label] = out.get(label, 0) + stop - cursor
                busy += stop - cursor
                cursor = stop
            if end > until:
                return
        cursor = max(cursor, until)

    for start, end, scope, which in sorted(
            events, key=lambda ev: (ev[0], -ev[1])):
        if cursor is None:
            cursor = start
        advance(start)
        outer = stack[-1][1] if stack else None
        latest = which or latest
        if scope is None:
            label = outer
        else:
            label = (scope, which or (outer[1] if outer else latest))
        stack.append((end, label))
    if stack:
        advance(max(end for end, _ in stack))
    return out, busy


def _executions(modules, op_names, first_ns):
    """[(start, end, label)] of the whole executions of the programs
    that have a map, in order."""
    out = []
    for start, end, name in sorted(modules):
        label = next((p for p in PROGRAMS if p in name), None)
        if label in op_names and start > first_ns + _CUT_NS:
            out.append((start, end, label))
    return out


def reduce(profile, op_names):
    """{"executions": {program: n}, "seconds": {program: {scope: {pass:
    device seconds an execution}}}, "busy_s", "matched_s": {program:
    seconds an execution}} of the whole executions of the programs in
    `op_names` ({program: {instruction: op_name}}); None without a map or
    without a whole execution of a program that has one."""
    if not op_names:
        return None
    totals = {}
    for plane in trace_reduce.device_planes(profile):
        ops = sorted(trace_reduce._events(plane, trace_reduce.OP_LINES))
        modules = trace_reduce._events(plane, trace_reduce.MODULE_LINES)
        if not ops:
            continue
        first_ns = min(ops[0][0], min((m[0] for m in modules),
                                      default=ops[0][0]))
        at = 0
        for start, end, program in _executions(modules, op_names, first_ns):
            while at < len(ops) and ops[at][0] < start:
                at += 1
            mine = []
            while at < len(ops) and ops[at][0] < end:
                s, e, name = ops[at]
                mine.append((s, e) + label_of(name, op_names[program]))
                at += 1
            times, busy = self_times(mine)
            total = totals.setdefault(
                program, {"executions": 0, "busy": 0, "ns": {}})
            total["executions"] += 1
            total["busy"] += busy
            for label, ns in times.items():
                total["ns"][label] = total["ns"].get(label, 0) + ns
    if not totals:
        return None
    out = {"executions": {}, "seconds": {}, "busy_s": {}, "matched_s": {}}
    for program, total in totals.items():
        n = total["executions"] * 1e9
        seconds = {}
        for label, ns in total["ns"].items():
            if label is not None:
                seconds.setdefault(label[0], {})[label[1]] = ns / n
        out["executions"][program] = total["executions"]
        out["seconds"][program] = seconds
        out["busy_s"][program] = total["busy"] / n
        out["matched_s"][program] = (
            total["busy"] - total["ns"].get(None, 0)) / n
    return out


# ------------------------------------------------- the run's own trace


def program_op_names():
    """The compile ledger's {program: {instruction: op_name}}, or None
    from a program that keeps none."""
    from imaginaire_tpu.telemetry import xla_obs

    return getattr(xla_obs.ledger(), "label_op_names", None) or None


@functools.lru_cache(maxsize=1)
def _reduced_file(path, mtime):
    op_names = program_op_names()
    if not op_names:
        return None
    reduced = reduce(trace_reduce.load(path), op_names)
    if reduced is not None:
        # beside the trace, for tools/describe_step_scopes.py
        from imaginaire_tpu.telemetry import xla_obs

        xla_obs.write_op_names(os.path.join(
            harness.CACHE_DIR, "trace", "scopes.json"))
    return reduced


def traced(observed):
    """`reduce` of the run's own trace (the newest under the harness's
    trace directory) by the program's own ledger, parsed once for all its
    readers; None for a run that was not traced."""
    if not observed.get("trace"):
        return None
    try:
        path = trace_reduce.newest_xplane(
            os.path.join(harness.CACHE_DIR, "trace"))
    except FileNotFoundError:
        return None
    return _reduced_file(path, os.path.getmtime(path))


def under(observed, prefixes):
    """Device milliseconds an iteration (one execution of each step
    program) under the scopes that start with one of `prefixes`, the
    three passes together; None where no program has such a scope."""
    reduced = traced(observed)
    if not reduced:
        return None
    found = [seconds
             for scopes in reduced["seconds"].values()
             for scope, passes in scopes.items()
             if scope.startswith(tuple(prefixes))
             for seconds in passes.values()]
    return 1e3 * sum(found) if found else None


def unscoped_ms(observed):
    """Device milliseconds an iteration of the step programs' whole
    executions that lie under no scope of `SCOPE`."""
    reduced = traced(observed)
    if not reduced:
        return None
    return 1e3 * sum(reduced["busy_s"][p] - reduced["matched_s"][p]
                     for p in reduced["busy_s"])


def table(reduced):
    """Rows (program, scope, forward ms, recompute ms, backward ms, total
    ms) an execution, the largest first within a program, the unscoped
    part last."""
    rows = []
    for program in sorted(reduced["seconds"]):
        scopes = reduced["seconds"][program]
        for scope in sorted(scopes, key=lambda s: -sum(scopes[s].values())):
            ms = [1e3 * scopes[scope].get(p, 0.0) for p in PASSES]
            rows.append((program, scope, *ms, sum(ms)))
        rest = 1e3 * (reduced["busy_s"][program]
                      - reduced["matched_s"][program])
        rows.append((program, "(no scope)", rest, 0.0, 0.0, rest))
    return rows
