# -----------------------------------------------------------------------------
# Software-pipelined rollout dispatch (ISSUE 14).
#
# The vid2vid rollout keeps the winning per-frame program structure from the
# Round-5 verdict (PROFILE.md): two programs per frame, D_t then G_t, with the
# generator's output threaded into frame t+1's conditioning ring buffers.  What
# caps host run-ahead in that loop is NOT the dispatches — jax dispatch is
# async — but the health monitor's one-behind finite poll: every
# ``diag.observe`` device_gets the *previous* program's finite/audited flags,
# so the host blocks until that program completes before it may slice and
# dispatch the next frame.  Each of those polls pays a full host<->device
# round trip, twice per frame.
#
# The scheduler here keeps the observation ORDER bit-for-bit identical but
# defers the polls by ``depth`` frames: dispatch D_t/G_t back-to-back, enqueue
# the completion record, and only drain records older than ``depth`` frames —
# by which time the polled program has long retired and the device_get returns
# at wire latency instead of compute latency.  All records drain at rollout
# end, so the monitor leaves each ``gen_update`` in exactly the state the
# sequential loop leaves it in (one pending entry, same history order).
#
# Donation safety: deferred records hold program OUTPUTS (loss/health trees)
# and the non-donated data dict — never the donated state buffer, which is
# rebound synchronously at every dispatch return.  The FrameDAG below encodes
# that constraint explicitly (D_t may not issue until G_{t-1} returned the
# replacement state handle) and raises on any out-of-order dispatch, which is
# what the donation-safety units in tests/test_pipeline.py exercise.
#
# Sharding: the pipeline never re-places anything mid-rollout.  Loop-invariant
# per-frame operands are hoisted ONCE per rollout, *before* frame 0 dispatches
# (see ``hoist_invariants``), so every per-frame program compiles against one
# fixed input sharding and the PR-6 partition plan never settles mid-pipeline.
# -----------------------------------------------------------------------------
from __future__ import annotations

import time
from collections import deque

from imaginaire_tpu.config import cfg_get

#: dispatch stages of one rollout frame, in issue order.  ``data`` is the
#: host-side slice/ring-buffer assembly, ``D``/``G`` the two compiled
#: programs, ``grads`` the gradient all-reduce (fused into the tail of each
#: program under the partition plan — modelled as a separate node so the DAG
#: states the full dependency story the HLO audit verifies).
STAGES = ("data", "D", "G", "grads")

_DEPS = {
    # data_t needs frame t-1's generator output (conditioning ring buffers).
    "data": (("G", -1),),
    # D_t consumes the donated state handle G_{t-1} returned, plus data_t.
    "D": (("data", 0), ("G", -1)),
    # G_t consumes the handle D_t returned.
    "G": (("D", 0),),
    # the gradient all-reduce rides the program that produced the grads.
    "grads": (("G", 0),),
}


class PipelineOrderError(RuntimeError):
    """A dispatch was issued before its DAG dependencies completed issue."""


class FrameDAG:
    """Explicit per-frame dependency DAG: data_t -> D_t -> G_t -> grads.

    The trainer marks each stage as it issues; ``mark`` raises if any
    dependency (including the cross-frame state-donation edge G_{t-1} -> D_t)
    has not been marked first.  This is a cheap set-membership assertion, not
    a scheduler — the schedule itself is the program order of the rollout
    loop, which the DAG proves legal at runtime.
    """

    def __init__(self):
        self._done = set()
        self._frames = 0

    def deps(self, stage, t):
        if stage not in _DEPS:
            raise KeyError(f"unknown pipeline stage {stage!r}")
        out = []
        for dep_stage, rel in _DEPS[stage]:
            dep_t = t + rel
            if dep_t >= 0:
                out.append((dep_stage, dep_t))
        return tuple(out)

    def mark(self, stage, t):
        missing = [d for d in self.deps(stage, t) if d not in self._done]
        if missing:
            raise PipelineOrderError(
                f"stage {stage!r} of frame {t} dispatched before "
                f"{missing} — donated state handle not yet rebound")
        self._done.add((stage, t))
        self._frames = max(self._frames, t + 1)

    def done(self, stage, t):
        return (stage, t) in self._done

    @property
    def frames(self):
        return self._frames

    def satisfy(self, t):
        """Mark every stage of frame ``t`` satisfied without a dispatch —
        a ``_frame_override`` supplied the frame's output outside the DAG
        (wc-vid2vid's frozen single-image takeover), so downstream frames'
        ring-buffer dependency on G_t is met by the override."""
        for stage in STAGES:
            self._done.add((stage, t))
        self._frames = max(self._frames, t + 1)

    def order(self):
        """Issue-legal topological order over all marked frames."""
        out = []
        for t in range(self._frames):
            for stage in STAGES:
                if (stage, t) in self._done:
                    out.append((stage, t))
        return out


class RolloutPipeline:
    """Depth-``k`` deferred-completion scheduler for the per-frame rollout.

    Also the instrument: it meters the per-frame *dispatch gap* (host time
    between the end of frame t's issue window and the start of frame t+1's)
    and the *overlap ratio* (fraction of the rollout wall spent issuing work
    rather than idling between issue windows).  The sequential loop runs the
    same meter at ``depth=0`` — completion records drain immediately, which
    reproduces the old observe-after-dispatch behaviour exactly — so the
    before/after table in PROFILE.md is one knob, same instrument.
    """

    def __init__(self, depth=2, overlap_collectives=True):
        self.depth = max(int(depth), 0)
        self.overlap_collectives = bool(overlap_collectives)
        self.dag = FrameDAG()
        self._pending = deque()
        self._gaps_s = []
        self._issue_s = []
        self._frame_t0 = None
        self._last_issue_end = None
        self._rollout_t0 = None
        self._gap_span = None

    # ------------------------------------------------------------ schedule

    def begin(self):
        """Reset per-rollout state.  Pending records never survive a rollout
        (``finish`` drains), so a fresh ``begin`` only resets the meters."""
        if self._pending:  # pragma: no cover - defensive
            self.drain()
        self.dag = FrameDAG()
        self._gaps_s = []
        self._issue_s = []
        self._last_issue_end = None
        self._rollout_t0 = time.perf_counter()
        return self

    def frame(self, t, tm=None, step=None):
        """Context manager bounding frame ``t``'s issue window."""
        return _FrameWindow(self, t, tm, step)

    def mark(self, stage, t):
        self.dag.mark(stage, t)

    def override(self, t):
        self.dag.satisfy(t)

    def defer(self, record):
        """Queue a completion callback; drain anything older than ``depth``
        frames.  At ``depth=0`` this degenerates to calling it inline."""
        self._pending.append(record)
        while len(self._pending) > self.depth:
            self._pending.popleft()()

    def drain(self):
        while self._pending:
            self._pending.popleft()()

    def finish(self, tm=None, step=None):
        """Drain all deferred records and emit the rollout's meters."""
        self._close_gap_span()
        self.drain()
        summary = self.summary()
        if tm is not None and getattr(tm, "enabled", False):
            tm.counter("pipeline/depth", self.depth, step=step)
            tm.counter("pipeline/dispatch_gap_ms",
                       summary["dispatch_gap_ms"], step=step)
            tm.counter("pipeline/overlap_ratio",
                       summary["overlap_ratio"], step=step)
        return summary

    # -------------------------------------------------------------- meters

    def summary(self):
        gaps = sum(self._gaps_s)
        issue = sum(self._issue_s)
        window = gaps + issue
        # the sequential path opens two issue windows per frame (one per
        # program, with the monitor's polls between them), so frame count
        # comes from the DAG, not the window count
        frames = self.dag.frames or len(self._issue_s)
        return {
            "depth": self.depth,
            "frames": frames,
            "dispatch_gap_ms": round(gaps / max(frames, 1) * 1e3, 4),
            "overlap_ratio": round(1.0 - gaps / window, 4) if window else 1.0,
            "issue_ms": round(issue * 1e3, 4),
        }

    def _open_gap_span(self, tm, step):
        if tm is not None and getattr(tm, "enabled", False):
            span = tm.span("pipeline_gap", step=step)
            span.__enter__()
            self._gap_span = span

    def _close_gap_span(self):
        span, self._gap_span = self._gap_span, None
        if span is not None:
            span.__exit__(None, None, None)


class _FrameWindow:
    """Bounds one frame's issue window; everything outside consecutive
    windows (deferred drains, ring-buffer maintenance, the monitor's polls
    on the sequential path) is charged to the dispatch gap."""

    __slots__ = ("_pipe", "_t", "_tm", "_step", "_span")

    def __init__(self, pipe, t, tm, step):
        self._pipe = pipe
        self._t = t
        self._tm = tm
        self._step = step
        self._span = None

    def __enter__(self):
        pipe = self._pipe
        now = time.perf_counter()
        if pipe._last_issue_end is not None:
            pipe._gaps_s.append(now - pipe._last_issue_end)
        pipe._close_gap_span()
        pipe._frame_t0 = now
        if self._tm is not None and getattr(self._tm, "enabled", False):
            self._span = self._tm.span("frame_dispatch", step=self._step)
            self._span.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        pipe = self._pipe
        if self._span is not None:
            self._span.__exit__(exc_type, exc, tb)
        pipe._last_issue_end = time.perf_counter()
        pipe._issue_s.append(pipe._last_issue_end - pipe._frame_t0)
        if exc_type is None:
            pipe._open_gap_span(self._tm, self._step)
        return False


# ------------------------------------------------------------------- config


def pipeline_settings(cfg):
    """Resolve the ``cfg.trainer.pipeline`` knob group.

    ``enabled`` — software-pipeline the rollout dispatch (default on: the
    pipelined path is bit-identical to the sequential loop, see
    tests/test_pipeline.py).  ``depth`` — how many frames of completion
    records may be outstanding before the oldest is polled.  ``depth=0``
    reproduces the sequential observe-after-dispatch behaviour exactly.
    ``overlap_collectives`` — hoist loop-invariant per-frame operands out of
    the per-frame programs (one gather per rollout instead of one per frame)
    so the remaining per-frame collectives overlap the next frame's issue.
    """
    trainer = cfg_get(cfg, "trainer", None)
    group = cfg_get(trainer, "pipeline", None) if trainer is not None else None
    return {
        "enabled": bool(cfg_get(group, "enabled", True)),
        "depth": max(int(cfg_get(group, "depth", 2)), 0),
        "overlap_collectives": bool(
            cfg_get(group, "overlap_collectives", True)),
    }


# -------------------------------------------------------- invariant hoisting


def hoist_invariants(data, constants, mesh=None):
    """Gather loop-invariant per-frame operands once per rollout.

    ``constants`` is the trainer's declared loop-invariant key set (the same
    contract ``_rollout_scan_constants`` already states for the scan tail:
    e.g. fs-vid2vid's reference window).  Each such operand is re-placed
    fully replicated HERE, before frame 0 dispatches, so every per-frame
    program receives an already-gathered input: the partitioner stops
    inserting its fixed per-frame all-gather for it (the ~384 KiB/frame line
    in the PR-12 collective table) and the one real gather happens once,
    overlapping frame 0's issue window.  Input shardings are therefore fixed
    from the first compile — no recompile, nothing settles mid-pipeline.

    Returns ``(data, hoisted_bytes)`` — ``data`` updated in place with the
    replicated operands, and the total bytes gathered once (0 when there was
    nothing to hoist or no non-trivial mesh is installed).
    """
    if not constants:
        return data, 0
    if mesh is None:
        from imaginaire_tpu.parallel.mesh import peek_mesh

        mesh = peek_mesh()
    if mesh is None or mesh.size <= 1:
        return data, 0
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    replicated = NamedSharding(mesh, PartitionSpec())
    hoisted_bytes = 0
    for key, value in constants.items():
        if value is None:
            continue
        sharding = getattr(value, "sharding", None)
        if sharding is not None and sharding.is_equivalent_to(
                replicated, getattr(value, "ndim", 0)):
            continue  # already replicated — nothing to gather
        gathered = jax.device_put(value, replicated)
        hoisted_bytes += getattr(gathered, "nbytes", 0)
        data[key] = gathered
    return data, hoisted_bytes
