"""Closed- and open-loop serving load generation (ISSUE 20).

A p99 over requests served one at a time is a p99 under ZERO
concurrent load, which is not a tail latency at all.
This module drives a :class:`ServingEngine` the way traffic actually
arrives and measures what the aggregate counters then mean:

- **open loop** (``run_open_loop``): Poisson arrivals at a configured
  offered rate. The generator never waits for responses, so queueing
  delay under overload is *measured, not hidden*: each request's
  ``t_submit`` is its SCHEDULED arrival time, which means a request
  submitted late because the engine was busy still accounts its full
  sojourn — the standard coordinated-omission fix.
- **closed loop** (``run_closed_loop``): a fixed concurrency of
  virtual users, each submitting its next request only after the
  previous answered. Measures best-case capacity; open loop measures
  overload behavior. Both are needed for an honest curve.
- **sweep** (``run_load_sweep``): open-loop points at increasing
  offered rates, ``engine.reset_stats()`` between points so point N's
  p99 cannot inherit point N-1's tail. ``scripts/serving_loadgen.py``
  prints its offered-load-vs-latency curve from this.
- **streams** (``run_stream_burst``): interleaved StreamSession frame
  loops, exercising the per-stream lifecycle traces under load.

Everything is deterministic under a fixed seed (numpy Generator;
arrivals, bucket mix, and request seeds all derive from it).
"""

from __future__ import annotations

import time

import numpy as np

from imaginaire_tpu.serving.engine import (ServeRequest, ServingError,
                                           _percentile)


def poisson_arrivals(rate_rps, duration_s, rng):
    """Arrival offsets (seconds from start) of a Poisson process at
    ``rate_rps`` over ``duration_s`` — exponential inter-arrivals."""
    out = []
    t = 0.0
    scale = 1.0 / max(float(rate_rps), 1e-9)
    while True:
        t += float(rng.exponential(scale))
        if t >= duration_s:
            return out
        out.append(t)


def _mixed_request(lanes, hws, rng):
    """One request over the configured resolution mix (uniform over
    buckets; each request gets its own noise seed)."""
    hw = hws[int(rng.integers(len(hws)))]
    return ServeRequest(data={k: np.asarray(v) for k, v in
                              lanes[hw].items()},
                        seed=int(rng.integers(1 << 31)))


def run_open_loop(engine, rate_rps, duration_s, lanes, seed=0):
    """Offer Poisson traffic at ``rate_rps`` for ``duration_s``;
    returns the point dict for the load curve.

    ``lanes`` maps ``(H, W) -> single-lane data dict`` (the resolution
    mix). The loop submits each request when the wall clock reaches its
    scheduled arrival — pumping the engine while waiting — and stamps
    ``t_submit`` with the SCHEDULED time, so a generator that falls
    behind charges the lateness to the engine (no coordinated
    omission). Queue overflow rejections are counted as shed load (and
    charged to the error budget by ``submit``), not retried.
    """
    rng = np.random.default_rng(seed)
    hws = sorted(lanes)
    arrivals = poisson_arrivals(rate_rps, duration_s, rng)
    depth_samples = []
    submitted = rejected = served = 0
    t0 = time.perf_counter()
    for offset in arrivals:
        target = t0 + offset
        while True:
            now = time.perf_counter()
            if now >= target:
                break
            out = engine.pump(now=now)
            if out:
                served += len(out)
            else:
                time.sleep(min(target - now, 5e-4))
        req = _mixed_request(lanes, hws, rng)
        req.t_submit = target
        try:
            engine.submit(req)
            submitted += 1
        except ServingError:
            rejected += 1
        depth_samples.append(engine.queue.depth)
        served += len(engine.pump())
    served += len(engine.flush())
    wall_s = time.perf_counter() - t0
    return _point(engine, "open", rate_rps, wall_s, submitted, rejected,
                  served, depth_samples)


def run_closed_loop(engine, concurrency, total_requests, lanes, seed=0):
    """``concurrency`` virtual users, each submitting its next request
    only once the previous answered; ``total_requests`` total. Returns
    the same point dict shape as ``run_open_loop`` with
    ``offered_rps=None`` (a closed loop offers whatever the engine
    sustains)."""
    rng = np.random.default_rng(seed)
    hws = sorted(lanes)
    depth_samples = []
    submitted = served = 0
    t0 = time.perf_counter()
    while submitted < total_requests:
        wave = min(int(concurrency), total_requests - submitted)
        for _ in range(wave):
            engine.submit(_mixed_request(lanes, hws, rng))
        submitted += wave
        depth_samples.append(engine.queue.depth)
        served += len(engine.flush())
    wall_s = time.perf_counter() - t0
    return _point(engine, "closed", None, wall_s, submitted, 0, served,
                  depth_samples)


def run_stream_burst(engine, stream_ids, frames, frame_data, seed=0):
    """Interleave ``frames`` frames across ``stream_ids`` streaming
    sessions (frame t of every stream before frame t+1 of any — the
    adversarial interleaving for per-stream state isolation), then
    close every stream. Returns {stream_id: [frame arrays]}."""
    outs = {sid: [] for sid in stream_ids}
    for sid in stream_ids:
        engine.stream(sid, seed=seed)
    for _ in range(int(frames)):
        for sid in stream_ids:
            outs[sid].append(engine.stream(sid).step(dict(frame_data)))
    for sid in stream_ids:
        engine.close_stream(sid)
    return outs


def _point(engine, mode, offered_rps, wall_s, submitted, rejected,
           served, depth_samples):
    # served is counted from the pump/flush results of THIS point; the
    # percentiles read the engine's latency ring, which covers only
    # this point when the caller reset_stats() at the boundary (the
    # sweep does) and the whole ring window otherwise.
    lat = list(engine._latencies)
    point = {
        "mode": mode,
        "offered_rps": (round(float(offered_rps), 3)
                        if offered_rps is not None else None),
        "achieved_rps": round(served / wall_s, 3) if wall_s > 0
        else None,
        "requests": submitted,
        "served": served,
        "rejected": rejected,
        "wall_s": round(wall_s, 3),
        "p50_ms": _round(_percentile(lat, 0.50)),
        "p99_ms": _round(_percentile(lat, 0.99)),
        "queue_depth_max": max(depth_samples) if depth_samples else 0,
        "queue_depth_mean": (round(sum(depth_samples)
                                   / len(depth_samples), 2)
                             if depth_samples else 0.0),
    }
    if engine.budget.enabled:
        point["slo_burn_rate"] = round(engine.budget.burn_rate(), 4)
        point["slo_breaches"] = engine.budget.breaches
    return point


def _round(value, digits=2):
    return None if value is None else round(float(value), digits)


def run_load_sweep(engine, rates, duration_s, lanes, seed=0):
    """One open-loop point per offered rate, lowest first,
    ``reset_stats()`` between points (the measurement-boundary
    contract: each point's percentiles cover only its own window).
    Returns the list of point dicts — the offered-load curve."""
    points = []
    for i, rate in enumerate(rates):
        engine.reset_stats()
        points.append(run_open_loop(engine, rate, duration_s, lanes,
                                    seed=seed + i))
    return points
