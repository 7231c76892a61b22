"""Arrival schedules of open-loop traffic, from a cell's traffic file.

Every seed offers the same work: the same number of requests and the same
set of gaps between them, in another order. So two runs differ in how the
gaps fall, not in how much is asked, and a metric's spread from seed to
seed is the system's and not the generator's.

Processes:
  exponential  gaps are the N quantile mid-points of the exponential
               distribution of mean 1/rate: Poisson-shaped traffic whose
               gaps sum to the window (independent users).
  uniform      N equal gaps (a paced client).
  onoff        exponential gaps at rate/on_share inside bursts of
               `burst_requests`, silence between bursts, same mean rate.
"""

from __future__ import annotations

import math

import numpy as np


def _exponential_gaps(n, rate):
    i = np.arange(n, dtype=np.float64)
    gaps = -np.log1p(-(i + 0.5) / n) / rate
    # the mid-point rule leaves the mean a little under 1/rate: rescale,
    # so that n requests span n/rate seconds whatever n is
    return gaps * (n / rate) / gaps.sum()


def schedule(traffic, seconds, seed):
    """Offsets in seconds from the window's start, ascending, all inside
    [0, seconds). `traffic` is the parsed traffic block of a cell file."""
    rate = float(traffic["rate_rps"])
    n = int(math.floor(rate * float(seconds)))
    if n < 1:
        raise ValueError(f"rate {rate} over {seconds} s offers no request")
    process = traffic.get("process", "exponential")
    rng = np.random.default_rng([int(seed), 0xA221])
    if process == "uniform":
        gaps = np.full(n, 1.0 / rate)
    elif process == "exponential":
        gaps = rng.permutation(_exponential_gaps(n, rate))
    elif process == "onoff":
        on_share = float(traffic["on_share"])
        burst = int(traffic["burst_requests"])
        base = _exponential_gaps(n, rate / on_share)
        # the gap before each burst carries the silence, sized so that the
        # mean rate stays; which gaps lead a burst is fixed, so every seed
        # has the same set of gaps
        lead = np.zeros(n, bool)
        lead[::burst] = True
        leading = rng.permutation(base[lead] + burst * (1.0 - on_share) / rate)
        inside = rng.permutation(base[~lead])
        gaps = np.empty(n)
        gaps[lead], gaps[~lead] = leading, inside
        gaps *= (n / rate) / gaps.sum()
    else:
        raise ValueError(f"unknown arrival process {process!r}")
    # the first request is due as the window opens, the last one gap
    # before it closes
    return np.cumsum(gaps) - gaps
