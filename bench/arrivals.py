"""The one traffic generator: an open-loop arrival schedule from a
traffic file and a seed.

A traffic file (``bench/traffic/<name>.json``) holds parameters only:

* ``{"kind": "poisson", "rate_hz": R}`` -- independent users: exponential
  gaps at mean rate ``R``;
* ``{"kind": "bursts", "burst_size": N, "gap_s": G}`` -- an instrument
  that dumps ``N`` frames at once every ``G`` seconds (``intra_s``
  optionally spreads a burst over that many seconds).

Every seed gets the same work in another order: the Poisson gaps are
drawn once from the file's ``gap_seed`` and the run's seed only permutes
them, so the window holds the same number of requests and the same gap
multiset for every seed. The seed also chooses which pooled input each
request carries. ``poisson_arrivals`` and ``bursty_arrivals`` are copies
of the generators in ``repro.core.scheduler`` (kept here so that the
yardstick cannot move with the program).
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple

import numpy as np


def poisson_arrivals(rate_hz: float, n: int, seed: int = 0,
                     start: float = 0.0) -> List[float]:
    """``n`` Poisson-process arrival times at ``rate_hz`` (exp gaps)."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate_hz, size=n)
    return [float(t) for t in start + np.cumsum(gaps)]


def bursty_arrivals(n: int, burst_size: int, gap_s: float,
                    intra_s: float = 0.0, seed: int = 0,
                    start: float = 0.0) -> List[float]:
    """Bursts of ``burst_size`` back-to-back arrivals every ``gap_s``;
    ``intra_s`` jitters samples inside a burst."""
    rng = np.random.default_rng(seed)
    times: List[float] = []
    t = start
    while len(times) < n:
        for _ in range(min(burst_size, n - len(times))):
            times.append(float(t + (rng.uniform(0, intra_s)
                                    if intra_s else 0.0)))
        t += gap_s
    return sorted(times)


class Schedule(NamedTuple):
    offsets: np.ndarray         # seconds after the window opens, sorted
    pool_index: np.ndarray      # which pooled input each request carries


def schedule(traffic: Dict, seed: int, seconds: float,
             pool_size: int) -> Schedule:
    """Arrivals inside ``[0, seconds)`` for one run."""
    kind = traffic["kind"]
    if kind == "poisson":
        rate = float(traffic["rate_hz"])
        # enough gaps to overrun the window, then keep those inside it;
        # the kept count depends only on the fixed gap draw
        n = int(rate * seconds * 1.5) + 64
        gaps = np.diff(poisson_arrivals(rate, n, seed=traffic.get(
            "gap_seed", 0)), prepend=0.0)
        gaps = gaps[:int(np.searchsorted(np.cumsum(gaps), seconds))]
        offsets = np.cumsum(np.random.default_rng(seed).permutation(gaps))
    elif kind == "bursts":
        size, gap = int(traffic["burst_size"]), float(traffic["gap_s"])
        n_bursts = int(np.ceil(seconds / gap - 1e-9))
        intra = float(traffic.get("intra_s", 0.0))
        offsets = np.asarray(bursty_arrivals(
            n_bursts * size, size, gap, intra_s=intra,
            seed=traffic.get("gap_seed", 0)))
        offsets = offsets[offsets < seconds]
    else:
        raise ValueError(f"unknown traffic kind {kind!r}")
    rng = np.random.default_rng([seed, 1])
    n = len(offsets)
    pool_index = (rng.permutation(max(n, pool_size))[:n] % pool_size
                  if n else np.zeros(0, np.int64))
    return Schedule(np.asarray(offsets, np.float64), pool_index)
