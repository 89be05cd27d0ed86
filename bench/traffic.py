"""Arrival traffic for a cell: one general generator driven by a traffic file.

A traffic file (``bench/traffic/<name>.json``) holds parameters only:

- ``period_s``: each worker's mean request period, so the fleet's mean
  arrival rate is ``workers / period_s`` requests per simulated second;
- ``phases`` (optional): ``[[seconds, factor], ...]``, cycled over
  simulated time, multiplying the rate (on/off bursts, steps); a rate
  that never changes has no phases.

The counts are Poisson per tick, and each request's workload is drawn
from the configuration's mix. The arithmetic is that of the program's
``RequestStream`` (a Poisson count per tick, then one workload choice per
request, then per-tick counts by workload), copied here so that the
yardstick cannot move with the program.
"""
from __future__ import annotations

import time

import numpy as np


def rate_per_tick(traffic: dict, workers: int, n_ticks: int,
                  dt: float) -> np.ndarray:
    """(n_ticks,) expected arrivals per tick."""
    base = workers / float(traffic["period_s"]) * dt
    lam = np.full(n_ticks, base)
    phases = traffic.get("phases") or []
    if phases:
        edges = np.cumsum([float(s) for s, _ in phases])
        factors = np.array([float(f) for _, f in phases])
        t = (np.arange(n_ticks) * dt) % edges[-1]
        lam = lam * factors[np.searchsorted(edges, t, side="right")]
    return lam


def arrival_rows(traffic: dict, workers: int, mix, n_ticks: int, dt: float,
                 seed: int) -> np.ndarray:
    """(n_ticks, W) int64 per-tick arrival counts by workload, from
    ``seed`` alone: the same seed gives the same rows."""
    rng = np.random.default_rng(seed)
    counts = rng.poisson(rate_per_tick(traffic, workers, n_ticks, dt))
    mix = np.asarray(mix, dtype=np.float64)
    wl = rng.choice(mix.shape[0], size=int(counts.sum()), p=mix / mix.sum())
    W = mix.shape[0]
    cell = np.repeat(np.arange(n_ticks) * W, counts) + wl
    return np.bincount(cell, minlength=n_ticks * W).reshape(n_ticks, W)


class ArrivalSource:
    """The serve loop's arrival client: ``take(k)`` hands out the next
    ``k`` rows of a matrix drawn in full before the run, so the generator
    can never run late, and stamps the host clock at every call."""

    def __init__(self, rows: np.ndarray):
        self.rows = rows
        self.pos = 0
        self.stamps: list[float] = []

    def take(self, k: int) -> np.ndarray:
        self.stamps.append(time.perf_counter())
        if self.pos + k > self.rows.shape[0]:
            raise ValueError(f"arrival rows exhausted at row {self.pos} "
                             f"(+{k} of {self.rows.shape[0]})")
        out = self.rows[self.pos:self.pos + k]
        self.pos += k
        return out
