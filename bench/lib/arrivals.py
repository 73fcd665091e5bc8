"""Arrival processes and length distributions, pure functions of a numpy
``Generator``.

The Poisson and two-state Markov-modulated Poisson processes are copies of
the program's ``serve/traffic/workload.py`` generators, kept here so that
the yardstick cannot move with the program.
"""
from __future__ import annotations

import numpy as np


def poisson_gaps(n: int, rate_rps: float, rng: np.random.Generator) -> np.ndarray:
    """``n`` iid exponential inter-arrival gaps at ``rate_rps``."""
    if rate_rps <= 0:
        raise ValueError(f"rate_rps must be > 0, got {rate_rps}")
    return rng.exponential(1.0 / rate_rps, size=n)


def mmpp_times(n: int, calm_rps: float, burst_rps: float, mean_calm_s: float,
               mean_burst_s: float, rng: np.random.Generator) -> np.ndarray:
    """Bursty two-state Markov-modulated Poisson arrivals: ``n`` sorted
    instants, exponential dwell in each state."""
    for name, v in (("calm_rps", calm_rps), ("burst_rps", burst_rps),
                    ("mean_calm_s", mean_calm_s),
                    ("mean_burst_s", mean_burst_s)):
        if v <= 0:
            raise ValueError(f"{name} must be > 0, got {v}")
    out = np.empty((n,), np.float64)
    t, burst = 0.0, False
    dwell = rng.exponential(mean_calm_s)
    for i in range(n):
        while True:
            gap = rng.exponential(1.0 / (burst_rps if burst else calm_rps))
            if gap <= dwell:
                dwell -= gap
                t += gap
                break
            # the state flips before the next arrival: move to the boundary
            # and redraw in the new state (exact, by memorylessness)
            t += dwell
            burst = not burst
            dwell = rng.exponential(mean_burst_s if burst else mean_calm_s)
        out[i] = t
    return out


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` integer lengths drawn from ``spec``:

    ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``
    (log-normal with that median, clipped to [a, b]) or
    ``{"dist": "uniform", "min": a, "max": b}`` (inclusive)."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(float(spec["median"])), float(spec["sigma"]),
                          size=n)
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    if spec["dist"] == "uniform":
        return rng.integers(lo, hi + 1, size=n).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")
