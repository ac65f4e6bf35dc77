"""Arrival schedules, read from a traffic file.

- ``"arrivals": "poisson"`` with ``events_per_s``: an open loop.  The
  window holds exactly ``round(events_per_s * seconds)`` events.  Their
  gaps are one fixed sample of exponential gaps (drawn from the traffic
  file's own ``gap_seed``, scaled so the last event falls inside the
  window), put in an order drawn from the run's seed: every seed offers
  the same set of gaps and the same count, in another order.
- ``"arrivals": "backlog"`` with ``events``: every event is due at the
  window's start, as after an outage.
"""
from __future__ import annotations

import numpy as np


def window_offsets(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times of the window's events, in seconds from its start."""
    kind = traffic["arrivals"]
    if kind == "backlog":
        return np.zeros(int(traffic["events"]))
    if kind != "poisson":
        raise ValueError(f"unknown arrivals {kind!r}")
    rate = float(traffic["events_per_s"])
    n = max(1, int(round(rate * seconds)))
    gaps = np.random.default_rng(int(traffic["gap_seed"])).exponential(
        1.0, n)
    gaps *= seconds * n / (n + 1) / gaps.sum()
    order = np.random.default_rng(seed_seq(seed, 9)).permutation(n)
    return np.cumsum(gaps[order])


def seed_seq(seed: int, stream: int) -> np.random.SeedSequence:
    """An independent random stream ``stream`` of the run seed ``seed``."""
    return np.random.SeedSequence([int(seed) % 2**64, stream])
