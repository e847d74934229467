"""Seeded open-loop arrival schedules, in milliseconds of wall time.

The arrivals are a Poisson process at the cell's rate, as the program's own
generator draws it (``repro.serving.online.traffic``: exponential
inter-arrivals, cumulated), kept here so that the yardstick cannot move with
the program.  Every run of a cell offers the same work: the gaps are drawn
once from the configuration's collection seed, scaled to fill the window
with exactly ``rate * seconds`` queries, and the run's seed only shuffles
their order.
"""

from __future__ import annotations

import numpy as np

import gen


def poisson_due_ms(qps: float, seconds: float, collection_seed: int,
                   seed: int) -> np.ndarray:
    """Due times (ms from the window's start) of ``round(qps * seconds)``
    arrivals whose exponential gaps fill ``seconds``, in a seeded order."""
    n = int(round(qps * seconds))
    gaps = gen.rng_for(collection_seed, 2).exponential(1.0, size=n + 1)
    gaps *= seconds * 1e3 / gaps.sum()
    order = gen.rng_for(seed, 2).permutation(n + 1)
    return np.cumsum(gaps[order])[:n]
