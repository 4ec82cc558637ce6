"""Open-loop arrival schedules.

The Poisson process of ``repro.serving.loadgen.poisson_arrivals``, drawn
conditioned on its count: ``round(rate · duration)`` arrivals at the sorted
times of that many uniform draws, which is the Poisson process given that
count. Every seed then offers the same number of requests, in another
order and at other times, so the rate a run offers does not move with the
seed.
"""
from __future__ import annotations

import numpy as np


def poisson_fixed_count(rate: float, duration_s: float,
                        rng: np.random.Generator) -> np.ndarray:
    """Ascending arrival offsets in [0, duration_s): ``round(rate ·
    duration_s)`` of them, at least one."""
    n = max(1, int(round(rate * duration_s)))
    return np.sort(rng.uniform(0.0, duration_s, n))
