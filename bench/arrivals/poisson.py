"""``poisson``: ``round(rate_per_s · seconds)`` arrivals whose gaps are the
exponential distribution's quantiles, shuffled by the seed and scaled so
the last arrives as the window closes.  Every seed sends the same gaps in
another order, so seeds differ in order, not in load."""

from __future__ import annotations

import numpy as np


def offsets(mix: dict, seconds: float, rng: np.random.Generator
            ) -> np.ndarray:
    n = max(1, round(mix["rate_per_s"] * seconds))
    gaps = rng.permutation(-np.log1p(-(np.arange(n) + 0.5) / n))
    return np.cumsum(gaps) * (seconds / gaps.sum())
