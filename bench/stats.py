"""Order statistics the metric readers share."""

from __future__ import annotations

import numpy as np

__all__ = ["percentile", "weighted_percentile"]


def percentile(values, q: float):
    """The ``q``-th percentile (linear interpolation), or None if empty."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, float), q))


def weighted_percentile(pairs, q: float):
    """Percentile of values each counted ``k`` times, from ``(value, k)``."""
    if not pairs:
        return None
    v = np.array([p[0] for p in pairs], float)
    k = np.array([p[1] for p in pairs], int)
    return percentile(np.repeat(v, k), q)
