"""Rate and percentile arithmetic on the host clock."""
from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile by the nearest rank (no interpolation):
    the smallest value with at least ``q`` percent of the values at or
    below it; None for no values."""
    if not values:
        return None
    v = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(v)))
    return v[k - 1]


def rate(times: Sequence[float], start: float, seconds: float) -> float:
    """Events per second of those at or before ``start + seconds``."""
    end = start + seconds
    return sum(1 for t in times if t <= end) / seconds
