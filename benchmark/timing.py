"""Arithmetic on host-clock call times: rates and tails."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of all values at or below it. Every value counts."""
    if not values:
        raise ValueError("no values")
    s = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(s)))
    return s[k - 1]


def rate(units_per_call: float, calls: int, window_s: float,
         chips: int) -> float:
    """Work per second per chip over all the work and time of a window."""
    return units_per_call * calls / window_s / chips
