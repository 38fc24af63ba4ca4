"""Percentiles and gaps, in one place so that every run and every test computes
them alike."""

from __future__ import annotations

import math
from typing import Iterable, List, Optional


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (``q`` in 0..100): the smallest value with at
    least ``q`` % of the sample at or below it. None for an empty sample."""
    ordered = sorted(values)
    if not ordered:
        return None
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return float(ordered[rank - 1])


def gaps(times: List[float]) -> List[float]:
    return [b - a for a, b in zip(times, times[1:])]
