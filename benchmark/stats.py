"""Metric arithmetic: medians, quartiles and percentiles over the
readings of one run.  ``percentile`` is nearest-rank, copied from
``tools/benchmark_driver._percentile``; ``quartiles`` are Python's
``statistics.quantiles(values, n=4)``, as the driver takes a spread."""

from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence, Tuple


def median(values: Sequence[float]) -> Optional[float]:
    """Middle value, the mean of the two middle ones for an even count;
    None for no readings."""
    if not values:
        return None
    s = sorted(values)
    mid = len(s) // 2
    return s[mid] if len(s) % 2 else (s[mid - 1] + s[mid]) / 2.0


def quartiles(values: Sequence[float]) -> Optional[Tuple[float, float]]:
    """(first, third) quartile; None for fewer than two readings."""
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    return q1, q3


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """Nearest-rank percentile (ceil, 1-indexed)."""
    if not values:
        return None
    s = sorted(values)
    return s[min(len(s) - 1, max(0, math.ceil(p / 100.0 * len(s)) - 1))]
