"""Metric arithmetic: medians and percentiles over the readings of one
run.  ``percentile`` is nearest-rank, copied from
``tools/benchmark_driver._percentile``."""

from __future__ import annotations

import math
from typing import Optional, Sequence


def median(values: Sequence[float]) -> Optional[float]:
    """Middle value, the mean of the two middle ones for an even count;
    None for no readings."""
    if not values:
        return None
    s = sorted(values)
    mid = len(s) // 2
    return s[mid] if len(s) % 2 else (s[mid - 1] + s[mid]) / 2.0


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """Nearest-rank percentile (ceil, 1-indexed)."""
    if not values:
        return None
    s = sorted(values)
    return s[min(len(s) - 1, max(0, math.ceil(p / 100.0 * len(s)) - 1))]
