"""Order statistics used by the metric readers."""

from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile: the smallest value with at least ``q`` percent
    of the sample at or below it.  None for an empty sample."""
    xs = sorted(values)
    if not xs:
        return None
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[rank - 1])


def mean(values: Sequence[float]) -> Optional[float]:
    xs = list(values)
    return float(sum(xs) / len(xs)) if xs else None
