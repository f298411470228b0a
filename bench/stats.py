"""Order statistics shared by the harness and the metric readers."""
from __future__ import annotations

import math
from typing import Optional, Sequence


def nearest_rank(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile: the ⌈q·n⌉-th smallest value (None if
    empty)."""
    if not values:
        return None
    v = sorted(values)
    return float(v[min(max(math.ceil(q * len(v)) - 1, 0), len(v) - 1)])


def median(values: Sequence[float]) -> Optional[float]:
    if not values:
        return None
    v = sorted(values)
    n = len(v)
    return float(v[n // 2] if n % 2 else 0.5 * (v[n // 2 - 1] + v[n // 2]))
