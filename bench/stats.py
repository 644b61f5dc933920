"""Order statistics shared by the jobs and the readers."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(xs: Sequence[float], p: float) -> float:
    """Nearest-rank percentile (the p-th of n sorted values is the
    ceil(p/100 * n)-th); NaN on empty input."""
    if not xs:
        return math.nan
    s = sorted(xs)
    k = max(1, math.ceil(p / 100.0 * len(s)))
    return s[k - 1]


def peak_bytes(devices) -> int:
    """Peak device memory in use since the process started, on the fullest
    of the devices (0 where the backend keeps no such count)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
