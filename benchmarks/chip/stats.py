"""The one percentile definition of the benchmark."""
from __future__ import annotations

import math
from typing import Optional


def percentile(values, q: float) -> Optional[float]:
    """Linear interpolation between closest ranks (numpy's default);
    None for no values.  An infinite value (a request that failed) sorts
    last, so a tail that reaches it is infinite."""
    vals = sorted(values)
    if not vals:
        return None
    rank = q / 100.0 * (len(vals) - 1)
    lo = int(math.floor(rank))
    hi = min(lo + 1, len(vals) - 1)
    if vals[hi] == vals[lo]:
        return vals[lo]
    return vals[lo] + (vals[hi] - vals[lo]) * (rank - lo)
