"""End-to-end statistics over all the work of a window."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    """The q-th percentile (linear interpolation) of every value given."""
    v = np.asarray(values, np.float64)
    if v.size == 0:
        raise ValueError("no values")
    return float(np.percentile(v, q))


def rate(work: float, seconds: float) -> float:
    """Work per second over the whole window."""
    if seconds <= 0:
        raise ValueError("window has no length")
    return float(work) / seconds
