"""Quantile arithmetic of the benchmark (a copy of the serving metrics'
linear-interpolated quantile, so that the yardstick stays here)."""
from __future__ import annotations

import math

import numpy as np


def quantile(samples, q: float) -> float:
    """Linear-interpolated quantile of raw samples. Samples may hold
    ``inf`` (a failed request): a quantile that reaches into them is
    ``inf``."""
    arr = np.sort(np.asarray(samples, dtype=np.float64).reshape(-1))
    if arr.size == 0:
        return float("nan")
    pos = q * (arr.size - 1)
    lo, hi = math.floor(pos), math.ceil(pos)
    if math.isinf(arr[hi]) or math.isinf(arr[lo]):
        return float("inf")
    return float(arr[lo] + (arr[hi] - arr[lo]) * (pos - lo))
