"""The arithmetic behind the reported numbers, kept apart so it can be tested."""

from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between order
    statistics (numpy's default), of a non-empty sample."""
    if not values:
        raise ValueError("percentile of an empty sample")
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return statistics.median(values)


def rate(work: float, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError(f"a rate over {seconds} seconds")
    return work / seconds


def tpot_s(first_token: float, finish: float, tokens: int) -> Optional[float]:
    """Mean gap between a request's output tokens after the first."""
    if tokens <= 1:
        return None
    return (finish - first_token) / (tokens - 1)


def describe(values: Sequence[float]) -> str:
    return f"median {median(values):.4f} over {len(values)} samples"
