"""Metric arithmetic of the benchmark: percentiles, medians, spreads."""
from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default), on a copy."""
    if len(values) == 0:
        raise ValueError("percentile of no values")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    v = sorted(float(x) for x in values)
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, by ``statistics.quantiles(values, n=4)`` — the driver's rule."""
    q1, _, q3 = statistics.quantiles([float(x) for x in values], n=4)
    return (q3 - q1) / statistics.median(values)


def tpot_ms(first_token_s: float, last_token_s: float, tokens: int) -> float:
    """Mean gap between a request's output tokens, in ms: the time from
    its first token to its last over the gaps between them."""
    if tokens < 2:
        raise ValueError("a gap between tokens needs two tokens")
    return (last_token_s - first_token_s) / (tokens - 1) * 1e3
