"""Percentile arithmetic — the benchmark's own, so that no PR moves it."""

from __future__ import annotations

import math


def percentile(values, p: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least `p` of
    the samples at or below it. Over 45 samples the 95th is the third
    largest."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    return xs[max(0, math.ceil(p * len(xs)) - 1)]


def median(values) -> float:
    xs = sorted(values)
    if not xs:
        raise ValueError("median of nothing")
    m = len(xs) // 2
    return xs[m] if len(xs) % 2 else (xs[m - 1] + xs[m]) / 2.0
