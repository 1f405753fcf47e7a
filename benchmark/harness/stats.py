"""Median and percentile arithmetic: the one place a cell's samples become a
number. No PR that claims a gain may change it."""

from __future__ import annotations

import math

# choosing-metrics guide: report the highest percentile that has at least
# ten samples beyond it. For p95 that is 200 samples.
MIN_BEYOND = 10


def median(samples: list[float]) -> float:
    if not samples:
        raise ValueError("median of no samples")
    s = sorted(samples)
    mid = len(s) // 2
    return s[mid] if len(s) % 2 else (s[mid - 1] + s[mid]) / 2.0


def min_samples(p: float) -> int:
    """Samples needed so that MIN_BEYOND of them lie beyond percentile p."""
    return math.ceil(MIN_BEYOND / (1.0 - p / 100.0) - 1e-9)


def percentile(samples: list[float], p: float) -> float:
    """Nearest-rank percentile. Refuses a tail the sample cannot carry."""
    need = min_samples(p)
    if len(samples) < need:
        raise ValueError(f"p{p:g} needs >= {need} samples so that "
                         f"{MIN_BEYOND} lie beyond it, got {len(samples)}")
    s = sorted(samples)
    return s[max(0, math.ceil(p / 100.0 * len(s)) - 1)]


def mean(samples: list[float]) -> float:
    if not samples:
        raise ValueError("mean of no samples")
    return math.fsum(samples) / len(samples)
