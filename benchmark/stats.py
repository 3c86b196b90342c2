"""Percentile arithmetic of the benchmark: a median, and the highest
percentile that still has ten samples beyond it."""

from __future__ import annotations

import math

import numpy as np

#: the percentiles the benchmark ever reports, lowest first
LADDER = (50.0, 90.0, 95.0, 99.0, 99.9)
#: samples that must lie beyond a percentile for it to be reported
BEYOND = 10


def percentile(values, q: float) -> float:
    """Nearest-rank percentile ``q`` (0 < q <= 100) of ``values``: the
    smallest sample with at least q% of the samples at or below it, so
    the number printed is one that was measured."""
    arr = np.sort(np.asarray(values, np.float64))
    if arr.size == 0:
        raise ValueError("percentile of no samples")
    return float(arr[_rank(arr.size, q) - 1])


def _rank(n: int, q: float) -> int:
    """Nearest rank of ``q`` among ``n`` (rounded first: 99.9% of
    10,000 is 9,990, not the 9,990.000000000002 floats make of it)."""
    return max(1, math.ceil(round(q * n / 100.0, 9)))


def samples_beyond(n: int, q: float) -> int:
    """Samples strictly above the nearest-rank position of ``q``."""
    return n - _rank(n, q)


def highest_supported(n: int) -> float | None:
    """The highest percentile of :data:`LADDER` that ``n`` samples
    support, or None when not even the median has ten beyond it."""
    best = None
    for q in LADDER:
        if samples_beyond(n, q) >= BEYOND:
            best = q
    return best


def label(q: float) -> str:
    """``p50``, ``p99``, ``p99.9``."""
    return f"p{q:g}"


def interquartile_mean(values) -> float:
    """Mean of the samples from the nearest-rank p25 to the p75."""
    arr = np.sort(np.asarray(values, np.float64))
    if arr.size == 0:
        raise ValueError("interquartile mean of no samples")
    return float(
        arr[_rank(arr.size, 25.0) - 1: _rank(arr.size, 75.0)].mean()
    )


def summary(values) -> dict:
    """Count, median and the highest supported percentile of a sample,
    as printed beside every timing the benchmark reports, with the mean
    and the interquartile mean beside the median (notes, no metric)."""
    n = len(values)
    out: dict = {"count": n}
    if n == 0:
        return out
    out["p50"] = percentile(values, 50.0)
    out["mean"] = float(np.mean(values))
    out["iqm"] = interquartile_mean(values)
    top = highest_supported(n)
    out["highest"] = None if top is None else label(top)
    if top is not None and top != 50.0:
        out[label(top)] = percentile(values, top)
    out["max"] = float(np.max(values))
    return out
