"""Percentiles over whole windows, and run-to-run spreads."""

from __future__ import annotations

import statistics

import numpy as np


def percentile(xs, q: float) -> float | None:
    """The q-th percentile of every sample (linear interpolation)."""
    if len(xs) == 0:
        return None
    return float(np.percentile(np.asarray(xs, np.float64), q))


def spread(values) -> float:
    """(Q3 - Q1) / median, quartiles as `statistics.quantiles(n=4)`."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
