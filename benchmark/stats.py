"""The spread of a set of runs, as the benchmark's contract defines it."""

from __future__ import annotations

import statistics


def median(values) -> float:
    return float(statistics.median(float(v) for v in values))


def iqr_share(values) -> float:
    """Distance between the first and third quartile
    (``statistics.quantiles(n=4)``) as a share of the median."""
    xs = [float(v) for v in values]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    return (q3 - q1) / statistics.median(xs)
