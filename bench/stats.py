"""Percentiles for the benchmark's metrics.

``percentile`` is nearest rank, copied from the repository's
``serving/metrics.py``: every reported value is an observed sample."""

from __future__ import annotations

import math


def percentile(xs, p: float) -> float:
    if not 0 < p <= 100:
        raise ValueError(f"percentile must be in (0, 100], got {p}")
    xs = sorted(xs)
    if not xs:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(p * len(xs) / 100.0 - 1e-9))
    return xs[min(rank, len(xs)) - 1]

