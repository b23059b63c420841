"""Percentiles and the spread rule, as the benchmark uses them."""

from __future__ import annotations

import math
import statistics
from typing import Iterable, Sequence


def percentile(values: Iterable[float], q: float, missing: int = 0) -> float:
    """The ``q``-th percentile (nearest rank) of ``values`` with ``missing``
    more samples placed at +inf: a request that was rejected, or never
    finished, misses every latency limit. An empty sample has no
    percentile (nan)."""
    xs = sorted(values) + [math.inf] * missing
    if not xs:
        return math.nan
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles(values, n=4)``), the contract's spread."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
