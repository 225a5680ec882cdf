"""Percentiles as the benchmark reports them."""

from __future__ import annotations

import math


def nearest_rank(values, q: float) -> float | None:
    """The ``q``-th percentile by nearest rank: the smallest value with
    at least ``q`` percent of the values at or below it.  Takes +inf
    (a request that never finished) like any other value."""
    xs = sorted(values)
    if not xs:
        return None
    return xs[max(0, math.ceil(q / 100.0 * len(xs)) - 1)]
