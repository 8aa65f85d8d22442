"""The percentile the benchmark reports (medians are `statistics.median`)."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by the nearest-rank rule: the smallest
    value with at least q% of the samples at or below it. No interpolation,
    so a p90 of 30 blocks IS one of the 30 block times."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of no samples")
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return float(ordered[rank - 1])
