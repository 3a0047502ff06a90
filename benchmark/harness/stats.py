"""Percentiles and spreads, in one place so every cell counts alike."""

from __future__ import annotations

import math


def percentile(sorted_values, q: float) -> float:
    """Nearest-rank percentile of an ascending list: the smallest value
    with at least q% of the samples at or below it."""
    if not len(sorted_values):
        raise ValueError("percentile of no samples")
    rank = max(1, math.ceil(q / 100.0 * len(sorted_values)))
    return float(sorted_values[rank - 1])


def latency_percentiles(latencies_ms, failed: int, failed_as_ms: float,
                        qs=(50, 99)) -> dict:
    """Percentiles over answered calls (ascending) AND failed ones: a failed or
    refused call counts as slower than any answer, at `failed_as_ms`
    (the call's timeout) or the slowest answer, whichever is larger."""
    vals = list(latencies_ms)  # ascending
    vals += [max(failed_as_ms, vals[-1] if vals else 0.0)] * failed
    return {q: percentile(vals, q) for q in qs}
