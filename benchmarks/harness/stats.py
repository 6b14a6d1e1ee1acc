"""Exact order statistics over per-request samples, with failures
counted as the worst case, and the spread the bounds are set from."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least ``q``
    percent of the samples at or below it.  Exact (no interpolation, no
    sketch); ``math.inf`` samples sort last."""
    if not values:
        raise ValueError("percentile of no samples")
    if not 0 < q <= 100:
        raise ValueError(f"q must be in (0, 100]: {q}")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return float(ordered[rank - 1])


def with_failures(values, n_failed: int) -> list[float]:
    """A failed or unfinished request misses every latency limit: it
    enters the sample as +inf, so a tail that reaches the failures reads
    inf and no run can hide them."""
    return list(values) + [math.inf] * int(n_failed)


def finite_or_worst(value: float, worst: float) -> float:
    """JSON has no inf: a percentile that landed on a failure is reported
    as ``worst`` (the drain limit in the metric's unit)."""
    return float(worst) if not math.isfinite(value) else float(value)


def iqr_share(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, by ``statistics.quantiles(values, n=4)`` — the spread the
    contract sets bounds from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def without_farthest(values) -> list[float]:
    """The set less the run farthest from its median: what the check
    reads a set's spread from when it asks whether a bound is too tight
    ("a spread leaves out the run farthest from its median")."""
    mid = statistics.median(values)
    return sorted(sorted(values, key=lambda v: abs(v - mid))[:-1])


def range_share(values) -> float:
    """Largest less smallest, as a share of the median: of a set without
    its farthest run, never under the quartiles' distance, so the
    stricter reading of the check's rule (ISSUE 32 sizes cells by it)."""
    return (max(values) - min(values)) / statistics.median(values)
