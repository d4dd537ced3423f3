"""The generic readers of per-layer metrics. ``read(metric, ctx)``
takes one metric file and the traced run's context and returns the
number, or None where it finds nothing to read."""

import math


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile of all ``values``: the harness's rule for
    its end-to-end tails and the readers' alike."""
    ordered = sorted(values)
    rank = max(math.ceil(q * len(ordered)), 1)
    return ordered[min(rank, len(ordered)) - 1]


def reduce_values(values: list, how: str):
    import statistics

    if not values:
        return None
    if how == "median":
        return statistics.median(values)
    if how == "mean":
        return statistics.fmean(values)
    if how == "sum":
        return sum(values)
    if how == "max":
        return max(values)
    if how == "count":
        return len(values)
    if how == "weighted_median":
        # the value that holds the middle of the total: of launch
        # times, the launch in which the median device second is spent
        ordered = sorted(values)
        half, run = sum(ordered) / 2.0, 0.0
        for v in ordered:
            run += v
            if run >= half:
                return v
    if how.startswith("p") and how[1:].isdigit():
        return percentile(values, int(how[1:]) / 100.0)
    raise ValueError(f"unknown reduction {how!r}")
