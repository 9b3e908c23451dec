"""Order statistics of the harness."""

from __future__ import annotations


def percentile(values, q: float) -> float:
    """Nearest-rank percentile, ``q`` in [0, 1]: the sorted value at
    ``round(q * (n - 1))``.  Every sample is one of the measured values, so
    a tail is always a time that some step or request really took."""
    if not values:
        raise ValueError("percentile of no samples")
    ordered = sorted(values)
    idx = min(len(ordered) - 1, max(0, round(q * (len(ordered) - 1))))
    return ordered[idx]

