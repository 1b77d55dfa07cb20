"""The arithmetic the metric readers share."""
from __future__ import annotations

import statistics


def percentile(values, q: float):
    """The q-th percentile (0..100) by linear interpolation between closest
    ranks, as numpy's default; None of nothing."""
    v = sorted(values)
    if not v:
        return None
    k = (len(v) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def median(values):
    v = list(values)
    return statistics.median(v) if v else None


def per_request_sum(run: dict, key: str):
    """For each request that carries launch records, the sum of `key` over
    them, in seconds; [] when the run kept no records (--trace 0)."""
    return [sum(r.get(key) or 0.0 for r in row["records"])
            for row in run["requests"] if "records" in row]
