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


def backlog_ratio(rows, window_s: float):
    """Of an open window's rows: the median wall of the requests scheduled
    in the window's last fifth over that of those in its first fifth.  A
    queue that grows all through the window reads well over 1; None where
    either fifth has no request."""
    first = [r["wall_s"] for r in rows if r["t_sched"] < window_s / 5]
    last = [r["wall_s"] for r in rows if r["t_sched"] >= window_s * 4 / 5]
    if not first or not last:
        return None
    return median(last) / median(first)
