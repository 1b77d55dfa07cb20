"""Median wall of one request on the caller's side, closed by the call's
return, in ms."""
from perfbench import stats


def read(run):
    m = stats.median(r["wall_s"] for r in run["requests"])
    return None if m is None else m * 1e3
