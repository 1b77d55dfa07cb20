"""Median wall of one request on the caller's side, closed by the call's
return, in ms.  In an open window the wall runs from the request's
scheduled arrival (the wait behind busy clients counts), and a late
request's is cut at the window's close + run.DRAIN_S."""
from perfbench import stats


def read(run):
    m = stats.median(r["wall_s"] for r in run["requests"])
    return None if m is None else m * 1e3
