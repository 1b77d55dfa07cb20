"""Route ladder: sum of `wall_s` of the devobs launch records a request
caused, median per request, in ms."""
from perfbench import stats


def read(run):
    m = stats.median(stats.per_request_sum(run, "wall_s"))
    return None if m is None else m * 1e3
