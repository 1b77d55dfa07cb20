"""Route ladder: sum of `h2d_s` of the launch records a request caused (the
`device_put` walls of the launch's chunks less their staging), median per
request, in ms.  Absent where no record of the run carries the key."""
from perfbench import stats


def read(run):
    if not any("h2d_s" in x for r in run["requests"]
               for x in r.get("records", ())):
        return None
    return stats.median(stats.per_request_sum(run, "h2d_s")) * 1e3
