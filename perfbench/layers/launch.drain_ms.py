"""Route ladder: sum of `drain_s` of the launch records a request caused
(the caller's wait after the last chunk of a pipelined launch was
dispatched: device work and read-back the pipeline did not hide), median
per request, in ms.  Absent where no record of the run carries the key (a
route that brackets compute apart has `collect_s` instead)."""
from perfbench import stats


def read(run):
    if not any("drain_s" in x for r in run["requests"]
               for x in r.get("records", ())):
        return None
    return stats.median(stats.per_request_sum(run, "drain_s")) * 1e3
