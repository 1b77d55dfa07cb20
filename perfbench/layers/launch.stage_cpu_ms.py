"""Host staging: `stage_cpu_s` of the launch records a request caused (the
staging thread's CPU time over the bracket of `stage_s`), in ms per request.
`launch.stage_ms` less this is time the thread was off the processor, not
staging work.  The MEAN over the requests, not their median: the thread CPU
clock of the benchmark's machines moves in ticks of 10 ms, so one request
reads 0 or 10 and only a sum over many is a measurement.  Absent where no
record of the run carries the key."""
from perfbench import stats


def read(run):
    if not any("stage_cpu_s" in x for r in run["requests"]
               for x in r.get("records", ())):
        return None
    sums = stats.per_request_sum(run, "stage_cpu_s")
    return sum(sums) / len(sums) * 1e3
