"""Scheduler: sum of `queue_wait_ns` over the request's `sched.launch`
spans: for EVERY window the request caused, its oldest submission's wait
from submit to window close (`sched.queue_wait_ms` is the last window's
only, sampled after the request).  Median per request, in ms.  Absent where
no `sched.launch` span carries the attribute: the parent's program does not
say (perfbench/progspans.py)."""
from perfbench import progspans


def read(run):
    per_request = progspans.by_request(run)
    if per_request is None:
        return None
    sums = []
    for recs in per_request:
        waits = [r["attrs"]["queue_wait_ns"] for r in recs
                 if r["name"] == "sched.launch"
                 and "queue_wait_ns" in r["attrs"]]
        if waits:
            sums.append(sum(waits))
    return progspans.median_ms(sums)
