"""Scheduler: what a submission costs its caller beyond the launch: for
each `sched.submit` instant of a request, the time to the `sched.resolve`
instant of the same submitter (both carry the submitter's span as parent),
less the part of it inside a `sched.launch` span; summed over the request's
submissions, median per request, in ms.  That is the coalescing window plus
the hand-offs to the scheduler's two threads and back to the point of
resolution (the caller's own wake-up after it is not in it).  Absent where
the program records no such instants (perfbench/progspans.py)."""
from perfbench import progspans


def handoff_ns(recs) -> float:
    """The sum over one request's submissions; None where it has none
    that resolved."""
    submits = sorted((r for r in recs if r["name"] == "sched.submit"),
                     key=lambda r: r["ts_ns"])
    resolves = sorted((r for r in recs if r["name"] == "sched.resolve"),
                      key=lambda r: r["ts_ns"])
    launches = [(r["ts_ns"], r["ts_ns"] + r["dur_ns"]) for r in recs
                if r["name"] == "sched.launch"]
    total, taken = None, set()
    for sub in submits:
        done = next((r for r in resolves if r["id"] not in taken
                     and r["parent"] == sub["parent"]
                     and r["ts_ns"] >= sub["ts_ns"]), None)
        if done is None:
            continue
        taken.add(done["id"])
        t0, t1 = sub["ts_ns"], done["ts_ns"]
        inside = sum(max(0, min(e, t1) - max(s, t0)) for s, e in launches)
        total = (total or 0) + (t1 - t0) - inside
    return total


def read(run):
    per_request = progspans.by_request(run)
    if per_request is None:
        return None
    sums = [handoff_ns(recs) for recs in per_request]
    return progspans.median_ms(s for s in sums if s is not None)
