"""Kernels: seconds in which an operation ran on the device inside the
traced requests (union of the `XLA Ops` intervals, perfbench/tracered.py),
over the real signatures launched in those requests (`n` of their devobs
records, padding lanes not counted), in us.  In an open window, whose
launches may serve several requests: the device's busy seconds over the
profiled span, over the real signatures of the launches that ended inside
it (a launch in flight at the span's start adds its signatures and part of
its time, one in flight at its end part of its time alone).  Absent without
a TPU plane."""


def read(run):
    red = run.get("trace") or {}
    if "arrivals" in run:
        if "busy_s" not in red:
            return None
        sigs = sum(x["n"] for x in run["profiled"]["records"])
        return red["busy_s"] / sigs * 1e6 if sigs else None
    if "request_busy_s" not in red:
        return None
    first, last = red["requests"]
    rows = [r for r in run["requests"] if first <= r["i"] <= last]
    sigs = sum(x["n"] for r in rows for x in r["records"])
    busy = sum(red["request_busy_s"][:len(rows)])
    return busy / sigs * 1e6 if sigs else None
