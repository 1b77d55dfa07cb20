"""Kernels: seconds in which an operation ran on the device inside the
traced requests (union of the `XLA Ops` intervals, perfbench/tracered.py),
over the real signatures launched in those requests (`n` of their devobs
records, padding lanes not counted), in us.  Absent without a TPU plane."""


def read(run):
    red = run.get("trace") or {}
    if "request_busy_s" not in red:
        return None
    first, last = red["requests"]
    rows = [r for r in run["requests"] if first <= r["i"] <= last]
    sigs = sum(x["n"] for r in rows for x in r["records"])
    busy = sum(red["request_busy_s"][:len(rows)])
    return busy / sigs * 1e6 if sigs else None
