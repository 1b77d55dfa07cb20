"""Device: 1 - (union of the intervals in which an operation ran on the
device) / (first traced request's start to the last one's end; in an open
window, the profiled span), in %.  Absent without a TPU plane."""


def read(run):
    red = run.get("trace") or {}
    if "busy_s" not in red:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
