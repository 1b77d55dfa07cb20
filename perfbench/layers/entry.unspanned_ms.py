"""Entry points: the part of a request that no name accounts for.  Over the
request's interval on the caller's thread (the main thread: the closed loop
has one caller), `trace.unnamed_ns` of the program's recorder: the time
whose innermost open span is an envelope (`trace.ENVELOPES`: a request's
root or a hand-off's bracket, whose self time names no work) or nothing at
all; less the wall the `votes` counter gained in the request (a tally's
wall is named time, perfbench/books.py).  Median per request, in ms.  It is
what the builders of PRs 30-34 got by subtracting spans from a request by
hand, and what `entry.host_ms` cannot tell from named host work.  Absent
where the program has no `unnamed_ns` (the parent's)."""
import threading

from perfbench import books, progspans


def read(run):
    try:
        from tendermint_tpu.libs.trace import unnamed_ns
    except ImportError:
        return None
    rows = books.requests(run)
    if rows is None:
        return None
    tid = threading.main_thread().ident
    left = [unnamed_ns(recs, tid, t0, t1) - gain
            for (t0, t1, recs), gain in zip(rows, books.votes_wall_gains(rows))
            if gain is not None]
    return progspans.median_ms(left)
