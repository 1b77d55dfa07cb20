"""The program's own spans, request by request, for the per-layer readers.

The program keeps a flight recorder (tendermint_tpu/libs/trace.py, on by
default): a ring of the newest 8,192 finished spans, each with its name,
start and wall duration on `time.perf_counter_ns`, the CPU time of its
thread, its thread and its parent span.  `run.py` does not hand those to a
reader (the `run` it builds holds the benchmark's own `pb.*` rows, the
devobs launch records and the scheduler's samples), and this PR may not
edit `run.py`.  So a reader takes them from the program, after the window,
through this module:

    from perfbench import progspans
    per_request = progspans.by_request(run)      # or None

- the request intervals are the `pb.request` rows of `run["spans"]`
  (`perf_counter` seconds, the same clock as the program's spans), which
  exist in a `--trace 1` run only;
- a program span belongs to the request whose interval holds its START
  (a worker's span that began inside the request is the request's, on
  whatever thread it ran);
- the ring forgets: once it has wrapped, only requests that began after
  the oldest span it still holds had ENDED are used (a span is recorded
  when it ends, so everything that began after that instant is still
  there).  8,192 spans are the newest ~320 requests of `val150-live` and
  ~14 windows of the catch-up;
- a reader gives a median over requests, and gives None, never raises,
  when fewer than MIN_REQUESTS usable requests carry the span it wants:
  the parent commit's recorder is off and holds nothing, a program
  without the module has no spans, `--trace 0` has no request rows, and
  an open window's requests overlap.
  MIN_REQUESTS is 3, not more, because a traced window is short: the
  runner stops the profiler inside it, that takes 50-70 s on the comb
  cells, and a `--trace 1` run of `val150-catchup` holds 6 requests in
  all (of `val150-live` 54), the same rows `entry.host_ms` takes its
  median over.

A `benchmark` PR that may edit `run.py` should sample the recorder per
request into `run`, as the devobs records are, and retire this detour
(PERF.md section 7).
"""
from __future__ import annotations

from bisect import bisect_right

from perfbench import stats

REQUEST_ROW = "pb.request"
MIN_REQUESTS = 3


def program_records():
    """(the recorder's finished records oldest first, whether the ring has
    dropped any); ([], False) where the program has no recorder."""
    try:
        from tendermint_tpu.libs import trace
    except ImportError:
        return [], False
    return trace.snapshot(), trace.dropped() > 0


def assign(requests, records, wrapped: bool) -> list:
    """[[record, ...], ...]: for each usable request interval
    (start_ns, end_ns) in time order, the records whose start it holds.
    Plain data in, plain data out (perfbench/tests/test_progspans.py)."""
    requests = sorted(requests)
    if not requests or not records:
        return []
    horizon = 0
    if wrapped:
        oldest = records[0]
        horizon = oldest["ts_ns"] + oldest["dur_ns"]
    starts = [r[0] for r in requests]
    out = [[] for _ in requests]
    for rec in records:
        k = bisect_right(starts, rec["ts_ns"]) - 1
        if k >= 0 and rec["ts_ns"] < requests[k][1]:
            out[k].append(rec)
    return [recs for (t0, _), recs in zip(requests, out) if t0 >= horizon]


def by_request(run: dict):
    """The program's records of each usable request of `run`, or None
    where there are no request rows or no records, and in an open window
    (`run["arrivals"]`): its requests overlap, so the request whose
    interval holds a span's start is no rule there."""
    if "arrivals" in run:
        return None
    requests = [(t0 * 1e9, t1 * 1e9) for name, t0, t1 in run.get("spans", [])
                if name == REQUEST_ROW]
    records, wrapped = program_records()
    return assign(requests, records, wrapped) or None


def median_ms(per_request_ns):
    """Median of one number per request, ns -> ms; None under
    MIN_REQUESTS of them."""
    values = list(per_request_ns)
    if len(values) < MIN_REQUESTS:
        return None
    return stats.median(values) / 1e6


def sum_ms(run: dict, *names: str):
    """Median per request of the summed wall durations of the spans named,
    in ms, over the requests that carry at least one of them."""
    per_request = by_request(run)
    if per_request is None:
        return None
    sums = []
    for recs in per_request:
        durs = [r["dur_ns"] for r in recs if r["name"] in names]
        if durs:
            sums.append(sum(durs))
    return median_ms(sums)
