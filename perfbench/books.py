"""Closing the books on a request: what the readers of the unnamed
remainder and of the `votes` counter share (perfbench/layers/
entry.unspanned_ms.py, votes.add_ms.py).

The program's recorder (tendermint_tpu/libs/trace.py) answers two
questions about an interval on a thread: which part of it lies inside a
span that names a piece of work or a wait, and which part inside an
envelope (a span that stands for a request or a hand-off) or inside
nothing: `trace.unnamed_ns`.  And a loop that may not have a span a turn,
`VoteSet.add_vote`, keeps a tally that the program samples as a counter
record, `votes`, at each `_preverify_votes`: the tally's wall between two
samples is named time although no span covers it.

Everything here returns None, and raises nothing, on a program without the
function or the counter (the parent's), in an untraced run, in an open
window, and under progspans.MIN_REQUESTS.
"""
from __future__ import annotations

from perfbench import progspans

VOTES = "votes"


def requests(run: dict):
    """[(start_ns, end_ns, the program's records that began inside)] for
    each usable request of `run` in time order (progspans.assign's rule:
    once the ring has wrapped, only requests it still holds whole), or
    None; None in an open window, as progspans.by_request."""
    if "arrivals" in run:
        return None
    rows = sorted((t0 * 1e9, t1 * 1e9) for name, t0, t1
                  in run.get("spans", []) if name == progspans.REQUEST_ROW)
    records, wrapped = progspans.program_records()
    per_request = progspans.assign(rows, records, wrapped)
    if not per_request:
        return None
    # the usable requests are the newest ones
    usable = rows[len(rows) - len(per_request):]
    return [(t0, t1, recs) for (t0, t1), recs in zip(usable, per_request)]


def votes_wall_gains(rows) -> list:
    """For each request of `rows`, the wall the `votes` tally gained from
    the request's first sample to the NEXT request's first (the adds of a
    request follow its samples), in ns: 0 for a request that never
    samples the counter, None for one that does with no sampled request
    after it."""
    firsts = [next((r["attrs"].get("wall_ns") for r in recs
                    if r["ph"] == "C" and r["name"] == VOTES), None)
              for _, _, recs in rows]
    gains = []
    for k, first in enumerate(firsts):
        if first is None:
            gains.append(0)
        elif k + 1 < len(firsts) and firsts[k + 1] is not None:
            gains.append(firsts[k + 1] - first)
        else:
            gains.append(None)
    return gains
