"""Entry points: what a request's `VoteSet.add_vote` calls cost together:
the wall of the program's `votes` counter (the process-wide tally add_vote
keeps: two clock reads a vote, no record a vote) from the request's first
sample to the next request's first, median per request, in ms.  The
program samples the tally at each `_preverify_votes`, ahead of the adds
that follow it.  Absent where the program records no such counter (the
parent's) (perfbench/books.py)."""
from perfbench import books, progspans


def read(run):
    rows = books.requests(run)
    if rows is None:
        return None
    sampled = [gain for (_, _, recs), gain
               in zip(rows, books.votes_wall_gains(rows))
               if gain is not None
               and any(r["name"] == books.VOTES for r in recs)]
    return progspans.median_ms(sampled)
