"""Entry points: of a request's `valset.hash` spans (ValidatorSet.hash(),
one span a call), the share whose `memo` attribute is true: the root was
answered by the memo on the validators list and no leaf was encoded or
hashed.  Median per request over the requests that hash at all, in %.
Absent where no `valset.hash` span carries `memo`: the parent's program
computes every time and says nothing (perfbench/progspans.py)."""
from perfbench import progspans, stats


def read(run):
    per_request = progspans.by_request(run)
    if per_request is None:
        return None
    shares = []
    for recs in per_request:
        memos = [r["attrs"]["memo"] for r in recs
                 if r["name"] == "valset.hash" and "memo" in r["attrs"]]
        if memos:
            shares.append(100.0 * sum(memos) / len(memos))
    if len(shares) < progspans.MIN_REQUESTS:
        return None
    return stats.median(shares)
