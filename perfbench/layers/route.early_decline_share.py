"""Route ladder: of a request's `comb.resolve` spans whose `outcome` is
`declined` (verify_batch asked the comb for tables and the budget said
no), the share whose `early` attribute is true: the batch left by the
bound on its distinct keys, ahead of the distinct-key sort and the sha256
over all its rows.  Median per request over the requests that hold such a
span, in %.  Absent where no declined span carries `early`: the parent's
program sorts every time and says nothing, and a cell whose tables are
resident declines nothing (perfbench/progspans.py)."""
from perfbench import progspans, stats


def read(run):
    per_request = progspans.by_request(run)
    if per_request is None:
        return None
    shares = []
    for recs in per_request:
        early = [r["attrs"]["early"] for r in recs
                 if r["name"] == "comb.resolve"
                 and r["attrs"].get("outcome") == "declined"
                 and "early" in r["attrs"]]
        if early:
            shares.append(100.0 * sum(early) / len(early))
    if len(shares) < progspans.MIN_REQUESTS:
        return None
    return stats.median(shares)
