"""Scheme lanes: `lane_overlap` of a request's `batch.verify` span (1 - the
wall from the first lane's start to the last lane's end over the sum of the
lanes' walls: 0 for lanes run one after another, 2/3 for three run side by
side), x 100, median over the requests whose span carries it, in %.
Absent where no `batch.verify` span of a request has more than one lane
(perfbench/progspans.py)."""
from perfbench import progspans, stats


def read(run):
    per_request = progspans.by_request(run)
    if per_request is None:
        return None
    shares = [100.0 * r["attrs"]["lane_overlap"]
              for recs in per_request for r in recs
              if r["name"] == "batch.verify" and "lane_overlap" in r["attrs"]]
    if len(shares) < progspans.MIN_REQUESTS:
        return None
    return stats.median(shares)
