"""Light client + store: `light.verify` calls a request (refused skips and
hops alike), median per request over the requests of a light CLIENT: those
that carry the program's `light.client.verify` root span.  Absent where the
program records no such span (perfbench/progspans.py)."""
from perfbench import progspans, stats


def read(run):
    per_request = progspans.by_request(run)
    if per_request is None:
        return None
    counts = [sum(1 for r in recs if r["name"] == "light.verify")
              for recs in per_request
              if any(r["name"] == "light.client.verify" for r in recs)]
    if len(counts) < progspans.MIN_REQUESTS:
        return None
    return stats.median(counts)
