"""Light client + store: what the bisection's refused skips cost: sum of
the program's `light.verify` spans whose `outcome` is `cant_trust` (header
checks, set hash and the match by address, no launch), median per request
over the requests that have one, in ms.  Absent where the program's
`light.verify` carries no `outcome` (perfbench/progspans.py)."""
from perfbench import progspans


def read(run):
    per_request = progspans.by_request(run)
    if per_request is None:
        return None
    sums = []
    for recs in per_request:
        durs = [r["dur_ns"] for r in recs if r["name"] == "light.verify"
                and r["attrs"].get("outcome") == "cant_trust"]
        if durs:
            sums.append(sum(durs))
    return progspans.median_ms(sums)
