"""Route ladder: what `ops.ed25519.verify_batch` does OUTSIDE its launch
bracket: over the request's `ops.ed25519.verify_batch` spans, the span's
duration less `bracket_ns` (the wall its launch record holds) less its
`comb.resolve` child (`route.resolve_ms` counts that); summed over the
request's spans, median per request, in ms: the plane and route checks
ahead of the bracket, the record's publication and the mask after it.
Absent where no such span carries `bracket_ns`: the parent's program does
not say (perfbench/progspans.py)."""
from perfbench import progspans


def read(run):
    per_request = progspans.by_request(run)
    if per_request is None:
        return None
    sums = []
    for recs in per_request:
        spans = [r for r in recs if r["name"] == "ops.ed25519.verify_batch"
                 and "bracket_ns" in r["attrs"]]
        if not spans:
            continue
        resolved = {}
        for r in recs:
            if r["name"] == "comb.resolve":
                resolved[r["parent"]] = resolved.get(r["parent"], 0) \
                    + r["dur_ns"]
        sums.append(sum(r["dur_ns"] - r["attrs"]["bracket_ns"]
                        - resolved.get(r["id"], 0) for r in spans))
    return progspans.median_ms(sums)
