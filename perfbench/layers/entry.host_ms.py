"""Entry points' self time: the request's span less the walls of the device
launches inside it, median per request, in ms.  Where a launch overlaps
host work (the catch-up's pipeline) the difference is what the launches
did not cover, not idle host."""
from perfbench import stats


def read(run):
    rows = [r for r in run["requests"] if "records" in r]
    m = stats.median(
        r["wall_s"] - sum(x.get("wall_s") or 0.0 for x in r["records"])
        for r in rows)
    return None if m is None else m * 1e3
