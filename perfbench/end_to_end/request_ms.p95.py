"""95th percentile of the request wall, of all requests of the window, in
ms.  Only cells whose window holds >= 200 requests list it, so that ten lie
beyond it."""
from perfbench import stats


def read(run):
    p = stats.percentile([r["wall_s"] for r in run["requests"]], 95)
    return None if p is None else p * 1e3
