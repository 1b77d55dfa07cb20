"""VerifyScheduler: `lanes` of last_latency_report(), sampled after each
request, median."""
from perfbench import stats


def read(run):
    return stats.median(r["sched"]["lanes"] for r in run["requests"]
                        if r.get("sched") and r["sched"].get("lanes"))
