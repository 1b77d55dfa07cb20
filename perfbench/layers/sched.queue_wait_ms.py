"""VerifyScheduler: `queue_wait_max_s` of last_latency_report(), sampled
after each request (it is the last window only), median, in ms."""
from perfbench import stats


def read(run):
    m = stats.median(
        r["sched"]["queue_wait_max_s"] for r in run["requests"]
        if r.get("sched") and r["sched"].get("queue_wait_max_s") is not None)
    return None if m is None else m * 1e3
