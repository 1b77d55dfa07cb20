"""Route ladder: sum of `head_s` of the launch records a request caused: on
the `pallas-split` route, the launch bracket's start to the return of the
first chunk's `launch_kernel`, i.e. what the device waits for before its
first kernel (the signature and key matrices, the rows' content key and,
on a miss, their uploads: `pub_rows_s` on the same record; the first
chunk's staging and put).  Median per request, in ms.  Absent where no
record of the run carries the key: the parent's program does not say."""
from perfbench import stats


def read(run):
    if not any("head_s" in x for r in run["requests"]
               for x in r.get("records", ())):
        return None
    return stats.median(stats.per_request_sum(run, "head_s")) * 1e3
