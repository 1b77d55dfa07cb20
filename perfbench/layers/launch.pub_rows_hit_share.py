"""Route ladder: of a request's `pallas-split` launch records, the share
whose `pub_rows_cached` is true: the batch's pubkey rows were found on the
device by their content and only R, s and k went over the wire.  Median
over the requests that have such a record, in %.  Absent where no record
carries `pub_rows_cached`: the parent's program does not say."""
from perfbench import stats


def read(run):
    shares = []
    for r in run["requests"]:
        found = [x["pub_rows_cached"] for x in r.get("records", ())
                 if x.get("path") == "pallas-split"
                 and "pub_rows_cached" in x]
        if found:
            shares.append(100.0 * sum(found) / len(found))
    return stats.median(shares)
