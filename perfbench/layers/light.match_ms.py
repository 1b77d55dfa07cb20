"""Entry points: sum of the program's `commit.match` spans in a request
(verify_commit_light_trusting finding each signer by address), median per
request, in ms.  Absent where the program records no such span
(perfbench/progspans.py)."""
from perfbench import progspans


def read(run):
    return progspans.sum_ms(run, "commit.match")
