"""Route ladder: sum of the program's `comb.resolve` spans in a request
(verify_batch looking the batch's keys up in the comb's tables, ahead of
the launch's own bracket: the key matrix, the distinct-key sort and a
sha256 where the batch may build, whatever the outcome), median per
request, in ms.  Absent where the program records no such span: the
parent's does the work and says nothing (perfbench/progspans.py)."""
from perfbench import progspans


def read(run):
    return progspans.sum_ms(run, "comb.resolve")
