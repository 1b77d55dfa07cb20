"""Apply + storage: sum of the program's `pipeline.wait_staged` spans in a
request (the apply loop blocked on the stage worker and on the block's
verdict), median per request, in ms.  Absent where the program records no
such span (perfbench/progspans.py)."""
from perfbench import progspans


def read(run):
    return progspans.sum_ms(run, "pipeline.wait_staged")
