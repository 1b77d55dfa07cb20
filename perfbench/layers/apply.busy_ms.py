"""Apply + storage: sum of the program's `pipeline.apply` spans in a
request (the serial apply loop: block store save + ABCI apply of each
block), median per request, in ms.  Absent where the program records no
such span (perfbench/progspans.py)."""
from perfbench import progspans


def read(run):
    return progspans.sum_ms(run, "pipeline.apply")
