"""Apply + storage: sum of the program's `pipeline.commit` spans in a
request (the writer thread's group commits into SQLite synchronous=FULL,
beside the apply loop), median per request, in ms.  Absent where the
program records no such span (perfbench/progspans.py)."""
from perfbench import progspans


def read(run):
    return progspans.sum_ms(run, "pipeline.commit")
