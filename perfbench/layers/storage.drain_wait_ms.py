"""Apply + storage: sum of the program's `pipeline.drain` spans in a
request (the window's return waiting for the writer's last group commit),
median per request, in ms.  Absent where the program records no such span
(perfbench/progspans.py)."""
from perfbench import progspans


def read(run):
    return progspans.sum_ms(run, "pipeline.drain")
