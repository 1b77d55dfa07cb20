"""Light client + store: what a request spends reading its store: sum of
the program's `light.store.load` spans (SQLite get + decode; a miss is a
span too), median per request, in ms.  Absent where the program records no
such span (perfbench/progspans.py)."""
from perfbench import progspans


def read(run):
    return progspans.sum_ms(run, "light.store.load")
