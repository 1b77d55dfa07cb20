"""Light client + store: what a request spends writing its store: sum of
the program's `light.store.save` (encode + SQLite set, one a block of the
trace) and `light.store.prune` spans, median per request, in ms.  Absent
where the program records no such span (perfbench/progspans.py)."""
from perfbench import progspans


def read(run):
    return progspans.sum_ms(run, "light.store.save", "light.store.prune")
