"""Light client + store: what a request spends making the store's record
of the blocks it saves: sum of the program's `light.store.encode` spans
(one a saved block, inside `light.store.save`, ahead of the db's set),
median per request, in ms.  Absent where the program records no such span:
the parent's program encodes inside `light.store.save` and says nothing
apart (perfbench/progspans.py)."""
from perfbench import progspans


def read(run):
    return progspans.sum_ms(run, "light.store.encode")
