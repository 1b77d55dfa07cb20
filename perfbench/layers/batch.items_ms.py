"""Scheme lanes: sum of the program's `batch.items` spans in a request
(`verify_sigs_bulk`'s list path: one `_Item` a row through
`BatchVerifier.add`, ahead of `batch.verify`), median per request, in ms.
Absent where the program records no such span: the parent's builds the
items and says nothing (perfbench/progspans.py)."""
from perfbench import progspans


def read(run):
    return progspans.sum_ms(run, "batch.items")
