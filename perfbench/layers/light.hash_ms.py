"""Entry points: sum of the program's `valset.hash` spans in a request
(ValidatorSet.hash(), every leaf on the host), median per request, in ms.
Absent where the program records no such span (perfbench/progspans.py)."""
from perfbench import progspans


def read(run):
    return progspans.sum_ms(run, "valset.hash")
