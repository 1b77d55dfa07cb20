"""Entry points: sum of the program's `consensus.screen` spans in a request
(the screening loop of `_preverify_votes_locked`, one a drained batch: which
votes the serial apply will verify, their sign bytes, up to the call of
`verify_items`), median per request, in ms.  Absent where the program
records no such span: the parent's does the work and says nothing
(perfbench/progspans.py)."""
from perfbench import progspans


def read(run):
    return progspans.sum_ms(run, "consensus.screen")
