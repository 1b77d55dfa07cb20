"""Entry points: what a commit check costs the host before its launch:
sum of the program's `commit.collect` (sign-bytes, pubkey rows, the
signature list), `commit.prefix` (the >2/3 tally) and
`commit.validate_basic` spans in a request, median per request, in ms.
Absent where the program records no such span (perfbench/progspans.py)."""
from perfbench import progspans


def read(run):
    return progspans.sum_ms(run, "commit.collect", "commit.prefix",
                            "commit.validate_basic")
