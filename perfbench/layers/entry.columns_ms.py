"""Entry points: sum of the program's `commit.columns` spans in a request
(one read of a commit's rows into numpy columns, types/commit._columns,
inside `commit.validate_basic`, `commit.collect` or `commit.prefix`: what
is left of the per-row Python of `entry.collect_ms`, and what columns
built where a commit is decoded could still remove), median per request,
in ms.  Absent where the program records no such span: the parent's walks
the rows a Python step at a time and says nothing apart
(perfbench/progspans.py)."""
from perfbench import progspans


def read(run):
    return progspans.sum_ms(run, "commit.columns")
