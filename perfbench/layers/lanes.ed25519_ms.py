"""Scheme lanes: sum of `wall_s` of the launch records of the ed25519 lane
that a request caused (staging, upload, kernel, read-back: the lane's whole
bracket on its worker), median per request, in ms.  Absent where no request
holds such a record (perfbench/lanes.py)."""
from perfbench import lanes


def read(run):
    return lanes.wall_ms(run, "ed25519")
