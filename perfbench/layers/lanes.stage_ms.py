"""Scheme lanes: sum of the program's `secp.stage` and `sr25519.stage` spans
in a request (the two lanes' host staging: challenges, range and encoding
screens, limb and digit packing, on the device-lane worker ahead of each
launch), median per request, in ms.  Absent where the program records no
such span (perfbench/progspans.py)."""
from perfbench import progspans


def read(run):
    return progspans.sum_ms(run, "secp.stage", "sr25519.stage")
