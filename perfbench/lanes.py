"""The scheme lanes' launch records, request by request, for the `lanes.*`
readers: which lane a devobs launch record belongs to, by the `path` it
carries.  The secp256k1 and sr25519 lanes name themselves
(tendermint_tpu/ops/secp.py, ops/sr25519.py LANE_PATH); every other path
(comb, pallas, pallas-split, xla, mesh-*) is a route of the ed25519 lane."""
from __future__ import annotations

from perfbench import stats

LANE_OF_PATH = {"secp-xla": "secp256k1", "sr25519-xla": "sr25519"}


def lane_of(record: dict) -> str:
    return LANE_OF_PATH.get(record.get("path"), "ed25519")


def wall_ms(run: dict, lane: str):
    """Median per request of the summed `wall_s` of the launch records of
    `lane`, in ms, over the requests that hold one; None where no request
    does (a program whose lanes write no record, or --trace 0)."""
    sums = []
    for row in run["requests"]:
        walls = [x.get("wall_s") or 0.0 for x in row.get("records", ())
                 if lane_of(x) == lane]
        if walls:
            sums.append(sum(walls))
    m = stats.median(sums)
    return None if m is None else m * 1e3
