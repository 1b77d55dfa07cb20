"""`val10k-mixed-commit` rehearsed on the CPU: the runner end to end, both
--trace values, on a test-only cell of the `mixed_commit` traffic kind (120
validators, 40 a scheme, 6 absent a height, ring 2) listed by a manifest of
its own, fixtures/manifest_mixed.json; the five `lanes.*` readers on
hand-made records and spans; and the check itself, which must say so when a
lane and the plain reference (perfbench/reference/mixed_commit.py) part.

The secp256k1 lane's XLA core costs ~80 s to compile on a CPU the first
time (the persistent cache keeps it afterwards); the sr25519 lane's ~30 s."""
import json
import os

import pytest

from perfbench import progspans
from perfbench.tests.conftest import FIXTURES
from perfbench.tests.test_progspans import (  # noqa: F401  (fixture)
    program, reader, rec, run_of)
from perfbench.tests.test_run_cpu import DEVICE_KEYS, E2E, RESULT_KEYS

LANES = {"lanes.ed25519_ms", "lanes.secp_ms", "lanes.sr25519_ms",
         "lanes.stage_ms", "lanes.overlap_share"}
LAYERS = {"entry.host_ms", "launch.wall_ms", "launch.count",
          "launch.stage_ms", "entry.collect_ms", "route.resolve_ms"}


@pytest.fixture
def mixed_runner(runner, monkeypatch):
    from tendermint_tpu.libs import trace

    monkeypatch.setattr(runner, "MANIFEST",
                        os.path.join(FIXTURES, "manifest_mixed.json"))
    monkeypatch.setattr(progspans, "MIN_REQUESTS", 2)
    trace.enable(capacity=8192)     # a neighbour may have left it off
    trace.reset()
    yield runner
    trace.disable()
    trace.reset()


def run_cell(runner, capfd, trace, seed):
    rc = runner.main(["--workload", "tiny-mixed", "--seed", str(seed),
                      "--seconds", "4", "--trace", str(trace)])
    out = capfd.readouterr()
    lines = out.out.strip().splitlines()
    assert rc == 0, out.err[-2000:]
    assert all(ln.startswith("# ") for ln in lines[:-1])
    res = json.loads(lines[-1])
    assert set(res) == RESULT_KEYS and set(res["device"]) == DEVICE_KEYS
    assert res["correct"] is True, out.err
    assert res["failed"] == 0 and res["attempted"] >= 2
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] >= 0
    return res, out


def test_untraced_line_holds_the_cells_end_to_end_metrics(mixed_runner,
                                                          capfd):
    res, out = run_cell(mixed_runner, capfd, 0, 2**31 + 41)
    assert set(res["metrics"]) == E2E
    assert "3 commits x 114 signatures of 120 validators" in out.out
    assert "routes ['secp-xla/64', 'sr25519-xla/64', 'xla/64']" in out.out
    assert "compiles in window 0" in out.out


def test_traced_line_holds_the_lane_readers(mixed_runner, capfd):
    res, out = run_cell(mixed_runner, capfd, 1, 2**31 + 42)
    m = res["metrics"]
    # 114 rows fit the running scheduler's window (max_batch 8,192), so
    # this cell's lanes are the scheduler's (`sched.launch`), and the
    # BatchVerifier's `batch.verify` span with its `lane_overlap` is the
    # full-size cell's alone (9,900 rows): that reader is tried on
    # hand-made spans below
    lanes_here = LANES - {"lanes.overlap_share"}
    assert LAYERS | lanes_here <= set(m) <= LAYERS | lanes_here | {
        "launch.stage_cpu_ms"}
    assert "holds no /device:TPU:" in out.out
    # three launches a request, one a scheme, and the lanes' walls are
    # the launches' walls
    assert m["launch.count"]["value"] == 3
    lanes = sum(m[k]["value"] for k in (
        "lanes.ed25519_ms", "lanes.secp_ms", "lanes.sr25519_ms"))
    assert lanes == pytest.approx(m["launch.wall_ms"]["value"], rel=0.1)
    assert 0 < m["lanes.stage_ms"]["value"] < m["launch.stage_ms"]["value"]


def test_the_check_says_where_a_lane_and_the_reference_part(mixed_runner,
                                                            monkeypatch):
    """An ed25519 lane that took s + L for s would be caught: the check
    compares verdicts scheme by scheme, it does not only run the lanes.
    (Of the six refusals this is the one a single missing screen lets
    through: sr25519's s + L is screened in the C stager, R + p changes
    the challenge, and secp256k1's s and r cannot be moved by a whole
    modulus inside 32 bytes.)"""
    import numpy as np

    from perfbench import run
    from perfbench.traffic import mixed_commit as gen
    from tendermint_tpu.crypto import degrade
    from tendermint_tpu.ops import ed25519 as edops

    cell = run.load_cell(mixed_runner.MANIFEST, "tiny-mixed")
    world = gen.setup(cell["config_file"], cell["params"], 2**31 + 43, 1.0)
    world["span"] = run.Spans(False).span
    # the runtime's own host spot check of one random row a launch would
    # catch the lane one time in 38 and send the batch to the host
    degrade.configure(degrade.DegradeConfig(spot_check=False))
    try:
        assert gen.check(world) == []
        monkeypatch.setattr(edops, "_s_canonical",
                            lambda s_bytes: np.ones(len(s_bytes), dtype=bool))
        failures = gen.check(world)
    finally:
        degrade.reset()
    assert len(failures) == 1 and "ed25519-s-plus-L" in failures[0] \
        and "accepted" in failures[0] and "wrong_signature" in failures[0]


def records_run(per_request):
    return {"requests": [{"i": i, "records": recs}
                         for i, recs in enumerate(per_request)]}


def test_lane_walls_are_sums_a_request_medians_over_requests():
    def request(i):
        return [{"path": "pallas", "n": 3300, "nb": 4096, "wall_s": 0.010},
                {"path": "secp-xla", "n": 3300, "nb": 4096,
                 "wall_s": 0.100 * (i + 1)},
                {"path": "sr25519-xla", "n": 3300, "nb": 4096,
                 "wall_s": 0.030},
                {"path": "comb", "n": 40, "nb": 64, "wall_s": 0.002}]
    run = records_run([request(i) for i in range(5)])
    assert reader("lanes.ed25519_ms").read(run) == pytest.approx(12.0)
    assert reader("lanes.secp_ms").read(run) == pytest.approx(300.0)
    assert reader("lanes.sr25519_ms").read(run) == pytest.approx(30.0)
    # an all-ed25519 cell, or a program whose lanes write no record
    run = records_run([[{"path": "pallas-split", "n": 99000, "nb": 114688,
                         "wall_s": 0.29}]] * 4)
    assert reader("lanes.ed25519_ms").read(run) == pytest.approx(290.0)
    assert reader("lanes.secp_ms").read(run) is None
    assert reader("lanes.sr25519_ms").read(run) is None
    untraced = {"requests": [{"i": 0}, {"i": 1}]}
    for metric in ("lanes.ed25519_ms", "lanes.secp_ms", "lanes.sr25519_ms"):
        assert reader(metric).read(untraced) is None


def test_stage_and_overlap_read_the_programs_spans(program):
    def one(i, t):
        verify = rec("batch.verify", t + 1, 8)
        verify["attrs"] = {"n": 9900, "device_lanes": 3, "host_lanes": 0,
                           "lane_overlap": 0.01 * i}
        return [verify, rec("secp.stage", t + 2, 3.0 + i),
                rec("sr25519.stage", t + 6, 1.0),
                rec("ops.secp.verify_batch", t + 2, 5)]
    run, records = run_of(5, one)
    program(records)
    # 4, 5, 6, 7, 8 ms; 0, 1, 2, 3, 4 %
    assert reader("lanes.stage_ms").read(run) == pytest.approx(6.0)
    assert reader("lanes.overlap_share").read(run) == pytest.approx(2.0)
    # one lane a batch: the program writes no lane_overlap; the parent's
    # lanes open no stage span
    run, records = run_of(5, lambda i, t: [rec("batch.verify", t + 1, 8)])
    program(records)
    assert reader("lanes.stage_ms").read(run) is None
    assert reader("lanes.overlap_share").read(run) is None
    program([])
    assert reader("lanes.overlap_share").read(run) is None
    assert reader("lanes.stage_ms").read(
        {"spans": [], "requests": [{"wall_s": 0.01}] * 8}) is None
