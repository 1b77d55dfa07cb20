"""`val100k-commit` rehearsed on the CPU: the runner end to end, both
--trace values, on a test-only cell of the `commit_heights` traffic kind
(300 validators, 3 absent a height, ring 8) listed by a manifest of its
own, fixtures/manifest_commit.json (the neighbours' manifests and tests are
theirs and stay as they are); the four new readers on hand-made records;
and the check itself, which must say so when the program and the plain
reference (perfbench/reference/commit.py) part."""
import json
import os

import pytest

from perfbench import progspans
from perfbench.tests.conftest import FIXTURES
from perfbench.tests.test_progspans import (  # noqa: F401  (fixture)
    program, reader, rec, run_of)
from perfbench.tests.test_run_cpu import DEVICE_KEYS, E2E, RESULT_KEYS

LAYERS = {"entry.host_ms", "launch.wall_ms", "launch.count",
          "launch.stage_ms", "entry.collect_ms"}
# the CPU's route keeps no pubkey rows and does not pipeline: of the four
# new readers, these two find something to read there
NEW_ON_CPU = {"launch.h2d_ms", "route.resolve_ms"}
NEW_ON_CHIP = {"launch.drain_ms", "launch.pub_rows_hit_share"}


@pytest.fixture
def commit_runner(runner, monkeypatch):
    from tendermint_tpu.libs import trace

    monkeypatch.setattr(runner, "MANIFEST",
                        os.path.join(FIXTURES, "manifest_commit.json"))
    monkeypatch.setattr(progspans, "MIN_REQUESTS", 2)
    trace.enable(capacity=8192)     # a neighbour may have left it off
    trace.reset()
    yield runner
    trace.disable()
    trace.reset()


def run_cell(runner, capfd, trace, seed):
    rc = runner.main(["--workload", "tiny-commit", "--seed", str(seed),
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
        assert set(m) == {"value", "unit"} and m["value"] > 0
    return res, out


def test_untraced_line_holds_the_cells_end_to_end_metrics(commit_runner,
                                                          capfd):
    res, out = run_cell(commit_runner, capfd, 0, 2**31 + 31)
    assert set(res["metrics"]) == E2E
    assert "9 commits x 297 signatures of 300 validators, 3 absent" \
        in out.out


def test_traced_line_holds_the_new_readers(commit_runner, capfd):
    res, out = run_cell(commit_runner, capfd, 1, 2**31 + 32)
    assert LAYERS | NEW_ON_CPU <= set(res["metrics"]) \
        <= LAYERS | NEW_ON_CPU | {"launch.stage_cpu_ms"}
    assert "holds no /device:TPU:" in out.out
    # one launch a request, of the 297 rows that signed: no absent row
    # went to the device
    assert res["metrics"]["launch.count"]["value"] == 1


def test_the_check_says_where_program_and_reference_part(commit_runner,
                                                         monkeypatch):
    """A program that took a commit one row short of 2/3 would be caught:
    the check compares verdicts, it does not only run the program."""
    from perfbench import run
    from perfbench.traffic import commit_heights as gen
    from tendermint_tpu.types.validator_set import ValidatorSet

    cell = run.load_cell(commit_runner.MANIFEST, "tiny-commit")
    world = gen.setup(cell["config_file"], cell["params"], 2**31 + 33, 1.0)
    world["span"] = run.Spans(False).span
    assert gen.check(world) == []
    real = ValidatorSet.verify_commit

    def lenient(self, chain_id, block_id, height, commit):
        try:
            real(self, chain_id, block_id, height, commit)
        except Exception as e:      # noqa: BLE001 - the fault under test
            if "insufficient voting power" not in str(e):
                raise

    monkeypatch.setattr(ValidatorSet, "verify_commit", lenient)
    (why,) = gen.check(world)
    assert "a commit of 200 of 300 rows" in why and "accepted" in why \
        and "not_enough_power" in why


def records_run(per_request):
    """A traced `run` whose request i caused the launch records
    per_request[i]."""
    return {"requests": [{"i": i, "records": recs}
                         for i, recs in enumerate(per_request)]}


def split(**keys):
    return dict({"path": "pallas-split", "n": 99000, "nb": 114688}, **keys)


def test_drain_and_h2d_are_sums_a_request_medians_over_requests():
    run = records_run([[split(drain_s=0.010 * (i + 1), h2d_s=0.002),
                        split(drain_s=0.001, h2d_s=0.003)]
                       for i in range(5)])
    # 11, 21, 31, 41, 51 ms
    assert reader("launch.drain_ms").read(run) == pytest.approx(31.0)
    assert reader("launch.h2d_ms").read(run) == pytest.approx(5.0)
    # a route that brackets compute apart has collect_s, and no drain_s
    run = records_run([[{"path": "xla", "n": 297, "nb": 512,
                         "h2d_s": 0.001, "collect_s": 0.002}]] * 4)
    assert reader("launch.drain_ms").read(run) is None
    assert reader("launch.h2d_ms").read(run) == pytest.approx(1.0)
    # an untraced run keeps no records
    untraced = {"requests": [{"i": 0}, {"i": 1}]}
    for metric in NEW_ON_CHIP | {"launch.h2d_ms"}:
        assert reader(metric).read(untraced) is None


def test_hit_share_counts_split_records_that_say_and_no_others():
    # every request misses: 0 is a reading, not an absence
    run = records_run([[split(pub_rows_cached=False,
                              pub_rows_bytes=3670016)]] * 6)
    assert reader("launch.pub_rows_hit_share").read(run) == 0.0
    # the skipping cells: a `pallas` launch that keeps no rows and says
    # nothing, then a split launch that found its rows
    run = records_run([[{"path": "pallas", "n": 3334, "nb": 4096},
                        split(pub_rows_cached=True)]] * 3)
    assert reader("launch.pub_rows_hit_share").read(run) == 100.0
    # a client's hops: one of two split launches finds its rows
    run = records_run([[split(pub_rows_cached=i % 2 == 0),
                        split(pub_rows_cached=True)] for i in range(4)])
    assert reader("launch.pub_rows_hit_share").read(run) == 75.0
    # the parent's program: split records without the key
    run = records_run([[split()]] * 4)
    assert reader("launch.pub_rows_hit_share").read(run) is None


def test_resolve_sums_the_comb_resolve_spans_and_is_absent_without(
        program):
    run, records = run_of(5, lambda i, t: [
        rec("comb.resolve", t + 1, 3.0), rec("comb.resolve", t + 5, 0.5),
        rec("commit.collect", t + 0.2, 0.5)])
    program(records)
    assert reader("route.resolve_ms").read(run) == pytest.approx(3.5)
    # the parent's program records no such span
    program([r for r in records if r["name"] != "comb.resolve"])
    assert reader("route.resolve_ms").read(run) is None
