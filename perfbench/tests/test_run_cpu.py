"""The runner end to end on the CPU, both --trace values, every traffic
kind at a tiny size given by test-only files under fixtures/."""
import json

import pytest

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}
E2E = {"request_ms.p50", "requests_per_s", "setup_s"}
LAYERS_CPU = {"entry.host_ms", "launch.wall_ms", "launch.count",
              "launch.stage_ms"}


def run_cell(runner, capfd, workload, trace, seconds="3"):
    # a seed per run: the process-wide SigCache outlives a run here, and a
    # second run of one seed would find its own votes in it
    run_cell.seed += 1
    rc = runner.main(["--workload", workload, "--seed", str(run_cell.seed),
                      "--seconds", seconds, "--trace", str(trace)])
    out = capfd.readouterr()
    lines = out.out.strip().splitlines()
    assert rc == 0, out.err[-2000:]
    assert all(ln.startswith("# ") for ln in lines[:-1])
    return json.loads(lines[-1]), out


run_cell.seed = 2**31 + 7      # more than 32 signed bits hold


# (cell, scheduler samples, p95 listed, open loop)
CELLS = [("tiny-live", True, True, False),
         ("tiny-adjacent", False, True, False),
         ("tiny-skipping", False, False, False),
         ("tiny-catchup", True, False, False),
         ("tiny-adjacent-open", False, True, True)]


@pytest.mark.parametrize("workload,sched,tail,open_loop", CELLS)
def test_untraced_line_holds_exactly_the_contract_keys(
        runner, capfd, workload, sched, tail, open_loop):
    res, out = run_cell(runner, capfd, workload, 0)
    assert set(res) == RESULT_KEYS
    assert set(res["device"]) == DEVICE_KEYS
    assert res["correct"] is True, out.err
    assert res["failed"] == 0 and res["attempted"] >= 2
    want = E2E | ({"request_ms.p95"} if tail else set())
    assert set(res["metrics"]) == want
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0


@pytest.mark.parametrize("workload,sched,tail,open_loop", CELLS)
def test_traced_line_reports_layers_and_no_device_metric_on_a_cpu(
        runner, capfd, workload, sched, tail, open_loop):
    res, out = run_cell(runner, capfd, workload, 1)
    assert set(res) == RESULT_KEYS        # no TPU plane: no breakdown
    assert set(res["device"]) == DEVICE_KEYS   # and no busy_s / window_s
    assert res["correct"] is True, out.err
    assert "holds no /device:TPU:" in out.out     # said, not made up
    if open_loop:
        # an open window's rows carry no launch records: no per-request
        # layer metric, and no device metric without a TPU plane
        assert res["metrics"] == {}
        assert res["failed"] == 0 and res["attempted"] >= 2
        assert "open loop: " in out.out
        return
    want = LAYERS_CPU | ({"sched.queue_wait_ms", "sched.lanes_per_launch"}
                         if sched else set())
    assert set(res["metrics"]) == want
    assert res["metrics"]["launch.count"]["value"] >= 1
