"""`val10k-client` rehearsed on the CPU: the runner end to end, both
--trace values, on a test-only cell of the `light_client` traffic kind (100
validators rotating 1 a block, requests 128 heights apart, a store pruned
to 8) listed by a manifest of
its own, fixtures/manifest_client.json; the neighbours' manifest.json and
test_run_cpu.py are theirs and stay as they are."""
import json
import os

import pytest

from perfbench import progspans
from perfbench.tests.conftest import FIXTURES
from perfbench.tests.test_run_cpu import DEVICE_KEYS, E2E, RESULT_KEYS

LAYERS = {"entry.host_ms", "launch.wall_ms", "launch.count",
          "launch.stage_ms", "light.hash_ms", "light.match_ms",
          "entry.collect_ms"}
NEW = {"client.store_write_ms", "client.store_read_ms",
       "client.refused_skip_ms", "client.verify_calls"}


@pytest.fixture
def client_runner(runner, monkeypatch):
    from tendermint_tpu.libs import trace

    monkeypatch.setattr(runner, "MANIFEST",
                        os.path.join(FIXTURES, "manifest_client.json"))
    # a traced window on a CPU holds a handful of requests (a launch of the
    # XLA kernel is 0.4 s there and stopping the profiler takes seconds),
    # fewer than a chip run is held to
    monkeypatch.setattr(progspans, "MIN_REQUESTS", 2)
    trace.enable(capacity=8192)     # a neighbour may have left it off
    trace.reset()
    yield runner
    trace.disable()
    trace.reset()


def run_cell(runner, capfd, trace, seed):
    rc = runner.main(["--workload", "tiny-client", "--seed", str(seed),
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


def test_untraced_line_holds_the_cells_end_to_end_metrics(client_runner,
                                                          capfd):
    res, _ = run_cell(client_runner, capfd, 0, 2**31 + 27)
    assert set(res["metrics"]) == E2E


def test_traced_line_holds_the_four_new_readers(client_runner, capfd):
    res, out = run_cell(client_runner, capfd, 1, 2**31 + 28)
    # launch.stage_cpu_ms is there where the host's thread CPU clock is
    # cheap to read (libs/trace), and nowhere else
    assert LAYERS | NEW <= set(res["metrics"]) \
        <= LAYERS | NEW | {"launch.stage_cpu_ms"}
    assert "holds no /device:TPU:" in out.out
    # a request of 128 heights where a skip reaches 66: one refused skip
    # and two hops, each hop two launches: the bisection really ran
    assert res["metrics"]["client.verify_calls"]["value"] == 3
    assert res["metrics"]["launch.count"]["value"] == 4
