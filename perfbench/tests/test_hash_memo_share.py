"""The reader of `light.hash_memo_share` on hand-made spans (a share over
mixed `memo` attributes; None where no span carries one, as on the
parent's program) and end to end on the CPU in the three tiny light cells,
under a manifest of its own that lists it
(fixtures/manifest_hash_memo.json; the neighbours' manifests and
test_progspans.py are theirs and stay as they are)."""
import json
import os

import pytest

from perfbench import progspans
from perfbench.tests.conftest import FIXTURES
from perfbench.tests.test_progspans import (  # noqa: F401  (fixture)
    program, reader, rec, run_of)

METRIC = "light.hash_memo_share"


def hashed(ts_ms, memo=None):
    r = rec("valset.hash", ts_ms, 0.001 if memo else 4.0)
    r["attrs"] = {"n": 10000} if memo is None else {"n": 10000, "memo": memo}
    return r


def test_share_is_hits_over_hash_spans_a_request_median(program):
    # request i answers i of its 4 hashes from the memo; request 5 hashes
    # nothing, and another span's `memo` is not a hash
    def one(i, t):
        if i == 5:
            return [rec("commit.prefix", t + 1, 1)]
        other = rec("commit.match", t + 6, 1)
        other["attrs"] = {"memo": True}
        return [hashed(t + 1 + k, memo=k < i) for k in range(4)] + [other]
    run, records = run_of(6, one)
    program(records)
    # 0, 25, 50, 75, 100 % over five requests
    assert reader(METRIC).read(run) == pytest.approx(50.0)
    # the light client's request: 9 hashes, 4 of them first hashes
    nine = [False, True, False, False, True, True, True, False, True]
    run, records = run_of(4, lambda i, t: [
        hashed(t + 0.5 + k, memo=m) for k, m in enumerate(nine)])
    program(records)
    assert reader(METRIC).read(run) == pytest.approx(500.0 / 9)
    # every hash a hit is 100, none is 0 and still a reading
    for memo, want in ((True, 100.0), (False, 0.0)):
        run, records = run_of(3, lambda i, t: [hashed(t + 1, memo=memo)])
        program(records)
        assert reader(METRIC).read(run) == want


def test_reader_says_none_where_no_span_carries_memo(program):
    # the parent's program: `valset.hash` spans with `n` alone
    run, records = run_of(8, lambda i, t: [hashed(t + 1), hashed(t + 6)])
    program(records)
    assert reader("light.hash_ms").read(run) == pytest.approx(8.0)
    assert reader(METRIC).read(run) is None
    # too few requests that carry it, no record at all, an untraced run
    run, records = run_of(8, lambda i, t: [
        hashed(t + 1, memo=True) if i < 2 else hashed(t + 1)])
    program(records)
    assert progspans.MIN_REQUESTS == 3
    assert reader(METRIC).read(run) is None
    program([])
    assert reader(METRIC).read(run) is None
    program([hashed(1, memo=True)])
    assert reader(METRIC).read(
        {"spans": [], "requests": [{"wall_s": 0.01}] * 8}) is None


# ---------------------------------------------------------------------------
# end to end on the CPU: what each tiny light cell reads
# ---------------------------------------------------------------------------

# the rings hold their five sets, hashed in the warm lap: every hash of the
# window is a hit.  A client request of 128 heights where a skip reaches
# 66 hashes 4 times (the client's validate_basic of the target, one refused
# skip, two hops), twice a set it has hashed: blocks arrive decoded
CELLS = {"tiny-adjacent": 100.0, "tiny-skipping": 100.0, "tiny-client": 50.0}


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_traced_line_holds_the_share(runner, monkeypatch, capfd, workload):
    from tendermint_tpu.libs import trace

    monkeypatch.setattr(runner, "MANIFEST",
                        os.path.join(FIXTURES, "manifest_hash_memo.json"))
    # a traced window on a CPU holds a handful of requests (stopping the
    # profiler takes seconds there), fewer than a chip run is held to
    monkeypatch.setattr(progspans, "MIN_REQUESTS", 2)
    trace.enable(capacity=8192)     # a neighbour may have left it off
    trace.reset()
    try:
        rc = runner.main(["--workload", workload, "--seed",
                          str(2**31 + 2809 + sorted(CELLS).index(workload)),
                          "--seconds", "4", "--trace", "1"])
    finally:
        trace.disable()
        trace.reset()
    out = capfd.readouterr()
    assert rc == 0, out.err[-2000:]
    res = json.loads(out.out.strip().splitlines()[-1])
    assert res["correct"] is True, out.err
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert res["metrics"][METRIC]["unit"] == "%"
    assert m[METRIC] == CELLS[workload]
    # the span is still opened on every call: light.hash_ms stays in the line
    assert 0 < m["light.hash_ms"] <= m["entry.host_ms"]
