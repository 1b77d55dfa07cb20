"""perfbench/progspans.py and the readers built on it: the assignment of
program spans to requests on hand-made rows, what a wrapped ring excludes,
None on too little, and the nine readers end to end on the CPU against a
manifest of the tiny cells that lists them (fixtures/manifest_progspans.json;
the neighbours' manifest.json is theirs and stays as it is)."""
import importlib.util
import json
import os

import pytest

from perfbench import progspans

MS = 1_000_000      # ns
LAYERS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "layers")
NEW = ["light.hash_ms", "light.match_ms", "entry.collect_ms",
       "sched.handoff_ms", "apply.busy_ms", "apply.verify_wait_ms",
       "storage.commit_ms", "storage.drain_wait_ms", "launch.stage_cpu_ms"]


def reader(metric):
    spec = importlib.util.spec_from_file_location(
        "reader_" + metric.replace(".", "_"),
        os.path.join(LAYERS, metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rec(name, ts_ms, dur_ms=0.0, parent=None, id_=None):
    rec.n += 1
    return {"seq": rec.n, "name": name, "ph": "X" if dur_ms else "i",
            "ts_ns": ts_ms * MS, "dur_ns": dur_ms * MS, "cpu_ns": 0,
            "tid": 1, "tname": "t", "id": id_ or rec.n, "parent": parent,
            "attrs": {}}


rec.n = 0


def requests(n, wall_ms=10, gap_ms=2):
    return [(i * (wall_ms + gap_ms) * MS,
             (i * (wall_ms + gap_ms) + wall_ms) * MS) for i in range(n)]


def test_a_span_belongs_to_the_request_that_holds_its_start():
    reqs = requests(3)          # [0, 10), [12, 22), [24, 34) ms
    records = [
        rec("valset.hash", 1, 2),
        rec("pipeline.commit", 9, 5),   # began in request 0, ends in the gap
        rec("between", 10.5, 1),        # in no request
        rec("valset.hash", 12, 1),      # on the edge: request 1's
        rec("valset.hash", 33.9, 3),
    ]
    got = progspans.assign(reqs, records, wrapped=False)
    assert [[r["name"] for r in recs] for recs in got] == [
        ["valset.hash", "pipeline.commit"], ["valset.hash"],
        ["valset.hash"]]
    # the order of the request rows does not matter
    assert progspans.assign(reqs[::-1], records, wrapped=False) == got
    assert progspans.assign([], records, False) == []
    assert progspans.assign(reqs, [], False) == []


def test_a_wrapped_ring_keeps_only_requests_it_holds_whole():
    reqs = requests(4)          # starts at 0, 12, 24, 36 ms
    # the oldest record the ring still holds began at 11 ms and ENDED at
    # 13 ms: a span of request 1 that ended before 13 ms may be gone, so
    # request 1 (began at 12) is out, requests 2 and 3 are whole
    records = [rec("commit.collect", 11, 2), rec("valset.hash", 13.5, 1),
               rec("valset.hash", 25, 1), rec("valset.hash", 37, 1)]
    whole = progspans.assign(reqs, records, wrapped=True)
    assert [[r["ts_ns"] // MS for r in recs] for recs in whole] == [[25],
                                                                    [37]]
    # the same rows from a ring that never dropped a span: all four
    assert len(progspans.assign(reqs, records, wrapped=False)) == 4


def run_of(n_requests, per_request):
    """A `run` whose request rows are n_requests intervals, and the records
    per_request(i, start_ms) makes for each."""
    reqs = requests(n_requests)
    spans = [("pb.request", t0 / 1e9, t1 / 1e9) for t0, t1 in reqs]
    spans.insert(1, ("pb.light.verify", 0.0, 1.0))
    records = [r for i, (t0, _) in enumerate(reqs)
               for r in per_request(i, t0 / MS)]
    return {"spans": spans, "requests": []}, records


@pytest.fixture
def program(monkeypatch):
    """Hand-made records in place of the program's recorder."""
    def put(records, wrapped=False):
        monkeypatch.setattr(progspans, "program_records",
                            lambda: (records, wrapped))
    return put


def test_sum_is_the_median_over_requests_that_carry_the_span(program):
    # request i holds two hashes of (i + 1) ms each; requests 4 and 5 none
    run, records = run_of(6, lambda i, t: [
        rec("valset.hash", t + 1, i + 1), rec("valset.hash", t + 5, i + 1),
        rec("commit.prefix", t + 2, 100)] if i < 4 else [])
    program(records)
    # sums 2, 4, 6, 8 ms over 4 requests: median 5
    assert progspans.sum_ms(run, "valset.hash") == pytest.approx(5.0)
    assert progspans.sum_ms(run, "valset.hash", "commit.prefix") == \
        pytest.approx(105.0)
    assert progspans.sum_ms(run, "commit.match") is None
    # two carrying requests are too few
    program([r for r in records if r["ts_ns"] >= 24 * MS])
    assert progspans.MIN_REQUESTS == 3
    assert progspans.sum_ms(run, "valset.hash") is None


def test_handoff_pairs_a_submit_with_its_submitters_resolve(program):
    handoff = reader("sched.handoff_ms")

    def one(i, t):
        # two pre-verifies under spans 100+ and 200+ (their ids), then an
        # unparented submission; launches of 3 ms inside each
        a, b = 100 + i, 200 + i
        return [
            rec("sched.submit", t + 0.0, parent=a),
            rec("sched.launch", t + 2.5, 3.0),
            rec("sched.resolve", t + 6.0, parent=a),       # 6 - 3 = 3
            rec("sched.submit", t + 6.5, parent=b),
            rec("sched.resolve", t + 9.5, parent=b),       # 3, no launch
            rec("sched.submit", t + 9.6, parent=None),     # never resolved
        ]
    run, records = run_of(9, one)
    program(records)
    assert handoff.read(run) == pytest.approx(6.0)
    assert handoff.handoff_ns([rec("sched.submit", 1.0)]) is None
    # a launch that overhangs the resolve is clipped to the interval
    recs = [rec("sched.submit", 0.0, parent=7),
            rec("sched.launch", 1.0, 10.0),
            rec("sched.resolve", 4.0, parent=7)]
    assert handoff.handoff_ns(recs) == pytest.approx(1.0 * MS)


def test_stage_cpu_reads_the_launch_records_and_is_absent_without_the_key():
    stage_cpu = reader("launch.stage_cpu_ms")
    rows = [{"records": [{"stage_s": 0.004, "stage_cpu_s": 0.001 * (i + 1)},
                         {"stage_s": 0.004, "stage_cpu_s": 0.001}]}
            for i in range(3)]
    # 2, 3 and 4 ms: the mean (the clock ticks in 10 ms on the chip's host)
    assert stage_cpu.read({"requests": rows}) == pytest.approx(3.0)
    parent = [{"records": [{"stage_s": 0.004}]} for _ in range(3)]
    assert stage_cpu.read({"requests": parent}) is None
    assert stage_cpu.read({"requests": [{"wall_s": 1.0}]}) is None


@pytest.mark.parametrize("metric", NEW)
def test_a_reader_finds_nothing_to_read_and_says_none(metric, program):
    """The parent commit's program: recorder off, records without the new
    key.  And an untraced run: no request rows at all."""
    program([])
    run, _ = run_of(12, lambda i, t: [])
    run["requests"] = [{"records": [{"stage_s": 0.001, "wall_s": 0.002}]}
                       for _ in range(12)]
    assert reader(metric).read(run) is None
    untraced = {"spans": [], "requests": [{"wall_s": 0.01}] * 12}
    program([rec("valset.hash", 1, 1)])
    assert reader(metric).read(untraced) is None


def test_the_programs_own_recorder_is_what_by_request_reads():
    from tendermint_tpu.libs import trace

    trace.enable()
    trace.reset()
    try:
        import time
        rows = []
        for _ in range(3):
            t0 = time.perf_counter()
            with trace.span("valset.hash", n=1):
                pass
            rows.append(("pb.request", t0, time.perf_counter()))
        got = progspans.by_request({"spans": rows})
    finally:
        trace.disable()
        trace.reset()
    assert [[r["name"] for r in recs] for recs in got] == \
        [["valset.hash"]] * 3


# ---------------------------------------------------------------------------
# end to end on the CPU: the tiny cells under a manifest that lists the new
# metrics
# ---------------------------------------------------------------------------

CELLS = {
    "tiny-live": {"entry.collect_ms", "sched.handoff_ms",
                  "launch.stage_cpu_ms"},
    "tiny-adjacent": {"light.hash_ms", "entry.collect_ms",
                      "launch.stage_cpu_ms"},
    "tiny-skipping": {"light.hash_ms", "light.match_ms", "entry.collect_ms",
                      "launch.stage_cpu_ms"},
    "tiny-catchup": {"apply.busy_ms", "apply.verify_wait_ms",
                     "storage.commit_ms", "storage.drain_wait_ms",
                     "launch.stage_cpu_ms"},
}


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_traced_line_holds_the_cells_new_metrics(runner, monkeypatch, capfd,
                                                 workload):
    from tendermint_tpu.libs import trace

    monkeypatch.setattr(runner, "MANIFEST", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "fixtures",
        "manifest_progspans.json"))
    # a traced window on a CPU holds a handful of requests (stopping the
    # profiler takes seconds there), fewer than a chip run is held to
    monkeypatch.setattr(progspans, "MIN_REQUESTS", 2)
    trace.enable(capacity=8192)     # a neighbour may have left it off
    trace.reset()
    try:
        rc = runner.main(["--workload", workload, "--seed",
                          str(2**31 + 1009 + sorted(CELLS).index(workload)),
                          "--seconds", "4", "--trace", "1"])
    finally:
        trace.disable()
        trace.reset()
    out = capfd.readouterr()
    assert rc == 0, out.err[-2000:]
    res = json.loads(out.out.strip().splitlines()[-1])
    assert res["correct"] is True, out.err
    got = {k: v["value"] for k, v in res["metrics"].items() if k in NEW}
    assert set(got) == CELLS[workload], got
    assert all(v > 0 for v in got.values()), got
    m = {k: v["value"] for k, v in res["metrics"].items()}
    if workload.startswith("tiny-a") or workload.startswith("tiny-s"):
        parts = sum(got[k] for k in ("light.hash_ms", "light.match_ms",
                                     "entry.collect_ms") if k in got)
        assert parts <= m["entry.host_ms"]
