"""The seven readers of ISSUE 35 on hand-built `run` dicts and records:
`entry.unspanned_ms`, `votes.add_ms`, `votes.screen_ms`, `batch.items_ms`,
`launch.head_ms`, `sched.window_wait_ms`, `route.outside_ms`.  Each reads
what its docstring says, gives None (and raises nothing) on a program
without the span, attribute, counter or function (the parent's), and is
found by the runner by its file's name and listed in the manifest BY NAME,
wherever in the list."""
import json
import os
import threading

import pytest

from perfbench import books, progspans
from perfbench.tests.test_progspans import (  # noqa: F401  (fixture)
    MS, program, reader, rec, run_of)

MAIN = threading.main_thread().ident
ALL = ["val150-live", "val10k-adjacent", "val10k-skipping",
       "val150-catchup", "val10k-client", "val100k-commit",
       "val10k-mixed-commit"]
WANT = {
    "entry.unspanned_ms": ("entry points", "program_span", ALL),
    "votes.add_ms": ("entry points", "program_counter", ["val150-live"]),
    "votes.screen_ms": ("entry points", "program_span", ["val150-live"]),
    "batch.items_ms": ("scheme lanes", "program_span",
                       ["val10k-mixed-commit"]),
    "launch.head_ms": ("route ladder", "program_span",
                       ["val100k-commit", "val10k-adjacent",
                        "val10k-skipping", "val10k-client"]),
    "sched.window_wait_ms": ("scheduler", "program_span",
                             ["val150-live", "val150-catchup"]),
    "route.outside_ms": ("route ladder", "program_span",
                         ["val150-live", "val150-catchup"]),
}


def on(tid, r, **attrs):
    r["tid"] = tid
    r["attrs"] = attrs
    return r


def votes(ts_ms, wall_ms):
    r = on(MAIN, rec("votes", ts_ms), calls=0, wall_ns=int(wall_ms * MS))
    r["ph"] = "C"
    return r


def live_request(i, t, screen=True, counter=True):
    """A `val150-live` request of 10 ms on the main thread: a pre-verify
    envelope [0, 4) holding a screen of 1 ms and a wait of 2.5, the adds
    (no span; the tally gains i + 2 ms), a collect of 1 ms and a
    device.collect of 2: 1.5 ms of nothing are left besides the adds."""
    recs = [on(MAIN, rec("consensus.preverify", t, 4.0)),
            on(MAIN, rec("sched.wait", t + 1.25, 2.5)),
            on(MAIN, rec("commit.collect", t + 6.5 + i, 1.0)),
            on(MAIN, rec("device.collect", t + 7.5 + i, 2.0)),
            # another thread's spans never enter the caller's books
            on(7, rec("sched.launch", t + 1.5, 2.0), queue_wait_ns=3 * MS)]
    if screen:
        recs.append(on(MAIN, rec("consensus.screen", t + 0.25, 1.0),
                       items=150))
    if counter:
        # cumulative: request i's adds take i + 2 ms
        recs.append(votes(t + 0.01, sum(k + 2 for k in range(i))))
    return recs


def test_unspanned_is_the_envelopes_and_nothing_less_the_tallys_wall(
        program):
    # the tally's cumulative wall reads 0 in an even request and 2 ms in
    # an odd one, so a request gains +2 or -2 ms by the reader's rule
    # (its first sample to the next request's first); the last has no next
    run, records = run_of(6, lambda i, t: live_request(i % 2, t))
    program(records)
    rows = books.requests(run)
    assert len(rows) == 6 and all(len(r[2]) == 7 for r in rows)
    assert books.votes_wall_gains(rows) == [2 * MS, -2 * MS, 2 * MS,
                                            -2 * MS, 2 * MS, None]
    # the envelope's own 4 - 1 - 2.5 = 0.5 and 10 - 4 - 3 = 3.0 of
    # nothing, less the gain: 1.5, 5.5, 1.5, 5.5, 1.5
    assert reader("entry.unspanned_ms").read(run) == pytest.approx(1.5)


def test_unspanned_and_add_ms_on_a_steady_window(program):
    # cumulative tally: every request's adds take 2.5 ms
    def one(i, t):
        recs = live_request(0, t, counter=False)
        return recs + [votes(t + 0.01, 2.5 * i), votes(t + 4.5, 2.5 * i + 1)]
    run, records = run_of(8, one)
    program(records)
    assert reader("votes.add_ms").read(run) == pytest.approx(2.5)
    assert reader("entry.unspanned_ms").read(run) == pytest.approx(
        0.5 + 3.0 - 2.5)
    assert reader("votes.screen_ms").read(run) == pytest.approx(1.0)
    assert reader("sched.window_wait_ms").read(run) == pytest.approx(3.0)


def test_a_cell_without_the_counter_subtracts_nothing(program):
    # a light request: the root is an envelope, the collect and the wait
    # are named; 10 - 9 of nothing + 9 - 2 - 5 of the root's own
    run, records = run_of(5, lambda i, t: [
        on(MAIN, rec("light.verify", t, 9.0)),
        on(MAIN, rec("commit.collect", t + 1, 2.0)),
        on(MAIN, rec("device.collect", t + 3.5, 5.0)),
        on(9, rec("device.launch", t + 3.6, 4.8), queued_ns=1000)])
    program(records)
    assert reader("entry.unspanned_ms").read(run) == pytest.approx(3.0)
    assert reader("votes.add_ms").read(run) is None
    assert reader("votes.screen_ms").read(run) is None
    assert reader("sched.window_wait_ms").read(run) is None


def test_a_program_without_the_function_reads_none(program, monkeypatch):
    from tendermint_tpu.libs import trace

    run, records = run_of(5, lambda i, t: live_request(0, t))
    program(records)
    assert reader("entry.unspanned_ms").read(run) is not None
    monkeypatch.delattr(trace, "unnamed_ns")
    assert reader("entry.unspanned_ms").read(run) is None


@pytest.mark.parametrize("metric", sorted(WANT))
def test_nothing_to_read_is_none_not_an_error(program, metric):
    read = reader(metric).read
    # the parent's program: the same requests, none of the new records
    run, records = run_of(8, lambda i, t: [
        on(MAIN, rec("consensus.preverify", t, 4.0)),
        on(7, rec("sched.launch", t + 1.5, 2.0)),
        on(8, rec("ops.ed25519.verify_batch", t + 1.6, 1.8)),
        on(8, rec("batch.verify", t + 5, 2))])
    run["requests"] = [{"wall_s": 0.01, "records": [
        {"path": "pallas-split", "wall_s": 0.004}]}] * 8
    program(records)
    if metric != "entry.unspanned_ms":  # (it needs only the function)
        assert read(run) is None
    # no record at all, an untraced run, too few requests
    program([])
    assert read(run) is None
    program(records)
    assert read({"spans": [], "requests": [{"wall_s": 0.01}] * 8}) is None
    run2, records2 = run_of(2, lambda i, t: live_request(0, t))
    program(records2)
    assert progspans.MIN_REQUESTS == 3
    if metric != "launch.head_ms":      # (it reads `run`, not the records)
        run2["requests"] = []
        assert read(run2) is None


def test_batch_items_sums_the_requests_spans(program):
    run, records = run_of(4, lambda i, t: [
        on(MAIN, rec("batch.items", t + 1, 2.0 + i), n=9900),
        on(MAIN, rec("batch.verify", t + 4, 5.0))])
    program(records)
    assert reader("batch.items_ms").read(run) == pytest.approx(3.5)


def test_launch_head_sums_the_records_head_s():
    read = reader("launch.head_ms").read
    run = {"requests": [
        {"records": [{"path": "pallas", "wall_s": 0.013},
                     {"path": "pallas-split", "wall_s": 0.028,
                      "head_s": 0.004 + 0.001 * i, "pub_rows_s": 0.001}]}
        for i in range(5)]}
    assert read(run) == pytest.approx(6.0)
    # the parent's records carry no such key; an untraced run no records
    assert read({"requests": [{"records": [{"wall_s": 0.02}]}] * 5}) is None
    assert read({"requests": [{"wall_s": 0.02}] * 5}) is None


def test_window_wait_sums_every_window_of_a_request(program):
    # a catch-up window: 18 launches a request, each says its own wait
    run, records = run_of(3, lambda i, t: [
        on(7, rec("sched.launch", t + 0.5 * k, 0.4),
           queue_wait_ns=int((0.1 * (i + 1)) * MS), exec_wait_ns=5)
        for k in range(18)])
    program(records)
    assert reader("sched.window_wait_ms").read(run) == pytest.approx(3.6)


def test_outside_is_the_span_less_its_bracket_less_its_resolve(program):
    def one(i, t):
        out = []
        for k in range(3):
            sid = 1000 * (i + 1) + k
            out += [on(8, rec("ops.ed25519.verify_batch", t + 3 * k, 2.5,
                              id_=sid), bracket_ns=int(1.5 * MS)),
                    rec("comb.resolve", t + 3 * k + 0.1, 0.25, parent=sid)]
        # a span of another request's tree, and one without the attribute
        out.append(rec("comb.resolve", t + 9.5, 0.2, parent=-1))
        return out
    run, records = run_of(4, one)
    program(records)
    # 3 x (2.5 - 1.5 - 0.25)
    assert reader("route.outside_ms").read(run) == pytest.approx(2.25)
    assert reader("route.resolve_ms").read(run) == pytest.approx(0.95)


@pytest.mark.parametrize("metric", sorted(WANT))
def test_the_runner_finds_the_reader_and_the_manifest_lists_it_by_name(
        metric):
    from perfbench import run as runner

    assert callable(runner.load_reader("layers", metric))
    with open(os.path.join(runner.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == metric]
    layer, source, cells = WANT[metric]
    assert entry == {"name": metric, "unit": "ms", "better": "lower",
                     "source": source, "layer": layer,
                     "moves": "request_ms.p50", "workloads": cells}
    # appended: nothing the manifest had before them moved
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.index(metric) > names.index("entry.columns_ms")
    cell = runner.load_cell(os.path.join(runner.ROOT, "BENCHMARK.json"),
                            cells[0])
    assert metric in [m["name"] for m in cell["per_layer"]]


# ---------------------------------------------------------------------------
# end to end on the CPU: the tiny cells under a manifest that lists the new
# metrics (fixtures/manifest_unspanned.json; the neighbours' stay theirs)
# ---------------------------------------------------------------------------

TINY = {
    "tiny-live": {"entry.unspanned_ms", "votes.add_ms", "votes.screen_ms",
                  "sched.window_wait_ms", "route.outside_ms"},
    "tiny-adjacent": {"entry.unspanned_ms"},
    "tiny-catchup": {"entry.unspanned_ms", "sched.window_wait_ms",
                     "route.outside_ms"},
}


@pytest.mark.parametrize("workload", sorted(TINY))
def test_the_traced_line_of_a_tiny_cell_holds_the_new_metrics(
        runner, capfd, monkeypatch, workload):
    from tendermint_tpu.libs import trace

    monkeypatch.setattr(runner, "MANIFEST", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "fixtures",
        "manifest_unspanned.json"))
    monkeypatch.setattr(progspans, "MIN_REQUESTS", 2)
    trace.enable(capacity=8192)
    trace.reset()
    try:
        rc = runner.main(["--workload", workload, "--seed",
                          str(2**31 + 3511 + sorted(TINY).index(workload)),
                          "--seconds", "4", "--trace", "1"])
    finally:
        trace.disable()
        trace.reset()
    out = capfd.readouterr()
    assert rc == 0, out.err[-2000:]
    res = json.loads(out.out.strip().splitlines()[-1])
    assert res["correct"] is True, out.err
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert TINY[workload] <= set(m), sorted(m)
    # (a CPU's walls are no result; only relations)  The unnamed
    # remainder is a part of the host's share, never more
    assert 0 <= m["entry.unspanned_ms"] <= m["entry.host_ms"]
    if workload == "tiny-live":
        assert m["votes.add_ms"] > 0 and m["votes.screen_ms"] > 0
