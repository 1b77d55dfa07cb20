"""Closing the books on a request (docs/adr/adr-011-flight-recorder.md,
"No wall without a name"): every nanosecond of a thread inside an
interval is either inside a span that names work or a wait, or it is
counted as unnamed; a loop with no span a turn is read through a counter;
and a request that takes many times its usual is kept, not overwritten.

The reductions are pure functions over snapshot records, so most of this
is hand-built records; the counter, the incident and the collector's
pause run the program.
"""
from __future__ import annotations

import gc
import json
import threading
import timeit
import urllib.request

import pytest

from helpers import Node, make_genesis
from tendermint_tpu.consensus.round_types import VoteMessage
from tendermint_tpu.crypto import scheduler as vsched
from tendermint_tpu.libs import fail, trace
from tendermint_tpu.libs.trace import Tracer
from tendermint_tpu.types import vote_set
from tendermint_tpu.types.basic import (BlockID, PartSetHeader,
                                        SignedMsgType, Timestamp)
from tendermint_tpu.types.vote import Vote
from tendermint_tpu.types.vote_set import VoteSet

MS = 1_000_000


@pytest.fixture
def recorder():
    trace.enable()
    trace.reset()
    yield trace
    trace.disable()
    trace.reset()


_ids = iter(range(1, 1 << 30))


def rec(name, t0_ms, t1_ms, tid=1, ph="X", **attrs):
    """One snapshot record, in milliseconds on a made-up clock."""
    i = next(_ids)
    return {"seq": i, "id": i, "parent": None, "name": name, "ph": ph,
            "ts_ns": int(t0_ms * MS), "dur_ns": int((t1_ms - t0_ms) * MS),
            "cpu_ns": None, "tid": tid, "tname": f"t{tid}", "attrs": attrs}


# ---------------------------------------------------------------------------
# self time and the unnamed remainder, on hand-built records
# ---------------------------------------------------------------------------

def _nesting():
    # an envelope 0-100 holding work 10-40 (itself holding 20-30) and a
    # wait 50-90: 30 ms of the envelope's own, 20 + 10 + 40 named
    recs = [rec("light.verify", 0, 100), rec("commit.collect", 10, 40),
            rec("commit.columns", 20, 30), rec("device.collect", 50, 90)]
    want_self = {"light.verify": 30, "commit.collect": 20,
                 "commit.columns": 10, "device.collect": 40}
    return recs, want_self, (1, 0, 100), 30


def _two_threads():
    # the caller waits 10-90 while a worker's envelope runs 12-88 with a
    # kernel dispatch 20-80 inside: each thread's books are its own
    recs = [rec("batch.verify", 0, 100), rec("device.collect", 10, 90),
            rec("device.launch", 12, 88, tid=2),
            rec("secp.stage", 20, 80, tid=2)]
    want_self = {"batch.verify": 20, "device.collect": 80,
                 "device.launch": 16, "secp.stage": 60}
    return recs, want_self, (2, 0, 100), 40  # 12 + 12 idle, 16 enveloped


def _envelope_in_envelope():
    # sched.launch > device.collect on one thread; on the lane's thread
    # device.launch > ops.ed25519.verify_batch > comb.resolve: both
    # envelopes' self times are unnamed, the resolve is not
    recs = [rec("device.launch", 0, 50, tid=3),
            rec("ops.ed25519.verify_batch", 5, 45, tid=3),
            rec("comb.resolve", 10, 15, tid=3)]
    want_self = {"device.launch": 10, "ops.ed25519.verify_batch": 35,
                 "comb.resolve": 5}
    return recs, want_self, (3, 0, 50), 45


def _straddling():
    # a span that began before the interval and one that ends after it
    # are clipped to it: [100, 200) holds 20 of the first, 30 of the
    # second and 50 of nothing
    recs = [rec("pipeline.apply", 60, 120), rec("pipeline.apply", 170, 260)]
    want_self = {"pipeline.apply": 60 + 90}
    return recs, want_self, (1, 100, 200), 50


def _instants_and_counters_cover_nothing():
    recs = [rec("consensus.preverify", 0, 30),
            rec("sched.submit", 5, 5, ph="i"),
            rec("votes", 1, 1, ph="C", calls=7),
            rec("sched.wait", 6, 28)]
    want_self = {"consensus.preverify": 8, "sched.wait": 22}
    return recs, want_self, (1, 0, 40), 18  # 8 enveloped + 10 of nothing


CASES = [_nesting, _two_threads, _envelope_in_envelope, _straddling,
         _instants_and_counters_cover_nothing]


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__.strip("_"))
def test_self_times_are_the_duration_less_same_thread_children(case):
    recs, want_self, _, _ = case()
    selfs = trace.self_times(recs)
    got = {}
    for r in recs:
        if r["ph"] == "X":
            got[r["name"]] = got.get(r["name"], 0) + selfs[r["id"]]
    assert got == {k: v * MS for k, v in want_self.items()}
    # nothing is counted twice: a thread's self times add up to the union
    # of its spans
    assert sum(selfs.values()) == sum(
        b - a for tid in {r["tid"] for r in recs}
        for a, b, inner in trace._segments(
            recs, tid, 0, 10_000 * MS) if inner is not None)


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__.strip("_"))
def test_unnamed_is_what_an_envelope_or_nothing_covers(case):
    recs, _, (tid, t0, t1), want = case()
    assert trace.unnamed_ns(recs, tid, t0 * MS, t1 * MS) == want * MS
    # named + unnamed = the interval, to the nanosecond
    named = sum(b - a for a, b, inner in trace._segments(
        recs, tid, t0 * MS, t1 * MS)
        if inner is not None and inner["name"] not in trace.ENVELOPES)
    assert named + want * MS == (t1 - t0) * MS


def test_every_envelope_and_kept_span_is_a_registered_name():
    assert trace.ENVELOPES <= trace.KNOWN_SPANS
    assert trace.KEPT_SPANS <= trace.KNOWN_SPANS
    # a named wait is never an envelope: its self time is the wait
    assert not trace.ENVELOPES & {"device.collect", "pipeline.wait_staged",
                                  "pipeline.drain", "sched.wait"}
    assert "consensus.vote" not in trace.KNOWN_SPANS


def test_rollup_counts_totals_self_times_and_the_newest_counter():
    recs, want_self, _, _ = _two_threads()
    recs += [rec("votes", 1, 1, ph="C", calls=3),
             rec("votes", 99, 99, ph="C", calls=303)]
    doc = trace.rollup(recs)
    assert doc["spans"]["device.launch"] == {
        "count": 1, "total_ns": 76 * MS, "self_ns": 16 * MS}
    assert {t["tname"]: t["unnamed_ns"] for t in doc["threads"]} == \
        {"t1": 20 * MS, "t2": 16 * MS}
    assert doc["counters"] == {"votes": {"calls": 303}}
    json.dumps(doc)


# ---------------------------------------------------------------------------
# the counter record
# ---------------------------------------------------------------------------

def test_a_counter_is_one_record_and_a_chrome_counter_event():
    tr = Tracer(capacity=16, enabled=True)
    with tr.span("consensus.preverify"):
        tr.counter("votes", calls=300, wall_ns=5)
    (c, _) = tr.snapshot()
    assert (c["ph"], c["name"], c["dur_ns"]) == ("C", "votes", 0)
    assert c["attrs"] == {"calls": 300, "wall_ns": 5}
    ev = tr.chrome_trace()["traceEvents"][0]
    assert ev["ph"] == "C" and ev["args"] == {"calls": 300, "wall_ns": 5}
    tr.disable()
    tr.counter("votes", calls=301)
    assert len(tr.snapshot()) == 2


def test_the_disabled_path_stays_under_a_microsecond():
    """tests/test_trace.py's guard, extended to the counter."""
    trace.disable()
    n = 20000
    sites = {
        "span": lambda: trace.span("overhead.probe", n=64).__enter__(),
        "instant": lambda: trace.instant("overhead.instant", height=7),
        "counter": lambda: trace.counter("overhead.counter", calls=1,
                                         wall_ns=2),
    }
    for name, site in sites.items():
        per_call = min(timeit.repeat(site, number=n, repeat=5)) / n
        assert per_call < 1e-6, f"disabled {name}: {per_call * 1e9:.0f} ns"


def _signed_votes(gdoc, privs, vals, vtype, height, bid):
    by_addr = {p.pub_key().address(): p for p in privs}
    votes = []
    for idx, val in enumerate(vals.validators):
        v = Vote(type=vtype, height=height, round=0, block_id=bid,
                 timestamp=Timestamp(1700000100, idx),
                 validator_address=val.address, validator_index=idx)
        v.signature = by_addr[val.address].sign(v.sign_bytes(gdoc.chain_id))
        votes.append(v)
    return votes


def test_a_height_of_150_validators_is_300_counted_adds_in_40_records(
        recorder):
    """The benchmark's `val150-live` request: 150 prevotes and 150
    precommits, each burst through _preverify_votes (the scheduler) and
    then add_vote one by one, then the commit.  The `votes` counter,
    sampled at each _preverify_votes, and the tally it samples say 300
    adds, every one a SigCache hit (the harness's own check), with no
    record a vote."""
    gdoc, privs = make_genesis(150)
    cs = Node(gdoc, privs[0]).cs
    vals, height = cs.state.validators, cs.rs.height
    # (a block id of this test's own: the SigCache is the process's, and
    # tests/test_trace_boundaries.py signs the same validators' votes)
    bid = BlockID(hash=bytes([0x35] * 32),
                  part_set_header=PartSetHeader(1, bytes([0x36] * 32)))
    bursts = [_signed_votes(gdoc, privs, vals, vtype, height, bid)
              for vtype in (SignedMsgType.PREVOTE, SignedMsgType.PRECOMMIT)]
    sched = vsched.install(vsched.VerifyScheduler(window_s=0.002))
    sched.start()
    try:
        recorder.reset()
        for votes in bursts:
            cs._preverify_votes([(VoteMessage(v), "peer") for v in votes])
            vs = VoteSet(gdoc.chain_id, height, 0, votes[0].type, vals)
            for v in votes:
                assert vs.add_vote(v)
        commit = vs.make_commit()
        vals.verify_commit(gdoc.chain_id, commit.block_id, height, commit)
    finally:
        sched.stop()
        vsched.uninstall(sched)
    spans = recorder.snapshot()
    assert len(spans) <= 40, sorted(r["name"] for r in spans)
    first, second = [r for r in spans if r["ph"] == "C"]
    assert first["name"] == second["name"] == "votes"
    gained = {k: vote_set.TALLY.sample()[k] - first["attrs"][k]
              for k in first["attrs"]}
    assert gained["wall_ns"] > 0
    del gained["wall_ns"]
    assert gained == {"calls": 300, "cache_hits": 300, "host_verifies": 0,
                      "refused": 0}
    assert second["attrs"]["calls"] - first["attrs"]["calls"] == 150
    by_name = {}
    for r in spans:
        by_name.setdefault(r["name"], []).append(r)
    # one screen a drained batch, inside the pre-verify, and the
    # submitter's wait is a span of its own on the submitter's thread
    pre = by_name["consensus.preverify"]
    assert [r["attrs"]["items"] for r in by_name["consensus.screen"]] == \
        [150, 150]
    assert [r["parent"] for r in by_name["consensus.screen"]] == \
        [r["id"] for r in pre]
    # (verify_commit's own submission, COMMIT class, waits likewise)
    waits = by_name["sched.wait"]
    assert [(r["attrs"]["n"], r["attrs"]["priority"], r["tid"])
            for r in waits][:2] == [(150, "consensus", pre[0]["tid"])] * 2
    assert [r["parent"] for r in waits[:2]] == [r["id"] for r in pre]
    # every window says what its oldest submission waited for
    for launch in by_name["sched.launch"]:
        assert launch["attrs"]["queue_wait_ns"] >= 0
        assert launch["attrs"]["exec_wait_ns"] >= 0
    # the caller's books: with the screen and the wait named, what is
    # left of a pre-verify is a small part of it
    tid = pre[0]["tid"]
    for p in pre:
        t0, t1 = p["ts_ns"], p["ts_ns"] + p["dur_ns"]
        assert trace.unnamed_ns(spans, tid, t0, t1) < p["dur_ns"] // 2


def test_a_refused_vote_and_a_host_verified_one_are_counted(recorder):
    gdoc, privs = make_genesis(4)
    cs = Node(gdoc, privs[0]).cs
    vals, height = cs.state.validators, cs.rs.height
    bid = BlockID(hash=bytes([7] * 32),
                  part_set_header=PartSetHeader(1, bytes([8] * 32)))
    votes = _signed_votes(gdoc, privs, vals, SignedMsgType.PREVOTE, height,
                          bid)
    votes[1].signature = bytes(64)
    before = vote_set.TALLY.sample()
    vs = VoteSet(gdoc.chain_id, height, 0, SignedMsgType.PREVOTE, vals)
    for v in votes:
        try:
            vs.add_vote(v)
        except vote_set.VoteSetError:
            pass
    after = vote_set.TALLY.sample()
    gained = {k: after[k] - before[k] for k in after if k != "wall_ns"}
    assert gained == {"calls": 4, "cache_hits": 0, "host_verifies": 4,
                      "refused": 1}
    assert recorder.snapshot() == []  # counted, never recorded


# ---------------------------------------------------------------------------
# kept, not overwritten
# ---------------------------------------------------------------------------

def _timed(tr, name, t0_ms, t1_ms, tid=1, **attrs):
    tr._record(name, "X", int(t0_ms * MS), int((t1_ms - t0_ms) * MS), None,
               tid, f"t{tid}", next(tr._ids), None, attrs)


def test_the_usual_is_a_running_median_and_warm_up_is_no_incident():
    tr = Tracer(capacity=64, enabled=True)
    # three compiles among the first sixteen: none is judged, and the
    # usual they leave behind is the ordinary one
    for k in range(16):
        _timed(tr, "light.verify", 100 * k, 100 * k + (40_000 if k < 3
                                                        else 30))
    assert tr.incidents() == []
    assert tr._usual["light.verify"][1] == 30 * MS
    # seven times the usual is slow, not a stall
    _timed(tr, "light.verify", 50_000, 50_000 + 7 * 30)
    assert tr.incidents() == []
    # a name that is not kept is never judged
    for k in range(40):
        _timed(tr, "commit.collect", 60_000 + k, 60_000 + k + (500 if k == 39
                                                               else 0.1))
    assert tr.incidents() == []
    # the usual follows a drift (a 16th a step), so a slower regime is
    # the new usual and not an incident a request
    for k in range(200):
        _timed(tr, "light.verify", 70_000 + 100 * k, 70_000 + 100 * k + 60)
    assert tr.incidents() == []
    assert 50 * MS < tr._usual["light.verify"][1] < 70 * MS


def test_a_stall_is_kept_with_every_threads_records_and_its_largest_gap():
    tr = Tracer(capacity=64, enabled=True)
    for k in range(20):
        _timed(tr, "device.collect", 10 * k, 10 * k + 2)
    # the stall: the lane's envelope holds 3 s before the dispatch opens
    _timed(tr, "ops.ed25519.verify_batch", 4_010, 4_030, tid=2)
    _timed(tr, "device.launch", 1_001, 4_031, tid=2, queued_ns=1000)
    _timed(tr, "device.collect", 1_000, 4_032)
    (inc,) = tr.incidents()
    assert (inc["name"], inc["dur_ns"]) == ("device.collect", 3_032 * MS)
    # (the running median hovers within a step, a 16th, of the true one)
    assert inc["usual_ns"] == pytest.approx(2 * MS, rel=0.07)
    assert [r["name"] for r in inc["records"]] == [
        "ops.ed25519.verify_batch", "device.launch", "device.collect"]
    gap = inc["gap"]
    assert (gap["inside"], gap["tname"], gap["dur_ns"]) == \
        ("device.launch", "t2", 3_009 * MS)
    assert (gap["before"], gap["after"]) == \
        (None, "ops.ed25519.verify_batch")
    assert inc["unnamed_ns"] == {"t1": 0, "t2": (3_009 + 1 + 20 + 2) * MS}
    # the span around it is the same stall, not a second incident
    for k in range(20):
        _timed(tr, "light.verify", 5_000 + 10 * k, 5_000 + 10 * k + 3)
    _timed(tr, "device.collect", 6_000, 6_003)
    _timed(tr, "light.verify", 5_990, 9_100)  # no stall of its own inside
    assert len(tr.incidents()) == 2
    _timed(tr, "device.collect", 10_000, 13_000)
    _timed(tr, "light.verify", 9_999, 13_002)
    assert [i["name"] for i in tr.incidents()] == [
        "device.collect", "light.verify", "device.collect"]
    assert tr.incidents()[-1]["within"] == ["light.verify"]
    assert tr.incident_count() == 3
    # the ring's wrap does not touch them, and the FIFO holds eight
    for k in range(200):
        _timed(tr, "commit.collect", 20_000 + k, 20_000 + k + 0.5)
    assert len(tr.snapshot()) == 64 and tr.dropped() > 0
    assert [len(i["records"]) for i in tr.incidents()] == [3, 2, 1]
    for k in range(10):
        _timed(tr, "device.collect", 30_000 + 100 * k, 30_000 + 100 * k + 50)
    assert len(tr.incidents()) == trace.INCIDENT_KEEP
    assert tr.incident_count() == 13


def test_eight_threads_recording_at_once_lose_no_record_and_no_incident():
    """More recording threads than cores, a shortened switch interval:
    the sequence, the drop count, the usual and the incident count are all
    behind the tracer's one lock, so none loses an update."""
    import sys

    tr = Tracer(capacity=256, enabled=True)
    threads, each, slow_every = 8, 2000, 500

    def work(j):
        base = j * 10_000_000
        for k in range(each):
            slow = k % slow_every == slow_every - 1
            _timed(tr, "device.collect", base + 2_000 * k,
                   base + 2_000 * k + (1_000 if slow else 2), tid=j + 1)
            if k % 97 == 0:
                tr._gc_pause((base + 2_000 * k + 1) * MS, 2 * MS,
                             {"generation": 0, "collected": 1})
                tr.snapshot()

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        ts = [threading.Thread(target=work, args=(j,)) for j in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
    finally:
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in ts)
    pauses = threads * len(range(0, each, 97))
    total = threads * each + pauses
    assert tr.last_seq() == total
    assert tr.dropped() == total - 256
    assert tr.incident_count() == threads * (each // slow_every)
    assert len(tr.incidents()) == trace.INCIDENT_KEEP
    assert all(i["dur_ns"] == 1_000 * MS for i in tr.incidents())
    seqs = [r["seq"] for r in tr.snapshot()]
    assert seqs == sorted(seqs) and len(set(seqs)) == 256


@pytest.fixture
def _device_lane(monkeypatch):
    """tests/test_trace.py's: the device lane on the CPU mesh with a
    compile-proof launch deadline, the recorder and the runtime clean."""
    from tendermint_tpu.crypto import degrade

    monkeypatch.setenv("TM_TPU_FORCE_BATCH", "1")
    monkeypatch.delenv("TM_TPU_DISABLE_BATCH", raising=False)
    degrade.configure(degrade.DegradeConfig(launch_timeout_s=600.0))
    trace.disable()
    trace.reset()
    yield
    fail.clear()
    trace.disable()
    trace.reset()
    degrade.reset()


def _batch_of_40():
    from tendermint_tpu.crypto import batch as cb
    from tendermint_tpu.crypto import ed25519 as edkeys

    privs = [edkeys.PrivKey(bytes([i + 1]) * 32) for i in range(40)]
    msgs = [b"books vote %d" % i for i in range(40)]
    sigs = [p.sign(m) for p, m in zip(privs, msgs)]

    def verify():
        bv = cb.BatchVerifier(tpu_threshold=8)
        for p, m, s in zip(privs, msgs, sigs):
            bv.add(p.pub_key(), m, s)
        ok, bits = bv.verify()
        assert ok and bits.all()
    return verify


def test_an_injected_stall_in_a_launch_is_one_incident_that_survives(
        _device_lane):
    """A latency fault at `ops.ed25519.verify_batch` (it sleeps on the
    lane worker, inside `device.launch`, ahead of the dispatch's own
    span): before sixteen launches have been seen nothing is kept; after,
    exactly one incident, whose largest gap lies inside `device.launch`,
    and 10,000 further spans do not overwrite it."""
    verify = _batch_of_40()
    verify()  # the bucket's compile, unrecorded
    trace.enable(capacity=8192)  # (a neighbour may have left it smaller)
    site = "ops.ed25519.verify_batch"
    for k in range(16):
        if k == 3:
            fail.set_mode(site, "latency:300")
        verify()
        fail.clear(site)
    assert trace.incidents() == []
    usual_ms = trace.TRACER._usual["device.collect"][1] / 1e6
    stall_ms = max(300.0, 12 * usual_ms)
    fail.set_mode(site, f"latency:{stall_ms:.0f}")
    verify()
    fail.clear(site)
    verify()
    (inc,) = trace.incidents()
    assert inc["name"] == "device.collect"
    assert inc["within"] == ["batch.verify"]
    assert inc["dur_ns"] >= 8 * inc["usual_ns"]
    gap = inc["gap"]
    assert gap["inside"] == "device.launch"
    assert gap["after"] == "ops.ed25519.verify_batch"
    assert gap["dur_ns"] >= 0.9 * stall_ms * 1e6
    assert gap["tid"] != inc["tid"]
    names = {r["name"] for r in inc["records"]}
    assert {"device.collect", "device.launch",
            "ops.ed25519.verify_batch"} <= names
    (launch,) = [r for r in inc["records"] if r["name"] == "device.launch"]
    assert launch["attrs"]["queued_ns"] < 0.5 * stall_ms * 1e6
    from tendermint_tpu.libs.metrics import DEFAULT
    assert "tendermint_trace_incidents_total 1" in DEFAULT.render_text() \
        or "trace_incidents_total" in DEFAULT.render_text()
    for k in range(10_000):
        with trace.span("bench.pass", k=k):
            pass
    assert trace.dropped() > 0
    (kept,) = trace.incidents()
    assert kept["records"] == inc["records"] and kept["gap"] == gap

    # the two reductions on the debug listener
    from tendermint_tpu.libs.pprof import PprofServer
    srv = PprofServer("127.0.0.1:0")
    srv.start()
    try:
        def get(query):
            with urllib.request.urlopen(
                    f"http://{srv.laddr}/debug/trace?{query}",
                    timeout=10) as r:
                return json.loads(r.read().decode())
        doc = get("incidents=1")
        assert [i["name"] for i in doc["incidents"]] == ["device.collect"]
        assert doc["incidents"][0]["gap"]["inside"] == "device.launch"
        doc = get("rollup=1")
        assert doc["spans"]["bench.pass"]["count"] > 8000
        assert {"tid", "tname", "window_ns", "unnamed_ns"} <= \
            set(doc["threads"][0])
        assert "traceEvents" in get("since=0")
    finally:
        srv.stop()


def test_a_stall_writes_one_warn_line(recorder, capsys):
    import io

    from tendermint_tpu.libs import log as tmlog

    out = io.StringIO()
    tmlog.setup("info", stream=out)
    try:
        tr = trace.TRACER
        for k in range(17):
            _timed(tr, "light.verify", 10 * k, 10 * k + 2)
        _timed(tr, "light.verify", 1_000, 4_000)
    finally:
        tmlog.setup("info")
    (line,) = out.getvalue().splitlines()
    assert line.startswith("W[") and "trace: a request stalled" in line
    assert "span=light.verify" in line and "ms=3000 usual_ms=1.875" in line
    assert "gap_in=light.verify" in line


# ---------------------------------------------------------------------------
# a pause has a name when it is one
# ---------------------------------------------------------------------------

class _Cell:
    __slots__ = ("other",)


def _garbage(n):
    for _ in range(n):
        a, b = _Cell(), _Cell()
        a.other, b.other = b, a


def test_a_forced_collection_over_a_large_graph_leaves_one_gc_pause(
        recorder):
    gc.collect()
    was = gc.isenabled()
    gc.disable()
    try:
        _garbage(200_000)
        recorder.reset()
        with recorder.span("state.apply_block"):
            collected = gc.collect()
    finally:
        if was:
            gc.enable()
    pause, outer = recorder.snapshot()
    assert (pause["name"], pause["ph"]) == ("gc.pause", "X")
    assert pause["attrs"] == {"generation": 2, "collected": collected}
    assert collected >= 400_000
    assert pause["dur_ns"] >= trace.GC_PAUSE_MIN_NS
    assert pause["parent"] == outer["id"] and pause["tid"] == outer["tid"]
    assert outer["ts_ns"] <= pause["ts_ns"] and \
        pause["ts_ns"] + pause["dur_ns"] <= outer["ts_ns"] + outer["dur_ns"]
    # a collection of nothing is under a millisecond and leaves nothing
    recorder.reset()
    gc.collect()
    gc.collect()
    assert [r for r in recorder.snapshot() if r["name"] == "gc.pause"
            and r["dur_ns"] < trace.GC_PAUSE_MIN_NS] == []


def test_the_collectors_hook_records_nothing_while_the_recorder_is_off():
    trace.disable()
    trace.reset()
    _garbage(50_000)
    gc.collect()
    assert trace.snapshot() == []


def test_a_pause_inside_record_cannot_deadlock_the_recorder(recorder):
    """The hook never takes the tracer's lock (a collection can begin
    under it): it stashes, and the next record or snapshot drains."""
    with trace.TRACER._lock:
        trace.TRACER._gc_pause(1, 5 * MS, {"generation": 0, "collected": 9})
    done = []
    t = threading.Thread(target=lambda: done.append(recorder.snapshot()))
    t.start()
    t.join(5)
    assert done and [r["name"] for r in done[0]] == ["gc.pause"]
