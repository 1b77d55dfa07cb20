"""Verify-path latency observatory (ADR-016, ISSUE 8) acceptance:

Real VerifyScheduler traffic under injected device-lane latency
(chaos ``latency:<ms>`` at ``sched.ed25519``) must surface in the
queue-wait and e2e histograms, trip ``sched_deadline_miss_total``, and
agree — within tolerance — across FOUR surfaces: the metrics bundle,
``scheduler.last_latency_report()``, ``GET /debug/latency`` on the
pprof listener, and the flight recorder's span timestamps.  The device
lane is a stubbed host-computing verifier (same trick as the
test_comb/test_mixed_lanes routing tests) so the chaos seam fires with
ZERO XLA compile cost.

Plus: the direct BatchVerifier path's ``path="direct"`` e2e bracket,
the degrade-fallback window labeling, the bench.probe chaos seam +
the benches' no-accelerator exit, bench_history.jsonl partial-run
capture, and the scripts/bench_trend.py harness over driver-capture
fixtures (rc=0, an rc 0 -> 1 capture gap flagged).
"""
from __future__ import annotations

import json
import os
import sys
import time
import urllib.request

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "scripts"))

from tendermint_tpu.crypto import batch as cb  # noqa: E402
from tendermint_tpu.crypto import degrade  # noqa: E402
from tendermint_tpu.crypto import ed25519 as edkeys  # noqa: E402
from tendermint_tpu.crypto import scheduler as vs  # noqa: E402
from tendermint_tpu.libs import fail, slo, trace  # noqa: E402
from tendermint_tpu.libs.metrics import Registry  # noqa: E402


@pytest.fixture(autouse=True)
def _clean():
    fail.reset()
    yield
    fail.reset()
    vs.uninstall()
    degrade.reset()
    slo.disable()
    slo.reset()
    trace.disable()


@pytest.fixture
def sched():
    created = []

    def make(**kw):
        s = vs.VerifyScheduler(**kw)
        created.append(s)
        vs.install(s)
        s.start()
        return s

    yield make
    for s in created:
        s.stop()
    vs.uninstall()


def _signed(n, tag=b"lat"):
    privs = [edkeys.PrivKey(bytes([(i * 11 + 5) % 255 + 1]) * 32)
             for i in range(n)]
    msgs = [tag + b" item %d" % i for i in range(n)]
    return [(p.pub_key(), m, p.sign(m)) for p, m in zip(privs, msgs)]


def _host_stub_verifier(pubs, msgs, sigs):
    """Stands in for the device kernel: verdict-identical, no XLA
    compile.  Runs INSIDE degrade's lane worker, after fail.inject at
    the sched.ed25519 seam — so injected lane latency/raise exercises
    the full degradation ladder."""
    return np.array([edkeys.PubKey(bytes(p)).verify_signature(m, s)
                     for p, m, s in zip(pubs, msgs, sigs)], dtype=bool)


@pytest.fixture
def _stub_device(monkeypatch):
    monkeypatch.setenv("TM_TPU_FORCE_BATCH", "1")
    monkeypatch.delenv("TM_TPU_DISABLE_BATCH", raising=False)
    monkeypatch.setattr(
        cb, "_device_verifier",
        lambda tname: _host_stub_verifier
        if tname == edkeys.KEY_TYPE else None)


def _spans(records, name):
    return [r for r in records if r["name"] == name]


# ---------------------------------------------------------------------------
# THE acceptance test: four surfaces agree under injected lane latency
# ---------------------------------------------------------------------------

def test_latency_observatory_four_surfaces_agree(sched, _stub_device):
    reg = Registry("latency")
    rt = degrade.configure(registry=reg)
    slo.set_config(enabled=True, window=64,
                   targets={"blocksync": 0.010})  # 10 ms: will be blown
    trace.enable(capacity=1 << 12)
    seq0 = trace.last_seq()

    s = sched(window_s=0.5, tpu_threshold=4)
    items = _signed(12, tag=b"acceptance")
    fail.set_mode("sched.ed25519", "latency:120")
    try:
        # deadline 20 ms out: the window closes early to chase it, but
        # the injected 120 ms lane latency guarantees the settle MISSES
        fut = s.submit(items, vs.Priority.BLOCKSYNC,
                       deadline=time.monotonic() + 0.02,
                       populate_cache=False)
        bits = fut.result(timeout=60)
    finally:
        fail.clear()
    trace.disable()
    assert bits.all()
    assert fail.fired("sched.ed25519", "latency:120") == 1

    # -- surface 1: the metrics bundle ---------------------------------
    m = rt.metrics
    assert m.sched_queue_wait.count(priority="blocksync") == 1
    qw_metric = m.sched_queue_wait.total(priority="blocksync")
    assert m.verify_e2e_latency.count(priority="blocksync",
                                      path="sched-device") == 1
    e2e_metric = m.verify_e2e_latency.total(priority="blocksync",
                                            path="sched-device")
    assert e2e_metric >= 0.12, "e2e must include the injected latency"
    assert m.sched_deadline_miss.value(priority="blocksync") == 1

    # -- surface 2: last_latency_report() ------------------------------
    rep = vs.last_latency_report()
    assert rep["path"] == "sched-device"
    assert rep["submissions"] == 1 and rep["items"] == 12
    assert rep["lanes"] == 12
    req = rep["requests"][0]
    assert req["priority"] == "blocksync" and req["deadline_met"] is False
    assert req["e2e_s"] == pytest.approx(e2e_metric, abs=1e-4)
    assert req["queue_wait_s"] == pytest.approx(qw_metric, abs=1e-4)
    # decomposition: the injected lane latency lands in execute_s
    assert rep["execute_s"] >= 0.11
    assert rep["e2e_max_s"] >= rep["execute_s"]

    # -- surface 3: flight-recorder span timestamps --------------------
    records = trace.snapshot(since=seq0)
    submit = _spans(records, "sched.submit")[0]
    resolve = _spans(records, "sched.resolve")[0]
    coalesce = _spans(records, "sched.coalesce")[0]
    launch = [r for r in _spans(records, "device.launch")
              if r["attrs"].get("site") == "sched.ed25519"][0]
    miss = _spans(records, "sched.deadline_miss")
    assert len(miss) == 1 and miss[0]["attrs"]["priority"] == "blocksync"
    # span-derived e2e (submit instant -> resolve instant) must agree
    # with the stamped report
    e2e_spans = (resolve["ts_ns"] - submit["ts_ns"]) / 1e9
    assert e2e_spans == pytest.approx(req["e2e_s"], abs=0.05)
    # span-derived queue wait (submit -> stage start) agrees too
    qw_spans = (coalesce["ts_ns"] - submit["ts_ns"]) / 1e9
    assert qw_spans == pytest.approx(req["queue_wait_s"], abs=0.05)
    # the device lane span carries the injected latency
    assert launch["dur_ns"] >= int(0.11e9)

    # -- surface 4: GET /debug/latency + the debug-latency CLI ---------
    from tendermint_tpu.libs.pprof import PprofServer
    srv = PprofServer("127.0.0.1:0")
    srv.start()
    try:
        with urllib.request.urlopen(
                f"http://{srv.laddr}/debug/latency", timeout=10) as r:
            assert r.status == 200
            assert r.headers["Content-Type"] == "application/json"
            doc = json.loads(r.read().decode())
        assert doc["last_latency_report"]["e2e_max_s"] == \
            rep["e2e_max_s"]
        assert doc["last_latency_report"]["requests"][0][
            "deadline_met"] is False
        stream = doc["slo"]["streams"]["blocksync"]
        assert stream["n"] == 1
        assert stream["p99_s"] == pytest.approx(req["e2e_s"], abs=1e-4)
        assert stream["burn_rate"] == pytest.approx(100.0)  # 1/1 over

        # the CLI mirrors debug-trace: fetch + write the same JSON
        from tendermint_tpu.cmd.__main__ import main as cli_main
        out = os.path.join(os.environ.get("TMPDIR", "/tmp"),
                           f"latency-cli-{os.getpid()}.json")
        try:
            cli_main(["debug-latency", "--pprof-laddr", srv.laddr,
                      "--output-file", out])
            with open(out) as f:
                cli_doc = json.load(f)
            assert cli_doc["last_latency_report"]["e2e_max_s"] == \
                rep["e2e_max_s"]
        finally:
            if os.path.exists(out):
                os.remove(out)
    finally:
        srv.stop()

    # SLO gauges were refreshed from the window
    assert m.slo_p99.value(stream="blocksync") == \
        pytest.approx(req["e2e_s"], abs=1e-4)
    assert m.slo_burn_rate.value(stream="blocksync") == \
        pytest.approx(100.0)


def test_fallback_window_labeled_sched_fallback(sched, _stub_device):
    """A device raise inside the window re-verifies on the host
    (degrade ladder) — the e2e path label must say sched-fallback, not
    claim device latency for a host re-verify."""
    rt = degrade.configure(registry=Registry("latfall"))
    s = sched(window_s=0.0, tpu_threshold=4)
    items = _signed(8, tag=b"fallback")
    fail.set_mode("sched.ed25519", "raise")
    try:
        bits = s.submit(items, vs.Priority.COMMIT,
                        populate_cache=False).result(timeout=60)
    finally:
        fail.clear()
    assert bits.all()
    m = rt.metrics
    assert m.verify_e2e_latency.count(priority="commit",
                                      path="sched-fallback") == 1
    assert m.verify_e2e_latency.count(priority="commit",
                                      path="sched-device") == 0
    assert vs.last_latency_report()["path"] == "sched-fallback"


def test_cache_resolved_window_and_queue_wait(sched, _stub_device):
    """A window resolved entirely from SigCache settles with
    path=sched-cache and still records queue wait + e2e."""
    rt = degrade.configure(registry=Registry("latcache"))
    s = sched(window_s=0.0, tpu_threshold=4)
    items = _signed(8, tag=b"cachewin")
    assert s.submit(items, vs.Priority.COMMIT).result(timeout=60).all()
    assert s.submit(items, vs.Priority.COMMIT).result(timeout=60).all()
    m = rt.metrics
    assert m.verify_e2e_latency.count(priority="commit",
                                      path="sched-cache") == 1
    assert m.sched_queue_wait.count(priority="commit") == 2
    rep = vs.last_latency_report()
    assert rep["path"] == "sched-cache" and rep["lanes"] == 0
    assert rep["requests"][0]["e2e_s"] is not None


def test_direct_path_publishes_e2e_at_context_priority():
    """The BatchVerifier direct path (scheduler not running) lands in
    the SAME e2e histogram, path="direct", at the caller's priority
    context — so per-request latency exists on every route."""
    rt = degrade.configure(registry=Registry("latdirect"))
    assert vs.running() is None
    items = _signed(6, tag=b"direct")

    bv = cb.BatchVerifier()
    for p, m_, s_ in items:
        bv.add(p, m_, s_)
    ok, _ = bv.verify()
    assert ok
    m = rt.metrics
    assert m.verify_e2e_latency.count(priority="commit",
                                      path="direct") == 1

    with vs.priority_context(vs.Priority.BLOCKSYNC):
        bv2 = cb.BatchVerifier()
        for p, m_, s_ in _signed(6, tag=b"direct2"):
            bv2.add(p, m_, s_)
        assert bv2.verify()[0]
    assert m.verify_e2e_latency.count(priority="blocksync",
                                      path="direct") == 1


# ---------------------------------------------------------------------------
# bench: probe chaos + opportunistic retry + history capture
# ---------------------------------------------------------------------------

def test_bench_requires_an_accelerator(capsys):
    """The device modes measure the chip or nothing: on this CPU, and
    under the bench.probe seam's dead backend, _require_accelerator
    exits non-zero with the reason and nothing reaches stdout — no
    host rate under a device metric's name."""
    import bench

    with pytest.raises(SystemExit) as e:
        bench._require_accelerator()
    assert e.value.code not in (0, None) and "'cpu'" in str(e.value.code)

    fail.set_mode("bench.probe", "raise")
    try:
        with pytest.raises(SystemExit) as e:
            bench._require_accelerator()
        assert "backend init failed" in str(e.value.code)
        assert "InjectedFault" in str(e.value.code)
        assert fail.fired("bench.probe", "raise") == 1
    finally:
        fail.clear()
    assert capsys.readouterr().out == ""


def test_bench_headline_prints_no_rate_without_a_chip():
    """`python bench.py` where JAX finds no accelerator: non-zero exit,
    no JSON line, no sigs/s/chip."""
    import subprocess

    r = subprocess.run([sys.executable, "bench.py"], cwd=ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert r.stdout == "" and "sigs/s/chip" not in r.stderr
    assert "no accelerator" in r.stderr


def test_bench_history_emit_partial_capture(monkeypatch, tmp_path,
                                            capsys):
    """_emit prints the driver's JSON line UNCHANGED and appends an
    enriched record to bench_history.jsonl immediately — a later
    config wedging cannot lose it.  Malformed lines never poison the
    load side."""
    import bench

    hist = tmp_path / "hist.jsonl"
    monkeypatch.setenv("BENCH_HISTORY", str(hist))
    monkeypatch.setenv("BENCH_ROUND", "r99")
    line1 = {"metric": "m1", "value": 10.0, "unit": "sigs/s"}
    bench._emit(line1)
    bench._emit({"metric": "m2", "value": 20.0, "unit": "sigs/s"})
    out = [json.loads(ln) for ln in
           capsys.readouterr().out.strip().splitlines()]
    assert out[0] == line1  # stdout contract untouched (no ts/source)
    recs = bench.load_history()
    assert [r["metric"] for r in recs] == ["m1", "m2"]
    assert recs[0]["source"] == "bench" and recs[0]["round"] == "r99"
    assert "ts" in recs[0]
    with open(hist, "a") as f:
        f.write('{"broken\n')
    assert len(bench.load_history()) == 2  # half-written line skipped


# ---------------------------------------------------------------------------
# the trend harness
# ---------------------------------------------------------------------------

def test_bench_trend_rc0_and_flags_capture_gap(tmp_path, capsys,
                                               monkeypatch):
    """rc=0 over a directory of driver captures, and an rc=0 -> rc=1
    capture gap (the shape of the old r04 -> r05 pair) is flagged in
    the trend table."""
    import bench_trend

    for n, rc, value in ((4, 0, 322959.0), (5, 1, None)):
        parsed = {} if value is None else {
            "metric": "ed25519_verify_throughput_e2e", "value": value,
            "unit": "sigs/s/chip", "vs_baseline": 43.15}
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(
            json.dumps({"n": n, "rc": rc, "parsed": parsed}))
    monkeypatch.delenv("BENCH_HISTORY", raising=False)
    rc = bench_trend.main(["--root", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "CAPTURE-FAILED rc=1" in out
    assert "r04 rc=0 -> r05 rc=1" in out
    assert "ed25519_verify_throughput_e2e" in out and "best" in out
    # --strict turns the gap into a nonzero exit (CI mode)
    assert bench_trend.main(["--root", str(tmp_path), "--strict"]) == 1
    capsys.readouterr()


def test_bench_trend_regression_flag(tmp_path, capsys):
    """A round dropping more than the threshold below best-known is
    flagged REGRESSION; a host-fallback capture is excluded from
    best-known instead of being mistaken for a regression."""
    import bench_trend

    def write(n, rc, value, note=None):
        parsed = {"metric": "x_e2e", "value": value, "unit": "sigs/s",
                  "vs_baseline": 1.0}
        if note:
            parsed["note"] = note
        doc = {"n": n, "rc": rc, "parsed": parsed}
        if value is None:
            doc["parsed"] = {}
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps(doc))

    write(1, 0, 100.0)
    write(2, 0, 9.0, note="device unavailable, host fallback")
    write(3, 0, 50.0)
    rc = bench_trend.main(["--root", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "REGRESSION" in out and "50" in out
    assert "host-fallback (excluded from best)" in out
    rows = bench_trend.trend_rows([
        {"label": "r01", "value": 100.0, "rc": 0, "note": None},
        {"label": "r02", "value": 9.0, "rc": 0,
         "note": "device unavailable, host fallback"},
        {"label": "r03", "value": 50.0, "rc": 0, "note": None},
    ], threshold=0.05)
    assert rows[0]["flag"] == "best"
    assert rows[1]["flag"].startswith("host-fallback")
    assert rows[2]["flag"].startswith("REGRESSION")
    # delta is computed against the last REAL capture (r01), not the
    # host-fallback row
    assert rows[2]["delta_vs_prev_pct"] == pytest.approx(-50.0)


def test_bench_report_prev_round_delta_columns():
    """bench_report's delta-vs-previous-round annotation is pure: the
    most recent comparable history record for the same config feeds
    prev_sigs_per_s / delta_vs_prev_pct; unknown configs pass
    through untouched."""
    from bench_trend import with_prev_round_delta

    hist = [
        {"config": "5: mixed", "sigs_per_s": 1000, "source": "bench_report"},
        {"config": "2: commit", "sigs_per_s": 77, "source": "bench_report"},
        {"config": "5: mixed", "sigs_per_s": 2000, "source": "bench_report"},
    ]
    out = with_prev_round_delta({"config": "5: mixed",
                                 "sigs_per_s": 3000}, hist)
    assert out["prev_sigs_per_s"] == 2000
    assert out["delta_vs_prev_pct"] == pytest.approx(50.0)
    untouched = {"config": "9: comb", "sigs_per_s": 5}
    assert with_prev_round_delta(untouched, hist) == untouched
    # bench lines key on "metric" instead of "config"
    mhist = [{"metric": "headline", "value": 10.0, "source": "bench"}]
    out2 = with_prev_round_delta({"metric": "headline", "value": 5.0},
                                 mhist)
    assert out2["delta_vs_prev_pct"] == pytest.approx(-50.0)
