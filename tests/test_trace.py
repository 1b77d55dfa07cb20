"""libs/trace: the flight recorder (docs/adr/adr-011-flight-recorder.md)
and its three surfaces — in-process export, GET /debug/trace on the
pprof listener, and the bench artifact round trip.

The span-tree test drives a REAL mixed batch through BatchVerifier with
tracing enabled (ISSUE 3 acceptance): the coalesce window, the device
lane launch (XLA kernel forced onto the CPU mesh, TM_TPU_FORCE_BATCH=1
— same trick as the chaos matrix), and the verdict application must
come back as one connected tree with route/occupancy attrs, exported as
valid Chrome-trace JSON both ways.  With tracing disabled the same path
records zero spans and costs sub-microsecond per call site.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import timeit
import urllib.request

import pytest

from tendermint_tpu.libs import trace
from tendermint_tpu.libs.trace import Tracer


# ---------------------------------------------------------------------------
# tracer unit behavior
# ---------------------------------------------------------------------------

def test_on_by_default_and_zero_switches_it_off(monkeypatch):
    """The recorder is on unless TM_TPU_TRACE is "0" (as devobs reads
    TM_TPU_DEVOBS): off at the incident, it would have recorded
    nothing.  An explicit constructor argument wins over the variable,
    and disable() is the switch at run time."""
    monkeypatch.delenv("TM_TPU_TRACE", raising=False)
    assert Tracer().is_enabled()
    for value, on in (("0", False), ("1", True), ("", True)):
        monkeypatch.setenv("TM_TPU_TRACE", value)
        assert Tracer().is_enabled() is on, value
    monkeypatch.setenv("TM_TPU_TRACE", "0")
    assert Tracer(enabled=True).is_enabled()
    tr = Tracer(capacity=8)
    with tr.span("off"):
        pass
    assert tr.snapshot() == []
    monkeypatch.delenv("TM_TPU_TRACE")
    tr = Tracer(capacity=8)
    with tr.span("on"):
        pass
    tr.disable()
    with tr.span("off again"):
        pass
    assert [r["name"] for r in tr.snapshot()] == ["on"]


def test_disabled_is_noop_and_records_nothing():
    tr = Tracer(capacity=64, enabled=False)
    with tr.span("a", x=1) as sp:
        sp.add(y=2)
        with tr.span("b"):
            tr.instant("c", z=3)
    assert tr.snapshot() == []
    assert tr.current_id() is None
    tr.enable()
    with tr.span("d"):
        pass
    assert [r["name"] for r in tr.snapshot()] == ["d"]
    tr.disable()
    with tr.span("e"):
        pass
    assert [r["name"] for r in tr.snapshot()] == ["d"]


def test_disabled_call_site_overhead_sub_microsecond():
    """The hot path pays `span()` unconditionally, so the disabled path
    must stay sub-microsecond per call site (enable-check + singleton
    return).  min-of-repeats dodges CI load spikes."""
    trace.disable()
    n = 20000

    def site():
        with trace.span("overhead.probe", n=64, threshold=32):
            pass

    per_call = min(timeit.repeat(site, number=n, repeat=5)) / n
    assert per_call < 1e-6, f"disabled span cost {per_call * 1e9:.0f} ns"

    def site_instant():
        trace.instant("overhead.instant", height=7, round=0)

    per_call = min(timeit.repeat(site_instant, number=n, repeat=5)) / n
    assert per_call < 1e-6, f"disabled instant cost {per_call * 1e9:.0f} ns"


def test_span_keeps_its_threads_cpu_time_beside_the_wall():
    """cpu_ns is the thread's CPU time inside the wall bracket: never
    more than the wall, near it for a span that computes, far below it
    for one that waits."""
    tr = Tracer(capacity=8, enabled=True)
    with tr.span("sleeps") as sp:
        time.sleep(0.05)
    assert sp.dur_ns >= 50e6 and sp.cpu_ns < sp.dur_ns / 5
    with tr.span("spins"):
        t_end = time.perf_counter() + 0.02
        while time.perf_counter() < t_end:
            pass
    tr.instant("mark")
    sleeps, spins, mark = tr.snapshot()
    assert (sleeps["dur_ns"], sleeps["cpu_ns"]) == (sp.dur_ns, sp.cpu_ns)
    for r in (sleeps, spins):
        assert 0 <= r["cpu_ns"] <= r["dur_ns"]
    # a loaded CI host can take the thread off the processor mid-spin
    assert spins["cpu_ns"] > spins["dur_ns"] / 4
    assert mark["dur_ns"] == mark["cpu_ns"] == 0
    ev = {e["name"]: e for e in tr.chrome_trace()["traceEvents"]}
    assert ev["sleeps"]["args"]["cpu_us"] == sleeps["cpu_ns"] / 1000.0
    assert "cpu_us" not in ev["mark"]["args"]


def test_spans_go_without_cpu_time_where_the_clock_is_dear(monkeypatch):
    """Under a sandboxed kernel one read of the thread CPU clock costs
    microseconds (6 us on the benchmark's hosts): the module times it
    once and such a host's spans record no cpu_ns."""
    assert trace._CPU_CLOCK is time.thread_time_ns    # cheap here

    def dear():
        t_end = time.perf_counter_ns() + 5000
        while time.perf_counter_ns() < t_end:
            pass
        return 0

    monkeypatch.setattr(time, "thread_time_ns", dear)
    assert trace._cheap_cpu_clock() is None
    monkeypatch.undo()
    monkeypatch.setattr(trace, "_CPU_CLOCK", None)
    tr = Tracer(capacity=8, enabled=True)
    with tr.span("s") as sp:
        pass
    assert sp.cpu_ns is None and tr.snapshot()[0]["cpu_ns"] is None
    assert "cpu_us" not in tr.chrome_trace()["traceEvents"][0]["args"]


def test_timed_hands_back_its_duration_on_and_off():
    """trace.timed is the one clock pair of a site that also feeds an
    operator metric: the same dur_ns goes into the ring and back to the
    caller, and with the recorder off the caller still gets it."""
    tr = Tracer(capacity=8, enabled=True)
    with tr.timed("t", k=1) as sp:
        time.sleep(0.002)
    assert tr.snapshot()[0]["dur_ns"] == sp.dur_ns >= 2e6
    tr.disable()
    with tr.timed("t") as off:
        time.sleep(0.002)
    assert off.dur_ns >= 2e6 and off.span_id is None
    assert off.add(x=1) is off and len(tr.snapshot()) == 1


def test_spans_stamp_perf_counter():
    """One clock, stated: a span's start lies between two
    perf_counter_ns reads taken around its entry (the benchmark lays
    its own perf_counter rows beside the program's spans)."""
    tr = Tracer(capacity=8, enabled=True)
    a = time.perf_counter_ns()
    with tr.span("s"):
        b = time.perf_counter_ns()
    tr.instant("i")
    c = time.perf_counter_ns()
    s, i = tr.snapshot()
    assert a <= s["ts_ns"] <= b <= i["ts_ns"] <= c


_NO_JAX = """
import sys
from tendermint_tpu.libs import trace
assert trace.is_enabled()
with trace.span("batch.verify"):
    trace.instant("batch.verdict")
assert len(trace.snapshot()) == 2
bad = sorted(m for m in sys.modules if m == "jax" or m.startswith("jax."))
assert not bad, bad
"""


def test_trace_never_imports_jax():
    """libs/trace is imported by every hot-path module and by processes
    that must stay off the chip (the ABCI app, the signer): recording
    spans must not pull jax in."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("TM_TPU_TRACE", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", _NO_JAX], cwd=repo, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]


def test_spans_lie_in_a_profiler_session(tmp_path):
    """With jax loaded, a span enters a TraceAnnotation of its own name:
    inside a profiler session it is in the profile's host plane, on the
    profile's clock."""
    import glob

    import jax

    tr = Tracer(capacity=8, enabled=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tr.span("light.verify", height=7):
            with tr.span("valset.hash"):
                time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    names = {e.name
             for pl in jax.profiler.ProfileData.from_file(path).planes
             if not pl.name.startswith("/device:")
             for ln in pl.lines for e in ln.events}
    assert {"light.verify", "valset.hash"} <= names


def test_ring_buffer_wraparound_keeps_newest():
    tr = Tracer(capacity=16, enabled=True)
    for i in range(40):
        with tr.span(f"s{i}", i=i):
            pass
    snap = tr.snapshot()
    assert len(snap) == 16
    # the ring holds exactly the most recent records, in order
    assert [r["name"] for r in snap] == [f"s{i}" for i in range(24, 40)]
    assert snap[-1]["seq"] == tr.last_seq() == 40
    # `since` cursors keep working across the wrap
    assert [r["name"] for r in tr.snapshot(since=38)] == ["s38", "s39"]


def test_parent_linkage_nesting_and_cross_thread():
    tr = Tracer(capacity=64, enabled=True)
    with tr.span("root") as root:
        with tr.span("child"):
            tr.instant("mark")
        # cross-thread: explicit parent id, the worker's thread-local
        # stack starts empty (the degrade lane-worker pattern)
        parent = tr.current_id()
        assert parent == root.span_id

        def worker():
            with tr.span("lane", parent=parent):
                pass
            tr.instant("settled", parent=parent)
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    by_name = {r["name"]: r for r in tr.snapshot()}
    assert by_name["child"]["parent"] == by_name["root"]["id"]
    assert by_name["mark"]["parent"] == by_name["child"]["id"]
    assert by_name["lane"]["parent"] == by_name["root"]["id"]
    assert by_name["settled"]["parent"] == by_name["root"]["id"]
    assert by_name["root"]["parent"] is None
    assert by_name["lane"]["tid"] != by_name["root"]["tid"]


def _assert_chrome_schema(doc):
    """Chrome-trace JSON object format: traceEvents list of events with
    name/ph/ts/pid/tid, complete events carrying a dur."""
    assert isinstance(doc, dict) and isinstance(doc["traceEvents"], list)
    assert isinstance(doc["last_seq"], int)
    for ev in doc["traceEvents"]:
        assert isinstance(ev["name"], str)
        assert ev["ph"] in ("X", "i")
        assert isinstance(ev["ts"], (int, float))
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert isinstance(ev["args"], dict) and "id" in ev["args"]
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
        else:
            assert ev["s"] == "t"


def test_chrome_trace_schema_and_since_cursor():
    tr = Tracer(capacity=64, enabled=True)
    with tr.span("a", detail="x"):
        tr.instant("b")
    doc = json.loads(json.dumps(tr.chrome_trace(), default=str))
    _assert_chrome_schema(doc)
    assert {e["name"] for e in doc["traceEvents"]} == {"a", "b"}
    # incremental poll from the cursor returns only newer events
    cur = doc["last_seq"]
    with tr.span("c"):
        pass
    inc = tr.chrome_trace(since=cur)
    assert [e["name"] for e in inc["traceEvents"]] == ["c"]


# ---------------------------------------------------------------------------
# the acceptance path: BatchVerifier span tree + both export surfaces
# ---------------------------------------------------------------------------

def _mixed_batch_verify(n_ed=40):
    """One coalesced mixed batch (ed25519 device lane + sr25519 host
    lane) through BatchVerifier; bucket 64 reuses the CPU-mesh kernel
    the chaos tests already compiled in this process."""
    from tendermint_tpu.crypto import batch as cb
    from tendermint_tpu.crypto import ed25519 as edkeys
    from tendermint_tpu.crypto import sr25519 as sr

    privs = [edkeys.PrivKey(bytes([i + 1]) * 32) for i in range(n_ed)]
    msgs = [b"trace vote %d" % i for i in range(n_ed)]
    sigs = [p.sign(m) for p, m in zip(privs, msgs)]
    bv = cb.BatchVerifier(tpu_threshold=8)
    for p, m, s in zip(privs, msgs, sigs):
        bv.add(p.pub_key(), m, s)
    sk = sr.PrivKey(b"\x77" * 32)
    bv.add(sk.pub_key(), b"sr trace msg", sk.sign(b"sr trace msg"))
    return bv.verify()


@pytest.fixture
def _device_lane(monkeypatch):
    """Force the device lane onto the CPU mesh with a compile-proof
    launch deadline, and leave the global tracer/runtime clean."""
    from tendermint_tpu.crypto import degrade

    monkeypatch.setenv("TM_TPU_FORCE_BATCH", "1")
    monkeypatch.delenv("TM_TPU_DISABLE_BATCH", raising=False)
    degrade.configure(degrade.DegradeConfig(launch_timeout_s=600.0))
    trace.disable()
    trace.reset()
    yield
    trace.disable()
    trace.reset()
    degrade.reset()


def test_batch_verifier_span_tree_and_exports(_device_lane, tmp_path):
    # warm pass (untraced): pays the one-off kernel compile for this
    # bucket if no earlier test has, so the traced pass is steady-state
    ok, bits = _mixed_batch_verify()
    assert ok and bits.all()

    before = trace.last_seq()
    trace.enable()
    ok, bits = _mixed_batch_verify()
    assert ok and bits.all()
    trace.disable()
    spans = {r["name"]: r for r in trace.snapshot(since=before)}

    # the coalesce window root, with the scheme-mix + threshold attrs
    root = spans["batch.verify"]
    assert root["parent"] is None
    assert root["attrs"]["n"] == 41
    assert root["attrs"]["threshold"] == 8
    assert "ed25519:40" in root["attrs"]["schemes"]
    assert "sr25519:1" in root["attrs"]["schemes"]
    assert root["attrs"]["device_lanes"] == 1

    # device launch on the lane worker, linked across the thread
    # boundary into the coalesce root
    launch = spans["device.launch"]
    assert launch["parent"] == root["id"]
    assert launch["tid"] != root["tid"]

    # the kernel dispatch under the launch, carrying route + occupancy
    opsspan = spans["ops.ed25519.verify_batch"]
    assert opsspan["parent"] == launch["id"]
    assert opsspan["attrs"]["path"] in ("mesh-xla", "mesh-sharded", "xla")
    assert opsspan["attrs"]["nb"] == 64
    assert opsspan["attrs"]["occupancy"] == pytest.approx(40 / 64)

    # settle + verdict application, both under the root
    assert spans["device.collect"]["parent"] == root["id"]
    assert spans["device.collect"]["attrs"]["outcome"] == "ok"
    verdict = spans["batch.verdict"]
    assert verdict["parent"] == root["id"]
    assert verdict["attrs"]["valid"] == 41

    # host lane (sr25519) rides the same tree
    assert spans["batch.host_lane"]["parent"] == root["id"]

    # export surface 1: libs/trace Chrome-trace JSON
    path = trace.export_file(str(tmp_path / "trace.json"), since=before)
    with open(path) as f:
        doc = json.load(f)
    _assert_chrome_schema(doc)
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"batch.verify", "device.launch",
            "ops.ed25519.verify_batch", "batch.verdict"} <= names

    # export surface 2: GET /debug/trace on the pprof listener
    from tendermint_tpu.libs.pprof import PprofServer
    srv = PprofServer("127.0.0.1:0")
    srv.start()
    try:
        with urllib.request.urlopen(
                f"http://{srv.laddr}/debug/trace?since={before}",
                timeout=10) as r:
            assert r.status == 200
            assert r.headers["Content-Type"] == "application/json"
            doc2 = json.loads(r.read().decode())
    finally:
        srv.stop()
    _assert_chrome_schema(doc2)
    assert {e["name"] for e in doc2["traceEvents"]} >= names

    # route/occupancy/compile promoted into CryptoMetrics: /metrics
    # (the DEFAULT registry the RPC endpoint renders) answers which
    # path ran without polling any module global
    from tendermint_tpu.libs.metrics import DEFAULT
    text = DEFAULT.render_text()
    assert "crypto_msm_route_total{path=" in text
    assert "crypto_batch_occupancy_ratio 0.625" in text
    assert "crypto_device_compile_seconds" in text

    # disabled: the SAME path records zero spans
    seq = trace.last_seq()
    ok, bits = _mixed_batch_verify()
    assert ok and bits.all()
    assert trace.last_seq() == seq, "disabled tracer recorded spans"


def test_last_launch_snapshot_is_immutable(_device_lane):
    from tendermint_tpu.ops import ed25519 as edops

    ok, bits = _mixed_batch_verify()
    assert ok
    rec = edops.last_launch()
    assert rec["path"] in ("mesh-xla", "mesh-sharded", "xla")
    assert rec["nb"] == 64 and rec["shards"] >= 1
    with pytest.raises(TypeError):
        rec["path"] = "tampered"


def test_last_launch_snapshot_immutable_and_counted(_device_lane,
                                                    monkeypatch):
    """ISSUE 3 satellite, restated for the routes that are left:
    last_launch() returns an immutable snapshot and the route lands in
    crypto_msm_route_total at record time."""
    import jax.numpy as jnp

    from tendermint_tpu.crypto import degrade
    from tendermint_tpu.ops import ed25519 as edops

    # the route and its record are the subject, not the kernel: a
    # stand-in keeps the xla/64 compile out of this test
    monkeypatch.setattr(
        edops, "verify_kernel",
        lambda pub, r, sd, kd: jnp.ones(pub.shape[0], dtype=bool))
    rt = degrade.runtime()
    before = rt.metrics.msm_route.value(path="xla", outcome="executed")
    # one key, one row: under the mesh's floor and the comb's, so the
    # batch takes the single-device XLA kernel
    seed = (0x7AC3).to_bytes(32, "little")
    from tendermint_tpu.crypto import _edref
    pub, msg = _edref.pubkey_from_seed(seed), b"route counter"
    assert edops.verify_batch([pub], [msg], [_edref.sign(seed, msg)]).all()
    route = edops.last_launch()
    assert route["path"] == "xla"
    with pytest.raises(TypeError):
        route["path"] = "tampered"
    assert rt.metrics.msm_route.value(
        path="xla", outcome="executed") == before + 1


# ---------------------------------------------------------------------------
# bench artifact round trip
# ---------------------------------------------------------------------------

def test_bench_trace_artifact_roundtrip(tmp_path, monkeypatch):
    """bench.py's JSON line carries a "trace" artifact path; the file it
    names must be loadable Chrome-trace JSON (host-fallback runs
    included — the artifact writer never needs a device)."""
    import bench

    monkeypatch.setenv("BENCH_TRACE_DIR", str(tmp_path))
    trace.reset()
    trace.enable()
    try:
        with trace.span("bench.pass", scheme="1", sigs_per_s=12345):
            pass
    finally:
        trace.disable()
    path = bench._trace_artifact("unit")
    assert path is not None and path.startswith(str(tmp_path))
    with open(path) as f:
        doc = json.load(f)
    _assert_chrome_schema(doc)
    ev = [e for e in doc["traceEvents"] if e["name"] == "bench.pass"]
    assert ev and ev[0]["args"]["sigs_per_s"] == 12345
    trace.reset()
