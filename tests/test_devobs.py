"""Device observatory (crypto/devobs.py, ADR-021): the per-launch
transfer/compute/compile decomposition, its debug surfaces, and the
ISSUE 13 satellites.

The acceptance test drives a real batch through the degradation
runtime onto the CPU mesh path (the one mesh path CI can exercise) and
proves the recorded stage + h2d + compute + collect phases sum to the
launch wall AND sit inside the flight recorder's device.launch /
device.collect spans — with CompileSentinel(max_new_compiles=0)
pinning that the whole proof reuses the shared nb=64 bucket.  Unit
tests pin the ring/inventory/ledger mechanics, the disabled
sub-microsecond no-op (timeit-gated like trace/slo/observatory), the
chaos shed at `devobs.record` with exact-bitmap identity, the
compile-inventory-vs-CompileSentinel agreement, `GET /debug` +
`GET /debug/device` + the debug-device/debug-index CLIs, the [devobs]
config section, the `[slo]` device_launch stream, and bench_trend's
compile-inflation exclusion.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import timeit
import urllib.request

import numpy as np
import pytest

from tendermint_tpu.crypto import devobs
from tendermint_tpu.crypto import ed25519 as edkeys
from tendermint_tpu.crypto.devobs import DevObs
from tendermint_tpu.libs import fail, slo, trace
from tendermint_tpu.libs.metrics import DevObsMetrics, Registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean():
    devobs.reset()
    devobs.enable()
    yield
    fail.clear()
    devobs.reset()
    devobs.enable()


def _batch(n, bad=()):
    privs = [edkeys.PrivKey((0xDB00 + i).to_bytes(32, "big"))
             for i in range(n)]
    msgs = [b"devobs %6d" % i for i in range(n)]
    sigs = [p.sign(m) for p, m in zip(privs, msgs)]
    for i in bad:
        s = bytearray(sigs[i])
        s[3] ^= 0x40
        sigs[i] = bytes(s)
    pubs = [p.pub_key().bytes() for p in privs]
    return pubs, msgs, sigs


# ---------------------------------------------------------------------------
# record mechanics: ring bounds, compile inventory, ledger
# ---------------------------------------------------------------------------

def test_ring_bounds_and_compile_inventory():
    o = DevObs(capacity=4, enabled=True)
    assert o.record({"path": "xla", "n": 48, "nb": 64, "shards": 1,
                     "first_launch": True, "wall_s": 2.0})
    for i in range(5):
        o.record({"path": "xla", "n": 40 + i, "nb": 64, "shards": 1,
                  "first_launch": False, "wall_s": 0.01})
    recs = o.records()
    assert len(recs) == 4                      # ring bound holds
    # ring turnover is benign rotation, NOT loss: the records were
    # stored and queued for publication before aging out
    assert o.rotated() >= 2
    assert o.shed_counts()["evict"] == 0
    assert [r["obs_seq"] for r in recs] == [3, 4, 5, 6]
    inv = o.compile_inventory()
    assert len(inv) == 1
    ent = inv[0]
    # the FIRST launch's wall is the attributed compile cost; the five
    # steady-state launches count as cache hits
    assert (ent["path"], ent["nb"], ent["shards"]) == ("xla", 64, 1)
    assert ent["compile_s"] == 2.0
    assert ent["hits"] == 5
    assert ent["first_seen_seq"] == 1
    # a second bucket shape is a second entry
    o.record({"path": "comb", "n": 100, "nb": 128, "shards": 1,
              "first_launch": True, "wall_s": 1.5})
    assert len(o.compile_inventory()) == 2


def test_pending_queue_overflow_is_a_real_shed():
    """With no drainer at all the deferred-publication queue drops its
    oldest UNPUBLISHED records — that IS loss, counted in shed{evict}
    (unlike benign ring rotation)."""
    from tendermint_tpu.crypto.devobs import _MAX_PENDING

    o = DevObs(capacity=4, enabled=True)
    for i in range(_MAX_PENDING + 10):
        o.record({"path": "xla", "n": 1, "nb": 64, "wall_s": 0.001})
    assert len(o._pending) <= _MAX_PENDING
    assert o.shed_counts()["evict"] >= 10


def test_device_block_totals_survive_ring_rotation():
    """device_block's compile_frac reads the lifetime totals (diffed
    against a cursor when one is given), so a run whose first-launch
    compile records aged out of the ring still reports the true compile
    share (the bench_trend compile-inflation exclusion depends on it) —
    and the ring-scoped phase sums are honestly labeled as a `window`
    with their own launch count."""
    o = DevObs(capacity=4, enabled=True)
    o._metrics = DevObsMetrics(Registry("devobs_totals"))
    cur0 = o.cursor()
    o.record({"path": "xla", "n": 64, "nb": 64, "shards": 1,
              "first_launch": True, "wall_s": 9.0})
    for i in range(20):                        # rotate the compile out
        o.record({"path": "xla", "n": 64, "nb": 64, "shards": 1,
                  "first_launch": False, "wall_s": 0.05,
                  "compute_s": 0.04})
    assert all(not r["first_launch"] for r in o.records())
    for blk in (o.device_block(), o.device_block(since=cur0)):
        assert blk["launches"] == 21
        assert blk["compile_s"] == pytest.approx(9.0)
        assert blk["compile_frac"] == pytest.approx(9.0 / 10.0)
        # the window decomposes only what the ring still holds
        assert blk["window"]["launches"] == 4
        assert blk["window"]["compute_s"] == pytest.approx(0.16)


def test_ledger_levels_and_high_water():
    o = DevObs(capacity=4, enabled=True)
    o.ledger_set("table_cache", 1000)
    o.ledger_set("table_cache", 400)           # level drops...
    o.ledger_add("staging", 300)
    o.ledger_add("staging", 200)
    o.ledger_add("staging", -500)
    o.ledger_add("staging", -50)               # clamped at zero
    rep = o.ledger_report()
    assert rep["table_cache"] == {"bytes": 400, "peak_bytes": 1000}
    assert rep["staging"] == {"bytes": 0, "peak_bytes": 500}
    # report orders known pools first and includes everything
    o.ledger_set("exotic_pool", 7)
    keys = list(o.ledger_report())
    assert keys.index("table_cache") < keys.index("exotic_pool")


def test_publish_pending_feeds_metrics_and_slo():
    o = DevObs(capacity=8, enabled=True)
    o._metrics = DevObsMetrics(Registry("devobs_pub"))
    o.ledger_set("staging", 123)
    o.record({"path": "mesh-sharded", "n": 48, "nb": 64, "shards": 8,
              "first_launch": False, "wall_s": 0.5, "stage_s": 0.1,
              "h2d_s": 0.1, "compute_s": 0.2, "collect_s": 0.1,
              "chunk_overlap": 0.75, "shard_imbalance": 1.25,
              "shard_h2d_s": [0.1, 0.3]})
    o.record({"path": "pallas-split", "n": 100, "nb": 128, "shards": 1,
              "first_launch": False, "wall_s": 0.3, "h2d_s": 0.1,
              "drain_s": 0.2})
    slo.reset()
    slo.enable(targets={"device_launch": 0.001})
    try:
        o.publish_pending()
        m = o._metrics
        assert m.device_transfer.count(path="mesh-sharded") == 1
        assert m.device_compute.total(path="mesh-sharded") == \
            pytest.approx(0.2)
        assert m.device_stage.count(path="mesh-sharded") == 1
        assert m.device_collect.count(path="mesh-sharded") == 1
        # a double-buffered path's merged final wait lands in the drain
        # histogram, never mislabeled as collect
        assert m.device_drain.count(path="pallas-split") == 1
        assert m.device_collect.count(path="pallas-split") == 0
        assert m.chunk_overlap.value() == 0.75
        # the companion freshness gauge advances with the launch's
        # observatory seq, so the control plane can tell "busy path
        # republishing the same ratio" from "idle path"
        assert m.chunk_overlap_seq.value() == 1.0
        assert m.shard_imbalance.value() == 1.25
        # per-shard put walls [0.1, 0.3]: max/mean = 0.3/0.2
        assert m.shard_h2d_imbalance.value() == pytest.approx(1.5)
        assert m.hbm_resident.value(pool="staging") == 123
        assert m.compile_cache_entries.value() == 2
        # the [slo] device_launch stream saw both walls, and the
        # hundreds-of-ms launches burn the 1 ms p99 budget
        rep = slo.stream_report("device_launch")
        assert rep is not None and rep["n"] == 2
        assert rep["burn_rate"] == pytest.approx(100.0)
    finally:
        slo.disable()
        slo.reset()


def test_disabled_is_noop_and_sub_microsecond():
    """record() is called on every device launch unconditionally, so
    the disabled path must stay sub-microsecond — the same gate trace /
    slo / the consensus observatory carry.  min-of-repeats dodges CI
    load spikes."""
    devobs.disable()
    try:
        dummy = {"path": "xla", "n": 1, "nb": 64, "wall_s": 0.1}
        assert devobs.record(dummy) is False
        devobs.ledger_add("staging", 100)
        assert devobs.records() == []
        assert devobs.ledger_report() == {}

        n = 20000

        def site():
            devobs.record(dummy)

        per_call = min(timeit.repeat(site, number=n, repeat=5)) / n
        assert per_call < 1e-6, f"disabled record cost {per_call:.2e}s"

        def site_ledger():
            devobs.ledger_add("staging", 1)

        per_call = min(timeit.repeat(site_ledger, number=n,
                                     repeat=5)) / n
        assert per_call < 1e-6, f"disabled ledger cost {per_call:.2e}s"
    finally:
        devobs.enable()


def test_set_config_wins_both_ways_and_resizes():
    o = DevObs(capacity=8, enabled=False)
    o.set_config(enabled=True)
    assert o.is_enabled()
    for i in range(6):
        o.record({"path": "xla", "n": i, "nb": 64, "wall_s": 0.1})
    o.set_config(capacity=3)
    assert o.capacity == 3 and len(o.records()) == 3
    o.set_config(enabled=False)                 # config disables too
    assert not o.is_enabled()
    o.set_config(capacity=5)                    # None leaves enabled alone
    assert not o.is_enabled() and o.capacity == 5


# ---------------------------------------------------------------------------
# the acceptance proof: CPU mesh decomposition + span agreement
# ---------------------------------------------------------------------------

def test_mesh_decomposition_sums_to_wall_and_agrees_with_spans():
    """On the production CPU mesh path (the overlapped compact ladder,
    "mesh-xla" since ADR-027) the launch record carries the overlapped
    decomposition — host stage, summed per-shard device_put wall, the
    chunk_overlap ratio and the merged drain — each phase bounded by
    the recorded wall (an overlapped pipeline's phases deliberately do
    NOT tile the wall: H2D hides behind compute), the psum'd all_valid
    verdict, per-shard rows/imbalance, and the record sits inside the
    flight recorder's device.launch/device.collect spans.  The whole
    proof reuses the shared nb=64 bucket (CompileSentinel
    max_new_compiles=0)."""
    from tendermint_tpu.crypto import degrade
    from tendermint_tpu.devtools.tmlint.runtime import CompileSentinel
    from tendermint_tpu.ops import ed25519 as edops
    from tendermint_tpu.parallel import sharding

    assert sharding.data_plane() is not None, "virtual CPU mesh absent"
    pubs, msgs, sigs = _batch(48)
    # warm: the mesh bucket compile (if this process hasn't paid it
    # yet) must not land inside the measured/asserted launch
    assert edops.verify_batch(pubs, msgs, sigs).all()

    devobs.reset()
    sentinel = CompileSentinel(max_new_compiles=0).start()
    trace.enable()
    rt = degrade.configure(registry=Registry("devobs_acc"))
    try:
        out = rt.run("batch.ed25519",
                     lambda: edops.verify_batch(pubs, msgs, sigs),
                     lambda: np.ones(len(pubs), dtype=bool))
        assert np.asarray(out).all()
        sentinel.check()  # no foreign bucket, no new compile

        recs = [r for r in devobs.records()
                if r.get("path") == "mesh-xla"]
        assert recs, devobs.records()
        rec = recs[-1]
        # the overlapped decomposition: each phase is a real sub-wall
        # of the launch, but their sum is only BOUNDED by the wall —
        # the per-shard puts of chunk j+1 hide behind chunk j's compute
        for k in ("stage_s", "h2d_s", "drain_s"):
            assert 0 <= rec[k] <= rec["wall_s"] + 0.05, (k, rec)
        assert 0.0 <= rec["chunk_overlap"] <= 1.0
        # the psum'd verdict bit is part of the record even when the
        # batch is clean (the global plane's cross-process contract)
        assert rec["all_valid"] is True
        # per-shard H2D walls: one put wall per mesh position
        assert len(rec["shard_h2d_s"]) == 8
        assert all(w >= 0 for w in rec["shard_h2d_s"])
        # per-shard real-row accounting: 48 rows over 8 shards of 8
        # lanes — six full shards, two pure-pad shards
        assert rec["shard_rows"] == [8, 8, 8, 8, 8, 8, 0, 0]
        assert rec["shard_imbalance"] == pytest.approx(8 / 6)
        assert rec["nb"] == 64 and rec["shards"] == 8

        # span agreement: the launch record was stamped inside the
        # degradation runtime's device.launch span (the dispatch runs
        # on the lane worker under it) and before device.collect
        # settled — all on the one monotonic clock
        evs = trace.snapshot()
        launch = [e for e in evs if e["name"] == "device.launch"
                  and e["attrs"].get("site") == "batch.ed25519"][-1]
        collect = [e for e in evs if e["name"] == "device.collect"
                   and e["attrs"].get("site") == "batch.ed25519"][-1]
        l0 = launch["ts_ns"] / 1e9
        l1 = l0 + launch["dur_ns"] / 1e9
        assert l0 <= rec["t_mono"] <= l1 + 0.05
        assert rec["wall_s"] <= launch["dur_ns"] / 1e9 + 0.05
        c0 = collect["ts_ns"] / 1e9
        c1 = c0 + collect["dur_ns"] / 1e9
        assert c0 <= rec["t_mono"] <= c1 + 0.05
    finally:
        degrade.reset()
        trace.disable()
        trace.reset()


def test_compile_inventory_agrees_with_compile_sentinel():
    """The inventory keys are exactly ops/ed25519._seen_buckets' —
    every (path, nb, shards) the observatory attributes a compile to
    must be a bucket the CompileSentinel would account, and a launch
    recorded through _record_launch lands in BOTH."""
    from tendermint_tpu.devtools.tmlint.runtime import CompileSentinel
    from tendermint_tpu.ops import ed25519 as edops

    pubs, msgs, sigs = _batch(16)
    devobs.reset()
    assert edops.verify_batch(pubs, msgs, sigs).all()
    inv = devobs.compile_inventory()
    assert inv, "no launch recorded"
    keys = {(e["path"], e["nb"], e["shards"]) for e in inv}
    seen = CompileSentinel._seen_buckets()
    assert keys <= seen, (keys, seen)
    for e in inv:
        assert CompileSentinel.bucket_allowed(e["nb"], e["shards"]), e


# ---------------------------------------------------------------------------
# chaos: a recording fault sheds, the launch and bitmap are untouched
# ---------------------------------------------------------------------------

def test_chaos_devobs_record_raise_sheds_bitmap_exact():
    from tendermint_tpu.ops import ed25519 as edops

    pubs, msgs, sigs = _batch(24, bad=(3, 17))
    want = np.ones(24, dtype=bool)
    want[[3, 17]] = False
    base = np.asarray(edops.verify_batch(pubs, msgs, sigs))
    assert (base == want).all(), base

    shed0 = DevObsMetrics().devobs_shed.value(reason="chaos")
    devobs.reset()
    fail.set_mode("devobs.record", "raise")
    try:
        out = np.asarray(edops.verify_batch(pubs, msgs, sigs))
        # EXACT bitmap identity: telemetry chaos must be invisible to
        # the verdict (the ADR-020 contract, now on the launch seam)
        assert (out == want).all(), out
        assert fail.fired("devobs.record", "raise") >= 1
        assert devobs.records() == []      # the record really shed
    finally:
        fail.clear("devobs.record")
    # the shed is visible once the deferred publication drains, and the
    # report surface shows the CUMULATIVE count (the endpoint flushes
    # before reading, so a delta view would always render zeros there)
    devobs.publish_pending()
    assert DevObsMetrics().devobs_shed.value(reason="chaos") > shed0
    assert devobs.report()["shed"]["chaos"] >= 1


def test_chaos_devobs_record_latency_swallowed_bitmap_exact():
    """latency:<ms> at devobs.record is absorbed into the recording —
    the launch proceeds, the bitmap is exact, nothing raises."""
    from tendermint_tpu.ops import ed25519 as edops

    pubs, msgs, sigs = _batch(16, bad=(5,))
    want = np.ones(16, dtype=bool)
    want[5] = False
    devobs.reset()
    fail.set_mode("devobs.record", "latency:5")
    try:
        out = np.asarray(edops.verify_batch(pubs, msgs, sigs))
        assert (out == want).all(), out
        assert fail.fired("devobs.record", "latency:5") >= 1
        # the record itself survives a latency injection (only raise
        # sheds): the launch is still fully decomposed
        assert devobs.records()
    finally:
        fail.clear("devobs.record")


# ---------------------------------------------------------------------------
# debug surfaces: GET /debug index, GET /debug/device, the CLIs
# ---------------------------------------------------------------------------

def _get(laddr, path):
    try:
        with urllib.request.urlopen(f"http://{laddr}{path}",
                                    timeout=10) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def test_debug_index_and_device_endpoint_and_clis(tmp_path, capsys):
    from tendermint_tpu.cmd.__main__ import main as cmd_main
    from tendermint_tpu.libs.pprof import DEBUG_ENDPOINTS, PprofServer
    from tendermint_tpu.ops import ed25519 as edops

    pubs, msgs, sigs = _batch(16)
    devobs.reset()
    assert edops.verify_batch(pubs, msgs, sigs).all()

    srv = PprofServer("127.0.0.1:0")
    srv.start()
    try:
        # satellite: the index page names every registered endpoint
        code, body = _get(srv.laddr, "/debug")
        assert code == 200
        for path, desc in DEBUG_ENDPOINTS:
            assert path in body, path
        assert "device observatory" in body

        code, body = _get(srv.laddr, "/debug/device?last=4")
        assert code == 200
        doc = json.loads(body)
        assert doc["enabled"] is True
        assert doc["launches"], doc
        rec = doc["launches"][-1]
        # the endpoint, the in-process report, and last_launch() agree
        # on the same decomposition
        local = devobs.report(last=4)["launches"][-1]
        assert rec["obs_seq"] == local["obs_seq"]
        assert rec["wall_s"] == pytest.approx(local["wall_s"])
        assert doc["compile_cache"] and "hbm" in doc
        ll = edops.last_launch()
        assert rec["path"] == ll["path"] and rec["nb"] == ll["nb"]

        # the 404 page points at the index now
        code, body = _get(srv.laddr, "/debug/nope")
        assert code == 404 and "/debug" in body

        # debug-device CLI writes the same JSON
        out_file = tmp_path / "device.json"
        cmd_main(["debug-device", "--pprof-laddr", srv.laddr,
                  "--output-file", str(out_file)])
        doc2 = json.loads(out_file.read_text())
        assert doc2["launches"][-1]["obs_seq"] == rec["obs_seq"]
        assert "launch records" in capsys.readouterr().out

        # debug-index CLI mirrors the index page
        cmd_main(["debug-index", "--pprof-laddr", srv.laddr])
        out = capsys.readouterr().out
        for path, _ in DEBUG_ENDPOINTS:
            assert path in out
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# HBM ledger integration: the real pools feed it
# ---------------------------------------------------------------------------

def test_hbm_ledger_real_pools():
    from tendermint_tpu.ops import ed25519 as edops

    devobs.reset()
    # static basepoint comb: accounted on every access, not just build
    by, bm, bt = edops._base_comb()
    rep = devobs.ledger_report()
    want = int(by.nbytes) + int(bm.nbytes) + int(bt.nbytes)
    assert rep["base_comb"]["bytes"] == want > 0

    # pubkey-row cache: put() now charges real bytes (it charged 0
    # before ADR-021, leaving the byte ledger blind to the pool)
    pub_rows = np.zeros((32, 64), dtype=np.uint8)
    pub_rows[0] = np.arange(64, dtype=np.uint8)
    edops._pub_cache_get(pub_rows, 1)(0)
    rep = devobs.ledger_report()
    assert rep["pub_cache"]["bytes"] >= pub_rows.nbytes
    assert edops._pub_cache.total_bytes >= pub_rows.nbytes

    # staging: the mesh launch brackets its in-flight buffers — level
    # returns to zero, the high-water mark records the footprint
    pubs, msgs, sigs = _batch(16)
    assert edops.verify_batch(pubs, msgs, sigs).all()
    rep = devobs.ledger_report()
    assert rep["staging"]["bytes"] == 0
    assert rep["staging"]["peak_bytes"] > 0


# ---------------------------------------------------------------------------
# locksan: record/drain concurrency under the monitor (satellite 5)
# ---------------------------------------------------------------------------

@pytest.mark.locksan
def test_locksan_record_drain_concurrency():
    """A fresh DevObs built UNDER the lockset monitor (so its lock is
    wrapped and ranked), hammered by concurrent recorders + ledger
    writers while the main thread drains — the declared leaf ordering
    holds (the conftest fixture fails the test on any inversion)."""
    o = DevObs(capacity=64, enabled=True)
    o._metrics = DevObsMetrics(Registry("devobs_locksan"))
    stop = threading.Event()

    def recorder(k):
        i = 0
        while not stop.is_set() and i < 500:
            o.record({"path": "xla", "n": 48, "nb": 64, "shards": 1,
                      "first_launch": i == 0, "wall_s": 0.001,
                      "stage_s": 0.0005, "compute_s": 0.0005})
            o.ledger_add("staging", 64 if i % 2 == 0 else -64)
            i += 1

    threads = [threading.Thread(target=recorder, args=(k,))
               for k in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(50):
            o.publish_pending()
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
    o.publish_pending()
    assert o._metrics.device_compute.count(path="xla") > 0
    assert o.shed_counts()["chaos"] == 0


# ---------------------------------------------------------------------------
# config + bench surfaces
# ---------------------------------------------------------------------------

def test_config_devobs_section_and_slo_stream_roundtrip(tmp_path):
    from tendermint_tpu.config.config import Config

    cfg = Config(home=str(tmp_path))
    cfg.devobs.enable = False
    cfg.devobs.capacity = 77
    cfg.slo.device_launch_p99_ms = 12.5
    cfg.validate_basic()
    cfg.save()
    back = Config.load(str(tmp_path))
    assert back.devobs.enable is False
    assert back.devobs.capacity == 77
    assert back.slo.device_launch_p99_ms == 12.5
    assert back.slo.targets_s().get("device_launch") == \
        pytest.approx(0.0125)
    cfg.devobs.capacity = 0
    with pytest.raises(ValueError, match="devobs.capacity"):
        cfg.validate_basic()


def test_device_block_shape_and_bench_trend_compile_exclusion():
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    import bench_trend

    from tendermint_tpu.ops import ed25519 as edops

    # a real block: launches counted since the cursor, phases summed,
    # compile share computed
    devobs.reset()
    cur0 = devobs.cursor()
    pubs, msgs, sigs = _batch(16)
    assert edops.verify_batch(pubs, msgs, sigs).all()
    blk = devobs.device_block(since=cur0)
    assert blk["launches"] == 1
    # the production mesh launch is the overlapped compact ladder
    # (ADR-027): the window carries the overlapped decomposition, not
    # a serialized compute bracket
    assert blk["wall_s"] > 0
    assert "h2d_s" in blk["window"] and "drain_s" in blk["window"]
    assert 0.0 <= blk["compile_frac"] <= 1.0
    assert blk["compile_cache_entries"] >= 1
    assert blk["window"]["paths"]
    # a cursor past the launch sees nothing — the bench_report
    # per-config isolation
    assert devobs.device_block(since=devobs.cursor()) \
        .get("launches") == 0

    # satellite: bench_trend excludes compile-inflated rounds from the
    # REGRESSION-vs-best baseline (a cold compile cache measured 9x
    # slow must not poison later rounds OR set a bogus best)
    obs = [
        {"label": "r01", "value": 50_000.0, "rc": 0,
         "device": {"compile_frac": 0.85}},      # compile-dominated
        {"label": "r02", "value": 40_000.0, "rc": 0,
         "device": {"compile_frac": 0.01}},      # honest capture
        {"label": "r03", "value": 39_000.0, "rc": 0},  # no block: legacy
    ]
    rows = bench_trend.trend_rows(obs, 0.05)
    assert rows[0]["flag"].startswith("compile-inflated")
    # the inflated 50k did NOT become best: the honest 40k is best, and
    # 39k is only ~2.5% below it (not the 22% a 50k best would imply)
    assert rows[1]["flag"] == "best"
    assert not rows[2]["flag"].startswith("REGRESSION")
    # a genuine later regression against the honest best still flags
    rows2 = bench_trend.trend_rows(
        obs + [{"label": "r04", "value": 30_000.0, "rc": 0}], 0.05)
    assert rows2[3]["flag"].startswith("REGRESSION")
