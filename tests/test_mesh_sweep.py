"""Overlapped mesh data plane (ADR-027): chunk-knob arithmetic, the
budget ladder for comb table placement, topology-keyed plane
invalidation, and chaos at the two mesh seams —
plus the slow-tier bitmap-identity sweeps with REAL kernels across
shard counts, ragged remainders, chunked double-buffered staging, and
the comb repl/shard/eviction matrix.

Tier-1 keeps to host-side structure and the pre-compile chaos seams
(the injects fire before any XLA work); every real-kernel sweep is
slow-tier, same budget discipline as tests/test_comb.py.
"""
from __future__ import annotations

import numpy as np
import pytest

from tendermint_tpu.crypto import _edref
from tendermint_tpu.crypto import degrade
from tendermint_tpu.crypto import devobs
from tendermint_tpu.libs import fail
from tendermint_tpu.ops import ed25519 as edops
from tendermint_tpu.parallel import sharding


@pytest.fixture(autouse=True)
def _mesh_state():
    """Each test starts from a clean mesh world: default chunk knob, no
    armed chaos, no comb overrides, and the plane latches restored.

    The process-wide plane OBJECT is saved and put back, never dropped:
    its _fns dict holds every mesh bucket the suite has compiled so
    far, and replacing it with None would force each later test file
    to recompile those buckets (tens of seconds per file)."""
    with sharding._PLANE_LOCK:
        saved = (sharding._PLANE, sharding._PLANE_KEY)
    sharding.set_mesh_chunk(None)
    fail.reset()
    edops._comb_enabled_override = None
    edops._comb_min_override = None
    edops._table_budget_override = None
    yield
    sharding.set_mesh_chunk(None)
    fail.reset()
    edops._comb_enabled_override = None
    edops._comb_min_override = None
    edops._table_budget_override = None
    with sharding._PLANE_LOCK:
        sharding._PLANE, sharding._PLANE_KEY = saved
    degrade.reset()


def _batch(n, pool=None, tag=b"sweep"):
    seeds = [(0x6B00 + (i % pool if pool else i)).to_bytes(32, "little")
             for i in range(n)]
    msgs = [b"%s %d" % (tag, i) for i in range(n)]
    pubs = [_edref.pubkey_from_seed(s) for s in seeds]
    sigs = [_edref.sign(s, m) for s, m in zip(seeds, msgs)]
    return pubs, msgs, sigs


def _oracle(pubs, msgs, sigs):
    out = np.zeros(len(pubs), dtype=bool)
    for i in range(len(pubs)):
        try:
            out[i] = bool(_edref.verify(bytes(pubs[i]), bytes(msgs[i]),
                                        bytes(sigs[i])))
        except Exception:  # noqa: BLE001 - malformed = invalid
            out[i] = False
    return out


def _corrupt(sigs, *lanes):
    sigs = list(sigs)
    for i in lanes:
        sigs[i] = sigs[i][:32] + bytes(32)
    return sigs


class _FakeEntry:
    """comb_mesh_mode consults only k_pad; the chaos seam fires before
    any table attribute is touched."""

    def __init__(self, k_pad=8):
        self.k_pad = k_pad
        self.mesh_repl = None
        self.mesh_shard = None


# ---------------------------------------------------------------------------
# tier-1: the chunk knob (raw coordinate vs pow2-floored effective)
# ---------------------------------------------------------------------------


def test_chunk_knob_pow2_floor_clamp_and_revert(monkeypatch):
    """The control plane steers the RAW value; the EFFECTIVE chunk is
    its power-of-two floor inside [_MESH_CHUNK_MIN, MAX_CHUNK] — so
    additive knob steps always move the raw coordinate (recovery can
    climb back to static) while launches stay in known compile
    buckets."""
    monkeypatch.delenv("TM_TPU_MESH_CHUNK", raising=False)
    assert sharding.mesh_chunk_raw() == sharding.MESH_CHUNK_DEFAULT
    assert sharding.mesh_chunk_lanes() == sharding.MESH_CHUNK_DEFAULT

    sharding.set_mesh_chunk(3000)          # raw moves exactly
    assert sharding.mesh_chunk_raw() == 3000
    assert sharding.mesh_chunk_lanes() == 2048   # pow2 floor
    sharding.set_mesh_chunk(4096 + 1024)   # a knob step past a pow2
    assert sharding.mesh_chunk_lanes() == 4096
    sharding.set_mesh_chunk(7)             # clamped at the floor
    assert sharding.mesh_chunk_lanes() == sharding._MESH_CHUNK_MIN
    sharding.set_mesh_chunk(10 ** 9)       # clamped at MAX_CHUNK
    assert sharding.mesh_chunk_lanes() == \
        1 << (edops.MAX_CHUNK.bit_length() - 1)

    sharding.set_mesh_chunk(None)          # revert to env/default
    monkeypatch.setenv("TM_TPU_MESH_CHUNK", "600")
    assert sharding.mesh_chunk_raw() == 600
    assert sharding.mesh_chunk_lanes() == 512
    monkeypatch.setenv("TM_TPU_MESH_CHUNK", "junk")
    assert sharding.mesh_chunk_raw() == sharding.MESH_CHUNK_DEFAULT


# ---------------------------------------------------------------------------
# tier-1: the comb table-placement budget ladder
# ---------------------------------------------------------------------------


def test_comb_mesh_mode_budget_ladder():
    """repl while TWO table copies fit (the build copy + one replica
    per device), shard while table + 1/nshard slice fits AND the
    validator bucket divides the mesh, None below that — never the
    ladder."""
    plane = sharding.data_plane()
    assert plane is not None and plane.nshard >= 2
    tb = edops._TABLE_BYTES_PER_KEY
    entry = _FakeEntry(k_pad=8)

    edops._table_budget_override = 2 * 8 * tb
    assert plane.comb_mesh_mode(entry) == "repl"
    edops._table_budget_override = 8 * tb + (8 * tb) // plane.nshard
    assert plane.comb_mesh_mode(entry) == "shard"
    edops._table_budget_override = 8 * tb + (8 * tb) // plane.nshard - 1
    assert plane.comb_mesh_mode(entry) is None
    # a validator bucket the mesh doesn't divide can't shard its table
    odd = _FakeEntry(k_pad=plane.nshard * 8 + 1)
    edops._table_budget_override = odd.k_pad * tb * 2 - 1
    if odd.k_pad % plane.nshard:
        assert plane.comb_mesh_mode(odd) is None


# ---------------------------------------------------------------------------
# tier-1: topology-keyed plane invalidation (the degrade re-probe seam)
# ---------------------------------------------------------------------------


def test_topology_invalidation_drops_stale_plane(monkeypatch):
    plane = sharding.data_plane()
    assert plane is not None
    # same topology: the latch holds, nothing dropped
    assert sharding.invalidate_on_topology_change() is False
    assert sharding.data_plane() is plane
    # the device list the plane latched on is gone (backend flap):
    # the next probe drops the latch for lazy rebuild
    with sharding._PLANE_LOCK:
        sharding._PLANE_KEY = ("stale", -1)
    assert sharding.invalidate_on_topology_change() is True
    assert sharding._PLANE is None
    fresh = sharding.data_plane()
    assert fresh is not None and fresh is not plane

    # the NO_MESH latch records its topology too: a re-probe on the
    # same device list must NOT thrash the forced-off plane
    monkeypatch.setenv("TM_TPU_NO_MESH", "1")
    with sharding._PLANE_LOCK:
        sharding._PLANE = None
        sharding._PLANE_KEY = None
    assert sharding.data_plane() is None
    assert sharding._PLANE is False
    assert sharding._PLANE_KEY is not None
    assert sharding.invalidate_on_topology_change() is False
    assert sharding._PLANE is False


def test_a_tpu_host_stays_on_one_device_until_the_mesh_is_switched_on(
        monkeypatch):
    """PR 21: the TPU mesh step runs, but a cold bucket of it costs
    minutes and chip_smoke.py has not passed with it on — so on a
    multi-chip TPU host the plane is off until MESH_ON_TPU says
    otherwise.  The forced-host-device CPU mesh is untouched."""
    monkeypatch.delenv("TM_TPU_NO_MESH", raising=False)

    def probe():
        with sharding._PLANE_LOCK:
            sharding._PLANE = None
            sharding._PLANE_KEY = None
        return sharding.data_plane()

    assert sharding.MESH_ON_TPU is False
    assert probe() is not None                      # CPU mesh: engages
    monkeypatch.setattr(edops, "_use_pallas", lambda: True)
    assert probe() is None and sharding._PLANE is False
    monkeypatch.setattr(sharding, "MESH_ON_TPU", True)
    assert probe() is not None


# ---------------------------------------------------------------------------
# tier-1: chaos at the two mesh seams (pre-compile, so cheap)
# ---------------------------------------------------------------------------


def test_chaos_mesh_stage_degrades_to_single_device(monkeypatch):
    """A raise at sharding.mesh_stage falls THIS batch back to the
    single-device ladder — the mesh fault is caught inside
    ops/ed25519.verify_batch, never escaping to the degrade runtime.
    The ladder itself is stubbed to the host oracle (keeping the seam
    pre-compile: the slow sweeps below pin the real-kernel bitmap);
    what this test owns is the route — chaos fires, the fallback takes
    the single-device path, and the host_ok mask/slice plumbing holds."""
    assert sharding.data_plane() is not None
    fail.set_mode("sharding.mesh_stage", "raise")
    pubs, msgs, sigs = _batch(13, tag=b"stage-chaos")
    sigs = _corrupt(sigs, 5)
    truth = _oracle(pubs, msgs, sigs)
    hit = {}

    def _ladder_stub(*arrs):
        hit["nb"] = int(arrs[0].shape[0])
        return edops.jnp.asarray(
            np.pad(truth, (0, hit["nb"] - len(truth))))

    monkeypatch.setattr(edops, "verify_kernel", _ladder_stub)
    bm = edops.verify_batch(pubs, msgs, sigs)
    assert fail.fired("sharding.mesh_stage", "raise") >= 1
    assert hit["nb"] == edops.bucket_size(13)
    ll = edops.last_launch()
    assert ll["shards"] == 1 and ll["path"] != "mesh-xla"
    assert (bm == truth).all()


def test_chaos_mesh_comb_seam_fires_before_any_launch():
    """The sharding.mesh_comb inject sits after the budget decision and
    before any staging/dispatch: arming it raises out of verify_comb
    (ops/ed25519._run_comb catches and runs the single-device comb)."""
    plane = sharding.data_plane()
    assert plane is not None
    edops._table_budget_override = 10 ** 12     # mode 'repl' for sure
    fail.set_mode("sharding.mesh_comb", "raise")
    with pytest.raises(fail.InjectedFault):
        plane.verify_comb(np.zeros((8, 32), np.uint8),
                          np.zeros((8, 64), np.int8),
                          np.zeros((8, 64), np.int8),
                          np.zeros(8, np.int32), _FakeEntry(), None)
    assert fail.fired("sharding.mesh_comb", "raise") >= 1
    # a declined budget never reaches the seam: the caller falls to the
    # single-device comb without a chaos hit
    fired0 = fail.fired("sharding.mesh_comb", "raise")
    edops._table_budget_override = 1
    assert plane.verify_comb(np.zeros((8, 32), np.uint8),
                             np.zeros((8, 64), np.int8),
                             np.zeros((8, 64), np.int8),
                             np.zeros(8, np.int32),
                             _FakeEntry(), None) is None
    assert fail.fired("sharding.mesh_comb", "raise") == fired0


class _FakeCoord:
    """A stand-in jax.distributed coordination client: a dict-backed
    KV store plus a barrier log (wait_at_barrier raising is the real
    client's timeout shape)."""

    def __init__(self, barrier_error=None):
        self.kv = {}
        self.barriers = []
        self.barrier_error = barrier_error

    def key_value_set(self, key, val):
        self.kv[key] = val

    def key_value_dir_get(self, d):
        return [(k, v) for k, v in sorted(self.kv.items())
                if k.startswith(d)]

    def key_value_delete(self, key):
        pref = key.rstrip("/")
        for k in [k for k in self.kv if k.startswith(pref)]:
            del self.kv[k]

    def wait_at_barrier(self, name, timeout_ms):
        if self.barrier_error is not None:
            raise self.barrier_error
        self.barriers.append(name)


def test_mesh_tables_ledger_charges_once_under_race():
    """Two threads racing the first comb-table replication both
    device_put (benign — one copy wins the slot) but the mesh_tables
    ledger must be charged exactly once: _table_evicted frees the
    winning tuple's bytes once, so a double charge would drift the
    gauge upward forever."""
    import threading as th

    plane = sharding.data_plane()
    assert plane is not None
    k_pad = 4
    tables = type("T", (), {})()
    for name in ("ypx", "ymx", "z", "t2d"):
        setattr(tables, name, np.zeros((1, 1, 1, k_pad), np.uint32))
    entry = _FakeEntry(k_pad=k_pad)
    entry.tables = tables
    entry.dec_ok = np.ones(k_pad, dtype=bool)
    entry.index = ()                   # _table_evicted walks the keys
    base = (np.zeros(1, np.uint32),) * 3
    tbytes = (plane.nshard - 1) * k_pad * edops._TABLE_BYTES_PER_KEY

    devobs.reset()
    devobs.enable()
    try:
        start = th.Barrier(4)
        outs = []

        def racer():
            start.wait()
            outs.append(plane._comb_repl_operands(entry, base))

        threads = [th.Thread(target=racer) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # every racer got the SAME committed tuple, charged once
        assert all(o is outs[0] for o in outs)
        rep = devobs.OBS.ledger_report()
        assert rep["mesh_tables"]["bytes"] == tbytes
        # eviction frees exactly what was charged: the gauge returns
        # to zero instead of drifting
        edops._table_evicted("race-set", entry)
        rep = devobs.OBS.ledger_report()
        assert rep["mesh_tables"]["bytes"] == 0
    finally:
        devobs.reset()
        devobs.enable()


# ---------------------------------------------------------------------------
# slow: bitmap-identity sweeps with REAL kernels
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_ladder_bitmap_identity_across_shard_counts():
    """The overlapped compact ladder at 2/4/8 shards, ragged remainders
    included: bitmap identical to the host oracle and the single-device
    ladder, pad lanes never valid, the psum'd all_valid bit recorded,
    every bucket a CompileSentinel-known shape."""
    from tendermint_tpu.devtools.tmlint.runtime import CompileSentinel

    devs = sharding.jax.local_devices()
    assert len(devs) >= 8
    pubs, msgs, sigs = _batch(13, tag=b"ladder-sweep")
    sigs = _corrupt(sigs, 3, 11)
    truth = _oracle(pubs, msgs, sigs)

    edops._comb_enabled_override = False        # pin the ladder
    single = None
    for k in (2, 4, 8):
        plane = sharding._DataPlane(sharding.make_mesh(devs[:k]))
        bm = plane.verify_batch(pubs, msgs, sigs)
        ll = edops.last_launch()
        assert ll["path"] == "mesh-xla" and ll["shards"] == k
        assert ll["nb"] % k == 0
        assert CompileSentinel.bucket_allowed(ll["nb"], k), ll
        assert ll["all_valid"] is False
        assert (bm == truth).all(), (k, bm, truth)
        single = bm if single is None else single
        assert (bm == single).all()


@pytest.mark.slow
def test_chunked_staging_overlap_and_identity():
    """Forcing the chunk knob to the floor on a 2-shard plane makes the
    nb=1024 bucket a 2-chunk double-buffered launch: chunk_overlap
    lands in the record (> 0: the second chunk's puts are issued while
    chunk one computes), per-shard put walls cover both chunks, and the
    bitmap stays identical to the host oracle."""
    devs = sharding.jax.local_devices()
    devobs.enable()
    try:
        plane = sharding._DataPlane(sharding.make_mesh(devs[:2]))
        sharding.set_mesh_chunk(256)            # chunk = 2 * 256 = 512
        pubs, msgs, sigs = _batch(700, tag=b"chunk-sweep")
        sigs = _corrupt(sigs, 650)
        bm = plane.verify_batch(pubs, msgs, sigs)
        ll = edops.last_launch()
        assert ll["path"] == "mesh-xla" and ll["nb"] == 1024
        assert ll["chunks"] == 2
        assert ll["chunk_overlap"] > 0.0
        assert len(ll["shard_h2d_s"]) == 2
        assert not bm[650] and bm[:650].all() and bm[651:].all()
    finally:
        devobs.disable()


@pytest.mark.slow
def test_comb_placement_matrix_subset_and_eviction():
    """The budget matrix with real kernels: replicated mesh comb,
    sharded-table gather layout (tight budget), single-device comb
    (budget below a slice), each bitwise-identical to the host oracle;
    the mesh_tables ledger charges replicas and frees them on
    eviction; a SUBSET batch after eviction still verifies exactly."""
    plane = sharding.data_plane()
    assert plane is not None and plane.nshard >= 8
    devobs.enable()
    edops._comb_min_override = 1
    tb = edops._TABLE_BYTES_PER_KEY
    try:
        pubs, msgs, sigs = _batch(23, pool=8, tag=b"comb-sweep")
        sigs = _corrupt(sigs, 7)
        truth = _oracle(pubs, msgs, sigs)

        # replicated: nshard-1 extra copies on the mesh_tables books
        bm = edops.verify_batch(pubs, msgs, sigs, cache_pubs=True)
        ll = edops.last_launch()
        assert ll["path"] == "mesh-comb" and ll["shards"] == plane.nshard
        assert (bm == truth).all()
        ledger = devobs.ledger_report()["mesh_tables"]["bytes"]
        assert ledger >= (plane.nshard - 1) * 8 * tb

        # subset of the cached set rides the same tables (no rebuild);
        # wide enough for worth_sharding on the 8-way mesh
        sub = [0, 2, 5, 7, 11, 13, 16, 19, 21]
        bs = edops.verify_batch([pubs[i] for i in sub],
                                [msgs[i] for i in sub],
                                [sigs[i] for i in sub])
        assert edops.last_launch()["path"] == "mesh-comb"
        assert not edops.last_launch()["table_build"]
        assert (bs == truth[sub]).all()

        # mid-run eviction frees the replicas; the next subset call
        # re-resolves (rebuild on this cache_pubs batch) — exact bitmap
        edops.table_cache_clear()
        assert devobs.ledger_report()["mesh_tables"]["bytes"] == 0
        bs2 = edops.verify_batch([pubs[i] for i in sub],
                                 [msgs[i] for i in sub],
                                 [sigs[i] for i in sub],
                                 cache_pubs=True)
        assert (bs2 == truth[sub]).all()

        # tight budget: the sharded-table gather layout, same bitmap
        edops.table_cache_clear()
        edops._table_budget_override = 8 * tb + (8 * tb) // plane.nshard
        bm2 = edops.verify_batch(pubs, msgs, sigs, cache_pubs=True)
        assert edops.last_launch()["path"] == "mesh-comb-sharded"
        assert (bm2 == truth).all()

        # below a slice: single-device comb, NOT the ladder
        edops.table_cache_clear()
        edops._table_budget_override = 8 * tb + tb // 4
        bm3 = edops.verify_batch(pubs, msgs, sigs, cache_pubs=True)
        ll3 = edops.last_launch()
        assert ll3["path"] == "comb" and ll3["shards"] == 1
        assert (bm3 == truth).all()
    finally:
        devobs.disable()
        edops.table_cache_clear()   # this test's tables, not the suite's
