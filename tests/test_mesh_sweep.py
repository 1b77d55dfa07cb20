"""Overlapped mesh data plane (ADR-027): chunk-knob arithmetic, the
budget ladder for comb table placement, topology-keyed plane
invalidation, global-plane gating/latching, lockstep propagation across
the degrade lane-worker boundary, and chaos at all three mesh seams —
plus the slow-tier bitmap-identity sweeps with REAL kernels across
shard counts, ragged remainders, chunked double-buffered staging, and
the comb repl/shard/eviction matrix.

Tier-1 keeps to host-side structure and the pre-compile chaos seams
(the injects fire before any XLA work); every real-kernel sweep is
slow-tier, same budget discipline as tests/test_comb.py.
"""
from __future__ import annotations

import os

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
        saved = (sharding._PLANE, sharding._PLANE_KEY,
                 sharding._GLOBAL_PLANE)
    sharding.set_mesh_chunk(None)
    sharding._poison_seen = False
    sharding._poison_next_check = 0.0
    fail.reset()
    edops._comb_enabled_override = None
    edops._comb_min_override = None
    edops._table_budget_override = None
    yield
    sharding.set_mesh_chunk(None)
    sharding._poison_seen = False
    sharding._poison_next_check = 0.0
    fail.reset()
    edops._comb_enabled_override = None
    edops._comb_min_override = None
    edops._table_budget_override = None
    with sharding._PLANE_LOCK:
        (sharding._PLANE, sharding._PLANE_KEY,
         sharding._GLOBAL_PLANE) = saved
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
    # the next probe drops all three latches for lazy rebuild
    with sharding._PLANE_LOCK:
        sharding._PLANE_KEY = ("stale", -1)
    assert sharding.invalidate_on_topology_change() is True
    assert sharding._PLANE is None and sharding._GLOBAL_PLANE is None
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
# tier-1: global-plane gating, the lockstep window, the failure latch
# ---------------------------------------------------------------------------


def test_global_plane_gating_and_failure_latch(monkeypatch):
    """global_plane() answers ONLY inside a lockstep() window on a
    multi-process runtime; a real collective fault latches it off
    until a topology re-probe clears the latch."""
    monkeypatch.delenv("TM_TPU_NO_MESH", raising=False)
    # single-process runtime: never ready, lockstep or not
    assert sharding.global_mesh_ready() is False
    with sharding.lockstep():
        assert sharding.global_plane() is None

    # pretend a multi-process runtime: still gated on lockstep
    monkeypatch.setattr(sharding.jax, "process_count", lambda: 2)
    assert sharding.global_mesh_ready() is True
    assert sharding.global_plane() is None          # not in lockstep
    with sharding.lockstep():
        assert sharding.in_lockstep()
        with sharding.lockstep():                   # re-entrant
            assert sharding.in_lockstep()
        gp = sharding.global_plane()
        assert gp is not None and gp.MESH_PATH == "global-mesh"
        # a real (non-chaos) collective fault latches the plane off
        sharding.disable_global_plane()
        assert sharding.global_plane() is None
    assert not sharding.in_lockstep()
    # the kill switches win over everything
    with sharding._PLANE_LOCK:
        sharding._GLOBAL_PLANE = None
    monkeypatch.setenv("TM_TPU_NO_GLOBAL_MESH", "1")
    with sharding.lockstep():
        assert sharding.global_plane() is None


def test_lockstep_propagates_across_lane_worker():
    """degrade.submit captures the caller's lockstep depth and re-arms
    it inside the lane worker (same discipline as the trace parent
    span): without it, every production dispatch would observe
    in_lockstep() == False on the worker thread and the global plane
    would be unreachable from the one call site built for it."""
    from tendermint_tpu.libs.metrics import Registry

    rt = degrade.configure(registry=Registry("mesh_lockstep"))
    try:
        seen = {}

        def probe():
            seen["locked"] = sharding.in_lockstep()
            return np.ones(4, dtype=bool)

        with sharding.lockstep():
            out = rt.run("batch.ed25519", probe,
                         lambda: np.zeros(4, dtype=bool))
        assert np.asarray(out).all()
        assert seen["locked"] is True

        out = rt.run("batch.ed25519", probe,
                     lambda: np.zeros(4, dtype=bool))
        assert np.asarray(out).all()
        assert seen["locked"] is False
    finally:
        degrade.reset()


# ---------------------------------------------------------------------------
# tier-1: chaos at the three mesh seams (pre-compile, so cheap)
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
    (ops/ed25519._comb_try catches and runs the single-device comb)."""
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


def test_global_plane_pins_static_chunk_lanes(monkeypatch):
    """The chunk count is part of the cross-process collective's
    shape, and the knob/env are steered PER-PROCESS: the global plane
    must pin the code-constant default while the local plane keeps
    following the governed knob — otherwise two peers steered across a
    power-of-two boundary launch mismatched chunk sequences into the
    same collective and deadlock."""
    monkeypatch.delenv("TM_TPU_MESH_CHUNK", raising=False)
    gp = sharding._GlobalDataPlane(
        sharding.make_mesh(sharding.jax.local_devices()))
    local = sharding.data_plane()
    assert local is not None
    static = sharding._static_chunk_lanes()
    assert static == sharding.mesh_chunk_lanes()  # untouched knob

    sharding.set_mesh_chunk(static // 2)           # steer the knob
    assert local._chunk_lanes() == static // 2
    assert gp._chunk_lanes() == static             # pinned
    monkeypatch.setenv("TM_TPU_MESH_CHUNK", str(static // 4))
    sharding.set_mesh_chunk(None)                  # env now governs
    assert local._chunk_lanes() == static // 4
    assert gp._chunk_lanes() == static             # still pinned


def test_barrier_propagates_real_rendezvous_failure(monkeypatch):
    """_barrier exists so no process dispatches into a collective a
    peer is still compiling: a REAL rendezvous failure (timeout,
    missing peer) must propagate so verify_batch's handler latches the
    plane off — only the no-service cases are silent no-ops."""
    boom = _FakeCoord(barrier_error=RuntimeError("barrier deadline"))
    monkeypatch.setattr(sharding, "_coord_client", lambda: boom)
    with pytest.raises(RuntimeError, match="barrier deadline"):
        sharding._barrier("tm_tpu_gmesh_step_64")
    # single-process / uninitialized runtime: no peers, no-op
    monkeypatch.setattr(sharding, "_coord_client", lambda: None)
    sharding._barrier("tm_tpu_gmesh_step_64")


def test_latch_poison_propagates_cross_process(monkeypatch):
    """disable_global_plane publishes a per-process poison key;
    global_plane() on a HEALTHY peer sees it and latches too — one
    faulted participant costs the job at most the in-flight batch, not
    one degrade timeout per peer per batch — and the topology re-probe
    that clears the local latch clears the poison directory with it."""
    coord = _FakeCoord()
    monkeypatch.setattr(sharding, "_coord_client", lambda: coord)
    monkeypatch.setattr(sharding.jax, "process_count", lambda: 2)
    monkeypatch.delenv("TM_TPU_NO_MESH", raising=False)
    monkeypatch.delenv("TM_TPU_NO_GLOBAL_MESH", raising=False)

    # the faulting process publishes its latch
    sharding.disable_global_plane()
    assert any(k.startswith(sharding._GMESH_POISON_DIR)
               for k in coord.kv)

    # a healthy peer with a LIVE plane latches on sight of the poison
    gp = sharding._GlobalDataPlane(
        sharding.make_mesh(sharding.jax.local_devices()))
    with sharding._PLANE_LOCK:
        sharding._GLOBAL_PLANE = gp
    sharding._poison_seen = False
    sharding._poison_next_check = 0.0
    with sharding.lockstep():
        assert sharding.global_plane() is None
    assert sharding._GLOBAL_PLANE is False

    # topology re-probe clears the local latch AND the poison keys
    assert sharding.data_plane() is not None   # populate _PLANE
    with sharding._PLANE_LOCK:
        sharding._PLANE_KEY = ("stale", -1)
    assert sharding.invalidate_on_topology_change() is True
    assert not coord.kv
    assert sharding._poison_seen is False


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


def test_lockstep_wedge_latches_global_plane_on_first_timeout():
    """A coordinated (lockstep) launch that wedges past the launch
    deadline on a multi-process runtime is the global collective's
    signature hang — a peer never entered, and the worker thread never
    returns, so verify_batch's exception handler can't latch.  The
    degrade settle latches on the FIRST such timeout, bounding the
    job-wide convergence to one hung batch per process instead of one
    launch deadline per subsequent batch."""
    import threading as th
    import unittest.mock as mock

    from tendermint_tpu.libs.metrics import Registry

    cfg = degrade.DegradeConfig()
    cfg.launch_timeout_s = 0.05
    rt = degrade.configure(cfg, registry=Registry("mesh_wedge"))
    release = th.Event()

    def wedged():
        release.wait(5.0)
        return np.ones(4, dtype=bool)

    try:
        with mock.patch.object(sharding.jax, "process_count",
                               lambda: 2):
            with sharding._PLANE_LOCK:
                sharding._GLOBAL_PLANE = None
            with sharding.lockstep():
                out = rt.run("batch.ed25519", wedged,
                             lambda: np.zeros(4, dtype=bool))
            assert not np.asarray(out).any()       # host fallback
            assert sharding._GLOBAL_PLANE is False  # first wedge latched

            # a NON-lockstep wedge never touches the global latch
            with sharding._PLANE_LOCK:
                sharding._GLOBAL_PLANE = None
            out = rt.run("batch.ed25519", wedged,
                         lambda: np.zeros(4, dtype=bool))
            assert not np.asarray(out).any()
            assert sharding._GLOBAL_PLANE is None
    finally:
        release.set()
        degrade.reset()


def test_chaos_global_plane_seam_fires_before_any_collective():
    """sharding.global_plane injects at the top of the global compact
    launch — BEFORE the AOT compile/barrier — so a chaos raise degrades
    the batch without ever entering a collective a peer would wait
    on."""
    gp = sharding._GlobalDataPlane(
        sharding.make_mesh(sharding.jax.local_devices()))
    fail.set_mode("sharding.global_plane", "raise")
    pubs, msgs, sigs = _batch(9, tag=b"gchaos")
    with pytest.raises(fail.InjectedFault):
        gp.verify_batch(pubs, msgs, sigs)
    assert fail.fired("sharding.global_plane", "raise") >= 1


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
