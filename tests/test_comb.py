"""Fixed-base comb verify path (ops/ed25519 + ops/curve, ADR-013).

Two tiers, split by XLA compile cost (the tier-1 budget has no headroom
for another kernel family — the guard tests below pin exactly that):

  * tier-1: structure and routing.  Group-op inventory by TRACING the
    kernels with instrumented curve ops (jax.eval_shape runs the Python
    body once, so the comb's zero doublings and the >= 2.5x group-op
    reduction are counted, not asserted from constants); lane/validator
    bucket guards (the comb reuses the ladder's bucket_size buckets —
    no new XLA shape family); the unified DeviceLRU (bounds under
    concurrency, the old _pub_cache one-over-bound race); comb routing
    with stubbed kernels (build/hit/subset/mixed/eviction/budget); the
    chaos matrix at the comb site (corrupt-bitmap caught by degrade's
    spot check, raise degrades, bitmaps exact).
  * slow: the bitmap-identity sweep with REAL kernels (comb vs ladder
    vs the host bignum oracle over valid/invalid/torsion/non-canonical
    encodings, mixed known+unknown keys, eviction mid-stream), the
    8-device CPU mesh path, the VerifyScheduler lane, and jit-vs-eager
    equality of the comb kernel itself.
"""
from __future__ import annotations

import threading

import numpy as np
import pytest

from tendermint_tpu.crypto import _edref
from tendermint_tpu.crypto import batch as cb
from tendermint_tpu.crypto import degrade
from tendermint_tpu.crypto import ed25519 as edkeys
from tendermint_tpu.libs import fail
from tendermint_tpu.libs.metrics import Registry
from tendermint_tpu.ops import curve as C
from tendermint_tpu.ops import ed25519 as edops
from tendermint_tpu.ops import field as F


@pytest.fixture(autouse=True)
def _comb_state():
    """Every test starts from a clean comb world: empty table cache, no
    config overrides, no armed chaos modes, fresh degrade runtime."""
    edops.table_cache_clear()
    edops._comb_enabled_override = None
    edops._comb_min_override = None
    edops._table_budget_override = None
    fail.reset()
    yield
    edops.table_cache_clear()
    edops._comb_enabled_override = None
    edops._comb_min_override = None
    edops._table_budget_override = None
    fail.reset()
    degrade.reset()


def _batch(n, pool=6, tag=b"comb"):
    seeds = [(0x7A00 + i % pool).to_bytes(32, "little") for i in range(n)]
    msgs = [b"%s vote %d" % (tag, i) for i in range(n)]
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


def _stub_kernels(monkeypatch, record=None, bits_for=None):
    """Replace the comb kernels with shape-checking stubs so routing
    tests never pay an XLA compile.  bits_for(nb) supplies the 'device'
    bitmap (defaults to all-true); record collects launch shapes."""
    import jax.numpy as jnp

    def build(pub):
        k = pub.shape[0]
        if record is not None:
            record.setdefault("builds", []).append(k)
        return C.Cached(None, None, None, None), jnp.ones(k, dtype=bool)

    def kernel(r, sd, kd, vidx, ty, tm, tz, td, dok, by, bm, bt):
        nb = r.shape[0]
        assert sd.shape == (nb, 64) and kd.shape == (nb, 64)
        assert vidx.shape == (nb,)
        if record is not None:
            record.setdefault("launches", []).append(nb)
        if bits_for is not None:
            return jnp.asarray(bits_for(nb))
        return jnp.ones(nb, dtype=bool)

    monkeypatch.setattr(edops, "comb_build_kernel", build)
    monkeypatch.setattr(edops, "comb_kernel", kernel)
    monkeypatch.setattr(edops, "_base_comb", lambda: (None, None, None))
    # stubbed tests are single-device: the conftest's 8-device CPU mesh
    # would route through the REAL jitted mesh comb (an XLA compile)
    from tendermint_tpu.parallel import sharding
    monkeypatch.setattr(sharding, "_PLANE", False)


# ---------------------------------------------------------------------------
# tier-1: group-op inventory by tracing (no compile)
# ---------------------------------------------------------------------------


# captured ONCE at import: repeated _count_group_ops calls re-patch the
# same attributes, and capturing at call time would nest the wrappers
_REAL_OPS = {n: getattr(C, n)
             for n in ("dbl", "dbl_no_t", "add_cached", "madd_niels")}


def _count_group_ops(monkeypatch, fn, *avals):
    """Trace fn over shape avals with instrumented curve group ops.
    Control-flow bodies are traced a small fixed number of times; the
    caller measures that multiplicity with a probe."""
    import jax

    counts = {"dbl": 0, "add": 0}

    def wrap(name, bucket):
        def inner(*a, **kw):
            counts[bucket] += 1
            return _REAL_OPS[name](*a, **kw)
        return inner

    monkeypatch.setattr(C, "dbl", wrap("dbl", "dbl"))
    monkeypatch.setattr(C, "dbl_no_t", wrap("dbl_no_t", "dbl"))
    monkeypatch.setattr(C, "add_cached", wrap("add_cached", "add"))
    monkeypatch.setattr(C, "madd_niels", wrap("madd_niels", "add"))
    jax.eval_shape(fn, *avals)
    return counts


def test_group_op_inventory_traced(monkeypatch):
    """The acceptance arithmetic, counted from the kernels themselves:
    the comb performs ZERO doublings and >= 2.5x fewer group ops per
    launch than the ladder; the published constants can't drift.

    jax may trace a loop body MORE than once (scan traces for aval
    discovery and again for the final jaxpr), so the loop-body
    multiplicity is measured with a one-op probe first."""
    import jax

    B, K = 8, 8
    i32 = np.int32
    sds = jax.ShapeDtypeStruct
    ext = C.Ext(*(sds((F.NLIMB, B), i32) for _ in range(4)))
    dig = sds((64, B), i32)

    # trace multiplicity of a fori body / a scan body (one dbl each)
    m_fori = _count_group_ops(
        monkeypatch,
        lambda p: jax.lax.fori_loop(0, 64, lambda i, q: C.dbl(q), p),
        ext)["dbl"]
    m_scan = _count_group_ops(
        monkeypatch,
        lambda p: jax.lax.scan(lambda g, _: (C.dbl(g), g.x), p, None,
                               length=64),
        ext)["dbl"]
    assert m_fori >= 1 and m_scan >= 1

    # ladder: one var-table build + 64 fori iterations
    tab = _count_group_ops(monkeypatch, edops._build_var_table, ext)
    assert (tab["dbl"], tab["add"]) == (4, 3)
    lad = _count_group_ops(monkeypatch, edops.straus_ladder,
                           ext, dig, dig)
    body_dbl, rd = divmod(lad["dbl"] - tab["dbl"], m_fori)
    body_add, ra = divmod(lad["add"] - tab["add"], m_fori)
    assert rd == 0 and ra == 0, lad
    ladder_total = {"doublings": tab["dbl"] + 64 * body_dbl,
                    "adds": tab["add"] + 64 * body_add}
    assert ladder_total == edops.LADDER_GROUP_OPS

    # comb: 64 iterations of two additions, nothing else
    comb = _count_group_ops(
        monkeypatch, edops.comb_verify_staged,
        sds((B, 32), np.uint8), sds((B, 64), np.int8),
        sds((B, 64), np.int8), sds((B,), i32),
        *(sds((64, 9, F.NLIMB, K), i32) for _ in range(4)),
        sds((K,), np.bool_),
        *(sds((64, 9, F.NLIMB), i32) for _ in range(3)))
    assert comb["dbl"] == 0
    body_add, ra = divmod(comb["add"], m_fori)
    assert ra == 0, comb
    comb_total = {"doublings": 0, "adds": 64 * body_add}
    assert comb_total == edops.COMB_GROUP_OPS

    lad_ops = ladder_total["doublings"] + ladder_total["adds"]
    comb_ops = comb_total["adds"]
    assert lad_ops / comb_ops >= 2.5, (lad_ops, comb_ops)

    # the build scan amortizes: 5 doublings + 3 additions per window,
    # paid once per validator SET, not per signature
    bld = _count_group_ops(monkeypatch, edops.comb_build_kernel_impl,
                           sds((K, 32), np.uint8))
    assert (bld["dbl"], bld["add"]) == (5 * m_scan, 3 * m_scan)


def test_comb_reuses_ladder_lane_buckets(monkeypatch):
    """Tier-1 shape guard: the comb kernel pads its batch axis with the
    SAME bucket_size buckets as every other kernel (floor nb=64) and
    pads the validator axis to powers of two (floor 8) — no new XLA
    shape family for the compile budget to absorb."""
    rec = {}
    _stub_kernels(monkeypatch, record=rec)
    monkeypatch.setattr(edops, "_comb_min_override", 1)
    for n in (5, 24, 64, 90):
        pubs, msgs, sigs = _batch(n, pool=min(n, 6), tag=b"bkt%d" % n)
        out = edops.verify_batch(pubs, msgs, sigs, cache_pubs=True)
        assert out.shape == (n,)
        assert edops.last_launch()["nb"] == edops.bucket_size(n)
    assert rec["launches"] == [edops.bucket_size(n)
                               for n in (5, 24, 64, 90)]
    for k in rec["builds"]:
        assert k >= 8 and (k & (k - 1)) == 0, rec["builds"]


# ---------------------------------------------------------------------------
# tier-1: the unified DeviceLRU
# ---------------------------------------------------------------------------


def test_device_lru_bounds_and_recency():
    evicted = []
    lru = edops.DeviceLRU(max_entries=3,
                          on_evict=lambda k, v: evicted.append(k))
    for i in range(5):
        lru.put(i, f"v{i}")
    assert len(lru) == 3 and evicted == [0, 1]
    assert lru.get(2) == "v2"   # refresh recency
    lru.put(9, "v9")
    assert 2 in lru and 3 not in lru  # 3 was oldest after the refresh
    assert lru.hits == 1 and lru.evictions == 3


def test_device_lru_byte_bound_and_first_wins():
    lru = edops.DeviceLRU(max_bytes=100)
    lru.put("a", 1, nbytes=60)
    lru.put("b", 2, nbytes=60)       # over budget: evicts a
    assert "a" not in lru and lru.total_bytes == 60
    assert lru.put("b", 3, nbytes=60) == 2  # racing upload: first wins
    assert lru.total_bytes == 60
    # a single entry larger than the budget is kept, not thrashed
    lru2 = edops.DeviceLRU(max_bytes=10)
    lru2.put("big", 1, nbytes=50)
    assert "big" in lru2


def test_device_lru_never_over_bound_under_concurrency():
    """The regression the old _pub_cache had: a hit's pop/re-insert
    racing a filler left the dict one over _PUB_CACHE_MAX.  Hammer
    get/put from many threads and assert the bound holds at every
    observation point."""
    lru = edops.DeviceLRU(max_entries=4)
    stop = threading.Event()
    violations = []

    def hammer(tid):
        rng = np.random.default_rng(tid)
        while not stop.is_set():
            k = int(rng.integers(0, 12))
            if lru.get(k) is None:
                lru.put(k, k)

    def watch():
        while not stop.is_set():
            n = len(lru)
            if n > 4:
                violations.append(n)

    threads = [threading.Thread(target=hammer, args=(t,), daemon=True)
               for t in range(6)] + \
        [threading.Thread(target=watch, daemon=True)]
    for t in threads:
        t.start()
    import time
    time.sleep(0.5)
    stop.set()
    for t in threads:
        t.join(timeout=5)
    assert not violations, violations
    assert len(lru) <= 4


# ---------------------------------------------------------------------------
# tier-1: routing (stubbed kernels — no compile)
# ---------------------------------------------------------------------------


def test_comb_routing_build_hit_subset_mixed(monkeypatch):
    rt = degrade.configure(registry=Registry("comb_route"))
    rec = {}
    _stub_kernels(monkeypatch, record=rec)
    monkeypatch.setattr(edops, "_comb_min_override", 8)

    pubs, msgs, sigs = _batch(24)
    # below the build threshold without tables: ladder, no build
    assert edops._comb_resolve(pubs[:4], True) is None
    assert "builds" not in rec

    # build + engage
    out = edops.verify_batch(pubs, msgs, sigs, cache_pubs=True)
    assert out.all() and rec["builds"] == [8]
    ll = edops.last_launch()
    assert ll["path"] == "comb" and ll["table_build"] and ll["set_k"] == 6
    assert ll["group_ops"]["doublings"] == 0

    # hit: same set, no cache_pubs (the scheduler-lane shape)
    assert edops.verify_batch(pubs, msgs, sigs).all()
    assert rec["builds"] == [8] and not edops.last_launch()["table_build"]
    assert rt.metrics.table_hits.value() == 1
    assert rt.metrics.table_cache_bytes.value() == \
        edops._table_cache.total_bytes > 0

    # subset of the set resolves through the key-level index
    assert edops.verify_batch(pubs[:5], msgs[:5], sigs[:5]).all()
    assert edops.last_launch()["path"] == "comb"
    assert rt.metrics.table_hits.value() == 2

    # mixed known+unknown keys: the whole batch ladders (stub would
    # have recorded a launch)
    s2 = (0x9911).to_bytes(32, "little")
    launches = len(rec["launches"])
    out = edops.verify_batch(
        pubs[:3] + [_edref.pubkey_from_seed(s2)],
        msgs[:3] + [b"m"], sigs[:3] + [_edref.sign(s2, b"m")])
    assert out.all() and len(rec["launches"]) == launches
    assert edops.last_launch()["path"] == "xla"


def test_table_build_compile_stays_off_the_launch_record(monkeypatch):
    """launch_kernel's trace + compile seconds go to whoever takes them
    next on the thread.  The table build takes its own (its span and
    crypto_device_compile_seconds{site="comb-build"}) and verify_batch
    drops leftovers at entry, so the comb launch that follows records
    only its own kernel's — here none, the kernel is a stub."""
    import jax

    rt = degrade.configure(registry=Registry("comb_compile"))
    _stub_kernels(monkeypatch)
    monkeypatch.setattr(edops, "comb_build_kernel",
                        jax.jit(edops.comb_build_kernel))
    monkeypatch.setattr(edops, "_comb_min_override", 8)
    pubs, msgs, sigs = _batch(24)
    edops._compile_tls.s = 9.0     # left by a dispatch that raised
    assert edops.verify_batch(pubs, msgs, sigs, cache_pubs=True).all()
    ll = edops.last_launch()
    assert ll["path"] == "comb" and ll["table_build"]
    assert "compile_s" not in ll
    hist = rt.metrics.device_compile_seconds
    assert hist.count(site="comb-build") == 1
    assert 0.0 < hist.total(site="comb-build") < 9.0
    assert hist.count(site="comb") == 0


def test_comb_disabled_and_budget_declined(monkeypatch):
    rt = degrade.configure(registry=Registry("comb_cfg"))
    rec = {}
    _stub_kernels(monkeypatch, record=rec)
    monkeypatch.setattr(edops, "_comb_min_override", 1)
    pubs, msgs, sigs = _batch(12)

    edops.set_comb_config(enabled=False)
    assert edops.verify_batch(pubs, msgs, sigs, cache_pubs=True).all()
    assert "launches" not in rec and edops.last_launch()["path"] == "xla"

    # budget 0: build declined, routed as comb/declined, ladder verifies
    edops.set_comb_config(enabled=True, table_cache_mb=0)
    assert edops.verify_batch(pubs, msgs, sigs, cache_pubs=True).all()
    assert "launches" not in rec
    assert rt.metrics.msm_route.value(path="comb", outcome="declined") == 1


def test_comb_eviction_midstream_falls_back(monkeypatch):
    """Evicting a set mid-stream degrades that set's batches to the
    ladder — same bitmap, eviction counted, key index cleaned up."""
    rt = degrade.configure(registry=Registry("comb_evict"))
    rec = {}
    _stub_kernels(monkeypatch, record=rec)
    monkeypatch.setattr(edops, "_comb_min_override", 1)
    # budget fits exactly one k_pad=8 set (~1.55 MB): 2 MB
    edops.set_comb_config(table_cache_mb=2)

    pubs_a, msgs_a, sigs_a = _batch(12, tag=b"setA")
    pubs_b, msgs_b, sigs_b = _batch(12, tag=b"setB")
    pubs_b = [_edref.pubkey_from_seed((0x7F00 + i % 6).to_bytes(
        32, "little")) for i in range(12)]
    sigs_b = [_edref.sign((0x7F00 + i % 6).to_bytes(32, "little"), m)
              for i, m in enumerate(msgs_b)]

    assert edops.verify_batch(pubs_a, msgs_a, sigs_a,
                              cache_pubs=True).all()
    assert edops.last_launch()["path"] == "comb"
    assert edops.verify_batch(pubs_b, msgs_b, sigs_b,
                              cache_pubs=True).all()  # evicts set A
    assert rt.metrics.table_evictions.value() == 1
    assert len(edops._table_cache) == 1

    # set A now unknown: ladder fallback, bitmap identical to the oracle
    out = edops.verify_batch(pubs_a, msgs_a, sigs_a)
    assert edops.last_launch()["path"] == "xla"
    assert (out == _oracle(pubs_a, msgs_a, sigs_a)).all() and out.all()
    # key index holds only set B's keys
    with edops._table_key_lock:
        assert len(edops._table_key_index) == 6


def test_eviction_of_overlapping_set_repoints_surviving_keys(monkeypatch):
    """Validator-set changes overlap: when set B (sharing keys with a
    still-resident set A) stole those keys' index entries and is then
    evicted, the index must repoint them to A — not drop them, which
    silently disabled A's subset/no-build comb lookups until rebuild."""
    degrade.configure(registry=Registry("comb_repoint"))
    rec = {}
    _stub_kernels(monkeypatch, record=rec)
    monkeypatch.setattr(edops, "_comb_min_override", 1)
    edops.set_comb_config(table_cache_mb=4)  # fits two k_pad=8 sets

    seeds_a = [(0x7A00 + i).to_bytes(32, "little") for i in range(6)]
    seeds_b = seeds_a[:4] + [(0x9A00 + i).to_bytes(32, "little")
                             for i in range(2)]
    seeds_c = [(0xBB00 + i).to_bytes(32, "little") for i in range(6)]

    def sigset(seeds, tag):
        msgs = [b"%s vote %d" % (tag, i) for i in range(len(seeds))]
        return ([_edref.pubkey_from_seed(s) for s in seeds], msgs,
                [_edref.sign(s, m) for s, m in zip(seeds, msgs)])

    for seeds, tag in ((seeds_a, b"A"), (seeds_b, b"B")):
        p, m, s = sigset(seeds, tag)
        assert edops.verify_batch(p, m, s, cache_pubs=True).all()
    assert len(edops._table_cache) == 2
    # touch A so B is the LRU victim, then build C to evict B
    p, m, s = sigset(seeds_a, b"A2")
    assert edops.verify_batch(p, m, s).all()
    p, m, s = sigset(seeds_c, b"C")
    assert edops.verify_batch(p, m, s, cache_pubs=True).all()
    assert len(edops._table_cache) == 2

    # the keys B shared with A survive B's eviction: a subset batch
    # over them (no cache_pubs — the scheduler-lane shape) still combs
    p, m, s = sigset(seeds_a[:4], b"A3")
    assert edops.verify_batch(p, m, s).all()
    assert edops.last_launch()["path"] == "comb"
    # B's unique keys are gone; A's 6 + C's 6 remain
    with edops._table_key_lock:
        assert len(edops._table_key_index) == 12


def test_comb_batch_over_max_chunk_is_chunked(monkeypatch):
    """A batch above MAX_CHUNK must sub-launch in MAX_CHUNK chunks like
    every other device path (split_chunked_launch), not mint a fresh
    power-of-two bucket shape per giant size class.  MAX_CHUNK shrunk to
    the MIN_BUCKET floor so the stub sees the chunking without a 65k
    staging bill."""
    degrade.configure(registry=Registry("comb_chunk"))
    rec = {}
    state = {"arm": False, "i": 0}

    def bits(nb):
        # armed: the 3rd launch (tail chunk) rejects its local lane 21
        state["i"] += 1
        v = np.ones(nb, dtype=bool)
        if state["arm"] and state["i"] % 3 == 0:
            v[21] = False
        return v

    _stub_kernels(monkeypatch, record=rec, bits_for=bits)
    monkeypatch.setattr(edops, "_comb_min_override", 1)
    monkeypatch.setattr(edops, "MAX_CHUNK", 64)

    pubs, msgs, sigs = _batch(150)
    out = edops.verify_batch(pubs, msgs, sigs, cache_pubs=True)
    assert out.all() and out.shape == (150,)
    # 64 + 64 + 22->64 lanes: every launch inside the existing bucket
    assert rec["launches"] == [64, 64, 64]
    ll = edops.last_launch()
    assert ll["path"] == "comb" and ll["n"] == 150 and ll["nb"] == 192
    # a device verdict in the LAST chunk lands on the right global lane
    # through the concatenation (tail lane 21 -> 2*64 + 21 = 149)
    state["arm"] = True
    out = edops.verify_batch(pubs, msgs, sigs)
    assert not out[149] and out[:149].all()


# ---------------------------------------------------------------------------
# tier-1: chaos at the comb site (stubbed kernels; the degrade plumbing
# above the kernel is exactly what runs against real hardware)
# ---------------------------------------------------------------------------


def _prebuild(monkeypatch, pubs, msgs, sigs, truth):
    _stub_kernels(monkeypatch,
                  bits_for=lambda nb: np.pad(truth, (0, nb - len(truth))))
    monkeypatch.setattr(edops, "_comb_min_override", 1)
    # build through the production seam (stubbed build kernel)
    assert edops.verify_batch(pubs, msgs, sigs, cache_pubs=True) is not None
    assert edops.last_launch()["path"] == "comb"


def _chaos_runtime():
    cfg = degrade.DegradeConfig(
        failure_threshold=3, launch_timeout_s=120.0,
        backoff_base_s=10.0, backoff_max_s=100.0, backoff_jitter=0.0)
    return degrade.configure(cfg, clock=lambda: 0.0,
                             registry=Registry("comb_chaos"))


@pytest.mark.parametrize("mode,reason", [
    ("corrupt-bitmap", "integrity"), ("raise", "raise")])
def test_chaos_at_comb_site_bitmap_exact(monkeypatch, mode, reason):
    """corrupt-bitmap at the comb site is caught by the degradation
    runtime's host spot check (a comb kernel replying garbage is
    degraded, not trusted); an injected raise degrades the lane.  In
    both classes the caller's bitmap is byte-identical to the host
    path."""
    monkeypatch.setenv("TM_TPU_FORCE_BATCH", "1")
    rt = _chaos_runtime()
    privs = [edkeys.PrivKey(bytes([i + 1]) * 32) for i in range(16)]
    msgs = [b"comb chaos %d" % i for i in range(16)]
    sigs = [p.sign(m) for p, m in zip(privs, msgs)]
    sigs[5] = bytes([sigs[5][0] ^ 1]) + sigs[5][1:]
    pubs = [p.pub_key().bytes() for p in privs]
    truth = _oracle(pubs, msgs, sigs)
    assert not truth[5] and truth.sum() == 15
    _prebuild(monkeypatch, pubs, msgs, sigs, truth)

    fail.set_mode("ops.ed25519.comb", mode)
    bv = cb.BatchVerifier(tpu_threshold=4)
    for p, m, s in zip(privs, msgs, sigs):
        bv.add(p.pub_key(), m, s)
    ok, bits = bv.verify()
    assert not ok and (bits == truth).all(), bits
    assert fail.fired("ops.ed25519.comb", mode) >= 1
    assert rt.metrics.device_failures.value(
        site="batch.ed25519", reason=reason) == 1
    assert rt.metrics.host_fallbacks.value(
        site="batch.ed25519", reason=reason) == 1


def test_real_device_fault_propagates_like_chaos(monkeypatch):
    """A RuntimeError out of the comb kernel (the class real device
    faults raise — jaxlib's XlaRuntimeError subclasses RuntimeError)
    must propagate to the degradation runtime exactly like an injected
    raise — NOT be swallowed as a comb bug and re-dispatched through
    the ladder on the same possibly-dead device."""
    rt = degrade.configure(registry=Registry("comb_fault"))
    rec = {}
    _stub_kernels(monkeypatch, record=rec)
    monkeypatch.setattr(edops, "_comb_min_override", 8)
    pubs, msgs, sigs = _batch(16)
    assert edops.verify_batch(pubs, msgs, sigs, cache_pubs=True).all()

    def dying(*a, **kw):
        raise RuntimeError("simulated XlaRuntimeError: device halted")

    monkeypatch.setattr(edops, "comb_kernel", dying)
    with pytest.raises(RuntimeError):
        edops.verify_batch(pubs, msgs, sigs, cache_pubs=True)
    # not routed as a swallowed comb bug
    assert rt.metrics.msm_route.value(path="comb", outcome="error") == 0


def test_ladder_bound_batch_skips_distinct_key_sort(monkeypatch):
    """Once some unrelated set is cached, a large batch of UNKNOWN keys
    (blocksync, cache_pubs=False) must bail on an O(1) key-index probe
    — never pay the O(n log n) distinct-key sort only to ladder
    anyway."""
    rec = {}
    _stub_kernels(monkeypatch, record=rec)
    monkeypatch.setattr(edops, "_comb_min_override", 8)
    pubs, msgs, sigs = _batch(16)
    assert edops.verify_batch(pubs, msgs, sigs, cache_pubs=True).all()
    assert rec["builds"] == [8]

    def boom(*a, **kw):
        raise AssertionError("np.unique on a ladder-bound batch")

    oseeds = [(0x8B00 + i).to_bytes(32, "little") for i in range(12)]
    omsgs = [b"unknown %d" % i for i in range(12)]
    other = [_edref.pubkey_from_seed(s) for s in oseeds]
    osigs = [_edref.sign(s, m) for s, m in zip(oseeds, omsgs)]
    real_unique = np.unique
    np.unique = boom
    try:
        assert edops._comb_resolve(other, False) is None
    finally:
        np.unique = real_unique
    # a known-set batch still resolves (the probe passes, unique runs)
    assert edops.verify_batch(pubs[:6], msgs[:6], sigs[:6]).all()
    assert edops.last_launch()["path"] == "comb"


# ---------------------------------------------------------------------------
# tier-1: config plumbing
# ---------------------------------------------------------------------------


def test_config_comb_roundtrip(tmp_path):
    from tendermint_tpu.config.config import Config

    cfg = Config(home=str(tmp_path))
    assert cfg.batch_verifier.comb is True
    assert cfg.batch_verifier.table_cache_mb == 256
    cfg.batch_verifier.comb = False
    cfg.batch_verifier.table_cache_mb = 64
    cfg.save()
    cfg2 = Config.load(str(tmp_path))
    assert cfg2.batch_verifier.comb is False
    assert cfg2.batch_verifier.table_cache_mb == 64
    cfg2.validate_basic()
    cfg2.batch_verifier.table_cache_mb = -1
    with pytest.raises(ValueError, match="table_cache_mb"):
        cfg2.validate_basic()


def test_set_comb_config_wins_over_env(monkeypatch):
    monkeypatch.setenv("TM_TPU_COMB", "0")
    monkeypatch.setenv("TM_TPU_TABLE_CACHE_MB", "1")
    assert not edops.comb_enabled()
    edops.set_comb_config(enabled=True, table_cache_mb=512)
    assert edops.comb_enabled()
    assert edops.table_cache_budget_bytes() == 512 << 20


# ---------------------------------------------------------------------------
# slow: real kernels — the bitmap-identity sweep and the mesh/scheduler
# paths.  Kernels run UNJITTED (eager) so the only compiles are the
# loop bodies; int32 limb arithmetic is exact, so eager and jit produce
# bit-identical results (pinned by test_comb_jit_matches_eager).
# ---------------------------------------------------------------------------


def _eager_kernels(monkeypatch):
    monkeypatch.setattr(edops, "comb_kernel", edops.comb_verify_staged)
    monkeypatch.setattr(edops, "comb_build_kernel",
                        edops.comb_build_kernel_impl)
    monkeypatch.setattr(edops, "verify_kernel", edops.verify_staged)


@pytest.mark.slow
def test_comb_bitmap_identity_sweep(monkeypatch):
    """Comb vs ladder vs host bignum oracle over every encoding class:
    valid, tampered, s >= L, non-canonical R, non-canonical pubkey y,
    negative zero, non-square y, identity key, torsion pubkey, and the
    ADR-009 torsion-residual signature.  One batch, nb=64 bucket."""
    monkeypatch.setenv("TM_TPU_NO_MESH", "1")
    from tendermint_tpu.parallel import sharding
    monkeypatch.setattr(sharding, "_PLANE", None)
    _eager_kernels(monkeypatch)
    monkeypatch.setattr(edops, "_comb_min_override", 1)

    n = 24
    pubs, msgs, sigs = _batch(n, pool=8, tag=b"sweep")
    pubs, sigs, msgs = list(pubs), list(sigs), list(msgs)
    # 1: tampered sig; 2: wrong message binding
    sigs[1] = bytes([sigs[1][0] ^ 1]) + sigs[1][1:]
    msgs[2] = msgs[2] + b"!"
    # 3: non-canonical s (>= L)
    s_big = int.from_bytes(sigs[3][32:], "little") + _edref.L
    sigs[3] = sigs[3][:32] + s_big.to_bytes(32, "little")
    # 4: non-canonical R encoding — y_enc = p + 1 decodes (to y = 1
    # after reduction) but the byte compare must reject it
    sigs[4] = (2 ** 255 - 18).to_bytes(32, "little") + sigs[4][32:]
    # 5: identity pubkey; 6: negative zero (x=0, sign=1); 7: non-square y
    pubs[5] = (1).to_bytes(32, "little")
    pubs[6] = ((1 << 255) | 1).to_bytes(32, "little")
    y = 2
    while _edref._recover_x(y, 0) is not None:
        y += 1
    pubs[7] = y.to_bytes(32, "little")
    # 8: torsion (order-8) pubkey with an honest-format signature
    from edvectors import order8_point, torsion_residual_sig
    T8 = order8_point()
    pubs[8] = _edref._encode(T8)
    # 9: torsion-residual signature (ADR-009 divergence vector)
    tseed = (0x7E01).to_bytes(32, "little")
    pubs[9], sigs[9] = torsion_residual_sig(tseed, msgs[9],
                                            b"comb torsion nonce")
    # 10: non-canonical pubkey y_enc = p (accepted-and-reduced to the
    # y = 0 order-4 point, matching Go's fe.SetBytes — the comb TABLES
    # are built from the same decompress, so the verdict must agree)
    pubs[10] = (2 ** 255 - 19).to_bytes(32, "little")

    truth = _oracle(pubs, msgs, sigs)
    assert not truth[1:4].any() and not truth[4]

    # ladder first (comb off), then comb (build + engage): bit-identical
    edops.set_comb_config(enabled=False)
    lad = edops.verify_batch(pubs, msgs, sigs, cache_pubs=True)
    assert edops.last_launch()["path"] == "xla"
    edops.set_comb_config(enabled=True)
    comb = edops.verify_batch(pubs, msgs, sigs, cache_pubs=True)
    ll = edops.last_launch()
    assert ll["path"] == "comb" and ll["table_build"]
    assert (comb == lad).all(), (comb, lad)
    assert (comb == truth).all(), (comb, truth)

    # and again as a pure cache hit (the steady-state block shape)
    comb2 = edops.verify_batch(pubs, msgs, sigs)
    assert edops.last_launch()["path"] == "comb"
    assert (comb2 == truth).all()


@pytest.mark.slow
def test_comb_mesh_identity_8dev():
    """The 8-device CPU mesh path: tables replicated per shard, batch
    rows split, bitmap bitwise-identical to single-device comb AND to
    the ladder, unaligned batch size included."""
    import os
    from tendermint_tpu.parallel import sharding

    os.environ.pop("TM_TPU_NO_MESH", None)
    sharding._PLANE = None
    try:
        plane = sharding.data_plane()
        assert plane is not None and plane.nshard >= 8
        edops._comb_min_override = 1

        n = 19  # deliberately not a multiple of the mesh
        pubs, msgs, sigs = _batch(n, pool=5, tag=b"mesh")
        sigs = list(sigs)
        sigs[4] = bytes([sigs[4][0] ^ 1]) + sigs[4][1:]
        truth = _oracle(pubs, msgs, sigs)

        comb = edops.verify_batch(pubs, msgs, sigs, cache_pubs=True)
        ll = edops.last_launch()
        assert ll["path"] == "mesh-comb" and ll["shards"] == plane.nshard
        assert (comb == truth).all(), (comb, truth)

        edops._comb_enabled_override = False
        lad = edops.verify_batch(pubs, msgs, sigs, cache_pubs=True)
        assert (comb == lad).all()
    finally:
        sharding._PLANE = None


@pytest.mark.slow
def test_comb_through_scheduler(monkeypatch):
    """A VerifyScheduler window whose keys resolve to a cached set runs
    the comb on the sched.ed25519 lane — same bitmap, path=comb in the
    launch record."""
    from tendermint_tpu.crypto import scheduler as vsched

    monkeypatch.setenv("TM_TPU_FORCE_BATCH", "1")
    monkeypatch.setenv("TM_TPU_NO_MESH", "1")
    from tendermint_tpu.parallel import sharding
    monkeypatch.setattr(sharding, "_PLANE", None)
    _eager_kernels(monkeypatch)
    monkeypatch.setattr(edops, "_comb_min_override", 1)

    privs = [edkeys.PrivKey(bytes([0x41 + i]) * 32) for i in range(8)]
    pubs = [p.pub_key().bytes() for p in privs]
    msgs = [b"sched comb %d" % i for i in range(32)]
    sigs = [privs[i % 8].sign(m) for i, m in enumerate(msgs)]
    sigs[7] = bytes([sigs[7][0] ^ 1]) + sigs[7][1:]
    truth = _oracle([pubs[i % 8] for i in range(32)], msgs, sigs)

    # build the set once through the bulk path
    assert edops.verify_batch(
        [pubs[i % 8] for i in range(32)], msgs, sigs,
        cache_pubs=True) is not None
    assert edops.last_launch()["path"] == "comb"

    cb.verified_sigs = cb.SigCache()  # no free hits for the window
    sched = vsched.install(vsched.VerifyScheduler(window_s=0.001,
                                                  tpu_threshold=4))
    sched.start()
    try:
        items = [(privs[i % 8].pub_key(), msgs[i], sigs[i])
                 for i in range(32)]
        bits = sched.submit(items, vsched.Priority.CONSENSUS).result(
            timeout=120)
        assert (bits == truth).all(), bits
        assert edops.last_launch()["path"] == "comb"
    finally:
        sched.stop()
        vsched.uninstall(sched)


@pytest.mark.slow
def test_comb_jit_matches_eager(monkeypatch):
    """Pins jit-vs-eager bit identity of the comb kernel itself (the
    sweep runs eager for compile budget; production runs jitted)."""
    import jax.numpy as jnp

    n = 12
    pubs, msgs, sigs = _batch(n, pool=4, tag=b"jit")
    sigs = list(sigs)
    sigs[2] = bytes([sigs[2][0] ^ 1]) + sigs[2][1:]
    pub_m = edops._to_u8_matrix(pubs, 32)
    uniq, inverse = np.unique(pub_m, axis=0, return_inverse=True)
    inverse = np.asarray(inverse).reshape(-1)
    k_pad = edops._comb_k_pad(uniq.shape[0])
    pub_pad = np.zeros((k_pad, 32), np.uint8)
    pub_pad[:uniq.shape[0]] = uniq
    tab, dec_ok = edops.comb_build_kernel_impl(pub_pad)
    _, r_b, s_b, kk, host_ok = edops._stage_rows(
        pub_m, edops._to_u8_matrix(sigs, 64), msgs)
    sd = edops.scalars_to_digits(s_b)
    kd = edops.scalars_to_digits(kk)
    vidx = inverse.astype(np.int32)
    args = (jnp.asarray(r_b), jnp.asarray(sd), jnp.asarray(kd),
            jnp.asarray(vidx), tab.ypx, tab.ymx, tab.z, tab.t2d,
            dec_ok, *edops._base_comb())
    eager = np.asarray(edops.comb_verify_staged(*args))
    jitted = np.asarray(edops.comb_kernel(*args))
    assert (eager == jitted).all()
    truth = _oracle(pubs, msgs, sigs)
    assert ((eager & host_ok) == truth).all()
