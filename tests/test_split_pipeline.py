"""The `pallas-split` route as a pipeline of several chunks, and what
verify_batch says of a batch on its way there (ISSUE 31): the chunks carry
real verdicts with bad lanes on both sides of a seam; `pub_rows_cached` on
the launch record and on the `ops.ed25519.verify_batch` span follows the
CONTENT of the batch's pubkey rows and the cache's four entries; and
`comb.resolve` names each way the comb's look-up can end, among them the
early exit of a batch no table set can hold (ISSUE 32), which is held to
the full look-up's answer and shown not to sort.  Since ISSUE 36 a batch
of up to SPLIT_CHUNK rows is a pipeline too, of SPLIT_CHUNK_SMALL-lane
chunks: the seams of a 6,667-row batch at the module's own constants,
what goes to the device ahead of each kernel on a miss and on a hit, and
that a prefix of another length launches, and records, nothing new.

The fused kernel compiles for a TPU only, so the route is taken here with
the chunk patched small (as tests/test_ed25519.py::test_pub_cache_routing
does) and the XLA kernel standing in for the Pallas one, chunk by chunk, on
the very rows the route staged for it: what is tested is the route, its
seams and its records, not the kernel (tests/test_pallas_ed25519.py,
tests/test_tpu_lowering.py)."""
from __future__ import annotations

import functools

import numpy as np
import pytest

from tendermint_tpu.crypto import _edref, degrade
from tendermint_tpu.libs import trace
from tendermint_tpu.ops import ed25519 as edops
from tendermint_tpu.ops import pallas_ed25519 as pe

CHUNK = 128


def xla_on_split_rows(pub_t, rsk, tile=None):
    """verify_packed_split_pallas's contract ((32, B) pubkey rows, (96, B)
    R | s | k rows -> (B,) bool) met by the XLA kernel."""
    import jax.numpy as jnp

    assert pub_t.shape == (32, CHUNK) and rsk.shape == (96, CHUNK)
    pub = np.ascontiguousarray(np.asarray(pub_t).view(np.uint8).T)
    rows = np.asarray(rsk).view(np.uint8)
    r, s, k = (np.ascontiguousarray(rows[a:a + 32].T) for a in (0, 32, 64))
    return edops.verify_kernel(
        jnp.asarray(pub), jnp.asarray(r),
        jnp.asarray(edops.scalars_to_digits(s)),
        jnp.asarray(edops.scalars_to_digits(k)))


@pytest.fixture
def split_route(monkeypatch):
    monkeypatch.setattr(edops, "_use_pallas", lambda: True)
    monkeypatch.setattr(edops, "PUB_CACHE_MIN", 64)
    monkeypatch.setattr(edops, "SPLIT_CHUNK", CHUNK)
    monkeypatch.setattr(edops, "PALLAS_TILE", 32)
    monkeypatch.setattr(edops, "_pub_cache",
                        edops.DeviceLRU(max_entries=edops._PUB_CACHE_MAX))
    monkeypatch.setattr(edops, "_comb_enabled_override", False)
    from tendermint_tpu.parallel import sharding
    monkeypatch.setattr(sharding, "_PLANE", False)
    trace.enable()
    trace.reset()
    yield
    trace.disable()
    trace.reset()


def batch(n, first=0, tag=b"split"):
    seeds = [(0x5100 + first + i).to_bytes(32, "little") for i in range(n)]
    msgs = [b"%s %d" % (tag, first + i) for i in range(n)]
    return ([_edref.pubkey_from_seed(s) for s in seeds], msgs,
            [_edref.sign(s, m) for s, m in zip(seeds, msgs)])


def all_true(pub_t, rsk, tile=None):
    import jax.numpy as jnp
    return jnp.ones(rsk.shape[1], dtype=bool)


def launch_facts():
    """(the last launch record, the verify_batch span's attrs)."""
    (span,) = [r for r in trace.snapshot()
               if r["name"] == "ops.ed25519.verify_batch"]
    trace.reset()
    return edops.last_launch(), span["attrs"]


def test_three_chunks_carry_real_verdicts_across_their_seams(
        split_route, monkeypatch):
    monkeypatch.setattr(pe, "verify_packed_split_pallas", xla_on_split_rows)
    n = 300
    pubs, msgs, sigs = batch(n)
    # both ends, both sides of the first seam, one in the third chunk
    bad = [0, CHUNK - 1, CHUNK, 2 * CHUNK + 7, n - 1]
    sigs = tamper(sigs, bad)
    out = edops.verify_batch(pubs, msgs, sigs, cache_pubs=True)
    want = np.array([_edref.verify(p, m, s)
                     for p, m, s in zip(pubs, msgs, sigs)])
    assert [int(i) for i in np.flatnonzero(~want)] == bad
    assert np.array_equal(out, want)
    rec, attrs = launch_facts()
    assert (rec["path"], rec["n"], rec["nb"]) == ("pallas-split", n, 384)
    assert rec["chunks"] == 3 and rec["drain_s"] >= 0 and rec["h2d_s"] >= 0
    assert 0.0 <= rec["chunk_overlap"] <= 1.0
    assert rec["pub_rows_cached"] is False
    assert rec["pub_rows_bytes"] == 32 * 384
    assert attrs["pub_rows_cached"] is False
    assert attrs["pub_rows_bytes"] == 32 * 384


def test_pub_rows_cached_follows_the_content_of_the_rows(
        split_route, monkeypatch):
    """The same rows again are found on the device; the same set with one
    signer absent is new content, and is uploaded again."""
    monkeypatch.setattr(pe, "verify_packed_split_pallas", all_true)
    pubs, msgs, sigs = batch(200)
    seen = []
    for rows in (range(200), range(200), [i for i in range(200) if i != 77],
                 range(200)):
        edops.verify_batch([pubs[i] for i in rows], [msgs[i] for i in rows],
                           [sigs[i] for i in rows], cache_pubs=True)
        rec, attrs = launch_facts()
        assert attrs["pub_rows_cached"] is rec["pub_rows_cached"]
        assert ("pub_rows_bytes" in rec) is not rec["pub_rows_cached"]
        seen.append(rec["pub_rows_cached"])
    assert seen == [False, True, False, True]
    assert len(edops._pub_cache) == 2


def test_a_fifth_set_of_rows_evicts_the_first(split_route, monkeypatch):
    monkeypatch.setattr(pe, "verify_packed_split_pallas", all_true)
    pubs, msgs, sigs = batch(205)
    seen = []
    # five commits of one set, another validator absent in each, then the
    # first again: the cache is four deep
    for absent in (200, 201, 202, 203, 204, 200, 204):
        rows = [i for i in range(205) if i != absent]
        edops.verify_batch([pubs[i] for i in rows], [msgs[i] for i in rows],
                           [sigs[i] for i in rows], cache_pubs=True)
        seen.append(launch_facts()[0]["pub_rows_cached"])
    assert seen == [False] * 6 + [True]
    assert len(edops._pub_cache) == edops._PUB_CACHE_MAX


def test_a_route_that_keeps_no_rows_says_nothing_of_them(split_route,
                                                         monkeypatch):
    import jax.numpy as jnp

    monkeypatch.setattr(edops, "_use_pallas", lambda: False)
    monkeypatch.setattr(edops, "verify_kernel", lambda pub, r, s, k:
                        jnp.ones(pub.shape[0], dtype=bool))
    pubs, msgs, sigs = batch(40)
    edops.verify_batch(pubs, msgs, sigs, cache_pubs=True)
    rec, attrs = launch_facts()
    assert rec["path"] == "xla"
    assert "pub_rows_cached" not in rec and "pub_rows_cached" not in attrs


# ---------------------------------------------------------------------------
# the small chunk (ISSUE 36): a batch of up to SPLIT_CHUNK rows is a
# pipeline of SPLIT_CHUNK_SMALL-lane launches
# ---------------------------------------------------------------------------

def signed(n, first=0, tag=b"small"):
    """batch() through OpenSSL where the image has it: 6,667 rows in under
    a second (the pure-Python signer takes 5 ms a row)."""
    from tendermint_tpu.crypto import ed25519 as edkeys

    privs = [edkeys.PrivKey((0x5100 + first + i).to_bytes(32, "little"))
             for i in range(n)]
    msgs = [b"%s %d" % (tag, first + i) for i in range(n)]
    return ([k.pub_key().bytes() for k in privs], msgs,
            [k.sign(m) for k, m in zip(privs, msgs)])


def oracle(pubs, msgs, sigs):
    from tendermint_tpu.crypto import ed25519 as edkeys
    return np.array([edkeys.PubKey(p).verify_signature(m, s)
                     for p, m, s in zip(pubs, msgs, sigs)])


def tamper(sigs, bad):
    return [bytes([s[0] ^ 1]) + s[1:] if i in bad else s
            for i, s in enumerate(sigs)]


def seam_rows(n, chunk):
    """Both ends, a row on each side of every chunk seam, and the padded
    tail's last real row (n - 1, twice named)."""
    seams = range(chunk, n, chunk)
    return sorted({0, n - 1} | {c - 1 for c in seams} | set(seams))


def test_small_chunks_carry_real_verdicts_across_every_seam(
        split_route, monkeypatch):
    """The rule at 1/16 of its size with the XLA kernel's own verdicts: a
    batch that the old rule launched as ONE 512-lane chunk runs as four of
    128, the tail chunk a quarter full."""
    monkeypatch.setattr(edops, "SPLIT_CHUNK", 8 * CHUNK)
    monkeypatch.setattr(edops, "SPLIT_CHUNK_SMALL", CHUNK)
    monkeypatch.setattr(pe, "verify_packed_split_pallas", xla_on_split_rows)
    n = 3 * CHUNK + 33
    pubs, msgs, sigs = signed(n)
    bad = seam_rows(n, CHUNK)
    assert bad == [0, 127, 128, 255, 256, 383, 384, n - 1]
    sigs = tamper(sigs, bad)
    out = edops.verify_batch(pubs, msgs, sigs, cache_pubs=True)
    want = oracle(pubs, msgs, sigs)
    assert [int(i) for i in np.flatnonzero(~want)] == bad
    assert np.array_equal(out, want)
    rec, attrs = launch_facts()
    assert (rec["path"], rec["n"], rec["nb"], rec["bucket"]) == \
        ("pallas-split", n, 4 * CHUNK, CHUNK)
    assert rec["chunks"] == attrs["chunks"] == 4
    assert 0.0 <= attrs["chunk_overlap"] <= 1.0
    assert attrs["chunk_overlap"] == round(rec["chunk_overlap"], 4)


@functools.lru_cache(maxsize=None)
def light_rows():
    pubs, msgs, sigs = signed(10000, tag=b"light")
    _, rsk, ok = edops.prepare_batch_split(pubs, sigs, msgs)
    assert ok.all()
    return pubs, msgs, sigs, frozenset(
        p + rsk[:, i].tobytes() for i, p in enumerate(pubs))


@pytest.fixture
def light_set(monkeypatch):
    """A light client's prefix at the size the cells send it, the module's
    own constants, and a stand-in for the kernel that knows the honest
    rows: a lane is valid when its (A, R, s, k) rows are one honest
    signature's, so a tampered row, a zeroed padding lane or a row that
    landed in another lane reads false.  Yields (pubs, msgs, sigs, the
    stand-in's log of (pub rows shape, rsk shape) a call)."""
    import jax.numpy as jnp

    assert (edops.PUB_CACHE_MIN, edops.SPLIT_CHUNK) == (4096, 16384)
    monkeypatch.setattr(edops, "_use_pallas", lambda: True)
    monkeypatch.setattr(edops, "_pub_cache",
                        edops.DeviceLRU(max_entries=edops._PUB_CACHE_MAX))
    monkeypatch.setattr(edops, "_comb_enabled_override", False)
    from tendermint_tpu.parallel import sharding
    monkeypatch.setattr(sharding, "_PLANE", False)
    pubs, msgs, sigs, honest = light_rows()
    calls = []

    def knows_the_honest_rows(pub_t, rsk, tile=None):
        calls.append((pub_t.shape, rsk.shape))
        a, r = np.asarray(pub_t), np.asarray(rsk)
        return jnp.asarray([a[:, i].tobytes() + r[:, i].tobytes() in honest
                            for i in range(r.shape[1])])

    monkeypatch.setattr(pe, "verify_packed_split_pallas",
                        knows_the_honest_rows)
    with edops._launch_lock:
        seen = set(edops._seen_buckets)
        edops._seen_buckets.difference_update(
            {k for k in seen if k[0] == "pallas-split"})
    trace.enable()
    trace.reset()
    yield pubs, msgs, sigs, calls
    trace.disable()
    trace.reset()
    with edops._launch_lock:
        edops._seen_buckets.clear()
        edops._seen_buckets.update(seen)


def test_6667_rows_are_seven_small_chunks_and_the_oracles_bitmap(
        light_set):
    pubs, msgs, sigs = (x[:6667] for x in light_set[:3])
    calls = light_set[3]
    c = edops.SPLIT_CHUNK_SMALL
    chunks = -(-6667 // c)
    bad = seam_rows(6667, c)
    assert len(bad) == 2 * chunks and 6666 in bad and 6666 % c < c - 1
    sigs = tamper(sigs, bad)
    out = edops.verify_batch(pubs, msgs, sigs, cache_pubs=True)
    want = oracle(pubs, msgs, sigs)
    assert [int(i) for i in np.flatnonzero(~want)] == bad
    assert np.array_equal(out, want)
    assert calls == [((32, c), (96, c))] * chunks
    rec, attrs = launch_facts()
    assert (rec["path"], rec["n"], rec["chunks"], rec["nb"]) == \
        ("pallas-split", 6667, chunks, chunks * c)
    assert rec["bucket"] == c and rec["first_launch"] is True
    assert (attrs["chunks"], attrs["nb"]) == (chunks, chunks * c)
    assert rec["head_s"] <= rec["wall_s"]


def test_the_same_rows_again_are_a_hit_and_upload_nothing(light_set,
                                                          monkeypatch):
    """What goes to the device and when: on a miss one chunk's pubkey rows
    and staged rows ahead of each kernel (the head holds ONE chunk's,
    whatever the number of chunks), on a hit the staged rows alone."""
    import jax

    pubs, msgs, sigs = (x[:6667] for x in light_set[:3])
    real_put, stand_in = jax.device_put, pe.verify_packed_split_pallas
    events = []

    def put(x, *a, **k):
        events.append("put%d" % x.shape[0])
        return real_put(x, *a, **k)

    def kernel(*a, **k):
        events.append("kernel")
        return stand_in(*a, **k)

    monkeypatch.setattr(jax, "device_put", put)
    monkeypatch.setattr(pe, "verify_packed_split_pallas", kernel)
    c = edops.SPLIT_CHUNK_SMALL
    chunks = -(-6667 // c)
    for cached, want in ((False, ["put32", "put96", "kernel"] * chunks),
                         (True, ["put96", "kernel"] * chunks)):
        del events[:]
        assert edops.verify_batch(pubs, msgs, sigs, cache_pubs=True).all()
        assert events == want
        rec, attrs = launch_facts()
        assert rec["pub_rows_cached"] is attrs["pub_rows_cached"] is cached
        assert rec.get("pub_rows_bytes") == \
            (None if cached else 32 * chunks * c)
    assert len(edops._pub_cache) == 1 and edops._pub_cache.hits == 1


@pytest.mark.parametrize("n", [4096, 5000, 6668, 10000])
def test_another_length_launches_no_new_kernel_shape(light_set, n):
    """A light client's prefix changes its length with the set: every
    length of 4,096..16,384 rows launches the one (96, C) shape, is no
    first launch, and adds no entry to the compile inventory."""
    from tendermint_tpu.crypto import devobs

    pubs, msgs, sigs, calls = light_set
    c = edops.SPLIT_CHUNK_SMALL
    devobs.reset()
    assert edops.verify_batch(pubs[:6667], msgs[:6667], sigs[:6667],
                              cache_pubs=True).all()
    assert edops.last_launch()["first_launch"] is True
    assert edops.verify_batch(pubs[:n], msgs[:n], sigs[:n],
                              cache_pubs=True).all()
    rec = edops.last_launch()
    chunks = -(-n // c)
    assert (rec["n"], rec["nb"], rec["chunks"]) == (n, chunks * c, chunks)
    assert rec["first_launch"] is False and "compile_s" not in rec
    assert set(calls) == {((32, c), (96, c))}
    assert [(e["path"], e["nb"], e["hits"])
            for e in devobs.compile_inventory()] == [("pallas-split", c, 1)]


# ---------------------------------------------------------------------------
# comb.resolve
# ---------------------------------------------------------------------------

@pytest.fixture
def comb_world(monkeypatch):
    import jax.numpy as jnp
    from tendermint_tpu.ops import curve as C

    edops.table_cache_clear()
    monkeypatch.setattr(edops, "_comb_enabled_override", None)
    monkeypatch.setattr(edops, "_comb_min_override", 8)
    monkeypatch.setattr(edops, "_table_budget_override", None)
    monkeypatch.setattr(
        edops, "comb_build_kernel",
        lambda pub: (C.Cached(None, None, None, None),
                     jnp.ones(pub.shape[0], dtype=bool)))
    trace.enable()
    trace.reset()
    yield
    trace.disable()
    trace.reset()
    edops.table_cache_clear()
    degrade.reset()


def resolve(pubs, cache_pubs):
    trace.reset()
    comb = edops._comb_resolve(pubs, cache_pubs)
    (span,) = [r for r in trace.snapshot() if r["name"] == "comb.resolve"]
    assert span["attrs"]["n"] == len(pubs)
    return comb, span["attrs"]["outcome"]


@pytest.mark.parametrize("outcome", ["built", "resident", "declined",
                                     "unknown"])
def test_comb_resolve_names_each_way_the_lookup_ends(comb_world, outcome,
                                                     monkeypatch):
    pubs, _, _ = batch(24, tag=b"resolve")
    if outcome == "built":
        comb, got = resolve(pubs, True)
        assert comb is not None and comb.built
    elif outcome == "resident":
        resolve(pubs, True)
        # found again, by a batch that may not build and by a subset
        comb, got = resolve(pubs, False)
        assert comb is not None and not comb.built
        assert resolve(pubs[:5], False)[1] == "resident"
    elif outcome == "declined":
        # the budget holds no table of 24 keys (32 padded)
        monkeypatch.setattr(edops, "_table_budget_override",
                            8 * edops._TABLE_BYTES_PER_KEY)
        comb, got = resolve(pubs, True)
        assert comb is None
    else:
        # keys no table holds, in a batch that may not build; the comb
        # switched off; an empty batch
        resolve(pubs, True)
        others, _, _ = batch(24, first=500, tag=b"resolve")
        comb, got = resolve(others, False)
        assert comb is None
        monkeypatch.setattr(edops, "_comb_enabled_override", False)
        assert resolve(pubs, True) == (None, "unknown")
    assert got == outcome


# ---------------------------------------------------------------------------
# the early exit of the look-up (ISSUE 32): a batch whose head already
# holds more distinct keys than any table set can hold leaves ahead of the
# distinct-key sort, with the answer the sort would have reached
# ---------------------------------------------------------------------------

CAP = 16   # keys the budget below holds: 16 padded keys, none more


def keys(n, first=0):
    """Distinct 32-byte keys; the stubbed build never decodes them."""
    import hashlib
    return [hashlib.sha256(b"early %d" % (first + i)).digest()
            for i in range(n)]


def budget(monkeypatch, padded_keys):
    monkeypatch.setattr(edops, "_table_budget_override",
                        padded_keys * edops._TABLE_BYTES_PER_KEY)


# name -> (batches resolved first, each (pubkeys, cache_pubs, budget in
# padded keys); the batch asked about, its cache_pubs and budget; the
# outcome and `early` its span must carry)
EARLY_CASES = {
    "distinct_keys_equal_the_cap":
        ([], (keys(CAP), True, CAP), "built", False),
    "a_long_batch_of_cap_distinct_keys":
        ([], (keys(CAP) * 3, True, CAP), "built", False),
    "distinct_keys_one_over_the_cap":
        ([], (keys(CAP + 1), True, CAP), "declined", True),
    "many_rows_of_a_few_resident_keys":
        ([(keys(10), True, CAP)],
         (keys(10) * 5, True, CAP), "resident", False),
    "duplicates_fill_the_head_the_sample_reads":
        ([], (keys(1) * (4 * (CAP + 1)) + keys(CAP + 4), True, CAP),
         "declined", False),
    "a_resident_set_larger_than_the_shrunk_budget":
        ([(keys(24), True, 32)], (keys(24), False, 8), "resident", False),
    "rows_of_that_set_beyond_its_size":
        ([(keys(24), True, 32)], (keys(24) * 2, True, 8), "resident",
         False),
    "over_the_resident_set_and_the_shrunk_budget":
        ([(keys(24), True, 32)], (keys(25), True, 8), "declined", True),
    "a_batch_that_may_not_build":
        ([(keys(10), True, CAP)],
         (keys(10) + keys(20, first=100), False, CAP), "unknown", True),
    "budget_zero":
        ([], (keys(12), True, 0), "declined", True),
}


def lookup_in_a_fresh_world(monkeypatch, case, early_exit: bool):
    """(comb, span attrs, comb/declined count) of the case's last batch."""
    from tendermint_tpu.libs.metrics import Registry

    before, asked, _, _ = EARLY_CASES[case]
    edops.table_cache_clear()
    rt = degrade.configure(registry=Registry("early_" + case))
    with monkeypatch.context() as m:
        if not early_exit:
            m.setattr(edops, "_comb_over_cap", lambda pub_m: False)
        for pubs, cache_pubs, padded in before + [asked]:
            budget(m, padded)
            trace.reset()
            comb = edops._comb_resolve(pubs, cache_pubs)
        (span,) = [r for r in trace.snapshot()
                   if r["name"] == "comb.resolve"]
    return comb, span["attrs"], rt.metrics.msm_route.value(
        path="comb", outcome="declined")


def comb_facts(comb):
    if comb is None:
        return None
    return (comb.entry.k, comb.entry.k_pad, comb.entry.set_hash,
            sorted(comb.entry.index.items()), comb.pub_m.tobytes(),
            comb.vidx.tolist(), comb.built)


@pytest.mark.parametrize("case", sorted(EARLY_CASES))
def test_the_early_exit_answers_as_the_full_lookup_does(comb_world, case,
                                                        monkeypatch):
    _, _, outcome, early = EARLY_CASES[case]
    comb, attrs, declined = lookup_in_a_fresh_world(monkeypatch, case, True)
    full, full_attrs, full_declined = lookup_in_a_fresh_world(
        monkeypatch, case, False)
    assert (attrs["outcome"], attrs["early"]) == (outcome, early)
    assert (full_attrs["outcome"], full_attrs["early"]) == (outcome, False)
    assert comb_facts(comb) == comb_facts(full)
    assert (comb is None) == (outcome in ("declined", "unknown"))
    assert declined == full_declined == (outcome == "declined")


def test_the_key_cap_is_the_budgets_or_a_residents(comb_world, monkeypatch):
    per_key = edops._TABLE_BYTES_PER_KEY
    for budget_bytes, cap in ((256 << 20, 1024), (2048 * per_key, 2048),
                              (2048 * per_key - 1, 1024), (8 * per_key, 8),
                              (8 * per_key - 1, 0), (0, 0), (-1, 0)):
        monkeypatch.setattr(edops, "_table_budget_override", budget_bytes)
        assert edops._comb_key_cap() == cap
        # the cap is _table_build's own test, from the other side
        for k, fits in ((cap, True), (cap + 1, False)):
            if k:
                assert (edops._comb_k_pad(k) * per_key
                        <= budget_bytes) == fits
    budget(monkeypatch, 32)
    assert edops._comb_resolve(keys(24), True).built
    budget(monkeypatch, 8)
    assert edops._comb_key_cap() == 24
    edops.table_cache_clear()
    assert edops._comb_key_cap() == 8


def test_an_early_decline_sorts_no_rows(comb_world, monkeypatch):
    from tendermint_tpu.libs.metrics import Registry

    real_unique = np.unique

    def unique_of_one_axis_only(a, *args, **kwargs):
        assert kwargs.get("axis") is None, "the distinct-key sort ran"
        return real_unique(a, *args, **kwargs)

    rt = degrade.configure(registry=Registry("early_no_sort"))
    budget(monkeypatch, CAP)
    pubs = keys(4 * CAP)
    with monkeypatch.context() as m:
        m.setattr(np, "unique", unique_of_one_axis_only)
        # a set the tables hold does sort: the guard is armed
        with pytest.raises(AssertionError, match="distinct-key sort"):
            edops._comb_lookup(pubs[:CAP], True)
        assert resolve(pubs, True) == (None, "declined")
        (span,) = [r for r in trace.snapshot()
                   if r["name"] == "comb.resolve"]
        assert span["attrs"]["early"] is True
        assert edops.prewarm(pubs) is False
        # a matrix of key rows (verify_sigs_bulk's aligned batches)
        rows = np.frombuffer(b"".join(pubs), dtype=np.uint8).reshape(-1, 32)
        assert edops._comb_lookup(rows, True) == (None, "declined", True)
    assert len(edops._table_cache) == 0
    assert rt.metrics.msm_route.value(path="comb", outcome="declined") == 3
