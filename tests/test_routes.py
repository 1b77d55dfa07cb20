"""The route ladder of ops/ed25519.verify_batch, stated.

`select_routes` decides which route a batch takes from what the code
observes (row count, cache_pubs, the backend, whether the set's comb
tables are resident, whether the local mesh wants the batch) and
nothing else; `test_route_table` pins it at every decision boundary and
at every shape a benchmark cell or chip_smoke.py sends, with the names
the launch records and the ledger print.  `test_every_route_is_
cofactorless` holds each route that runs on this backend to the
reference's cofactorless verdicts on the vectors a cofactored batch
check gets wrong: it is the test that says why the RLC/MSM route was
withdrawn (ADR-009).  The last two say what went with it: blocksync
replay has one way to the verifier, through the scheduler, and an old
`rlc = true` in a config file changes nothing."""
from __future__ import annotations

import os

import numpy as np
import pytest

from edvectors import torsion_residual_sig
from tendermint_tpu.crypto import _edref
from tendermint_tpu.crypto import degrade
from tendermint_tpu.ops import ed25519 as edops
from tendermint_tpu.parallel import sharding

MESH = sharding._DataPlane.MESH_PATH


# (n, cache_pubs, pallas, comb_resident, plane_worth) ->
# [(path, padded lanes, launches), ...] most preferred first
ROUTE_TABLE = [
    # val150-live, chip_smoke at 150 rows: the prewarmed set's comb...
    ("150 rows, tables resident",
     (150, True, True, True, False), [("comb", 256, 1), ("pallas", 256, 1)]),
    # ...and the same rows before any prewarm
    ("150 rows, no tables, TPU",
     (150, True, True, False, False), [("pallas", 256, 1)]),
    # val150-catchup's scheduler windows
    ("251 rows, tables resident",
     (251, False, True, True, False), [("comb", 256, 1), ("pallas", 256, 1)]),
    ("326 rows, tables resident",
     (326, False, True, True, False), [("comb", 512, 1), ("pallas", 512, 1)]),
    # val10k-skipping / val10k-client: the trusting prefix
    ("3,334 cached rows, TPU",
     (3334, True, True, False, False), [("pallas", 4096, 1)]),
    # val10k-mixed-commit and chip_smoke's mixed phase: the ed25519 third
    # of a commit in three key schemes, a list of keys from the
    # BatchVerifier's lane (the other two schemes' lanes are their own
    # modules': ops/secp, ops/sr25519, one bucket_size(n) launch each)
    ("3,300 rows of a BatchVerifier lane, not cached, TPU",
     (3300, False, True, False, False), [("pallas", 4096, 1)]),
    # the PUB_CACHE_MIN edge
    ("4,095 cached rows, TPU",
     (4095, True, True, False, False), [("pallas", 4096, 1)]),
    ("4,096 cached rows, TPU: 4 chunks of SPLIT_CHUNK_SMALL",
     (4096, True, True, False, False), [("pallas-split", 4096, 4)]),
    # val10k-adjacent, and the 2/3 certificate of the other two
    ("6,667 cached rows, TPU: 7 chunks of SPLIT_CHUNK_SMALL",
     (6667, True, True, False, False), [("pallas-split", 7168, 7)]),
    ("6,667 rows, not cached, TPU",
     (6667, False, True, False, False), [("pallas", 8192, 1)]),
    # chip_smoke at 10,000 and 100,000 validators
    ("10,000 cached rows, TPU: a multiple of the chunk, no power of two",
     (10000, True, True, False, False), [("pallas-split", 10240, 10)]),
    # the SPLIT_CHUNK edge: the last batch of small chunks, the first
    # of large ones
    ("16,384 cached rows, TPU",
     (16384, True, True, False, False), [("pallas-split", 16384, 16)]),
    ("16,385 cached rows, TPU: 2 chunks of SPLIT_CHUNK",
     (16385, True, True, False, False), [("pallas-split", 32768, 2)]),
    # val100k-commit (its workload pins this shape), chip_smoke at 100,000
    ("99,000 cached rows, TPU: 7 chunks of SPLIT_CHUNK",
     (99000, True, True, False, False), [("pallas-split", 114688, 7)]),
    ("100,000 cached rows, TPU: 7 chunks of SPLIT_CHUNK",
     (100000, True, True, False, False), [("pallas-split", 114688, 7)]),
    ("70,000 rows, not cached, TPU: 2 sub-launches of MAX_CHUNK",
     (70000, False, True, False, False), [("pallas", 131072, 2)]),
    # every other backend
    ("any rows, CPU",
     (6667, True, False, False, False), [("xla", 8192, 1)]),
    ("under MIN_BUCKET, CPU",
     (1, False, False, False, False), [("xla", 64, 1)]),
    # the local mesh: worth sharding and no tables
    ("plane worth sharding, no tables",
     (1024, False, False, False, True), [(MESH, None, None),
                                         ("xla", 1024, 1)]),
    ("plane worth sharding, no tables, TPU",
     (10000, True, True, False, True), [("mesh-pallas", None, None),
                                        ("pallas-split", 10240, 10)]),
    # a plane is present but the batch is under its floor
    ("plane present, batch under its floor",
     (5, False, False, False, False), [("xla", 64, 1)]),
    # comb first, then the mesh, then one device
    ("tables resident and plane worth sharding",
     (1024, False, False, True, True), [("comb", 1024, 1),
                                        (MESH, None, None),
                                        ("xla", 1024, 1)]),
    # a resident set past MAX_CHUNK launches in MAX_CHUNK chunks
    ("70,000 rows, tables resident",
     (70000, True, True, True, False), [("comb", 65536 + 8192, 2),
                                        ("pallas-split", 81920, 5)]),
]


@pytest.mark.parametrize("observed,want",
                         [c[1:] for c in ROUTE_TABLE],
                         ids=[c[0] for c in ROUTE_TABLE])
def test_route_table(observed, want):
    n, cache_pubs, pallas, comb_resident, plane_worth = observed
    got = edops.select_routes(n, cache_pubs, pallas=pallas,
                              comb_resident=comb_resident,
                              plane_worth=plane_worth)
    assert [tuple(r) for r in got] == want
    # the thresholds the table is written against
    assert (edops.PUB_CACHE_MIN, edops.PALLAS_TILE,
            edops.SPLIT_CHUNK_SMALL, edops.SPLIT_CHUNK, edops.MAX_CHUNK,
            edops.MIN_BUCKET) == (4096, 256, 1024, 16384, 65536, 64)


# n -> lanes of one launch of the split route: the row count and nothing
# else decides, one small chunk up to SPLIT_CHUNK rows, SPLIT_CHUNK above
SPLIT_CHUNKS = {4096: 1024, 6667: 1024, 10000: 1024, 16384: 1024,
                16385: 16384, 99000: 16384, 100000: 16384}


@pytest.mark.parametrize("n", sorted(SPLIT_CHUNKS))
def test_split_chunk_reads_the_row_count_alone(n):
    chunk = edops._split_chunk(n)
    assert chunk == SPLIT_CHUNKS[n]
    assert chunk % edops.PALLAS_TILE == 0
    (route,) = edops.select_routes(n, True, pallas=True,
                                   comb_resident=False, plane_worth=False)
    assert route.path == "pallas-split"
    assert route.launches == -(-n // chunk)
    assert route.nb == route.launches * chunk
    assert 0 <= route.nb - n < chunk


def test_the_plane_answers_the_selectors_question():
    """`plane_worth` is what the live plane says, and under its floor a
    batch stays on one device although a plane exists (the tests' 8
    forced host devices)."""
    plane = sharding.data_plane()
    assert plane is not None and plane.nshard == 8
    assert plane.worth_sharding(8) and not plane.worth_sharding(7)
    assert plane.MESH_PATH == "mesh-xla"


# ---------------------------------------------------------------------------
# every route is cofactorless
# ---------------------------------------------------------------------------

P = 2 ** 255 - 19
N_LANES = 24


def _vector_batch():
    """24 lanes over 8 honest keys, five of them replaced by a vector.
    Returns (pubs, msgs, sigs, {vector name: lane})."""
    seeds = [(0xC0FA + i % 8).to_bytes(32, "little")
             for i in range(N_LANES)]
    msgs = [b"cofactorless %d" % i for i in range(N_LANES)]
    pubs = [_edref.pubkey_from_seed(s) for s in seeds]
    sigs = [_edref.sign(s, m) for s, m in zip(seeds, msgs)]
    lanes = {}
    # the ADR-009 divergence vector: residual a pure order-8 component
    lanes["torsion_residual"] = 3
    pubs[3], sigs[3] = torsion_residual_sig(
        (0xADC9).to_bytes(32, "little"), msgs[3])
    # non-canonical R, y = p (decodes to y = 0) and y = p + 5: a decode
    # succeeds, the byte compare must refuse
    lanes["noncanonical_R_y_eq_p"] = 5
    sigs[5] = P.to_bytes(32, "little") + sigs[5][32:]
    lanes["noncanonical_R_y_gt_p"] = 6
    sigs[6] = (P + 5).to_bytes(32, "little") + sigs[6][32:]
    # s >= L: s + L satisfies the group equation, canonicity refuses it
    lanes["s_plus_L"] = 9
    s_big = int.from_bytes(sigs[9][32:], "little") + _edref.L
    sigs[9] = sigs[9][:32] + s_big.to_bytes(32, "little")
    lanes["s_top_byte_ff"] = 10
    sigs[10] = sigs[10][:63] + b"\xff"
    # non-canonical A, y = p: accepted-and-reduced by the reference
    # (Go's fe.SetBytes) to the order-4 point y = 0; an honest-format
    # signature under it is refused by the equation, not by the decode
    lanes["noncanonical_A_y_eq_p"] = 12
    pubs[12] = P.to_bytes(32, "little")
    return pubs, msgs, sigs, lanes


def _oracles(pubs, msgs, sigs):
    from tendermint_tpu.crypto import ed25519 as edkeys
    ref = np.array([bool(_edref.verify(p, m, s))
                    for p, m, s in zip(pubs, msgs, sigs)])
    # OpenSSL where the image has it (PubKey falls back to _edref)
    ssl = np.array([bool(edkeys.PubKey(p).verify_signature(m, s))
                    for p, m, s in zip(pubs, msgs, sigs)])
    return ref, ssl


@pytest.fixture(scope="module")
def route_bitmaps():
    """The vector batch through each route this backend runs, once:
    {route: (bitmap, recorded path)}, with both oracles' verdicts.  One
    device (no mesh), real kernels: the xla/64 ladder and the comb at
    its nb=64, k_pad=16 shapes."""
    mp = pytest.MonkeyPatch()
    mp.setenv("TM_TPU_NO_MESH", "1")
    mp.setattr(sharding, "_PLANE", None)
    mp.setattr(edops, "_comb_min_override", 1)
    degrade.reset()
    edops.table_cache_clear()
    pubs, msgs, sigs, lanes = _vector_batch()
    out = {}
    try:
        mp.setattr(edops, "_comb_enabled_override", False)
        bits = edops.verify_batch(pubs, msgs, sigs, cache_pubs=True)
        out["xla"] = (np.asarray(bits), edops.last_launch()["path"])
        mp.setattr(edops, "_comb_enabled_override", True)
        bits = edops.verify_batch(pubs, msgs, sigs, cache_pubs=True)
        out["comb"] = (np.asarray(bits), edops.last_launch()["path"])
    finally:
        edops.table_cache_clear()
        mp.undo()
        degrade.reset()
    ref, ssl = _oracles(pubs, msgs, sigs)
    return out, lanes, ref, ssl


VECTORS = ["torsion_residual", "noncanonical_R_y_eq_p",
           "noncanonical_R_y_gt_p", "s_plus_L", "s_top_byte_ff",
           "noncanonical_A_y_eq_p"]


@pytest.mark.parametrize("route", ["xla", "comb"])
@pytest.mark.parametrize("vector", VECTORS)
def test_every_route_is_cofactorless(route_bitmaps, vector, route):
    out, lanes, ref, ssl = route_bitmaps
    bits, path = out[route]
    assert path == route
    lane = lanes[vector]
    # refused by the reference's verifier, by OpenSSL, and by the route
    assert not ref[lane] and not ssl[lane]
    assert not bits[lane], f"{route} accepted {vector}"
    # and lane for lane: exactly the vectors are refused, nothing else
    assert (bits == ref).all() and (bits == ssl).all()
    assert int((~bits).sum()) == len(lanes)


@pytest.mark.slow
def test_pallas_route_is_cofactorless_in_interpret_mode(monkeypatch):
    """The same vectors through the fused Pallas kernel, interpreted
    (the TPU's `pallas` route; slow beside the two full-kernel interpret
    tests of test_pallas_ed25519.py)."""
    from jax.experimental import pallas as pl

    import tendermint_tpu.ops.pallas_ed25519 as pe

    orig = pl.pallas_call
    monkeypatch.setattr(
        pe.pl, "pallas_call",
        lambda *a, **k: orig(*a, **{**k, "interpret": True}))
    pubs, msgs, sigs, lanes = _vector_batch()
    packed, host_ok = edops.prepare_batch_packed(pubs, sigs, msgs)
    nb = edops.bucket_size(N_LANES)
    packed = np.pad(packed, [(0, 0), (0, nb - N_LANES)])
    out = np.asarray(pe.verify_packed_pallas(
        np.asarray(packed), tile=nb))[:N_LANES] & host_ok
    ref, _ = _oracles(pubs, msgs, sigs)
    assert (out == ref).all()
    assert not out[sorted(lanes.values())].any()


# ---------------------------------------------------------------------------
# what went with the withdrawn routes
# ---------------------------------------------------------------------------


def test_replay_window_verifies_through_the_scheduler(monkeypatch):
    """One window, a running VerifyScheduler: the coalesced batch is
    submitted at Priority.BLOCKSYNC and nothing steps around the
    scheduler (the `coordinated` fork for the multi-process plane is
    gone: replay has one way to the verifier)."""
    from helpers import build_chain, make_genesis
    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.blocksync.replay import replay_window
    from tendermint_tpu.crypto import scheduler as vsched
    from tendermint_tpu.libs.kvdb import MemDB
    from tendermint_tpu.state.execution import BlockExecutor
    from tendermint_tpu.state.state import state_from_genesis
    from tendermint_tpu.state.store import StateStore
    from tendermint_tpu.store.block_store import BlockStore

    gdoc, privs = make_genesis(4)
    blocks, commits, _ = build_chain(gdoc, privs, 6)
    ex = BlockExecutor(StateStore(MemDB()), KVStoreApplication())
    store, state = BlockStore(MemDB()), state_from_genesis(gdoc)

    submitted = []
    real_submit = vsched.VerifyScheduler.submit

    def spy(self, items, prio=vsched.Priority.COMMIT, **kw):
        submitted.append((vsched.Priority(prio), len(items)))
        return real_submit(self, items, prio, **kw)

    def bypass(*a, **kw):
        raise AssertionError("replay stepped around the scheduler")

    monkeypatch.setattr(vsched.VerifyScheduler, "submit", spy)
    sch = vsched.install(vsched.VerifyScheduler())
    sch.start()
    try:
        # the direct path verify_items takes without a scheduler
        monkeypatch.setattr(vsched._batch, "BatchVerifier", bypass)
        state, n = replay_window(ex, store, state, blocks, commits,
                                 max_window=8)
    finally:
        sch.stop()
        vsched.uninstall(sch)
    assert n == 6 and state.last_block_height == 6
    window = [s for s in submitted if s[0] == vsched.Priority.BLOCKSYNC]
    assert len(window) == 1 and window[0][1] >= 6 * 3
    import inspect
    assert "coordinated" not in inspect.signature(
        vsched.verify_items).parameters


def test_old_rlc_key_in_toml_is_ignored_and_verdicts_stay_exact(
        tmp_path, monkeypatch):
    """An operator's config file from before the RLC route was withdrawn
    still loads; `rlc = true` is an unknown key, no field takes it, and
    the node's verifier gives the reference's cofactorless verdict on
    the vector the cofactored route accepted."""
    from tendermint_tpu.config.config import Config
    from tendermint_tpu.crypto import ed25519 as edkeys
    from tendermint_tpu.crypto.batch import BatchVerifier

    cfg = Config(home=str(tmp_path), moniker="old-file")
    cfg.save()
    path = os.path.join(cfg.config_dir(), "config.toml")
    text = open(path).read()
    assert "rlc" not in text
    assert "[batch_verifier]\n" in text
    with open(path, "w") as f:
        f.write(text.replace("[batch_verifier]\n",
                             "[batch_verifier]\nrlc = true\n"))
    loaded = Config.load(str(tmp_path))
    assert not hasattr(loaded.batch_verifier, "rlc")
    assert loaded.batch_verifier == cfg.batch_verifier
    assert not any(k.startswith("TM_TPU_RLC") for k in os.environ)

    # the node's verifier at that config, on the device lane
    monkeypatch.setenv("TM_TPU_FORCE_BATCH", "1")
    monkeypatch.setenv("TM_TPU_NO_MESH", "1")
    monkeypatch.setattr(sharding, "_PLANE", None)
    degrade.configure(degrade.DegradeConfig(launch_timeout_s=600.0))
    try:
        pubs, msgs, sigs, lanes = _vector_batch()
        n = max(N_LANES, loaded.batch_verifier.tpu_threshold)
        bv = BatchVerifier(tpu_threshold=loaded.batch_verifier.tpu_threshold)
        for i in range(n):
            j = i % N_LANES
            bv.add(edkeys.PubKey(pubs[j]), msgs[j], sigs[j])
        all_ok, bits = bv.verify()
        assert edops.last_launch()["path"] == "xla"
    finally:
        degrade.reset()
    assert not all_ok
    assert not bits[lanes["torsion_residual"]]
    want = np.array([bool(_edref.verify(pubs[i % N_LANES],
                                        msgs[i % N_LANES],
                                        sigs[i % N_LANES]))
                     for i in range(n)])
    assert (np.asarray(bits) == want).all()
