#!/usr/bin/env python3
"""chip_smoke.py — the verify path, once, on the chip, at deployment size.

One process drives the node's own entry points (ValidatorSet.verify_commit*,
ConsensusState._preverify_votes + VoteSet.add_vote behind the VerifyScheduler,
edops.prewarm, blocksync.replay_window behind the BlockPipeline) at 150 /
10,000 / 100,000 validators, checks every verdict against per-signature
OpenSSL (the `cryptography` package, called directly — not through the repo's
key types), and checks that each launch took the route the row count says it
must.  One phase verifies a 10,000-validator commit whose keys are
ed25519, secp256k1 and sr25519 in thirds, one device lane a scheme, against
the benchmark's plain per-scheme reference.  The degrade ladder stays armed;
any use of it fails the run.

It refuses to start (exit 2, reason on stderr, nothing on stdout) unless JAX's
platform is `tpu`, no TM_TPU_* variable steers the path, the native staging
library built and OpenSSL is present.  Exit 0 only if every phase and the
end-of-run gate passed.  The last line of stdout is one JSON object with
exactly these keys: {"ok": ..., "device": {"platform", "kind", "count"}}.
The line before it is the PR's summary (phases, gate, walls, compile cache,
..., "claim": null); the same goes to chiprun_out/chip_smoke/report.json.
Walls in it are set-up facts for planning, not results; nothing is claimed.

    python chip_smoke.py [--seed N]
"""
from __future__ import annotations

import argparse
import contextlib
import faulthandler
import hashlib
import json
import os
import shutil
import sys
import time
import traceback
from fractions import Fraction

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
WATCHDOG_S = 1150        # the driver allows 1200 s, compilation included;
#                          a cold run takes 474-487 s on one chip (PERF.md)
CATCHUP_BLOCKS = 64      # two of BlocksyncReactor's 32-block windows
CHAIN_ID = "chip-smoke"


# ---------------------------------------------------------------------------
# start-up refusals and the end-of-run gate: pure functions of what they are
# handed, so tests/test_chip_smoke.py drives them on the CPU
# ---------------------------------------------------------------------------

def refusals(environ) -> list:
    """Reasons the environment alone gives not to start: the smoke tests
    the default path, not a steered one."""
    out = []
    steered = sorted(k for k in environ if k.startswith("TM_TPU_"))
    if steered:
        out.append("steering variables are set: " + ", ".join(steered))
    if "xla_force_host_platform_device_count" in environ.get("XLA_FLAGS", ""):
        out.append("XLA_FLAGS forces host platform devices")
    return out


def expected_route(n: int, cache_pubs: bool, resident: bool, nshard: int,
                   pallas: bool = True):
    """(path, padded lanes, shards) ops/ed25519.verify_batch must take for
    an n-row all-ed25519 batch — derived from the row count through the
    module's own constants, never read back from a launch record.
    `resident`: every key of the batch is in one cached comb table set."""
    from tendermint_tpu.ops import ed25519 as edops

    bucket = edops.bucket_size(n)
    tile = edops.PALLAS_TILE
    if nshard > 1 and n >= nshard * (tile if pallas else 1):
        shard_mult = max(-(-bucket // nshard) * nshard, nshard)
        if resident:
            return "mesh-comb", shard_mult, nshard
        if not pallas:
            return "mesh-xla", shard_mult, nshard
        unit = nshard * tile
        return "mesh-pallas", -(-max(bucket, unit) // unit) * unit, nshard
    if resident:
        nb = sum(edops.bucket_size(min(edops.MAX_CHUNK, n - a))
                 for a in range(0, n, edops.MAX_CHUNK))
        return "comb", nb, 1
    if not pallas:
        return "xla", bucket, 1
    if cache_pubs and n >= edops.PUB_CACHE_MIN:
        chunk = edops._split_chunk(n)
        return "pallas-split", -(-n // chunk) * chunk, 1
    return "pallas", max(tile, bucket), 1


def gate(rt, phases: dict, comb_declines: int) -> list:
    """The whole-process gate, read after the last phase: `rt` is the
    degrade runtime every dispatch went through, `phases` maps a phase
    name to its result dict, `comb_declines` is how many budget declines
    of the comb table build the phases accounted for.  Returns the list
    of failures (empty = pass)."""
    from tendermint_tpu.crypto import degrade

    m = rt.metrics
    bad = []
    for name, counter in (("host_fallbacks", m.host_fallbacks),
                          ("device_failures", m.device_failures)):
        hits = {k: v for k, v in counter.items().items() if v}
        if hits:
            bad.append(f"{name}: {_fmt_labels(hits)}")
    if rt.breaker.state != degrade.CLOSED or rt.breaker.opened_total:
        bad.append(f"breaker {rt.breaker.state}, opened "
                   f"{rt.breaker.opened_total}x")
    declined = 0
    for (path, outcome), v in m.msm_route.items().items():
        if outcome == "error" and v:
            bad.append(f"route {path} outcome=error x{v:g}")
        if outcome == "declined" and v:
            if path == "comb":
                declined = int(v)
            else:
                bad.append(f"route {path} outcome=declined x{v:g}")
    if declined != comb_declines:
        bad.append(f"comb declined x{declined}, the phases account for "
                   f"{comb_declines}")
    for name, res in phases.items():
        if not res.get("launches"):
            bad.append(f"{name}: no device launch")
        if res.get("repeat_compiles"):
            bad.append(f"{name}: compiled inside repeat calls: "
                       f"{res['repeat_compiles']}")
    return bad


def _fmt_labels(hits: dict) -> str:
    return ", ".join(f"{'/'.join(k)} x{v:g}" for k, v in sorted(hits.items()))


# ---------------------------------------------------------------------------
# data from a seed, through the repo's own key / types / privval code
# ---------------------------------------------------------------------------

def seeded_privs(seed: int, tag: str, n: int):
    from tendermint_tpu.crypto import ed25519 as edkeys
    return [edkeys.PrivKey(hashlib.sha256(
        b"chip-smoke/%d/%s/%d" % (seed, tag.encode(), i)).digest())
        for i in range(n)]


def make_valset(privs, power: int = 10):
    """(ValidatorSet, privs reordered to the set's own validator order)."""
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import ValidatorSet

    vset = ValidatorSet([Validator.new(p.pub_key(), power) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    return vset, [by_addr[v.address] for v in vset.validators]


def block_id(tag: bytes):
    from tendermint_tpu.types.basic import BlockID, PartSetHeader
    return BlockID(hashlib.sha256(b"block/" + tag).digest(),
                   PartSetHeader(1, hashlib.sha256(b"parts/" + tag).digest()))


def signed_commit(vset, privs, height: int, bid):
    """A full commit for `bid`: one precommit per validator, each with its
    own timestamp (so no two sign-bytes are equal), signed by its key."""
    from tendermint_tpu.types.basic import (BlockIDFlag, SignedMsgType,
                                            Timestamp)
    from tendermint_tpu.types.canonical import canonical_vote_bytes
    from tendermint_tpu.types.commit import Commit, CommitSig

    sigs = []
    for i, (val, priv) in enumerate(zip(vset.validators, privs)):
        ts = Timestamp(1_700_000_000 + height, i)
        sb = canonical_vote_bytes(CHAIN_ID, SignedMsgType.PRECOMMIT, height,
                                  0, bid, ts)
        sigs.append(CommitSig(BlockIDFlag.COMMIT, val.address, ts,
                              priv.sign(sb)))
    return Commit(height, 0, bid, sigs)


def flip(sig: bytes) -> bytes:
    return bytes([sig[0] ^ 1]) + sig[1:]


def tampered_commit(commit, idxs):
    from tendermint_tpu.types.commit import Commit, CommitSig

    sigs = list(commit.signatures)
    for i in idxs:
        cs = sigs[i]
        sigs[i] = CommitSig(cs.block_id_flag, cs.validator_address,
                            cs.timestamp, flip(cs.signature))
    return Commit(commit.height, commit.round, commit.block_id, sigs)


def oracle(pubs, msgs, sigs) -> np.ndarray:
    """Per-signature OpenSSL verdicts, independent of the code under test."""
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey)

    out = np.zeros(len(pubs), dtype=bool)
    for i, (p, m, s) in enumerate(zip(pubs, msgs, sigs)):
        try:
            Ed25519PublicKey.from_public_bytes(bytes(p)).verify(
                bytes(s), bytes(m))
            out[i] = True
        except (InvalidSignature, ValueError):
            pass
    return out


def commit_triples(vset, commit, idxs=None):
    idxs = range(len(commit.signatures)) if idxs is None else idxs
    return ([vset.validators[i].pub_key.bytes() for i in idxs],
            [commit.vote_sign_bytes(CHAIN_ID, i) for i in idxs],
            [commit.signatures[i].signature for i in idxs])


def bulk_bitmap(vset, commit, idxs=None) -> np.ndarray:
    """The bitmap behind verify_commit*: the same verify_sigs_bulk call
    ValidatorSet._verify_sigs_batch makes (raw pubkey matrix rows, batched
    sign bytes), returned instead of collapsed into raise / no raise."""
    from tendermint_tpu.crypto.batch import verify_sigs_bulk
    from tendermint_tpu.types.canonical import commit_sign_bytes_batch

    idxs = list(range(len(commit.signatures))) if idxs is None else idxs
    mat, _ = vset._pub_matrix()
    pubs = mat if len(idxs) == mat.shape[0] else mat[np.asarray(idxs)]
    return verify_sigs_bulk(
        pubs, commit_sign_bytes_batch(CHAIN_ID, commit, idxs),
        [commit.signatures[i].signature for i in idxs])


# ---------------------------------------------------------------------------
# one phase: steps run under launch capture, each compared with what the
# row count says the route must be
# ---------------------------------------------------------------------------

class Phase:
    def __init__(self, name: str, nshard: int, pallas: bool):
        self.name = name
        self.nshard = nshard
        self.pallas = pallas
        self.failures = []
        self.launches = []         # every launch record of the phase
        self.repeat_compiles = []
        self.facts = {}
        self._t0 = time.perf_counter()

    def check(self, ok: bool, what: str):
        if not ok:
            self.failures.append(what)
        return ok

    def capture(self, fn, repeat: bool = False):
        """Run fn; returns (its result, the launch records it caused,
        its wall).  `repeat`: every shape was launched before in this
        process, so any compile is a failure."""
        from tendermint_tpu.crypto import devobs

        seq0 = devobs.last_seq()
        t0 = time.perf_counter()
        res = fn()
        wall = time.perf_counter() - t0
        recs = devobs.records(since_seq=seq0)
        self.launches.extend(recs)
        if repeat:
            self.repeat_compiles.extend(
                f"{r['path']}/nb={r['nb']}" for r in recs
                if r.get("first_launch") or r.get("compile_s"))
        return res, recs, wall

    def expect(self, recs, rows, what: str):
        """rows: [(n, cache_pubs, resident)] — one expected launch each,
        in order."""
        want = [(n,) + expected_route(n, cp, res, self.nshard, self.pallas)
                for n, cp, res in rows]
        got = [(r["n"], r["path"], r["nb"], r["shards"]) for r in recs]
        # the budget decides between the replicated and the sharded-table
        # mesh comb; both are the mesh comb
        got = [(n, "mesh-comb" if p.startswith("mesh-comb") else p, nb, s)
               for n, p, nb, s in got]
        self.check(got == want, f"{what}: launches (n, path, nb, shards) "
                                f"{got}, expected {want}")
        from tendermint_tpu.ops import ed25519 as edops
        for r in recs:
            if r["shards"] > 1:
                rows_ = r.get("shard_rows") or []
                self.check(len(rows_) == r["shards"]
                           and sum(rows_) == r["n"],
                           f"{what}: shard rows {rows_} for {r['n']} rows")
                # a launch the mesh threshold admits must give every
                # chip rows.  The pow2 bucket cut into contiguous slices
                # does not (10,000 rows: 4096/4096/1808/0, PERF.md), so
                # this fails on a TPU mesh until the split is repaired
                if r["n"] >= r["shards"] * edops.PALLAS_TILE:
                    self.check(all(rows_),
                               f"{what}: a shard with no rows, {rows_} "
                               f"for {r['n']} rows in {r['nb']} lanes")

    def expect_consistent(self, recs, resident: bool, what: str):
        """Each record's route must be what its own row count implies
        (for launches whose sizes the coalescing window decides)."""
        for r in recs:
            self.expect([r], [(r["n"], False, resident)], what)

    def result(self) -> dict:
        cold = [{"path": r["path"], "nb": r["nb"], "shards": r["shards"],
                 "n": r["n"], "wall_s": round(r["wall_s"], 3),
                 "compile_s": round(r.get("compile_s", 0.0), 3)}
                for r in self.launches
                if r.get("first_launch") or r.get("compile_s")]
        return {"ok": not self.failures, "failures": self.failures,
                "launches": len(self.launches),
                "paths": sorted({f"{r['path']}/nb={r['nb']}"
                                 f"/shards={r['shards']}"
                                 for r in self.launches}),
                "cold_launches": cold,
                "repeat_compiles": self.repeat_compiles,
                "phase_s": round(time.perf_counter() - self._t0, 3),
                **self.facts}


def raises(fn, exc_type):
    try:
        fn()
    except exc_type as e:
        return e
    return None


# ---------------------------------------------------------------------------
# the phases
# ---------------------------------------------------------------------------

def phase_commit_150(ph: Phase, world, resident: bool):
    """ValidatorSet.verify_commit over 3 heights, then one tampered
    commit: attribution through the raise, exact lanes through the
    bitmap.  Runs twice: before prewarm (ladder) and after (comb)."""
    from tendermint_tpu.types.validator_set import CommitVerifyError

    vset, commits = world["vset150"], world["commits150"]
    n = vset.size()
    repeat_walls = []
    for k, c in enumerate(commits):
        _, recs, wall = ph.capture(
            lambda c=c: vset.verify_commit(CHAIN_ID, c.block_id, c.height,
                                           c), repeat=k > 0)
        ph.expect(recs, [(n, True, resident)], f"height {c.height}")
        ph.check(bool(recs) and recs[0]["first_launch"] == (k == 0),
                 f"height {c.height}: first_launch flag "
                 f"{[r['first_launch'] for r in recs]}")
        if resident and recs:
            ph.check(recs[0].get("group_ops", {}).get("doublings") == 0,
                     f"height {c.height}: comb record counts doublings")
        if k:
            repeat_walls.append(wall)
        ph.check(oracle(*commit_triples(vset, c)).all(),
                 f"height {c.height}: the oracle rejects an honest commit")
    bad_idx = [7, 64, n - 1]
    bad = tampered_commit(commits[0], bad_idx)
    err, recs, _ = ph.capture(lambda: raises(
        lambda: vset.verify_commit(CHAIN_ID, bad.block_id, bad.height, bad),
        CommitVerifyError), repeat=True)
    ph.check(err is not None and f"(#{bad_idx[0]})" in str(err),
             f"tampered commit: {err!r}, expected wrong signature "
             f"#{bad_idx[0]}")
    bits, recs, _ = ph.capture(lambda: bulk_bitmap(vset, bad), repeat=True)
    ph.expect(recs, [(n, True, resident)], "tampered bitmap")
    want = oracle(*commit_triples(vset, bad))
    ph.check(np.array_equal(bits, want) and
             sorted(np.flatnonzero(~bits)) == bad_idx,
             f"tampered bitmap rejects {sorted(np.flatnonzero(~bits))}, "
             f"oracle {sorted(np.flatnonzero(~want))}, tampered {bad_idx}")
    ph.facts["repeat_call_s"] = [round(w, 4) for w in repeat_walls]
    return bits


def phase_votes_150(ph: Phase, world):
    """150 prevotes of one height/round through the consensus receive
    loop's batch pre-verification (scheduler, CONSENSUS class), then the
    serial VoteSet.add_vote applies: SigCache hits, one bad vote out."""
    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.consensus.config import ConsensusConfig
    from tendermint_tpu.consensus.round_types import VoteMessage
    from tendermint_tpu.consensus.state import ConsensusState
    from tendermint_tpu.crypto import batch as cbatch
    from tendermint_tpu.crypto import scheduler as vsched
    from tendermint_tpu.libs.kvdb import MemDB
    from tendermint_tpu.privval.file_pv import FilePV
    from tendermint_tpu.state.execution import BlockExecutor
    from tendermint_tpu.state.state import state_from_genesis
    from tendermint_tpu.state.store import StateStore
    from tendermint_tpu.store.block_store import BlockStore
    from tendermint_tpu.types.basic import SignedMsgType, Timestamp
    from tendermint_tpu.types.vote import Vote
    from tendermint_tpu.types.vote_set import VoteSet, VoteSetError

    state = state_from_genesis(world["gdoc150"])
    cs = ConsensusState(ConsensusConfig(), state,
                        BlockExecutor(StateStore(MemDB()),
                                      KVStoreApplication()),
                        BlockStore(MemDB()), name="chip-smoke")
    vals = state.validators
    by_addr = {p.pub_key().address(): p for p in world["privs150"]}
    bid = block_id(b"votes")
    votes = []
    for i, val in enumerate(vals.validators):
        v = Vote(type=SignedMsgType.PREVOTE, height=cs.rs.height, round=0,
                 block_id=bid, timestamp=Timestamp(1_700_000_100, i),
                 validator_address=val.address, validator_index=i)
        votes.append(FilePV(by_addr[val.address]).sign_vote(CHAIN_ID, v))
    bad_i = 11
    votes[bad_i].signature = flip(votes[bad_i].signature)
    n = len(votes)
    hits0 = cbatch.verified_sigs.hits
    _, recs, wall = ph.capture(lambda: cs._preverify_votes(
        [(VoteMessage(v), "peer") for v in votes]), repeat=True)
    ph.expect(recs, [(n, False, False)], "preverify")
    rep = vsched.last_latency_report()
    ph.check(rep.get("path") == "sched-device" and rep.get("lanes") == n
             and [r["priority"] for r in rep.get("requests", [])]
             == ["consensus"],
             f"scheduler window: {rep.get('path')}, {rep.get('lanes')} "
             f"lanes, {rep.get('requests')}")
    vs = VoteSet(CHAIN_ID, cs.rs.height, 0, SignedMsgType.PREVOTE, vals)
    rejected = []
    for v in votes:
        try:
            vs.add_vote(v)
        except VoteSetError:
            rejected.append(v.validator_index)
    ph.check(rejected == [bad_i], f"rejected votes {rejected}")
    ph.check(cbatch.verified_sigs.hits - hits0 == n - 1,
             f"{cbatch.verified_sigs.hits - hits0} SigCache hits for "
             f"{n - 1} honest votes")
    ph.check(vs.has_two_thirds_majority(), "no 2/3 majority")
    msgs = [v.sign_bytes(CHAIN_ID) for v in votes]
    want = oracle([vals.validators[v.validator_index].pub_key.bytes()
                   for v in votes], msgs, [v.signature for v in votes])
    ph.check(sorted(np.flatnonzero(~want)) == [bad_i],
             "the oracle disagrees about the bad vote")
    ph.facts["preverify_s"] = round(wall, 4)


def phase_prewarmed(ph: Phase, world, ladder_bits):
    """edops.prewarm exactly as LightServe calls it at start, then
    commit_150 again: the comb, no doublings, the same bitmap."""
    from tendermint_tpu.ops import ed25519 as edops

    vset = world["vset150"]
    ok, recs, wall = ph.capture(lambda: edops.prewarm(
        [v.pub_key.bytes() for v in vset.validators]))
    ph.check(ok is True, f"prewarm returned {ok!r}")
    ph.expect(recs, [(4, False, True)], "prewarm kernel warm-up")
    ph.facts["prewarm_s"] = round(wall, 3)
    bits = phase_commit_150(ph, world, resident=True)
    ph.check(np.array_equal(bits, ladder_bits),
             "comb bitmap differs from the ladder's")


def phase_light_10k(ph: Phase, world):
    """The three commit checks of a light client on a 10,000-validator
    chain, twice each; tampered lanes through the light prefix and the
    full bitmap."""
    from tendermint_tpu.crypto import devobs
    from tendermint_tpu.ops import ed25519 as edops
    from tendermint_tpu.types.validator_set import CommitVerifyError

    vset, commit = world["vset10k"], world["commit10k"]
    n = vset.size()
    power = vset.validators[0].voting_power
    total = vset.total_voting_power()
    n_light = (total * 2 // 3) // power + 1
    n_trust = (total // 3) // power + 1
    bid, h = commit.block_id, commit.height
    calls = [
        ("verify_commit", n,
         lambda: vset.verify_commit(CHAIN_ID, bid, h, commit)),
        ("verify_commit_light", n_light,
         lambda: vset.verify_commit_light(CHAIN_ID, bid, h, commit)),
        ("verify_commit_light_trusting", n_trust,
         lambda: vset.verify_commit_light_trusting(CHAIN_ID, commit,
                                                   Fraction(1, 3))),
    ]
    walls = {}
    split = ph.pallas and ph.nshard == 1   # the path with a pubkey cache
    for name, rows, fn in calls:
        hits0 = edops._pub_cache.hits
        for rep in (False, True):
            _, recs, wall = ph.capture(fn, repeat=rep)
            ph.expect(recs, [(rows, True, False)], name)
            walls.setdefault(name, []).append(round(wall, 4))
        if split and rows >= edops.PUB_CACHE_MIN:
            ph.check(edops._pub_cache.hits > hits0,
                     f"{name}: second call missed the pubkey cache")
    if split:
        pub_bytes = devobs.ledger_report().get("pub_cache", {}).get("bytes")
        ph.check(bool(pub_bytes), f"HBM ledger pub_cache = {pub_bytes}")
        ph.facts["pub_cache_bytes"] = pub_bytes
    ph.check(oracle(*commit_triples(vset, commit)).all(),
             "the oracle rejects the honest commit")
    bad_idx = [3, n_trust, n_light - 1, n - 1]
    bad = tampered_commit(commit, bad_idx)
    err, recs, _ = ph.capture(lambda: raises(
        lambda: vset.verify_commit_light(CHAIN_ID, bid, h, bad),
        CommitVerifyError), repeat=True)
    ph.check(err is not None and f"(#{bad_idx[0]})" in str(err),
             f"tampered light check: {err!r}")
    bits, recs, _ = ph.capture(lambda: bulk_bitmap(vset, bad), repeat=True)
    ph.expect(recs, [(n, True, False)], "tampered bitmap")
    want = oracle(*commit_triples(vset, bad))
    ph.check(np.array_equal(bits, want) and
             sorted(np.flatnonzero(~bits)) == bad_idx,
             f"tampered bitmap rejects {sorted(np.flatnonzero(~bits))}, "
             f"oracle {sorted(np.flatnonzero(~want))}")
    ph.facts["call_s"] = walls
    ph.facts["rows"] = {"full": n, "light": n_light, "trusting": n_trust}
    # one budget decline per call that could have built the tables
    return sum(1 for r in ph.launches if r["n"] >= edops.comb_min_batch())


def build_chain(world, n_blocks: int, n_txs: int):
    """The source chain the catch-up replays: real blocks proposed by the
    set's proposer, 20 kvstore txs each, committed by all 150 keys and
    applied through a BlockExecutor (what a peer would serve)."""
    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.blocksync.replay import block_id_of
    from tendermint_tpu.libs.kvdb import MemDB
    from tendermint_tpu.state.execution import BlockExecutor
    from tendermint_tpu.state.state import state_from_genesis
    from tendermint_tpu.state.store import StateStore
    from tendermint_tpu.types.basic import BlockID, Timestamp
    from tendermint_tpu.types.commit import Commit

    by_addr = {p.pub_key().address(): p for p in world["privs150"]}
    ex = BlockExecutor(StateStore(MemDB()), KVStoreApplication())
    state = state_from_genesis(world["gdoc150"])
    blocks, commits = [], []
    last_commit = Commit(0, 0, BlockID(), [])
    for h in range(1, n_blocks + 1):
        txs = [b"smoke%d.%d=%s" % (h, i, b"v" * 64) for i in range(n_txs)]
        block = state.make_block(
            h, txs, last_commit, [],
            state.validators.get_proposer().address,
            block_time=Timestamp(1_700_000_000 + h, 0))
        bid, _ = block_id_of(block)
        privs = [by_addr[v.address] for v in state.validators.validators]
        commit = signed_commit(state.validators, privs, h, bid)
        blocks.append(block)
        commits.append(commit)
        state, _ = ex.apply_block(state, bid, block)
        last_commit = commit
    return blocks, commits, state


def phase_catchup_150(ph: Phase, world, resident: bool):
    """blocksync.replay_window through the installed BlockPipeline and
    scheduler into file-backed SQLite stores (synchronous=FULL), then the
    same chain with one flipped LastCommit signature."""
    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.blocksync.replay import (WindowSyncError,
                                                 replay_window)
    from tendermint_tpu.libs.kvdb import GroupCommitDB, SQLiteDB
    from tendermint_tpu.state import pipeline as blockpipe
    from tendermint_tpu.state.execution import BlockExecutor
    from tendermint_tpu.state.state import state_from_genesis
    from tendermint_tpu.state.store import StateStore
    from tendermint_tpu.store.block_store import BlockStore
    from tendermint_tpu.types.block import Block

    n_blocks = CATCHUP_BLOCKS
    t0 = time.perf_counter()
    blocks, commits, src = build_chain(world, n_blocks, n_txs=20)
    ph.facts["build_chain_s"] = round(time.perf_counter() - t0, 3)
    all_ok = True
    for c in commits:
        all_ok &= bool(oracle(*commit_triples(world["vset150"], c)).all())
    ph.check(all_ok, "the oracle rejects an honest chain commit")
    window = 32                      # BlocksyncReactor's default
    dbs = []

    def stores(tag):
        # the node's own layout (node/node.py:208-223), durability FULL
        bdb = GroupCommitDB(SQLiteDB(
            os.path.join(OUT_DIR, f"{tag}_blocks.db"), synchronous="FULL"))
        sdb = GroupCommitDB(SQLiteDB(
            os.path.join(OUT_DIR, f"{tag}_state.db"), commit_every=64,
            synchronous="FULL"))
        dbs.extend((bdb, sdb))
        return (BlockExecutor(StateStore(sdb), KVStoreApplication()),
                BlockStore(bdb))

    def replay(tag, blks):
        # the reactor's flow (blocksync/reactor.py try_sync): block i is
        # certified by block i+1's LastCommit; the tip by its seen commit
        ex, store = stores(tag)
        state = state_from_genesis(world["gdoc150"])
        certs = [b.last_commit for b in blks[1:]] + [commits[-1]]
        done = 0
        while done < len(blks):
            state, k = replay_window(ex, store, state, blks[done:],
                                     certs[done:], max_window=window)
            done += k
        return state, store

    pipe = blockpipe.running()
    piped0 = pipe.windows_pipelined
    (state, store), recs, wall = ph.capture(lambda: replay("sync", blocks))
    ph.check(state.last_block_height == n_blocks
             and store.height() == n_blocks,
             f"synced to {state.last_block_height}/{store.height()}, "
             f"chain is {n_blocks}")
    ph.check(state.app_hash == src.app_hash,
             "app hash differs from the source chain's")
    ph.check(pipe.windows_pipelined - piped0 == -(-n_blocks // window),
             f"{pipe.windows_pipelined - piped0} pipelined windows")
    ph.expect_consistent(recs, resident, "replay")
    # one block brings at most its certifier's >2/3 prefix plus its own
    # full LastCommit; a launch of more rows spans more than one block
    nval = world["vset150"].size()
    per_block = (2 * nval) // 3 + 1 + nval
    largest = max((r["n"] for r in recs), default=0)
    ph.check(largest > per_block,
             f"largest launch {largest} rows: none spans more than one "
             f"block ({per_block} rows at most)")
    ph.facts.update(replay_s=round(wall, 3), replay_launches=len(recs),
                    largest_launch_rows=largest,
                    rows_per_block_max=per_block,
                    launch_rows=sorted({r["n"] for r in recs}))
    # a lying peer: one signature of block h's LastCommit flipped, past
    # the >2/3 prefix that certifies block h-1 (so h-1 applies and h is
    # the height that fails)
    h = n_blocks // 2
    lied = list(blocks)
    orig = blocks[h - 1]
    lied[h - 1] = Block(
        header=orig.header, data=orig.data, evidence=orig.evidence,
        last_commit=tampered_commit(orig.last_commit,
                                    [world["vset150"].size() - 1]))
    degraded0 = pipe.windows_degraded
    err, recs, _ = ph.capture(lambda: raises(
        lambda: replay("lied", lied), WindowSyncError))
    ph.check(err is not None and err.height == h,
             f"flipped LastCommit signature at height {h}: {err!r}")
    ph.check(pipe.windows_degraded == degraded0 + 1,
             "the device batch did not reject the flipped signature "
             "(the window never degraded to the strict path)")
    ph.expect_consistent(recs, resident, "lying replay")
    for db in dbs:
        db.close()
    for name in os.listdir(OUT_DIR):
        if name.endswith(".db"):
            os.unlink(os.path.join(OUT_DIR, name))


def phase_commit_100k(ph: Phase, world):
    """ValidatorSet.verify_commit at 100,000 validators, then the bulk
    bitmap with 5 tampered lanes against the full host oracle."""
    from tendermint_tpu.ops import ed25519 as edops

    vset, commit = world["vset100k"], world["commit100k"]
    n = vset.size()
    _, recs, wall = ph.capture(lambda: vset.verify_commit(
        CHAIN_ID, commit.block_id, commit.height, commit))
    ph.expect(recs, [(n, True, False)], "verify_commit")
    if ph.pallas and ph.nshard == 1 and recs:
        chunks = -(-n // edops._split_chunk(n))
        ph.check(recs[0].get("chunks") == chunks,
                 f"{recs[0].get('chunks')} chunks, expected {chunks}")
    ph.facts["first_call_s"] = round(wall, 3)
    # five lanes: both ends, both sides of a chunk seam, one mid-batch
    c = edops._split_chunk(n)
    bad_idx = [0, c - 1, c, 4 * c, n - 1] if n > 4 * c + 1 \
        else [0, n // 4, n // 2, n - 2, n - 1]
    bad = tampered_commit(commit, bad_idx)
    bits, recs, wall = ph.capture(lambda: bulk_bitmap(vset, bad),
                                  repeat=True)
    ph.expect(recs, [(n, True, False)], "tampered bitmap")
    ph.facts["repeat_call_s"] = round(wall, 3)
    t0 = time.perf_counter()
    want = oracle(*commit_triples(vset, bad))
    ph.facts["oracle_s"] = round(time.perf_counter() - t0, 3)
    ph.check(np.array_equal(bits, want) and
             sorted(np.flatnonzero(~bits)) == bad_idx,
             f"tampered bitmap rejects {sorted(np.flatnonzero(~bits))}, "
             f"oracle {sorted(np.flatnonzero(~want))}")
    honest = commit_triples(vset, commit, bad_idx)
    ph.check(oracle(*honest).all(), "the oracle rejects the honest lanes")
    return sum(1 for r in ph.launches if r["n"] >= edops.comb_min_batch())


def phase_commit_10k_mixed(ph: Phase, world):
    """ValidatorSet.verify_commit on a set whose keys are ed25519,
    secp256k1 and sr25519 in thirds (BASELINE config 5): one device lane
    a scheme, each on the route its row count says, the other two lanes'
    first launch compiled on the lane worker inside the launch deadline's
    stopped clock.  Then every case of `val10k-mixed-commit`'s check
    (perfbench/traffic/mixed_commit.py `cases`): the program's verdicts
    and its tampered bitmap against the plain per-scheme reference
    (perfbench/reference/mixed_commit.py: OpenSSL, BIP-340 and schnorrkel
    in Python integers)."""
    from perfbench.traffic import mixed_commit as gen
    from tendermint_tpu.ops import ed25519 as edops
    from tendermint_tpu.ops import secp, sr25519

    w = world["mixed"]
    commit = w["commits"][-1]
    rows = gen._scheme_rows(w, commit)
    _, recs, wall = ph.capture(lambda: w["vset"].verify_commit(
        CHAIN_ID, commit.block_id, commit.height, commit))
    n_ed = len(rows["ed25519"])
    want = sorted([
        (n_ed,) + expected_route(n_ed, False, False, ph.nshard, ph.pallas),
        (len(rows["secp256k1"]), secp.LANE_PATH,
         edops.bucket_size(len(rows["secp256k1"])), 1),
        (len(rows["sr25519"]), sr25519.LANE_PATH,
         edops.bucket_size(len(rows["sr25519"])), 1)])
    got = sorted((r["n"], r["path"], r["nb"], r["shards"]) for r in recs)
    ph.check(got == want, f"verify_commit: launches (n, path, nb, shards) "
                          f"{got}, expected {want}")
    ph.facts["first_call_s"] = round(wall, 3)
    ph.facts["lane_wall_s"] = {r["path"]: round(r["wall_s"], 3)
                               for r in recs}
    # not `repeat`: the check's short commit (2/3 of the rows) pads to
    # the first call's buckets at 10,000 validators, and may not at a
    # test's few hundred; a compile here shows under cold_launches
    failures, _, wall = ph.capture(lambda: gen.check(w))
    ph.facts["check_s"] = round(wall, 3)
    for why in failures:
        ph.check(False, why)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def build_world(seed: int, n_mid: int, n_big: int) -> dict:
    from tendermint_tpu.types.basic import Timestamp
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator

    w = {}
    privs = seeded_privs(seed, "150", 150)
    w["gdoc150"] = GenesisDoc(
        chain_id=CHAIN_ID, genesis_time=Timestamp(1_700_000_000, 0),
        validators=[GenesisValidator(
            address=p.pub_key().address(), pub_key_type="ed25519",
            pub_key_bytes=p.pub_key().bytes(), power=10) for p in privs])
    w["vset150"], w["privs150"] = make_valset(privs)
    w["commits150"] = [
        signed_commit(w["vset150"], w["privs150"], h,
                      block_id(b"commit150/%d" % h)) for h in (1, 2, 3)]
    for tag, n in (("10k", n_mid), ("100k", n_big)):
        vset, ordered = make_valset(seeded_privs(seed, tag, n), power=1)
        w["vset" + tag] = vset
        w["commit" + tag] = signed_commit(vset, ordered, 9,
                                          block_id(tag.encode()))
    # the mid-size set again in three key schemes, the k-th key of scheme
    # k mod 3: the benchmark's own generator (keys and signatures by the
    # plain signers beside its reference, in worker processes), one
    # commit, 1% of the set absent
    from perfbench.traffic import mixed_commit as gen
    schemes = ("ed25519", "secp256k1", "sr25519")
    w["mixed"] = gen.setup(
        {"name": "chip-smoke-mixed", "chain_id": CHAIN_ID,
         "validators": n_mid, "voting_power": 1, "absent_share": 0.01,
         "key_types": {s: len(range(j, n_mid, 3))
                       for j, s in enumerate(schemes)}},
        {"ring": 0, "expect_launch": []}, seed, 0.0)
    w["mixed"]["span"] = lambda name: contextlib.nullcontext()
    return w


def run_phases(seed: int, nshard: int, pallas: bool = True,
               n_mid: int = 10_000, n_big: int = 100_000):
    """Build the data, install the scheduler and the pipeline as
    node/node.py does, run every phase.  Returns ({phase: result}, the
    comb budget declines the phases account for).  The sizes are
    parameters only so the CPU tests can walk the same code small."""
    from tendermint_tpu.config.config import Config
    from tendermint_tpu.crypto import scheduler as vsched
    from tendermint_tpu.ops import ed25519 as edops
    from tendermint_tpu.state import pipeline as blockpipe

    t0 = time.perf_counter()
    world = build_world(seed, n_mid, n_big)
    print(f"# data built in {time.perf_counter() - t0:.1f} s", flush=True)
    cfg = Config()
    phases = {}

    def run(name, fn, *a):
        ph = Phase(name, nshard, pallas)
        out = None
        try:
            out = fn(ph, *a)
        except Exception:  # noqa: BLE001 - one phase's crash must not
            # hide the others' results; it is that phase's failure
            ph.failures.append("crashed:\n" + traceback.format_exc())
        phases[name] = ph.result()
        line = json.dumps({name: phases[name]})
        print(f"# {'ok' if phases[name]['ok'] else 'FAILED'} {line}",
              flush=True)
        # also on disk as it happens: a run the watchdog kills still
        # leaves every finished phase behind
        with open(os.path.join(OUT_DIR, "phases.jsonl"), "a") as f:
            f.write(line + "\n")
        return out

    sched = None
    declines = 0
    try:
        ladder_bits = run("commit_150", phase_commit_150, world, False)
        # before the scheduler is up: its 9,900 rows are more than a
        # scheduler window holds (max_batch 8,192) and take the direct
        # path beside a running scheduler too; so placed, the small walk
        # of the tests takes that path as well
        run("commit_10k_mixed", phase_commit_10k_mixed, world)
        vs = cfg.verify_scheduler
        sched = vsched.install(vsched.VerifyScheduler(
            window_s=vs.window_ms / 1000.0, max_batch=vs.max_batch,
            max_pending=vs.max_pending,
            tpu_threshold=cfg.batch_verifier.tpu_threshold))
        sched.start()
        bp = cfg.block_pipeline
        blockpipe.set_config(enable=True, depth=bp.depth,
                             group_commit_heights=bp.group_commit_heights)
        run("votes_150", phase_votes_150, world)
        run("commit_150_prewarmed", phase_prewarmed, world, ladder_bits)
        resident = len(edops._table_cache) > 0
        declines += run("light_10k", phase_light_10k, world) or 0
        run("catchup_150", phase_catchup_150, world, resident)
        declines += run("commit_100k", phase_commit_100k, world) or 0
    finally:
        blockpipe.set_config(enable=False)
        if sched is not None:
            sched.stop()
            vsched.uninstall(sched)
    return phases, declines


def emit(summary: dict) -> None:
    """The summary, then the verdict line the driver parses: the last line
    of stdout holds "ok" and "device" and no other key."""
    print(json.dumps(summary), flush=True)
    print(json.dumps({"ok": summary["ok"], "device": summary["device"]}),
          flush=True)


def cache_entries(path) -> int:
    try:
        return len(os.listdir(path))
    except OSError:
        return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    def refuse(*reasons) -> int:
        for why in reasons:
            print(f"chip_smoke: refusing to start: {why}", file=sys.stderr)
        return 2

    reasons = refusals(os.environ)
    if reasons:
        return refuse(*reasons)
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        return refuse(f"JAX found no backend: {e}")
    if devices[0].platform != "tpu":
        return refuse(f"JAX platform is {devices[0].platform!r}, not 'tpu' "
                      f"(no accelerator here)")
    try:
        import tendermint_tpu  # noqa: F401 - places the compile cache
    except ImportError as e:
        return refuse(f"the program is not here beside the script: {e}")
    from tendermint_tpu.crypto import degrade
    from tendermint_tpu.crypto import ed25519 as edkeys
    from tendermint_tpu.libs import native
    from tendermint_tpu.parallel import sharding
    if native.get_lib() is None:
        return refuse("the native staging library did not build "
                      "(tendermint_tpu/native/*.c)")
    if not edkeys._HAVE_OSSL:
        return refuse("the cryptography package (OpenSSL) is missing")

    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    cache_dir = jax.config.jax_compilation_cache_dir
    cache = {"dir": cache_dir, "entries_before": cache_entries(cache_dir)}
    print(f"# device {device}; compile cache {cache_dir} "
          f"({cache['entries_before']} entries)", flush=True)

    # the shards a launch of >= nshard x 256 rows must report: every
    # local chip once the mesh engages on TPU, one until then
    nshard = jax.local_device_count() if sharding.MESH_ON_TPU else 1
    rt = degrade.runtime()
    phases, declines = run_phases(args.seed, nshard)
    gate_failures = gate(rt, phases, declines)
    cache["entries_after"] = cache_entries(cache_dir)
    ok = not gate_failures and all(p["ok"] for p in phases.values())
    summary = {
        "ok": ok,
        "device": device,
        "shards": nshard,
        "phases": phases,
        "gate": gate_failures,
        "launch_timeout_s": rt.cfg.launch_timeout_s,
        "compile_timeout_s": degrade.COMPILE_TIMEOUT_S,
        "compile_cache": cache,
        "seed": args.seed,
        "total_s": round(time.perf_counter() - t_start, 1),
        "claim": None,
    }
    with open(os.path.join(OUT_DIR, "report.json"), "w") as f:
        json.dump(summary, f, indent=1)
    faulthandler.cancel_dump_traceback_later()
    for name, p in phases.items():
        for why in p["failures"]:
            print(f"chip_smoke: {name}: {why}", file=sys.stderr)
    for why in gate_failures:
        print(f"chip_smoke: gate: {why}", file=sys.stderr)
    emit(summary)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
