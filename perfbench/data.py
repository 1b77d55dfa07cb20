"""Data from a seed, the per-signature OpenSSL oracle, the start-up refusals
and the end-of-run gate: the yardstick's copies of what chip_smoke.py proved
on the chip (PR 21), kept here so that no later PR to the program can move
them.  Nothing in this file imports chip_smoke.py or tests/.

Keys, signatures and blocks are built through the repo's own types (the
system under test is handed real objects), but every signature is made by
the `cryptography` package directly and every verdict the benchmark trusts
comes from `oracle`, which calls that package and nothing of the program.
"""
from __future__ import annotations

import hashlib
import os

import numpy as np


# ---------------------------------------------------------------------------
# start-up refusals and the gate: pure functions of what they are handed
# ---------------------------------------------------------------------------

def refusals(environ) -> list:
    """Reasons the environment alone gives not to start: the benchmark
    measures the default path, not a steered one."""
    out = []
    steered = sorted(k for k in environ if k.startswith("TM_TPU_"))
    if steered:
        out.append("steering variables are set: " + ", ".join(steered))
    if "xla_force_host_platform_device_count" in environ.get("XLA_FLAGS", ""):
        out.append("XLA_FLAGS forces host platform devices")
    return out


def gate(rt, window_records, new_buckets=()) -> list:
    """The whole-process gate, read after the window: `rt` is the degrade
    runtime every dispatch went through, `window_records` the devobs
    launch records of the timed window (all of them in a traced run, the
    ring's tail otherwise), `new_buckets` the path/bucket pairs first
    launched inside it.  Returns the failures (empty = pass).  A comb
    `declined` route is not a failure: the budget declines the 10,000-key
    tables by design (PERF.md, Chip bring-up)."""
    from tendermint_tpu.crypto import degrade

    m = rt.metrics
    bad = []
    for name, counter in (("host_fallbacks", m.host_fallbacks),
                          ("device_failures", m.device_failures)):
        hits = {k: v for k, v in counter.items().items() if v}
        if hits:
            bad.append(f"{name}: " + ", ".join(
                f"{'/'.join(k)} x{v:g}" for k, v in sorted(hits.items())))
    if rt.breaker.state != degrade.CLOSED or rt.breaker.opened_total:
        bad.append(f"breaker {rt.breaker.state}, opened "
                   f"{rt.breaker.opened_total}x")
    for (path, outcome), v in m.msm_route.items().items():
        if v and (outcome == "error"
                  or (outcome == "declined" and path != "comb")):
            bad.append(f"route {path} outcome={outcome} x{v:g}")
    if not window_records:
        bad.append("no device launch inside the window")
    compiled = sorted(set(new_buckets) | {
        f"{r['path']}/nb={r['nb']}" for r in window_records
        if r.get("first_launch") or r.get("compile_s")})
    if compiled:
        bad.append("compiled inside the window: " + ", ".join(compiled))
    return bad


# ---------------------------------------------------------------------------
# set-up in several processes
# ---------------------------------------------------------------------------

def _worker_init():
    # a worker signs and builds blocks; it never touches the chip, and
    # must not be able to, whatever it ends up importing
    os.environ["JAX_PLATFORMS"] = "cpu"


def fan_out(fn, jobs: list) -> list:
    """[fn(job) for job in jobs], in worker processes: set-up only (signing
    is ~50 us a signature on one core and does not release the GIL, and a
    cell can need 300,000).  `fn` is a module-level function of a picklable
    job and returns picklable data that depends on the job alone, so the
    number of workers changes nothing but the time.  Every worker has
    ended when this returns."""
    import multiprocessing as mp

    n = min(len(jobs), 8, max(1, (os.cpu_count() or 2) - 2))
    if n <= 1:
        return [fn(job) for job in jobs]
    pool = mp.get_context("spawn").Pool(n, initializer=_worker_init)
    try:
        out = pool.map(fn, jobs, chunksize=1)
        pool.close()
    finally:
        pool.terminate()
        pool.join()
    return out


# ---------------------------------------------------------------------------
# keys, validator sets, commits
# ---------------------------------------------------------------------------

class Key:
    """One validator key: the repo's PubKey for the system under test, and
    OpenSSL's signer held open (the repo's PrivKey.sign rebuilds it on
    every call, which doubles the set-up of a 300,000-signature cell)."""
    __slots__ = ("pub", "sign")

    def __init__(self, seed32: bytes):
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PrivateKey)
        from tendermint_tpu.crypto import ed25519 as edkeys

        k = Ed25519PrivateKey.from_private_bytes(seed32)
        self.sign = k.sign
        self.pub = edkeys.PubKey(k.public_key().public_bytes_raw())


def seeded_keys(seed: int, tag: str, n: int) -> list:
    return [Key(hashlib.sha256(
        b"perfbench/%d/%s/%d" % (seed, tag.encode(), i)).digest())
        for i in range(n)]


def make_valset(keys, power: int):
    """(ValidatorSet, keys reordered to the set's own validator order)."""
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import ValidatorSet

    vset = ValidatorSet([Validator.new(k.pub, power) for k in keys])
    by_addr = {k.pub.address(): k for k in keys}
    return vset, [by_addr[v.address] for v in vset.validators]


def seeded_genesis(seed: int, config: dict):
    """(the configuration's genesis doc, {validator address: its key}):
    the same in the runner and in every worker of one seed."""
    from tendermint_tpu.types.basic import Timestamp
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator

    keys = seeded_keys(seed, config["name"], config["validators"])
    gdoc = GenesisDoc(
        chain_id=config["chain_id"],
        genesis_time=Timestamp(1_700_000_000, 0),
        validators=[GenesisValidator(
            address=k.pub.address(), pub_key_type="ed25519",
            pub_key_bytes=k.pub.bytes(), power=config["voting_power"])
            for k in keys])
    return gdoc, {k.pub.address(): k for k in keys}


def block_id(tag: bytes):
    from tendermint_tpu.types.basic import BlockID, PartSetHeader
    return BlockID(hashlib.sha256(b"block/" + tag).digest(),
                   PartSetHeader(1, hashlib.sha256(b"parts/" + tag).digest()))


def signed_commit(chain_id: str, vset, keys, height: int, bid):
    """A full commit for `bid`: one precommit per validator, each with its
    own timestamp (so no two sign-bytes are equal), signed by its key."""
    from tendermint_tpu.types.basic import (BlockIDFlag, SignedMsgType,
                                            Timestamp)
    from tendermint_tpu.types.canonical import canonical_vote_bytes
    from tendermint_tpu.types.commit import Commit, CommitSig

    sigs = []
    for i, (val, key) in enumerate(zip(vset.validators, keys)):
        ts = Timestamp(1_700_000_000 + height, i)
        sb = canonical_vote_bytes(chain_id, SignedMsgType.PRECOMMIT, height,
                                  0, bid, ts)
        sigs.append(CommitSig(BlockIDFlag.COMMIT, val.address, ts,
                              key.sign(sb)))
    return Commit(height, 0, bid, sigs)


def signed_votes(chain_id: str, vset, keys, vtype, height: int, round_: int,
                 bid, sigs=None) -> list:
    """One vote of `vtype` per validator at (height, round), signed by its
    key; or, where a worker has signed already, carrying its `sigs`."""
    from tendermint_tpu.types.basic import Timestamp
    from tendermint_tpu.types.canonical import canonical_vote_bytes
    from tendermint_tpu.types.vote import Vote

    out = []
    for i, (val, key) in enumerate(zip(vset.validators, keys)):
        ts = Timestamp(1_700_000_100 + round_, i)
        sig = sigs[i] if sigs is not None else key.sign(canonical_vote_bytes(
            chain_id, vtype, height, round_, bid, ts))
        out.append(Vote(type=vtype, height=height, round=round_,
                        block_id=bid, timestamp=ts,
                        validator_address=val.address, validator_index=i,
                        signature=sig))
    return out


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


def commit_triples(chain_id: str, vset, commit, idxs=None):
    idxs = range(len(commit.signatures)) if idxs is None else idxs
    return ([vset.validators[i].pub_key.bytes() for i in idxs],
            [commit.vote_sign_bytes(chain_id, i) for i in idxs],
            [commit.signatures[i].signature for i in idxs])


def bulk_bitmap(chain_id: str, vset, commit, idxs=None) -> np.ndarray:
    """The bitmap behind verify_commit*: the same verify_sigs_bulk call
    ValidatorSet._verify_sigs_batch makes (raw pubkey matrix rows, batched
    sign bytes), returned instead of collapsed into raise / no raise."""
    from tendermint_tpu.crypto.batch import verify_sigs_bulk
    from tendermint_tpu.types.canonical import commit_sign_bytes_batch

    idxs = list(range(len(commit.signatures))) if idxs is None else idxs
    mat, _ = vset._pub_matrix()
    pubs = mat if len(idxs) == mat.shape[0] else mat[np.asarray(idxs)]
    return verify_sigs_bulk(
        pubs, commit_sign_bytes_batch(chain_id, commit, idxs),
        [commit.signatures[i].signature for i in idxs])


def raises(fn, exc_type):
    try:
        fn()
    except exc_type as e:
        return e
    return None
