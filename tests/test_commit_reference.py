"""`Commit.validate_basic` + `ValidatorSet.verify_commit`, which is what a
node does to a block's LastCommit, against the plain reference
perfbench/reference/commit.py (the same checks in plain Python, one
OpenSSL call a signature) on seeded commits of 40 validators with absent
and nil rows: equal verdicts, and the reference's bitmap equal to the
program's.  `val100k-commit`'s `correct` rests on the same comparison at
100,000 (perfbench/traffic/commit_heights.py)."""
from __future__ import annotations

import contextlib
import functools

import numpy as np
import pytest

from perfbench import data
from perfbench.reference import commit as reference
from perfbench.traffic import commit_heights as traffic
from tendermint_tpu.libs import trace
from tendermint_tpu.types.basic import (BlockID, BlockIDFlag, SignedMsgType,
                                        Timestamp)
from tendermint_tpu.types.canonical import canonical_vote_bytes
from tendermint_tpu.types.commit import Commit, CommitSig
from tendermint_tpu.types.validator_set import CommitVerifyError

CHAIN, N, HEIGHT = "commit-reference-test", 40, 7
ABSENT, NIL = (3, 17, 29), (5, 22)


@functools.lru_cache(maxsize=None)
def world(seed: int = 31):
    keys = data.seeded_keys(seed, "commit-reference", N)
    vset, ordered = data.make_valset(keys, 1)
    full = data.signed_commit(CHAIN, vset, ordered, HEIGHT,
                              data.block_id(b"seven"))
    rows = list(full.signatures)
    for i in ABSENT:
        rows[i] = CommitSig.absent()
    for i in NIL:
        ts = Timestamp(1_700_000_000 + HEIGHT, i)
        sb = canonical_vote_bytes(CHAIN, SignedMsgType.PRECOMMIT, HEIGHT, 0,
                                  BlockID(), ts)
        rows[i] = CommitSig(BlockIDFlag.NIL, vset.validators[i].address, ts,
                            ordered[i].sign(sb))
    commit = Commit(HEIGHT, 0, full.block_id, rows)
    return {"chain": CHAIN, "vset": vset, "honest": commit,
            "span": lambda name: contextlib.nullcontext()}


def absent(commit, rows):
    return traffic.with_rows(commit, {i: CommitSig.absent() for i in rows})


def cases():
    w = world()
    honest = w["honest"]
    signed = [i for i, cs in enumerate(honest.signatures)
              if not cs.is_absent()]
    for_block = [i for i in signed if honest.signatures[i].for_block()]
    other = Commit(HEIGHT, 0, data.block_id(b"another"), honest.signatures)
    return {
        "honest with absent and nil rows": (
            honest, reference.ACCEPTED),
        "first, a nil and the last row tampered": (
            data.tampered_commit(honest, [NIL[0], signed[-1], signed[0]]),
            ("wrong_signature", signed[0])),
        "a nil row's signature is checked": (
            data.tampered_commit(honest, [NIL[1]]),
            ("wrong_signature", NIL[1])),
        "an absent row carries a signature": (
            traffic.with_rows(honest, {ABSENT[1]: CommitSig(
                BlockIDFlag.ABSENT,
                signature=honest.signatures[0].signature)}),
            ("invalid", ABSENT[1])),
        "an absent row carries an address": (
            traffic.with_rows(honest, {ABSENT[2]: CommitSig(
                BlockIDFlag.ABSENT,
                validator_address=honest.signatures[0].validator_address)}),
            ("invalid", ABSENT[2])),
        "a signed row has lost its signature": (
            traffic.with_rows(honest, {8: CommitSig(
                BlockIDFlag.COMMIT, honest.signatures[8].validator_address,
                honest.signatures[8].timestamp, b"")}),
            ("invalid", 8)),
        "two thirds for the block and no more": (
            absent(honest, for_block[N * 2 // 3:]),
            ("not_enough_power", N * 2 // 3, N * 2 // 3)),
        "one more than two thirds for the block": (
            absent(honest, for_block[N * 2 // 3 + 1:]),
            reference.ACCEPTED),
        "a row short of the set's size": (
            Commit(HEIGHT, 0, honest.block_id, honest.signatures[:-1]),
            ("invalid", None)),
        "signed for another block": (other, ("wrong_signature", signed[0])),
    }


@pytest.mark.parametrize("name", list(cases()))
def test_the_program_and_the_plain_reference_give_one_verdict(name):
    w = world()
    commit, want = cases()[name]
    got_ref, bits = reference.check(CHAIN, w["vset"], commit.block_id,
                                    commit.height, commit)
    assert got_ref == want
    assert traffic.verdict(w, commit) == want
    if bits is not None:
        signed = [i for i, cs in enumerate(commit.signatures)
                  if not cs.is_absent()]
        assert np.array_equal(
            bits, data.bulk_bitmap(CHAIN, w["vset"], commit, signed))


@pytest.mark.parametrize("what,height,tag", [
    ("height", HEIGHT + 1, b"seven"), ("block ID", HEIGHT, b"eight")])
def test_a_commit_for_another_height_or_block_is_refused_by_both(
        what, height, tag):
    w = world()
    commit, bid = w["honest"], data.block_id(tag)
    got, bits = reference.verify_commit(CHAIN, w["vset"], bid, height,
                                        commit)
    assert got == ("invalid", None) and bits is None
    with pytest.raises(CommitVerifyError, match="wrong " + what):
        w["vset"].verify_commit(CHAIN, bid, height, commit)


def test_validate_basic_is_one_span_a_call_on_the_nodes_path_too():
    """`commit.validate_basic` is Commit.validate_basic's own span: one a
    call whoever calls (the node before verify_commit, the light verifier
    through SignedHeader.validate_basic: tests/test_trace_boundaries.py),
    so a node's request carries it and no request carries it twice."""
    w = world()
    trace.enable()
    trace.reset()
    try:
        assert traffic.verdict(w, w["honest"]) == reference.ACCEPTED
        names = [r["name"] for r in trace.snapshot()]
        (basic,) = [r for r in trace.snapshot()
                    if r["name"] == "commit.validate_basic"]
    finally:
        trace.disable()
        trace.reset()
    assert basic["attrs"]["sigs"] == N
    assert names.count("commit.collect") == 1
