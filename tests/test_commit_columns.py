"""A commit's rows are read once into columns inside each entry call
(types/commit._columns) and the checks that were a Python step a row are
numpy over them.  Held here to what they replaced:

(a) `Commit.validate_basic` against the per-row loop it was, over a table
    of bad rows placed first, last and twice in commits of 1 to 10,000
    rows: the same exception, message and index;
(b) `verify_commit`, `verify_commit_light`, `verify_commit_light_trusting`
    and `check_commit_no_sigs` against plain serial loops in the manner of
    perfbench/reference/commit.py (one row at a time, one plain verifier
    call a signature: OpenSSL for ed25519, perfbench/reference/
    mixed_commit.py's for the other two): the same verdict, `tallied` /
    `needed` and bad index, on an all-ed25519 set (as rows of the cached
    pubkey matrix and as key objects) and on a mixed one (key objects);
(c) nothing outlives a call: no attribute appears on the Commit, its list
    or a CommitSig, and a second call reads the rows again.
"""
from __future__ import annotations

import functools
import hashlib
import pickle
import re
from fractions import Fraction

import numpy as np
import pytest

from perfbench import data
from perfbench.reference import mixed_commit as plain
from perfbench.traffic import commit_heights, mixed_commit
from tendermint_tpu.crypto import batch
from tendermint_tpu.libs import trace
from tendermint_tpu.types import validator_set as vsmod
from tendermint_tpu.types.basic import (BlockID, BlockIDFlag, SignedMsgType,
                                        Timestamp)
from tendermint_tpu.types.canonical import (canonical_vote_bytes,
                                            commit_sign_bytes_batch)
from tendermint_tpu.types.commit import Commit, CommitSig, _columns
from tendermint_tpu.types.validator import Validator
from tendermint_tpu.types.validator_set import (CommitVerifyError,
                                                NotEnoughVotingPowerError,
                                                ValidatorSet)

CHAIN, HEIGHT, T0 = "commit-columns-test", 9, 1_700_000_000
ADDR = bytes(range(20))
SIG = bytes(range(64))


# -- (a) Commit.validate_basic against the per-row loop ----------------------

def _voted(i: int) -> CommitSig:
    return CommitSig(BlockIDFlag.NIL if i % 7 == 3 else BlockIDFlag.COMMIT,
                     ADDR, Timestamp(T0, i), SIG)


BAD_ROWS = {
    "unknown flag": lambda: CommitSig(BlockIDFlag.UNKNOWN, ADDR,
                                      Timestamp(T0, 1), SIG),
    "a flag no uint8 holds": lambda: CommitSig(300, ADDR, Timestamp(T0, 1),
                                               SIG),
    "absent with an address": lambda: CommitSig(BlockIDFlag.ABSENT, ADDR),
    "absent with a timestamp": lambda: CommitSig(
        BlockIDFlag.ABSENT, timestamp=Timestamp(T0, 0)),
    "absent with a signature": lambda: CommitSig(BlockIDFlag.ABSENT,
                                                 signature=SIG),
    "wrong address size": lambda: CommitSig(BlockIDFlag.COMMIT, ADDR[:19],
                                            Timestamp(T0, 1), SIG),
    "missing signature": lambda: CommitSig(BlockIDFlag.NIL, ADDR,
                                           Timestamp(T0, 1), b""),
    "signature of 65 bytes": lambda: CommitSig(BlockIDFlag.COMMIT, ADDR,
                                               Timestamp(T0, 1), SIG + b"x"),
}


def _sized_commit(n: int, bad: dict) -> Commit:
    rows = [CommitSig.absent() if i % 10 == 4 else _voted(i)
            for i in range(n)]
    for i, row in bad.items():
        rows[i] = row
    return Commit(HEIGHT, 0, data.block_id(b"sized"), rows)


def _per_row_loop(commit: Commit):
    """Commit.validate_basic's loop as it was: the statement of the rule."""
    for i, sig in enumerate(commit.signatures):
        try:
            sig.validate_basic()
        except ValueError as e:
            return ValueError, f"wrong CommitSig #{i}: {e}"
    return None


def _outcome(fn):
    try:
        fn()
    except Exception as e:  # noqa: BLE001 - the type is what is compared
        return type(e), str(e)
    return None


@pytest.mark.parametrize("n", [1, 31, 150, 10_000])
@pytest.mark.parametrize("where", ["first", "last", "twice"])
@pytest.mark.parametrize("kind", list(BAD_ROWS))
def test_validate_basic_names_the_row_the_loop_named(kind, where, n):
    kinds = list(BAD_ROWS)
    other = BAD_ROWS[kinds[(kinds.index(kind) + 3) % len(kinds)]]
    bad = {"first": {0: BAD_ROWS[kind]()},
           "last": {n - 1: BAD_ROWS[kind]()},
           # another rule's bad row after it: the first is the one named
           "twice": {n // 3: BAD_ROWS[kind](), n - 1: other()}}[where]
    commit = _sized_commit(n, bad)
    want = _per_row_loop(commit)
    assert want is not None and f"#{min(bad)}:" in want[1]
    assert _outcome(commit.validate_basic) == want


@pytest.mark.parametrize("n", [1, 31, 150, 10_000])
def test_validate_basic_passes_what_the_loop_passed(n):
    commit = _sized_commit(n, {})
    assert _per_row_loop(commit) is None
    commit.validate_basic()
    # a signature of any length up to 64 is well formed here
    commit = _sized_commit(n, {n - 1: CommitSig(
        BlockIDFlag.COMMIT, ADDR, Timestamp(T0, 1), SIG[:63])})
    commit.validate_basic()


def test_columns_hold_what_the_rows_hold():
    rows = [_voted(0), CommitSig.absent(), _voted(3),
            CommitSig(BlockIDFlag.COMMIT, ADDR[:7], Timestamp(-5, 999_999_999),
                      SIG[:63]),
            CommitSig(300, b"", Timestamp.zero(), SIG * 5), _voted(5)]
    cols = _columns(rows, ("flag", "sig_len", "addr_len", "seconds",
                           "nanos", "sig"))
    assert cols["flag"].tolist() == [2, 1, 3, 2, 0, 2]
    assert cols["sig_len"].tolist() == [64, 0, 64, 63, 255, 64]
    assert cols["addr_len"].tolist() == [20, 0, 20, 7, 0, 20]
    assert cols["seconds"].tolist() == [
        T0, Timestamp.zero().seconds, T0, -5, Timestamp.zero().seconds, T0]
    assert cols["nanos"].tolist() == [0, 0, 3, 999_999_999, 0, 5]
    assert cols["sig"].shape == (3, 64) and cols["sig"].dtype == np.uint8
    assert [bytes(r) for r in cols["sig"]] == [SIG] * 3
    # what was asked for and nothing else (`sig` says which rows it holds)
    assert set(_columns(rows, ("flag",))) == {"flag"}
    assert set(_columns(rows, ("seconds", "sig"))) == {
        "seconds", "sig", "sig_len"}
    empty = _columns([], ("flag", "sig", "seconds"))
    assert empty["flag"].shape == (0,) and empty["sig"].shape == (0, 64)


# -- (b) the four entry points against plain serial loops --------------------

N, ABSENT, NIL = 90, (3, 17, 41), (5, 22)


def _sign(key, flag, bid, i):
    ts = Timestamp(T0 + HEIGHT, i)
    sb = canonical_vote_bytes(
        CHAIN, SignedMsgType.PRECOMMIT, HEIGHT, 0,
        bid if flag == BlockIDFlag.COMMIT else BlockID(), ts)
    return ts, key.sign(sb)


@functools.lru_cache(maxsize=None)
def world(kind: str) -> dict:
    schemes = ("ed25519",) if kind == "ed25519" else (
        "ed25519", "secp256k1", "sr25519")
    keys = [plain.Key(schemes[k % len(schemes)], hashlib.sha256(
        b"commit-columns/%s/%d" % (kind.encode(), k)).digest())
        for k in range(N)]
    pubs = [mixed_commit._pub_key(k.scheme, k.pub_bytes) for k in keys]
    vset = ValidatorSet([Validator.new(p, 1 + k % 2)
                         for k, p in enumerate(pubs)])
    key_of = {p.address(): k for p, k in zip(pubs, keys)}
    bid = data.block_id(b"columns")
    rows = []
    for i, val in enumerate(vset.validators):
        if i in ABSENT:
            rows.append(CommitSig.absent())
            continue
        flag = BlockIDFlag.NIL if i in NIL else BlockIDFlag.COMMIT
        ts, sig = _sign(key_of[val.address], flag, bid, i)
        rows.append(CommitSig(flag, val.address, ts, sig))
    # a set that overlaps the signers, in another order and with other
    # powers, and three strangers: what a trusting check may be handed
    other = ValidatorSet(
        [Validator.new(p, 2 + k % 3) for k, p in enumerate(pubs[8:80])]
        + [Validator.new(mixed_commit._pub_key(
            "ed25519", plain.Key("ed25519", bytes([200 + k] * 32)).pub_bytes),
            3) for k in range(3)])
    return {"kind": kind, "vset": vset, "other": other, "bid": bid,
            "honest": Commit(HEIGHT, 0, bid, rows), "memo": {}}


def _row_ok(w, pub_key, msg: bytes, sig: bytes) -> bool:
    triple = (pub_key.type_name, pub_key.bytes(), msg, sig)
    if triple not in w["memo"]:
        w["memo"][triple] = bool(plain.verify_rows([triple])[0])
    return w["memo"][triple]


def _plain_full(w, vset, commit):
    """Reference VerifyCommit :662-709: every non-absent row."""
    rows = commit.signatures
    for i, cs in enumerate(rows):
        if int(cs.block_id_flag) == 1:
            continue
        if not _row_ok(w, vset.validators[i].pub_key,
                       commit.vote_sign_bytes(CHAIN, i), cs.signature):
            return ("wrong_signature", i)
    return _plain_no_sigs(w, vset, commit)


def _plain_no_sigs(w, vset, commit):
    tallied = sum(vset.validators[i].voting_power
                  for i, cs in enumerate(commit.signatures)
                  if int(cs.block_id_flag) == 2)
    needed = sum(v.voting_power for v in vset.validators) * 2 // 3
    if tallied <= needed:
        return ("not_enough_power", tallied, needed)
    return ("accepted",)


def _plain_light(w, vset, commit):
    """Reference VerifyCommitLight :717-760: for-block rows, front to
    back, until their power crosses 2/3."""
    needed = sum(v.voting_power for v in vset.validators) * 2 // 3
    tallied = 0
    for i, cs in enumerate(commit.signatures):
        if int(cs.block_id_flag) != 2:
            continue
        if not _row_ok(w, vset.validators[i].pub_key,
                       commit.vote_sign_bytes(CHAIN, i), cs.signature):
            return ("wrong_signature", i)
        tallied += vset.validators[i].voting_power
        if tallied > needed:
            return ("accepted",)
    return ("not_enough_power", tallied, needed)


def _plain_trusting(w, vset, commit, level: Fraction):
    """Reference VerifyCommitLightTrusting :770-821: for-block rows matched
    by address, every lookup a scan, until trust level is crossed."""
    needed = (sum(v.voting_power for v in vset.validators)
              * level.numerator // level.denominator)
    tallied, seen = 0, set()
    for i, cs in enumerate(commit.signatures):
        if int(cs.block_id_flag) != 2:
            continue
        at = [k for k, v in enumerate(vset.validators)
              if v.address == cs.validator_address]
        if not at:
            continue
        assert at[0] not in seen
        seen.add(at[0])
        if not _row_ok(w, vset.validators[at[0]].pub_key,
                       commit.vote_sign_bytes(CHAIN, i), cs.signature):
            return ("wrong_signature", i)
        tallied += vset.validators[at[0]].voting_power
        if tallied > needed:
            return ("accepted",)
    return ("not_enough_power", tallied, needed)


def _verdict(fn):
    try:
        fn()
    except NotEnoughVotingPowerError as e:
        return ("not_enough_power", e.got, e.needed)
    except CommitVerifyError as e:
        row = re.fullmatch(r"wrong signature \(#(\d+)\): [0-9a-f]*", str(e))
        assert row, str(e)
        return ("wrong_signature", int(row.group(1)))
    return ("accepted",)


ENTRIES = {
    "verify_commit": (
        lambda w, c: w["vset"].verify_commit(CHAIN, w["bid"], HEIGHT, c),
        lambda w, c: _plain_full(w, w["vset"], c)),
    "verify_commit_light": (
        lambda w, c: w["vset"].verify_commit_light(CHAIN, w["bid"], HEIGHT,
                                                   c),
        lambda w, c: _plain_light(w, w["vset"], c)),
    "trusting, its own set at 2/3": (
        lambda w, c: w["vset"].verify_commit_light_trusting(
            CHAIN, c, Fraction(2, 3)),
        lambda w, c: _plain_trusting(w, w["vset"], c, Fraction(2, 3))),
    "trusting, another set at 1/3": (
        lambda w, c: w["other"].verify_commit_light_trusting(
            CHAIN, c, Fraction(1, 3)),
        lambda w, c: _plain_trusting(w, w["other"], c, Fraction(1, 3))),
    "check_commit_no_sigs": (
        lambda w, c: w["vset"].check_commit_no_sigs(CHAIN, w["bid"], HEIGHT,
                                                    c),
        lambda w, c: _plain_no_sigs(w, w["vset"], c)),
}


def _short(commit, at: int) -> Commit:
    cs = commit.signatures[at]
    return commit_heights.with_rows(commit, {at: CommitSig(
        cs.block_id_flag, cs.validator_address, cs.timestamp,
        cs.signature[:63])})


def _drop_for_block_until(w, commit, accepted: bool) -> Commit:
    """The honest commit with for-block rows made absent from the back
    until the tally is one row short of > 2/3 (or one row over it)."""
    vset = w["vset"]
    needed = sum(v.voting_power for v in vset.validators) * 2 // 3
    for_block = [i for i, cs in enumerate(commit.signatures)
                 if cs.for_block()]
    tallied = sum(vset.validators[i].voting_power for i in for_block)
    gone = {}
    while tallied > needed:
        i = for_block.pop()
        tallied -= vset.validators[i].voting_power
        gone[i] = CommitSig.absent()
    if accepted:
        gone.popitem()
    return commit_heights.with_rows(commit, gone)


def _cases(w) -> dict:
    honest = w["honest"]
    signed = [i for i, cs in enumerate(honest.signatures)
              if not cs.is_absent()]
    for_block = [i for i in signed if honest.signatures[i].for_block()]
    return {
        "honest, with nil votes and absent rows": honest,
        "the first row tampered": data.tampered_commit(honest, [signed[0]]),
        "the last row tampered": data.tampered_commit(honest, [signed[-1]]),
        "both ends and a middle row tampered": data.tampered_commit(
            honest, [signed[-1], for_block[20], signed[0]]),
        "a nil vote tampered": data.tampered_commit(honest, [NIL[0]]),
        "a signature of 63 bytes": _short(honest, for_block[7]),
        "63 bytes, then a tampered row": data.tampered_commit(
            _short(honest, for_block[2]), [for_block[9]]),
        "a tampered row, then 63 bytes": data.tampered_commit(
            _short(honest, for_block[9]), [for_block[2]]),
        "63 bytes in the last row": _short(honest, signed[-1]),
        "one vote short of 2/3": _drop_for_block_until(w, honest, False),
        "that vote back": _drop_for_block_until(w, honest, True),
        "every row absent": commit_heights.with_rows(
            honest, {i: CommitSig.absent() for i in signed}),
    }


# _cases' names, known without building a world at collection time
CASE_NAMES = ("honest, with nil votes and absent rows",
              "the first row tampered", "the last row tampered",
              "both ends and a middle row tampered", "a nil vote tampered",
              "a signature of 63 bytes", "63 bytes, then a tampered row",
              "a tampered row, then 63 bytes", "63 bytes in the last row",
              "one vote short of 2/3", "that vote back", "every row absent")


@pytest.fixture(params=["ed25519 as matrix rows", "ed25519 as key objects",
                        "mixed"])
def served(request, monkeypatch):
    """The world and how its batches reach verify_sigs_bulk.  On a chip an
    all-ed25519 batch of 32 rows or more goes down as rows of the set's
    cached pubkey matrix beside an (n, 64) signature matrix: taken here
    as on a chip, every signature then verified by OpenSSL.  The other
    two take the program's own host path as it is."""
    calls = []
    if request.param == "ed25519 as matrix rows":
        def bulk(pubs, msgs, sigs):
            rows = isinstance(pubs, np.ndarray)
            assert isinstance(sigs, np.ndarray) is rows
            if rows:
                assert pubs.shape == (len(msgs), 32) \
                    and sigs.shape == (len(msgs), 64)
            calls.append((rows, len(pubs)))
            return data.oracle(
                [bytes(p) if rows else p.bytes() for p in pubs],
                [bytes(msgs[j]) for j in range(len(msgs))],
                [bytes(s) for s in sigs])

        monkeypatch.setattr(batch, "_use_device", lambda: True)
        monkeypatch.setattr(vsmod, "verify_sigs_bulk", bulk)
    w = world("mixed" if request.param == "mixed" else "ed25519")
    return w, calls, request.param


@pytest.mark.parametrize("case", CASE_NAMES)
@pytest.mark.parametrize("entry", list(ENTRIES))
def test_entry_points_give_the_plain_loops_verdict(served, entry, case):
    w, calls, how = served
    assert tuple(_cases(w)) == CASE_NAMES
    commit = _cases(w)[case]
    program, reference = ENTRIES[entry]
    want = reference(w, commit)
    assert _verdict(lambda: program(w, commit)) == want
    # 32 rows or more of the set's own go down as rows; the other set's,
    # matched out of its order, as key objects
    for rows, n in calls:
        assert not rows if "another set" in entry else rows or n < 32
    if how == "ed25519 as matrix rows" and case.startswith("honest") \
            and entry != "check_commit_no_sigs":
        ((rows, n),) = calls
        assert rows is ("another set" not in entry)


def test_the_cases_reach_every_verdict():
    w = world("ed25519")
    seen = {(entry, ENTRIES[entry][1](w, commit)[0])
            for entry in ENTRIES for commit in _cases(w).values()}
    for entry in ENTRIES:
        assert (entry, "accepted") in seen
        assert (entry, "not_enough_power") in seen
        if entry != "check_commit_no_sigs":
            assert (entry, "wrong_signature") in seen
    # a bad row past the light prefix's end is the full check's alone
    late = _cases(w)["the last row tampered"]
    assert _plain_light(w, w["vset"], late) == ("accepted",)
    assert _plain_full(w, w["vset"], late)[0] == "wrong_signature"


def test_collect_commit_light_returns_the_serial_prefix():
    w = world("ed25519")
    vset, commit = w["vset"], w["honest"]
    needed = vset.total_voting_power() * 2 // 3
    want, tallied = [], 0
    for i, cs in enumerate(commit.signatures):
        if not cs.for_block():
            continue
        want.append(i)
        tallied += vset.validators[i].voting_power
        if tallied > needed:
            break
    got = vset.collect_commit_light(CHAIN, w["bid"], HEIGHT, commit)
    assert got == want and all(type(i) is int for i in got)


# -- the set's voting-power column -------------------------------------------

def test_the_power_column_follows_the_validators_list():
    w = world("ed25519")
    vset = w["vset"].copy()
    column = vset._power_column()
    assert column.dtype == np.int64
    assert column.tolist() == [v.voting_power for v in vset.validators]
    assert vset._power_column() is column           # read once per list
    held = vset.validators
    change = vset.validators[0].copy()
    change.voting_power += 1_000
    vset.update_with_change_set([change])
    # every set change assigns a fresh list: the memo falls with the old
    assert vset.validators is not held
    assert vset._power_column() is not column
    assert vset._power_column().tolist() == [
        v.voting_power for v in vset.validators]
    assert max(vset._power_column()) == change.voting_power
    assert vset.copy()._power_column().tolist() == \
        vset._power_column().tolist()
    assert "_power_memo" not in pickle.loads(pickle.dumps(vset)).__dict__


# -- crypto/batch: an (n, 64) signature matrix needs no length screen --------

def test_verify_ed25519_batch_screens_lists_not_matrices(monkeypatch):
    seen = []

    def device(pubkeys, msgs, sigs, cache_pubs=False):
        seen.append((type(pubkeys), type(sigs), len(pubkeys)))
        return np.ones(len(pubkeys), dtype=bool)

    monkeypatch.setattr(batch, "ed_ops_verify", device)
    pubs = np.zeros((5, 32), dtype=np.uint8)
    sigs = np.zeros((5, 64), dtype=np.uint8)
    msgs = [b"m"] * 5
    assert batch.verify_ed25519_batch(pubs, msgs, sigs).all()
    assert seen == [(np.ndarray, np.ndarray, 5)]
    # a list is screened: the malformed row is invalid, the rest launch
    listed = [bytes(64), bytes(63), bytes(64), b"", bytes(64)]
    assert batch.verify_ed25519_batch(pubs, msgs, listed).tolist() == [
        True, False, True, False, True]
    assert seen[-1] == (list, list, 3)
    keys = [bytes(32), bytes(31), bytes(32), bytes(32), bytes(32)]
    assert batch.verify_ed25519_batch(keys, msgs, listed).tolist() == [
        True, False, True, False, True]


def test_verify_sigs_bulk_takes_a_signature_matrix_to_the_host_too():
    """Matrix rows under the device's floor (or with no device) fall to the
    BatchVerifier, which is handed bytes."""
    keys = data.seeded_keys(5, "bulk-matrix", 4)
    msgs = [b"message %d" % i for i in range(4)]
    sigs = [k.sign(m) for k, m in zip(keys, msgs)]
    sigs[2] = data.flip(sigs[2])
    pubs = np.frombuffer(b"".join(k.pub.bytes() for k in keys),
                         dtype=np.uint8).reshape(-1, 32)
    mat = np.frombuffer(b"".join(sigs), dtype=np.uint8).reshape(-1, 64)
    assert batch.verify_sigs_bulk(pubs, msgs, mat).tolist() == [
        True, True, False, True]


# -- (c) nothing outlives a call ---------------------------------------------

def _columns_spans():
    return [r["attrs"] for r in trace.snapshot()
            if r["name"] == "commit.columns"]


CALLS = dict(ENTRIES, **{
    "validate_basic": (lambda w, c: c.validate_basic(), None),
    "commit_sign_bytes_batch": (
        lambda w, c: commit_sign_bytes_batch(CHAIN, c, [0, 7, N - 1]), None),
})


@pytest.mark.parametrize("call", list(CALLS))
def test_a_call_leaves_nothing_on_the_commit_and_reads_it_again(served,
                                                                call):
    w, _, _ = served
    commit = w["honest"]
    rows = commit.signatures
    sample = [rows[0], rows[ABSENT[0]], rows[NIL[0]], rows[-1]]
    before = (dict(vars(commit)), list(rows),
              [dict(vars(cs)) for cs in sample])
    trace.enable()
    trace.reset()
    try:
        CALLS[call][0](w, commit)
        first = _columns_spans()
        trace.reset()
        CALLS[call][0](w, commit)
        second = _columns_spans()
    finally:
        trace.disable()
        trace.reset()
    assert vars(commit) == before[0] and commit.signatures is rows
    assert all(a is b for a, b in zip(rows, before[1])) \
        and len(rows) == len(before[1])
    assert [dict(vars(cs)) for cs in sample] == before[2]
    assert not hasattr(rows, "__dict__")
    # every call reads the rows: the same spans, over the same rows
    assert first and first == second
    assert all(set(a) >= {"rows", "fields"} and 0 < a["rows"] <= N
               for a in first)
