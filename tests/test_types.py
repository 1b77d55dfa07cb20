"""Canonical encodings and validator-set semantics.

Sign-bytes golden vectors are copied from the reference's own test suite
(reference types/vote_test.go:60-131 TestVoteSignBytesTestVectors) — the
encodings must match the Go implementation byte-for-byte.
"""
import hashlib
import random
from fractions import Fraction

import pytest

from tendermint_tpu.crypto import ed25519 as edkeys
from tendermint_tpu.crypto import merkle
from tendermint_tpu.types.basic import (
    BlockID, BlockIDFlag, PartSetHeader, SignedMsgType, Timestamp)
from tendermint_tpu.types.canonical import (
    canonical_proposal_bytes, canonical_vote_bytes)
from tendermint_tpu.types.commit import Commit, CommitSig
from tendermint_tpu.types.validator import Validator
from tendermint_tpu.types.validator_set import (
    CommitVerifyError, NotEnoughVotingPowerError, ValidatorSet)

rng = random.Random(99)


# --- sign bytes golden vectors (reference types/vote_test.go:60-131) -------

ZERO_TS_BYTES = bytes([0xb, 0x8, 0x80, 0x92, 0xb8, 0xc3, 0x98, 0xfe,
                       0xff, 0xff, 0xff, 0x1])


def test_vote_sign_bytes_vector_0():
    # ("", &Vote{}) — zero vote
    got = canonical_vote_bytes("", SignedMsgType.UNKNOWN, 0, 0, BlockID(),
                               Timestamp.zero())
    want = bytes([0xd, 0x2a]) + ZERO_TS_BYTES
    assert got == want


def test_vote_sign_bytes_vector_precommit():
    got = canonical_vote_bytes("", SignedMsgType.PRECOMMIT, 1, 1, BlockID(),
                               Timestamp.zero())
    want = (bytes([0x21, 0x8, 0x2,
                   0x11, 0x1, 0, 0, 0, 0, 0, 0, 0,
                   0x19, 0x1, 0, 0, 0, 0, 0, 0, 0,
                   0x2a]) + ZERO_TS_BYTES)
    assert got == want


def test_vote_sign_bytes_vector_prevote():
    got = canonical_vote_bytes("", SignedMsgType.PREVOTE, 1, 1, BlockID(),
                               Timestamp.zero())
    want = (bytes([0x21, 0x8, 0x1,
                   0x11, 0x1, 0, 0, 0, 0, 0, 0, 0,
                   0x19, 0x1, 0, 0, 0, 0, 0, 0, 0,
                   0x2a]) + ZERO_TS_BYTES)
    assert got == want


def test_vote_sign_bytes_vector_no_type():
    got = canonical_vote_bytes("", SignedMsgType.UNKNOWN, 1, 1, BlockID(),
                               Timestamp.zero())
    want = (bytes([0x1f,
                   0x11, 0x1, 0, 0, 0, 0, 0, 0, 0,
                   0x19, 0x1, 0, 0, 0, 0, 0, 0, 0,
                   0x2a]) + ZERO_TS_BYTES)
    assert got == want


def test_vote_sign_bytes_vector_chain_id():
    got = canonical_vote_bytes("test_chain_id", SignedMsgType.UNKNOWN, 1, 1,
                               BlockID(), Timestamp.zero())
    want = (bytes([0x2e,
                   0x11, 0x1, 0, 0, 0, 0, 0, 0, 0,
                   0x19, 0x1, 0, 0, 0, 0, 0, 0, 0,
                   0x2a]) + ZERO_TS_BYTES
            + bytes([0x32, 0xd]) + b"test_chain_id")
    assert got == want


def test_proposal_vs_vote_sign_bytes_differ():
    v = canonical_vote_bytes("", SignedMsgType.UNKNOWN, 1, 1, BlockID(),
                             Timestamp.zero())
    p = canonical_proposal_bytes("", 1, 1, 0, BlockID(), Timestamp.zero())
    assert v != p  # reference TestVoteProposalNotEq


def test_sign_bytes_with_block_id_roundtrip_sig():
    """A signature over our sign bytes must verify through the key API."""
    priv = edkeys.PrivKey(bytes(range(32)))
    bid = BlockID(hash=bytes(32), part_set_header=PartSetHeader(1, bytes(32)))
    sb = canonical_vote_bytes("chain", SignedMsgType.PRECOMMIT, 5, 2, bid,
                              Timestamp(1700000000, 123456789))
    sig = priv.sign(sb)
    assert priv.pub_key().verify_signature(sb, sig)


# --- merkle ---------------------------------------------------------------

def test_merkle_empty_and_single():
    assert merkle.hash_from_byte_slices([]) == hashlib.sha256(b"").digest()
    leaf = b"hello"
    assert (merkle.hash_from_byte_slices([leaf])
            == hashlib.sha256(b"\x00" + leaf).digest())


def test_merkle_inner_structure():
    items = [b"a", b"b", b"c"]
    l0 = hashlib.sha256(b"\x00a").digest()
    l1 = hashlib.sha256(b"\x00b").digest()
    l2 = hashlib.sha256(b"\x00c").digest()
    left = hashlib.sha256(b"\x01" + l0 + l1).digest()
    want = hashlib.sha256(b"\x01" + left + l2).digest()
    assert merkle.hash_from_byte_slices(items) == want


def test_merkle_proofs():
    items = [f"item{i}".encode() for i in range(11)]
    root, proofs = merkle.proofs_from_byte_slices(items)
    assert root == merkle.hash_from_byte_slices(items)
    for i, p in enumerate(proofs):
        assert p.verify(root, items[i]), i
        assert not p.verify(root, items[(i + 1) % len(items)])


# --- validator set --------------------------------------------------------

def _mkvals(n, power=lambda i: 10):
    out = []
    for i in range(n):
        priv = edkeys.PrivKey(i.to_bytes(32, "big"))
        out.append((priv, Validator.new(priv.pub_key(), power(i))))
    return out


def test_valset_sorted_and_total_power():
    pairs = _mkvals(7, power=lambda i: (i + 1) * 5)
    vs = ValidatorSet([v for _, v in pairs])
    assert vs.total_voting_power() == sum((i + 1) * 5 for i in range(7))
    powers = [v.voting_power for v in vs.validators]
    assert powers == sorted(powers, reverse=True)


def test_proposer_rotation_weighted():
    """Over one full cycle, each validator proposes proportionally to its
    power (the proposer-selection contract, reference
    spec/consensus/proposer-selection.md)."""
    pairs = _mkvals(3, power=lambda i: [1, 2, 3][i])
    vs = ValidatorSet([v for _, v in pairs])
    counts = {}
    for _ in range(60):
        p = vs.get_proposer()
        counts[p.address] = counts.get(p.address, 0) + 1
        vs.increment_proposer_priority(1)
    by_power = {v.address: v.voting_power for _, v in pairs}
    got = sorted(counts.values())
    assert got == [10, 20, 30], (got, counts, by_power)


def test_valset_update_and_remove():
    pairs = _mkvals(4, power=lambda i: 10)
    vs = ValidatorSet([v for _, v in pairs])
    # raise one validator's power
    target = pairs[0][1]
    vs.update_with_change_set(
        [Validator.new(pairs[0][0].pub_key(), 100)])
    assert vs.total_voting_power() == 130
    # remove it (power 0)
    vs.update_with_change_set([Validator.new(pairs[0][0].pub_key(), 0)])
    assert vs.total_voting_power() == 30
    assert not vs.has_address(target.address)


def test_valset_hash_changes_with_membership():
    pairs = _mkvals(4)
    vs = ValidatorSet([v for _, v in pairs])
    h1 = vs.hash()
    vs.update_with_change_set([Validator.new(pairs[0][0].pub_key(), 99)])
    assert vs.hash() != h1


# --- the address index behind get_by_address / has_address -----------------

ABSENT = bytes(20)


def _scan(vs, address):
    """The plain front-to-back scan the index replaced: the reference."""
    for i, v in enumerate(vs.validators):
        if v.address == address:
            return i
    return -1


def _assert_lookups_are_the_scan(vs):
    for v in list(vs.validators):
        i, got = vs.get_by_address(v.address)
        assert i == _scan(vs, v.address) == vs.validators.index(v)
        assert got == v and got is not v
        assert vs.has_address(v.address)
    assert vs.get_by_address(ABSENT) == (-1, None)
    assert not vs.has_address(ABSENT)


def _indexed_set(n=9):
    """A set whose index has been built and used, and its key pairs."""
    pairs = _mkvals(n, power=lambda i: 10 + i % 3)
    vs = ValidatorSet([v for _, v in pairs])
    _assert_lookups_are_the_scan(vs)
    return vs, pairs


def _lookup_mid_update(vs, changes):
    """What the explicit drop after the in-place sort is for: a lookup
    between _apply_updates and the sort builds the index on the fresh
    list in its unsorted order."""
    shift = vs._shift_by_avg_proposer_priority

    def shift_after_a_lookup():
        assert vs.has_address(changes[0].address)
        shift()

    vs._shift_by_avg_proposer_priority = shift_after_a_lookup
    vs.update_with_change_set(changes)


# each gives the set to look at, or changes `vs` in place and gives None
INDEX_CASES = {
    "constructor": lambda vs, pairs: vs,
    "from_proto": lambda vs, pairs: ValidatorSet.from_proto(vs.proto()),
    "copy": lambda vs, pairs: vs.copy(),
    "copy_of_a_fresh_set":
        lambda vs, pairs: ValidatorSet([v for _, v in pairs]).copy(),
    "copy_increment_proposer_priority":
        lambda vs, pairs: vs.copy_increment_proposer_priority(3),
    "addition": lambda vs, pairs: vs.update_with_change_set(
        [Validator.new(edkeys.PrivKey(bytes([200 + k] * 32)).pub_key(),
                       5 + 20 * k) for k in range(2)]),
    # moves the last validator to the front: every position shifts
    "power_change": lambda vs, pairs: vs.update_with_change_set(
        [Validator.new(vs.validators[-1].pub_key, 1000)]),
    "removal": lambda vs, pairs: vs.update_with_change_set(
        [Validator.new(vs.validators[0].pub_key, 0),
         Validator.new(vs.validators[4].pub_key, 0)]),
    "all_three_at_once": lambda vs, pairs: vs.update_with_change_set(
        [Validator.new(edkeys.PrivKey(bytes([201] * 32)).pub_key(), 11),
         Validator.new(vs.validators[-1].pub_key, 1000),
         Validator.new(vs.validators[2].pub_key, 0)]),
    "lookup_between_merge_and_sort": lambda vs, pairs: _lookup_mid_update(
        vs, [Validator.new(vs.validators[-1].pub_key, 1000),
             Validator.new(edkeys.PrivKey(bytes([202] * 32)).pub_key(), 1)]),
}


@pytest.mark.parametrize("case", sorted(INDEX_CASES))
def test_address_index_agrees_with_a_scan(case):
    vs, pairs = _indexed_set()
    before = list(vs.validators)
    got = INDEX_CASES[case](vs, pairs) or vs
    _assert_lookups_are_the_scan(got)
    if got is not vs:            # the source set answers as it did
        assert vs.validators == before
        _assert_lookups_are_the_scan(vs)


def test_copy_shares_a_built_index_and_a_change_to_either_drops_it_there():
    vs, pairs = _indexed_set()
    c = vs.copy()
    assert c._addr_index[1] is vs._addr_index[1]
    assert c._addr_index[0] is c.validators
    gone = c.validators[0].address
    c.update_with_change_set([Validator.new(c.validators[0].pub_key, 0)])
    assert not c.has_address(gone) and vs.has_address(gone)
    _assert_lookups_are_the_scan(c)
    _assert_lookups_are_the_scan(vs)


def test_from_proto_repeated_address_resolves_to_the_first():
    """from_proto rejects no repeated address; the scan answered with the
    first, and the trusting check's double-vote detection is keyed by the
    index it gets."""
    from tendermint_tpu.libs import protoenc as pe
    pairs = _mkvals(5)
    vals = [v for _, v in pairs]
    twin = Validator(address=vals[1].address, pub_key=vals[3].pub_key,
                     voting_power=77)
    body = b"".join(pe.message_field_always(1, v.proto())
                    for v in vals + [twin, vals[0]])
    vs = ValidatorSet.from_proto(body)
    assert [v.address for v in vs.validators].count(vals[1].address) == 2
    assert vs.get_by_address(vals[1].address) == (1, vals[1])
    assert vs.get_by_address(vals[0].address) == (0, vals[0])
    for v in vs.validators:
        assert vs.get_by_address(v.address)[0] == _scan(vs, v.address)


def test_get_by_address_returns_a_copy():
    vs, pairs = _indexed_set(4)
    addr = vs.validators[2].address
    i, v = vs.get_by_address(addr)
    v.voting_power += 1
    v.proposer_priority = 12345
    v.address = ABSENT
    assert vs.validators[i].voting_power == v.voting_power - 1
    assert vs.validators[i].proposer_priority != 12345
    assert vs.get_by_address(addr)[0] == i and not vs.has_address(ABSENT)


def test_pickling_carries_no_address_index():
    import pickle
    vs, pairs = _indexed_set(4)
    assert vs._addr_index is not None
    assert "_addr_index" not in vs.__getstate__()
    back = pickle.loads(pickle.dumps(vs))
    assert "_addr_index" not in back.__dict__ and back._addr_index is None
    _assert_lookups_are_the_scan(back)


# --- commit verification over the batch data plane ------------------------

CHAIN = "test-chain"


def _make_commit(pairs, height=3, round_=0, absent=(), nil=(), bad=()):
    bid = BlockID(hash=bytes([7] * 32),
                  part_set_header=PartSetHeader(1, bytes([8] * 32)))
    vs = ValidatorSet([v for _, v in pairs])
    sigs = []
    # commit order must match validator-set order; map address -> priv
    by_addr = {v.address: priv for priv, v in pairs}
    for idx, val in enumerate(vs.validators):
        priv = by_addr[val.address]
        if idx in absent:
            sigs.append(CommitSig.absent())
            continue
        flag = BlockIDFlag.NIL if idx in nil else BlockIDFlag.COMMIT
        voted = BlockID() if idx in nil else bid
        ts = Timestamp(1700000000 + idx, idx)
        from tendermint_tpu.types.canonical import canonical_vote_bytes
        sb = canonical_vote_bytes(CHAIN, SignedMsgType.PRECOMMIT, height,
                                  round_, voted, ts)
        sig = priv.sign(sb)
        if idx in bad:
            sig = bytes([sig[0] ^ 1]) + sig[1:]
        sigs.append(CommitSig(flag, val.address, ts, sig))
    return vs, bid, Commit(height, round_, bid, sigs)


def test_verify_commit_all_good():
    pairs = _mkvals(6)
    vs, bid, commit = _make_commit(pairs)
    vs.verify_commit(CHAIN, bid, 3, commit)          # must not raise
    vs.verify_commit_light(CHAIN, bid, 3, commit)
    vs.verify_commit_light_trusting(CHAIN, commit, Fraction(1, 3))


def test_verify_commit_with_absent_and_nil():
    pairs = _mkvals(7)
    vs, bid, commit = _make_commit(pairs, absent={2}, nil={4})
    vs.verify_commit(CHAIN, bid, 3, commit)


def test_verify_commit_bad_sig_identified():
    pairs = _mkvals(6)
    vs, bid, commit = _make_commit(pairs, bad={3})
    with pytest.raises(CommitVerifyError, match=r"wrong signature \(#3\)"):
        vs.verify_commit(CHAIN, bid, 3, commit)


def test_verify_commit_insufficient_power():
    pairs = _mkvals(6)
    vs, bid, commit = _make_commit(pairs, absent={0, 1, 2, 3})
    with pytest.raises(NotEnoughVotingPowerError):
        vs.verify_commit(CHAIN, bid, 3, commit)


def test_verify_commit_light_ignores_bad_sig_after_twothirds():
    """The serial reference exits at 2/3 and never sees later signatures; the
    batched implementation must preserve that acceptance."""
    pairs = _mkvals(6)
    vs, bid, commit = _make_commit(pairs, bad={5})
    # full check rejects...
    with pytest.raises(CommitVerifyError):
        vs.verify_commit(CHAIN, bid, 3, commit)
    # ...light check (prefix crosses 2/3 before index 5) accepts
    vs.verify_commit_light(CHAIN, bid, 3, commit)


def test_verify_commit_wrong_height_and_blockid():
    pairs = _mkvals(4)
    vs, bid, commit = _make_commit(pairs)
    with pytest.raises(CommitVerifyError, match="wrong height"):
        vs.verify_commit(CHAIN, bid, 4, commit)
    other = BlockID(hash=bytes([9] * 32),
                    part_set_header=PartSetHeader(1, bytes([8] * 32)))
    with pytest.raises(CommitVerifyError, match="wrong block ID"):
        vs.verify_commit(CHAIN, other, 3, commit)


def test_light_trusting_different_valset():
    """Commit from a 6-val set verified against a 4-val overlapping set."""
    pairs = _mkvals(6)
    vs, bid, commit = _make_commit(pairs)
    # trusted set = subset of 4 validators (by the same keys)
    sub = ValidatorSet([v for _, v in pairs[:4]])
    sub.verify_commit_light_trusting(CHAIN, commit, Fraction(1, 3))


def _trusting_reference(vs, commit, level):
    """Reference :770-821 as its serial loop reads, every lookup a scan:
    what the check must match, raise for, or fall short by."""
    needed = vs.total_voting_power() * level.numerator // level.denominator
    seen, prefix, tallied = {}, [], 0
    for idx, cs in enumerate(commit.signatures):
        if not cs.for_block():
            continue
        vi = _scan(vs, cs.validator_address)
        if vi < 0:
            continue
        if vi in seen:
            return "double", (vi, seen[vi], idx)
        seen[vi] = idx
        prefix.append((idx, vi))
        tallied += vs.validators[vi].voting_power
        if tallied > needed:
            return "prefix", prefix
    return "short", (tallied, needed)


@pytest.fixture
def trusting(monkeypatch):
    """A 120-validator commit, its own set, and an overlapping set of 100
    of its signers (and 5 strangers) in another order; the device route's
    choice of input (rows of the cached pubkey matrix when aligned, key
    objects when not) taken as on a chip, every signature then verified
    on the host; the recorder on."""
    import numpy as np
    from tendermint_tpu.crypto import batch
    from tendermint_tpu.libs import trace
    from tendermint_tpu.types import validator_set as vsmod

    calls = []

    def bulk(pubs, msgs, sigs):
        rows = isinstance(pubs, np.ndarray)
        keys = [edkeys.PubKey(bytes(p)) for p in pubs] if rows \
            else list(pubs)
        # beside matrix rows the signatures are an (n, 64) matrix too
        assert isinstance(sigs, np.ndarray) is rows
        sigs = [bytes(s) for s in sigs]
        calls.append({"rows": rows, "keys": [k.bytes() for k in keys],
                      "sigs": sigs})
        return np.array([k.verify_signature(msgs[j], sigs[j])
                         for j, k in enumerate(keys)], dtype=bool)

    monkeypatch.setattr(batch, "_use_device", lambda: True)
    monkeypatch.setattr(vsmod, "verify_sigs_bulk", bulk)
    pairs = _mkvals(120)
    own, _, commit = _make_commit(pairs, absent={5}, nil={9})
    other = ValidatorSet(
        [Validator.new(priv.pub_key(), 10 + i % 7)
         for i, (priv, _) in enumerate(pairs[10:110])]
        + [Validator.new(edkeys.PrivKey(bytes([240 + k] * 32)).pub_key(),
                         15) for k in range(5)])
    trace.enable()
    trace.reset()
    yield {"own": own, "other": other, "commit": commit, "calls": calls,
           "spans": lambda name: [r for r in trace.snapshot()
                                  if r["name"] == name]}
    trace.disable()
    trace.reset()


def _tamper(commit, idx):
    sig = commit.signatures[idx].signature
    commit.signatures[idx].signature = bytes([sig[0] ^ 1]) + sig[1:]


@pytest.mark.parametrize("which", ["own", "other"])
def test_light_trusting_matches_the_scanning_reference(trusting, which):
    """The same commit against (a) its own set and (b) an overlapping set
    in another order: the prefix the reference's scan gives, each
    signature against its own key, from matrix rows in (a) alone."""
    vs, commit = trusting[which], trusting["commit"]
    level = Fraction(1, 3)
    kind, prefix = _trusting_reference(vs, commit, level)
    assert kind == "prefix" and len(prefix) >= 32
    vs.verify_commit_light_trusting(CHAIN, commit, level)
    (call,) = trusting["calls"]
    assert call["sigs"] == [commit.signatures[i].signature
                            for i, _ in prefix]
    assert call["keys"] == [vs.validators[vi].pub_key.bytes()
                            for _, vi in prefix]
    (match,) = trusting["spans"]("commit.match")
    assert match["attrs"] == {
        "scanned": prefix[-1][0] + 1, "matched": len(prefix),
        "lookups": sum(cs.for_block() for cs in
                       commit.signatures[:prefix[-1][0] + 1]),
        "index_built": True}
    (collect,) = trusting["spans"]("commit.collect")
    aligned = all(i == vi for i, vi in prefix)
    assert aligned is (which == "own")
    assert collect["attrs"]["aligned"] is aligned is call["rows"]


@pytest.mark.parametrize("which", ["own", "other"])
def test_light_trusting_names_the_lowest_bad_index(trusting, which):
    vs, commit = trusting[which], trusting["commit"]
    level = Fraction(1, 3)
    _, prefix = _trusting_reference(vs, commit, level)
    lanes = [prefix[k][0] for k in (len(prefix) // 2, 3, len(prefix) - 1)]
    # of a signer the trusted set does not know, or past the prefix: unseen
    matched, end = {i for i, _ in prefix}, prefix[-1][0]
    unseen = [i for i in range(end) if i not in matched | {5, 9}][:2] \
        + [end + 1]
    for i in lanes + unseen:
        _tamper(commit, i)
    with pytest.raises(CommitVerifyError,
                       match=rf"wrong signature \(#{min(lanes)}\)"):
        vs.verify_commit_light_trusting(CHAIN, commit, level)
    for i in lanes:
        _tamper(commit, i)
    vs.verify_commit_light_trusting(CHAIN, commit, level)


@pytest.mark.parametrize("which", ["own", "other"])
def test_light_trusting_double_vote_and_short_power(trusting, which):
    vs, commit = trusting[which], trusting["commit"]
    _, prefix = _trusting_reference(vs, commit, Fraction(1, 3))
    (first, vi), (second, _) = prefix[2], prefix[7]
    honest = commit.signatures[second]
    commit.signatures[second] = commit.signatures[first]
    assert _trusting_reference(vs, commit, Fraction(1, 3)) == \
        ("double", (vi, first, second))
    with pytest.raises(CommitVerifyError) as ei:
        vs.verify_commit_light_trusting(CHAIN, commit, Fraction(1, 3))
    assert str(ei.value) == \
        f"double vote from validator {vi} ({first} and {second})"
    commit.signatures[second] = honest
    # all of the trusted power cannot be exceeded: short, by the same sums
    kind, (got, needed) = _trusting_reference(vs, commit, Fraction(1, 1))
    assert kind == "short"
    with pytest.raises(NotEnoughVotingPowerError) as ei:
        vs.verify_commit_light_trusting(CHAIN, commit, Fraction(1, 1))
    assert (ei.value.got, ei.value.needed) == (got, needed)
    assert not trusting["calls"]


def test_commit_hash_covers_signatures():
    pairs = _mkvals(4)
    _, _, c1 = _make_commit(pairs)
    _, _, c2 = _make_commit(pairs, nil={1})
    assert c1.hash() != c2.hash()
    assert len(c1.hash()) == 32


def test_commit_sign_bytes_batch_byte_exact():
    """commit_sign_bytes_batch must be byte-identical to the per-index
    canonical_vote_bytes encoder, for both the native C assembler and the
    pure-Python fallback (nil votes, zero nanos, Go-zero timestamps)."""
    from tendermint_tpu.libs import native
    from tendermint_tpu.types.canonical import commit_sign_bytes_batch

    pairs = _mkvals(9)
    vs, bid, commit = _make_commit(pairs, nil={1, 5})
    # edge-case timestamps: zero nanos, Go zero time (negative seconds)
    commit.signatures[2].__dict__["timestamp"] = Timestamp(1700000000, 0)
    commit.signatures[5].__dict__["timestamp"] = Timestamp.zero()
    idxs = list(range(len(commit.signatures)))
    want = [commit.vote_sign_bytes(CHAIN, i) for i in idxs]

    got = commit_sign_bytes_batch(CHAIN, commit, idxs)
    assert len(got) == len(want)
    assert [got[i] for i in idxs] == want

    if native.get_lib() is not None:  # force the no-C fallback too
        orig = native.vote_sign_bytes
        native.vote_sign_bytes = lambda *a, **k: None
        try:
            fb = commit_sign_bytes_batch(CHAIN, commit, idxs)
        finally:
            native.vote_sign_bytes = orig
        assert [fb[i] for i in idxs] == want

    # subsets and duplicates resolve by index
    sub = commit_sign_bytes_batch(CHAIN, commit, [7, 0, 7])
    assert [sub[0], sub[1], sub[2]] == [want[7], want[0], want[7]]

    # fed from the rows' columns, as the entry points feed it: an index
    # array over columns the caller has read, nil-vote rows among them,
    # the columns no longer than the last row asked for
    import numpy as np
    from tendermint_tpu.types.commit import _columns
    cols = _columns(commit.signatures, ("seconds", "nanos", "flag"))
    for pick in ([1, 2, 5, 6], [5], [0, 1, 5, 8], idxs):
        got = commit_sign_bytes_batch(
            CHAIN, commit, np.asarray(pick, dtype=np.int64), cols)
        assert [got[j] for j in range(len(pick))] == [want[i] for i in pick]
    short = _columns(commit.signatures[:6], ("seconds", "nanos", "flag"))
    got = commit_sign_bytes_batch(CHAIN, commit, np.array([1, 4, 5]), short)
    assert [got[0], got[1], got[2]] == [want[1], want[4], want[5]]
    assert len(want[5]) < len(want[4])      # a nil vote names no block
