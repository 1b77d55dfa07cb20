"""LightStore over every KVDB backend: which blocks it holds is answered
from the keys alone (KVDB.iterate_keys), a lookup reads exactly the one
value it returns, prune drops oldest first, and the store's own counters say
so.  And the record it keeps a block as (light/record.py): columns that
give back what the generic codec gives back, field for field."""
from __future__ import annotations

import dataclasses
import os
import pickle
import random
import time

import pytest

from perfbench.traffic import light_client as chain
from tendermint_tpu.crypto import ed25519, secp256k1
from tendermint_tpu.libs import safe_codec, trace
from tendermint_tpu.libs.kvdb import GroupCommitDB, MemDB, SQLiteDB
from tendermint_tpu.light import record
from tendermint_tpu.light.store import _PREFIX, LightStore, _key
from tendermint_tpu.types.basic import (BlockID, BlockIDFlag, PartSetHeader,
                                        Timestamp)
from tendermint_tpu.types.block import Header
from tendermint_tpu.types.commit import Commit, CommitSig
from tendermint_tpu.types.light_block import LightBlock, SignedHeader
from tendermint_tpu.types.validator import Validator
from tendermint_tpu.types.validator_set import ValidatorSet

CONFIG = {"name": "store-test", "chain_id": "store-test", "validators": 4,
          "voting_power": 1, "rotation_per_block": 1}
HEIGHTS = list(range(1, 25))


@pytest.fixture(scope="module")
def blocks():
    return {h: chain.light_block_at(11, CONFIG, h) for h in HEIGHTS}


def _grouping(inner):
    db = GroupCommitDB(inner)
    db.begin_group_mode()
    return db


BACKENDS = {
    "mem": lambda tmp: MemDB(),
    "sqlite": lambda tmp: SQLiteDB(str(tmp / "light.db")),
    "sqlite-deferred": lambda tmp: SQLiteDB(str(tmp / "light.db"),
                                            commit_every=8),
    "group-passthrough": lambda tmp: GroupCommitDB(MemDB()),
    "group-buffering": lambda tmp: _grouping(SQLiteDB(str(tmp / "l.db"))),
}


@pytest.fixture(params=sorted(BACKENDS))
def db(request, tmp_path):
    db = BACKENDS[request.param](tmp_path)
    yield db
    db.close()


class Spy:
    """A KVDB that counts the calls that hand out values."""

    def __init__(self, inner):
        self._inner = inner
        self.gets = self.value_scans = 0

    def get(self, key):
        self.gets += 1
        return self._inner.get(key)

    def iterate_prefix(self, prefix):
        self.value_scans += 1
        return self._inner.iterate_prefix(prefix)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def plain_heights(db) -> list:
    """What the store did before it had a key-only scan."""
    import struct
    return sorted(struct.unpack(">q", k[len(_PREFIX):])[0]
                  for k, _ in db.iterate_prefix(_PREFIX))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_heights_and_lookups_equal_a_plain_scan(db, blocks, seed):
    rng = random.Random(seed)
    store, model = LightStore(db), set()
    db.set(b"la/unrelated", b"x")       # neighbours of the prefix
    db.set(b"lc", b"y")
    for _ in range(120):
        op = rng.choice(["save", "save", "save", "delete", "prune"])
        if op == "save":
            h = rng.choice(HEIGHTS)
            store.save(blocks[h])
            model.add(h)
        elif op == "delete":
            h = rng.choice(HEIGHTS)
            store.delete(h)
            model.discard(h)
        else:
            keep = rng.randrange(0, 12)
            store.prune(keep)
            model = set(sorted(model)[-keep:]) if keep else set()
        want = sorted(model)
        assert store.heights() == want == plain_heights(db)
        probe = rng.randrange(0, 27)
        below = [h for h in want if h <= probe]
        for got, height in ((store.latest(), want[-1:]),
                            (store.first(), want[:1]),
                            (store.latest_before(probe), below[-1:])):
            assert (got.height if got else None) == \
                (height[0] if height else None)
            assert got is None or got.hash() == blocks[got.height].hash()


def test_only_a_lookup_reads_a_value_and_it_reads_one(db, blocks):
    spy = Spy(db)
    store = LightStore(spy)
    for h in (3, 9, 4, 17, 12):
        store.save(blocks[h])
    written = store.bytes_written
    assert written > 0 and (spy.gets, spy.value_scans) == (0, 0)
    assert store.heights() == [3, 4, 9, 12, 17]
    store.prune(4)
    store.delete(4)
    assert store.heights() == [9, 12, 17] and store.pruned == 1
    assert (spy.gets, spy.value_scans, store.value_reads) == (0, 0, 0)
    for lookup, height in ((store.latest, 17), (store.first, 9),
                           (lambda: store.latest_before(16), 12),
                           (lambda: store.get(9), 9)):
        gets, reads, nbytes = spy.gets, store.value_reads, store.bytes_read
        assert lookup().height == height
        assert spy.gets == gets + 1 and store.value_reads == reads + 1
        assert store.bytes_read > nbytes
    assert store.latest_before(8) is None and store.get(5) is None
    assert store.value_reads == 4       # a miss reads no value
    assert spy.value_scans == 0 and store.bytes_written == written


@pytest.mark.parametrize("keep", [0, 1, 3, 5, 50])
def test_prune_drops_oldest_first(db, blocks, keep):
    store = LightStore(db)
    held = [2, 21, 7, 13, 5]
    for h in held:
        store.save(blocks[h])
    store.prune(keep)
    assert store.heights() == (sorted(held)[-keep:] if keep else [])
    assert store.pruned == max(0, len(held) - keep)


def test_iterate_keys_is_iterate_prefix_without_the_values(db):
    rng = random.Random(7)
    for _ in range(200):
        key = bytes(rng.choice(b"ab\xff") for _ in range(rng.randrange(1, 4)))
        if rng.random() < 0.3:
            db.delete(key)
        else:
            db.set(key, bytes(rng.randrange(1, 9)))
        if isinstance(db, GroupCommitDB) and db.group_mode() \
                and rng.random() < 0.1:
            group = db.take_group()     # in flight, then landed
            if group and rng.random() < 0.5:
                db.commit_group(group)
        prefix = rng.choice([b"", b"a", b"ab", b"b", b"\xff", b"\xff\xff"])
        assert list(db.iterate_keys(prefix)) == \
            [k for k, _ in db.iterate_prefix(prefix)]


def test_sqlite_answers_a_key_scan_from_its_index_alone(tmp_path):
    """The point of iterate_keys on the backend `cmd light` uses: the
    rows, where megabytes of values lie, are not visited."""
    db = SQLiteDB(str(tmp_path / "light.db"))
    try:
        plan = " ".join(str(row[-1]) for row in db._conn.execute(
            "EXPLAIN QUERY PLAN SELECT k FROM kv WHERE k >= ? AND k < ? "
            "ORDER BY k", (b"lb/", b"lb0")))
    finally:
        db.close()
    assert "COVERING INDEX" in plan, plan


# ---------------------------------------------------------------------------
# the record (light/record.py): what `save` writes and `get` reads
# ---------------------------------------------------------------------------

DERIVED = ("_hash_memo", "_addr_index", "_pubmat_cache")
# a global the safe codec does not allow (nothing here ever loads it)
FORBIDDEN_PICKLE = pickle.dumps(os.getcwd, protocol=4)


def with_signatures(lb, change) -> LightBlock:
    """`lb` with its commit's signatures passed through `change`."""
    sh = lb.signed_header
    sigs = change(list(sh.commit.signatures))
    return LightBlock(SignedHeader(sh.header, dataclasses.replace(
        sh.commit, signatures=sigs)), lb.validators)


def absent_and_nil(sigs):
    for i in (1, 7, 8, 149):
        sigs[i] = CommitSig.absent()
    for i in (0, 33):
        sigs[i] = dataclasses.replace(sigs[i],
                                      block_id_flag=BlockIDFlag.NIL)
    return sigs


def synthetic(keys, proposer="own") -> LightBlock:
    """A block over `keys` (public keys of any scheme) with made-up
    signatures; the header's validators_hash is the set's."""
    vset = ValidatorSet([Validator.new(k, 3 + i) for i, k in enumerate(keys)])
    if proposer is None:
        vset.proposer = None
    header = Header(chain_id="record-test", height=9, time=Timestamp(77, 5),
                    validators_hash=vset.hash(),
                    proposer_address=vset.validators[0].address)
    bid = BlockID(header.hash(), PartSetHeader(1, b"\x07" * 32))
    sigs = [CommitSig(BlockIDFlag.COMMIT, v.address, Timestamp(80 + i, i),
                      bytes([i]) * 64) for i, v in enumerate(vset.validators)]
    return LightBlock(SignedHeader(header, Commit(9, 2, bid, sigs)), vset)


def ed_keys(n):
    return [ed25519.PrivKey(bytes([i + 1]) * 32).pub_key() for i in range(n)]


@pytest.fixture(scope="module")
def cases():
    plain = chain.light_block_at(5, dict(CONFIG, validators=150), 3)
    other_scheme = ed_keys(3) + [
        secp256k1.PrivKey.gen_from_secret(b"record").pub_key()]
    stale = synthetic(ed_keys(4))
    stale.validators = stale.validators.copy()      # proposer: the old
    stale.validators.validators[0].proposer_priority += 1   # list's, and
    stale.validators.proposer.proposer_priority -= 5        # equal to none
    beyond_int64 = synthetic(ed_keys(2))
    beyond_int64.validators.validators[1].voting_power = 1 << 70
    return {
        "all_signing_150": (plain, record.COLUMNS),
        "absent_and_nil": (with_signatures(plain, absent_and_nil),
                           record.COLUMNS),
        "one_secp256k1_key": (synthetic(other_scheme), record.COLUMNS),
        "single_validator": (synthetic(ed_keys(1)), record.COLUMNS),
        "no_proposer": (synthetic(ed_keys(5), proposer=None),
                        record.COLUMNS),
        "stale_proposer": (stale, record.GENERIC),
        "power_beyond_int64": (beyond_int64, record.GENERIC),
    }


CASES = ["all_signing_150", "absent_and_nil", "one_secp256k1_key",
         "single_validator", "no_proposer", "stale_proposer",
         "power_beyond_int64"]


def assert_same_block(got: LightBlock, want: LightBlock):
    """Field for field, types included; `want` may carry memos."""
    assert type(got) is LightBlock and got.signed_header == want.signed_header
    for a, b in zip(got.signed_header.commit.signatures,
                    want.signed_header.commit.signatures):
        assert type(a.block_id_flag) is type(b.block_id_flag)
    state, wanted = got.validators.__dict__, want.validators.__getstate__()
    assert list(state) == list(wanted)
    assert state["validators"] == wanted["validators"]
    assert [type(v.pub_key) for v in state["validators"]] == \
        [type(v.pub_key) for v in wanted["validators"]]
    assert state["proposer"] == wanted["proposer"]
    assert state["_total_voting_power"] == wanted["_total_voting_power"]


def spans_since(seq, name):
    return [r for r in trace.snapshot(since=seq) if r["name"] == name]


@pytest.fixture
def recorder_on():
    was = trace.is_enabled()
    trace.enable()
    yield
    if not was:
        trace.disable()


@pytest.mark.parametrize("backend", ["mem", "sqlite"])
@pytest.mark.parametrize("case", CASES)
def test_a_saved_block_comes_back_as_the_generic_codec_gives_it(
        cases, case, backend, tmp_path, recorder_on):
    lb, kind = cases[case]
    db = BACKENDS[backend](tmp_path)
    try:
        store, seq = LightStore(db), trace.last_seq()
        store.save(lb)
        raw = db.get(_key(lb.height))
        assert raw[:len(record.MAGIC)] == record.MAGIC
        assert store.bytes_written == len(raw) > 0
        got = store.get(lb.height)
        assert store.bytes_read == len(raw)
    finally:
        db.close()
    (save,) = spans_since(seq, "light.store.save")
    (enc,) = spans_since(seq, "light.store.encode")
    (load,) = spans_since(seq, "light.store.load")
    (dec,) = spans_since(seq, "light.store.decode")
    assert enc["attrs"]["record"] == dec["attrs"]["record"] == kind
    assert enc["parent"] == save["id"] and dec["parent"] == load["id"]
    assert save["attrs"]["bytes"] == load["attrs"]["bytes"] == len(raw)
    assert_same_block(got, lb)
    if case != "one_secp256k1_key":     # which the generic codec refuses
        assert_same_block(got, safe_codec.loads(safe_codec.dumps(lb)))
    # the proposer is the list's own element, and nothing derived came
    # back: the set hashes its own bytes
    vset = got.validators
    assert vset.proposer is None or case == "stale_proposer" \
        or any(vset.proposer is v for v in vset.validators)
    assert not any(name in vset.__dict__ for name in DERIVED)
    assert case == "power_beyond_int64" \
        or vset.hash() == lb.signed_header.header.validators_hash


def test_a_set_with_memos_is_written_as_one_without(cases):
    lb, _ = cases["all_signing_150"]
    bare = safe_codec.loads(safe_codec.dumps(lb))
    vset = lb.validators
    vset.hash(), vset.has_address(b"x")
    assert vset._hash_memo is not None and vset._addr_index is not None
    assert not any(name in bare.validators.__dict__ for name in DERIVED)
    raw, kind = record.encode(lb)
    assert kind == record.COLUMNS and raw == record.encode(bare)[0]
    # the root is in the record once: where the header names it
    assert raw.count(vset.hash()) == 1


def frames_of(raw: bytes) -> list:
    return record._frames(memoryview(raw)[len(record.MAGIC) + 1:])


def reframed(raw: bytes, frames: list) -> bytes:
    out = [raw[:len(record.MAGIC) + 1]]
    for f in frames:
        out += [len(f).to_bytes(4, "little"), f]
    return b"".join(out)


def damaged_records(raw: bytes) -> dict:
    frames = frames_of(raw)
    head = safe_codec.loads(frames[0])
    one_validator_more = list(head)
    one_validator_more[4] += 1
    one_signature_fewer = list(head)
    one_signature_fewer[5] -= 1
    proposer_outside = list(head)
    proposer_outside[6] = head[4]
    swapped = list(frames)
    swapped[1], swapped[3] = frames[3], frames[1]    # addresses <-> keys

    def with_head(h):
        return reframed(raw, [safe_codec.dumps(tuple(h))] + frames[1:])

    return {
        "truncated_by_one": raw[:-1],
        "truncated_in_the_head": raw[:40],
        "truncated_in_a_column": raw[:len(raw) // 2],
        "only_the_magic": raw[:len(record.MAGIC)],
        "one_byte_more": raw + b"\x00",
        "a_frame_more": reframed(raw, frames + [b""]),
        "a_frame_fewer": reframed(raw, frames[:-1]),
        "unknown_version": raw[:len(record.MAGIC)] + b"\x09"
        + raw[len(record.MAGIC) + 1:],
        "another_magic": b"\xfeTLB" + raw[len(record.MAGIC):],
        "count_above_the_columns": with_head(one_validator_more),
        "count_below_the_columns": with_head(one_signature_fewer),
        "proposer_outside_the_set": with_head(proposer_outside),
        "head_is_no_tuple": reframed(
            raw, [safe_codec.dumps(list(head))] + frames[1:]),
        "head_of_a_class_not_allowed": reframed(
            raw, [FORBIDDEN_PICKLE] + frames[1:]),
        "a_short_key": reframed(raw, swapped),
        "an_unknown_flag": reframed(
            raw, frames[:6] + [b"\x07" + frames[6][1:]] + frames[7:]),
        "an_unknown_key_scheme": reframed(
            raw, frames[:2] + [b"\x08"] + frames[3:]),
        "a_column_with_no_kind": reframed(
            raw, frames[:1] + [b"X" + frames[1][1:]] + frames[2:]),
        "row_lengths_past_the_column": reframed(
            raw, frames[:10] + [b"R" + b"\xff\xff\xff\x7f" * head[5]]),
    }


DAMAGE = sorted(damaged_records(record.encode(synthetic(ed_keys(2)))[0]))


@pytest.mark.parametrize("damage", DAMAGE)
@pytest.mark.parametrize("case", ["all_signing_150", "absent_and_nil"])
def test_a_damaged_record_raises_and_gives_nothing(cases, case, damage):
    lb, _ = cases[case]
    raw, _ = record.encode(lb)
    assert_same_block(record.decode(raw)[0], lb)
    bad = damaged_records(raw)[damage]
    db = MemDB()
    db.set(_key(lb.height), bad)
    store, got = LightStore(db), None
    with pytest.raises(record.RecordError):
        got = store.get(lb.height)
    assert got is None


def test_a_value_an_earlier_build_pickled_is_read_back(cases, tmp_path,
                                                       recorder_on):
    lb, _ = cases["absent_and_nil"]
    path = str(tmp_path / "light.db")
    db = SQLiteDB(path)
    db.set(_key(lb.height), safe_codec.dumps(lb))
    db.close()
    db = SQLiteDB(path)
    try:
        store, seq = LightStore(db), trace.last_seq()
        assert_same_block(store.latest(), lb)
        (dec,) = spans_since(seq, "light.store.decode")
        assert dec["attrs"]["record"] == record.LEGACY
        # and what is saved from now on is the new record, beside it
        newer, _ = cases["single_validator"]
        assert newer.height > lb.height
        store.save(newer)
        assert db.get(_key(newer.height))[:4] == record.MAGIC
        assert db.get(_key(lb.height))[:2] == record.LEGACY_MARK
        assert store.heights() == sorted([lb.height, newer.height])
        for want in (lb, newer):
            assert_same_block(store.get(want.height), want)
    finally:
        db.close()
    # something pickled that is no light block is refused, not returned
    with pytest.raises(record.RecordError):
        record.decode(safe_codec.dumps(lb.signed_header))
    with pytest.raises(record.RecordError):
        record.decode(FORBIDDEN_PICKLE)


def test_ten_thousand_validators_are_columns_of_under_1_8_mb_in_a_quarter_of_the_time(
        recorder_on):
    """The reason for the record: at the protocol's largest set the
    generic codec walks ~40,000 objects to write 2.29 MB; the columns
    hold the same in under 1.8 MB, and the store's encode span is under
    a quarter of a `safe_codec.dumps` of the same block in the same
    process (best of five each: a loaded machine slows both)."""
    config = dict(CONFIG, validators=10_000, rotation_per_block=100)
    lb = chain.light_block_at(3, config, 2)
    generic = []
    for _ in range(5):
        t0 = time.perf_counter_ns()
        pickled = safe_codec.dumps(lb)
        generic.append(time.perf_counter_ns() - t0)
    db = MemDB()
    store, seq = LightStore(db), trace.last_seq()
    for _ in range(5):
        store.save(lb)
    spans = spans_since(seq, "light.store.encode")
    assert [s["attrs"]["record"] for s in spans] == [record.COLUMNS] * 5
    raw = db.get(_key(lb.height))
    assert len(raw) < 1_800_000 < len(pickled)
    assert store.bytes_written == 5 * len(raw)
    assert min(s["dur_ns"] for s in spans) < min(generic) / 4, \
        (sorted(s["dur_ns"] for s in spans), sorted(generic))
    assert_same_block(store.get(lb.height), lb)
