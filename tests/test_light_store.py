"""LightStore over every KVDB backend: which blocks it holds is answered
from the keys alone (KVDB.iterate_keys), a lookup reads exactly the one
value it returns, prune drops oldest first, and the store's own counters say
so."""
from __future__ import annotations

import random

import pytest

from perfbench.traffic import light_client as chain
from tendermint_tpu.libs.kvdb import GroupCommitDB, MemDB, SQLiteDB
from tendermint_tpu.light.store import _PREFIX, LightStore

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
