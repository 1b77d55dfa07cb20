"""The light client proper on chains whose validator set rotates, which is
the only reason skipping verification bisects: light.client.Client (on a
LightStore) against the plain reference perfbench/reference/light_client.py
(dict store, hashlib, one OpenSSL call a signature) on seeded chains of 100
validators, 1 leaving and 1 joining a block: equal fetch order, verify
calls, trace, verdict and store.  The first tests here in which the client
really bisects."""
from __future__ import annotations

import functools
from fractions import Fraction

import pytest

from perfbench.reference import light_client as reference
from perfbench.traffic import light_client as chain
from tendermint_tpu.libs import trace
from tendermint_tpu.libs.kvdb import MemDB, SQLiteDB
from tendermint_tpu.light import (Client, DictProvider, LightClientError,
                                  LightStore, TrustOptions)
from tendermint_tpu.types.basic import Timestamp
from tendermint_tpu.types.light_block import LightBlock

CONFIG = {"name": "rotating-test", "chain_id": "rotating-test",
          "validators": 100, "voting_power": 1, "rotation_per_block": 1,
          "trust_level": [1, 3], "trusting_period_s": 1209600,
          "max_clock_drift_s": 10}
REACH = chain.reach(CONFIG)
ANCHOR = 1
NOW = Timestamp(chain.T0 + 5000, 0)
N_TRUST, N_LIGHT = 34, 67


@functools.lru_cache(maxsize=None)
def block(seed: int, height: int) -> LightBlock:
    return chain.light_block_at(seed, CONFIG, height)


def targets(gap: int, n: int) -> list:
    return [ANCHOR + gap * (k + 1) for k in range(n)]


def blocks_for(seed: int, wanted: list) -> dict:
    heights = {ANCHOR}
    for a, t in zip([ANCHOR] + wanted, wanted):
        heights.update(chain.fetch_plan(a, t, REACH))
    return {h: block(seed, h) for h in heights}


def make_pair(blocks: dict, pruning_size: int, db=None):
    """(the system's client on its store, the reference on its dict), both
    trusting the anchor, each with a provider and a witness of its own."""
    client = Client(
        CONFIG["chain_id"],
        TrustOptions(ANCHOR, blocks[ANCHOR].hash(),
                     float(CONFIG["trusting_period_s"])),
        chain.RecordingProvider(CONFIG["chain_id"], blocks), [DictProvider(CONFIG["chain_id"], blocks)],
        LightStore(db if db is not None else MemDB()),
        trust_level=Fraction(1, 3), pruning_size=pruning_size)
    plain = reference.LightClient(
        CONFIG["chain_id"], chain.RecordingProvider(CONFIG["chain_id"], blocks),
        DictProvider(CONFIG["chain_id"], blocks), {ANCHOR: blocks[ANCHOR]},
        pruning_size=pruning_size)
    return client, plain


def ask(client, target: int):
    """(block or refusal, heights fetched, [(height, outcome)] of the
    request's light.verify spans, heights saved)."""
    client.primary.asked = []
    before, seq = client.store.heights(), trace.last_seq()
    try:
        got = client.verify_light_block_at_height(target, NOW)
    except LightClientError as e:
        got = e
    spans = trace.snapshot(since=seq)
    calls = [(r["attrs"]["height"], r["attrs"]["outcome"]) for r in spans
             if r["name"] == "light.verify"]
    saved = sorted(set(client.store.heights()) - set(before))
    return got, client.primary.asked, calls, saved


@pytest.fixture(autouse=True)
def recorder_on():
    was = trace.is_enabled()
    trace.enable()
    yield
    if not was:
        trace.disable()


@pytest.mark.parametrize("pruning_size", [4, 1000])
@pytest.mark.parametrize("gap,n_requests", [(64, 4), (256, 3), (1000, 2)])
def test_client_equals_the_reference_on_a_rotating_chain(gap, n_requests,
                                                         pruning_size):
    seed = 3 + gap
    wanted = targets(gap, n_requests)
    client, plain = make_pair(blocks_for(seed, wanted), pruning_size)
    refused = 0
    for target in wanted:
        got, asked, calls, saved = ask(client, target)
        res = plain.verify_to_height(target, NOW)
        assert res.verdict == reference.OK and got is block(seed, target)
        assert asked == res.fetched
        assert calls == [(to, outcome) for _, to, outcome in res.checks]
        assert saved == [h for h in res.saved if h in res.store]
        assert client.store.heights() == res.store
        assert len(res.store) <= pruning_size
        root = [r for r in trace.snapshot()
                if r["name"] == "light.client.verify"][-1]["attrs"]
        assert (root["target"], root["fetched"], root["saved"]) == \
            (target, len(asked), len(res.saved))
        assert root["hops"] == len(res.saved) >= -(-gap // REACH)
        assert root["hops"] + root["refused_skips"] == len(calls)
        refused += root["refused_skips"]
    # the point of the chain: a skip of more than 66 heights is refused
    assert (refused > 0) == (gap > REACH)
    assert client.last_trusted_height() == wanted[-1]


def test_a_request_of_256_heights_is_four_refused_skips_and_four_hops():
    wanted = targets(256, 1)
    client, _ = make_pair(blocks_for(5, wanted), 1000)
    seq = trace.last_seq()
    _, asked, calls, saved = ask(client, wanted[0])
    t = wanted[0]
    assert asked == [t, t - 128, t - 192, t - 64]
    assert calls == [
        (t, "cant_trust"), (t - 128, "cant_trust"), (t - 192, "ok"),
        (t, "cant_trust"), (t - 128, "ok"), (t, "cant_trust"),
        (t - 64, "ok"), (t, "ok")]
    assert saved == [t - 192, t - 128, t - 64, t]
    # a refused skip launches nothing: its commit.match span says what it
    # found, of the 100 rows it went through
    matches = [r["attrs"] for r in trace.snapshot(since=seq)
               if r["name"] == "commit.match"]
    refused = [m for m in matches if "needed" in m]
    assert len(matches) == 8 and len(refused) == 4
    for m in refused:
        assert m["needed"] == 33 >= m["tallied"] == m["matched"]
        assert m["scanned"] == m["lookups"] == 100


def test_a_request_of_256_heights_hashes_nine_times_and_computes_four():
    """The request above on blocks as a provider delivers them (decoded,
    so no set has been hashed in this process): the client's own
    validate_basic of the target and the first verify TO each of the
    pivots compute a root, the five later hashes of those four sets are
    answered by the memo."""
    from tendermint_tpu.libs import safe_codec
    wanted = targets(256, 1)
    t = wanted[0]
    blocks = {h: safe_codec.loads(safe_codec.dumps(lb))
              for h, lb in blocks_for(5, wanted).items()}
    client, _ = make_pair(blocks, 1000)
    seq = trace.last_seq()
    got, asked, calls, _ = ask(client, t)
    assert got is blocks[t] and asked == [t, t - 128, t - 192, t - 64]
    assert len(calls) == 8
    spans = trace.snapshot(since=seq)
    hashed = [r for r in spans if r["name"] == "valset.hash"]
    assert [r["attrs"]["n"] for r in hashed] == [100] * 9
    # a verify hashes the set it verifies TO, once
    by_id = {r["id"]: r for r in spans}
    under = [by_id[r["parent"]]["attrs"].get("height")
             if by_id[r["parent"]]["name"] == "light.verify" else None
             for r in hashed]
    assert under == [None] + [h for h, _ in calls]
    assert [r["attrs"]["memo"] for r in hashed] == [
        False,                  # lb.validate_basic(t)
        True, False, False,     # a -> t, a -> t-128, a -> t-192
        True, True, True,       # t-192 -> t, t-192 -> t-128, t-128 -> t
        False, True]            # t-128 -> t-64, t-64 -> t


@pytest.mark.parametrize("where", ["pivot_trusting_prefix",
                                   "target_light_prefix"])
def test_a_tampered_lane_is_refused_and_the_store_untouched(where):
    seed, (first, target) = 17, targets(256, 2)
    blocks = blocks_for(seed, [first, target])
    client, plain = make_pair(blocks, 1000)
    assert not isinstance(ask(client, first)[0], Exception)
    assert plain.verify_to_height(first, NOW).verdict == reference.OK
    if where == "pivot_trusting_prefix":
        # the first hop: a pivot 64 heights on, checked against the set of
        # the block just saved, whose signers are scattered rows of it
        height = target - 192
        prefix = chain.rows_signed_by(blocks[height].signed_header.commit,
                                blocks[first].validators, True)[:N_TRUST]
        lanes = [prefix[5], prefix[-1]]
    else:
        # the last hop's >2/3 prefix, in rows its trusting check passes by
        height = target
        fresh = [i for i in chain.rows_signed_by(
            blocks[target].signed_header.commit,
            blocks[target - 64].validators, False) if i < N_LIGHT]
        lanes = [fresh[1], fresh[-1]]
    served = dict(blocks)
    served[height] = chain.tampered(blocks[height], lanes)
    client.primary.blocks = plain.provider.blocks = served
    before = list(client.store.db.iterate_prefix(b""))
    written = client.store.bytes_written
    got, asked, calls, saved = ask(client, target)
    res = plain.verify_to_height(target, NOW)
    assert isinstance(got, LightClientError)
    assert f"wrong signature (#{lanes[0]})" in str(got)
    assert res.lane == lanes[0] and res.verdict.startswith("refused")
    assert asked == res.fetched and saved == res.saved == []
    assert calls[-1] == (height, "error")
    assert list(client.store.db.iterate_prefix(b"")) == before
    assert client.store.bytes_written == written
    # and the honest chain is still accepted afterwards
    client.primary.blocks = plain.provider.blocks = blocks
    assert ask(client, target)[0] is blocks[target]
    assert plain.verify_to_height(target, NOW).verdict == reference.OK
    assert client.store.heights() == sorted(plain.store)


def test_a_witness_with_another_header_refuses_both():
    seed, (target,) = 23, targets(64, 1)
    blocks = blocks_for(seed, [target])
    client, plain = make_pair(blocks, 1000)
    forged = dict(blocks)
    forged[target] = block(seed + 1, target)
    client.witnesses[0].blocks = plain.witness.blocks = forged
    # the witness's header does not verify from the common block, so the
    # witness is dropped, and a client left without one trusts nothing
    with pytest.raises(LightClientError, match="no witnesses left"):
        client.verify_light_block_at_height(target, NOW)
    assert plain.verify_to_height(target, NOW).verdict.startswith("refused")
    assert client.store.heights() == sorted(plain.store) == [ANCHOR]


def test_the_sqlite_file_reopened_gives_the_last_target(tmp_path):
    wanted = targets(256, 2)
    blocks = blocks_for(29, wanted)
    db = SQLiteDB(str(tmp_path / "light.db"))
    client, plain = make_pair(blocks, 6, db=db)
    for target in wanted:
        assert ask(client, target)[0] is blocks[target]
        plain.verify_to_height(target, NOW)
    held = client.store.heights()
    assert held == sorted(plain.store) and len(held) == 6
    again = SQLiteDB(str(tmp_path / "light.db"))   # while the first is open
    try:
        store = LightStore(again)
        assert store.heights() == held
        assert store.latest().hash() == blocks[wanted[-1]].hash()
    finally:
        again.close()
        db.close()


def test_a_client_reopened_on_the_file_continues_from_the_columns(tmp_path):
    """Closed and opened again on the same file, a client goes on from a
    block the store's own record holds: the anchor of its next request
    is decoded from columns, and the request is the reference's."""
    from tendermint_tpu.light import record
    first, second = targets(256, 2)
    blocks = blocks_for(43, [first, second])
    path = str(tmp_path / "light.db")
    db = SQLiteDB(path)
    client, plain = make_pair(blocks, 1000, db=db)
    assert ask(client, first)[0] is blocks[first]
    assert plain.verify_to_height(first, NOW).verdict == reference.OK
    db.close()
    db = SQLiteDB(path)
    try:
        assert all(v[:len(record.MAGIC)] == record.MAGIC
                   for _, v in db.iterate_prefix(b"lb/"))
        client, _ = make_pair(blocks, 1000, db=db)   # finds its store
        assert client.primary.asked == []            # full: fetched nothing
        assert client.last_trusted_height() == first
        seq = trace.last_seq()
        got, asked, calls, saved = ask(client, second)
        res = plain.verify_to_height(second, NOW)
        assert res.verdict == reference.OK and got is blocks[second]
        assert asked == res.fetched
        assert calls == [(to, outcome) for _, to, outcome in res.checks]
        assert saved == res.saved and client.store.heights() == res.store
        spans = trace.snapshot(since=seq)
        decoded = [r["attrs"] for r in spans
                   if r["name"] == "light.store.decode"]
        assert decoded == [{"height": first, "record": record.COLUMNS}]
        root = [r for r in spans if r["name"] == "light.client.verify"][-1]
        assert root["attrs"]["anchor"] == first
        # the anchor came back without a root: the request hashed it anew
        assert client.store.get(first).validators._hash_memo is None
    finally:
        db.close()


def test_value_reads_a_request_do_not_grow_with_the_store():
    wanted = targets(64, 6)
    client, _ = make_pair(blocks_for(31, wanted), 1000)
    reads = []
    for target in wanted:
        before = client.store.value_reads
        ask(client, target)
        reads.append(client.store.value_reads - before)
    assert len(set(reads)) == 1 and reads[0] == 1, reads


def test_pruning_size_defaults_to_the_references_and_is_checked():
    blocks = blocks_for(37, [])
    client, _ = make_pair(blocks, 1000)
    plain_default = Client(
        CONFIG["chain_id"], TrustOptions(ANCHOR, blocks[ANCHOR].hash()),
        DictProvider(CONFIG["chain_id"], blocks), [], LightStore(MemDB()))
    assert plain_default.pruning_size == client.pruning_size == 1000
    with pytest.raises(ValueError):
        make_pair(blocks, 0)


def test_the_fetch_plan_is_the_references_fetches():
    """perfbench/traffic/light_client.fetch_plan, which decides the blocks
    a benchmark run signs, against the reference on a real chain."""
    seed, wanted = 41, targets(1000, 1) + [ANCHOR + 1000 + 77]
    blocks = blocks_for(seed, wanted)
    _, plain = make_pair(blocks, 1000)
    plain.check_signatures = False
    for a, t in zip([ANCHOR] + wanted, wanted):
        assert plain.verify_to_height(t, NOW).fetched == \
            chain.fetch_plan(a, t, REACH)
