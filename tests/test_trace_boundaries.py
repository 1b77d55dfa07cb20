"""The spans at the entry points (docs/adr/adr-011-flight-recorder.md):
one request is one connected tree under its root span, across the
pipeline's and the scheduler's threads, and no boundary is so fine that
a span fires per vote or per signature.

The trees are read as the benchmark's per-layer readers read them
(perfbench/progspans.py): from trace.snapshot(), by name and parent.
Everything here runs on the host lanes at 40-1,000 validators: which
lane verified is not what a tree's shape depends on.
"""
from __future__ import annotations

from fractions import Fraction

import pytest

from helpers import Node, build_chain, make_genesis
from tendermint_tpu.abci.kvstore import KVStoreApplication
from tendermint_tpu.blocksync.replay import replay_window
from tendermint_tpu.consensus.round_types import VoteMessage
from tendermint_tpu.crypto import scheduler as vsched
from tendermint_tpu.libs import trace
from tendermint_tpu.libs.kvdb import GroupCommitDB, MemDB
from tendermint_tpu.light import verifier
from tendermint_tpu.state import pipeline
from tendermint_tpu.state.execution import BlockExecutor
from tendermint_tpu.state.state import state_from_genesis
from tendermint_tpu.state.store import StateStore
from tendermint_tpu.store.block_store import BlockStore
from tendermint_tpu.types.basic import (BlockID, PartSetHeader,
                                        SignedMsgType, Timestamp)
from tendermint_tpu.types.light_block import SignedHeader
from tendermint_tpu.types.validator_set import ValidatorSet
from tendermint_tpu.types.vote import Vote
from tendermint_tpu.types.vote_set import VoteSet

PERIOD = 3600.0 * 24 * 14
NOW = Timestamp(1700005000, 0)


@pytest.fixture
def recorder():
    """The process-wide recorder, on and empty; off and empty after (the
    neighbours' convention, tests/test_trace.py)."""
    trace.enable()
    trace.reset()
    yield trace
    trace.disable()
    trace.reset()


def _tree(records):
    """(the roots, {span id: its children}) of a snapshot."""
    ids = {r["id"] for r in records}
    roots = [r for r in records if r["parent"] not in ids]
    children = {}
    for r in records:
        children.setdefault(r["parent"], []).append(r)
    return roots, children


def _names(records, name):
    return [r for r in records if r["name"] == name]


# ---------------------------------------------------------------------------
# the light client's request
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("adjacent", [True, False])
def test_light_verify_is_one_tree_with_the_lumps_split(recorder, adjacent):
    gdoc, privs = make_genesis(40)
    blocks, commits, states = build_chain(gdoc, privs, 12)
    trusted, target = (3, 4) if adjacent else (3, 11)
    sh = {h: SignedHeader(blocks[h - 1].header, commits[h - 1])
          for h in (trusted, target)}
    recorder.reset()
    verifier.verify(sh[trusted], states[trusted - 1].validators, sh[target],
                    states[target - 1].validators, PERIOD, NOW, 10.0,
                    Fraction(1, 3))
    spans = recorder.snapshot()
    roots, _ = _tree(spans)
    assert [r["name"] for r in roots] == ["light.verify"]
    assert roots[0]["attrs"] == {"height": target, "adjacent": adjacent,
                                 "outcome": "ok"}
    got = {r["name"] for r in spans}
    assert {"valset.hash", "commit.validate_basic", "commit.prefix",
            "commit.collect"} <= got
    (hashed,) = _names(spans, "valset.hash")
    assert hashed["attrs"]["n"] == 40
    (basic,) = _names(spans, "commit.validate_basic")
    assert basic["attrs"]["sigs"] == 40
    # the >2/3 prefix of 40 equal powers is 27 signatures
    collected = _names(spans, "commit.collect")
    assert collected[-1]["attrs"]["n"] == 27
    assert _names(spans, "commit.prefix")[-1]["attrs"]["prefix"] == 27
    matches = _names(spans, "commit.match")
    if adjacent:
        assert not matches and len(collected) == 1
    else:
        # the trusting check: 1/3 of 400 is crossed by 14 signatures,
        # each found by address, in one span for the whole loop
        (m,) = matches
        assert m["attrs"]["matched"] == 14 == collected[0]["attrs"]["n"]
        assert m["attrs"]["lookups"] >= m["attrs"]["matched"]
        assert m["attrs"]["scanned"] >= m["attrs"]["lookups"]
        assert len(collected) == 2


def test_one_hash_span_a_verify_says_whether_the_memo_answered(recorder):
    """A set's first hash computes (`memo` false), every later one is
    answered by the memo on its validators list, and either way a verify
    records exactly one `valset.hash` span (light.hash_ms sums them,
    light.hash_memo_share reads the attribute)."""
    gdoc, privs = make_genesis(40)
    blocks, commits, states = build_chain(gdoc, privs, 5)
    sh = {h: SignedHeader(blocks[h - 1].header, commits[h - 1])
          for h in (3, 4)}
    # as a provider delivers it: decoded, so never hashed in this process
    delivered = ValidatorSet.from_proto(states[3].validators.proto())
    for memo in (False, True, True):
        recorder.reset()
        verifier.verify(sh[3], states[2].validators, sh[4], delivered,
                        PERIOD, NOW, 10.0, Fraction(1, 3))
        (hashed,) = _names(recorder.snapshot(), "valset.hash")
        assert hashed["attrs"] == {"n": 40, "memo": memo}


def test_trusting_check_at_1000_validators_records_a_dozen_spans(recorder):
    """A span per signature or per lookup cannot come back unnoticed."""
    gdoc, privs = make_genesis(1000, power=1)
    _, commits, states = build_chain(gdoc, privs, 1)
    vals = states[0].validators
    recorder.reset()
    vals.verify_commit_light_trusting(gdoc.chain_id, commits[0],
                                      Fraction(1, 3))
    spans = recorder.snapshot()
    assert len(spans) <= 12, sorted(r["name"] for r in spans)
    (m,) = _names(spans, "commit.match")
    counts = {"scanned": 334, "matched": 334, "lookups": 334}
    assert m["attrs"] == {**counts, "index_built": True}
    # the index is the set's: a second check on the same set finds it
    vals.verify_commit_light_trusting(gdoc.chain_id, commits[0],
                                      Fraction(1, 3))
    assert _names(recorder.snapshot(), "commit.match")[-1]["attrs"] == \
        {**counts, "index_built": False}


class _CountedAddress(bytes):
    """An address that counts how often it is compared."""
    compares = 0
    __hash__ = bytes.__hash__

    def __eq__(self, other):
        _CountedAddress.compares += 1
        return bytes.__eq__(self, other)


@pytest.mark.parametrize("index", ["cold", "built"])
def test_trusting_check_at_1000_validators_is_linear_in_its_lookups(
        recorder, monkeypatch, index):
    """Counts, not times: the match loop compares about one address a
    lookup (a scan from the front compares lookups x validators / 2 of
    them: 55,945 here) and copies no validator, whether or not it has to
    build the index first."""
    from tendermint_tpu.types.commit import Commit, CommitSig
    from tendermint_tpu.types.validator import Validator

    gdoc, privs = make_genesis(1000, power=1)
    _, commits, states = build_chain(gdoc, privs, 1)
    vals, c = states[0].validators, commits[0]
    counted = Commit(c.height, c.round, c.block_id, [
        CommitSig(s.block_id_flag, _CountedAddress(s.validator_address),
                  s.timestamp, s.signature) for s in c.signatures])
    if index == "built":
        assert vals.has_address(vals.validators[0].address)
    copies = []
    copy = Validator.copy
    monkeypatch.setattr(Validator, "copy",
                        lambda v: copies.append(v) or copy(v))
    recorder.reset()
    _CountedAddress.compares = 0
    vals.verify_commit_light_trusting(gdoc.chain_id, counted,
                                      Fraction(1, 3))
    compares = _CountedAddress.compares
    (m,) = _names(recorder.snapshot(), "commit.match")
    assert m["attrs"] == {"scanned": 334, "matched": 334, "lookups": 334,
                          "index_built": index == "cold"}
    assert copies == []
    assert 334 <= compares <= 2 * 334


# ---------------------------------------------------------------------------
# the catching-up node's request
# ---------------------------------------------------------------------------

def test_pipelined_window_is_one_tree_across_three_threads(recorder):
    gdoc, privs = make_genesis(4)
    blocks, commits, _ = build_chain(gdoc, privs, 4)
    ex = BlockExecutor(StateStore(GroupCommitDB(MemDB())),
                       KVStoreApplication())
    store = BlockStore(GroupCommitDB(MemDB()))
    pipeline.set_config(enable=True, depth=3, group_commit_heights=2)
    try:
        recorder.reset()
        state, n = replay_window(ex, store, state_from_genesis(gdoc),
                                 blocks, commits, max_window=4)
    finally:
        pipeline.set_config(enable=False)
    assert n == 4 and state.last_block_height == 4
    spans = recorder.snapshot()
    roots, children = _tree(spans)
    assert [r["name"] for r in roots] == ["blocksync.replay_window"]
    root = roots[0]
    assert root["attrs"] == {"blocks": 4, "applied": 4,
                             "path": "pipelined"}
    under_root = {}
    for r in children[root["id"]]:
        under_root.setdefault(r["name"], []).append(r)
    for name in ("pipeline.stage", "pipeline.wait_staged",
                 "pipeline.apply"):
        assert len(under_root[name]) == 4, name
    assert len(under_root["pipeline.commit"]) >= 1
    assert len(under_root["pipeline.drain"]) == 1
    assert len(_names(spans, "store.save_block")) == 4
    apply_ids = {r["id"] for r in under_root["pipeline.apply"]}
    assert {r["parent"] for r in _names(spans, "store.save_block")} \
        <= apply_ids
    threads = {r["name"]: r["tname"] for r in spans}
    assert len({threads["pipeline.apply"], threads["pipeline.stage"],
                threads["pipeline.commit"]}) == 3
    # the drain waits for the writer's last group: it ends after it
    drain = under_root["pipeline.drain"][0]
    last_commit = max(r["ts_ns"] + r["dur_ns"]
                      for r in under_root["pipeline.commit"])
    assert drain["ts_ns"] + drain["dur_ns"] >= last_commit
    assert all(r["cpu_ns"] <= r["dur_ns"] for r in spans)


def test_unpipelined_window_names_its_path(recorder):
    gdoc, privs = make_genesis(4)
    blocks, commits, _ = build_chain(gdoc, privs, 3)
    ex = BlockExecutor(StateStore(MemDB()), KVStoreApplication())
    state, n = replay_window(ex, BlockStore(MemDB()),
                             state_from_genesis(gdoc), blocks, commits)
    assert n == 3
    (root,) = _names(recorder.snapshot(), "blocksync.replay_window")
    assert root["attrs"]["path"] == "coalesced"
    assert root["attrs"]["applied"] == 3
    state, n = replay_window(ex, BlockStore(MemDB()),
                             state_from_genesis(gdoc), blocks[:1],
                             commits[:1])
    root = _names(recorder.snapshot(), "blocksync.replay_window")[-1]
    assert root["attrs"]["path"] == "strict"


@pytest.mark.parametrize("pipelined", [False, True])
def test_a_replayed_window_computes_each_sets_root_once(recorder, pipelined):
    """validate_block hashes state.validators and state.next_validators in
    every block and the window's stability check both once more; a set
    that no block changes is computed once (the state's two set objects
    at genesis), because update_state's per-block copies carry the root."""
    gdoc, privs = make_genesis(4)
    blocks, commits, _ = build_chain(gdoc, privs, 6)
    ex = BlockExecutor(StateStore(MemDB()), KVStoreApplication())
    pipeline.set_config(enable=pipelined, depth=3, group_commit_heights=2)
    try:
        recorder.reset()
        state, n = replay_window(ex, BlockStore(MemDB()),
                                 state_from_genesis(gdoc), blocks, commits,
                                 max_window=6)
    finally:
        pipeline.set_config(enable=False)
    assert n == 6
    memos = [r["attrs"]["memo"]
             for r in _names(recorder.snapshot(), "valset.hash")]
    assert len(memos) >= 2 * 6 + 2
    assert memos.count(False) == 2
    # and the state handed back keeps it: the next window computes nothing
    assert state.validators._memoised_root() == state.validators.hash()
    assert state.next_validators._memoised_root() is not None


# ---------------------------------------------------------------------------
# the validator's height: a span per batch, never per vote
# ---------------------------------------------------------------------------

def _signed_votes(gdoc, privs, vals, vtype, height, bid):
    by_addr = {p.pub_key().address(): p for p in privs}
    votes = []
    for idx, val in enumerate(vals.validators):
        v = Vote(type=vtype, height=height, round=0, block_id=bid,
                 timestamp=Timestamp(1700000100, idx),
                 validator_address=val.address, validator_index=idx)
        v.signature = by_addr[val.address].sign(v.sign_bytes(gdoc.chain_id))
        votes.append(v)
    return votes


def test_a_height_of_150_validators_records_at_most_40_spans(recorder):
    """One _preverify_votes of 150 precommits through the scheduler,
    150 add_vote, the commit they make through verify_commit: spans per
    batch, launch and window only, and the scheduler's part hangs
    under consensus.preverify although it ran on two other threads."""
    gdoc, privs = make_genesis(150)
    cs = Node(gdoc, privs[0]).cs
    vals, height = cs.state.validators, cs.rs.height
    bid = BlockID(hash=bytes([5] * 32),
                  part_set_header=PartSetHeader(1, bytes([6] * 32)))
    votes = _signed_votes(gdoc, privs, vals, SignedMsgType.PRECOMMIT,
                          height, bid)
    sched = vsched.install(vsched.VerifyScheduler(window_s=0.002))
    sched.start()
    try:
        recorder.reset()
        cs._preverify_votes([(VoteMessage(v), "peer") for v in votes])
        vs = VoteSet(gdoc.chain_id, height, 0, SignedMsgType.PRECOMMIT,
                     vals)
        for v in votes:
            vs.add_vote(v)
        commit = vs.make_commit()
        vals.verify_commit(gdoc.chain_id, commit.block_id, height, commit)
    finally:
        sched.stop()
        vsched.uninstall(sched)
    spans = recorder.snapshot()
    assert len(spans) <= 40, sorted(r["name"] for r in spans)
    roots, children = _tree(spans)
    (pre,) = _names(spans, "consensus.preverify")
    assert pre in roots
    under = {r["name"] for r in children[pre["id"]]}
    assert {"sched.submit", "sched.coalesce", "sched.resolve"} <= under
    # (verify_commit's own window comes after, under no open span)
    coalesce = _names(spans, "sched.coalesce")[0]
    assert coalesce["parent"] == pre["id"]
    (launch,) = children[coalesce["id"]]
    assert launch["name"] == "sched.launch"
    assert len({pre["tid"], coalesce["tid"], launch["tid"]}) == 3
    (collect,) = _names(spans, "commit.collect")
    assert collect["attrs"]["n"] == 150
