"""Traffic `live_heights`: one validator's verification share of a height.

A request is 150 (the set's size) prevotes delivered in one burst to
ConsensusState._preverify_votes (CONSENSUS class, through the scheduler)
and applied one by one with VoteSet.add_vote; the precommits likewise; then
the commit those precommits make (VoteSet.make_commit) checked with
ValidatorSet.verify_commit, which is what validating the next block's
LastCommit costs.  Closed loop, one caller, no message delay injected: one
node's processor time per height, not a network.

The harness does not run the state machine, and _preverify_votes takes only
votes of the state's current height with a round below 4,096, so request i
is round i of that height: new sign-bytes and signatures every request (a
repeated vote would be a SigCache hit and measure the cache), a VoteSet of
its own each time.  All votes are signed in set-up; a window that outruns
them fails loudly (perfbench/run.py), it does not cycle.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np
from tendermint_tpu.types.basic import SignedMsgType
from tendermint_tpu.types.validator_set import CommitVerifyError
from tendermint_tpu.types.vote_set import VoteSet, VoteSetError

from perfbench import data

SPARE = 5        # rounds kept back for warm-up (3) and the check (2)
MAX_ROUNDS = 4096
VOTE_TYPES = (SignedMsgType.PREVOTE, SignedMsgType.PRECOMMIT)


def _genesis(seed: int, config: dict):
    """(state at genesis, its validator set, the keys in the set's order)."""
    from tendermint_tpu.state.state import state_from_genesis

    gdoc, by_addr = data.seeded_genesis(seed, config)
    state = state_from_genesis(gdoc)
    return state, state.validators, [by_addr[v.address]
                                     for v in state.validators.validators]


def _sign_rounds(job: dict) -> list:
    """A worker's share (data.fan_out): for each round of the job, the
    signatures of its prevotes and of its precommits."""
    config = job["config"]
    _, vset, ordered = _genesis(job["seed"], config)
    return [[[v.signature for v in data.signed_votes(
        config["chain_id"], vset, ordered, vtype, job["height"], r,
        data.block_id(b"live/%d" % r))] for vtype in VOTE_TYPES]
        for r in job["rounds"]]


def setup(config: dict, params: dict, seed: int, seconds: float) -> dict:
    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.consensus.config import ConsensusConfig
    from tendermint_tpu.consensus.round_types import VoteMessage
    from tendermint_tpu.consensus.state import ConsensusState
    from tendermint_tpu.libs.kvdb import MemDB
    from tendermint_tpu.state.execution import BlockExecutor
    from tendermint_tpu.state.store import StateStore
    from tendermint_tpu.store.block_store import BlockStore

    chain = config["chain_id"]
    state, vset, ordered = _genesis(seed, config)
    cs = ConsensusState(ConsensusConfig(), state,
                        BlockExecutor(StateStore(MemDB()),
                                      KVStoreApplication()),
                        BlockStore(MemDB()), name="perfbench")
    height = cs.rs.height
    capacity = math.ceil(params["max_requests_per_s"] * seconds)
    if capacity + SPARE > MAX_ROUNDS:
        raise ValueError(f"{capacity} requests need more rounds than "
                         f"_preverify_votes takes ({MAX_ROUNDS})")
    rounds = list(range(capacity + SPARE))
    share = 32
    sigs = [row for part in data.fan_out(_sign_rounds, [
        {"seed": seed, "config": config, "height": height,
         "rounds": rounds[a:a + share]}
        for a in range(0, len(rounds), share)]) for row in part]
    pool = []
    for r in rounds:
        bid = data.block_id(b"live/%d" % r)
        row = []
        for vtype, vote_sigs in zip(VOTE_TYPES, sigs[r]):
            votes = data.signed_votes(chain, vset, ordered, vtype, height,
                                      r, bid, sigs=vote_sigs)
            row.append(([(VoteMessage(v), "peer") for v in votes], votes))
        pool.append(row)
    return {"chain": chain, "cs": cs, "vset": vset, "height": height,
            "pool": pool, "capacity": capacity, "keys_in_order": ordered,
            "made": f"{len(pool)} rounds x {2 * vset.size()} signed votes"}


def _apply(world, vtype, round_, votes):
    """The serial apply: returns (the VoteSet, indices it rejected)."""
    vs = VoteSet(world["chain"], world["height"], round_, vtype,
                 world["vset"])
    rejected = []
    for v in votes:
        try:
            vs.add_vote(v)
        except VoteSetError:
            rejected.append(v.validator_index)
    return vs, rejected


def _height(world, round_, row):
    """One request's work; returns (prevote set, precommit set, rejected
    indices, the commit error or None)."""
    span, cs = world["span"], world["cs"]
    sets, rejected = [], []
    for vtype, (msgs, votes) in zip(VOTE_TYPES, row):
        with span("preverify"):
            cs._preverify_votes(msgs)
        with span("apply"):
            vs, bad = _apply(world, vtype, round_, votes)
        sets.append(vs)
        rejected.extend(bad)
    err = None
    with span("verify_commit"):
        try:
            commit = sets[1].make_commit()
            world["vset"].verify_commit(world["chain"], commit.block_id,
                                        world["height"], commit)
        except (VoteSetError, CommitVerifyError) as e:
            err = e
    return sets[0], sets[1], rejected, err


def request(world: dict, i: int) -> bool:
    pv, pc, rejected, err = _height(world, i, world["pool"][i])
    return (not rejected and err is None and pv.has_two_thirds_majority()
            and pc.has_two_thirds_majority())


def warm(world: dict):
    """The cell reaches one bucket (the set's size, padded), by whichever
    route verify_batch picks after bring-up, from the scheduler and from
    verify_commit: three whole requests meet both."""
    base = world["capacity"]
    for k in range(3):
        if not request(world, base + k):
            raise RuntimeError(f"warm-up request {k} was not accepted")


def check(world: dict):
    """One honest height (every pre-verified vote must then be a SigCache
    hit on apply), one with tampered lanes: the rejected votes and the
    commit's bitmap must be the per-signature OpenSSL oracle's."""
    from tendermint_tpu.consensus.round_types import VoteMessage
    from tendermint_tpu.crypto import batch as cbatch

    chain, vset = world["chain"], world["vset"]
    n = vset.size()
    pubs = [v.pub_key.bytes() for v in vset.validators]
    base = world["capacity"] + 3
    bad = []

    def votes_oracle(votes):
        return data.oracle(pubs, [v.sign_bytes(chain) for v in votes],
                           [v.signature for v in votes])

    hits0 = cbatch.verified_sigs.hits
    if not request(world, base):
        bad.append("an honest height was not accepted")
    hits = cbatch.verified_sigs.hits - hits0
    if hits != 2 * n:
        bad.append(f"{hits} SigCache hits on apply for {2 * n} pre-verified "
                   f"votes")
    for _, votes in world["pool"][base]:
        if not votes_oracle(votes).all():
            bad.append("the oracle rejects an honest vote")
    lanes = sorted({3, n // 2, n - 1})
    row = []
    for _, votes in world["pool"][base + 1]:
        votes = [dataclasses.replace(v, signature=data.flip(v.signature))
                 if v.validator_index in lanes else v for v in votes]
        row.append(([(VoteMessage(v), "peer") for v in votes], votes))
    _, pc, rejected, err = _height(world, base + 1, row)
    want = [int(i) for vs in row
            for i in np.flatnonzero(~votes_oracle(vs[1]))]
    if rejected != want or want != lanes + lanes:
        bad.append(f"tampered votes: rejected {rejected}, the oracle "
                   f"rejects {want}, tampered {lanes}")
    if err is not None:
        bad.append(f"the commit of the {n - len(lanes)} honest precommits "
                   f"did not verify: {err!r}")
    # the commit path's own attribution: flipped lanes in a whole commit
    commit = data.signed_commit(chain, vset, world["keys_in_order"], 9,
                                data.block_id(b"check"))
    tampered = data.tampered_commit(commit, lanes)
    err = data.raises(lambda: vset.verify_commit(
        chain, tampered.block_id, tampered.height, tampered),
        CommitVerifyError)
    if err is None or f"(#{lanes[0]})" not in str(err):
        bad.append(f"tampered commit: {err!r}, expected wrong signature "
                   f"#{lanes[0]}")
    bits = data.bulk_bitmap(chain, vset, tampered)
    want_bits = data.oracle(*data.commit_triples(chain, vset, tampered))
    if not np.array_equal(bits, want_bits) or \
            sorted(np.flatnonzero(~bits)) != lanes:
        bad.append(f"tampered commit bitmap rejects "
                   f"{sorted(np.flatnonzero(~bits))}, the oracle "
                   f"{sorted(np.flatnonzero(~want_bits))}")
    return bad


def window_begin(world: dict):
    from tendermint_tpu.crypto import batch as cbatch
    world["hits_before_window"] = cbatch.verified_sigs.hits


def window_end(world: dict, run: dict):
    """Every pre-verified vote of the window must have been a SigCache hit
    on apply: a pre-verify that silently fell out would leave add_vote to
    verify on the host, one by one, with the right verdicts."""
    from tendermint_tpu.crypto import batch as cbatch

    n = 2 * world["vset"].size() * len(run["requests"])
    hits = cbatch.verified_sigs.hits - world["hits_before_window"]
    return [] if hits == n else [
        f"{hits} SigCache hits on apply for {n} pre-verified votes"]
