"""Traffic `catchup`: a node catching up through blocksync.

A request is one blocksync.replay.replay_window call of `window_blocks`
blocks (the reactor's window) behind the installed BlockPipeline and
VerifyScheduler, into file-backed SQLite stores with synchronous=FULL under
GroupCommitDB, as node/node.py lays its stores out.  Closed loop, one
caller (the reactor's sync loop is one thread).

The source chains are made and applied in set-up: the configuration's
`chain_blocks` blocks x its validators x `txs_per_block` kvstore txs of
~`tx_bytes` bytes.
Each chain is replayed once, from genesis, window by window, into fresh
store files: a fresh node.  crypto/batch.verified_sigs sits on this path
(the scheduler's stager drops every triple it finds there), so a second
replay of the same chain would verify nothing; every replay therefore gets
a chain of its own signatures from the seed (the txs carry the chain's
number, so block hashes, sign-bytes and signatures all differ), and after
the window `window_end` fails the run if any request launched fewer lanes
than the signatures of its blocks' LastCommits, each of which a fresh node
has to verify once.  The stores live under TMPDIR, outside the checkout,
and are removed.
"""
from __future__ import annotations

import math
import os
import shutil
import tempfile

import numpy as np
from tendermint_tpu.blocksync.replay import WindowSyncError, replay_window
from tendermint_tpu.crypto import scheduler as vsched

from perfbench import data


def _build_chain(job: dict) -> dict:
    """A worker's share (data.fan_out): chain number `tag` of `n_blocks`
    real blocks, proposed by the set's proposer, committed by every key and
    applied through a BlockExecutor (what a peer would serve).  The builder
    made each LastCommit itself one step earlier, so it marks it verified
    instead of verifying its own signatures again."""
    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.blocksync.replay import block_id_of
    from tendermint_tpu.libs.kvdb import MemDB
    from tendermint_tpu.state.execution import BlockExecutor
    from tendermint_tpu.state.state import state_from_genesis
    from tendermint_tpu.state.store import StateStore
    from tendermint_tpu.types.basic import BlockID, Timestamp
    from tendermint_tpu.types.commit import Commit

    config, tag = job["config"], job["tag"]
    gdoc, by_addr = data.seeded_genesis(job["seed"], config)
    pad = b"v" * max(1, config["tx_bytes"] - 16)
    ex = BlockExecutor(StateStore(MemDB()), KVStoreApplication())
    state = state_from_genesis(gdoc)
    blocks, commits = [], []
    last_commit = Commit(0, 0, BlockID(), [])
    for h in range(1, job["n_blocks"] + 1):
        txs = [b"c%d.%d.%d=%s" % (tag, h, i, pad)
               for i in range(config["txs_per_block"])]
        block = state.make_block(
            h, txs, last_commit, [],
            state.validators.get_proposer().address,
            block_time=Timestamp(1_700_000_000 + h, 0))
        bid, _ = block_id_of(block)
        keys = [by_addr[v.address] for v in state.validators.validators]
        commit = data.signed_commit(config["chain_id"], state.validators,
                                    keys, h, bid)
        blocks.append(block)
        commits.append(commit)
        if h > 1:
            ex.mark_commit_verified(h - 1, last_commit)
        state, _ = ex.apply_block(state, bid, block)
        last_commit = commit
    certs = [b.last_commit for b in blocks[1:]] + [commits[-1]]
    return {"blocks": blocks, "commits": commits, "certs": certs,
            "app_hash": state.app_hash}


def setup(config: dict, params: dict, seed: int, seconds: float) -> dict:
    from tendermint_tpu.state.state import state_from_genesis

    gdoc, _ = data.seeded_genesis(seed, config)
    world = {
        "chain": config["chain_id"], "gdoc": gdoc,
        "vset": state_from_genesis(gdoc).validators,
        "window_blocks": params["window_blocks"],
        "dir": tempfile.mkdtemp(prefix="perfbench-catchup-"),
        "node": None, "dbs": [],
    }
    chain_blocks = config["chain_blocks"]
    per_chain = chain_blocks // params["window_blocks"]
    if per_chain * params["window_blocks"] != chain_blocks:
        raise ValueError("chain_blocks must be whole windows")
    capacity = math.ceil(params["max_requests_per_s"] * seconds)
    n_chains = -(-capacity // per_chain)
    world["windows_per_chain"] = per_chain
    world["capacity"] = n_chains * per_chain
    # the last chain is the check's own: two windows are enough to flip a
    # signature in the second and see the first apply
    *world["chains"], world["check_chain"] = data.fan_out(_build_chain, [
        {"seed": seed, "config": config, "tag": c,
         "n_blocks": chain_blocks if c < n_chains
         else 2 * params["window_blocks"]} for c in range(n_chains + 1)])
    world["close"] = lambda: _close(world)
    world["made"] = (f"{n_chains} chains x {chain_blocks} blocks x "
                     f"{config['validators']} signatures, and one of "
                     f"{2 * params['window_blocks']} blocks for the check")
    return world


def _close_dbs(world):
    for db in world["dbs"]:
        db.close()
    world["dbs"] = []


def _close_node(world):
    _close_dbs(world)
    for name in os.listdir(world["dir"]):
        os.unlink(os.path.join(world["dir"], name))


def _close(world):
    _close_node(world)
    shutil.rmtree(world["dir"], ignore_errors=True)


def _stores(world, tag: str):
    """The node's own layout (node/node.py), durability FULL."""
    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.libs.kvdb import GroupCommitDB, SQLiteDB
    from tendermint_tpu.state.execution import BlockExecutor
    from tendermint_tpu.state.store import StateStore
    from tendermint_tpu.store.block_store import BlockStore

    bdb = GroupCommitDB(SQLiteDB(
        os.path.join(world["dir"], f"{tag}_blocks.db"), synchronous="FULL"))
    sdb = GroupCommitDB(SQLiteDB(
        os.path.join(world["dir"], f"{tag}_state.db"), commit_every=64,
        synchronous="FULL"))
    world["dbs"] += [bdb, sdb]
    return (BlockExecutor(StateStore(sdb), KVStoreApplication()),
            BlockStore(bdb))


def _fresh_node(world, tag: str):
    from tendermint_tpu.state.state import state_from_genesis

    _close_node(world)
    ex, store = _stores(world, tag)
    world["node"] = {"ex": ex, "store": store,
                     "state": state_from_genesis(world["gdoc"])}
    return world["node"]


def _replay(world, node, chain, lo: int, hi: int) -> int:
    """replay_window over chain blocks [lo, hi): returns blocks applied."""
    with world["span"]("replay_window"):
        node["state"], k = replay_window(
            node["ex"], node["store"], node["state"], chain["blocks"][lo:hi],
            chain["certs"][lo:hi], max_window=world["window_blocks"])
    return k


def _synced(node, chain) -> bool:
    n = len(chain["blocks"])
    return (node["state"].last_block_height == n
            and node["store"].height() == n
            and node["state"].app_hash == chain["app_hash"])


def request(world: dict, i: int) -> bool:
    c, w = divmod(i, world["windows_per_chain"])
    chain = world["chains"][c]
    node = _fresh_node(world, f"c{c}") if w == 0 else world["node"]
    nb = world["window_blocks"]
    lanes0 = vsched.running().stats()["lanes"]
    try:
        ok = _replay(world, node, chain, w * nb, (w + 1) * nb) == nb
    except WindowSyncError:
        ok = False
    world["lanes"].append(vsched.running().stats()["lanes"] - lanes0)
    if w == world["windows_per_chain"] - 1:
        ok = ok and _synced(node, chain)
    return ok


def warm(world: dict):
    """Every bucket the coalescing can reach, by direct verify_batch calls
    on the resident set, whether or not a rehearsal replay meets it: from
    the smallest bucket up to the one that holds what the stage worker can
    have in flight (pipeline depth + the block applying + the one being
    staged, each its certifier's >2/3 prefix plus its own full LastCommit),
    capped by the scheduler's max_batch.  A traced run slows the host, the
    coalescing shifts, and a bucket first met inside the window is a
    compile of 6-12 s there (what took PR 22 down)."""
    from tendermint_tpu.ops import ed25519 as edops

    cfg = world["node_config"]
    nval = world["vset"].size()
    per_block = (2 * nval) // 3 + 1 + nval
    most = min(cfg.verify_scheduler.max_batch,
               (cfg.block_pipeline.depth + 2) * per_block)
    pubs = [v.pub_key.bytes() for v in world["vset"].validators]
    nb = edops.bucket_size(1)
    buckets = []
    while True:
        buckets.append(nb)
        edops.verify_batch([pubs[i % nval] for i in range(nb)],
                           [b"perfbench-warm"] * nb, [b"\x01" * 64] * nb)
        if nb >= edops.bucket_size(most):
            break
        nb *= 2
    world["warmed_buckets"] = buckets


def check(world: dict):
    """An honest replay of the check's chain: final height and app hash
    equal the source chain's, and the height reads back from the reopened
    stores.  Then the same chain with one flipped LastCommit signature:
    WindowSyncError at its height.  The window's commits hold to the
    per-signature OpenSSL oracle, and so does the flipped lane."""
    from tendermint_tpu.state import pipeline as blockpipe
    from tendermint_tpu.types.block import Block

    chain = world["check_chain"]
    vset = world["vset"]
    nb, n = world["window_blocks"], len(chain["blocks"])
    bad = []
    node = _fresh_node(world, "check")
    applied = sum(_replay(world, node, chain, lo, lo + nb)
                  for lo in range(0, n, nb))
    if applied != n or not _synced(node, chain):
        bad.append(f"honest replay: {applied} of {n} blocks, height "
                   f"{node['state'].last_block_height}, app hash "
                   f"{'equal' if node['state'].app_hash == chain['app_hash'] else 'differs'}")
    _close_dbs(world)
    _, store = _stores(world, "check")         # the same files, reopened
    if store.height() != n:
        bad.append(f"reopened block store is at {store.height()}, not {n}")
    for c in chain["commits"][:nb]:
        if not data.oracle(*data.commit_triples(world["chain"], vset,
                                                c)).all():
            bad.append(f"the oracle rejects an honest commit at {c.height}")
    # a lying peer: one signature of block h's LastCommit flipped, past the
    # >2/3 prefix that certifies block h-1 (so h-1 applies and h fails)
    h = nb + nb // 2
    lane = vset.size() - 1
    orig = chain["blocks"][h - 1]
    tampered = data.tampered_commit(orig.last_commit, [lane])
    want = data.oracle(*data.commit_triples(world["chain"], vset, tampered))
    if sorted(np.flatnonzero(~want)) != [lane]:
        bad.append(f"tampered LastCommit: the oracle rejects "
                   f"{sorted(np.flatnonzero(~want))}, tampered [{lane}]")
    lied = dict(chain, blocks=list(chain["blocks"]))
    lied["blocks"][h - 1] = Block(header=orig.header, data=orig.data,
                                  evidence=orig.evidence,
                                  last_commit=tampered)
    pipe = blockpipe.running()
    degraded0 = pipe.windows_degraded
    node = _fresh_node(world, "lied")
    err = data.raises(lambda: [_replay(world, node, lied, lo, lo + nb)
                               for lo in range(0, n, nb)], WindowSyncError)
    if err is None or err.height != h:
        bad.append(f"tampered LastCommit signature at height {h}: {err!r}")
    elif pipe.windows_degraded != degraded0 + 1:
        bad.append("the device batch did not reject the tampered signature "
                   "(the window never degraded to the strict path)")
    _close_node(world)
    return bad


def window_begin(world: dict):
    world["lanes"] = []


def window_end(world: dict, run: dict):
    """A fresh node has to verify every LastCommit signature of the blocks
    it replays at least once (the first block of a chain has none)."""
    nval, nb = world["vset"].size(), world["window_blocks"]
    short = [(i, lanes) for i, lanes in enumerate(world["lanes"])
             if lanes < nval * (nb - (i % world["windows_per_chain"] == 0))]
    return [] if not short else [
        f"requests launched fewer lanes than their blocks' LastCommits "
        f"hold: {short[:5]} (a cache served a fresh node)"]
