"""Traffic `light_client`: the light client proper, updating its store.

A request is one light.client.Client.verify_light_block_at_height(last +
`update_gap_heights`) by ONE Client over ONE LightStore for the whole run:
the store's reads, the bisection (refused skips and hops, each a
light.verifier.verify), the witness's cross-check, the trace's saves and the
prune.  Closed loop, one caller: the client's verify holds its lock.

The chain is the configuration's: `validators` keys of power
`voting_power`, of which `rotation_per_block` leave and as many join at
every height.  The set of height h is keys [r(h-1), r(h-1) + n) of one key
sequence made from the seed (`light_block_at`), so a header can be trusted
from another only while enough keys of the older set still sign: with
10,000 validators, 100 a block and trust level 1/3, 66 heights.  Headers
are synthetic as in `light_headers` (built directly; a commit signs its
header's own hash; `last_block_id` is not a link, which skipping
verification never follows), except that `validators_hash` and
`next_validators_hash` are the hashes of the sets of h and h + 1.

Set-up: the anchor block (height `pruning_size`) first and in this process;
then the Client on a SQLiteDB file opened exactly as `cmd light` opens
`light.db`, which takes the anchor from the primary as a first start does;
then the store filled to `pruning_size` with the anchor's own bytes under
the heights below it (the client counts, scans and prunes those rows and
never decodes one: honest blocks there would be 10 M signatures of set-up);
then, in workers, every block the requests will fetch (`fetch_plan`).  The
Client is built before the fan-out signs anything, so a program whose
Client takes no `pruning_size` fails within seconds.

The primary is an in-memory DictProvider of decoded blocks that records
what it is asked for; the witness another over the same blocks.  Requests
are consecutive: the warm-up's, the check's, then the window's.  What
decides `correct` is in `request`, `check` and `window_end`; the plain
reference is perfbench/reference/light_client.py.
"""
from __future__ import annotations

import hashlib
import math
import os
import shutil
import sqlite3
import tempfile
import time
from fractions import Fraction

import numpy as np
from tendermint_tpu.libs import safe_codec
from tendermint_tpu.light import store as lstore
from tendermint_tpu.light.client import Client, TrustOptions
from tendermint_tpu.light.detector import Divergence, LightClientError
from tendermint_tpu.light.provider import DictProvider, ProviderError
from tendermint_tpu.light.store import LightStore
from tendermint_tpu.types.light_block import LightValidationError

from perfbench import data
from perfbench.reference import light_client as reference

T0 = 1_700_000_000
WARM_REQUESTS = 2      # the first meets both buckets and the first prune;
#                        the second starts from a block this client saved
CHECK_REQUESTS = 1     # the honest one; the tampered two are refused and
#                        leave the next target for the window
FILL_ROWS_A_BATCH = 50
REFUSALS = (LightClientError, ProviderError, LightValidationError,
            Divergence, ValueError)


# ---------------------------------------------------------------------------
# the chain
# ---------------------------------------------------------------------------

def _keys(seed: int, config: dict, height: int) -> list:
    r, n = config["rotation_per_block"], config["validators"]
    tag = config["name"].encode()
    return [data.Key(hashlib.sha256(
        b"perfbench/%d/%s/%d" % (seed, tag, i)).digest())
        for i in range(r * (height - 1), r * (height - 1) + n)]


def light_block_at(seed: int, config: dict, height: int):
    """The chain's light block at `height`, every validator signing."""
    from tendermint_tpu.types.basic import BlockID, PartSetHeader, Timestamp
    from tendermint_tpu.types.block import Header
    from tendermint_tpu.types.light_block import LightBlock, SignedHeader

    def h32(tag):
        return hashlib.sha256(b"%s/%d" % (tag, height)).digest()

    chain, power = config["chain_id"], config["voting_power"]
    vset, ordered = data.make_valset(_keys(seed, config, height), power)
    next_vset, _ = data.make_valset(_keys(seed, config, height + 1), power)
    header = Header(
        chain_id=chain, height=height, time=Timestamp(T0 + height, 0),
        last_block_id=BlockID(h32(b"prev"), PartSetHeader(1, h32(b"lparts"))),
        last_commit_hash=h32(b"lc"), data_hash=h32(b"data"),
        validators_hash=vset.hash(), next_validators_hash=next_vset.hash(),
        consensus_hash=h32(b"cons"), app_hash=h32(b"app"),
        last_results_hash=h32(b"res"), evidence_hash=h32(b"ev"),
        proposer_address=vset.validators[0].address)
    bid = BlockID(header.hash(), PartSetHeader(1, h32(b"parts")))
    return LightBlock(
        SignedHeader(header, data.signed_commit(chain, vset, ordered,
                                                height, bid)), vset)


def _block_job(job: dict) -> bytes:
    """A worker's share (data.fan_out): one height, in the store's own
    codec (a tenth of what pickling the objects costs the parent)."""
    return safe_codec.dumps(light_block_at(job["seed"], job["config"],
                                           job["height"]))


def reach(config: dict) -> int:
    """The farthest a header can be trusted from: the most heights after
    which the keys still in the set hold over trust_level of its power."""
    n, r = config["validators"], config["rotation_per_block"]
    num, den = config["trust_level"]
    return (n - n * num // den - 1) // r


def fetch_plan(anchor: int, target: int, reach_: int) -> list:
    """The heights one request fetches, in order, on a chain where a skip
    is refused exactly when it is longer than `reach_`: the bisection on
    heights alone, to know which blocks to sign.  A plan that is wrong
    leaves the provider without a block the client asks for, and the
    request fails."""
    fetched, goals, d = [target], [target], 0
    while goals:
        if goals[d] - anchor > reach_:
            if d == len(goals) - 1:
                goals.append(anchor + (goals[d] - anchor) // 2)
                fetched.append(goals[-1])
            d += 1
        else:
            anchor, goals, d = goals[d], goals[:d], 0
    return fetched


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

class RecordingProvider(DictProvider):
    """Records the heights it is asked for; serves `overlay` (a check's
    tampered blocks) before its own."""

    def __init__(self, chain_id: str, blocks=None):
        super().__init__(chain_id, blocks)
        self.asked, self.overlay = [], {}

    def light_block(self, height: int):
        self.asked.append(height)
        return self.overlay.get(height) or super().light_block(height)


def setup(config: dict, params: dict, seed: int, seconds: float) -> dict:
    from tendermint_tpu.types.basic import Timestamp

    chain, gap = config["chain_id"], params["update_gap_heights"]
    keep = config["pruning_size"]
    anchor_h = keep               # so that the rows below it have heights
    capacity = math.ceil(params["max_requests_per_s"] * seconds)
    n_requests = WARM_REQUESTS + CHECK_REQUESTS + capacity
    targets = [anchor_h + gap * (k + 1) for k in range(n_requests)]
    world = {
        "chain": chain, "config": config, "targets": targets, "next": 0,
        "capacity": capacity, "log": [],
        "now": Timestamp(T0 + targets[-1] + 5, 0),
        "dir": tempfile.mkdtemp(prefix="perfbench-light-client-"),
    }
    world["close"] = lambda: _close(world)
    try:
        _make(world, seed, anchor_h, gap)
    except BaseException:
        _close(world)       # the runner closes only a world it was given
        raise
    return world


def _make(world: dict, seed: int, anchor_h: int, gap: int):
    from tendermint_tpu.libs.kvdb import SQLiteDB

    chain, config, targets = world["chain"], world["config"], world["targets"]
    keep = config["pruning_size"]
    num, den = config["trust_level"]
    clock = [time.perf_counter()]
    primary = world["primary"] = RecordingProvider(chain)
    anchor = light_block_at(seed, config, anchor_h)
    primary.add(anchor)
    world["path"] = os.path.join(world["dir"], "light.db")
    db = world["db"] = SQLiteDB(world["path"])
    store = world["store"] = LightStore(db)
    witness = DictProvider(chain)
    witness.blocks = primary.blocks
    world["client"] = Client(
        chain, TrustOptions(anchor_h, anchor.hash(),
                            float(config["trusting_period_s"])),
        primary, [witness], store, trust_level=Fraction(num, den),
        max_clock_drift_s=float(config["max_clock_drift_s"]),
        pruning_size=keep)
    clock.append(time.perf_counter())
    _fill(db, anchor_h)
    clock.append(time.perf_counter())
    plans = [fetch_plan(a, t, reach(config))
             for a, t in zip([anchor_h] + targets, targets)]
    heights = sorted({h for plan in plans for h in plan})
    for h, raw in zip(heights, data.fan_out(_block_job, [
            {"seed": seed, "config": config, "height": h}
            for h in heights])):
        primary.add(safe_codec.loads(raw))
    clock.append(time.perf_counter())
    world["hashes"] = {h: lb.hash() for h, lb in primary.blocks.items()}
    # the reference's two faces over ONE dict store, fed by a provider of
    # their own that serves what the primary serves: `plain` checks every
    # signature by the oracle, `plan_only` none (its docstring)
    served = RecordingProvider(chain)
    served.blocks, served.overlay = primary.blocks, primary.overlay
    ref_store = world["ref_store"] = {h: anchor
                                      for h in range(1, anchor_h + 1)}
    world["plain"], world["plan_only"] = (reference.LightClient(
        chain, served, witness, ref_store, trust_level=(num, den),
        trusting_period_s=config["trusting_period_s"],
        max_clock_drift_s=config["max_clock_drift_s"], pruning_size=keep,
        check_signatures=sigs) for sigs in (True, False))
    world["made"] = (
        f"{len(heights) + 1} light blocks x {config['validators']} "
        f"signatures for {len(targets)} requests {gap} heights apart (a "
        f"skip reaches {reach(config)}); store of {len(store.heights())} "
        f"rows, {os.path.getsize(world['path']) / 1e6:.0f} MB at "
        f"{world['path']} ({shutil.disk_usage(world['dir']).free / 1e9:.0f} "
        f"GB free there); anchor + client / fill / blocks took "
        + " / ".join(f"{b - a:.1f}" for a, b in zip(clock, clock[1:]))
        + " s")


def _fill(db, anchor_h: int):
    """Rows 1 .. anchor_h - 1: the anchor's stored bytes, by the store's
    own key, written straight to the db in a few transactions."""
    raw = db.get(lstore._key(anchor_h))
    for lo in range(1, anchor_h, FILL_ROWS_A_BATCH):
        db.write_batch([(lstore._key(h), raw) for h in
                        range(lo, min(lo + FILL_ROWS_A_BATCH, anchor_h))])


def _close(world):
    if "db" in world:
        world.pop("db").close()
    shutil.rmtree(world["dir"], ignore_errors=True)


# ---------------------------------------------------------------------------
# requests
# ---------------------------------------------------------------------------

def _ask(world, target: int):
    """(the block the client returned or the refusal it raised, the
    heights it fetched)."""
    primary = world["primary"]
    primary.asked = []
    try:
        with world["span"]("light.client"):
            got = world["client"].verify_light_block_at_height(
                target, world["now"])
    except REFUSALS as e:
        got = e
    return got, primary.asked


def request(world: dict, i=None) -> bool:
    """The next target of the run (the warm-up's, the check's and the
    window's are one sequence, so `i` is not used): accepted, and the
    block returned is the provider's.  What it fetched and how many values
    it read from the store go to the log that `window_end` holds to the
    reference."""
    target = world["targets"][world["next"]]
    world["next"] += 1
    reads = world["store"].value_reads
    got, asked = _ask(world, target)
    world["log"].append((target, asked,
                         world["store"].value_reads - reads))
    return not isinstance(got, Exception) \
        and got.hash() == world["hashes"][target]


def _against_reference(world, ref, log) -> list:
    """`ref` makes the requests of `log` after the system; a failure for
    each whose fetches differ."""
    bad = []
    for target, asked, _ in log:
        res = ref.verify_to_height(target, world["now"])
        if res.verdict != reference.OK or res.fetched != asked:
            bad.append(f"target {target}: the client fetched {asked}, the "
                       f"reference {res.fetched} ({res.verdict})")
    return bad


def warm(world: dict):
    """Whole requests: the first launches both buckets the traffic can
    reach (the trust-level prefix and the >2/3 prefix, in every hop) and
    prunes a full store."""
    for _ in range(WARM_REQUESTS):
        if not request(world):
            raise RuntimeError(f"warm-up request to {world['log'][-1][0]} "
                               f"was not accepted")
    bad = _against_reference(world, world["plan_only"], world["log"])
    if bad or world["store"].pruned == 0:
        raise RuntimeError(f"warm-up: {bad}, pruned "
                           f"{world['store'].pruned}")


def window_begin(world: dict):
    world["window_from"] = len(world["log"])


def window_end(world: dict, run: dict) -> list:
    """The window's requests against the reference's fetch plan; the store
    at its pruning size with the reference's heights; the file reopened
    gives the last target; the store read as many values in the last
    request as in the first."""
    from tendermint_tpu.libs.kvdb import SQLiteDB

    log = world["log"][world["window_from"]:]
    bad = _against_reference(world, world["plan_only"], log)
    store, keep = world["store"], world["config"]["pruning_size"]
    heights = store.heights()
    if heights != sorted(world["ref_store"]) or len(heights) != keep:
        bad.append(f"the store holds {len(heights)} blocks "
                   f"{heights[:2]}..{heights[-4:]}, the reference "
                   f"{len(world['ref_store'])} "
                   f"..{sorted(world['ref_store'])[-4:]}, pruning size "
                   f"{keep}")
    if log:
        last = log[-1][0]
        again = SQLiteDB(world["path"])
        try:
            latest = LightStore(again).latest()
        finally:
            again.close()
        if latest is None or latest.height != last \
                or latest.hash() != world["hashes"][last]:
            bad.append(f"the file reopened gives "
                       f"{latest and latest.height} as latest(), the last "
                       f"verified target was {last}")
        if log[0][2] != log[-1][2]:
            bad.append(f"value reads a request grew with the store: "
                       f"{log[0][2]} in the first, {log[-1][2]} in the "
                       f"last")
    return bad


# ---------------------------------------------------------------------------
# the check, with the oracle
# ---------------------------------------------------------------------------

def _file_digest(path: str) -> str:
    """Every row of the store's file, keys and values, through a second,
    read-only connection: what another process would find there."""
    conn = sqlite3.connect(f"file:{path}?mode=ro", uri=True)
    try:
        h = hashlib.sha256()
        for k, v in conn.execute("SELECT k, v FROM kv ORDER BY k"):
            h.update(k)
            h.update(v)
        return h.hexdigest()
    finally:
        conn.close()


def tampered(lb, lanes):
    """`lb` with the signatures of its commit's rows `lanes` flipped."""
    from tendermint_tpu.types.light_block import LightBlock, SignedHeader

    sh = lb.signed_header
    return LightBlock(SignedHeader(sh.header, data.tampered_commit(
        sh.commit, lanes)), lb.validators)


def rows_signed_by(commit, vset, member: bool) -> list:
    """The commit's rows whose signer is (or is not) a validator of
    `vset`."""
    addresses = {v.address for v in vset.validators}
    return [i for i, cs in enumerate(commit.signatures)
            if (cs.validator_address in addresses) == member]


def _unaligned_bitmaps(chain: str, trusted_vset, commit, rows):
    """(the program's bitmap, the oracle's) of the commit's `rows`, each
    against its signer's key in `trusted_vset`: the verify_sigs_bulk call
    ValidatorSet._verify_sigs_batch makes when a trusting check's commit
    is another set's (key objects found by address, not rows of the
    set's pubkey matrix), beside one OpenSSL call a signature."""
    from tendermint_tpu.crypto.batch import verify_sigs_bulk
    from tendermint_tpu.types.canonical import commit_sign_bytes_batch

    by_address = {v.address: v for v in trusted_vset.validators}
    pubs = [by_address[commit.signatures[i].validator_address].pub_key
            for i in rows]
    sigs = [commit.signatures[i].signature for i in rows]
    return (verify_sigs_bulk(pubs, commit_sign_bytes_batch(
                chain, commit, rows), sigs),
            data.oracle([p.bytes() for p in pubs],
                        [commit.vote_sign_bytes(chain, i) for i in rows],
                        sigs))


def _on_a_copy(ref, target: int, now):
    """What the reference would make of a request, its store left as it
    is."""
    kept, ref.store = ref.store, dict(ref.store)
    try:
        return ref.verify_to_height(target, now)
    finally:
        ref.store = kept


def _refused_alike(world, what: str, target: int, height: int, lanes) -> list:
    """The block at `height` served with `lanes` tampered: the client and
    the reference (on a copy of its store) both refuse the request to
    `target` naming the first of them, after the same fetches, and the
    store has written and pruned nothing."""
    primary, store = world["primary"], world["store"]
    primary.overlay[height] = tampered(primary.blocks[height], lanes)
    before = (store.heights(), store.bytes_written, store.pruned)
    try:
        got, asked = _ask(world, target)
        res = _on_a_copy(world["plain"], target, world["now"])
    finally:
        primary.overlay.clear()
    bad = []
    if not isinstance(got, Exception) or f"(#{lanes[0]})" not in str(got):
        bad.append(f"{what}, lanes {lanes} tampered: the client gave "
                   f"{got!r}, expected wrong signature #{lanes[0]}")
    if res.lane != lanes[0] or res.fetched != asked:
        bad.append(f"{what}: the reference refused lane {res.lane} after "
                   f"fetching {res.fetched} ({res.verdict}), the client "
                   f"fetched {asked}, tampered {lanes}")
    if (store.heights(), store.bytes_written, store.pruned) != before:
        bad.append(f"{what}: the refused request wrote to the store")
    return bad


def check(world: dict) -> list:
    """One honest request equal to the reference (every signature by the
    oracle) in fetches, saved heights and store.  Then the next target
    twice with tampered lanes, and the store's file byte for byte as
    before: once inside the target's >2/3 prefix, in rows the last hop's
    trusted set did not sign (so the trusting check passes them by), once
    inside a pivot's trust-level prefix, which is the unaligned batch,
    whose bitmap must equal the oracle's."""
    chain, config = world["chain"], world["config"]
    store, blocks = world["store"], world["primary"].blocks
    bad = []
    before = store.heights()
    accepted = request(world)
    target, asked, _ = world["log"][-1]
    res = world["plain"].verify_to_height(target, world["now"])
    after = store.heights()
    if not accepted or res.verdict != reference.OK or res.fetched != asked \
            or sorted(set(after) - set(before)) != res.saved \
            or after != res.store:
        bad.append(f"honest request to {target}: accepted {accepted}, "
                   f"fetched {asked}, saved "
                   f"{sorted(set(after) - set(before))}; the reference "
                   f"{res.verdict}, {res.fetched}, {res.saved}; stores "
                   f"equal: {after == res.store}")
    # the next request's checks, from the plan: its first hop (a pivot
    # verified from the block just saved) and its last (the target)
    nxt = world["targets"][world["next"]]
    hops = [(a, b) for a, b, outcome in _on_a_copy(
        world["plan_only"], nxt, world["now"]).checks
        if outcome == reference.OK]
    total = config["validators"] * config["voting_power"]
    num, den = config["trust_level"]
    n_light = total * 2 // 3 // config["voting_power"] + 1
    n_trust = total * num // den // config["voting_power"] + 1
    digest = _file_digest(world["path"])

    a, _ = hops[-1]
    fresh = [i for i in rows_signed_by(
        blocks[nxt].signed_header.commit, blocks[a].validators, False)
        if i < n_light]
    lanes = sorted({fresh[0], fresh[len(fresh) // 2], fresh[-1]})
    bad += _refused_alike(world, "target's >2/3 prefix", nxt, nxt, lanes)

    a, pivot = hops[0]
    commit = blocks[pivot].signed_header.commit
    prefix = rows_signed_by(commit, blocks[a].validators, True)[:n_trust]
    lanes = sorted({prefix[3], prefix[n_trust // 2], prefix[-1]})
    bad += _refused_alike(world, f"pivot {pivot}'s trust-level prefix",
                          nxt, pivot, lanes)
    bits, want = _unaligned_bitmaps(
        chain, blocks[a].validators, data.tampered_commit(commit, lanes),
        prefix)
    rejected = [prefix[j] for j in np.flatnonzero(~bits)]
    if not np.array_equal(bits, want) or rejected != lanes:
        bad.append(f"unaligned {n_trust}-row prefix bitmap rejects rows "
                   f"{rejected}, the oracle "
                   f"{[prefix[j] for j in np.flatnonzero(~want)]}, "
                   f"tampered {lanes}")
    if _file_digest(world["path"]) != digest:
        bad.append("the refused requests changed the store's file")
    return bad
