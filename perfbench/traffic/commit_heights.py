"""Traffic `commit_heights`: a full node validating one block's LastCommit
after another.

A request is `commit.validate_basic()` then `vset.verify_commit(chain_id,
commit.block_id, commit.height, commit)`: what BlockExecutor.validate_block
does to a block's LastCommit (state/execution.py, reference
state/validation.go:92), on ONE ValidatorSet object held for the whole run,
as a node holds `state.last_validators`.  Closed loop, one caller: the apply
loop is one thread and waits for the verdict.

A ring of `ring` commits of consecutive heights is made from the seed and
walked round.  In each, `absent_share` of the set (the configuration's) is
absent, a fresh draw for every commit; every other validator signed for the
block, no nil votes.  So the rows a request verifies differ from the last
request's, which is what a chain gives a node and what no other cell does:
the pubkey rows the program keeps on the device are keyed by their content,
and the ring is longer than that cache is deep, so a lap behaves as fresh
heights do.  No verdict cache sits on this path (verify_sigs_bulk skips the
SigCache), so every request launches.

`correct` rests on perfbench/reference/commit.py (the same checks in plain
Python, one OpenSSL call a signature) for the verdicts, and on
`data.oracle` for the bitmap.
"""
from __future__ import annotations

import itertools
import random
import re

import numpy as np
from tendermint_tpu.types.validator_set import CommitVerifyError

from perfbench import data
from perfbench.reference import commit as reference

T0 = 1_700_000_000
JOB_ROWS = 20_000       # rows a worker signs or checks at a time; a set
#                         smaller than this is signed in this process
_keys_memo = {}         # a worker's keys, made once (data.seeded_keys)


def _seeded_keys(seed: int, tag: str, n: int) -> list:
    memo = (seed, tag, n)
    if memo not in _keys_memo:
        _keys_memo.clear()
        _keys_memo[memo] = data.seeded_keys(seed, tag, n)
    return _keys_memo[memo]


def _sign_rows(job: dict) -> list:
    """A worker's share (data.fan_out): the signatures of rows [a, b) of
    one commit, b"" for an absent row.  Row r is signed by key
    `key_of_row[r - a]` of the seed's keys over the precommit with
    timestamp (T0 + height, r), as data.signed_commit stamps them."""
    from tendermint_tpu.types.basic import SignedMsgType, Timestamp
    from tendermint_tpu.types.canonical import canonical_vote_bytes

    keys = _seeded_keys(job["seed"], job["tag"], job["n"])
    bid, height, absent = (data.block_id(job["bid_tag"]), job["height"],
                           job["absent"])
    out = []
    for r, k in zip(range(job["a"], job["b"]), job["key_of_row"]):
        if r in absent:
            out.append(b"")
            continue
        sb = canonical_vote_bytes(job["chain"], SignedMsgType.PRECOMMIT,
                                  height, 0, bid,
                                  Timestamp(T0 + height, r))
        out.append(keys[k].sign(sb))
    return out


def _oracle_rows(job) -> np.ndarray:
    return data.oracle(*job)


def _oracle(pubs, msgs, sigs) -> np.ndarray:
    """data.oracle, JOB_ROWS rows to a worker: one OpenSSL call a
    signature all the same, on several cores (99,000 take a core 12 s and
    the check verifies four commits)."""
    jobs = [(pubs[a:a + JOB_ROWS], msgs[a:a + JOB_ROWS],
             sigs[a:a + JOB_ROWS]) for a in range(0, len(pubs), JOB_ROWS)]
    parts = data.fan_out(_oracle_rows, jobs)
    return np.concatenate(parts) if parts else np.zeros(0, dtype=bool)


def _commit(vset, height: int, bid_tag: bytes, absent, sigs):
    from tendermint_tpu.types.basic import BlockIDFlag, Timestamp
    from tendermint_tpu.types.commit import Commit, CommitSig

    rows = [CommitSig.absent() if r in absent else
            CommitSig(BlockIDFlag.COMMIT, val.address,
                      Timestamp(T0 + height, r), sigs[r])
            for r, val in enumerate(vset.validators)]
    return Commit(height, 0, data.block_id(bid_tag), rows)


def setup(config: dict, params: dict, seed: int, seconds: float) -> dict:
    chain, n = config["chain_id"], config["validators"]
    keys = _seeded_keys(seed, config["name"], n)
    vset, ordered = data.make_valset(keys, config["voting_power"])
    key_index = {id(k): i for i, k in enumerate(keys)}
    key_of_row = [key_index[id(k)] for k in ordered]
    ring = params["ring"]
    n_absent = round(config["absent_share"] * n)
    rng = random.Random(seed)
    heights = list(range(1, ring + 2))       # the last is the check's own
    absents = [frozenset(rng.sample(range(n), n_absent)) for _ in heights]
    jobs = [{"seed": seed, "tag": config["name"], "n": n, "chain": chain,
             "height": h, "bid_tag": b"commit/%d" % h, "absent": absent,
             "a": a, "b": min(a + JOB_ROWS, n),
             "key_of_row": key_of_row[a:a + JOB_ROWS]}
            for h, absent in zip(heights, absents)
            for a in range(0, n, JOB_ROWS)]
    parts = data.fan_out(_sign_rows, jobs)
    per_commit = len(jobs) // len(heights)
    commits = []
    for j, (h, absent) in enumerate(zip(heights, absents)):
        sigs = list(itertools.chain.from_iterable(
            parts[j * per_commit:(j + 1) * per_commit]))
        commits.append(_commit(vset, h, b"commit/%d" % h, absent, sigs))
    return {
        "chain": chain, "vset": vset, "ring": ring, "commits": commits,
        "n_signed": n - n_absent, "seam": params["seam_rows"],
        "expect_launch": params.get("expect_launch"),
        "made": f"{len(commits)} commits x {n - n_absent} signatures of "
                f"{n} validators, {n_absent} absent in each",
    }


def _check_commit(world, commit):
    """The timed path.  Raises what the program raises."""
    with world["span"]("validate_basic"):
        commit.validate_basic()
    with world["span"]("verify_commit"):
        world["vset"].verify_commit(world["chain"], commit.block_id,
                                    commit.height, commit)


def request(world: dict, i: int) -> bool:
    try:
        _check_commit(world, world["commits"][i % world["ring"]])
    except (ValueError, CommitVerifyError):
        return False
    return True


def warm(world: dict):
    """One lap: the route's one chunk shape is compiled by the first
    request, `_pub_matrix` is made by it, and after the lap the device's
    row cache is as full as it stays."""
    for i in range(world["ring"]):
        if not request(world, i):
            raise RuntimeError(f"warm-up request {i} was not accepted")


def verdict(world, commit):
    """The program's verdict on `commit` through the timed path, in the
    reference's terms (perfbench/reference/commit.py)."""
    from tendermint_tpu.types.validator_set import NotEnoughVotingPowerError

    try:
        _check_commit(world, commit)
    except NotEnoughVotingPowerError as e:
        return ("not_enough_power", e.got, e.needed)
    except (ValueError, CommitVerifyError) as e:
        text = str(e)
        row = re.search(r"#(\d+)", text)
        return ("wrong_signature" if text.startswith("wrong signature")
                else "invalid", int(row.group(1)) if row else None)
    return reference.ACCEPTED


def with_rows(commit, rows: dict):
    from tendermint_tpu.types.commit import Commit

    sigs = list(commit.signatures)
    for i, cs in rows.items():
        sigs[i] = cs
    return Commit(commit.height, commit.round, commit.block_id, sigs)


def tamper_lanes(n: int, seam: int) -> list:
    """Rows of an n-row batch to tamper: both ends, both sides of the
    first chunk seam, one in the fifth chunk; in a batch too small to
    have them, both ends and three between."""
    if n > 5 * seam:
        return [0, seam - 1, seam, 4 * seam + seam // 2, n - 1]
    return sorted({0, n // 4, n // 2, n - 2, n - 1})


def check(world: dict):
    from tendermint_tpu.crypto import devobs
    from tendermint_tpu.types.commit import CommitSig

    chain, vset, n_signed = world["chain"], world["vset"], world["n_signed"]
    honest = world["commits"][-1]
    signed = [i for i, cs in enumerate(honest.signatures)
              if not cs.is_absent()]
    absent = [i for i, cs in enumerate(honest.signatures) if cs.is_absent()]
    bad = []

    def compare(what, commit):
        """Program against reference on one commit; the reference's
        (verdict, bitmap)."""
        got = verdict(world, commit)
        want, bits = reference.check(chain, vset, commit.block_id,
                                     commit.height, commit, oracle=_oracle)
        if got != want:
            bad.append(f"{what}: the program says {got}, the reference "
                       f"{want}")
        return want, bits

    seq0 = devobs.last_seq()
    want, _ = compare("an honest commit", honest)
    if want != reference.ACCEPTED:
        bad.append(f"the reference refuses an honest commit: {want}")
    launched = [r["n"] for r in devobs.records(since_seq=seq0)]
    if launched != [n_signed]:
        bad.append(f"an honest commit launched rows {launched}, expected "
                   f"one launch of the {n_signed} non-absent rows")

    lanes = tamper_lanes(n_signed, world["seam"])
    tampered = data.tampered_commit(honest, [signed[j] for j in lanes])
    want, want_bits = compare(f"batch rows {lanes} tampered", tampered)
    if want != ("wrong_signature", signed[lanes[0]]):
        bad.append(f"the reference says {want} of a commit tampered first "
                   f"at #{signed[lanes[0]]}")
    bits = data.bulk_bitmap(chain, vset, tampered, signed)
    if not np.array_equal(bits, want_bits) or \
            [int(j) for j in np.flatnonzero(~bits)] != lanes:
        bad.append(f"{n_signed}-row bitmap rejects "
                   f"{sorted(np.flatnonzero(~bits))}, the oracle "
                   f"{sorted(np.flatnonzero(~want_bits))}, tampered {lanes}")

    i = absent[len(absent) // 2]
    carrying = with_rows(honest, {i: CommitSig(
        honest.signatures[i].block_id_flag,
        signature=honest.signatures[signed[0]].signature)})
    want, _ = compare(f"absent row #{i} carrying a signature", carrying)
    if want != ("invalid", i):
        bad.append(f"the reference says {want} of an absent row #{i} that "
                   f"carries a signature")

    # equal powers: with 2/3 of the rows for the block, rounded down, the
    # tally stops at what it has to exceed
    n = len(honest.signatures)
    kept = n * 2 // 3
    short = with_rows(honest, {i: CommitSig.absent()
                                for i in signed[kept:]})
    want, _ = compare(f"a commit of {kept} of {n} rows", short)
    if want[0] != "not_enough_power":
        bad.append(f"the reference says {want} of a commit with "
                   f"{n - kept} rows absent")
    return bad


def window_end(world: dict, run: dict):
    bad = []
    refused = [r["i"] for r in run["requests"] if not r["ok"]]
    if refused:
        bad.append(f"requests {refused[:5]} of the window were refused")
    expect = world["expect_launch"] or {}
    for r in run["window_records"]:
        if r["n"] != world["n_signed"] or r.get("compile_s") \
                or {k: r.get(k) for k in expect} != expect:
            bad.append(f"a launch of the window was {r['path']}/{r['nb']} "
                       f"n={r['n']} chunks={r.get('chunks')} compile_s="
                       f"{r.get('compile_s')}, expected {expect} "
                       f"n={world['n_signed']}")
            break
    return bad
