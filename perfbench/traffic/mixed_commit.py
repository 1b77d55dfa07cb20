"""Traffic `mixed_commit`: a full node validating one block's LastCommit
after another, on a chain whose validators hold keys in three schemes.

A request is `commit.validate_basic()` then `vset.verify_commit(chain_id,
commit.block_id, commit.height, commit)` on ONE ValidatorSet object held for
the whole run: `commit_heights`' request (its `_check_commit`, `request`
and `verdict` are used as they are), on a set whose k-th seeded key is of
scheme k mod 3 (ed25519, secp256k1, sr25519, the order of the
configuration's `key_types`), so that in the set's own order (power, then
address) the schemes interleave at random.  Closed loop, one caller.

A set that is not all ed25519 has no pubkey matrix, so the commit goes down
the LIST path of verify_sigs_bulk; its 9,900 rows are more than the
scheduler's max_batch, so a BatchVerifier takes them: one device lane a
scheme, each with its own host staging, and 9,900 SigCache inserts in
`batch.verdict`.  No lane asks that cache for a verdict (only the host
lanes do, crypto/batch._host_verify_items), and a lap of the ring (79,200
triples) is more than it holds (65,536) besides; `window_end` fails the run
unless the window's launch records are the expected ones and their real
rows add up to every request's non-absent rows, i.e. every verdict of the
window was computed on the device.

A ring of `ring` commits of consecutive heights is made from the seed and
walked round; in each, `absent_share` of the set is absent, a fresh draw
for every commit, the rest signed for the block, no nil votes.

Keys and signatures come from perfbench/reference/mixed_commit.py's `Key`
(OpenSSL for ed25519, plain-Python BIP-340 and schnorrkel signers for the
other two), made in `data.fan_out` workers, a slice of the keys to a
worker: it makes each key once and signs every height with it.  `correct`
rests on that file's verifiers, never on its signers.
"""
from __future__ import annotations

import hashlib
import random

import numpy as np

from perfbench import data
from perfbench.reference import mixed_commit as reference
from perfbench.traffic import commit_heights as plain

T0 = plain.T0
JOB_KEYS = 250          # keys a worker makes and signs with at a time
JOB_ROWS = 500          # rows a worker checks at a time (~10 ms a row)

request = plain.request
verdict = plain.verdict


def _scheme(k: int, schemes) -> str:
    return schemes[k % len(schemes)]


def _sign_keys(job: dict):
    """A worker's share (data.fan_out): keys [a, b) of the seed, and for
    each its signature over the precommit of every height in `heights`,
    stamped (T0 + height, k).  Returns ([pub bytes], [[sig a height]])."""
    from tendermint_tpu.types.basic import SignedMsgType, Timestamp
    from tendermint_tpu.types.canonical import canonical_vote_bytes

    bids = {h: data.block_id(b"commit/%d" % h) for h in job["heights"]}
    pubs, sigs = [], []
    for k in range(job["a"], job["b"]):
        key = reference.Key(_scheme(k, job["schemes"]), hashlib.sha256(
            b"perfbench/%d/%s/%d" % (job["seed"], job["tag"].encode(), k)
        ).digest())
        pubs.append(key.pub_bytes)
        sigs.append([key.sign(canonical_vote_bytes(
            job["chain"], SignedMsgType.PRECOMMIT, h, 0, bids[h],
            Timestamp(T0 + h, k))) for h in job["heights"]])
    return pubs, sigs


def _oracle(rows) -> np.ndarray:
    """reference.verify_rows, JOB_ROWS rows to a worker: one plain call a
    signature all the same, on several cores."""
    parts = data.fan_out(reference.verify_rows,
                         [rows[a:a + JOB_ROWS]
                          for a in range(0, len(rows), JOB_ROWS)])
    return np.concatenate(parts) if parts else np.zeros(0, dtype=bool)


def _pub_key(scheme: str, pub: bytes):
    from tendermint_tpu.crypto import ed25519, secp256k1, sr25519

    return {"ed25519": ed25519, "secp256k1": secp256k1,
            "sr25519": sr25519}[scheme].PubKey(pub)


def setup(config: dict, params: dict, seed: int, seconds: float) -> dict:
    from tendermint_tpu.types.basic import BlockIDFlag, Timestamp
    from tendermint_tpu.types.commit import Commit, CommitSig
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import ValidatorSet

    chain, n = config["chain_id"], config["validators"]
    schemes = list(config["key_types"])
    counts = {s: len(range(j, n, len(schemes)))
              for j, s in enumerate(schemes)}
    if counts != config["key_types"]:
        raise ValueError(f"key_types {config['key_types']} is not the "
                         f"split k mod {len(schemes)} gives: {counts}")
    ring = params["ring"]
    heights = list(range(1, ring + 2))       # the last is the check's own
    parts = data.fan_out(_sign_keys, [
        {"seed": seed, "tag": config["name"], "chain": chain,
         "schemes": schemes, "heights": heights,
         "a": a, "b": min(a + JOB_KEYS, n)} for a in range(0, n, JOB_KEYS)])
    pubs = [p for part in parts for p in part[0]]
    sigs = [s for part in parts for s in part[1]]
    keys = [_pub_key(_scheme(k, schemes), pub) for k, pub in enumerate(pubs)]
    vset = ValidatorSet([Validator.new(key, config["voting_power"])
                         for key in keys])
    key_of = {key.address(): k for k, key in enumerate(keys)}
    key_of_row = [key_of[v.address] for v in vset.validators]
    n_absent = round(config["absent_share"] * n)
    rng = random.Random(seed)
    commits = []
    for j, h in enumerate(heights):
        absent = frozenset(rng.sample(range(n), n_absent))
        commits.append(Commit(h, 0, data.block_id(b"commit/%d" % h), [
            CommitSig.absent() if r in absent else
            CommitSig(BlockIDFlag.COMMIT, val.address,
                      Timestamp(T0 + h, key_of_row[r]),
                      sigs[key_of_row[r]][j])
            for r, val in enumerate(vset.validators)]))
    return {
        "chain": chain, "vset": vset, "ring": ring, "commits": commits,
        "n_signed": n - n_absent, "counts": counts, "n_absent": n_absent,
        "expect_launch": params["expect_launch"],
        "made": f"{len(commits)} commits x {n - n_absent} signatures of "
                f"{n} validators ({counts}), {n_absent} absent in each",
    }


def _scheme_rows(world, commit) -> dict:
    """{scheme: the commit's non-absent rows of that scheme, in order}."""
    out = {s: [] for s in world["counts"]}
    for i, (v, cs) in enumerate(zip(world["vset"].validators,
                                    commit.signatures)):
        if not cs.is_absent():
            out[v.pub_key.type_name].append(i)
    return out


def warm(world: dict):
    """Every bucket each lane can reach, by direct calls off any request:
    a scheme's rows number between its keys less every absent validator
    and all its keys.  The ed25519 route is warmed on real rows of the
    check's commit (a list of keys, as the BatchVerifier hands them), the
    other two lanes by their `warm_bucket`.  Then one lap, which takes the
    same launches through the degrade runtime's lane worker."""
    from tendermint_tpu.crypto import batch
    from tendermint_tpu.ops import ed25519 as edops
    from tendermint_tpu.ops import secp as secp_ops
    from tendermint_tpu.ops import sr25519 as sr_ops

    honest = world["commits"][-1]
    ed_rows = _scheme_rows(world, honest)["ed25519"]
    for scheme, count in world["counts"].items():
        fewest = max(1, count - world["n_absent"])
        for size in {edops.bucket_size(m): m
                     for m in (fewest, count)}.values():
            if scheme == "secp256k1":
                secp_ops.warm_bucket(size)
            elif scheme == "sr25519":
                sr_ops.warm_bucket(size)
            else:
                rows = (ed_rows * (size // len(ed_rows) + 1))[:size]
                bits = batch.verify_ed25519_batch(
                    *data.commit_triples(world["chain"], world["vset"],
                                         honest, rows))
                if not bits.all():
                    raise RuntimeError("warm-up: the ed25519 route refused "
                                       "honest rows")
    for i in range(world["ring"]):
        if not request(world, i):
            raise RuntimeError(f"warm-up request {i} was not accepted")


def bulk_bitmap(world, commit, idxs) -> np.ndarray:
    """The bitmap behind verify_commit: the verify_sigs_bulk call
    ValidatorSet._verify_sigs_batch makes for a set that has no pubkey
    matrix (the validators' PubKey objects, batched sign bytes)."""
    from tendermint_tpu.crypto.batch import verify_sigs_bulk
    from tendermint_tpu.types.canonical import commit_sign_bytes_batch

    vals = world["vset"].validators
    return verify_sigs_bulk(
        [vals[i].pub_key for i in idxs],
        commit_sign_bytes_batch(world["chain"], commit, idxs),
        [commit.signatures[i].signature for i in idxs])


def with_signature(commit, i: int, signature: bytes):
    from tendermint_tpu.types.commit import CommitSig

    cs = commit.signatures[i]
    return plain.with_rows(commit, {i: CommitSig(
        cs.block_id_flag, cs.validator_address, cs.timestamp, signature)})


def _be(x: int) -> bytes:
    return x.to_bytes(32, "big")


def _le(x: int) -> bytes:
    return x.to_bytes(32, "little")


def _low255(b: bytes) -> int:
    return int.from_bytes(b, "little") & ~(1 << 255)


# (name, scheme, honest signature -> the signature that scheme must
# refuse): a scalar or an encoding moved to another representative of the
# same residue (s + L, R + p), or just out of range, so that a verifier
# that reduces where it should screen accepts it
REFUSALS = (
    ("secp256k1-s-not-below-n", "secp256k1",
     lambda s: s[:32] + _be(reference.SECP_N + 5)),
    ("secp256k1-r-not-below-p", "secp256k1",
     lambda s: _be(reference.SECP_P + 1) + s[32:]),
    ("sr25519-without-marker", "sr25519",
     lambda s: s[:63] + bytes([s[63] & 0x7F])),
    ("sr25519-s-plus-L", "sr25519",
     lambda s: s[:32] + _le(_low255(s[32:]) + reference.ED_L | 1 << 255)),
    ("sr25519-R-plus-p", "sr25519",
     lambda s: _le(_low255(s[:32]) + reference.ED_P) + s[32:]),
    ("ed25519-s-plus-L", "ed25519",
     lambda s: s[:32] + _le(_low255(s[32:]) + reference.ED_L)),
)
CASES = ("honest", "ends-tampered", *(name for name, _, _ in REFUSALS),
         "absent-carrying-a-signature", "a-third-absent")


def refusals(rows: dict, commit) -> list:
    """[(name, row, the signature that row's scheme must refuse)], each
    on a row of its own: rows 1.. of a scheme, its first and last being
    the tampered commit's."""
    taken = {scheme: 0 for scheme in rows}
    out = []
    for name, scheme, make in REFUSALS:
        taken[scheme] += 1
        i = rows[scheme][taken[scheme]]
        out.append((name, i, make(commit.signatures[i].signature)))
    return out


def cases(world: dict) -> list:
    """[(name, the commit, the head of the verdict the reference must give
    of it)]: the honest commit of the check and what is made of it.
    (a) honest; (b) the first and the last row of each scheme tampered in
    one commit; (c) what each scheme must refuse by its own rule, one row
    a commit; (d) an absent row that carries a signature, and a third of
    the rows absent."""
    from tendermint_tpu.types.commit import CommitSig

    honest = world["commits"][-1]
    signed = [i for i, cs in enumerate(honest.signatures)
              if not cs.is_absent()]
    absent = [i for i, cs in enumerate(honest.signatures) if cs.is_absent()]
    rows = _scheme_rows(world, honest)
    ends = sorted(i for v in rows.values() for i in (v[0], v[-1]))
    out = [("honest", honest, reference.ACCEPTED),
           ("ends-tampered", data.tampered_commit(honest, ends),
            ("wrong_signature", ends[0]))]
    out += [(name, with_signature(honest, i, signature),
             ("wrong_signature", i))
            for name, i, signature in refusals(rows, honest)]
    i = absent[len(absent) // 2]
    out.append(("absent-carrying-a-signature", plain.with_rows(honest, {
        i: CommitSig(honest.signatures[i].block_id_flag,
                     signature=honest.signatures[signed[0]].signature)}),
        ("invalid", i)))
    kept = len(honest.signatures) * 2 // 3
    out.append(("a-third-absent", plain.with_rows(
        honest, {i: CommitSig.absent() for i in signed[kept:]}),
        ("not_enough_power",)))
    return out


def compare(world: dict, name: str, commit, expect, memo: dict) -> list:
    """Program against reference on one commit through the timed path:
    (the failures, the reference's bitmap)."""
    got = verdict(world, commit)
    want, bits = reference.check(
        world["chain"], world["vset"], commit.block_id, commit.height,
        commit, oracle=_oracle, memo=memo)
    bad = []
    if got != want:
        bad.append(f"{name}: the program says {got}, the reference {want}")
    if want[:len(expect)] != expect:
        bad.append(f"{name}: the reference says {want}, expected {expect}")
    return bad, bits


def check(world: dict):
    from tendermint_tpu.crypto import devobs

    honest = world["commits"][-1]
    signed = [i for i, cs in enumerate(honest.signatures)
              if not cs.is_absent()]
    rows = _scheme_rows(world, honest)
    memo, bad = {}, []
    for name, commit, expect in cases(world):
        seq0 = devobs.last_seq()
        failures, want_bits = compare(world, name, commit, expect, memo)
        bad += failures
        if name == "honest":
            # three launches, one a scheme, every row of it in that one
            launched = sorted(r["n"] for r in devobs.records(since_seq=seq0))
            if launched != sorted(len(v) for v in rows.values()):
                bad.append(f"an honest commit launched rows {launched}, "
                           f"expected one launch a scheme of "
                           f"{ {s: len(v) for s, v in rows.items()} }")
        if name == "ends-tampered":
            bits = bulk_bitmap(world, commit, signed)
            if not np.array_equal(bits, want_bits) or \
                    int((~bits).sum()) != 2 * len(rows):
                bad.append(
                    f"{len(signed)}-row bitmap rejects rows "
                    f"{[signed[j] for j in np.flatnonzero(~bits)]}, the "
                    f"reference "
                    f"{[signed[j] for j in np.flatnonzero(~want_bits)]}")
    return bad


def window_end(world: dict, run: dict):
    bad = []
    refused = [r["i"] for r in run["requests"] if not r["ok"]]
    if refused:
        bad.append(f"requests {refused[:5]} of the window were refused")
    expect = {(e["path"], e["nb"]) for e in world["expect_launch"]}
    records = run["window_records"]
    for r in records:
        if (r["path"], r["nb"]) not in expect or r.get("compile_s"):
            bad.append(f"a launch of the window was {r['path']}/{r['nb']} "
                       f"n={r['n']} compile_s={r.get('compile_s')}, "
                       f"expected one of {sorted(expect)}, compiled before")
            break
    # every request's rows were launched: per request where the run kept
    # its records (--trace 1), over the window where the ring still holds
    # all of its launches
    per_request = [sum(x["n"] for x in r["records"])
                   for r in run["requests"] if "records" in r]
    if not per_request and len(records) == run["launches_in_window"]:
        per_request = [sum(r["n"] for r in records) / len(run["requests"])]
    if any(rows != world["n_signed"] for rows in per_request):
        bad.append(f"the window's launches carried {per_request[:5]} rows "
                   f"a request, not the {world['n_signed']} non-absent "
                   f"rows: a verdict came from somewhere else")
    return bad
