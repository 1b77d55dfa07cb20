"""Traffic `light_headers`: a light client verifying headers of one chain.

A request is one light.verifier.verify(trusted, trusted_vals, untrusted,
untrusted_vals, ...): the header checks, the validator-set hash (not
memoised: every leaf on the host, every request) and the commit checks.
`mode` is `adjacent` (heights h, h+1: verify_commit_light over the >2/3
prefix) or `skipping` (heights `height_stride` apart:
verify_commit_light_trusting at the trust level, matched by address, then
verify_commit_light).  Closed loop, one caller: sequential verification is a
chain of dependent steps.

A ring of `ring` signed headers is made from the seed and walked round,
request i verifying header (i mod (ring-1)) + 1 from the one before it; each
header has a ValidatorSet object of its own, as a provider delivers them.
No verdict cache sits on this path (verify_sigs_bulk skips the SigCache), so
every request launches.  Assumed, and said in the configuration: the chain
is synthetic (headers built directly, commits signed over the header's own
hash) and the validator set does not change between headers, which is what
keeps the pubkey rows resident on the device.
"""
from __future__ import annotations

import hashlib
from fractions import Fraction

import numpy as np
from tendermint_tpu.light import verifier
from tendermint_tpu.light.verifier import LightError

from perfbench import data

T0 = 1_700_000_000


def _signed_header(chain, vset, keys, height, vhash, prev_hash):
    from tendermint_tpu.types.basic import BlockID, PartSetHeader, Timestamp
    from tendermint_tpu.types.block import Header
    from tendermint_tpu.types.light_block import SignedHeader

    def h32(tag):
        return hashlib.sha256(b"%s/%d" % (tag, height)).digest()

    header = Header(
        chain_id=chain, height=height, time=Timestamp(T0 + height, 0),
        last_block_id=BlockID(prev_hash, PartSetHeader(1, h32(b"lparts"))),
        last_commit_hash=h32(b"lc"), data_hash=h32(b"data"),
        validators_hash=vhash, next_validators_hash=vhash,
        consensus_hash=h32(b"cons"), app_hash=h32(b"app"),
        last_results_hash=h32(b"res"), evidence_hash=h32(b"ev"),
        proposer_address=vset.validators[0].address)
    bid = BlockID(header.hash(), PartSetHeader(1, h32(b"parts")))
    return SignedHeader(header, data.signed_commit(chain, vset, keys,
                                                   height, bid))


def setup(config: dict, params: dict, seed: int, seconds: float) -> dict:
    from tendermint_tpu.types.basic import Timestamp

    chain = config["chain_id"]
    keys = data.seeded_keys(seed, config["name"], config["validators"])
    vset, ordered = data.make_valset(keys, config["voting_power"])
    vhash = vset.hash()
    ring, stride = params["ring"], params["height_stride"]
    if (stride == 1) != (params["mode"] == "adjacent"):
        raise ValueError("mode adjacent means height_stride 1, and only it")
    headers, vsets = [], []
    prev = hashlib.sha256(b"genesis").digest()
    for j in range(ring + 1):        # the last one is the check's own
        sh = _signed_header(chain, vset, ordered, 1 + j * stride, vhash,
                            prev)
        prev = sh.hash()
        headers.append(sh)
        vsets.append(vset.copy())
    num, den = config["trust_level"]
    power = config["voting_power"]
    total = vset.total_voting_power()
    return {
        "chain": chain, "mode": params["mode"], "ring": ring,
        "headers": headers, "vsets": vsets,
        "now": Timestamp(T0 + headers[-1].height + 5, 0),
        "trusting_period_s": float(config["trusting_period_s"]),
        "max_clock_drift_s": float(config["max_clock_drift_s"]),
        "trust_level": Fraction(num, den),
        # the rows each commit check verifies: the minimal prefixes
        "n_light": (total * 2 // 3) // power + 1,
        "n_trust": (total * num // den) // power + 1,
        "made": f"{len(headers)} signed headers x {vset.size()} signatures",
    }


def _verify(world, trusted_j: int, untrusted, untrusted_vals):
    with world["span"]("light.verify"):
        verifier.verify(
            world["headers"][trusted_j], world["vsets"][trusted_j],
            untrusted, untrusted_vals, world["trusting_period_s"],
            world["now"], world["max_clock_drift_s"], world["trust_level"])


def request(world: dict, i: int) -> bool:
    j = i % (world["ring"] - 1)
    try:
        _verify(world, j, world["headers"][j + 1], world["vsets"][j + 1])
    except LightError:
        return False
    return True


def warm(world: dict):
    """One lap and a step: every bucket the mode reaches (the >2/3 prefix,
    and in skipping mode the trust-level prefix before it) is launched by
    the first request, and after the lap every ValidatorSet object of the
    ring has met the path once, as in the steady state."""
    for i in range(world["ring"]):
        if not request(world, i):
            raise RuntimeError(f"warm-up request {i} was not accepted")


def check(world: dict):
    """The ring's last header, honest and with tampered lanes inside the
    prefixes the mode verifies: the verdict names the first bad lane, and
    the bitmap of each prefix equals the per-signature OpenSSL oracle's."""
    from tendermint_tpu.types.light_block import SignedHeader

    chain = world["chain"]
    j = world["ring"] - 1
    sh, vset = world["headers"][j + 1], world["vsets"][j + 1]
    bad = []
    try:
        _verify(world, j, sh, vset)
    except LightError as e:
        bad.append(f"an honest header was refused: {e!r}")
    prefixes = [world["n_light"]]
    if world["mode"] == "skipping":
        prefixes.insert(0, world["n_trust"])
    for n in prefixes:
        lanes = sorted({3, n // 2, n - 1})
        tampered = data.tampered_commit(sh.commit, lanes)
        err = data.raises(lambda: _verify(
            world, j, SignedHeader(sh.header, tampered), vset), LightError)
        if err is None or f"(#{lanes[0]})" not in str(err):
            bad.append(f"lanes {lanes} tampered: {err!r}, expected wrong "
                       f"signature #{lanes[0]}")
        idxs = list(range(n))
        bits = data.bulk_bitmap(chain, vset, tampered, idxs)
        want = data.oracle(*data.commit_triples(chain, vset, tampered, idxs))
        if not np.array_equal(bits, want) or \
                sorted(np.flatnonzero(~bits)) != lanes:
            bad.append(f"{n}-row prefix bitmap rejects "
                       f"{sorted(np.flatnonzero(~bits))}, the oracle "
                       f"{sorted(np.flatnonzero(~want))}, tampered {lanes}")
    return bad
