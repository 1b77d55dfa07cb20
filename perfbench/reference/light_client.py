"""The plain reference of the light client proper: what `val10k-client`'s
`correct` and the tier-1 tests compare tendermint_tpu/light/client.py and
light/store.py with.  It imports neither, nor the verifier, nor
ValidatorSet's commit checks: it reads the light blocks a provider hands
out as data (header fields, the commit's rows, the validators' addresses,
keys, powers and bytes) and does every check the plain way:

- the header checks (chain, heights, times, trusting period, clock drift,
  the commit is for this header) field by field;
- the validator-set hash by `hashlib`, the RFC 6962 tree over the
  validators' bytes, recursively;
- the trust-level tally by a dict of the trusted set's addresses over the
  WHOLE commit; the >2/3 tally over the new set's own rows;
- every signature of both minimal prefixes by `data.oracle`: one OpenSSL
  call a signature, nothing batched.

The store is a dict.  The rule is the reference client's (light/client.go
verifySkipping): try the target from the latest trusted block at or below
it; on "not enough trusted power" fetch the pivot
`trusted + (target - trusted) / 2` and try that; on success move the anchor
and try again what was fetched, the farthest first; when the target has
verified, ask the witness for its hash, save the whole trace, prune to
`pruning_size`, oldest first.

Departures from the reference client, each on purpose:
- one witness, compared by hash; a witness that differs refuses the
  request and nothing more (no bisection of the witness's chain, no
  evidence);
- no backwards walk (a target below the store's first block is refused)
  and no sequential mode;
- like this repo's verifier, and unlike a client that would verify first,
  a skip refused for want of trusted power checks no signature;
- `check_signatures=False` leaves the OpenSSL calls out: the fetch plan of
  an honest chain (which skips are refused is decided by the tally alone),
  for the requests of a timed window, whose signatures the system under
  test verifies and `check` holds to the oracle.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass, field

from perfbench import data

OK, CANT_TRUST = "ok", "cant_trust"


class Refused(Exception):
    """A header the light client must not accept.  `lane` is the commit
    row of the first wrong signature, where that is the reason."""

    def __init__(self, why: str, lane=None):
        super().__init__(why)
        self.lane = lane


@dataclass
class Result:
    verdict: str                    # "ok", or why the request was refused
    fetched: list = field(default_factory=list)   # heights, in order
    saved: list = field(default_factory=list)     # the trace's heights
    checks: list = field(default_factory=list)    # (from, to, outcome)
    store: list = field(default_factory=list)     # heights after pruning
    block: object = None            # the verified target, if accepted
    lane: object = None             # Refused.lane


def merkle_root(items) -> bytes:
    """RFC 6962 as Tendermint uses it: leaf = H(0x00 | item), inner =
    H(0x01 | left | right), split at the largest power of two below n."""
    n = len(items)
    if n == 0:
        return hashlib.sha256(b"").digest()
    if n == 1:
        return hashlib.sha256(b"\x00" + items[0]).digest()
    k = 1 << ((n - 1).bit_length() - 1)
    return hashlib.sha256(b"\x01" + merkle_root(items[:k])
                          + merkle_root(items[k:])).digest()


def valset_hash(validators) -> bytes:
    return merkle_root([v.bytes() for v in validators])


def _t(ts) -> int:
    return ts.seconds * 10**9 + ts.nanos


class LightClient:
    def __init__(self, chain_id: str, provider, witness, store: dict,
                 trust_level=(1, 3), trusting_period_s: float = 1209600.0,
                 max_clock_drift_s: float = 10.0, pruning_size: int = 1000,
                 check_signatures: bool = True):
        self.chain_id = chain_id
        self.provider, self.witness = provider, witness
        self.store = store              # {height: light block}
        self.trust_num, self.trust_den = trust_level
        self.period_ns = int(trusting_period_s * 10**9)
        self.drift_ns = int(max_clock_drift_s * 10**9)
        self.pruning_size = pruning_size
        self.check_signatures = check_signatures

    # -- one header from another -------------------------------------------

    def _signatures(self, commit, rows, validators):
        """Every signature of `rows` (commit rows, each signed by the
        validator beside it) by the oracle; the first wrong one refuses."""
        if not self.check_signatures:
            return
        good = data.oracle(
            [v.pub_key.bytes() for v in validators],
            [commit.vote_sign_bytes(self.chain_id, i) for i in rows],
            [commit.signatures[i].signature for i in rows])
        for i, ok in zip(rows, good):
            if not ok:
                raise Refused(f"wrong signature (#{i})", lane=i)

    def _header_checks(self, trusted, new, now):
        th, nh = trusted.signed_header.header, new.signed_header.header
        commit = new.signed_header.commit
        if nh.chain_id != self.chain_id:
            raise Refused("another chain")
        if commit.height != nh.height or commit.block_id.hash != nh.hash():
            raise Refused("the commit is not for this header")
        if len(commit.signatures) != len(new.validators.validators):
            raise Refused("the commit has not one row a validator")
        if nh.height <= th.height:
            raise Refused("not above the trusted height")
        if _t(th.time) + self.period_ns <= _t(now):
            raise Refused("the trusted header has expired")
        if not _t(th.time) < _t(nh.time) < _t(now) + self.drift_ns:
            raise Refused("header time not after the trusted one's, or "
                          "from the future")
        if nh.validators_hash != valset_hash(new.validators.validators):
            raise Refused("validators_hash is not the hash of the set")

    def _trusted_prefix(self, trusted, commit):
        """[(commit row, its validator of the TRUSTED set)]: the shortest
        run of for-block rows signed by trusted validators whose power is
        over the trust level; None where the whole commit's is not."""
        by_address = {}
        for v in trusted.validators.validators:
            by_address.setdefault(v.address, v)
        matched = [(i, by_address[cs.validator_address])
                   for i, cs in enumerate(commit.signatures)
                   if cs.for_block() and cs.validator_address in by_address]
        needed = (sum(v.voting_power for v in trusted.validators.validators)
                  * self.trust_num // self.trust_den)
        if sum(v.voting_power
               for v in {v.address: v for _, v in matched}.values()) \
                <= needed:
            return None
        prefix, seen, tallied = [], set(), 0
        for i, v in matched:
            if v.address in seen:
                raise Refused(f"double vote from {v.address.hex()}")
            seen.add(v.address)
            prefix.append((i, v))
            tallied += v.voting_power
            if tallied > needed:
                break
        return prefix

    def check(self, trusted, new, now) -> str:
        """OK, CANT_TRUST, or raises Refused."""
        self._header_checks(trusted, new, now)
        commit = new.signed_header.commit
        if new.height == trusted.height + 1:
            if new.signed_header.header.validators_hash != \
                    trusted.signed_header.header.next_validators_hash:
                raise Refused("not the set the trusted header announced")
        else:
            prefix = self._trusted_prefix(trusted, commit)
            if prefix is None:
                return CANT_TRUST
            self._signatures(commit, [i for i, _ in prefix],
                             [v for _, v in prefix])
        # more than 2/3 of the new set, its own rows in its own order
        vals = new.validators.validators
        needed = sum(v.voting_power for v in vals) * 2 // 3
        rows, tallied = [], 0
        for i, cs in enumerate(commit.signatures):
            if cs.for_block() and tallied <= needed:
                rows.append(i)
                tallied += vals[i].voting_power
        if tallied <= needed:
            raise Refused("not over 2/3 of the new set signed")
        self._signatures(commit, rows, [vals[i] for i in rows])
        return OK

    # -- one request -------------------------------------------------------

    def verify_to_height(self, height: int, now) -> Result:
        res = Result(verdict=OK)
        try:
            res.block = self._verify_to_height(height, now, res)
        except Refused as e:
            res.verdict, res.lane = f"refused: {e}", e.lane
        res.store = sorted(self.store)
        return res

    def _fetch(self, height: int, res: Result):
        res.fetched.append(height)
        return self.provider.light_block(height)

    def _verify_to_height(self, height: int, now, res: Result):
        if height in self.store:
            return self.store[height]
        target = self._fetch(height, res)
        below = [h for h in self.store if h <= height]
        if not below:
            raise Refused("below the store's first block: no backwards "
                          "walk here")
        anchor = self.store[max(below)]
        trace, goals, d = [], [target], 0
        while goals:
            outcome = self.check(anchor, goals[d], now)
            res.checks.append((anchor.height, goals[d].height, outcome))
            if outcome == CANT_TRUST:
                if d == len(goals) - 1:
                    goals.append(self._fetch(
                        anchor.height
                        + (goals[d].height - anchor.height) // 2, res))
                d += 1
            else:
                anchor = goals[d]
                trace.append(anchor)
                goals, d = goals[:d], 0
        if self.witness.light_block(height).hash() != target.hash():
            raise Refused("the witness has another header at this height")
        for lb in trace:
            self.store[lb.height] = lb
        res.saved = [lb.height for lb in trace]
        for h in sorted(self.store)[:-self.pruning_size]:
            del self.store[h]
        return target
