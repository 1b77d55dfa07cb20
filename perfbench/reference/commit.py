"""The plain reference of a full node's commit check: what
`val100k-commit`'s `correct` and the tier-1 tests compare
`Commit.validate_basic` + `ValidatorSet.verify_commit` with.  It imports
neither tendermint_tpu/types/validator_set.py nor crypto/batch.py: it reads
the validator set and the commit as data (each validator's key bytes and
voting power, each commit row's flag, address, time and signature) and does
every check the plain way, in the reference's order
(types/block.go CommitSig.ValidateBasic, Commit.ValidateBasic;
types/validator_set.go VerifyCommit :662-709):

- `validate_basic`: height and round not negative, a block id, at least one
  row, and every row well formed: an absent row is empty, any other has a
  20-byte address and a signature of at most 64 bytes;
- `verify_commit`: the set's size against the commit's, the height, the
  block id; then every non-absent row, one OpenSSL call each
  (`data.oracle` over `data.commit_triples`), nothing batched and none
  left out; then the for-block tally against `total * 2 // 3`.

A verdict is a tuple: `ACCEPTED`; `("invalid", i)` for a malformed row i
(`("invalid", None)` for the commit's own fields, the set's size, the
height or the block id); `("wrong_signature", i)` with the first bad row;
`("not_enough_power", tallied, needed)`.  `verify_commit` returns it with
the bitmap of the rows it verified (None where it verified none).
"""
from __future__ import annotations

from perfbench import data

ACCEPTED = ("accepted",)
ABSENT, COMMIT, NIL = 1, 2, 3      # BlockIDFlag, reference types/block.go
GO_ZERO_TIME_S = -62135596800      # time.Time{}: 0001-01-01T00:00:00Z


def validate_basic(commit):
    """ACCEPTED, or the first reason a node refuses the commit unseen."""
    if commit.height < 0 or commit.round < 0:
        return ("invalid", None)
    if commit.height < 1:
        return ACCEPTED
    if commit.block_id.is_zero() or not commit.signatures:
        return ("invalid", None)
    for i, cs in enumerate(commit.signatures):
        flag = int(cs.block_id_flag)
        if flag == ABSENT:
            empty = (not cs.validator_address and not cs.signature
                     and cs.timestamp.seconds == GO_ZERO_TIME_S
                     and cs.timestamp.nanos == 0)
            if not empty:
                return ("invalid", i)
        elif flag in (COMMIT, NIL):
            if len(cs.validator_address) != 20 or \
                    not 0 < len(cs.signature) <= 64:
                return ("invalid", i)
        else:
            return ("invalid", i)
    return ACCEPTED


def verify_commit(chain_id: str, vset, block_id, height: int, commit,
                  oracle=data.oracle):
    """(verdict, bitmap of the non-absent rows in commit order).  `vset` is
    read as data: `validators[i].pub_key` and `.voting_power`.  `oracle`
    is `data.oracle` or something that calls it on slices."""
    rows = commit.signatures
    if len(vset.validators) != len(rows) or height != commit.height \
            or block_id != commit.block_id:
        return ("invalid", None), None
    idxs = [i for i, cs in enumerate(rows) if int(cs.block_id_flag) != ABSENT]
    bits = oracle(*data.commit_triples(chain_id, vset, commit, idxs))
    for i, ok in zip(idxs, bits):
        if not ok:
            return ("wrong_signature", i), bits
    tallied = sum(vset.validators[i].voting_power for i in idxs
                  if int(rows[i].block_id_flag) == COMMIT)
    needed = sum(v.voting_power for v in vset.validators) * 2 // 3
    if tallied <= needed:
        return ("not_enough_power", tallied, needed), bits
    return ACCEPTED, bits


def check(chain_id: str, vset, block_id, height: int, commit,
          oracle=data.oracle):
    """What a node does to a block's LastCommit: `validate_basic`, then
    `verify_commit`.  Returns (verdict, bitmap or None)."""
    verdict = validate_basic(commit)
    if verdict != ACCEPTED:
        return verdict, None
    return verify_commit(chain_id, vset, block_id, height, commit, oracle)
