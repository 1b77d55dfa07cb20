"""Canonical sign-bytes encoders (reference types/canonical.go:42-66,
proto/tendermint/types/canonical.proto, canonical.pb.go:517-567).

These byte layouts are the *messages the TPU kernel verifies* — every
(pubkey, msg, sig) triple's msg comes from here, so they must match the
reference bit-for-bit.  Per-validator commit messages differ only in the
Timestamp field (reference types/block.go:799-804), which is what makes
commit batches near-constant-length.
"""
from __future__ import annotations

from tendermint_tpu.libs import protoenc as pe

from .basic import BlockID, SignedMsgType, Timestamp


def canonical_vote_bytes(chain_id: str, vtype: SignedMsgType, height: int,
                         round_: int, block_id: BlockID,
                         timestamp: Timestamp) -> bytes:
    """Length-delimited CanonicalVote encoding = Vote/Precommit sign bytes
    (reference types/vote.go:93, canonical.pb.go CanonicalVote)."""
    body = (
        pe.varint_field(1, int(vtype))
        + pe.sfixed64_field(2, height)
        + pe.sfixed64_field(3, round_)
        + pe.message_field(4, block_id.canonical_proto())
        + pe.message_field_always(5, timestamp.proto())
        + pe.string_field(6, chain_id)
    )
    return pe.length_delimited(body)


def commit_sign_bytes_batch(chain_id: str, commit, indices, cols=None):
    """Sign bytes of the precommits at `indices` of one commit, assembled
    as a batch (RaggedBytes).

    Within a commit the per-validator encodings share everything except the
    Timestamp field and the BlockID variant (for-block vs nil — reference
    types/block.go:799-811), so fields 1..4 are encoded once per variant
    and only the timestamp is encoded per entry (native/staging.c
    tm_vote_sign_bytes; numpy-free Python fallback below).  Byte-identical
    to canonical_vote_bytes per index (tests/test_types.py).

    `cols` holds the rows' `seconds`, `nanos` and `flag` columns
    (types/commit._columns) where the caller has read them, over the
    commit's rows from the first up to the last index at least; without
    them they are read here, once, and the per-entry inputs are numpy
    takes at `indices` either way.
    """
    import numpy as np

    from tendermint_tpu.libs import native
    from tendermint_tpu.libs.ragged import RaggedBytes

    from .basic import BlockIDFlag

    head = (pe.varint_field(1, int(SignedMsgType.PRECOMMIT))
            + pe.sfixed64_field(2, commit.height)
            + pe.sfixed64_field(3, commit.round))
    prefix0 = head + pe.message_field(4, commit.block_id.canonical_proto())
    prefix1 = head  # nil vote: zero BlockID encodes to an absent field 4
    suffix = pe.string_field(6, chain_id)

    if cols is None:
        from .commit import _columns
        cols = _columns(commit.signatures, ("seconds", "nanos", "flag"))
    indices = np.asarray(indices, dtype=np.int64)
    n = len(indices)
    seconds = cols["seconds"][indices]
    nanos = cols["nanos"][indices]
    variant = (cols["flag"][indices] != BlockIDFlag.COMMIT).astype(np.uint8)
    out = native.vote_sign_bytes(seconds, nanos, variant,
                                 prefix0, prefix1, suffix)
    if out is not None:
        return RaggedBytes(*out)
    # no C toolchain: per-index Python assembly (same shared-prefix trick)
    pieces = []
    for j in range(n):
        ts = pe.timestamp_msg(int(seconds[j]), int(nanos[j]))
        body = ((prefix1 if variant[j] else prefix0)
                + pe.message_field_always(5, ts) + suffix)
        pieces.append(pe.length_delimited(body))
    return RaggedBytes.from_list(pieces)


def canonical_proposal_bytes(chain_id: str, height: int, round_: int,
                             pol_round: int, block_id: BlockID,
                             timestamp: Timestamp) -> bytes:
    """Length-delimited CanonicalProposal encoding = Proposal sign bytes
    (reference types/proposal.go SignBytes, canonical.pb.go)."""
    body = (
        pe.varint_field(1, int(SignedMsgType.PROPOSAL))
        + pe.sfixed64_field(2, height)
        + pe.sfixed64_field(3, round_)
        + pe.varint_field(4, pol_round)
        + pe.message_field(5, block_id.canonical_proto())
        + pe.message_field_always(6, timestamp.proto())
        + pe.string_field(7, chain_id)
    )
    return pe.length_delimited(body)
