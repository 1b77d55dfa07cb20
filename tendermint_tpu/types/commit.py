"""Commit and CommitSig (reference types/block.go:556-830).

Commit.Signatures[i] corresponds 1:1 with ValidatorSet.Validators[i]; the
per-validator sign bytes differ only in Timestamp (reference
types/block.go:799-804), which makes whole-commit verification a natural
fixed-shape TPU batch.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from itertools import compress
from operator import attrgetter
from typing import Dict, List, Optional, Sequence

import numpy as np

from tendermint_tpu.crypto import merkle
from tendermint_tpu.libs import protodec as pd
from tendermint_tpu.libs import protoenc as pe
from tendermint_tpu.libs import trace

from .basic import BlockID, BlockIDFlag, SignedMsgType, Timestamp
from .canonical import canonical_vote_bytes


@dataclass
class CommitSig:
    block_id_flag: BlockIDFlag
    validator_address: bytes = b""
    timestamp: Timestamp = field(default_factory=Timestamp.zero)
    signature: bytes = b""

    @classmethod
    def absent(cls) -> "CommitSig":
        """No vote received from this validator (reference
        types/block.go:628)."""
        return cls(block_id_flag=BlockIDFlag.ABSENT)

    def is_absent(self) -> bool:
        return self.block_id_flag == BlockIDFlag.ABSENT

    def for_block(self) -> bool:
        return self.block_id_flag == BlockIDFlag.COMMIT

    def block_id(self, commit_block_id: BlockID) -> BlockID:
        """The BlockID this sig voted for (reference types/block.go:722)."""
        if self.block_id_flag == BlockIDFlag.COMMIT:
            return commit_block_id
        return BlockID()

    def proto(self) -> bytes:
        return (
            pe.varint_field(1, int(self.block_id_flag))
            + pe.bytes_field(2, self.validator_address)
            + pe.message_field_always(3, self.timestamp.proto())
            + pe.bytes_field(4, self.signature)
        )

    @classmethod
    def from_proto(cls, body: bytes) -> "CommitSig":
        f = pd.parse(body)
        ts = pd.get_message(f, 3)
        try:
            flag = BlockIDFlag(pd.get_int(f, 1, 0))
        except ValueError as e:
            raise pd.ProtoError(f"bad BlockIDFlag: {e}") from e
        return cls(
            block_id_flag=flag,
            validator_address=pd.get_bytes(f, 2),
            timestamp=(Timestamp.from_proto(ts) if ts is not None
                       else Timestamp.zero()),
            signature=pd.get_bytes(f, 4))

    def validate_basic(self):
        if self.block_id_flag not in (BlockIDFlag.ABSENT, BlockIDFlag.COMMIT,
                                      BlockIDFlag.NIL):
            raise ValueError(f"unknown BlockIDFlag {self.block_id_flag}")
        if self.block_id_flag == BlockIDFlag.ABSENT:
            if self.validator_address:
                raise ValueError("absent sig has validator address")
            if not self.timestamp.is_zero():
                raise ValueError("absent sig has non-zero timestamp")
            if self.signature:
                raise ValueError("absent sig has signature")
        else:
            if len(self.validator_address) != 20:
                raise ValueError("wrong validator address size")
            if not self.signature:
                raise ValueError("signature is missing")
            if len(self.signature) > 64:
                raise ValueError("signature too big")


_FLAG = attrgetter("block_id_flag")
_ADDRESS = attrgetter("validator_address")
_SIGNATURE = attrgetter("signature")
_SECONDS = attrgetter("timestamp.seconds")
_NANOS = attrgetter("timestamp.nanos")


def _flag_column(sigs: Sequence[CommitSig]) -> np.ndarray:
    try:
        return np.frombuffer(bytes(map(_FLAG, sigs)), dtype=np.uint8)
    except (TypeError, ValueError):
        # a flag no uint8 holds is as unknown to every rule as UNKNOWN
        # itself, and reads as it; what the row says of itself is
        # CommitSig.validate_basic's to tell
        return np.frombuffer(
            bytes(f if isinstance(f, int) and 0 <= f <= 255 else 0
                  for f in map(_FLAG, sigs)), dtype=np.uint8)


def _length_column(values: Sequence[bytes]) -> np.ndarray:
    """len() of each value, saturating at 255: the rules compare a
    length with 0, 20 and 64 only."""
    try:
        return np.frombuffer(bytes(map(len, values)), dtype=np.uint8)
    except ValueError:
        return np.minimum(
            np.fromiter(map(len, values), dtype=np.int64,
                        count=len(values)), 255).astype(np.uint8)


def _columns(sigs: Sequence[CommitSig],
             fields: Sequence[str]) -> Dict[str, np.ndarray]:
    """The fields asked for, of every row of `sigs`, as numpy columns:
    `flag` (uint8), `sig_len` and `addr_len` (uint8, saturating),
    `seconds` and `nanos` (int64), and `sig`, the (m, 64) uint8 matrix
    of the m rows whose signature is 64 bytes long, in row order (it
    brings `sig_len`, which says which rows those are).

    Each field is one C-level pass over the rows (map + attrgetter into
    bytes / join / fromiter): what the entry points did by a method call
    or a generator step a row.  ONE span a call.  Nothing is kept: no
    attribute is set on the rows or their list, and a second call reads
    them again; every height's commit is a new object to a node, so a
    memo here would only teach a benchmark's ring to stop measuring."""
    n = len(sigs)
    with trace.span("commit.columns", rows=n, fields=",".join(fields)):
        cols = {}
        if "flag" in fields:
            cols["flag"] = _flag_column(sigs)
        if "addr_len" in fields:
            cols["addr_len"] = _length_column(list(map(_ADDRESS, sigs)))
        if "sig_len" in fields or "sig" in fields:
            raw = list(map(_SIGNATURE, sigs))
            sig_len = cols["sig_len"] = _length_column(raw)
            if "sig" in fields:
                whole = sig_len == 64
                if not (whole | (sig_len == 0)).all():
                    raw = compress(raw, whole.tolist())
                cols["sig"] = np.frombuffer(
                    b"".join(raw), dtype=np.uint8).reshape(-1, 64)
        if "seconds" in fields:
            cols["seconds"] = np.fromiter(map(_SECONDS, sigs),
                                          dtype=np.int64, count=n)
        if "nanos" in fields:
            cols["nanos"] = np.fromiter(map(_NANOS, sigs),
                                        dtype=np.int64, count=n)
        return cols


@dataclass
class Commit:
    height: int
    round: int
    block_id: BlockID
    signatures: List[CommitSig]

    def size(self) -> int:
        return len(self.signatures)

    def vote_sign_bytes(self, chain_id: str, idx: int) -> bytes:
        """Sign bytes of the precommit at idx (reference
        types/block.go:808-811)."""
        cs = self.signatures[idx]
        return canonical_vote_bytes(
            chain_id, SignedMsgType.PRECOMMIT, self.height, self.round,
            cs.block_id(self.block_id), cs.timestamp)

    def proto(self) -> bytes:
        return (
            pe.varint_field(1, self.height)
            + pe.varint_field(2, self.round)
            + pe.message_field_always(3, self.block_id.proto())
            + pe.repeated_message_field(4, [s.proto() for s in self.signatures])
        )

    @classmethod
    def from_proto(cls, body: bytes) -> "Commit":
        f = pd.parse(body)
        bid = pd.get_message(f, 3)
        return cls(
            height=pd.get_int(f, 1, 0),
            round=pd.get_int(f, 2, 0),
            block_id=(BlockID.from_proto(bid) if bid is not None
                      else BlockID()),
            signatures=[CommitSig.from_proto(s)
                        for s in pd.get_messages(f, 4)])

    def hash(self) -> bytes:
        """Merkle root of the proto-encoded signatures (reference
        types/block.go:700-711)."""
        return merkle.hash_from_byte_slices(
            [s.proto() for s in self.signatures])

    def validate_basic(self):
        # ONE span a commit, around the loop over its signatures: the
        # light verifier (through SignedHeader.validate_basic) and the
        # node (Block.validate_basic, a LastCommit before verify_commit)
        # both come through here
        with trace.span("commit.validate_basic",
                        sigs=len(self.signatures)):
            if self.height < 0:
                raise ValueError("negative height")
            if self.round < 0:
                raise ValueError("negative round")
            if self.height >= 1:
                if self.block_id.is_zero():
                    raise ValueError("commit cannot be for nil block")
                if not self.signatures:
                    raise ValueError("no signatures in commit")
                for i in self._suspect_rows():
                    # the per-row method is the one statement of the
                    # rule and of its messages: the columns only say
                    # which rows to ask
                    try:
                        self.signatures[i].validate_basic()
                    except ValueError as e:
                        raise ValueError(
                            f"wrong CommitSig #{i}: {e}") from e

    def _suspect_rows(self) -> List[int]:
        """Indices, ascending, of every row CommitSig.validate_basic
        would refuse (and of no other), from boolean masks over the
        rows' columns; the timestamps of the absent rows alone are read
        by the row (1% of a commit)."""
        sigs = self.signatures
        cols = _columns(sigs, ("flag", "sig_len", "addr_len"))
        flag, sig_len, addr_len = (cols["flag"], cols["sig_len"],
                                   cols["addr_len"])
        absent = flag == BlockIDFlag.ABSENT
        voted = (flag == BlockIDFlag.COMMIT) | (flag == BlockIDFlag.NIL)
        ok = np.where(absent, (addr_len == 0) & (sig_len == 0),
                      voted & (addr_len == 20) & (sig_len > 0)
                      & (sig_len <= 64))
        for i in np.flatnonzero(absent & ok).tolist():
            if not sigs[i].timestamp.is_zero():
                ok[i] = False
        return np.flatnonzero(~ok).tolist()
