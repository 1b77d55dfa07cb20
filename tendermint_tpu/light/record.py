"""The bytes a LightStore keeps for one light block.

A light block of 10,000 validators is ~40,000 small objects (Validator,
PubKey, CommitSig, Timestamp), and the generic codec walks them one by
one: 2.29 MB and most of what a save cost.  The record here writes the
same information as columns, one `b"".join` / `struct.pack` a field:

    MAGIC, one version byte, then frames of (u32 length, payload):
      head        safe_codec of (header, commit height, round, block id,
                  count of validators, of signatures, the proposer's
                  index or None, total voting power): a dozen small
                  objects
      validators  addresses, key types, key bytes, voting powers (int64),
                  proposer priorities (int64)
      signatures  block-id flags, addresses, timestamp seconds and nanos
                  (int64), signatures

Widths are observed, never assumed: a bytes column whose rows are all of
one width is that width and the rows; any other carries a u32 length a
row (a commit with ABSENT rows has empty addresses and signatures beside
full ones).  Key types are one byte for a set of one scheme, a byte a row
otherwise.  A block the columns cannot hold exactly (a field that is not
of its declared type, a power outside int64, a proposer that is not an
element of the list, a key scheme without a code, an attribute nobody
declared) goes through the generic codec whole, under a version of its
own.  Which of the two is decided by the block, by nothing else.

No derived state is written: no root, no address index, no pubkey matrix
(ValidatorSet.__getstate__: a set that arrives as bytes hashes its own
bytes).  The set is rebuilt as unpickling builds one, without __init__:
the stored order, proposer and total ARE the state.

A value that begins with pickle's protocol-4 mark is what an earlier
build wrote, and is read through the generic codec as it always was.
Every count and length is held against the bytes that are there; a
record that is short, long or at odds with itself raises RecordError.
"""
from __future__ import annotations

import struct
from itertools import accumulate
from typing import List, Tuple

from tendermint_tpu.crypto import ed25519, secp256k1, sr25519
from tendermint_tpu.libs import safe_codec
from tendermint_tpu.types.basic import BlockID, BlockIDFlag, Timestamp
from tendermint_tpu.types.block import Header
from tendermint_tpu.types.commit import Commit, CommitSig
from tendermint_tpu.types.light_block import LightBlock, SignedHeader
from tendermint_tpu.types.validator import Validator
from tendermint_tpu.types.validator_set import ValidatorSet

MAGIC = b"\xffTLB"
V_GENERIC, V_COLUMNS = 1, 2
LEGACY_MARK = b"\x80\x04"           # pickle.PROTO, protocol 4
COLUMNS, GENERIC, LEGACY = "columns", "generic", "legacy"

_KEY_CODES = {ed25519.PubKey: 1, secp256k1.PubKey: 2, sr25519.PubKey: 3}
_KEY_CLASSES = {code: cls for cls, code in _KEY_CODES.items()}
_FLAGS = {int(f): f for f in BlockIDFlag}
_UNIFORM, _RAGGED = b"U", b"R"
_U32 = struct.Struct("<I")
_N_FRAMES = 11                      # the head and ten columns


class RecordError(ValueError):
    """The bytes are not a record this build can read."""


class _NotColumnar(Exception):
    """The block holds something the columns would not give back."""


# ---------------------------------------------------------------------------
# encode
# ---------------------------------------------------------------------------

def encode(lb: LightBlock) -> Tuple[bytes, str]:
    """(the record, which kind it is: COLUMNS or GENERIC)."""
    try:
        frames = _columns(lb)
    except _NotColumnar:
        return MAGIC + bytes([V_GENERIC]) + safe_codec.dumps(lb), GENERIC
    out = [MAGIC + bytes([V_COLUMNS])]
    for frame in frames:
        out.append(_U32.pack(len(frame)))
        out.append(frame)
    return b"".join(out), COLUMNS


def _plain(obj, cls, fields) -> dict:
    """`obj`'s attributes, if it is a `cls` holding exactly `fields`."""
    if type(obj) is not cls or obj.__dict__.keys() != fields:
        raise _NotColumnar
    return obj.__dict__


def _all_are(items, cls):
    if not set(map(type, items)) <= {cls}:
        raise _NotColumnar


def _columns(lb: LightBlock) -> List[bytes]:
    top = _plain(lb, LightBlock, {"signed_header", "validators"})
    sh = _plain(top["signed_header"], SignedHeader, {"header", "commit"})
    commit = _plain(sh["commit"], Commit,
                    {"height", "round", "block_id", "signatures"})
    vset = top["validators"]
    if type(vset) is not ValidatorSet:
        raise _NotColumnar
    state = vset.__getstate__()
    if state.keys() != {"validators", "proposer", "_total_voting_power"}:
        raise _NotColumnar
    vals, sigs = state["validators"], commit["signatures"]
    if type(vals) is not list or type(sigs) is not list:
        raise _NotColumnar
    _all_are(vals, Validator)
    _all_are(sigs, CommitSig)

    addresses = [v.address for v in vals]
    keys = [v.pub_key for v in vals]
    proposer = state["proposer"]
    if proposer is not None:
        # the list's own element, or one equal to it in every field
        try:
            at = addresses.index(proposer.address)
        except (ValueError, AttributeError):
            raise _NotColumnar from None
        if vals[at] is not proposer and (type(proposer) is not Validator
                                         or vals[at] != proposer):
            raise _NotColumnar
        proposer = at
    kinds = set(map(type, keys))
    if not kinds <= _KEY_CODES.keys():
        raise _NotColumnar
    if len(kinds) == 1:
        key_codes = bytes([_KEY_CODES[kinds.pop()]])
    else:
        key_codes = bytes([_KEY_CODES[type(k)] for k in keys])
    stamps = [cs.timestamp for cs in sigs]
    flags = [cs.block_id_flag for cs in sigs]
    _all_are(stamps, Timestamp)
    _all_are(flags, BlockIDFlag)

    head = safe_codec.dumps((
        sh["header"], commit["height"], commit["round"], commit["block_id"],
        len(vals), len(sigs), proposer, state["_total_voting_power"]))
    return [
        head,
        _bytes_column(addresses),
        key_codes,
        _bytes_column([k.bytes() for k in keys]),
        _int_column([v.voting_power for v in vals]),
        _int_column([v.proposer_priority for v in vals]),
        bytes(flags),
        _bytes_column([cs.validator_address for cs in sigs]),
        _int_column([t.seconds for t in stamps]),
        _int_column([t.nanos for t in stamps]),
        _bytes_column([cs.signature for cs in sigs]),
    ]


def _bytes_column(rows: List[bytes]) -> bytes:
    _all_are(rows, bytes)
    widths = set(map(len, rows))
    if len(widths) <= 1:
        return b"".join([_UNIFORM, _U32.pack(widths.pop() if widths else 0),
                         *rows])
    return b"".join([_RAGGED, _pack("I", map(len, rows)), *rows])


def _int_column(values: List[int]) -> bytes:
    _all_are(values, int)
    try:
        return _pack("q", values)
    except struct.error:            # beyond int64
        raise _NotColumnar from None


def _pack(fmt: str, values) -> bytes:
    """`values` as one little-endian column of `fmt`."""
    values = tuple(values)
    return struct.pack(f"<{len(values)}{fmt}", *values)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def decode(raw: bytes) -> Tuple[LightBlock, str]:
    """(the light block, which kind of record held it); RecordError for
    bytes that are no whole record."""
    if raw[:2] == LEGACY_MARK:
        return _generic(raw), LEGACY
    if raw[:len(MAGIC)] != MAGIC or len(raw) <= len(MAGIC):
        raise RecordError("not a light block record")
    version, body = raw[len(MAGIC)], memoryview(raw)[len(MAGIC) + 1:]
    if version == V_GENERIC:
        return _generic(body), GENERIC
    if version != V_COLUMNS:
        raise RecordError(f"light block record of unknown version {version}")
    try:
        return _from_columns(_frames(body)), COLUMNS
    except RecordError:
        raise
    except Exception as e:      # a column at odds with what it should hold
        raise RecordError(f"inconsistent light block record: {e!r}") from e


def _generic(raw) -> LightBlock:
    try:
        lb = safe_codec.loads(raw)
    except Exception as e:
        raise RecordError(f"unreadable light block record: {e!r}") from e
    if type(lb) is not LightBlock:
        raise RecordError(f"a {type(lb).__name__}, not a light block")
    return lb


def _frames(body: memoryview) -> List[bytes]:
    frames, at = [], 0
    while at < len(body):
        if at + 4 > len(body):
            raise RecordError("truncated light block record")
        (n,) = _U32.unpack_from(body, at)
        at += 4
        if at + n > len(body):
            raise RecordError("truncated light block record")
        frames.append(bytes(body[at:at + n]))
        at += n
    if len(frames) != _N_FRAMES:
        raise RecordError(f"{len(frames)} frames in a record of {_N_FRAMES}")
    return frames


def _from_columns(frames: List[bytes]) -> LightBlock:
    (head, addresses, key_codes, keys, powers, priorities,
     flags, sig_addresses, seconds, nanos, signatures) = frames
    head = safe_codec.loads(head)
    if type(head) is not tuple or len(head) != 8:
        raise RecordError("malformed head")
    header, height, round_, block_id, n_vals, n_sigs, proposer, total = head
    if type(header) is not Header or type(block_id) is not BlockID \
            or not all(type(x) is int for x in
                       (height, round_, n_vals, n_sigs, total)) \
            or min(n_vals, n_sigs) < 0:
        raise RecordError("malformed head")

    # the int64 columns and the flags are a fixed width a row, so they
    # hold the counts to the bytes that are there before anything is
    # sized by them
    powers = _int_rows(powers, n_vals)
    if len(flags) != n_sigs:
        raise RecordError("flags at odds with the signature count")
    if len(key_codes) == 1:
        key_codes = key_codes * n_vals
    elif len(key_codes) != n_vals:
        raise RecordError("key types at odds with the validator count")
    key_rows = _bytes_rows(keys, n_vals)
    if set(key_codes) <= {1}:
        pub_keys = list(map(ed25519.PubKey, key_rows))
    else:
        pub_keys = [_KEY_CLASSES[c](k) for c, k in zip(key_codes, key_rows)]
    vals = list(map(Validator, _bytes_rows(addresses, n_vals), pub_keys,
                    powers, _int_rows(priorities, n_vals)))
    if proposer is not None:
        if type(proposer) is not int or not 0 <= proposer < n_vals:
            raise RecordError("proposer outside the validators")
        proposer = vals[proposer]
    vset = ValidatorSet.__new__(ValidatorSet)
    vset.__dict__.update(validators=vals, proposer=proposer,
                         _total_voting_power=total)

    stamps = map(Timestamp, _int_rows(seconds, n_sigs),
                 _int_rows(nanos, n_sigs))
    sigs = list(map(CommitSig, [_FLAGS[f] for f in flags],
                    _bytes_rows(sig_addresses, n_sigs), stamps,
                    _bytes_rows(signatures, n_sigs)))
    return LightBlock(
        SignedHeader(header, Commit(height, round_, block_id, sigs)), vset)


def _bytes_rows(column: bytes, n: int) -> List[bytes]:
    kind, data = column[:1], column[1:]
    if kind == _UNIFORM:
        if len(data) < 4:
            raise RecordError("truncated column")
        (width,) = _U32.unpack_from(data)
        if len(data) - 4 != n * width:
            raise RecordError("column at odds with its row count")
        return [data[i:i + width] for i in range(4, 4 + n * width, width)] \
            if width else [b""] * n
    if kind != _RAGGED or len(data) < 4 * n:
        raise RecordError("malformed column")
    lens = struct.unpack_from(f"<{n}I", data)
    ends = list(accumulate(lens, initial=4 * n))
    if ends[-1] != len(data):
        raise RecordError("column at odds with its row lengths")
    return list(map(data.__getitem__, map(slice, ends, ends[1:])))


def _int_rows(column: bytes, n: int) -> Tuple[int, ...]:
    if len(column) != 8 * n:
        raise RecordError("column at odds with its row count")
    return struct.unpack(f"<{n}q", column)
