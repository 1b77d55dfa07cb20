"""The plain reference of a full node's commit check over a validator set in
three key schemes: what `val10k-mixed-commit`'s `correct` and the tier-1
tests compare `Commit.validate_basic` + `ValidatorSet.verify_commit` with.

`CommitSig.ValidateBasic`, `Commit.ValidateBasic` and `VerifyCommit` are
perfbench/reference/commit.py's (the reference's types/block.go and
types/validator_set.go :662-709); the per-row check is by the row's key
type, each written here from the scheme's public specification in plain
Python integers, one signature at a time, nothing batched:

- ed25519: one OpenSSL call (the `cryptography` package), as `data.oracle`;
- secp256k1: BIP-340 verification (lift_x, the tagged-hash challenge,
  R = sG - eP, R finite, even y, x = r; the range screens px, r < p and
  s < n) over SHA-256 of the sign bytes, which is what this fork of the
  reference signs (crypto/secp256k1/secp256k1.go:134-146, :195-213); the
  key is the 33-byte compressed form and its first byte must be 2 or 3, as
  secp256k1.go:203-212 parses it before the x-only check;
- sr25519: schnorrkel's verify (reference crypto/sr25519/pubkey.go:34-59):
  the signature's marker bit, s canonical, ristretto255 decoding of A and R
  with the canonical / non-negative screens (RFC 9496 section 4.3.1), the
  merlin transcript `SigningContext` with the empty context and the message
  under `sign-bytes`, then `proto-name`, `sign:pk`, `sign:R` and the
  challenge `sign:c`, over a STROBE-128 and a Keccak-f[1600] of its own.

It imports nothing of the program's secp256k1 / sr25519 code
(tendermint_tpu.crypto.{secp256k1,sr25519,_strobe,_ristretto}), nothing of
tendermint_tpu.ops and nothing native.  Departures from the specifications,
each marked where it is made: fixed-base multiples come from a table of
2^i * base (a speed matter: the sums are the same group elements); R is
ristretto-DECODED and compared as a point, as go-schnorrkel does, where
Rust schnorrkel compares R' re-encoded with the signature's bytes (the same
verdict: an encoding decodes iff it is what encoding its point gives); the
transcript prefix common to every signature (`SigningContext`, the empty
context) is computed once and copied.

A verdict is commit.py's tuple.  `check` may be handed a `memo` (dict): a
triple's verdict is remembered in it, so the rows a tampered commit shares
with the honest one are verified once within one `gen.check`.

The signers at the end (`Key`) make the traffic generator's keys and
signatures from a seed.  `correct` does not rest on them: every signature
they make is checked by the verifiers above and by the program.
"""
from __future__ import annotations

import functools
import hashlib

import numpy as np

from perfbench.reference import commit as plain

ACCEPTED = plain.ACCEPTED
ED25519, SECP256K1, SR25519 = "ed25519", "secp256k1", "sr25519"


# ---------------------------------------------------------------------------
# secp256k1, BIP-340
# ---------------------------------------------------------------------------

SECP_P = 2**256 - 2**32 - 977
SECP_N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
SECP_G = (0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798,
          0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8)


def _secp_add(a, b):
    """Affine addition on y^2 = x^3 + 7; None is the point at infinity."""
    if a is None:
        return b
    if b is None:
        return a
    (x1, y1), (x2, y2) = a, b
    if x1 == x2:
        if (y1 + y2) % SECP_P == 0:
            return None
        lam = 3 * x1 * x1 * pow(2 * y1, -1, SECP_P) % SECP_P
    else:
        lam = (y2 - y1) * pow(x2 - x1, -1, SECP_P) % SECP_P
    x3 = (lam * lam - x1 - x2) % SECP_P
    return x3, (lam * (x1 - x3) - y1) % SECP_P


def _bit_table(base, add, bits=256):
    """[2^i * base for i < bits].  Departure (speed): k * base is then the
    sum of the entries at k's set bits, no doubling at verification time."""
    out, cur = [], base
    for _ in range(bits):
        out.append(cur)
        cur = add(cur, cur)
    return out


def _table_mul(k: int, table, add, zero):
    acc, i = zero, 0
    while k:
        if k & 1:
            acc = add(acc, table[i])
        k >>= 1
        i += 1
    return acc


def _secp_mul(k: int, pt):
    """Double-and-add from the top bit."""
    acc = None
    for bit in bin(k)[2:] if k else "":
        acc = _secp_add(acc, acc)
        if bit == "1":
            acc = _secp_add(acc, pt)
    return acc


@functools.cache
def _secp_g_table():
    return _bit_table(SECP_G, _secp_add)


def _secp_mul_g(k: int):
    return _table_mul(k, _secp_g_table(), _secp_add, None)


def _secp_lift_x(x: int):
    """BIP-340 lift_x: the point with this x and an even y, or None."""
    if x >= SECP_P:
        return None
    c = (pow(x, 3, SECP_P) + 7) % SECP_P
    y = pow(c, (SECP_P + 1) // 4, SECP_P)
    if y * y % SECP_P != c:
        return None
    return x, (y if y % 2 == 0 else SECP_P - y)


def _tagged_hash(tag: bytes, data: bytes) -> bytes:
    t = hashlib.sha256(tag).digest()
    return hashlib.sha256(t + t + data).digest()


def bip340_verify(pub_x: bytes, m: bytes, sig: bytes) -> bool:
    """BIP-340 Verify(pk, m, sig) on a 32-byte x-only key and a 32-byte
    message."""
    if len(pub_x) != 32 or len(sig) != 64:
        return False
    point = _secp_lift_x(int.from_bytes(pub_x, "big"))
    r = int.from_bytes(sig[:32], "big")
    s = int.from_bytes(sig[32:], "big")
    if point is None or r >= SECP_P or s >= SECP_N:
        return False
    e = int.from_bytes(_tagged_hash(
        b"BIP0340/challenge", sig[:32] + pub_x + m), "big") % SECP_N
    big_r = _secp_add(_secp_mul_g(s), _secp_mul(SECP_N - e, point))
    return big_r is not None and big_r[1] % 2 == 0 and big_r[0] == r


def secp256k1_verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    """The reference's secp256k1 PubKey.VerifySignature: the compressed
    key has to parse (33 bytes, first byte 2 or 3, x on the curve, which
    lift_x checks), then BIP-340 on its x alone over SHA-256(msg)."""
    if len(pub) != 33 or pub[0] not in (2, 3):
        return False
    return bip340_verify(pub[1:], hashlib.sha256(msg).digest(), sig)


# ---------------------------------------------------------------------------
# Keccak-f[1600], STROBE-128 as merlin uses it, merlin
# ---------------------------------------------------------------------------

_M64 = (1 << 64) - 1


def _rol64(v: int, n: int) -> int:
    return ((v << n) | (v >> (64 - n))) & _M64 if n else v


def keccak_f1600(state: bytearray):
    """The permutation, in place, on 200 bytes (FIPS 202 section 3.3):
    lane (x, y) is the little-endian 64 bits at byte 8 * (x + 5 * y)."""
    a = [int.from_bytes(state[8 * i:8 * i + 8], "little") for i in range(25)]
    lfsr = 1
    for _ in range(24):
        c = [a[x] ^ a[x + 5] ^ a[x + 10] ^ a[x + 15] ^ a[x + 20]
             for x in range(5)]
        d = [c[(x + 4) % 5] ^ _rol64(c[(x + 1) % 5], 1) for x in range(5)]
        a = [a[i] ^ d[i % 5] for i in range(25)]
        # rho and pi, walking the 24 lanes other than (0, 0)
        x, y, cur = 1, 0, a[1]
        for t in range(24):
            x, y = y, (2 * x + 3 * y) % 5
            a[x + 5 * y], cur = \
                _rol64(cur, (t + 1) * (t + 2) // 2 % 64), a[x + 5 * y]
        for y in range(0, 25, 5):
            row = a[y:y + 5]
            for x in range(5):
                a[y + x] = row[x] ^ (~row[(x + 1) % 5] & _M64
                                     & row[(x + 2) % 5])
        for j in range(7):      # iota: the round constant's bits, by LFSR
            lfsr = ((lfsr << 1) ^ ((lfsr >> 7) * 0x71)) & 0xFF
            if lfsr & 2:
                a[0] ^= 1 << ((1 << j) - 1)
    state[:] = b"".join(v.to_bytes(8, "little") for v in a)


_STROBE_R = 166
_FLAG_I, _FLAG_A, _FLAG_C, _FLAG_M = 1, 2, 4, 16


class Strobe128:
    """STROBE-128/1600 v1.0.2, the operations merlin calls (meta-AD, AD,
    PRF), never with a transport flag."""

    def __init__(self, protocol: bytes):
        self.state = bytearray(200)
        self.state[:6] = bytes([1, _STROBE_R + 2, 1, 0, 1, 96])
        self.state[6:18] = b"STROBEv1.0.2"
        keccak_f1600(self.state)
        self.pos = self.pos_begin = self.flags = 0
        self.meta_ad(protocol, False)

    def copy(self) -> "Strobe128":
        new = object.__new__(Strobe128)
        new.state = bytearray(self.state)
        new.pos, new.pos_begin, new.flags = \
            self.pos, self.pos_begin, self.flags
        return new

    def _run_f(self):
        self.state[self.pos] ^= self.pos_begin
        self.state[self.pos + 1] ^= 0x04
        self.state[_STROBE_R + 1] ^= 0x80
        keccak_f1600(self.state)
        self.pos = self.pos_begin = 0

    def _absorb(self, data: bytes):
        for byte in data:
            self.state[self.pos] ^= byte
            self.pos += 1
            if self.pos == _STROBE_R:
                self._run_f()

    def _squeeze(self, n: int) -> bytes:
        out = bytearray()
        for _ in range(n):
            out.append(self.state[self.pos])
            self.state[self.pos] = 0
            self.pos += 1
            if self.pos == _STROBE_R:
                self._run_f()
        return bytes(out)

    def _begin_op(self, flags: int, more: bool):
        if more:
            if flags != self.flags:
                raise ValueError("a continued operation changed its flags")
            return
        old_begin, self.pos_begin, self.flags = \
            self.pos_begin, self.pos + 1, flags
        self._absorb(bytes([old_begin, flags]))
        if flags & _FLAG_C and self.pos != 0:
            self._run_f()

    def meta_ad(self, data: bytes, more: bool):
        self._begin_op(_FLAG_M | _FLAG_A, more)
        self._absorb(data)

    def ad(self, data: bytes, more: bool):
        self._begin_op(_FLAG_A, more)
        self._absorb(data)

    def prf(self, n: int) -> bytes:
        self._begin_op(_FLAG_I | _FLAG_A | _FLAG_C, False)
        return self._squeeze(n)


class Transcript:
    """merlin's Transcript: `new`, `append_message`, `challenge_bytes`."""

    def __init__(self, label: bytes = None, strobe: Strobe128 = None):
        self.strobe = strobe or Strobe128(b"Merlin v1.0")
        if strobe is None:
            self.append_message(b"dom-sep", label)

    def copy(self) -> "Transcript":
        return Transcript(strobe=self.strobe.copy())

    def append_message(self, label: bytes, message: bytes):
        self.strobe.meta_ad(label, False)
        self.strobe.meta_ad(len(message).to_bytes(4, "little"), True)
        self.strobe.ad(message, False)

    def challenge_bytes(self, label: bytes, n: int) -> bytes:
        self.strobe.meta_ad(label, False)
        self.strobe.meta_ad(n.to_bytes(4, "little"), True)
        return self.strobe.prf(n)


# ---------------------------------------------------------------------------
# ristretto255 (RFC 9496) over edwards25519, schnorrkel's verify
# ---------------------------------------------------------------------------

ED_P = 2**255 - 19
ED_L = 2**252 + 27742317777372353535851937790883648493
ED_D = -121665 * pow(121666, -1, ED_P) % ED_P
SQRT_M1 = pow(2, (ED_P - 1) // 4, ED_P)
ED_IDENTITY = (0, 1, 1, 0)       # extended coordinates (X, Y, Z, T)


def _ed_add(p, q):
    """add-2008-hwcd-3 on -x^2 + y^2 = 1 + d x^2 y^2, complete."""
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = (y1 - x1) * (y2 - x2) % ED_P
    b = (y1 + x1) * (y2 + x2) % ED_P
    c = 2 * ED_D * t1 * t2 % ED_P
    d = 2 * z1 * z2 % ED_P
    e, f, g, h = b - a, d - c, d + c, b + a
    return e * f % ED_P, g * h % ED_P, f * g % ED_P, e * h % ED_P


def _ed_neg(p):
    return (-p[0] % ED_P, p[1], p[2], -p[3] % ED_P)


def _ed_mul(k: int, pt):
    acc = ED_IDENTITY
    for bit in bin(k)[2:] if k else "":
        acc = _ed_add(acc, acc)
        if bit == "1":
            acc = _ed_add(acc, pt)
    return acc


def _is_negative(x: int) -> bool:
    return x % ED_P % 2 == 1


def _sqrt_ratio_m1(u: int, v: int):
    """RFC 9496 section 4.2: (was_square, the non-negative root of u/v or
    of SQRT_M1 * u/v)."""
    v3 = v * v % ED_P * v % ED_P
    v7 = v3 * v3 % ED_P * v % ED_P
    r = u * v3 % ED_P * pow(u * v7 % ED_P, (ED_P - 5) // 8, ED_P) % ED_P
    check = v * r % ED_P * r % ED_P
    correct = check == u % ED_P
    flipped = check == -u % ED_P
    flipped_i = check == -u * SQRT_M1 % ED_P
    if flipped or flipped_i:
        r = r * SQRT_M1 % ED_P
    if _is_negative(r):
        r = -r % ED_P
    return correct or flipped, r


def ristretto_decode(enc: bytes):
    """RFC 9496 section 4.3.1: the point, or None for bytes that are no
    canonical encoding of a ristretto255 element."""
    if len(enc) != 32:
        return None
    s = int.from_bytes(enc, "little")
    if s >= ED_P or _is_negative(s):
        return None
    ss = s * s % ED_P
    u1, u2 = (1 - ss) % ED_P, (1 + ss) % ED_P
    u2_sqr = u2 * u2 % ED_P
    v = (-(ED_D * u1 % ED_P * u1) - u2_sqr) % ED_P
    was_square, invsqrt = _sqrt_ratio_m1(1, v * u2_sqr % ED_P)
    den_x = invsqrt * u2 % ED_P
    den_y = invsqrt * den_x % ED_P * v % ED_P
    x = 2 * s * den_x % ED_P
    if _is_negative(x):
        x = -x % ED_P
    y = u1 * den_y % ED_P
    t = x * y % ED_P
    if not was_square or _is_negative(t) or y == 0:
        return None
    return x, y, 1, t


def ristretto_encode(p) -> bytes:
    """RFC 9496 section 4.3.2 (the signers' and the tests')."""
    x0, y0, z0, t0 = p
    u1 = (z0 + y0) * (z0 - y0) % ED_P
    u2 = x0 * y0 % ED_P
    _, invsqrt = _sqrt_ratio_m1(1, u1 * u2 % ED_P * u2 % ED_P)
    den1, den2 = invsqrt * u1 % ED_P, invsqrt * u2 % ED_P
    z_inv = den1 * den2 % ED_P * t0 % ED_P
    invsqrt_a_minus_d = _sqrt_ratio_m1(1, (-1 - ED_D) % ED_P)[1]
    if _is_negative(t0 * z_inv):
        x, y = y0 * SQRT_M1 % ED_P, x0 * SQRT_M1 % ED_P
        den_inv = den1 * invsqrt_a_minus_d % ED_P
    else:
        x, y, den_inv = x0, y0, den2
    if _is_negative(x * z_inv):
        y = -y % ED_P
    s = (z0 - y) * den_inv % ED_P
    if _is_negative(s):
        s = -s % ED_P
    return s.to_bytes(32, "little")


def ristretto_equal(p, q) -> bool:
    """RFC 9496 section 4.3.3."""
    return (p[0] * q[1] - p[1] * q[0]) % ED_P == 0 \
        or (p[1] * q[1] - p[0] * q[0]) % ED_P == 0


def _ed_base():
    """The ed25519 base point, which generates ristretto255: y = 4/5 and
    the even x (RFC 8032 section 5.1)."""
    y = 4 * pow(5, -1, ED_P) % ED_P
    _, x = _sqrt_ratio_m1((y * y - 1) % ED_P, (ED_D * y * y + 1) % ED_P)
    return x, y, 1, x * y % ED_P


@functools.cache
def _ed_base_table():
    return _bit_table(_ed_base(), _ed_add)


def _ed_mul_base(k: int):
    return _table_mul(k, _ed_base_table(), _ed_add, ED_IDENTITY)


@functools.cache
def _signing_context() -> Transcript:
    t = Transcript(b"SigningContext")
    t.append_message(b"", b"")
    return t


def _signing_transcript(msg: bytes) -> Transcript:
    """schnorrkel's signing_context(b"").bytes(msg).  Departure (speed):
    the prefix every signature shares is made once and copied."""
    t = _signing_context().copy()
    t.append_message(b"sign-bytes", msg)
    return t


def _sr25519_challenge(t: Transcript, pub: bytes, r_enc: bytes) -> int:
    t.append_message(b"proto-name", b"Schnorr-sig")
    t.append_message(b"sign:pk", pub)
    t.append_message(b"sign:R", r_enc)
    return int.from_bytes(t.challenge_bytes(b"sign:c", 64), "little") % ED_L


def sr25519_verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    if len(pub) != 32 or len(sig) != 64 or not sig[63] & 0x80:
        return False
    s = int.from_bytes(sig[32:], "little") & ~(1 << 255)
    a_pt = ristretto_decode(pub)
    # departure: go-schnorrkel decodes R and compares points; Rust
    # schnorrkel compares R' encoded with these bytes.  Same verdicts.
    r_pt = ristretto_decode(sig[:32])
    if s >= ED_L or a_pt is None or r_pt is None:
        return False
    k = _sr25519_challenge(_signing_transcript(msg), pub, sig[:32])
    return ristretto_equal(
        _ed_add(_ed_mul_base(s), _ed_mul(k, _ed_neg(a_pt))), r_pt)


# ---------------------------------------------------------------------------
# the per-row check and the commit check
# ---------------------------------------------------------------------------

def ed25519_verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey)

    try:
        Ed25519PublicKey.from_public_bytes(pub).verify(sig, msg)
    except (InvalidSignature, ValueError):
        return False
    return True


VERIFIERS = {ED25519: ed25519_verify, SECP256K1: secp256k1_verify,
             SR25519: sr25519_verify}


def verify_rows(rows) -> np.ndarray:
    """The verdicts of (scheme, pub, msg, sig) rows, one call each: a
    module-level function of picklable data, so `data.fan_out` can spread
    slices of a 9,900-row commit over worker processes."""
    return np.array([VERIFIERS[scheme](bytes(pub), bytes(msg), bytes(sig))
                     for scheme, pub, msg, sig in rows], dtype=bool)


def commit_rows(chain_id: str, vset, commit, idxs) -> list:
    """(scheme, key bytes, sign bytes, signature) of the commit's rows
    `idxs`: the set read as data (`validators[i].pub_key`)."""
    return [(vset.validators[i].pub_key.type_name,
             vset.validators[i].pub_key.bytes(),
             commit.vote_sign_bytes(chain_id, i),
             commit.signatures[i].signature) for i in idxs]


def verify_commit(chain_id: str, vset, block_id, height: int, commit,
                  oracle=verify_rows, memo: dict = None):
    """commit.py's verify_commit with the per-row check by key type.
    `oracle` is `verify_rows` or something that calls it on slices;
    `memo` remembers a row's verdict."""
    rows = commit.signatures
    if len(vset.validators) != len(rows) or height != commit.height \
            or block_id != commit.block_id:
        return ("invalid", None), None
    idxs = [i for i, cs in enumerate(rows)
            if int(cs.block_id_flag) != plain.ABSENT]
    triples = commit_rows(chain_id, vset, commit, idxs)
    memo = {} if memo is None else memo
    fresh = [t for t in dict.fromkeys(triples) if t not in memo]
    memo.update(zip(fresh, (bool(b) for b in oracle(fresh))))
    bits = np.array([memo[t] for t in triples], dtype=bool)
    for i, ok in zip(idxs, bits):
        if not ok:
            return ("wrong_signature", i), bits
    tallied = sum(vset.validators[i].voting_power for i in idxs
                  if int(rows[i].block_id_flag) == plain.COMMIT)
    needed = sum(v.voting_power for v in vset.validators) * 2 // 3
    if tallied <= needed:
        return ("not_enough_power", tallied, needed), bits
    return ACCEPTED, bits


def check(chain_id: str, vset, block_id, height: int, commit,
          oracle=verify_rows, memo: dict = None):
    """What a node does to a block's LastCommit: `validate_basic`, then
    `verify_commit`.  Returns (verdict, bitmap or None)."""
    verdict = plain.validate_basic(commit)
    if verdict != ACCEPTED:
        return verdict, None
    return verify_commit(chain_id, vset, block_id, height, commit, oracle,
                         memo)


# ---------------------------------------------------------------------------
# keys and signers for the traffic generator (not part of the reference)
# ---------------------------------------------------------------------------

class Key:
    """One seeded validator key of `scheme`: `pub_bytes`, `sign(msg)`.
    ed25519 by OpenSSL, as data.Key; secp256k1 by BIP-340's default
    signing with zero auxiliary randomness over SHA-256(msg), the key
    (sha256(secret) mod (n - 1)) + 1 as the reference's
    GenPrivKeySecp256k1 derives it; sr25519 by schnorrkel's sign with
    MiniSecretKey.ExpandEd25519 and a witness hashed from the nonce half,
    the key and the message in place of its random one."""
    __slots__ = ("scheme", "pub_bytes", "sign")

    def __init__(self, scheme: str, seed32: bytes):
        self.scheme = scheme
        self.pub_bytes, self.sign = _KEY_MAKERS[scheme](seed32)


def _ed25519_key(seed32: bytes):
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey)

    k = Ed25519PrivateKey.from_private_bytes(seed32)
    return k.public_key().public_bytes_raw(), k.sign


def _secp256k1_key(seed32: bytes):
    d0 = int.from_bytes(hashlib.sha256(seed32).digest(), "big") \
        % (SECP_N - 1) + 1
    px, py = _secp_mul_g(d0)
    d = d0 if py % 2 == 0 else SECP_N - d0
    pxb = px.to_bytes(32, "big")
    mask = int.from_bytes(_tagged_hash(b"BIP0340/aux", bytes(32)), "big")

    def sign(msg: bytes) -> bytes:
        m = hashlib.sha256(msg).digest()
        k0 = int.from_bytes(_tagged_hash(
            b"BIP0340/nonce", (d ^ mask).to_bytes(32, "big") + pxb + m),
            "big") % SECP_N
        rx, ry = _secp_mul_g(k0)
        k = k0 if ry % 2 == 0 else SECP_N - k0
        rxb = rx.to_bytes(32, "big")
        e = int.from_bytes(_tagged_hash(
            b"BIP0340/challenge", rxb + pxb + m), "big") % SECP_N
        return rxb + ((k + e * d) % SECP_N).to_bytes(32, "big")

    return bytes([2 + py % 2]) + pxb, sign


def _sr25519_key(seed32: bytes):
    h = bytearray(hashlib.sha512(seed32).digest())
    h[0] &= 248
    h[31] &= 63
    h[31] |= 64
    scalar = int.from_bytes(h[:32], "little") >> 3
    nonce = bytes(h[32:])
    pub = ristretto_encode(_ed_mul_base(scalar))

    def sign(msg: bytes) -> bytes:
        r = int.from_bytes(hashlib.sha512(nonce + pub + msg).digest(),
                           "little") % ED_L
        r_enc = ristretto_encode(_ed_mul_base(r))
        k = _sr25519_challenge(_signing_transcript(msg), pub, r_enc)
        s = (k * scalar + r) % ED_L
        return r_enc + (s | 1 << 255).to_bytes(32, "little")

    return pub, sign


_KEY_MAKERS = {ED25519: _ed25519_key, SECP256K1: _secp256k1_key,
               SR25519: _sr25519_key}
