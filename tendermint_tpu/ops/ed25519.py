"""Batched ed25519 signature verification on TPU.

The data-plane replacement for the reference's per-signature serial loop
(reference: crypto/ed25519/ed25519.go:148-155 called from
types/validator_set.go:680-702 and types/vote.go:147): a whole batch of
(pubkey, msg, sig) triples is verified at once, one signature per TPU vector
lane.

Verification is the exact cofactorless RFC 8032 / Go-crypto check: decode
A and reject bad encodings, reject s >= L, compute k = SHA-512(R || A || M)
mod L, and accept iff encode([s]B + [k](-A)) == R byte-for-byte (which also
rejects non-canonical R).  No batch-random-linear-combination tricks: every
lane is an independent exact verify, so a failing lane is identified for
free (the caller gets a bitmap, matching VerifyCommit's check-all semantics,
reference types/validator_set.go:657-661).

Split of labor:
  host (numpy / hashlib): parse 32/64-byte encodings, SHA-512 challenge
    hashing + reduction mod L, signed radix-16 digit decomposition,
    s < L canonicity.
  device (jit, batched over lanes): point decompression, the 64-iteration
    joint Straus ladder (4 doublings + 1 fixed-base niels add + 1
    variable-base cached add per digit position), final encode + compare.
"""
from __future__ import annotations

import atexit
import hashlib
import threading
import time
from functools import partial
from types import MappingProxyType
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from tendermint_tpu.crypto import degrade, devobs
from tendermint_tpu.libs import fail, trace
from . import field as F
from . import curve as C

# group order
L = (1 << 252) + 27742317777372353535851937790883648493

# ---------------------------------------------------------------------------
# import-time static basepoint table: j*B for j = 0..8 in niels form
# ---------------------------------------------------------------------------

def _affine_niels_ints(x: int, y: int):
    return ((y + x) % C.P, (y - x) % C.P, 2 * C.D_INT * x % C.P * y % C.P)

def _edwards_add_int(p, q):
    """Affine edwards addition in Python bignum (import-time/lazy static
    table construction only)."""
    x1, y1 = p
    x2, y2 = q
    den = C.D_INT * x1 * x2 % C.P * y1 % C.P * y2 % C.P
    x3 = (x1 * y2 + x2 * y1) * pow(1 + den, C.P - 2, C.P)
    y3 = (y1 * y2 + x1 * x2) * pow(1 - den, C.P - 2, C.P)
    return (x3 % C.P, y3 % C.P)

def _niels_rows(pts):
    """[(x, y)] -> ((len, NLIMB) ypx, ymx, t2d) numpy niels limb rows."""
    ypx = np.stack([F.int_to_limbs((y + x) % C.P) for x, y in pts])
    ymx = np.stack([F.int_to_limbs((y - x) % C.P) for x, y in pts])
    t2d = np.stack([F.int_to_limbs(C.D2_INT * x % C.P * y % C.P)
                    for x, y in pts])
    return ypx, ymx, t2d

def _window_pts(base):
    """[j * base] for j = 0..8 — the signed-radix-16 window points of
    one table row, shared by the static ladder table and the comb."""
    pts = [(0, 1)]
    acc = (0, 1)
    for _ in range(8):
        acc = _edwards_add_int(acc, base)
        pts.append(acc)
    return pts


def _base_table_np():
    # python bignum point arithmetic for the static table
    return _niels_rows(_window_pts((C.BX_INT, C.BY_INT)))  # each (9, NLIMB)

_BASE_YPX, _BASE_YMX, _BASE_T2D = (jnp.asarray(t) for t in _base_table_np())


# ---------------------------------------------------------------------------
# fixed-base comb tables for B: [j * 16^i] B for i = 0..63, j = 0..8, in
# niels form — the basepoint half of the comb verify path (ADR-013).
# Built lazily on first comb use (~512 bignum adds, tens of ms): the
# ladder path, which most test processes are, never pays for it.
# ---------------------------------------------------------------------------

COMB_WINDOWS = 64

_base_comb_lock = threading.Lock()
_base_comb_cache = None


def _base_comb_np():
    ypx = np.zeros((COMB_WINDOWS, 9, F.NLIMB), dtype=np.int32)
    ymx = np.zeros_like(ypx)
    t2d = np.zeros_like(ypx)
    base = (C.BX_INT, C.BY_INT)
    for i in range(COMB_WINDOWS):
        pts = _window_pts(base)
        ypx[i], ymx[i], t2d[i] = _niels_rows(pts)
        # 16^{i+1} B = 2 * (8 * 16^i B)
        base = _edwards_add_int(pts[8], pts[8])
    return ypx, ymx, t2d


def _base_comb():
    """The (64, 9, NLIMB) jnp comb tables of B, built once per process."""
    global _base_comb_cache
    with _base_comb_lock:
        if _base_comb_cache is None:
            _base_comb_cache = tuple(jnp.asarray(t) for t in _base_comb_np())
        cache = _base_comb_cache
    # HBM residency ledger (ADR-021): refreshed on every access, not
    # just the build — a comb user in a process whose tables another
    # consumer built must still see the pool accounted
    devobs.ledger_set("base_comb", sum(int(t.nbytes) for t in cache))
    return cache


# ---------------------------------------------------------------------------
# host-side staging
# ---------------------------------------------------------------------------

def scalars_to_digits(s_bytes: np.ndarray) -> np.ndarray:
    """(B, 32) uint8 little-endian scalars (< 2^253) -> (B, 64) int8 signed
    radix-16 digits in [-8, 7], least-significant first.

    Closed form (no 63-step carry chain): t = s + 0x88...8 computed with
    256-bit arithmetic (four uint64 words, vectorized carry), then
    digit_j = nibble_j(t) - 8.  Since every nibble of t is the original
    nibble plus 8 plus the incoming carry, subtracting 8 per position
    yields the balanced radix-16 representation directly.  The top nibble
    of s is <= 1 (s < 2^253), so t never overflows 256 bits."""
    s_bytes = np.ascontiguousarray(np.asarray(s_bytes, dtype=np.uint8))
    words = s_bytes.view("<u8")  # (B, 4)
    EIGHTS = np.uint64(0x8888888888888888)
    t = np.empty_like(words)
    carry = np.zeros(words.shape[0], dtype=np.uint64)
    for w in range(4):
        a = words[:, w]
        x = a + EIGHTS
        c1 = x < EIGHTS
        x = x + carry
        c2 = x < carry
        t[:, w] = x
        carry = (c1 | c2).astype(np.uint64)
    tb = t.view(np.uint8)  # (B, 32) little-endian bytes of t
    dig = np.empty((s_bytes.shape[0], 64), dtype=np.int8)
    dig[:, 0::2] = (tb & 15).astype(np.int8) - 8
    dig[:, 1::2] = (tb >> 4).astype(np.int8) - 8
    return dig


def _to_u8_matrix(rows, width):
    if isinstance(rows, np.ndarray):
        return np.ascontiguousarray(rows, dtype=np.uint8)
    return np.frombuffer(b"".join(bytes(r) for r in rows),
                         dtype=np.uint8).reshape(-1, width)


def _s_canonical(s_bytes: np.ndarray) -> np.ndarray:
    """Vectorized s < L check (Go: scMinimal): compare the four
    little-endian uint64 words against L's, most-significant first."""
    from tendermint_tpu.libs import native

    out = native.scalar_canonical(s_bytes)
    if out is not None:
        return out
    s_words = s_bytes.view("<u8")  # (B, 4)
    l_words = np.frombuffer(L.to_bytes(32, "little"), dtype="<u8")
    B = s_bytes.shape[0]
    ok = np.zeros(B, dtype=bool)
    decided = np.zeros(B, dtype=bool)
    for w in (3, 2, 1, 0):
        lt = ~decided & (s_words[:, w] < l_words[w])
        gt = ~decided & (s_words[:, w] > l_words[w])
        ok |= lt
        decided |= lt | gt
    return ok  # undecided = equal to L -> not ok


def _as_fixed_width(msgs, B):
    """Collapse a list of equal-length bytes into a (B, mlen) uint8 array
    (the C staging's fixed-width fast path); pass arrays/ragged through."""
    from tendermint_tpu.libs.ragged import RaggedBytes

    if isinstance(msgs, np.ndarray) or B == 0:
        return msgs
    if isinstance(msgs, RaggedBytes):
        fw = msgs.fixed_width()
        return fw if fw is not None else msgs
    if len(msgs[0]) == len(msgs[-1]) and \
            all(len(m) == len(msgs[0]) for m in msgs):
        return np.frombuffer(b"".join(msgs), dtype=np.uint8).reshape(B, -1)
    return msgs


def _sha512_digests(r_bytes, pubkeys, msgs) -> np.ndarray:
    """(B, 64) uint8 SHA-512(R || A || M) digests.

    Native batch path (libs/native.py -> native/staging.c): one C call for
    the whole batch, no per-signature Python objects.  Fallback: hashlib
    loop (OpenSSL) where no C toolchain exists."""
    from tendermint_tpu.libs import native

    B = r_bytes.shape[0]
    prefix = np.concatenate([r_bytes, pubkeys], axis=1)
    if native.get_lib() is not None:
        out = native.sha512_prefixed(prefix, _as_fixed_width(msgs, B))
        if out is not None:
            return out
    rp = prefix.tobytes()
    _sha = hashlib.sha512
    return np.frombuffer(b"".join(
        _sha(rp[64 * i: 64 * i + 64] + bytes(msgs[i])).digest()
        for i in range(B)), dtype=np.uint8).reshape(B, 64)


def prepare_batch_compact(pubkeys, sigs, msgs):
    """Stage a verification batch for the fused Pallas kernel.

    Host work is byte packing, the s < L canonicity check, and hashlib
    SHA-512 digests — the mod-L reduction and balanced radix-16 digit
    decomposition run on-device (ops/pallas_ed25519.py _mod_l /
    _digits_from_limbs).  Returns (device_inputs, host_ok)."""
    pubkeys = _to_u8_matrix(pubkeys, 32)
    sigs = _to_u8_matrix(sigs, 64)
    B = pubkeys.shape[0]
    assert pubkeys.shape == (B, 32) and sigs.shape == (B, 64) \
        and len(msgs) == B
    r_bytes = np.ascontiguousarray(sigs[:, :32])
    s_bytes = np.ascontiguousarray(sigs[:, 32:])
    host_ok = _s_canonical(s_bytes)
    digests = _sha512_digests(r_bytes, pubkeys, msgs)
    # lane-major (transposed) int8 — the kernel's native layout; device
    # transposes of int8 are ~4x the cost of the whole verify kernel
    dev = dict(pub=np.ascontiguousarray(pubkeys.T).view(np.int8),
               r=np.ascontiguousarray(r_bytes.T).view(np.int8),
               s=np.ascontiguousarray(s_bytes.T).view(np.int8),
               digest=np.ascontiguousarray(digests.T).view(np.int8))
    return dev, host_ok


def prepare_batch_packed(pubkeys, sigs, msgs):
    """Stage a verification batch as ONE lane-major (128, B) int8 array:
    rows 0:32 pubkey bytes, 32:64 R bytes, 64:96 s bytes, 96:128 the
    challenge scalar k = SHA-512(R || A || M) mod L (reduced on the host
    by the native C staging; native/staging.c tm_challenge_*).

    One array = one host->device transfer per round (each transfer
    pays a fixed dispatch cost; not re-measured on a co-located chip),
    and k at 32 bytes (vs the 64-byte raw digest) cuts payload
    160 -> 128 B/sig.  Returns (packed, host_ok)."""
    pubkeys, r_bytes, s_bytes, k, host_ok = _stage_rows(pubkeys, sigs, msgs)
    B = pubkeys.shape[0]
    packed = np.empty((128, B), dtype=np.uint8)
    packed[0:32] = pubkeys.T
    packed[32:64] = r_bytes.T
    packed[64:96] = s_bytes.T
    packed[96:128] = k.T
    return packed.view(np.int8), host_ok


def _stage_rows(pubkeys, sigs, msgs):
    """Shared host staging for the packed/split kernel layouts: byte
    coercion, R/s split, s-canonicity, and the challenge scalar
    k = SHA-512(R || A || M) mod L (native C, numpy fallback).  Returns
    (pubkeys (B,32), r_bytes, s_bytes, k, host_ok)."""
    from tendermint_tpu.libs import native

    pubkeys = _to_u8_matrix(pubkeys, 32)
    sigs = _to_u8_matrix(sigs, 64)
    B = pubkeys.shape[0]
    assert pubkeys.shape == (B, 32) and sigs.shape == (B, 64) \
        and len(msgs) == B
    r_bytes = np.ascontiguousarray(sigs[:, :32])
    s_bytes = np.ascontiguousarray(sigs[:, 32:])
    host_ok = _s_canonical(s_bytes)
    prefix = np.concatenate([r_bytes, pubkeys], axis=1)
    k = None
    if native.get_lib() is not None:
        k = native.challenge_scalars(prefix, _as_fixed_width(msgs, B))
    if k is None:  # no C toolchain: hashlib + numpy fallback
        from . import sha512_np
        k = sha512_np.mod_l_batch(_sha512_digests(r_bytes, pubkeys, msgs))
    return pubkeys, r_bytes, s_bytes, k, host_ok


def prepare_batch_split(pubkeys, sigs, msgs):
    """prepare_batch_packed with the pubkey rows separated from the
    per-call rows, for the device-resident pubkey cache: returns
    (pub_rows (32, B) uint8, rsk (96, B) int8 — rows 0:32 R, 32:64 s,
    64:96 k, host_ok).  A validator set's keys are fixed across blocks,
    so steady-state VerifyCommit uploads pub_rows once and ships only
    96 B/sig per commit."""
    pubkeys, r_bytes, s_bytes, k, host_ok = _stage_rows(pubkeys, sigs, msgs)
    B = pubkeys.shape[0]
    rsk = np.empty((96, B), dtype=np.uint8)
    rsk[0:32] = r_bytes.T
    rsk[32:64] = s_bytes.T
    rsk[64:96] = k.T
    return np.ascontiguousarray(pubkeys.T), rsk.view(np.int8), host_ok


def prepare_batch(pubkeys, sigs, msgs):
    """Stage a verification batch for the device kernel.

    pubkeys: (B, 32) uint8 (or list of 32-byte objects)
    sigs:    (B, 64) uint8 (or list of 64-byte objects)
    msgs:    list of B bytes objects
    Returns (device_inputs: dict of np arrays, host_ok: (B,) bool).

    Host work is only what the device can't do: the SHA-512 challenge
    hash (variable-length messages), its mod-L reduction, s-canonicity,
    and the balanced radix-16 digit decomposition.  Everything shipped is
    compact uint8/int8, batch-major — bit/limb expansion happens on-device
    in verify_staged (160 B/signature of transfer instead of ~1.5 KB).
    """
    pubkeys = _to_u8_matrix(pubkeys, 32)
    sigs = _to_u8_matrix(sigs, 64)
    B = pubkeys.shape[0]
    assert pubkeys.shape == (B, 32) and sigs.shape == (B, 64) and len(msgs) == B

    r_bytes = np.ascontiguousarray(sigs[:, :32])
    s_bytes = np.ascontiguousarray(sigs[:, 32:])
    host_ok = _s_canonical(s_bytes)

    # challenge k = SHA-512(R || A || M) mod L.  hashlib (OpenSSL) beats a
    # vectorized numpy SHA-512 on short messages, but the mod-L reduction
    # is vectorized int64-limb arithmetic (ops/sha512_np.py) — the round-1
    # per-signature Python bignum `% L` was ~half the staging cost
    # (VERDICT r1 weak #2).
    from . import sha512_np

    digests = _sha512_digests(r_bytes, pubkeys, msgs)
    k_red = sha512_np.mod_l_batch(digests)

    dev = dict(
        pub=pubkeys,                        # (B, 32) uint8
        r=r_bytes,                          # (B, 32) uint8
        s_digits=scalars_to_digits(s_bytes),  # (B, 64) int8
        k_digits=scalars_to_digits(k_red),    # (B, 64) int8
    )
    return dev, host_ok


# ---------------------------------------------------------------------------
# device kernel
# ---------------------------------------------------------------------------

def _gather_base_niels(digit):
    """digit: (B,) int32 in [-8, 8] -> Niels of j*B with sign applied."""
    j = jnp.abs(digit)
    ypx = jnp.take(_BASE_YPX, j, axis=0).T  # (NLIMB, B)
    ymx = jnp.take(_BASE_YMX, j, axis=0).T
    t2d = jnp.take(_BASE_T2D, j, axis=0).T
    return C.cond_neg_niels(C.Niels(ypx, ymx, t2d), digit < 0)


def _build_var_table(a: C.Ext):
    """Cached multiples j*a for j = 0..8, stacked on axis 0: (9, NLIMB, B).
    One signed-radix-16 window unit (ops/curve.cached_window) — the comb
    table scan builds 64 of these per validator, once, instead of one per
    signature per launch."""
    return C.cached_window(a)[0]


def _gather_cached(tab: C.Cached, digit):
    """Per-lane gather from a (9, NLIMB, B) cached table by |digit|, with
    conditional negation for negative digits."""
    j = jnp.abs(digit)  # (B,)
    idx = j[None, None, :]  # (1, 1, B)
    sel = lambda t: jnp.take_along_axis(t, idx, axis=0)[0]
    q = C.Cached(sel(tab.ypx), sel(tab.ymx), sel(tab.z), sel(tab.t2d))
    return C.cond_neg_cached(q, digit < 0)


def straus_ladder(neg_a: C.Ext, s_digits, k_digits):
    """The 64-iteration joint Straus ladder shared by the ed25519 and
    sr25519 XLA lanes: returns [s]B + [k]neg_a for per-lane signed
    radix-16 digit columns s_digits/k_digits ((64, B) int32)."""
    tab = _build_var_table(neg_a)
    p0 = C.identity(neg_a.x.shape[1:])

    def body(i, p):
        pos = 63 - i
        # first 3 doublings skip the T output (next op is another dbl,
        # which ignores input T); only the last one feeds an addition
        p = C.dbl(C.dbl_no_t(C.dbl_no_t(C.dbl_no_t(p))))
        db = jax.lax.dynamic_index_in_dim(s_digits, pos, 0, keepdims=False)
        p = C.madd_niels(p, _gather_base_niels(db))
        da = jax.lax.dynamic_index_in_dim(k_digits, pos, 0, keepdims=False)
        p = C.add_cached(p, _gather_cached(tab, da))
        return p

    return jax.lax.fori_loop(0, 64, body, p0)


def verify_impl(a_y, a_sign, r_bits, s_digits, k_digits):
    """Batched cofactorless verify: ok iff A decodes and
    encode([s]B + [k](-A)) == R.   All inputs batched on the last axis.

    a_y: (NLIMB, B) limbs of A's y-encoding (sign bit masked)
    a_sign: (B,) 0/1     r_bits: (256, B) 0/1
    s_digits, k_digits: (64, B) int32 signed radix-16 digits
    Returns (B,) bool.
    """
    a, decode_ok = C.decompress(a_y, a_sign)
    neg_a = C.Ext(F.carry_lazy(-a.x), a.y, a.z, F.carry_lazy(-a.t))
    p = straus_ladder(neg_a, s_digits, k_digits)
    bits = C.encode_bits(p)
    r_eq = jnp.all(bits == r_bits, axis=0)
    return decode_ok & r_eq


def bytes256_to_limbs(b, mask_sign: bool = False):
    """(B, 32) uint8 rows -> ((NLIMB, B) radix-2^12 limbs, (B,) bit 255).
    With mask_sign the top bit is cleared before packing (the ed25519
    y-encoding convention); the returned sign is bit 255 either way.
    Shared by the ed25519 staging and the sr25519 ristretto lane."""
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = ((b[:, :, None] >> shifts) & 1).reshape(b.shape[0], 256)
    bits = bits.astype(jnp.int32)
    sign = bits[:, 255]
    if mask_sign:
        bits = bits.at[:, 255].set(0)
    pad = jnp.zeros((b.shape[0], F.TOTAL_BITS - 256), dtype=jnp.int32)
    bits = jnp.concatenate([bits, pad], axis=1)
    weights = (1 << jnp.arange(F.RADIX, dtype=jnp.int32))
    limbs = (bits.reshape(-1, F.NLIMB, F.RADIX) * weights).sum(
        axis=-1, dtype=jnp.int32).T
    return limbs, sign


def device_stage(pub, r, s_digits, k_digits):
    """On-device expansion of the compact staged arrays (all batch-major)
    into verify_impl's limb/bit layout.  Runs inside jit — a handful of
    vector ops, negligible next to the ladder, and cuts host->device
    transfer ~10x.

    pub, r: (B, 32) uint8;  s_digits, k_digits: (B, 64) int8.
    """
    a_y, a_sign = bytes256_to_limbs(pub, mask_sign=True)
    shifts = jnp.arange(8, dtype=jnp.uint8)
    r_bits = ((r[:, :, None] >> shifts) & 1).reshape(r.shape[0], 256)
    r_bits = r_bits.astype(jnp.int32).T
    return (a_y, a_sign, r_bits,
            s_digits.astype(jnp.int32).T, k_digits.astype(jnp.int32).T)


def verify_staged(pub, r, s_digits, k_digits):
    """Full device path: expand compact staging, then verify."""
    return verify_impl(*device_stage(pub, r, s_digits, k_digits))


verify_kernel = jax.jit(verify_staged)
_XLA_ARGS = ("pub", "r", "s_digits", "k_digits")  # prepare_batch's keys


# ---------------------------------------------------------------------------
# fixed-base comb verify (ADR-013): when the batch's pubkeys all belong
# to a known validator set, [s]B + [k](-A) is 64 iterations of two table
# gathers + two unified additions — ZERO doublings — against the static
# basepoint comb (_base_comb) and a per-validator device-resident window
# table built once per set (comb_build_kernel).  ~3x fewer group ops per
# verify than the Straus ladder, no per-launch table build, and the wire
# payload is the cache path's 96 B/sig.
# ---------------------------------------------------------------------------

# group-op inventory per lane, published in last_launch(): the ladder
# pays the per-launch variable-base window (4 dbl + 3 add) plus 64
# iterations of 4 doublings + 2 additions; the comb pays 2 additions per
# window and nothing else.  tests/test_comb.py re-counts these by tracing
# the kernels with instrumented group ops, so the constants can't drift.
LADDER_GROUP_OPS = {"doublings": 4 * 64 + 4, "adds": 2 * 64 + 3}
COMB_GROUP_OPS = {"doublings": 0, "adds": 2 * COMB_WINDOWS}
_GROUP_OPS_BY_PATH = {
    "xla": LADDER_GROUP_OPS, "mesh-sharded": LADDER_GROUP_OPS,
    "pallas": LADDER_GROUP_OPS, "pallas-split": LADDER_GROUP_OPS,
    "mesh-pallas": LADDER_GROUP_OPS, "mesh-xla": LADDER_GROUP_OPS,
    "global-mesh": LADDER_GROUP_OPS,
    "comb": COMB_GROUP_OPS, "mesh-comb": COMB_GROUP_OPS,
    "mesh-comb-sharded": COMB_GROUP_OPS,
}


def comb_build_kernel_impl(pub):
    """Device-side comb table build for a (K, 32) uint8 pubkey matrix:
    decompress each A, negate, and scan out the 64 signed-radix-16
    window tables of -A (ops/curve.comb_table_scan).  Returns
    (Cached tables, fields (64, 9, NLIMB, K); decode_ok (K,) bool).
    All group math runs under jit with the same C.dbl/C.add_cached
    kernels the ladder uses — no host bignum."""
    a_y, a_sign = bytes256_to_limbs(pub, mask_sign=True)
    a, ok = C.decompress(a_y, a_sign)
    neg_a = C.Ext(F.carry_lazy(-a.x), a.y, a.z, F.carry_lazy(-a.t))
    return C.comb_table_scan(neg_a, windows=COMB_WINDOWS), ok


comb_build_kernel = jax.jit(comb_build_kernel_impl)


def _gather_comb_cached(tab: "C.Cached", i, digit, vidx):
    """Two-level gather from the per-validator comb tables: window i
    (loop-carried scalar), then tables[window, |digit|, :, vidx[lane]]
    per lane, with conditional negation for negative digits.  Pure
    gathers — this is the entire per-iteration cost of the A term."""
    j = jnp.abs(digit)
    idx = j[None, None, :]  # (1, 1, B) for the digit take_along_axis

    def sel(t):
        row = jax.lax.dynamic_index_in_dim(t, i, 0, keepdims=False)
        lane = jnp.take(row, vidx, axis=2)        # (9, NLIMB, B)
        return jnp.take_along_axis(lane, idx, axis=0)[0]

    q = C.Cached(sel(tab.ypx), sel(tab.ymx), sel(tab.z), sel(tab.t2d))
    return C.cond_neg_cached(q, digit < 0)


def _gather_base_comb(base, i, digit):
    """Niels gather from the static basepoint comb (window i, per-lane
    digit) — _gather_base_niels generalized to 64 windows."""
    by, bm, bt = base
    j = jnp.abs(digit)
    ypx = jnp.take(jax.lax.dynamic_index_in_dim(by, i, 0, keepdims=False),
                   j, axis=0).T
    ymx = jnp.take(jax.lax.dynamic_index_in_dim(bm, i, 0, keepdims=False),
                   j, axis=0).T
    t2d = jnp.take(jax.lax.dynamic_index_in_dim(bt, i, 0, keepdims=False),
                   j, axis=0).T
    return C.cond_neg_niels(C.Niels(ypx, ymx, t2d), digit < 0)


def comb_verify_staged(r, s_digits, k_digits, vidx,
                       tab_ypx, tab_ymx, tab_z, tab_t2d, dec_ok,
                       base_ypx, base_ymx, base_t2d):
    """Comb variant of verify_staged: same cofactorless verdict, zero
    doublings.  All per-signature inputs batch-major:

    r: (B, 32) uint8     s_digits, k_digits: (B, 64) int8
    vidx: (B,) int32 row index into the validator table axis
    tab_*: (64, 9, NLIMB, K) cached window tables of -A per validator
    dec_ok: (K,) bool precomputed decode verdict per validator
    base_*: (64, 9, NLIMB) static comb of B
    Returns (B,) bool.

    Addition order differs from the ladder (per-window instead of
    Horner), but the group is commutative and encode_bits normalizes by
    1/Z, so the encoded bits — and therefore the bitmap — are bitwise
    identical to the ladder's on every input class (asserted across the
    sweep in tests/test_comb.py)."""
    shifts = jnp.arange(8, dtype=jnp.uint8)
    r_bits = ((r[:, :, None] >> shifts) & 1).reshape(r.shape[0], 256)
    r_bits = r_bits.astype(jnp.int32).T
    sd = s_digits.astype(jnp.int32).T   # (64, B)
    kd = k_digits.astype(jnp.int32).T
    ok_lane = jnp.take(dec_ok, vidx)
    tab = C.Cached(tab_ypx, tab_ymx, tab_z, tab_t2d)
    base = (base_ypx, base_ymx, base_t2d)
    p0 = C.identity((r.shape[0],))

    def body(i, p):
        db = jax.lax.dynamic_index_in_dim(sd, i, 0, keepdims=False)
        p = C.madd_niels(p, _gather_base_comb(base, i, db))
        da = jax.lax.dynamic_index_in_dim(kd, i, 0, keepdims=False)
        p = C.add_cached(p, _gather_comb_cached(tab, i, da, vidx))
        return p

    p = jax.lax.fori_loop(0, COMB_WINDOWS, body, p0)
    bits = C.encode_bits(p)
    return jnp.all(bits == r_bits, axis=0) & ok_lane


comb_kernel = jax.jit(comb_verify_staged)


PALLAS_TILE = 256  # best-measured batch tile for the fused TPU kernel
MAX_CHUNK = 1 << 16  # biggest single-launch lane count (verify_batch)


def _use_pallas() -> bool:
    """The fused Pallas kernel is TPU-only (Mosaic); every other backend
    uses the XLA-composed kernel.  A backend that fails to initialize
    raises here — it is a device fault for the degrade runtime wrapping
    the dispatch to count, never "not a TPU"."""
    return jax.default_backend() == "tpu"


MIN_BUCKET = 64


# ---------------------------------------------------------------------------
# launch observability: every device dispatch (this module AND the mesh
# plane in parallel/sharding.py) funnels through _record_launch, which
# publishes route + lane occupancy + the first-launch compile split into
# CryptoMetrics and onto the enclosing trace span.  The first launch of
# a (path, lane-bucket) pair in a process pays the jit/Mosaic compile —
# tens of seconds on a cold cache — while steady-state launches are
# milliseconds; conflating them is how round 5's perf numbers went
# unmeasured, so the split is recorded explicitly.
# ---------------------------------------------------------------------------

_launch_lock = threading.Lock()
_seen_buckets: set = set()
_launch_seq = 0
_last_launch = MappingProxyType({"path": None, "seq": 0})
_compiled: set = set()
_compile_tls = threading.local()


def launch_kernel(fn, *args, **static):
    """Call a jitted kernel.  The first call of a (kernel, operand
    shapes) pair in the process traces and compiles it ahead of the
    call, inside degrade.compiling(): the unrolled ladder costs tens of
    seconds of host-side tracing per lane bucket before the persistent
    cache can even be consulted, and the launch deadline is there to
    bound device work, not the compiler.  The ahead-of-time result
    lands in the in-process caches the call below reads; whether an
    operand is committed to a device is part of their key, so the real
    operands are lowered, not ShapeDtypeStructs.  The seconds spent go
    to whoever takes them next on this thread (_take_compile_s): the
    dispatch's launch record, or the table_build span."""
    # no .lower: an executable compiled ahead already (the global
    # plane's sealed step) or a test's stand-in — nothing to compile
    if hasattr(fn, "lower"):
        key = (fn, tuple((a.shape, a.dtype) for a in args),
               tuple(sorted(static.items())))
        with _launch_lock:
            cold = key not in _compiled
        if cold:
            t0 = time.perf_counter()
            with degrade.compiling():
                fn.lower(*args, **static).compile()
            _compile_tls.s = getattr(_compile_tls, "s", 0.0) + \
                (time.perf_counter() - t0)
            with _launch_lock:
                _compiled.add(key)
    return fn(*args, **static)


def _take_compile_s() -> float:
    """Trace + compile seconds launch_kernel has spent on this thread
    since the last take.  verify_batch takes (and drops) at entry, so a
    dispatch that raised before its record leaves nothing behind and a
    record's compile_s is part of its own wall_s."""
    s = getattr(_compile_tls, "s", 0.0)
    _compile_tls.s = 0.0
    return s


def last_launch():
    """Immutable snapshot of the most recent device-launch record:
    path / n / nb (padded lanes) / occupancy / shards / first_launch /
    wall_s.  Aggregate history lives in crypto_msm_route_total and
    crypto_device_compile_seconds on /metrics."""
    with _launch_lock:
        return _last_launch


def _set_last_launch(rec: dict):
    """Publish a fresh immutable launch snapshot (ops/msm routes call
    this too, so last_launch() covers the RLC fast path — a bench row
    must never claim the device was idle when RLC vouched).  Each
    snapshot carries a monotonically increasing "seq" so a reader that
    bracketed its own dispatch can tell whether the record it sees is
    its launch or a concurrent verifier's (crypto/scheduler's route
    span attr).

    This is also THE funnel into the device observatory (ADR-021):
    every launch record — ladder/comb/split/mesh via _record_launch
    and the RLC route mirror from ops/msm._set_route — is stored into
    crypto/devobs's ring here, and the deferred publication drains
    right after, with _launch_lock already released (devobs records
    under its own leaf lock and never publishes — the PR 12
    discipline)."""
    global _last_launch, _launch_seq
    with _launch_lock:
        _launch_seq += 1
        snap = dict(rec, seq=_launch_seq)
        _last_launch = MappingProxyType(snap)
    devobs.record(snap)
    devobs.publish_pending()


def _record_launch(path: str, n: int, nb: int, wall_s: float,
                   shards: int = 1, extra: dict = None,
                   bucket: int = None):
    """`bucket`: the lanes of the shape the launch compiled, where that
    is not `nb` — pallas-split launches chunks of one (96, chunk) shape,
    so a batch of another length is another `nb` and no first launch.
    The record says so (`bucket`), and devobs' inventory keys on it."""
    occupancy = n / nb if nb else 1.0
    key = (path, bucket or nb, shards)
    with _launch_lock:
        first = key not in _seen_buckets
        _seen_buckets.add(key)
    rec = {
        "path": path, "n": n, "nb": nb, "occupancy": occupancy,
        "shards": shards, "first_launch": first, "wall_s": wall_s}
    if bucket:
        rec["bucket"] = bucket
    # the part of wall_s that was not the launch
    compile_s = _take_compile_s()
    if compile_s:
        rec["compile_s"] = compile_s
    # per-lane group-op inventory of the dispatched kernel family, so a
    # bench row (and the comb acceptance guard) can assert "no doublings"
    # from the launch record instead of re-deriving it from the code
    ops = _GROUP_OPS_BY_PATH.get(path)
    if ops is not None:
        rec["group_ops"] = dict(ops)
    if extra:
        rec.update(extra)
    _set_last_launch(rec)
    degrade.publish_route(path, "executed", n=n, nb=nb,
                          compile_s=compile_s or None)
    trace.current().add(path=path, n=n, nb=nb,
                        occupancy=round(occupancy, 4), shards=shards,
                        first_launch=first)


def _overlap_phases(probe: dict) -> dict:
    """Normalize a DMA probe (verify_packed_pipelined /
    split_chunked_launch) into launch-record phase keys for the device
    observatory: h2d_s is the summed device_put wall, chunk_overlap the
    fraction of that wall issued while an earlier chunk's kernel was in
    flight — the first put has nothing to hide behind, every later one
    is bracketed between a dispatch and the final block, so it overlaps
    compute by construction (an issued-while-in-flight fraction; see
    crypto/devobs.py for why a tighter number would require serializing
    the pipeline being measured)."""
    out = {}
    for key in ("stage_s", "stage_cpu_s", "pub_rows_s", "head_s"):
        if probe.get(key) is not None:
            out[key] = probe[key]
    dma = probe.get("dma_s")
    if dma is not None:
        out["h2d_s"] = dma
        first = probe.get("dma_first_s", 0.0)
        out["chunk_overlap"] = max(0.0, (dma - first) / dma) \
            if dma > 0 else 0.0
        out["chunks"] = probe.get("chunks")
    return out


def bucket_size(n: int) -> int:
    """Round a batch size up to the next power of two (>= MIN_BUCKET) so the
    jitted kernel sees few distinct shapes (one compile per bucket)."""
    return max(MIN_BUCKET, 1 << (n - 1).bit_length())


def _pad_dev(dev: dict, n: int, nb: int) -> dict:
    """Pad the batch axis (axis 0 of the compact staged arrays) to nb."""
    if nb == n:
        return dev
    return {k: np.pad(v, [(0, nb - n)] + [(0, 0)] * (v.ndim - 1))
            for k, v in dev.items()}


def verify_packed_pipelined(packed: np.ndarray, nsub: int = 4,
                            tile: int = None, probe: dict = None):
    """Launch the packed Pallas verify over `nsub` sub-batches, explicitly
    pipelining host->device transfer against kernel execution: sub-batch
    j+1's device_put is issued right after sub-batch j's kernel dispatch,
    so its DMA proceeds while the kernel runs (scripts/exp_overlap.py;
    the gain was taken over a network link to the chip and is not
    re-measured on a co-located one).

    packed: (128, B) int8 with B % nsub == 0 and (B//nsub) % tile == 0.
    Returns a list of device arrays (caller blocks/concatenates).

    `probe` (optional dict, ADR-021): filled with the per-chunk DMA
    walls — dma_s (sum of device_put call durations), dma_first_s (the
    unoverlapped first put) and chunks — so the caller can record the
    chunk-overlap ratio without ever serializing the pipeline with an
    extra block."""
    from . import pallas_ed25519 as pe

    tile = tile or PALLAS_TILE
    B = packed.shape[1]
    assert B % nsub == 0 and (B // nsub) % tile == 0, (B, nsub, tile)
    sub = B // nsub
    dev = jax.devices()[0]
    outs = []
    # the double-buffered window keeps at most TWO sub-chunks in
    # flight on the device (cur + nxt) — charging the whole host batch
    # would overstate the device-resident peak nsub/2-fold
    inflight = packed.nbytes if nsub == 1 else 2 * (packed.nbytes // nsub)
    devobs.ledger_add("staging", inflight)
    try:
        put_walls = []
        t_put = time.perf_counter()
        nxt = jax.device_put(np.ascontiguousarray(packed[:, :sub]), dev)
        put_walls.append(time.perf_counter() - t_put)
        for j in range(nsub):
            cur = nxt
            # dispatch the kernel FIRST, then issue the next transfer: the
            # kernel only depends on `cur`, so the j+1 DMA proceeds while it
            # runs; putting first would queue the transfer ahead of the kernel
            # and serialize the pipeline (scheme C in scripts/exp_overlap.py)
            outs.append(launch_kernel(pe.verify_packed_pallas, cur,
                                      tile=tile))
            if j + 1 < nsub:
                t_put = time.perf_counter()
                nxt = jax.device_put(
                    np.ascontiguousarray(
                        packed[:, (j + 1) * sub:(j + 2) * sub]),
                    dev)
                put_walls.append(time.perf_counter() - t_put)
        if probe is not None:
            probe["dma_s"] = sum(put_walls)
            probe["dma_first_s"] = put_walls[0]
            probe["chunks"] = nsub
        return outs
    finally:
        devobs.ledger_add("staging", -inflight)


# ---------------------------------------------------------------------------
# device-resident caches.  One bounded LRU implementation backs both the
# pubkey-row cache (the 96 B/sig split path) and the comb table cache:
# the old _pub_cache hand-rolled its bound at the insert site only, and
# a hit's pop/re-insert raced a concurrent filler into one-over-bound
# (ISSUE 5 small fix) — here every mutation enforces the bound inside
# the same critical section.
# ---------------------------------------------------------------------------


class DeviceLRU:
    """Bounded, thread-safe LRU of device-resident uploads.

    Bounds: `max_entries` (count) and/or `max_bytes` (sum of the nbytes
    passed to put) — whichever is set; eviction is oldest-first and never
    evicts the entry just inserted (a single set larger than the budget
    is kept rather than thrashed; callers budget-check before building).
    put() is first-wins: when two threads race the same key, the loser's
    upload is dropped and both use the winner's arrays, so a double
    upload can't leave two resident copies.  `on_evict(key, value)`
    fires outside the lock."""

    def __init__(self, max_entries: int = None, max_bytes: int = None,
                 on_evict=None):
        import collections
        self.max_entries = max_entries
        self.max_bytes = max_bytes
        self._on_evict = on_evict
        self._od: "collections.OrderedDict" = collections.OrderedDict()
        self._bytes = 0
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key):
        with self._lock:
            ent = self._od.get(key)
            if ent is None:
                self.misses += 1
                return None
            self._od.move_to_end(key)
            self.hits += 1
            return ent[0]

    def put(self, key, value, nbytes: int = 0):
        evicted = []
        with self._lock:
            ent = self._od.get(key)
            if ent is not None:  # racing upload lost: first wins
                self._od.move_to_end(key)
                return ent[0]
            self._od[key] = (value, nbytes)
            self._bytes += nbytes
            while len(self._od) > 1 and self._over_locked():
                k, (v, b) = self._od.popitem(last=False)
                self._bytes -= b
                self.evictions += 1
                evicted.append((k, v))
        if self._on_evict is not None:
            for k, v in evicted:
                self._on_evict(k, v)
        return value

    def _over_locked(self) -> bool:
        if self.max_entries is not None and \
                len(self._od) > self.max_entries:
            return True
        return self.max_bytes is not None and self._bytes > self.max_bytes

    def pop(self, key):
        with self._lock:
            ent = self._od.pop(key, None)
            if ent is None:
                return None
            self._bytes -= ent[1]
        return ent[0]

    def clear(self):
        with self._lock:
            self._od.clear()
            self._bytes = 0

    @property
    def total_bytes(self) -> int:
        with self._lock:
            return self._bytes

    def __len__(self) -> int:
        with self._lock:
            return len(self._od)

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._od

    def peek(self, key):
        """get() without touching recency or the hit/miss counters —
        for bookkeeping scans that must not perturb eviction order."""
        with self._lock:
            ent = self._od.get(key)
            return None if ent is None else ent[0]

    def keys(self):
        with self._lock:
            return list(self._od.keys())


# -- pubkey-row cache (validator-set split path): a chain's validator
# keys are fixed across blocks, so the (32, B) pubkey rows are uploaded
# once and every subsequent VerifyCommit against the same set ships only
# the 96 B/sig of per-commit data (R, s, k).  Keyed by content hash of
# the padded pubkey rows; tiny LRU — a node tracks very few sets (own
# chain + maybe a light client's). ------------------------------------

# chosen when a remote round trip dominated smaller batches; that reason
# is gone with the link and the threshold is not re-measured on a
# co-located chip
PUB_CACHE_MIN = 4096
PREWARM_MIN_KEYS = 32     # the device-lane batch floor (crypto/batch
# tpu_threshold): a set smaller than this never reaches the device, so
# prewarming it would burn an XLA compile for tables nothing uses.
# comb_min_batch() (TM_TPU_COMB_MIN / set_comb_config) lowers the
# effective floor for kernel tests
_PUB_CACHE_MAX = 4
_pub_cache = DeviceLRU(max_entries=_PUB_CACHE_MAX)


def _pub_cache_get(pub_rows: np.ndarray, nsub: int, probe: dict = None):
    """pub_rows: (32, NB) uint8, already padded; nsub: pipeline chunk
    count.  Returns rows_of(j) -> the (32, NB/nsub) device array of
    chunk j (the pipelined launch shape), to be asked for in launch
    order.  Rows the cache holds are handed out; rows it does not are
    uploaded chunk by chunk AS THEY ARE ASKED FOR, so a miss puts one
    chunk's rows ahead of the first kernel and every later chunk's
    behind a kernel (split_chunked_launch asks beside chunk j's
    staging), and enter the cache with the last chunk.  One content key
    over all the rows.  Thread-safe: multiple verifier threads
    (consensus, light client) route through verify_sigs_bulk
    concurrently; a racing double upload resolves to one resident copy
    (DeviceLRU.put is first-wins).

    `probe` (optional dict) is told whether the rows were found on the
    device (pub_rows_cached) and, when they were not, the bytes this
    launch uploads (pub_rows_bytes): a commit whose signers differ from
    the last one's is new content, and pays the upload again."""
    key = (hashlib.sha256(pub_rows.tobytes()).digest(), nsub)
    chunks = _pub_cache.get(key)
    if probe is not None:
        probe["pub_rows_cached"] = chunks is not None
        if chunks is None:
            probe["pub_rows_bytes"] = int(pub_rows.nbytes)
    if chunks is not None:
        return chunks.__getitem__
    sub = pub_rows.shape[1] // nsub
    dev = jax.devices()[0]
    mine = []

    def upload(j):
        # outside the cache lock (device_put blocks on the copy)
        while len(mine) <= j:
            a = len(mine) * sub
            mine.append(jax.device_put(np.ascontiguousarray(
                pub_rows[:, a:a + sub]).view(np.int8), dev))
            if len(mine) == nsub:
                _pub_cache.put(key, mine, nbytes=int(pub_rows.nbytes))
                devobs.ledger_set("pub_cache", _pub_cache.total_bytes)
        return mine[j]

    return upload


# -- comb table cache (ADR-013): per-validator fixed-base window tables,
# device-resident, keyed by validator-set content hash (sha256 of the
# sorted distinct pubkey rows).  Subsumes the role of the pubkey-row
# cache for sets it holds: a batch against a cached set ships only
# (validator_index, R, s, k) and runs the zero-doubling comb kernel.
# Bounded in BYTES (config [batch_verifier] table_cache_mb): one padded
# key costs 64 windows x 9 entries x 4 cached fields x NLIMB x 4 B
# (~198 KB), so a 256 MB default budget holds ~1.3k validator keys. ----

_TABLE_BYTES_PER_KEY = COMB_WINDOWS * 9 * 4 * F.NLIMB * 4
TABLE_CACHE_MB_DEFAULT = 256

_comb_enabled_override = None   # node config wins over env, either way
_comb_min_override = None
_table_budget_override = None


def set_comb_config(enabled: bool = None, table_cache_mb: int = None,
                    min_batch: int = None):
    """Node-assembly override of the comb-path knobs (None leaves a knob
    on its env/default; the env stays the knob only for node-less
    tooling — benches, tests)."""
    global _comb_enabled_override, _comb_min_override, \
        _table_budget_override
    if enabled is not None:
        _comb_enabled_override = bool(enabled)
    if table_cache_mb is not None:
        _table_budget_override = int(table_cache_mb) << 20
    if min_batch is not None:
        _comb_min_override = int(min_batch)


def comb_enabled() -> bool:
    import os
    if _comb_enabled_override is not None:
        return _comb_enabled_override
    return os.environ.get("TM_TPU_COMB", "1") != "0"


def comb_min_batch() -> int:
    """Smallest batch that triggers a table BUILD (a cache hit engages
    at any size — the tables are already resident)."""
    import os
    if _comb_min_override is not None:
        return _comb_min_override
    return int(os.environ.get("TM_TPU_COMB_MIN", PUB_CACHE_MIN))


def table_cache_budget_bytes() -> int:
    import os
    if _table_budget_override is not None:
        return _table_budget_override
    return int(os.environ.get("TM_TPU_TABLE_CACHE_MB",
                              TABLE_CACHE_MB_DEFAULT)) << 20


class CombTables:
    """One cached validator set: device-resident comb tables + metadata."""
    __slots__ = ("set_hash", "index", "tables", "dec_ok", "nbytes",
                 "k", "k_pad", "mesh_repl", "mesh_shard")

    def __init__(self, set_hash, index, tables, dec_ok, nbytes, k, k_pad):
        self.set_hash = set_hash
        self.index = index        # pubkey bytes -> table row
        self.tables = tables      # C.Cached, fields (64, 9, NLIMB, K_pad)
        self.dec_ok = dec_ok      # (K_pad,) bool device array
        self.nbytes = nbytes
        self.k = k
        self.k_pad = k_pad
        # (mesh, operand tuple, ledger bytes) placed once by the data
        # plane's verify_comb — without it every mesh launch would
        # re-replicate the full table set (~198 KB/key) across shards.
        # mesh_repl holds full per-device copies, mesh_shard the
        # validator-axis slices of the budget-fallback gather path
        self.mesh_repl = None
        self.mesh_shard = None


_table_key_lock = threading.Lock()
_table_key_index: "dict[bytes, bytes]" = {}  # pubkey bytes -> set hash


def _table_evicted(set_hash, entry):
    # release the data plane's mesh copies with the build copy — the
    # mesh_tables ledger pool must not keep charging bytes whose owner
    # the LRU already let go (the device buffers free when the entry's
    # last reference drops)
    freed = 0
    for slot in ("mesh_repl", "mesh_shard"):
        cached = getattr(entry, slot, None)
        if cached is not None:
            freed += cached[2]
            setattr(entry, slot, None)
    if freed:
        devobs.ledger_add("mesh_tables", -freed)
    with _table_key_lock:
        for kb in entry.index:
            if _table_key_index.get(kb) != set_hash:
                continue
            # overlapping sets (a validator-set change keeps most keys):
            # repoint the key to a surviving resident owner instead of
            # dropping it, or the survivor's subset lookups — gated on
            # this index — would silently stop engaging the comb
            for owner in _table_cache.keys():
                surv = _table_cache.peek(owner)
                if surv is not None and kb in surv.index:
                    _table_key_index[kb] = owner
                    break
            else:
                del _table_key_index[kb]
    degrade.publish_table_cache(bytes_=_table_cache.total_bytes,
                                evicted=True)
    devobs.ledger_set("table_cache", _table_cache.total_bytes)


_table_cache = DeviceLRU(max_bytes=None, on_evict=_table_evicted)


def table_cache_clear():
    """Drop every cached set (tests / operator tooling)."""
    for h in _table_cache.keys():
        entry = _table_cache.pop(h)
        if entry is not None:
            _table_evicted(h, entry)


def _comb_k_pad(k: int) -> int:
    """Validator-axis compile bucket: power of two, floor 8 — few table
    shapes per process, same discipline as the lane buckets."""
    return max(8, 1 << (k - 1).bit_length())


def _comb_key_cap() -> int:
    """The most distinct keys any table set can hold right now: the
    largest k whose padded tables fit the budget (_table_build's own
    test: 1,024 at the default 256 MB, 0 under 8 padded keys), or the
    largest k resident, whichever is larger: an entry built under a
    budget that set_comb_config has since shrunk is still found by
    _table_lookup."""
    fit = table_cache_budget_bytes() // _TABLE_BYTES_PER_KEY
    cap = 1 << (fit.bit_length() - 1) if fit >= 8 else 0
    for set_hash in _table_cache.keys():
        entry = _table_cache.peek(set_hash)
        if entry is not None:
            cap = max(cap, entry.k)
    return cap


def _comb_over_cap(pub_m: np.ndarray) -> bool:
    """True when the key matrix provably holds more distinct keys than
    any table set can (_comb_key_cap), from work that is O(cap): the
    distinct 8-byte prefixes of the first 4 * (cap + 1) rows are a
    lower bound on the batch's distinct keys.  Over the cap no look-up
    can succeed (an entry holds every distinct key of a batch it
    answers) and no build fit (_comb_k_pad is monotone), so the caller
    leaves ahead of the distinct-key sort with the answer the sort
    would have reached.  False decides nothing: a head of repeated keys
    falls through to the full look-up.  A batch of <= cap rows counts
    nothing."""
    cap = _comb_key_cap()
    if pub_m.shape[0] <= cap:
        return False
    head = np.ascontiguousarray(pub_m[:4 * (cap + 1), :8])
    return np.unique(head.view("<u8")).size > cap


def _table_build(uniq: np.ndarray, set_hash: bytes):
    """Build + cache the comb tables for a distinct-key matrix (K, 32).
    Returns the CombTables entry, or None when the HBM budget says no
    (route comb/declined — the ladder handles the batch).  The LRU
    charges ONE copy: the mesh replication decision moved to the data
    plane (sharding.comb_mesh_mode, ADR-027), which charges its extra
    per-device copies — or the budget-fallback sharded slices — to the
    mesh_tables ledger pool against the same budget at launch time."""
    k = uniq.shape[0]
    k_pad = _comb_k_pad(k)
    nbytes = k_pad * _TABLE_BYTES_PER_KEY
    budget = table_cache_budget_bytes()
    if nbytes > budget:
        degrade.publish_route("comb", "declined")
        return None
    _table_cache.max_bytes = budget  # config may have changed
    pub_pad = np.zeros((k_pad, 32), dtype=np.uint8)
    pub_pad[:k] = uniq
    t0 = time.perf_counter()
    with trace.span("table_build", k=k, k_pad=k_pad, bytes=nbytes) as sp:
        tab, dec_ok = launch_kernel(comb_build_kernel,
                                    jnp.asarray(pub_pad))
        jax.block_until_ready(tab)
        # the build's own compile stays off the launch record of the
        # dispatch that follows it
        compile_s = _take_compile_s()
        sp.add(wall_s=round(time.perf_counter() - t0, 4),
               compile_s=round(compile_s, 4))
    if compile_s:
        degrade.publish_compile("comb-build", compile_s)
    index = {uniq[i].tobytes(): i for i in range(k)}
    entry = CombTables(set_hash, index, tab, dec_ok, nbytes, k, k_pad)
    entry = _table_cache.put(set_hash, entry, nbytes)
    with _table_key_lock:
        for kb, i in entry.index.items():
            _table_key_index[kb] = set_hash
    degrade.publish_table_cache(bytes_=_table_cache.total_bytes)
    devobs.ledger_set("table_cache", _table_cache.total_bytes)
    return entry


def _table_lookup(uniq: np.ndarray):
    """Resolve a distinct-key matrix against the table cache.  Returns
    (entry, remap) where remap maps the uniq row order onto the entry's
    table rows, or (None, None).  A batch whose keys are a SUBSET of a
    cached set (a partial vote window, the VerifyScheduler's coalesced
    lanes) resolves through the key-level index; any unknown or
    cross-set key falls back to the ladder."""
    set_hash = hashlib.sha256(uniq.tobytes()).digest()
    entry = _table_cache.get(set_hash)
    if entry is not None:
        return entry, np.arange(uniq.shape[0], dtype=np.int32)
    with _table_key_lock:
        owner = _table_key_index.get(uniq[0].tobytes())
    if owner is None:
        return None, None
    entry = _table_cache.get(owner)
    if entry is None:
        return None, None
    remap = np.empty(uniq.shape[0], dtype=np.int32)
    for i in range(uniq.shape[0]):
        row = entry.index.get(uniq[i].tobytes())
        if row is None:  # mixed known+unknown keys: whole batch ladders
            return None, None
        remap[i] = row
    return entry, remap


def prewarm(pubkeys, warm_kernel: bool = True) -> bool:
    """Build the comb tables for a validator set OFF the request path
    (LightServe / node.py call this on validator-set change, ADR-026),
    so the first post-change verify pays gathers, not a table build.

    `warm_kernel` additionally runs one tiny throwaway verify against
    the freshly cached set, priming the nb=64 comb-kernel shape and
    marking the (comb, 64, 1) launch bucket seen — the first real
    request then records ``first_launch=False`` and compiles nothing.
    Returns True when the tables are resident (already or newly built);
    False when the comb path is disabled, the HBM budget declined, or
    the set is below the device-lane floor (batches that small never
    dispatch to the device, so tables — and the XLA compile a build
    pays — are pure waste; a dev-node stopping seconds after start
    must not leave a background compile racing interpreter teardown).
    The tables are over ed25519 keys: a caller with a set in several
    schemes hands in its ed25519 keys alone, chosen by
    `pub_key.type_name` (a 32-byte sr25519 key cannot be told from one
    by its length); a list that holds a key of another length is
    declined, not raised on."""
    if not comb_enabled() or table_cache_budget_bytes() <= 0:
        return False
    keys = list(pubkeys)
    if len(keys) < min(PREWARM_MIN_KEYS, comb_min_batch()):
        return False
    if not keys:
        return False
    if any(len(k) != 32 for k in keys):
        degrade.publish_route("comb", "declined")
        return False
    pub_m = _to_u8_matrix(keys, 32)
    if pub_m.shape != (len(keys), 32):
        return False
    if _comb_over_cap(pub_m):
        degrade.publish_route("comb", "declined")
        return False
    uniq = np.unique(pub_m, axis=0)
    entry, _ = _table_lookup(uniq)
    if entry is None:
        entry = _table_build(uniq,
                             hashlib.sha256(uniq.tobytes()).digest())
        if entry is None:
            return False
    if warm_kernel:
        k = min(4, uniq.shape[0])
        try:
            verify_batch([uniq[i].tobytes() for i in range(k)],
                         [b"tm-tpu-prewarm"] * k, [b"\x01" * 64] * k)
        except Exception as e:  # noqa: BLE001 - warm-up is best-effort
            # (the tables above are already resident), but a kernel the
            # compiler rejected must show as a failed route, not as a
            # quiet prewarm: the first real request hits the same fault
            _prewarm_failed(e)
    return True


def _prewarm_failed(e: BaseException):
    degrade.publish_route("comb-prewarm", "error")
    trace.instant("comb.prewarm_failed", error=type(e).__name__)


def prewarm_async(pubkeys) -> None:
    """Dispatch ``prewarm`` onto a host-lane pool worker (or a
    throwaway daemon thread when host verification is serial) — the
    off-path seam the valset-change subscribers use."""
    keys = [bytes(k) for k in pubkeys]

    def _run():
        try:
            prewarm(keys)
        except Exception as e:  # noqa: BLE001 - warm path must never
            _prewarm_failed(e)  # raise; a failed table build is counted

    from tendermint_tpu.crypto import lanepool
    p = lanepool.pool()
    if p is not None and p.try_submit(_run) is not None:
        return
    # a prewarm can be deep inside an XLA compile when the process
    # exits, and freezing the worker there leaves the compiler's C++
    # thread pool joinable at static teardown — std::terminate.  The
    # atexit join (which runs BEFORE that teardown) waits the compile
    # out; the small-set decline in prewarm() keeps the wait off dev
    # nodes, and a finished thread joins instantly.
    t = threading.Thread(target=_run, name="comb-prewarm", daemon=True)
    atexit.register(t.join)
    t.start()


class _CombSet(NamedTuple):
    """A batch every key of which resolved to one resident table set."""
    entry: CombTables
    pub_m: np.ndarray   # (n, 32) the batch's keys
    vidx: np.ndarray    # (n,) int32: each lane's row in the tables
    built: bool         # this call built the tables


class _Launched(NamedTuple):
    """What a route hands verify_batch to read back and record."""
    out: object          # device array still in flight, a list of them
    #                      in lane order (one a chunk, joined on the
    #                      host), or the host bitmap of a route that
    #                      blocked inside
    host_ok: np.ndarray  # (n,) the host-side screens (lengths, s < L)
    nb: int              # padded lanes launched
    phases: dict         # devobs phase walls + the route's own fields
    t0: float            # perf_counter at the start of the route's bracket
    path: str = None     # set when the route ran as a variant of itself
    shards: int = 1
    bucket: int = None   # lanes of the shape that compiled, where not nb


def _comb_resolve(pubkeys, cache_pubs: bool):
    """What verify_batch observes for the comb: the table set every key
    of the batch resolves to (building it on a cache_pubs batch >=
    comb_min_batch()), or None: unknown keys, mixed sets, evicted
    tables, a blown HBM budget, the comb disabled.  One span a batch,
    `outcome` resident / built / declined (the budget refused the
    build) / unknown (every other None), and `early`: true when the
    batch left by the bound (_comb_over_cap: more distinct keys in its
    head than any table set can hold, so it paid the key matrix and a
    count of O(cap) prefixes), false when it paid the distinct-key sort
    and a sha256 over all its rows, as a batch the tables might hold
    does on every call, ahead of the launch's own bracket."""
    with trace.span("comb.resolve", n=len(pubkeys)) as sp:
        comb, outcome, early = _comb_lookup(pubkeys, cache_pubs)
        sp.add(outcome=outcome, early=early)
    return comb


def _comb_lookup(pubkeys, cache_pubs: bool):
    """(_CombSet or None, the outcome and the `early` that
    _comb_resolve's span names)."""
    n = len(pubkeys)
    if n == 0 or not comb_enabled():
        return None, "unknown", False
    can_build = cache_pubs and n >= comb_min_batch()
    # cheap short-circuit: with nothing cached and no build possible,
    # don't pay the key-matrix conversion on every ladder-bound batch
    if len(_table_cache) == 0 and not can_build:
        return None, "unknown", False
    pub_m = _to_u8_matrix(pubkeys, 32)
    if pub_m.shape != (n, 32):
        return None, "unknown", False
    if not can_build:
        # a batch can only resolve to a cached set if EVERY key is in
        # the key-level index (_table_build indexes all of a set's
        # keys), so one O(1) membership probe on the first key gates
        # the O(n log n) distinct-key sort below — a large ladder-bound
        # batch of unknown keys must not pay the lexsort just because
        # some unrelated set is cached
        with _table_key_lock:
            if pub_m[0].tobytes() not in _table_key_index:
                return None, "unknown", False
    if _comb_over_cap(pub_m):
        # what the sort, the look-up and _table_build would answer
        if not can_build:
            return None, "unknown", True
        degrade.publish_route("comb", "declined")
        return None, "declined", True
    uniq, inverse = np.unique(pub_m, axis=0, return_inverse=True)
    inverse = np.asarray(inverse).reshape(-1)
    entry, remap = _table_lookup(uniq)
    built = False
    if entry is None:
        if not can_build:
            return None, "unknown", False
        entry = _table_build(uniq,
                             hashlib.sha256(uniq.tobytes()).digest())
        if entry is None:
            return None, "declined", False
        remap = np.arange(uniq.shape[0], dtype=np.int32)
        built = True
    else:
        degrade.publish_table_cache(hit=True)
    return (_CombSet(entry, pub_m, remap[inverse].astype(np.int32), built),
            "built" if built else "resident", False)


def _comb_buckets(n: int) -> list:
    """Lane buckets of the single-device comb's launches: chunked like
    every other device path (split_chunked_launch, the nb > MAX_CHUNK
    pipelined sub-batching) — one unbounded launch for a huge batch
    would mint a fresh XLA bucket shape per size class and outgrow
    degrade timeouts tuned for <= MAX_CHUNK."""
    return [bucket_size(min(a + MAX_CHUNK, n) - a)
            for a in range(0, n, MAX_CHUNK)]


def _stage_phases(t0: float, c0: float) -> dict:
    """The staging bracket a route opened with (perf_counter,
    thread_time) at its entry, closed now."""
    return {"stage_cpu_s": time.thread_time() - c0,
            "stage_s": time.perf_counter() - t0}


def _launch_serial(obs_on: bool, phases: dict, put, launch):
    """One monolithic launch: `put()` issues the host->device copies
    and returns the operands, `launch(*operands)` dispatches on them.
    Launch decomposition (ADR-021): with the observatory enabled the
    two are bracketed apart — the bracket opens BEFORE the jnp.asarray
    conversions, which are what actually issue the copy, then
    dispatch->block is the compute share — into phases["h2d_s"] /
    ["compute_s"] (summed over a route's launches), at the price of one
    extra block_until_ready on the staged buffers; these paths are
    already device_put -> dispatch -> full block, so nothing is
    serialized that wasn't.  Disabled, it is put -> dispatch and
    nothing else."""
    if not obs_on:
        return launch(*put())
    t_put = time.perf_counter()
    args = put()
    for arg in args:
        arg.block_until_ready()
    t_h2d = time.perf_counter()
    phases["h2d_s"] = phases.get("h2d_s", 0.0) + (t_h2d - t_put)
    out = launch(*args)
    out.block_until_ready()
    phases["compute_s"] = phases.get("compute_s", 0.0) + \
        (time.perf_counter() - t_h2d)
    return out


def lane_bracket() -> tuple:
    """Open a scheme lane's launch bracket (launch_lane closes it): the
    (perf_counter, thread_time) its staging starts at.  Compile seconds
    a dispatch that raised left on this thread are dropped here, as
    verify_batch drops them at its entry."""
    _take_compile_s()
    return time.perf_counter(), time.thread_time()


def launch_lane(path: str, n: int, nb: int, bracket: tuple, operands,
                kernel) -> np.ndarray:
    """One monolithic launch of a scheme lane that lives outside this
    module (ops/secp, ops/sr25519), launched and recorded as the routes
    here are: `operands` are the staged host arrays of `kernel`, padded
    to nb lanes, `bracket` the lane_bracket() taken before staging.  The
    first launch of a (kernel, shapes) pair compiles inside
    degrade.compiling() (launch_kernel); one devobs record under `path`
    with the phases _run_xla's carry.  Returns the kernel's (nb,) bitmap
    on the host."""
    obs_on = devobs.is_enabled()
    phases = _stage_phases(*bracket) if obs_on else {}
    out = _launch_serial(
        obs_on, phases, lambda: [jnp.asarray(a) for a in operands],
        lambda *arrs: launch_kernel(kernel, *arrs))
    t_col = time.perf_counter()
    res = np.asarray(out)  # blocks: the wall below includes execution
    if obs_on:
        phases["collect_s"] = time.perf_counter() - t_col
    _record_launch(path, n, nb, time.perf_counter() - bracket[0],
                   extra=phases)
    return res


def _run_comb(comb: _CombSet, msgs, sigs, plane, obs_on: bool):
    """The comb route (ADR-013) on a resolved set.  Runs under the same
    degrade lane as every other device dispatch, so breaker / timeout /
    host-fallback and the corrupt-bitmap integrity check apply
    unchanged (site ops.ed25519.comb).  `plane`: the local mesh when
    the batch is worth sharding, else None."""
    # chaos seam: a raise/latency armed here fails exactly the comb
    # dispatch (the ladder is NOT retried in-process — the degradation
    # runtime owns the fallback, preserving bitmap identity)
    fail.inject("ops.ed25519.comb")
    entry, pub_m, vidx, built = comb
    n = pub_m.shape[0]
    t0, c0 = time.perf_counter(), time.thread_time()
    _, r_b, s_b, kscal, host_ok = _stage_rows(
        pub_m, _to_u8_matrix(sigs, 64), msgs)
    s_digits = scalars_to_digits(s_b)
    k_digits = scalars_to_digits(kscal)
    phases = _stage_phases(t0, c0) if obs_on else {}
    res, path, nb, shards = None, None, 0, 1
    if plane is not None:
        # the data plane takes the FULL batch: it owns the chunking
        # (double-buffered per-shard staging, ADR-027) and the
        # budget-aware table layout; None (budget declined) or a chaos
        # fault at its seam falls back to the single-device comb below
        # — the tables are resident on the build device, so declining
        # to the ladder would throw the cached work away
        probe = {}
        try:
            mesh_out = plane.verify_comb(r_b, s_digits, k_digits, vidx,
                                         entry, _base_comb(),
                                         probe=probe)
        except fail.InjectedFault:
            degrade.publish_route("mesh-comb", "declined")
            mesh_out = None
        if mesh_out is not None:
            res, nb, shards, path = mesh_out
            if obs_on:
                phases.update(_overlap_phases({
                    "stage_s": phases["stage_s"],
                    "dma_s": probe.get("dma_s", 0.0),
                    "dma_first_s": probe.get("dma_first_s", 0.0),
                    "chunks": probe.get("chunks", 1)}))
                if probe.get("shard_h2d_s"):
                    phases["shard_h2d_s"] = probe["shard_h2d_s"]
                phases.update(devobs.shard_fields(n, nb, shards))
    if res is None:
        by, bm, bt = _base_comb()
        tb = entry.tables
        parts, a = [], 0
        for cnb in _comb_buckets(n):
            m = min(MAX_CHUNK, n - a)
            rc, sc, kc, vc = (r_b[a:a + m], s_digits[a:a + m],
                              k_digits[a:a + m], vidx[a:a + m])
            if cnb != m:
                pad = [(0, cnb - m), (0, 0)]
                rc = np.pad(rc, pad)
                sc = np.pad(sc, pad)
                kc = np.pad(kc, pad)
                vc = np.pad(vc, (0, cnb - m))
            # the operands are the per-launch transfer (the tables are
            # device-resident already: they are the cache)
            out = _launch_serial(
                obs_on, phases,
                lambda: (jnp.asarray(rc), jnp.asarray(sc),
                         jnp.asarray(kc), jnp.asarray(vc)),
                lambda *ops: launch_kernel(
                    comb_kernel, *ops, tb.ypx, tb.ymx, tb.z, tb.t2d,
                    entry.dec_ok, by, bm, bt))
            t_col = time.perf_counter()
            parts.append(np.asarray(out)[:m])
            if obs_on:
                phases["collect_s"] = phases.get("collect_s", 0.0) + \
                    (time.perf_counter() - t_col)
            a += m
            nb += cnb
        res = parts[0] if len(parts) == 1 else np.concatenate(parts)
    phases.update(table_build=built, set_k=entry.k, k_pad=entry.k_pad)
    res = fail.corrupt_bitmap("ops.ed25519.comb",
                              np.asarray(res[:n], dtype=bool))
    return _Launched(res, host_ok, nb, phases, t0, path, shards)


SPLIT_CHUNK = 16384  # chunk of a split launch of more rows than that
# chunk of every split launch up to SPLIT_CHUNK rows, a multiple of
# PALLAS_TILE: of 1,024 / 2,048 / 4,096 the one the chip's sweep kept
# (PERF.md section 6, PR 36)
SPLIT_CHUNK_SMALL = 1024


def _split_chunk(n: int) -> int:
    """Lanes per launch of the split path, from the row count alone:
    SPLIT_CHUNK_SMALL for a batch of up to SPLIT_CHUNK rows, SPLIT_CHUNK
    above.  A batch pads to a multiple of the chunk, NOT to a
    power-of-two bucket: every launch has the same (96, chunk) shape
    (one compile for every length of its range), 6,667 rows pad to
    7 x 1,024 = 7,168 lanes instead of 8,192 and a 100k batch to
    7 x 16,384 = 114,688 instead of 131,072.  The small chunk is what
    lets a batch of one old chunk run as a pipeline at all: its kernel
    starts after a chunk's staging, not the batch's.  (That the chunk
    jumps back up past SPLIT_CHUNK rows is held by the benchmark's pin
    of val100k-commit's launch shape, not by a measurement: PERF.md
    section 7 row 36.)"""
    return SPLIT_CHUNK_SMALL if n <= SPLIT_CHUNK else SPLIT_CHUNK


def _msgs_slice(msgs, a: int, b: int):
    from tendermint_tpu.libs.ragged import RaggedBytes

    if isinstance(msgs, RaggedBytes):
        return msgs.slice(a, b)
    return msgs[a:b]


def split_chunked_launch(pubkeys, msgs, sigs, probe: dict = None):
    """Cache-path launcher with a three-stage pipeline over chunks of
    _split_chunk(n) lanes: while the kernel runs chunk j, the host
    stages chunk j+1 (C challenge hashing + packing) and its DMA
    proceeds — so staging AND transfer hide behind compute and the wall
    clock approaches the kernel floor plus ONE chunk's staging, for a
    light client's 6,667 rows (7 chunks of 1,024) as for a
    100k-validator VerifyCommit (7 of 16,384).  Pubkey rows come from
    the device-resident cache (96 B/sig on the wire); rows it does not
    hold go up chunk by chunk with the staged rows, never all ahead of
    the first kernel.

    NON-BLOCKING: returns (outs, host_ok, n) where outs is the list of
    per-chunk device result arrays still in flight — callers that
    pipeline multiple batches (bench.py) block once at the end; the
    verify_batch wrapper below reads them back chunk by chunk.

    `probe` (optional dict, ADR-021): filled with the summed per-chunk
    staging walls (stage_s) and DMA walls (dma_s / dma_first_s /
    chunks), measured without adding any synchronization — the
    decomposition must never serialize the pipeline it measures — and
    with what _pub_cache_get found (pub_rows_cached / pub_rows_bytes)."""
    from . import pallas_ed25519 as pe

    n = len(pubkeys)
    chunk = _split_chunk(n)
    nb = -(-n // chunk) * chunk
    nsub = nb // chunk
    pub_m = _to_u8_matrix(pubkeys, 32)
    sig_m = _to_u8_matrix(sigs, 64)
    pub_rows = np.ascontiguousarray(pub_m.T)
    if nb != n:
        pub_rows = np.pad(pub_rows, [(0, 0), (0, nb - n)])
    t_rows = time.perf_counter()
    rows_of = _pub_cache_get(pub_rows, nsub, probe)
    if probe is not None:
        # the rows' content key and the look-up (a miss's uploads ride
        # with the chunks' puts below)
        probe["pub_rows_s"] = time.perf_counter() - t_rows
    host_ok = np.zeros(nb, dtype=bool)

    stage_walls = []
    stage_cpus = []

    def stage(j):
        t_st, c_st = time.perf_counter(), time.thread_time()
        a, b = j * chunk, min((j + 1) * chunk, n)
        if a >= n:  # pure padding chunk: zeroed inputs fail on-device
            stage_cpus.append(time.thread_time() - c_st)
            stage_walls.append(time.perf_counter() - t_st)
            return np.zeros((96, chunk), dtype=np.int8)
        _, r_b, s_b, k, ok = _stage_rows(pub_m[a:b], sig_m[a:b],
                                         _msgs_slice(msgs, a, b))
        host_ok[a:b] = ok
        rsk = np.zeros((96, chunk), dtype=np.uint8)
        rsk[0:32, : b - a] = r_b.T
        rsk[32:64, : b - a] = s_b.T
        rsk[64:96, : b - a] = k.T
        stage_cpus.append(time.thread_time() - c_st)
        stage_walls.append(time.perf_counter() - t_st)
        return rsk.view(np.int8)

    dev = jax.devices()[0]
    put_walls = []

    def put(j):
        # chunk j's pubkey rows (an upload where the cache has none, and
        # then ahead of the staging, so that it flies meanwhile), then
        # its staged rows
        t_put = time.perf_counter()
        ops = rows_of(j), jax.device_put(stage(j), dev)
        put_walls.append(time.perf_counter() - t_put)
        return ops

    outs = []
    # two rsk chunks in flight at the peak (cur being consumed + nxt
    # staged-and-transferring) — the double-buffered window, same
    # accounting as verify_packed_pipelined
    inflight = (2 if nsub > 1 else 1) * 96 * chunk
    devobs.ledger_add("staging", inflight)
    try:
        nxt = put(0)
        for j in range(nsub):
            outs.append(launch_kernel(pe.verify_packed_split_pallas,
                                      *nxt, tile=PALLAS_TILE))
            if j == 0 and probe is not None:
                # the head of the launch ends here: the first chunk's
                # kernel is dispatched (a clock read, no synchronisation)
                probe["head_t"] = time.perf_counter()
            if j + 1 < nsub:
                # stage j+1 on the host while the kernel runs chunk j; its
                # device_put is issued after the dispatch so the DMA also
                # overlaps (same scheme as verify_packed_pipelined)
                nxt = put(j + 1)
    finally:
        devobs.ledger_add("staging", -inflight)
    if probe is not None:
        # the put wall here includes the chunk's host staging (staged
        # inline inside the put expression): report the DMA share with
        # staging subtracted so stage_s + dma_s don't double-count
        probe["stage_s"] = sum(stage_walls)
        probe["stage_cpu_s"] = sum(stage_cpus)
        probe["dma_s"] = max(0.0, sum(put_walls) - sum(stage_walls))
        probe["dma_first_s"] = max(0.0, put_walls[0] - stage_walls[0])
        probe["chunks"] = nsub
    return outs, host_ok[:n], n


class Route(NamedTuple):
    """One rung of verify_batch's ladder, as select_routes names it."""
    path: str      # what the launch record, crypto_msm_route_total and
    #                the benchmark's ledger print
    nb: int        # padded lanes of the whole call (the record's nb);
    #                None on the mesh: the plane buckets per shard
    launches: int  # kernel launches the call makes; None on the mesh


def select_routes(n: int, cache_pubs: bool, *, pallas: bool,
                  comb_resident: bool, plane_worth: bool) -> tuple:
    """The routes verify_batch tries for a batch of n rows, in order;
    it takes the first, and the next only when that one declines (a
    comb bug, a chaos fault at the mesh seam).  Pure: decided by what
    the caller observed and the module's thresholds, nothing else.

      comb          every key resolves to one device-resident table set
                    (ADR-013), whatever the backend
      mesh-*        the local plane says the batch is worth sharding
                    (parallel/sharding.data_plane; off on TPU while
                    sharding.MESH_ON_TPU is)
      pallas-split  on TPU, a cache_pubs batch of >= PUB_CACHE_MIN rows:
                    pubkey rows device-resident, 96 B/sig on the wire,
                    ceil(n / chunk) pipelined launches of chunk =
                    _split_chunk(n) lanes: SPLIT_CHUNK_SMALL up to
                    SPLIT_CHUNK rows, SPLIT_CHUNK above
      pallas        on TPU otherwise: one packed launch of the
                    power-of-two bucket, MAX_CHUNK sub-launches past it
      xla           every other backend: the XLA-composed kernel

    The last is always a single-device route, which never declines."""
    routes = []
    if comb_resident:
        buckets = _comb_buckets(n)
        routes.append(Route("comb", sum(buckets), len(buckets)))
    if plane_worth:
        routes.append(Route("mesh-pallas" if pallas else "mesh-xla",
                            None, None))
    if not pallas:
        routes.append(Route("xla", bucket_size(n), 1))
    elif cache_pubs and n >= PUB_CACHE_MIN:
        chunk = _split_chunk(n)
        launches = -(-n // chunk)
        routes.append(Route("pallas-split", launches * chunk, launches))
    else:
        nb = max(PALLAS_TILE, bucket_size(n))
        routes.append(Route("pallas", nb, max(1, nb // MAX_CHUNK)))
    return tuple(routes)


def _run_split(pubkeys, msgs, sigs, route: Route, obs_on: bool):
    t0 = time.perf_counter()
    probe = {}
    outs, host_ok, _ = split_chunked_launch(pubkeys, msgs, sigs,
                                            probe=probe)
    # head_s: the bracket's start to the return of the first chunk's
    # launch_kernel, i.e. everything the device waited for before its
    # first kernel (one chunk's staging and puts, whatever the number of
    # chunks); pub_rows_s (_pub_cache_get's wall) is the rows' content
    # key and look-up inside it
    probe["head_s"] = probe.pop("head_t") - t0
    overlap = _overlap_phases(probe)
    # how many chunks the launch had, and what share of its puts had a
    # kernel to hide behind
    trace.current().add(pub_rows_s=probe["pub_rows_s"],
                        head_s=probe["head_s"], chunks=overlap["chunks"],
                        chunk_overlap=round(overlap["chunk_overlap"], 4))
    phases = overlap if obs_on else {}
    # what _pub_cache_get found: facts, not timings, so on the launch
    # record and on verify_batch's span whether or not the observatory
    # brackets the launch
    rows = {k: probe[k] for k in ("pub_rows_cached", "pub_rows_bytes")
            if k in probe}
    phases.update(rows)
    trace.current().add(**rows)
    # the chunks' results are joined on the host (no program of `chunks`
    # operands to compile for a prefix of another length), and the
    # chunk's shape is all that compiles
    return _Launched(outs, host_ok, route.nb, phases, t0,
                     bucket=route.nb // route.launches)


def _run_pallas(pubkeys, msgs, sigs, route: Route, obs_on: bool):
    from . import pallas_ed25519 as pe

    t0, c0 = time.perf_counter(), time.thread_time()
    packed, host_ok = prepare_batch_packed(pubkeys, sigs, msgs)
    n, nb = host_ok.shape[0], route.nb
    if nb != n:  # pad the trailing (lane) axis
        packed = np.pad(packed, [(0, 0), (0, nb - n)])
    phases = _stage_phases(t0, c0) if obs_on else {}
    if route.launches > 1:
        # huge batches (100k-validator VerifyCommit) run as MAX_CHUNK
        # sub-batches with transfer/compute pipelining — same lane
        # buckets the headline path uses, and the DMA of chunk j+1
        # overlaps the kernel of chunk j
        probe = {}
        out = jnp.concatenate(verify_packed_pipelined(
            packed, nsub=route.launches, probe=probe))
        if obs_on:
            phases.update(_overlap_phases(probe))
    else:
        out = _launch_serial(
            obs_on, phases, lambda: (jnp.asarray(packed),),
            lambda buf: launch_kernel(pe.verify_packed_pallas, buf,
                                      tile=min(PALLAS_TILE, nb)))
    return _Launched(out, host_ok, nb, phases, t0)


def _run_xla(pubkeys, msgs, sigs, route: Route, obs_on: bool):
    t0, c0 = time.perf_counter(), time.thread_time()
    dev, host_ok = prepare_batch(pubkeys, sigs, msgs)
    dev = _pad_dev(dev, host_ok.shape[0], route.nb)
    phases = _stage_phases(t0, c0) if obs_on else {}
    out = _launch_serial(
        obs_on, phases, lambda: [jnp.asarray(dev[k]) for k in _XLA_ARGS],
        lambda *arrs: launch_kernel(verify_kernel, *arrs))
    return _Launched(out, host_ok, route.nb, phases, t0)


# stage + launch of each single-device route: (pubkeys, msgs, sigs,
# route, obs_on) -> _Launched with the result still in flight
_SINGLE_DEVICE = {"pallas-split": _run_split, "pallas": _run_pallas,
                  "xla": _run_xla}


def _comb_policy(sp, fn, *args):
    """The comb's error policy around its two steps (_comb_resolve,
    _run_comb).  A comb fault degrades like any other device fault:
    chaos AND real device faults (XlaRuntimeError subclasses
    RuntimeError) must reach the degrade runtime wrapping this dispatch
    — re-dispatching the batch through the ladder on the same
    possibly-dead device would just burn a doomed launch before the
    breaker sees the failure.  A comb BUG (shape / typing / indexing)
    must not kill verification: it is routed, and None sends the batch
    down the ladder."""
    try:
        return fn(*args)
    except (fail.InjectedFault, RuntimeError):
        raise
    except Exception as e:  # noqa: BLE001 - see above
        degrade.publish_route("comb", "error")
        sp.add(comb_error=type(e).__name__)
        return None


def _local_plane():
    # sharding imports this module at load
    from tendermint_tpu.parallel.sharding import data_plane
    return data_plane()


def verify_batch(pubkeys, msgs, sigs, cache_pubs: bool = False) -> np.ndarray:
    """End-to-end batched verify (host staging + device kernel).
    Returns a (B,) bool validity bitmap.

    Select, run, record.  select_routes says which route a batch takes
    and why; on TPU the fused Pallas kernel (ops/pallas_ed25519.py)
    runs the whole verification in VMEM, elsewhere the XLA kernel; a set
    whose comb tables are resident takes the comb; on a multi-device
    host the batch shards across the local mesh.  This function is the
    single seam every verifier in the node goes through.

    cache_pubs: the caller asserts the pubkey set recurs across calls
    (validator-set paths — crypto/batch.verify_sigs_bulk): the (32, B)
    pubkey rows are kept device-resident keyed by content hash, so
    steady-state VerifyCommit ships 96 B/sig instead of 128."""
    # chaos seam: the degradation runtime (crypto/degrade.py) wraps every
    # dispatch into this function, so an injected raise/latency here is
    # indistinguishable from a real device fault to the callers
    fail.inject("ops.ed25519.verify_batch")
    _take_compile_s()
    n = len(pubkeys)
    with trace.span("ops.ed25519.verify_batch", n=n) as sp:
        plane = _local_plane()
        worth = plane is not None and plane.worth_sharding(n)
        comb = _comb_policy(sp, _comb_resolve, pubkeys, cache_pubs)
        routes = select_routes(n, cache_pubs, pallas=_use_pallas(),
                               comb_resident=comb is not None,
                               plane_worth=worth)
        obs_on = devobs.is_enabled()
        for route in routes:
            if route.path == "comb":
                launched = _comb_policy(sp, _run_comb, comb, msgs, sigs,
                                        plane if worth else None, obs_on)
            elif route.path in _SINGLE_DEVICE:
                launched = _SINGLE_DEVICE[route.path](
                    pubkeys, msgs, sigs, route, obs_on)
            else:
                # the mesh stages, launches, reads back and records on
                # its own (parallel/sharding)
                try:
                    return plane.verify_batch(pubkeys, msgs, sigs)
                except fail.InjectedFault:
                    # chaos at the mesh staging seam
                    # (sharding.mesh_stage): degrade THIS batch to the
                    # single-device route, bitmap identical
                    degrade.publish_route(route.path, "declined")
                    sp.add(mesh_fault=True)
                    launched = None
            if launched is not None:
                break
        out, host_ok, nb, phases, t0, path, shards, bucket = launched
        if isinstance(out, np.ndarray):
            res = out  # the route blocked inside and read back itself
        else:
            t_col = time.perf_counter()
            # blocks: wall below includes execution
            res = np.concatenate([np.asarray(o) for o in out]) \
                if isinstance(out, list) else np.asarray(out)
            if obs_on:
                # routes that bracketed compute have only the readback
                # left here (collect_s); the double-buffered ones block
                # for the FIRST time here, so the wait is residual
                # compute + D2H merged — recorded as drain_s, never
                # mislabeled collect
                key = "collect_s" if "compute_s" in phases else "drain_s"
                phases[key] = time.perf_counter() - t_col
        wall_s = time.perf_counter() - t0
        _record_launch(path or route.path, n, nb, wall_s, shards=shards,
                       extra=phases, bucket=bucket)
        # the wall the launch record holds: the span less this less its
        # comb.resolve child is what verify_batch does OUTSIDE its bracket
        sp.add(bracket_ns=int(wall_s * 1e9))
        return res[:n] & host_ok
