"""Batched secp256k1 BIP-340 Schnorr verification on TPU lanes.

The reference verifies secp256k1 one signature at a time through btcec
(reference crypto/secp256k1/secp256k1.go:197-212, x-only Schnorr); the
repo's host C lane (native/ecverify.c tm_secp_verify*) batches on one CPU
core.  This lane moves the curve work onto the TPU: one signature per
vector lane over ops/field_secp.py, with a 64-step fixed-window Straus
ladder computing R' = [s]G + [e](-P).

Design notes (vs the ed25519 lane):
  * Jacobian coordinates on y^2 = x^3 + 7.  Short-Weierstrass addition
    formulas are NOT complete, and an attacker fully controls (s, P), so
    every table/ladder addition is made complete by computing both the
    generic add (add-2007-bl) and the doubling (dbl-2009-l) and selecting
    per lane on the degenerate flags (P = Q, P = -Q, either infinity).
    A formula breakdown here would be attacker-steerable garbage that
    the final x-compare could be made to accept.
  * UNSIGNED radix-16 digits (64 per 256-bit scalar) with 16-entry
    tables: secp scalars span the full 256 bits, so the balanced-digit
    trick used for ed25519 (top nibble <= 1) does not apply.
  * Verdicts are per-signature exact (BIP-340 semantics: R' finite, even
    y, x(R') == r), matching the host C per-sig path bit-for-bit.
"""
from __future__ import annotations

import hashlib
import os
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from . import field_secp as FS


# default ON since ADR-015 (config [batch_verifier] secp_lane /
# TM_TPU_SECP_LANE=0 is the rollback switch, wired by node assembly via
# set_lane_enabled()).  The lane only ever engages when an accelerator
# is actually attached (crypto/batch._use_device gates every device
# dispatch), runs under the full degradation runtime — breaker,
# per-launch timeout, host C fallback with exact bitmaps — at sites
# batch.secp256k1/sched.secp256k1, and its verdicts are per-signature
# exact (BIP-340), pinned against the host oracle in
# tests/test_secp_lane.py.  On a host with no device nothing changes:
# the host C lane keeps serving, now multi-core through
# crypto/lanepool.py.
_lane_override: "bool | None" = None


def set_lane_enabled(on: "bool | None"):
    """Config-driven override of the device-lane default (wins over the
    env, both directions).  None clears the override so
    TM_TPU_SECP_LANE governs again."""
    global _lane_override
    _lane_override = None if on is None else bool(on)


def use_lane() -> bool:
    if _lane_override is not None:
        return _lane_override
    # rollback accepts the natural spellings, not just "0" — an
    # operator typing TM_TPU_SECP_LANE=false (mirroring the config's
    # `secp_lane = false`) must not silently keep the lane on
    return os.environ.get("TM_TPU_SECP_LANE", "1").strip().lower() \
        not in ("0", "false", "off", "no")

_i32 = jnp.int32

P = FS.P
# group order
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
GX = 0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798
GY = 0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8


class Jac(NamedTuple):
    x: jnp.ndarray
    y: jnp.ndarray
    z: jnp.ndarray


def infinity(batch=()):
    return Jac(FS.one(batch), FS.one(batch), FS.zero(batch))


def dbl(p: Jac) -> Jac:
    """dbl-2009-l (a = 0).  Complete for every input except y = 0 points
    (none exist on x^3 + 7: -7 is not a cube mod p), and maps infinity
    (z = 0) to z = 0."""
    a = FS.sqr(p.x)
    b = FS.sqr(p.y)
    c = FS.sqr(b)
    d = FS.carry(2 * (FS.sqr(FS.carry(p.x + b)) - a - c))
    e = FS.carry(3 * a)
    f = FS.sqr(e)
    x3 = FS.carry(f - 2 * d)
    y3 = FS.carry(FS.mul(e, FS.carry(d - x3)) - FS.carry(8 * c))
    z3 = FS.carry(2 * FS.mul(p.y, p.z))
    return Jac(x3, y3, z3)


def add(p: Jac, q: Jac) -> Jac:
    """Complete addition: add-2007-bl with per-lane select fallbacks for
    the degenerate cases (infinity operands, P = Q -> dbl, P = -Q ->
    infinity)."""
    z1z1 = FS.sqr(p.z)
    z2z2 = FS.sqr(q.z)
    u1 = FS.mul(p.x, z2z2)
    u2 = FS.mul(q.x, z1z1)
    s1 = FS.mul(FS.mul(p.y, q.z), z2z2)
    s2 = FS.mul(FS.mul(q.y, p.z), z1z1)
    h = FS.carry(u2 - u1)
    i = FS.sqr(FS.carry(2 * h))
    j = FS.mul(h, i)
    r = FS.carry(2 * (s2 - s1))
    v = FS.mul(u1, i)
    x3 = FS.carry(FS.sqr(r) - j - 2 * v)
    y3 = FS.carry(FS.mul(r, FS.carry(v - x3)) - 2 * FS.mul(s1, j))
    z3 = FS.mul(FS.carry(FS.sqr(FS.carry(p.z + q.z)) - z1z1 - z2z2), h)
    generic = Jac(x3, y3, z3)

    inf1 = FS.is_zero(p.z)
    inf2 = FS.is_zero(q.z)
    same_x = FS.is_zero(h)
    same_y = FS.is_zero(r)
    doubled = dbl(p)
    ident = infinity(h.shape[1:])

    def sel(cond, a, b):
        return Jac(FS.select(cond, a.x, b.x), FS.select(cond, a.y, b.y),
                   FS.select(cond, a.z, b.z))

    out = sel(same_x & same_y, doubled, generic)   # P = Q
    out = sel(same_x & ~same_y & ~inf1 & ~inf2, ident, out)  # P = -Q
    out = sel(inf2, p, out)
    out = sel(inf1, q, out)
    return out


def _gather16(digit, rows):
    """Per-lane gather of digit in 0..15 from a (16, NLIMB, B) stacked
    array (take_along_axis, the ed25519 lane's _gather_cached idiom —
    the seed's 15-step jnp.where chain per coordinate bloated the ladder
    body's HLO for no benefit)."""
    idx = digit[None, None, :]  # (1, 1, B)
    return jnp.take_along_axis(rows, idx, axis=0)[0]


def _g_table_np():
    """Affine multiples j*G for j = 0..15 as Jacobian rows (z = 0 for
    j = 0, z = 1 otherwise), import-time bignum."""
    def aff_add(a, b):
        if a is None:
            return b
        (x1, y1), (x2, y2) = a, b
        if x1 == x2 and (y1 + y2) % P == 0:
            return None
        lam = ((3 * x1 * x1) * pow(2 * y1, P - 2, P)) % P if a == b \
            else ((y2 - y1) * pow(x2 - x1, P - 2, P)) % P
        x3 = (lam * lam - x1 - x2) % P
        return (x3, (lam * (x1 - x3) - y1) % P)

    pts = [None]
    acc = None
    for _ in range(15):
        acc = aff_add(acc, (GX, GY)) if acc else (GX, GY)
        pts.append(acc)
    xs = np.stack([FS.int_to_limbs(p[0] if p else 1) for p in pts])
    ys = np.stack([FS.int_to_limbs(p[1] if p else 1) for p in pts])
    zs = np.stack([FS.int_to_limbs(0 if p is None else 1) for p in pts])
    return xs, ys, zs


_G_X, _G_Y, _G_Z = (jnp.asarray(t) for t in _g_table_np())


def _p_table(negp: Jac):
    """Jacobian multiples j*(-P) for j = 0..15 as stacked (16, NLIMB, B)
    coordinate arrays, built on device: 1 dbl + a 13-step lax.scan of
    complete adds (the seed unrolled the 13 adds — each one a complete
    add+dbl+select tree — into straight-line HLO, a major share of the
    graph that kept this lane from ever compiling)."""
    batch = negp.x.shape[1:]
    d = dbl(negp)

    def step(acc, _):
        nxt = add(acc, negp)
        return nxt, nxt

    _, rest = jax.lax.scan(step, d, None, length=13)  # 3P .. 15P
    inf = infinity(batch)
    return Jac(*(
        jnp.concatenate([jnp.stack([getattr(p, f) for p in (inf, negp, d)],
                                   axis=0),
                         getattr(rest, f)], axis=0)
        for f in ("x", "y", "z")))


@jax.jit
def _verify_core(px_limbs, rx_limbs, s_digits, e_digits):
    """px/rx: (NLIMB, B) canonical field limbs; s/e digits: (64, B) int32
    unsigned radix-16, most-significant first.  Returns (B,) bool."""
    batch = px_limbs.shape[1:]
    # lift_x: even-y point with x = px (BIP-340)
    xx = FS.sqr(px_limbs)
    x3p7 = FS.carry(FS.mul(xx, px_limbs) + FS.one(batch) * 7)
    y = FS.sqrt(x3p7)
    decode_ok = FS.eq(FS.sqr(y), x3p7)
    y = FS.select(FS.is_odd(y), FS.carry(-y), y)
    # negate for R' = [s]G + [e](-P)
    negp = Jac(px_limbs, FS.carry(-y), FS.one(batch))
    ptab = _p_table(negp)

    def gather_g(digit):
        """Fixed-base row: per-lane take from the (16, NLIMB) import-time
        G table (cf. ed25519 _gather_base_niels)."""
        return Jac(jnp.take(_G_X, digit, axis=0).T,
                   jnp.take(_G_Y, digit, axis=0).T,
                   jnp.take(_G_Z, digit, axis=0).T)

    def body(i, acc):
        acc = dbl(dbl(dbl(dbl(acc))))
        ds = jax.lax.dynamic_index_in_dim(s_digits, i, 0, keepdims=False)
        de = jax.lax.dynamic_index_in_dim(e_digits, i, 0, keepdims=False)
        acc = add(acc, gather_g(ds))
        q = Jac(_gather16(de, ptab.x), _gather16(de, ptab.y),
                _gather16(de, ptab.z))
        return add(acc, q)

    rp = jax.lax.fori_loop(0, 64, body, infinity(batch))
    inf = FS.is_zero(rp.z)
    zi = FS.invert(rp.z)
    zi2 = FS.sqr(zi)
    x_aff = FS.mul(rp.x, zi2)
    y_aff = FS.mul(rp.y, FS.mul(zi2, zi))
    return decode_ok & ~inf & FS.eq(x_aff, rx_limbs) & ~FS.is_odd(y_aff)


# ---------------------------------------------------------------------------
# host staging
# ---------------------------------------------------------------------------

def _tagged_hash(tag: str, data: bytes) -> bytes:
    th = hashlib.sha256(tag.encode()).digest()
    return hashlib.sha256(th + th + data).digest()


def _nibbles_be(rows: np.ndarray) -> np.ndarray:
    """(B, 32) big-endian scalar bytes -> (64, B) int32 nibbles, most
    significant first."""
    hi = rows >> 4
    lo = rows & 0x0F
    out = np.empty((rows.shape[0], 64), dtype=np.int32)
    out[:, 0::2] = hi
    out[:, 1::2] = lo
    return np.ascontiguousarray(out.T)


def _limbs_of_be(rows: np.ndarray) -> np.ndarray:
    """(B, 32) big-endian field-element bytes -> (NLIMB, B) limbs."""
    B = rows.shape[0]
    out = np.zeros((FS.NLIMB, B), dtype=np.int32)
    vals = rows.astype(np.int64)
    # bit j of the value = byte (31 - j//8), bit (j%8)
    for limb in range(FS.NLIMB):
        lo_bit = limb * FS.RADIX
        for bit in range(FS.RADIX):
            j = lo_bit + bit
            if j >= 256:
                break
            byte = 31 - (j // 8)
            out[limb] |= ((vals[:, byte] >> (j % 8)) & 1).astype(
                np.int32) << bit
    return out


LANE_PATH = "secp-xla"    # the lane's name in launch records and routes


def _stage(pubs, msgs, sigs):
    """Host staging of n well-formed rows: the BIP-340 range screens and
    the tagged-hash challenge a row, then limb and digit packing of the
    batch padded to its bucket.  Returns (the four operands of
    _verify_core, host_ok (n,), the padded lanes)."""
    from . import ed25519 as ed

    n = len(pubs)
    nb = ed.bucket_size(n)
    px = np.zeros((nb, 32), dtype=np.uint8)
    rx = np.zeros((nb, 32), dtype=np.uint8)
    s_rows = np.zeros((nb, 32), dtype=np.uint8)
    e_rows = np.zeros((nb, 32), dtype=np.uint8)
    host_ok = np.zeros(n, dtype=bool)
    for i in range(n):
        pub = bytes(pubs[i])
        sig = bytes(sigs[i])
        px_i = int.from_bytes(pub[1:], "big")
        r_i = int.from_bytes(sig[:32], "big")
        s_i = int.from_bytes(sig[32:], "big")
        if px_i >= P or r_i >= P or s_i >= N:
            continue  # BIP-340 range screens
        m32 = hashlib.sha256(bytes(msgs[i])).digest()
        e_i = int.from_bytes(
            _tagged_hash("BIP0340/challenge", sig[:32] + pub[1:] + m32),
            "big") % N
        px[i] = np.frombuffer(pub[1:], np.uint8)
        rx[i] = np.frombuffer(sig[:32], np.uint8)
        s_rows[i] = np.frombuffer(sig[32:], np.uint8)
        e_rows[i] = np.frombuffer(e_i.to_bytes(32, "big"), np.uint8)
        host_ok[i] = True
    return (_limbs_of_be(px), _limbs_of_be(rx), _nibbles_be(s_rows),
            _nibbles_be(e_rows)), host_ok, nb


def verify_batch_device(pubs, msgs, sigs) -> np.ndarray:
    """Batched BIP-340 verify: host staging (tagged-hash challenge,
    scalar screens) + the device ladder.  pubs: 33-byte compressed keys
    (x-only semantics: the parity byte must parse, reference
    secp256k1.go:203-212); sigs: 64-byte (r, s) big-endian.  Malformed
    lengths are rejected host-side without poisoning the batch.  One
    span and one launch record (path LANE_PATH) a launch, the first
    launch of a bucket compiled inside degrade.compiling(), as the
    ed25519 routes do it (ops/ed25519.launch_lane)."""
    from tendermint_tpu.libs import fail, trace

    from . import ed25519 as ed

    # chaos seam: same role as ops/ed25519.verify_batch's — it fires at
    # entry, BEFORE any staging or kernel dispatch, so an armed "raise"
    # proves the degrade plumbing without spending the multi-minute
    # XLA-on-CPU compile of the 64-step complete-add ladder
    fail.inject("ops.secp.verify_batch")
    n = len(pubs)
    if n == 0:
        return np.zeros(0, dtype=bool)
    ok_len = np.array([
        len(pubs[i]) == 33 and bytes(pubs[i])[0] in (2, 3)
        and len(sigs[i]) == 64 for i in range(n)])
    if not ok_len.all():
        good = np.flatnonzero(ok_len)
        if good.size == 0:
            return ok_len
        out = np.zeros(n, dtype=bool)
        out[good] = verify_batch_device([pubs[i] for i in good],
                                        [msgs[i] for i in good],
                                        [sigs[i] for i in good])
        return out
    with trace.span("ops.secp.verify_batch", n=n):
        bracket = ed.lane_bracket()
        with trace.span("secp.stage", n=n):
            operands, host_ok, nb = _stage(pubs, msgs, sigs)
        out = ed.launch_lane(LANE_PATH, n, nb, bracket, operands,
                             _verify_core)
    return out[:n] & host_ok


def warm_bucket(n: int) -> int:
    """Launch the bucket an n-row batch pads to, on rows that stage to
    nothing (all-zero operands, every lane refused), by a direct call:
    its one-time trace + compile is paid here, off any request, and the
    (LANE_PATH, nb) bucket is marked seen.  What a benchmark's warm-up
    calls, and what a node can call when a set with secp256k1 keys
    comes into force.  Returns nb."""
    from . import ed25519 as ed

    nb = ed.bucket_size(n)
    zeros = np.zeros((nb, 32), dtype=np.uint8)
    ed.launch_lane(LANE_PATH, 0, nb, ed.lane_bracket(),
                   (_limbs_of_be(zeros), _limbs_of_be(zeros),
                    _nibbles_be(zeros), _nibbles_be(zeros)), _verify_core)
    return nb
