"""Batched sr25519 (schnorrkel) verification on TPU.

The reference verifies sr25519 serially through go-schnorrkel (reference
crypto/sr25519/pubkey.go:29-59).  sr25519 lives on the SAME curve as
ed25519 (curve25519 in Edwards form, ristretto-encoded), so the TPU lane
reuses the whole ed25519 device stack — field (ops/field.py), curve ops,
and the joint Straus ladder (ops/ed25519.straus_ladder) — and only the
encoding differs:

  host   merlin transcript challenge k (native C tm_sr25519_stage; the
         pure-Python _strobe fallback), s-canonicity, ristretto byte
         screens
  device ristretto decode of A and R (ops/ristretto.py), the ladder
         [s]B + [k](-A), ristretto equality against R

Per-signature exact (no RLC): each lane independently reproduces
schnorrkel's accept/reject, so the bitmap is attribution-ready, matching
the host C lane's per-sig semantics (native/ecverify.c
tm_sr25519_verify)."""
from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from . import curve as C
from . import ed25519 as ed
from . import field as F
from . import ristretto

_i32 = jnp.int32


def _bytes_to_limbs_dev(b):
    """(m, 32) uint8 -> (NLIMB, m) limbs of the full 256-bit value (the
    ristretto screens already force bit 255 = 0)."""
    limbs, _sign = ed.bytes256_to_limbs(b)
    return limbs


@jax.jit
def _verify_core(pub_bytes, r_bytes, s_digits, k_digits):
    """pub/r: (n, 32) uint8 ristretto encodings; s/k digits: (n, 64) int8
    signed radix-16.  Returns (n,) bool."""
    a_pt, a_ok = ristretto.decode(_bytes_to_limbs_dev(pub_bytes))
    r_pt, r_ok = ristretto.decode(_bytes_to_limbs_dev(r_bytes))
    neg_a = C.Ext(F.carry_lazy(-a_pt.x), a_pt.y, a_pt.z,
                  F.carry_lazy(-a_pt.t))
    p = ed.straus_ladder(neg_a, s_digits.astype(_i32).T,
                         k_digits.astype(_i32).T)
    return a_ok & r_ok & ristretto.eq(p, r_pt)


def _stage_host(pubs, msgs, sigs):
    """(k (n,32), s (n,32), ok (n,)) via the C stager, pure-Python merlin
    fallback otherwise."""
    from tendermint_tpu.libs import native

    res = native.sr25519_stage(pubs, msgs, sigs)
    if res is not None:
        return res
    from tendermint_tpu.crypto import sr25519 as srpy

    n = len(pubs)
    k = np.zeros((n, 32), dtype=np.uint8)
    s = np.zeros((n, 32), dtype=np.uint8)
    ok = np.zeros(n, dtype=bool)
    for i in range(n):
        sig, pub = bytes(sigs[i]), bytes(pubs[i])
        if len(sig) != 64 or len(pub) != 32 or not (sig[63] & 0x80):
            continue
        s_b = bytearray(sig[32:])
        s_b[31] &= 0x7F
        if int.from_bytes(bytes(s_b), "little") >= srpy.L:
            continue
        t = srpy.signing_context(b"", bytes(msgs[i]))
        t.append_message(b"proto-name", b"Schnorr-sig")
        t.append_message(b"sign:pk", pub)
        t.append_message(b"sign:R", sig[:32])
        ki = srpy._challenge_scalar(t, b"sign:c")
        k[i] = np.frombuffer(ki.to_bytes(32, "little"), dtype=np.uint8)
        s[i] = np.frombuffer(bytes(s_b), dtype=np.uint8)
        ok[i] = True
    return k, s, ok


LANE_PATH = "sr25519-xla"   # the lane's name in launch records and routes


def _stage(pubs, msgs, sigs):
    """Host staging of n well-formed rows: the merlin challenge and the
    marker / s < L screens a row (_stage_host), the ristretto byte
    screens, digit packing, padding to the batch's bucket.  Returns (the
    four operands of _verify_core, host_ok (n,), the padded lanes)."""
    n = len(pubs)
    nb = ed.bucket_size(n)
    pub_m = ed._to_u8_matrix([bytes(p) for p in pubs], 32)
    sig_m = ed._to_u8_matrix([bytes(s) for s in sigs], 64)
    k, s, host_ok = _stage_host(pubs, msgs, sigs)
    r_bytes = np.ascontiguousarray(sig_m[:, :32])
    # ristretto byte screens (host-vectorized): encodings must be
    # canonical (< p) and nonnegative (even)
    host_ok = host_ok & ristretto.bytes_canonical_nonneg(pub_m) \
        & ristretto.bytes_canonical_nonneg(r_bytes)
    operands = (pub_m, r_bytes, ed.scalars_to_digits(s),
                ed.scalars_to_digits(k))
    if nb != n:
        pad = [(0, nb - n), (0, 0)]
        operands = tuple(np.pad(a, pad) for a in operands)
    return operands, host_ok, nb


def verify_batch_device(pubs, msgs, sigs) -> np.ndarray:
    """End-to-end batched sr25519 verify: host staging + device lanes.
    Returns a (n,) bool bitmap with per-sig exact semantics.  Malformed
    lengths are rejected host-side without poisoning the batch (same
    guard as crypto/batch.verify_ed25519_batch).  One span and one
    launch record (path LANE_PATH) a launch, the first launch of a
    bucket compiled inside degrade.compiling(), as the ed25519 routes
    do it (ops/ed25519.launch_lane)."""
    from tendermint_tpu.libs import fail, trace

    # chaos seam: same role as ops/ed25519.verify_batch's — the degrade
    # runtime treats an injected fault here as a device-lane failure
    fail.inject("ops.sr25519.verify_batch")
    n = len(pubs)
    if n == 0:
        return np.zeros(0, dtype=bool)
    ok_len = np.array([
        len(pubs[i]) == 32 and len(sigs[i]) == 64 for i in range(n)])
    if not ok_len.all():
        good = np.flatnonzero(ok_len)
        if good.size == 0:
            return ok_len
        out = np.zeros(n, dtype=bool)
        out[good] = verify_batch_device([pubs[i] for i in good],
                                        [msgs[i] for i in good],
                                        [sigs[i] for i in good])
        return out
    with trace.span("ops.sr25519.verify_batch", n=n):
        bracket = ed.lane_bracket()
        with trace.span("sr25519.stage", n=n):
            operands, host_ok, nb = _stage(pubs, msgs, sigs)
        out = ed.launch_lane(LANE_PATH, n, nb, bracket, operands,
                             _verify_core)
    return out[:n] & host_ok


def warm_bucket(n: int) -> int:
    """Launch the bucket an n-row batch pads to, on all-zero operands,
    by a direct call: its one-time trace + compile is paid here, off
    any request, and the (LANE_PATH, nb) bucket is marked seen
    (ops/secp.warm_bucket is the secp256k1 lane's).  Returns nb."""
    nb = ed.bucket_size(n)
    ed.launch_lane(LANE_PATH, 0, nb, ed.lane_bracket(),
                   (np.zeros((nb, 32), np.uint8),
                    np.zeros((nb, 32), np.uint8),
                    np.zeros((nb, 64), np.int8),
                    np.zeros((nb, 64), np.int8)), _verify_core)
    return nb
