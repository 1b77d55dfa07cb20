"""Batched secp256k1 BIP-340 Schnorr verification on TPU lanes.

The reference verifies secp256k1 one signature at a time through btcec
(reference crypto/secp256k1/secp256k1.go:197-212, x-only Schnorr); the
repo's host C lane (native/ecverify.c tm_secp_verify*) batches on one CPU
core.  This lane moves the curve work onto the TPU, one signature per
vector lane: the host stages the BIP-340 range screens, the tagged-hash
challenge and the limb / digit packing (_stage), and one Pallas kernel
(ops/pallas_secp.py) runs the whole verification, the 64-step Straus
ladder R' = [s]G + [e](-P) over complete projective formulas included.

Design notes (vs the ed25519 lane):
  * Short-Weierstrass curve y^2 = x^3 + 7 in projective coordinates with
    the Renes-Costello-Batina complete formulas: an attacker fully
    controls (s, P), and a formula breakdown would be attacker-steerable
    garbage that the final x-compare could be made to accept.
  * UNSIGNED radix-16 digits (64 per 256-bit scalar) with 16-entry
    tables: secp scalars span the full 256 bits, so the balanced-digit
    trick used for ed25519 (top nibble <= 1) does not apply.
  * Verdicts are per-signature exact (BIP-340 semantics: R' finite, even
    y, x(R') == r), matching the host C per-sig path bit-for-bit.
"""
from __future__ import annotations

import hashlib
import os

import numpy as np
import jax

from . import field_secp as FS
from . import pallas_secp as PS


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


P = FS.P
# group order
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141


@jax.jit
def _verify_core(px_limbs, rx_limbs, s_digits, e_digits):
    """px/rx: (NLIMB, B) canonical field limbs; s/e digits: (64, B) int32
    unsigned radix-16, most-significant first.  Returns (B,) bool.  The
    tile adapts to the bucket (B is a power of two of at least 64); off
    a TPU the kernel runs through the Pallas interpreter."""
    nb = px_limbs.shape[1]
    return PS.verify(px_limbs, rx_limbs, s_digits, e_digits,
                     tile=min(PS.DEFAULT_TILE, nb),
                     interpret=jax.default_backend() != "tpu")


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


# the lane's name in launch records and routes.  It names the lane, not
# its implementation: the XLA ladder it was named for is gone, and the
# benchmark's lane reader and launch checks, the smoke's gate and the
# records already written key on this string.
LANE_PATH = "secp-xla"


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
    # proves the degrade plumbing without compiling the kernel
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
