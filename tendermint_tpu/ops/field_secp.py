"""GF(2^256 - 2^32 - 977) — the secp256k1 base field — on TPU-friendly
int32 limb vectors, mirroring the design of ops/field.py (radix 2^12,
NLIMB = 22, limb axis 0, batch axes trailing; see that module's docstring
for the layout rationale).

The reference verifies secp256k1 serially via btcec on the host
(reference crypto/secp256k1/secp256k1.go:197).  The lane's ladder runs
in the Pallas kernel ops/pallas_secp.py, which takes its limb layout,
reduction constants and conversions from here; the jnp ops below are
the plain reference its field arithmetic is tested against.

Reduction structure: 22 limbs * 12 bits = 264 bits and
    2^264 = 2^8 * 2^256 ≡ 2^8 * (2^32 + 977) = 2^40 + 250112 (mod p)
so a coefficient of weight 2^264 folds back with THREE small per-limb
multipliers: 256 at limb 0, 61 at limb 1 (250112 = 61*2^12 + 256) and 16
at limb 3 (2^40 = 16 * 2^36).  Similarly the in-carry fold at the 2^256
boundary (bit 4 of limb 21) adds co*977 at limb 0 and co*256 at limb 2
(2^32 = 256 * 2^24).  All fold multipliers are <= 256 — far below
ops/field.py's FOLD = 9728 — so every int32 bound of the parent design
holds with extra headroom; the bounds are regression-checked against a
bignum oracle in tests/test_secp_lane.py rather than re-proved.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

RADIX = 12
NLIMB = 22
MASK = (1 << RADIX) - 1
TOTAL_BITS = RADIX * NLIMB  # 264

P = (1 << 256) - (1 << 32) - 977

_i32 = jnp.int32

_TOP_BITS = 256 - RADIX * (NLIMB - 1)  # 4: bits of limb 21 below 2^256


# ---------------------------------------------------------------------------
# host <-> limb conversion
# ---------------------------------------------------------------------------

def int_to_limbs(x: int) -> np.ndarray:
    x %= P
    out = np.zeros(NLIMB, dtype=np.int32)
    for i in range(NLIMB):
        out[i] = (x >> (RADIX * i)) & MASK
    return out


def limbs_to_int(limbs) -> int:
    v = 0
    for i, limb in enumerate(np.asarray(limbs).tolist()):
        v += int(limb) << (RADIX * i)
    return v


# ---------------------------------------------------------------------------
# carries
# ---------------------------------------------------------------------------

def _carry_pass(v):
    """One vectorized carry-save pass with the 2^256 fold: carries shift
    up one limb; the top limb splits at its 2^256 boundary and that carry
    co folds back as co*977 at limb 0 and co*256 at limb 2 (round-to-
    nearest signed digit split keeps products < 2^31)."""
    c = v >> RADIX
    r = v & MASK
    co = v[-1] >> _TOP_BITS
    r = r.at[-1].set(v[-1] & ((1 << _TOP_BITS) - 1))
    r = r + jnp.concatenate([jnp.zeros_like(c[:1]), c[:-1]], axis=0)
    co_hi = (co + (1 << (RADIX - 1))) >> RADIX
    co_lo = co - (co_hi << RADIX)
    r = r.at[0].add(977 * co_lo)
    r = r.at[1].add(977 * co_hi)
    r = r.at[2].add(256 * co_lo)
    r = r.at[3].add(256 * co_hi)
    return r


def carry(c):
    """Signed int32 limbs -> loose-carried form (same contract shape as
    ops/field.py: |limb| small enough for one lazy add per operand).
    Three passes + tail: the 977-fold injects larger terms than the
    parent's 19-fold, so one extra pass buys the same convergence with
    margin (oracle-checked, not interval-proved)."""
    return _tail_pass(_carry_pass(_carry_pass(_carry_pass(c))))


def _tail_pass(v):
    c0 = v[0] >> RADIX
    v = v.at[0].set(v[0] & MASK)
    return v.at[1].add(c0)


# ---------------------------------------------------------------------------
# ring ops
# ---------------------------------------------------------------------------

def _bcast(x, batch):
    want = (NLIMB,) + batch
    return x if x.shape == want else jnp.broadcast_to(x, want)


def add(a, b):
    return a + b  # lazy


def sub(a, b):
    return a - b  # lazy


def mul(a, b):
    """Field multiply; result loose-carried.  Same operand budget as
    ops/field.py mul (the fold terms here are strictly smaller)."""
    B = jnp.broadcast_shapes(a.shape[1:], b.shape[1:])
    a = _bcast(a, B)
    b = _bcast(b, B)
    pad_spec = lambda i: [(i, NLIMB - 1 - i)] + [(0, 0)] * len(B)
    c = jnp.pad(a[0] * b, pad_spec(0))
    for i in range(1, NLIMB):
        c = c + jnp.pad(a[i] * b, pad_spec(i))
    return _reduce_wide(c)


def _reduce_wide(c):
    """(2N-1, ...) conv columns -> loose limbs.  Fold-first: hi column h
    at offset t (weight 2^264 * 2^(12t)) adds 256*h at t, 61*h at t+1,
    16*h at t+3 after a signed 12-bit digit split of h.  Offsets that
    land at or beyond limb 22 (only the topmost few h2/h1 digits) wrap
    with the same rule once more — those coefficients are tiny (< 2^17)
    so the second fold cannot overflow."""
    B = c.shape[1:]
    lo = c[:NLIMB]
    hi = c[NLIMB:]  # 21 coefficients, t = 0..20
    zpad = [(0, 0)] * len(B)
    h_hi = (hi + (1 << (RADIX - 1))) >> RADIX
    h0 = hi - (h_hi << RADIX)
    h2 = (h_hi + (1 << (RADIX - 1))) >> RADIX
    h1 = h_hi - (h2 << RADIX)

    ext = jnp.zeros((NLIMB + 6,) + B, dtype=_i32)
    for mult, off in ((256, 0), (61, 1), (16, 3)):
        for dig, sh in ((h0, 0), (h1, 1), (h2, 2)):
            ext = ext.at[off + sh:off + sh + 21].add(mult * dig)
    lo = lo + ext[:NLIMB]
    # wrap the (tiny) columns 22..27 once more
    over = ext[NLIMB:]
    for mult, off in ((256, 0), (61, 1), (16, 3)):
        lo = lo.at[off:off + 6].add(mult * over)
    return carry(lo)


def sqr(a):
    B = a.shape[1:]
    a2 = a + a
    pad_spec = lambda i: [(2 * i, NLIMB - 1 - i)] + [(0, 0)] * len(B)
    c = jnp.pad(a[0] * jnp.concatenate([a[0:1], a2[1:]], axis=0),
                pad_spec(0))
    for i in range(1, NLIMB):
        v = jnp.concatenate([a[i:i + 1], a2[i + 1:]], axis=0)
        c = c + jnp.pad(a[i] * v, pad_spec(i))
    return _reduce_wide(c)


def mul_small(a, k: int):
    return carry(a * jnp.int32(k))


# ---------------------------------------------------------------------------
# canonicalization / predicates
# ---------------------------------------------------------------------------

def _carry_chain(c, out_len):
    outs = []
    cy = jnp.zeros_like(c[0])
    for i in range(c.shape[0]):
        v = c[i] + cy
        outs.append(v & MASK)
        cy = v >> RADIX
    while len(outs) < out_len:
        outs.append(cy & MASK)
        cy = cy >> RADIX
    return jnp.stack(outs, axis=0), cy


_TWO_P = jnp.asarray(
    np.array([(2 * P >> (RADIX * i)) & MASK for i in range(NLIMB)],
             dtype=np.int32))


def _freeze_pass(a):
    """One quotient-estimate pass: q = floor((a + (2^32+977)) / 2^256) —
    the offset makes values in [p, 2^256) round up to q = 1, the parent
    module's +19 trick — then a - q*p = a - q*2^256 + q*(2^32 + 977)."""
    t, co = _carry_chain(a.at[0].add(977).at[2].add(256), NLIMB)
    q = (t[NLIMB - 1] >> _TOP_BITS) + (co << (RADIX - _TOP_BITS))
    a = a.at[0].add(977 * q)
    a = a.at[2].add(256 * q)
    a = a.at[NLIMB - 1].add(-(q << _TOP_BITS))
    out, _ = _carry_chain(a, NLIMB)
    return out


def freeze(a):
    """Any-bounds limbs -> canonical representative in [0, p)."""
    v = carry(a)
    v = v + _TWO_P.reshape((NLIMB,) + (1,) * (v.ndim - 1))
    return _freeze_pass(_freeze_pass(v))


def eq(a, b):
    B = jnp.broadcast_shapes(a.shape[1:], b.shape[1:])
    return jnp.all(_bcast(freeze(a), B) == _bcast(freeze(b), B), axis=0)


def is_zero(a):
    return jnp.all(freeze(a) == 0, axis=0)


def is_odd(a):
    return (freeze(a)[0] & 1).astype(jnp.bool_)


# ---------------------------------------------------------------------------
# exponentiation chains
# ---------------------------------------------------------------------------

def _pow_fixed(a, e: int):
    """MSB-first square-and-multiply by a fixed public exponent, rolled
    into ONE lax.scan over the exponent's bit vector (the r5 seed
    unrolled ~256 sqr + ~230 mul into straight-line HLO — that alone was
    a ~100k-op graph per call site and XLA-on-CPU never finished
    compiling the verify kernel; cf. ops/field.py _pow2k, which keeps
    the parent module's chains small the same way).  The multiply is
    computed unconditionally and selected per bit — both branches are
    loose-carried, so the jnp.where is bound-safe — trading ~popcount
    savings for a compile-sized graph.  Used once per decompress (sqrt)
    and once per batch affine-ize (invert), amortized across lanes."""
    import jax

    bits = jnp.asarray([int(b) for b in bin(e)[2:][1:]], dtype=jnp.int32)

    def step(acc, bit):
        acc = sqr(acc)
        acc = jnp.where(bit == 1, mul(acc, a), acc)
        return acc, None

    acc, _ = jax.lax.scan(step, a, bits)
    return acc


def invert(a):
    return _pow_fixed(a, P - 2)


def sqrt(a):
    """p ≡ 3 (mod 4): sqrt(a) = a^((p+1)/4) when a is a QR.  The caller
    checks sqr(result) == a (non-residues yield garbage)."""
    return _pow_fixed(a, (P + 1) // 4)
