"""Fused secp256k1 BIP-340 batch-verify Pallas TPU kernel.

The whole verification of a signature lane runs inside one pallas_call
tiled over the batch (lane) axis, as ops/pallas_ed25519.py does for
ed25519: lift_x (square root, even-y choice), the 16-entry table of
multiples of -P, the 64-step joint Straus ladder R' = [s]G + [e](-P)
against the fixed-base G table, the final inversion and the BIP-340
checks (R' finite, x(R') == r, y(R') even).  Every intermediate lives in
VMEM and vregs; HBM holds only the staged operands of ops/secp._stage
and a 4-byte verdict a lane.

Curve formulas: Renes-Costello-Batina 2016 (eprint 2015/1060), the
complete projective formulas for a = 0 (algorithm 7, addition, 12M;
algorithm 9, doubling, 6M + 2S), b3 = 3 * 7 = 21.  secp256k1 has prime
order, so they are correct for EVERY pair of inputs, the point at
infinity (0 : 1 : 0) and P = +-Q included: no per-lane select, nothing
an attacker who picks (s, P) can steer into a formula breakdown.

Field: GF(2^256 - 2^32 - 977) as (NLIMB=22, T) int32, radix 2^12, limbs
on sublanes and lanes on lanes, with ops/field_secp.py's reduction
constants (2^264 = 2^40 + 250112 and 2^256 = 2^32 + 977 mod p; fold
multipliers <= 977).  Bounds, as multiples of the loose vector L that
_carry leaves (|limb| <= 4,095 / 5,566 / 6,403 / 4,482 / 4,097 ... /
17, top limb last): _mul takes operands whose multiples multiply to at
most 4 (2L x 2L, 4L x L) and returns L; _carry_lazy takes up to 128L
and returns L; points pass between _dbl and _add at 2L.  Machine-checked
by interval propagation in tests/test_pallas_secp.py, and every op is
checked against ops/field_secp.py and Python bignums there.
"""
from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tendermint_tpu.crypto import secp256k1 as host

from . import field_secp as FS

DEFAULT_TILE = 128  # lanes a grid step, the fastest-compiling of a v5e sweep

RADIX = FS.RADIX
NLIMB = FS.NLIMB
MASK = FS.MASK
TOP = 256 - RADIX * (NLIMB - 1)  # 4: bits of limb 21 below 2^256
B3 = 21  # 3 * b for y^2 = x^3 + 7

_i32 = jnp.int32


def _rows(t):
    return jax.lax.broadcasted_iota(_i32, (NLIMB, t), 0)


# ---------------------------------------------------------------------------
# field ops on (NLIMB, T) int32 values
# ---------------------------------------------------------------------------

def _shift_down(x, i):
    """Rows move down by i (toward higher weight), zero-filled on top;
    the i top rows fall off."""
    if i == 0:
        return x
    return jnp.concatenate([jnp.zeros((i, x.shape[1]), _i32), x[:NLIMB - i]],
                           axis=0)


def _shift_up(x, i):
    """Rows 0..i-1 take x's top i rows (what _shift_down(x, i) drops);
    zero-filled below."""
    return jnp.concatenate([x[NLIMB - i:],
                            jnp.zeros((NLIMB - i, x.shape[1]), _i32)], axis=0)


def _carry_pass(v):
    """One carry-save pass (field_secp._carry_pass): carries move up a
    limb; limb 21 splits at 2^256 and its carry co folds back as
    977 * co at limb 0 and 256 * co at limb 2, co split into signed
    12-bit digits so the products stay small."""
    rows = _rows(v.shape[1])
    r = jnp.where(rows == NLIMB - 1, v & ((1 << TOP) - 1), v & MASK)
    r = r + _shift_down(v >> RADIX, 1)
    co = v[NLIMB - 1:] >> TOP
    co_hi = (co + (1 << (RADIX - 1))) >> RADIX
    co_lo = co - (co_hi << RADIX)
    fold = jnp.concatenate(
        [977 * co_lo, 977 * co_hi, 256 * co_lo, 256 * co_hi,
         jnp.zeros((NLIMB - 4, v.shape[1]), _i32)], axis=0)
    return r + fold


def _tail_pass(v):
    """Split limb 0 (it takes the 977 fold) and carry into limb 1."""
    c0 = v[0:1] >> RADIX
    return jnp.concatenate([v[0:1] & MASK, v[1:2] + c0, v[2:]], axis=0)


def _carry(v):  # any int32 limbs -> L (3 passes + tail)
    return _tail_pass(_carry_pass(_carry_pass(_carry_pass(v))))


def _carry_lazy(v):  # |limbs| <= 128L -> L (2 passes + tail)
    return _tail_pass(_carry_pass(_carry_pass(v)))


def _reduce_wide(lo, hi):
    """Conv columns 0..21 (lo) and 22..43 (hi; row 21 is zero) -> L.
    hi is first carried once into 12-bit-ish columns h, then column t of
    weight 2^(264 + 12t) folds as 256 at t, 61 at t+1 and 16 at t+3
    (2^264 = 2^40 + 61 * 2^12 + 256 mod p); the few products that land
    past limb 21 fold once more the same way (they are small: the top
    limbs of L are)."""
    h_hi = (hi + (1 << (RADIX - 1))) >> RADIX
    h = hi - (h_hi << RADIX) + _shift_down(h_hi, 1)
    lo = lo + 256 * h + _shift_down(61 * h, 1) + _shift_down(16 * h, 3)
    spill = _shift_up(61 * h, 1) + _shift_up(16 * h, 3)
    lo = lo + 256 * spill + _shift_down(61 * spill, 1) \
        + _shift_down(16 * spill, 3)
    return _carry(lo)


def _mul(a, b):
    """Field multiply, schoolbook into (lo, hi) column halves, result L.
    Contract: the operands' multiples of L multiply to at most 4."""
    lo = b * a[0:1]
    hi = None
    for i in range(1, NLIMB):
        p = b * a[i:i + 1]
        lo = lo + _shift_down(p, i)
        up = _shift_up(p, i)
        hi = up if hi is None else hi + up
    return _reduce_wide(lo, hi)


def _sqr(a):
    return _mul(a, a)


def _chain(x):
    """Exact serial carry over the 22 rows: (12-bit limbs, carry out)."""
    outs = []
    cy = jnp.zeros((1, x.shape[1]), _i32)
    for i in range(NLIMB):
        t = x[i:i + 1] + cy
        outs.append(t & MASK)
        cy = t >> RADIX
    return jnp.concatenate(outs, axis=0), cy


def _freeze(a, two_p):
    """Canonical representative in [0, p) of a value of at most 128L
    (field_secp.freeze: + 2p makes it positive, then two quotient-
    estimate passes q = floor((a + 2^32 + 977) / 2^256))."""
    rows = _rows(a.shape[1])
    v = _carry_lazy(a) + two_p

    def fpass(x):
        t, co = _chain(x + jnp.where(rows == 0, 977, 0)
                       + jnp.where(rows == 2, 256, 0))
        q = (t[NLIMB - 1:] >> TOP) + (co << (RADIX - TOP))
        x = x + jnp.where(rows == 0, 977 * q, 0) \
            + jnp.where(rows == 2, 256 * q, 0) \
            - jnp.where(rows == NLIMB - 1, q << TOP, 0)
        return _chain(x)[0]

    return fpass(fpass(v))


def _is_zero(a, two_p):
    """(1, T) bool: a == 0 mod p."""
    return jnp.all(_freeze(a, two_p) == 0, axis=0, keepdims=True)


def _is_odd(a, two_p):
    return (_freeze(a, two_p)[0:1] & 1) == 1


def _pow2k(x, k):
    return jax.lax.fori_loop(0, k, lambda _, v: _sqr(v), x)


def _chain_223(a):
    """libsecp256k1's addition chain: a^(2^k - 1) for the block lengths
    of (p + 1) / 4 and p - 2.  Returns (x2, x22, x223)."""
    x2 = _mul(_sqr(a), a)
    x3 = _mul(_sqr(x2), a)
    x6 = _mul(_pow2k(x3, 3), x3)
    x9 = _mul(_pow2k(x6, 3), x3)
    x11 = _mul(_pow2k(x9, 2), x2)
    x22 = _mul(_pow2k(x11, 11), x11)
    x44 = _mul(_pow2k(x22, 22), x22)
    x88 = _mul(_pow2k(x44, 44), x44)
    x176 = _mul(_pow2k(x88, 88), x88)
    x220 = _mul(_pow2k(x176, 44), x44)
    x223 = _mul(_pow2k(x220, 3), x3)
    return x2, x22, x223


def _sqrt(a):
    """a^((p + 1) / 4): a square root when a is a square (p = 3 mod 4);
    the caller checks the square."""
    x2, x22, x223 = _chain_223(a)
    t = _mul(_pow2k(x223, 23), x22)
    return _pow2k(_mul(_pow2k(t, 6), x2), 2)


def _invert(a):
    """a^(p - 2); 0 maps to 0."""
    x2, x22, x223 = _chain_223(a)
    t = _mul(_pow2k(x223, 23), x22)
    t = _mul(_pow2k(t, 5), a)
    t = _mul(_pow2k(t, 3), x2)
    return _mul(_pow2k(t, 2), a)


# ---------------------------------------------------------------------------
# complete projective formulas (Renes-Costello-Batina, a = 0); every
# coordinate in and out is at most 2L
# ---------------------------------------------------------------------------

def _dbl(p):
    """Algorithm 9: 6M + 2S."""
    x, y, z = p
    t0 = _sqr(y)
    t2 = _carry_lazy(B3 * _sqr(z))
    z8 = _carry_lazy(8 * t0)
    x3 = _mul(t2, z8)
    z3 = _mul(_mul(y, z), z8)
    u = _carry_lazy(t0 - 3 * t2)
    y3 = _mul(u, t0 + t2) + x3
    x3 = _mul(u, _mul(x, y))
    return x3 + x3, y3, z3


def _add(p, q):
    """Algorithm 7: 12M.  Sums of 2L coordinates are carried before they
    meet another sum, as the _mul contract needs."""
    x1, y1, z1 = p
    x2, y2, z2 = q
    t0 = _mul(x1, x2)
    t1 = _mul(y1, y2)
    t2 = _mul(z1, z2)
    t3 = _carry_lazy(_mul(_carry_lazy(x1 + y1), x2 + y2) - t0 - t1)
    t4 = _carry_lazy(_mul(_carry_lazy(y1 + z1), y2 + z2) - t1 - t2)
    y3 = _mul(_carry_lazy(x1 + z1), x2 + z2) - t0 - t2
    t0 = 3 * t0
    t2 = _carry_lazy(B3 * t2)
    z3 = t1 + t2
    t1 = t1 - t2
    y3 = _carry_lazy(B3 * y3)
    return (_mul(t3, t1) - _mul(t4, y3),
            _mul(y3, t0) + _mul(t1, z3),
            _mul(z3, t4) + _mul(t0, t3))


def _gather16(digit, rows):
    """Per-lane pick of rows[digit], digit (1, T) in 0..15."""
    acc = rows[0]
    for j in range(1, 16):
        acc = jnp.where(digit == j, rows[j], acc)
    return acc


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

# columns of the packed (NLIMB, 128) constant input: the affine G table
# x and y of j * G (j = 0 is the point at infinity, (0 : 1 : 0)), 2p,
# one and zero.  Limb constants enter the kernel through a ref load:
# compile-time limb vectors fed into the convolution crash Mosaic's
# constant folder (as in ops/pallas_ed25519).
_COL_GX, _COL_GY, _COL_TWO_P, _COL_ONE, _COL_ZERO = 0, 16, 32, 33, 34


def _make_consts() -> np.ndarray:
    cols = np.zeros((NLIMB, 128), dtype=np.int32)
    for j in range(16):
        x, y = host._affine(host._jmul(j, host._G)) if j else (0, 1)
        cols[:, _COL_GX + j] = FS.int_to_limbs(x)
        cols[:, _COL_GY + j] = FS.int_to_limbs(y)
    cols[:, _COL_TWO_P] = np.asarray(FS._TWO_P)
    cols[0, _COL_ONE] = 1
    return cols


_CONSTS = _make_consts()


def _kernel(const_ref, px_ref, rx_ref, s_ref, e_ref, out_ref,
            one_scr, zero_scr, dig_scr, tab_scr):
    """One tile of T lanes.  px_ref, rx_ref: (NLIMB, T) canonical limbs;
    s_ref, e_ref: (64, T) unsigned radix-16 digits, most significant
    first; out_ref: (8, T) int32 verdicts (row 0 read).  Scratch:
    one/zero (NLIMB, T) (a store/load round trip gives the uniform
    constants the tiled layout the convolution's row slices need),
    dig_scr (128, 1, T) the digits a row each (the ladder indexes the
    leading axis dynamically), tab_scr (48, NLIMB, T) the multiples
    j * (-P), coordinates 3j .. 3j + 2."""
    consts = const_ref[:]
    t = px_ref.shape[1]
    two_p = consts[:, _COL_TWO_P:_COL_TWO_P + 1]
    one_scr[:] = jnp.broadcast_to(consts[:, _COL_ONE:_COL_ONE + 1],
                                  (NLIMB, t))
    zero_scr[:] = jnp.broadcast_to(consts[:, _COL_ZERO:_COL_ZERO + 1],
                                   (NLIMB, t))
    one = one_scr[:]
    zero = zero_scr[:]
    s_dig = s_ref[:]
    e_dig = e_ref[:]
    for j in range(64):
        dig_scr[j] = s_dig[j:j + 1]
        dig_scr[64 + j] = e_dig[j:j + 1]

    # lift_x: the even-y point with x = px, negated for [e](-P)
    px = px_ref[:]
    x3p7 = _carry_lazy(_mul(_sqr(px), px) + 7 * one)
    y = _sqrt(x3p7)
    decode_ok = _is_zero(_sqr(y) - x3p7, two_p)
    neg_p = (px, jnp.where(_is_odd(y, two_p), y, -y), one)

    # j * (-P), j = 0..15: infinity, -P, then complete additions
    for c in range(3):
        tab_scr[c] = (zero, one, zero)[c]
        tab_scr[3 + c] = neg_p[c]

    def build(j, acc):
        acc = _add(acc, neg_p)
        for c in range(3):
            tab_scr[3 * j + c] = acc[c]
        return acc

    jax.lax.fori_loop(2, 16, build, neg_p)

    g_rows = [[jnp.broadcast_to(consts[:, col + j:col + j + 1], (NLIMB, t))
               for j in range(16)] for col in (_COL_GX, _COL_GY)]

    def step(i, acc):
        for _ in range(4):
            acc = _dbl(acc)
        ds = dig_scr[i]
        g = (_gather16(ds, g_rows[0]), _gather16(ds, g_rows[1]),
             jnp.where(ds == 0, zero, one))
        acc = _add(acc, g)
        de = dig_scr[64 + i]
        q = tuple(_gather16(de, [tab_scr[3 * j + c] for j in range(16)])
                  for c in range(3))
        return _add(acc, q)

    x, y, z = jax.lax.fori_loop(0, 64, step, (zero, one, zero))

    zi = _invert(z)
    ok = decode_ok & ~_is_zero(z, two_p) \
        & _is_zero(_mul(x, zi) - rx_ref[:], two_p) \
        & ~_is_odd(_mul(y, zi), two_p)
    out_ref[:] = jnp.broadcast_to(ok.astype(_i32), out_ref.shape)


@partial(jax.jit, static_argnames=("tile", "interpret"))
def verify(px, rx, s_digits, e_digits, tile: int = DEFAULT_TILE,
           interpret: bool = False):
    """BIP-340 verdicts of nb lanes from ops/secp._stage's operands:
    px, rx (NLIMB, nb) int32 limbs, s, e (64, nb) int32 digits.  nb must
    be a multiple of tile.  Returns (nb,) bool."""
    nb = px.shape[1]
    assert nb % tile == 0, (nb, tile)

    def lanes(rows):
        return pl.BlockSpec((rows, tile), lambda i: (0, i),
                            memory_space=pltpu.VMEM)

    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((8, nb), _i32),
        grid=(nb // tile,),
        in_specs=[pl.BlockSpec((NLIMB, 128), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
                  lanes(NLIMB), lanes(NLIMB), lanes(64), lanes(64)],
        out_specs=lanes(8),
        scratch_shapes=[pltpu.VMEM((NLIMB, tile), _i32),
                        pltpu.VMEM((NLIMB, tile), _i32),
                        pltpu.VMEM((128, 1, tile), _i32),
                        pltpu.VMEM((48, NLIMB, tile), _i32)],
        interpret=interpret,
    )(jnp.asarray(_CONSTS), px, rx, s_digits, e_digits)
    return out[0].astype(jnp.bool_)
