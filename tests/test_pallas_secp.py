"""ops/pallas_secp.py, the secp256k1 Pallas kernel, piece by piece.

Off a TPU the kernel's value-level field and curve ops run inside small
pallas_calls through the interpreter (the same jaxpr Mosaic lowers on
the chip):

- the int32 bounds its docstring states, machine-checked by interval
  propagation over the carry, reduction and multiply structure and over
  every call site of the two curve formulas;
- mul, sqr, carry, carry_lazy, freeze, invert and sqrt against
  ops/field_secp.py and Python bignums, the operands at the contract's
  extreme limbs included;
- the complete addition and the doubling on the degenerate inputs
  (P + P, P + (-P), infinity on either side or both) against the host's
  bignum curve arithmetic.

The whole kernel's bitmap against the host oracles is the slow tier's
tests/test_secp_lane.py::test_secp_device_lane_bitmap_vs_host_oracles.
"""
from __future__ import annotations

import random

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from tendermint_tpu.crypto import secp256k1 as host
from tendermint_tpu.ops import field_secp as FS
from tendermint_tpu.ops import pallas_secp as PS

P = FS.P
NLIMB, RADIX, MASK, TOP = PS.NLIMB, PS.RADIX, PS.MASK, PS.TOP
INT32 = 2.0 ** 31
rng = random.Random(20261017)

EDGE = [0, 1, 2, 976, 977, 978, (1 << 32) - 1, 1 << 32, (1 << 32) + 977,
        (1 << 40) - 1, 1 << 40, P - 1, P - 2, (1 << 255),
        int("aa" * 32, 16) % P, int("55" * 32, 16)]


# ---------------------------------------------------------------------------
# interval propagation: max |limb| vectors through the kernel's structure
# ---------------------------------------------------------------------------

def _pass_bound(b):
    b = np.asarray(b, dtype=np.float64)
    c = (b + MASK) // (1 << RADIX)            # |v >> 12|
    r = np.minimum(b, MASK)
    r[-1] = min(b[-1], (1 << TOP) - 1)
    r[1:] += c[:-1]
    co = (b[-1] + (1 << TOP) - 1) // (1 << TOP)
    co_hi = (co + (1 << (RADIX - 1))) // (1 << RADIX) + 1
    co_lo = min(co, 1 << (RADIX - 1))
    r[0] += 977 * co_lo
    r[1] += 977 * co_hi
    r[2] += 256 * co_lo
    r[3] += 256 * co_hi
    assert r.max() < INT32
    return r


def _tail_bound(b):
    b = b.copy()
    c0 = (b[0] + MASK) // (1 << RADIX)
    b[0] = min(b[0], MASK)
    b[1] += c0
    return b


def _carry_bound(b):
    return _tail_bound(_pass_bound(_pass_bound(_pass_bound(b))))


def _carry_lazy_bound(b):
    return _tail_bound(_pass_bound(_pass_bound(b)))


LOOSE = _carry_bound(np.full(NLIMB, INT32))


def _down(b, i):
    out = np.zeros(NLIMB)
    out[i:] = b[:NLIMB - i]
    return out


def _up(b, i):
    out = np.zeros(NLIMB)
    out[:i] = b[NLIMB - i:]
    return out


def _mul_bound(ka, kb):
    """Bound of _mul's output for operands ka * LOOSE and kb * LOOSE,
    asserting every intermediate fits int32."""
    a, b = ka * LOOSE, kb * LOOSE
    conv = np.zeros(2 * NLIMB)
    for i in range(NLIMB):
        conv[i:i + NLIMB] += a[i] * b
    lo, hi = conv[:NLIMB], conv[NLIMB:]
    assert hi[-1] == 0 and conv.max() < INT32
    h_hi = (hi + (1 << (RADIX - 1))) // (1 << RADIX) + 1
    h = np.minimum(hi, 1 << (RADIX - 1)) + _down(h_hi, 1)
    lo = lo + 256 * h + _down(61 * h, 1) + _down(16 * h, 3)
    spill = _up(61 * h, 1) + _up(16 * h, 3)
    lo = lo + 256 * spill + _down(61 * spill, 1) + _down(16 * spill, 3)
    assert lo.max() < INT32, lo.max() / INT32
    return _carry_bound(lo)


def test_bounds_proof():
    """The loose vector L that _carry leaves from any int32 input, the
    _mul contract (multiples of L multiplying to at most 4, output L),
    _carry_lazy's (128L -> L), freeze's positivity after + 2p, and the
    call sites of _dbl / _add (points in and out at 2L) and of the
    kernel."""
    assert LOOSE[-1] <= 17 and LOOSE.max() < 6500, LOOSE
    assert (_carry_lazy_bound(128 * LOOSE) <= LOOSE).all()
    for ka, kb in ((1, 1), (2, 2), (4, 1), (1, 4), (3, 1), (2, 1)):
        assert (_mul_bound(ka, kb) <= LOOSE).all(), (ka, kb)
    # 2p makes every value of at most L positive ahead of freeze's chain
    assert sum(int(b) << (RADIX * i) for i, b in enumerate(LOOSE)) < 2 * P

    def mul(ka, kb):
        assert ka * kb <= 4, (ka, kb)
        return 1

    def lazy(k):
        assert k <= 128, k
        return 1

    def dbl(x, y, z):                     # mirrors PS._dbl
        t0 = mul(y, y)
        t2 = lazy(PS.B3 * mul(z, z))
        z8 = lazy(8 * t0)
        x3 = mul(t2, z8)
        z3 = mul(mul(y, z), z8)
        u = lazy(t0 + 3 * t2)
        y3 = mul(u, t0 + t2) + x3
        x3 = mul(u, mul(x, y))
        return x3 + x3, y3, z3

    def add(p, q):                        # mirrors PS._add
        (x1, y1, z1), (x2, y2, z2) = p, q
        t0, t1, t2 = mul(x1, x2), mul(y1, y2), mul(z1, z2)
        t3 = lazy(mul(lazy(x1 + y1), x2 + y2) + t0 + t1)
        t4 = lazy(mul(lazy(y1 + z1), y2 + z2) + t1 + t2)
        y3 = mul(lazy(x1 + z1), x2 + z2) + t0 + t2
        t0 = 3 * t0
        t2 = lazy(PS.B3 * t2)
        z3, t1 = t1 + t2, t1 + t2
        y3 = lazy(PS.B3 * y3)
        return (mul(t3, t1) + mul(t4, y3), mul(y3, t0) + mul(t1, z3),
                mul(z3, t4) + mul(t0, t3))

    two_l = (2, 2, 2)
    assert max(dbl(*two_l)) <= 2 and max(add(two_l, two_l)) <= 2
    # the kernel: lift_x, the ladder's start (0 : 1 : 0) and its G rows
    # are canonical (<= L), the freeze inputs at most 2L
    assert lazy(mul(mul(1, 1), 1) + 7) == 1
    assert max(add(dbl(1, 1, 1), (1, 1, 1))) <= 2


# ---------------------------------------------------------------------------
# the interpreter harness
# ---------------------------------------------------------------------------

def _col(xs):
    return jnp.asarray(np.stack([FS.int_to_limbs(x) for x in xs], axis=1))


def _vals(limbs):
    arr = np.asarray(limbs)
    return [FS.limbs_to_int(arr[:, j]) % P for j in range(arr.shape[1])]


def _interpret(fn, *ins, n_out=1, two_p=False):
    """fn over (NLIMB, T) int32 inputs inside one interpreted pallas_call
    (and 2p as a (NLIMB, 1) column after them where two_p is set); fn
    returns n_out values, each (NLIMB, T) or (1, T), returned as
    (NLIMB, T) int32 arrays."""
    t = ins[0].shape[1]
    if two_p:
        ins = ins + (jnp.asarray(
            PS._CONSTS[:, PS._COL_TWO_P:PS._COL_TWO_P + 1]),)

    def kernel(*refs):
        outs = fn(*[r[:] for r in refs[:len(ins)]])
        outs = outs if isinstance(outs, tuple) else (outs,)
        for ref, v in zip(refs[len(ins):], outs):
            ref[:] = jnp.broadcast_to(v.astype(jnp.int32), (NLIMB, t))

    res = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((NLIMB, t), jnp.int32)] * n_out,
        interpret=True)(*ins)
    return res if n_out > 1 else res[0]


def _extreme(k, signs):
    """Limb vectors at k * LOOSE, limb by limb, with the given signs."""
    lim = (k * LOOSE).astype(np.int64)
    return jnp.asarray(np.stack([lim * s for s in signs], axis=1)
                       .astype(np.int32))


def _sign_patterns():
    alt = np.array([1 if i % 2 else -1 for i in range(NLIMB)])
    return [np.ones(NLIMB), -np.ones(NLIMB), alt, -alt]


def _limb_vals(arr):
    arr = np.asarray(arr)
    return [FS.limbs_to_int(arr[:, j]) for j in range(arr.shape[1])]


def _assert_loose(arr):
    assert (np.abs(np.asarray(arr)) <= LOOSE[:, None]).all()


# ---------------------------------------------------------------------------
# field ops against field_secp and bignums
# ---------------------------------------------------------------------------

def _case_mul():
    xs = EDGE + [rng.randrange(P) for _ in range(16)]
    ys = list(reversed(xs))
    got = _interpret(PS._mul, _col(xs), _col(ys))
    assert _vals(got) == [x * y % P for x, y in zip(xs, ys)]
    assert _vals(got) == _vals(FS.mul(_col(xs), _col(ys)))
    _assert_loose(got)
    # the contract's extremes: 2L x 2L and 4L x L, signed limb patterns
    for ka, kb in ((2, 2), (4, 1)):
        sg = _sign_patterns()
        a, b = _extreme(ka, sg), _extreme(kb, sg[1:] + sg[:1])
        got = _interpret(PS._mul, a, b)
        want = [x * y % P for x, y in zip(_limb_vals(a), _limb_vals(b))]
        assert _vals(got) == want, (ka, kb)
        _assert_loose(got)


def _case_sqr():
    xs = EDGE + [rng.randrange(P) for _ in range(16)]
    got = _interpret(PS._sqr, _col(xs))
    assert _vals(got) == [x * x % P for x in xs]
    _assert_loose(got)


def _case_carry():
    """_carry from any int32 limbs, _carry_lazy from 128L: the value is
    kept mod p and the limbs land inside L."""
    wide = np.array([[rng.randrange(-2 ** 31, 2 ** 31) for _ in range(12)]
                     for _ in range(NLIMB)], dtype=np.int64)
    wide[:, 0] = 2 ** 31 - 1
    wide[:, 1] = -2 ** 31
    wide = jnp.asarray(wide.astype(np.int32))
    got = _interpret(PS._carry, wide)
    assert _vals(got) == [v % P for v in _limb_vals(wide)]
    _assert_loose(got)
    lazy = _extreme(128, _sign_patterns())
    got = _interpret(PS._carry_lazy, lazy)
    assert _vals(got) == [v % P for v in _limb_vals(lazy)]
    _assert_loose(got)


def _case_freeze():
    """Canonical limbs in [0, p) from loose and 2L representations, and
    the predicates on them."""
    xs = EDGE + [P - 1, 0, 1] + [rng.randrange(P) for _ in range(8)]
    loose = _interpret(PS._mul, _col(xs), _col([1] * len(xs)))
    lazy = _extreme(2, _sign_patterns())
    for arr in (loose, lazy):
        want = [v % P for v in _limb_vals(arr)]
        froz, zero, odd = _interpret(
            lambda a, tp: (PS._freeze(a, tp), PS._is_zero(a, tp),
                           PS._is_odd(a, tp)), arr, n_out=3, two_p=True)
        froz = np.asarray(froz)
        for j, w in enumerate(want):
            assert (froz[:, j] == FS.int_to_limbs(w)).all(), w
        assert np.asarray(zero)[0].tolist() == [int(w == 0) for w in want]
        assert np.asarray(odd)[0].tolist() == [w & 1 for w in want]


def _case_invert():
    xs = [x for x in EDGE if x] + [rng.randrange(1, P) for _ in range(8)]
    got = _interpret(PS._invert, _col(xs + [0]))
    assert _vals(got) == [pow(x, P - 2, P) for x in xs] + [0]


def _case_sqrt():
    """Roots of squares (the caller's check sqr(root) == a holds) and of
    non-squares (it fails: lift_x refuses the key)."""
    roots = [1, 2, 3, P - 2] + [rng.randrange(P) for _ in range(8)]
    squares = [r * r % P for r in roots]
    non = [x for x in range(2, 40) if pow(x, (P - 1) // 2, P) == P - 1][:4]
    got = _vals(_interpret(PS._sqrt, _col(squares + non)))
    assert got == [pow(a, (P + 1) // 4, P) for a in squares + non]
    assert [g * g % P for g in got[:len(squares)]] == squares
    assert all(g * g % P != a for g, a in zip(got[len(squares):], non))


_FIELD_CASES = {"mul": _case_mul, "sqr": _case_sqr, "carry": _case_carry,
                "freeze": _case_freeze, "invert": _case_invert,
                "sqrt": _case_sqrt}


@pytest.mark.parametrize("op", list(_FIELD_CASES))
def test_field_op_matches_bignum(op):
    _FIELD_CASES[op]()


# ---------------------------------------------------------------------------
# the complete formulas against the host's bignum curve arithmetic
# ---------------------------------------------------------------------------

def _affine(k):
    """k * G as (x, y), None for infinity (host bignum Jacobian)."""
    return host._affine(host._jmul(k % host.N, host._G)) if k % host.N \
        else None


_P_LIMBS = np.array([(P >> (RADIX * i)) & MASK for i in range(NLIMB)])


def _projective(pt, lam):
    """(x, y) -> (lam x : lam y : lam), each coordinate's limbs lifted by
    p's (a 2L representation, not a canonical one); None ->
    (0 : lam : 0)."""
    coords = (0, lam, 0) if pt is None else \
        (pt[0] * lam % P, pt[1] * lam % P, lam)
    return [FS.int_to_limbs(c) + _P_LIMBS for c in coords]


def _neg(pt):
    return None if pt is None else (pt[0], (-pt[1]) % P)


# (name, P1, P2) with P1 + P2 and 2 * P1 checked
_CURVE_CASES = {
    "P+P": (_affine(7), _affine(7)),
    "P+(-P)": (_affine(11), _neg(_affine(11))),
    "inf+Q": (None, _affine(5)),
    "Q+inf": (_affine(5), None),
    "inf+inf": (None, None),
    "P+Q": (_affine(3), _affine(0x1234567)),
    "G+(-G)": (_affine(1), _affine(host.N - 1)),
    "G+G": (_affine(1), _affine(1)),
}


@pytest.fixture(scope="module")
def curve_results():
    names = list(_CURVE_CASES)
    ins = [[], [], [], [], [], []]
    for name in names:
        p1, p2 = _CURVE_CASES[name]
        for c, v in enumerate(_projective(p1, rng.randrange(2, P))
                              + _projective(p2, rng.randrange(2, P))):
            ins[c].append(v)
    arrs = [jnp.asarray(np.stack(col, axis=1).astype(np.int32))
            for col in ins]

    def fn(x1, y1, z1, x2, y2, z2):
        s = PS._add((x1, y1, z1), (x2, y2, z2))
        d = PS._dbl((x1, y1, z1))
        return s + d

    outs = [np.asarray(o) for o in _interpret(fn, *arrs, n_out=6)]
    return {name: [o[:, j] for o in outs] for j, name in enumerate(names)}


def _to_affine(x, y, z):
    x, y, z = (FS.limbs_to_int(v) % P for v in (x, y, z))
    if z == 0:
        return None
    zi = pow(z, P - 2, P)
    return x * zi % P, y * zi % P


def _host_add(a, b):
    ja = None if a is None else (a[0], a[1], 1)
    jb = None if b is None else (b[0], b[1], 1)
    return host._affine(host._jadd(ja, jb))


@pytest.mark.parametrize("name", list(_CURVE_CASES))
def test_complete_formulas_match_bignum(curve_results, name):
    p1, p2 = _CURVE_CASES[name]
    out = curve_results[name]
    for v in out:
        assert (np.abs(v) <= 2 * LOOSE).all()
    for pt in (out[:3], out[3:]):  # never (0 : 0 : 0)
        assert any(FS.limbs_to_int(v) % P for v in pt)
    assert _to_affine(*out[:3]) == _host_add(p1, p2)
    assert _to_affine(*out[3:]) == _host_add(p1, p1)
    if p1 is None:  # infinity stays (0 : Y : 0) with Y != 0
        assert FS.limbs_to_int(out[4]) % P != 0


def test_g_table_is_the_multiples_of_g():
    """Row 0 is the point at infinity (0 : 1 : 0); every other row is on
    the curve and is the row before it plus G."""
    rows = [(FS.limbs_to_int(PS._CONSTS[:, PS._COL_GX + j]),
             FS.limbs_to_int(PS._CONSTS[:, PS._COL_GY + j]))
            for j in range(16)]
    assert rows[0] == (0, 1)
    prev = None
    for x, y in rows[1:]:
        assert (y * y - x ** 3 - 7) % P == 0
        assert (x, y) == _host_add(prev, (host.GX, host.GY))
        prev = (x, y)
