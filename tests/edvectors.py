"""Adversarial ed25519 vectors shared by the route tests: the encodings
on which a cofactored or sloppy verifier and the reference's
cofactorless one (crypto/ed25519/ed25519.go:148) disagree.  Every one
is REFUSED by the reference; tests hold each route to that, lane for
lane, against crypto/_edref and OpenSSL."""
from __future__ import annotations

import hashlib

from tendermint_tpu.crypto import _edref as er


def order8_point():
    """An order-8 torsion point on edwards25519 in extended coords.

    The order-4 points are (+-i, 0) (from -x^2 = 1 with y = 0), and the
    a = -1 doubling map gives y(2T) = (y^2 + x^2)/(1 - d x^2 y^2) — so
    an order-8 point satisfies y^2 = -x^2.  Substituting into the curve
    equation: d x^4 - 2 x^2 - 1 = 0, i.e. x^2 = (1 +- sqrt(1 + d))/d
    and y = +-sqrt(-1) x.  Solve, then pick the candidate whose order
    is exactly 8 (checked via the reference bignum ladder)."""
    p = er.P

    def sqrt_mod(a):
        a %= p
        x = pow(a, (p + 3) // 8, p)
        if (x * x - a) % p:
            x = x * er.SQRT_M1 % p
        return None if (x * x - a) % p else x

    s1 = sqrt_mod(1 + er.D)
    assert s1 is not None
    d_inv = pow(er.D, p - 2, p)
    ident = er._encode(er.IDENT)
    for t in ((1 + s1) * d_inv % p, (1 - s1) * d_inv % p):
        x = sqrt_mod(t)
        if x is None:
            continue
        for xx in (x, p - x):
            for y in (xx * er.SQRT_M1 % p, p - xx * er.SQRT_M1 % p):
                # on-curve check for -x^2 + y^2 = 1 + d x^2 y^2
                if (-xx * xx + y * y - 1
                        - er.D * xx * xx % p * y * y) % p:
                    continue
                T = (xx, y, 1, xx * y % p)
                if er._encode(er._mul(8, T)) == ident and \
                        er._encode(er._mul(4, T)) != ident:
                    return T
    raise AssertionError("no order-8 point found")


def torsion_residual_sig(seed: bytes, msg: bytes,
                         nonce_tag: bytes = b"torsion nonce"):
    """The ADR-009 divergence vector, (pub, sig): R' = [r]B + T8 with T8
    of order 8, k = H(R'||A||M), s = r + k*a.  Then [s]B - [k]A =
    R' - T8 != R' (cofactorless REJECT) while [8]([s]B - R' - [k]A) =
    [8](-T8) = O: a cofactored batch check accepts it.  The withdrawn
    RLC route did; no route left may."""
    pub = er.pubkey_from_seed(seed)
    h = hashlib.sha512(seed).digest()
    a = int.from_bytes(h[:32], "little")
    a &= (1 << 254) - 8
    a |= 1 << 254
    r_nonce = int.from_bytes(
        hashlib.sha512(nonce_tag).digest(), "little") % er.L
    r_enc = er._encode(er._add(er._mul(r_nonce, er.BASE), order8_point()))
    k = int.from_bytes(
        hashlib.sha512(r_enc + pub + msg).digest(), "little") % er.L
    s = (r_nonce + k * a) % er.L
    return pub, r_enc + s.to_bytes(32, "little")
