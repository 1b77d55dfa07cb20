"""ops/field_secp.py limb arithmetic against a Python-bignum oracle.

GF(2^256 - 2^32 - 977) on radix-2^12 int32 limb vectors is the secp256k1
counterpart of ops/field.py; its docstring promises the int32 bounds are
"regression-checked against a bignum oracle in tests/test_secp_lane.py
rather than re-proved" — this is that file.  Every ring op, predicate and
exponentiation chain is compared to Python integer arithmetic mod p over
structured edge values (0, 1, p-1, p, 2^256-1, fold-boundary patterns)
and seeded random field elements, both as single lanes and batched.
"""
from __future__ import annotations

import hashlib
import random

import numpy as np
import jax.numpy as jnp
import pytest

from tendermint_tpu.ops import field_secp as FS

P = FS.P
rng = random.Random(20260803)

# structured values that stress every fold path: small, the 2^256
# boundary, the p boundary, all-ones limbs, and the fold multipliers'
# weight positions (2^32, 2^40)
EDGE = [0, 1, 2, 976, 977, 978,
        (1 << 32) - 1, 1 << 32, (1 << 32) + 977,
        (1 << 40) - 1, 1 << 40,
        P - 1, P, P + 1, P + 977,
        (1 << 255), (1 << 256) - 1,
        int("aa" * 32, 16), int("55" * 32, 16)]


def _rand(n):
    return [rng.randrange(P) for _ in range(n)]


def _col(xs):
    """ints -> (NLIMB, B) device array (batch on the trailing axis)."""
    return jnp.stack([jnp.asarray(FS.int_to_limbs(x)) for x in xs], axis=1)


def _vals(limbs):
    """(NLIMB, B) limbs -> list of ints (no reduction: callers mod p)."""
    arr = np.asarray(limbs)
    return [FS.limbs_to_int(arr[:, j]) for j in range(arr.shape[1])]


def test_int_limb_roundtrip_and_canonical_range():
    for x in EDGE + _rand(20):
        limbs = FS.int_to_limbs(x)
        assert FS.limbs_to_int(limbs) == x % P
        assert ((limbs >= 0) & (limbs <= FS.MASK)).all(), x


def test_mul_oracle():
    xs = EDGE + _rand(30)
    ys = list(reversed(xs))
    out = _vals(FS.mul(_col(xs), _col(ys)))
    for x, y, got in zip(xs, ys, out):
        assert got % P == (x % P) * (y % P) % P, (x, y)


def test_sqr_oracle():
    xs = EDGE + _rand(30)
    out = _vals(FS.sqr(_col(xs)))
    for x, got in zip(xs, out):
        assert got % P == (x % P) ** 2 % P, x


def test_mul_small_oracle():
    xs = EDGE + _rand(10)
    for k in (0, 1, 2, 8, 977, 250112):
        out = _vals(FS.mul_small(_col(xs), k))
        for x, got in zip(xs, out):
            assert got % P == (x % P) * k % P, (x, k)


def test_add_sub_carry_chain_oracle():
    """Lazy add/sub feed the next mul without an intermediate carry —
    the operand-budget contract of the parent design.  Exercise the
    worst chain the curve formulas produce: (a+b) * (c-d)."""
    a, b = EDGE + _rand(10), list(reversed(EDGE + _rand(10)))
    c, d = _rand(len(a)), _rand(len(a))
    la, lb, lc, ld = map(_col, (a, b, c, d))
    out = _vals(FS.mul(FS.add(la, lb), FS.sub(lc, ld)))
    for i, got in enumerate(out):
        want = (a[i] + b[i]) % P * ((c[i] - d[i]) % P) % P
        assert got % P == want, i


def test_carry_bounds_after_mul():
    """mul's output limbs must be loose-carried (small enough for lazy
    reuse): check against a generous int32-safety envelope."""
    xs = EDGE + _rand(50)
    limbs = np.asarray(FS.mul(_col(xs), _col(list(reversed(xs)))))
    assert np.abs(limbs).max() < (1 << 16), np.abs(limbs).max()


def test_freeze_canonical_oracle():
    """freeze: any loose value -> the canonical representative in
    [0, p), limb-exact against int_to_limbs."""
    xs = EDGE + _rand(30)
    ys = list(reversed(xs))
    loose = FS.mul(_col(xs), _col(ys))  # loose-carried input
    frozen = np.asarray(FS.freeze(loose))
    for j, (x, y) in enumerate(zip(xs, ys)):
        want = FS.int_to_limbs(x * y % P)
        assert (frozen[:, j] == want).all(), (x, y)
        assert ((frozen[:, j] >= 0) & (frozen[:, j] <= FS.MASK)).all()


def test_eq_is_zero_is_odd_oracle():
    xs = [0, 1, P - 1, 977] + _rand(8)
    la = _col(xs)
    # a representation shifted by +p must still compare equal
    lb = la + jnp.asarray(FS.int_to_limbs(0) +
                          np.array([(P >> (12 * i)) & FS.MASK
                                    for i in range(FS.NLIMB)],
                                   dtype=np.int32)).reshape(FS.NLIMB, 1)
    assert np.asarray(FS.eq(la, lb)).all()
    assert np.asarray(FS.is_zero(la)).tolist() == [x % P == 0 for x in xs]
    assert np.asarray(FS.is_odd(la)).tolist() == [x % P % 2 == 1
                                                  for x in xs]


def test_invert_oracle():
    xs = [x for x in EDGE if x % P != 0] + _rand(10)
    inv = FS.invert(_col(xs))
    prod = _vals(FS.mul(_col(xs), inv))
    assert all(v % P == 1 for v in prod)
    for x, got in zip(xs, _vals(inv)):
        assert got % P == pow(x, P - 2, P), x


def test_sqrt_oracle():
    """p = 3 (mod 4): sqrt via a^((p+1)/4) on quadratic residues; the
    caller-side contract is sqr(sqrt(a)) == a, checked here, plus the
    value against the bignum exponentiation."""
    roots = [2, 3, 976, P - 2] + _rand(8)
    qrs = [r * r % P for r in roots]
    s = FS.sqrt(_col(qrs))
    back = _vals(FS.sqr(s))
    for a, got in zip(qrs, back):
        assert got % P == a, a
    for a, got in zip(qrs, _vals(s)):
        assert got % P == pow(a, (P + 1) // 4, P), a


def test_sqrt_non_residue_detectable():
    """Non-residues yield garbage by contract — but sqr(result) != a
    must hold so the caller's check catches them."""
    # find a non-residue (Euler's criterion)
    nr = next(x for x in range(2, 50)
              if pow(x, (P - 1) // 2, P) == P - 1)
    s = FS.sqrt(_col([nr]))
    assert _vals(FS.sqr(s))[0] % P != nr


# ---------------------------------------------------------------------------
# the device lane itself (ops/secp.py) — orphaned in the r5 seed (559 LoC
# imported by nothing, tested by nothing, and its unrolled pow chains
# never even finished compiling); now wired into crypto/batch behind
# TM_TPU_SECP_LANE=1 / [batch_verifier] secp_lane
# ---------------------------------------------------------------------------

def _secp_adversarial_vectors():
    """The consensus-relevant structured encodings (mirrors
    test_native_ec._secp_adversarial_cases): s >= N, r >= P, pubkey
    x >= P, non-square lift_x, off-curve R_x, plus valid controls."""
    from tendermint_tpu.crypto import secp256k1 as secp

    k = secp.PrivKey.gen_from_secret(b"\x77" * 32)
    pub = k.pub_key().bytes()
    m = b"structured secp lane"
    good = k.sign(m)
    r_good, s_good = good[:32], good[32:]

    def be(x):
        return x.to_bytes(32, "big")

    x = 5
    while pow((pow(x, 3, secp.P) + 7) % secp.P,
              (secp.P - 1) // 2, secp.P) == 1:
        x += 1
    off_curve_x = be(x)

    k2 = secp.PrivKey.gen_from_secret(b"\x78" * 32)
    m2 = b"second control"
    return [
        (pub, m, r_good + be(secp.N)),           # s == group order
        (pub, m, r_good + be(secp.N + 1)),       # s > group order
        (pub, m, be(secp.P) + s_good),           # r == field prime
        (pub, m, be(secp.P + 1) + s_good),       # r > field prime
        (pub, m, off_curve_x + s_good),          # R_x: non-square lift_x
        (b"\x02" + be(secp.P), m, good),         # pubkey x >= p
        (b"\x02" + off_curve_x, m, good),        # pubkey off curve
        (pub, m, r_good + be(0)),                # s == 0
        (pub, m, good),                          # control: valid
        (k2.pub_key().bytes(), m2, k2.sign(m2)),  # second valid control
    ]


def _secp_degenerate_key_vectors():
    """Secret keys 1 and N-1 (P = G and P = -G: x-only, so both lift to
    G, whose table of -P multiples meets the fixed-base G table's own
    rows inside the ladder, as P = Q and P = -Q additions), each signed
    honestly, with a flipped bit, and with s = e, for which
    R' = [e]G - [e]G is the point at infinity."""
    from tendermint_tpu.crypto import secp256k1 as secp

    out = []
    for d in (1, secp.N - 1):
        k = secp.PrivKey(d.to_bytes(32, "big"))
        pub = k.pub_key().bytes()
        m = b"degenerate key %d" % (d & 0xFF)
        good = k.sign(m)
        bad = bytearray(good)
        bad[40] ^= 4
        r = good[:32]
        e = int.from_bytes(secp._tagged_hash(
            "BIP0340/challenge",
            r + pub[1:] + hashlib.sha256(m).digest()), "big") % secp.N
        out += [(pub, m, good), (pub, m, bytes(bad)),
                (pub, m, r + e.to_bytes(32, "big"))]
    return out


@pytest.mark.slow
def test_secp_device_lane_bitmap_vs_host_oracles():
    """Bitmap of the TPU lane pinned against the host oracles on the
    adversarial vectors, the degenerate keys 1 and N-1 and a corrupted-
    signature sweep.  Slow tier: the whole Pallas kernel through the
    interpreter off a TPU costs minutes of XLA-on-CPU compile (one per
    process)."""
    from tendermint_tpu.crypto import secp256k1 as secp
    from tendermint_tpu.libs import native
    from tendermint_tpu.ops import secp as secp_ops

    cases = _secp_adversarial_vectors() + _secp_degenerate_key_vectors()
    # plus a corrupted sweep over fresh keys
    for i in range(6):
        k = secp.PrivKey.gen_from_secret((0xE100 + i).to_bytes(32, "big"))
        m = b"sweep %d" % i
        s = bytearray(k.sign(m))
        if i % 2:
            s[(i * 11) % 64] ^= 1 << (i % 8)
        cases.append((k.pub_key().bytes(), m, bytes(s)))
    pubs = [c[0] for c in cases]
    msgs = [c[1] for c in cases]
    sigs = [c[2] for c in cases]

    want = [secp.PubKey(p).verify_signature(m, s)
            for p, m, s in zip(pubs, msgs, sigs)]
    assert any(want) and not all(want)
    got = secp_ops.verify_batch_device(pubs, msgs, sigs)
    assert [bool(b) for b in got] == want
    cok = native.secp_verify(pubs, msgs, sigs) \
        if native.get_lib() is not None else None
    if cok is not None:  # the C oracle, where a toolchain exists
        assert [bool(b) for b in got] == [bool(b) for b in cok]


def test_secp_lane_routing_default_on_with_rollback(monkeypatch):
    """crypto/batch routes secp256k1 to the device lane BY DEFAULT
    (ADR-015); TM_TPU_SECP_LANE=0 or config secp_lane=false ->
    set_lane_enabled is the rollback switch back to the host C lane,
    config winning over env both directions.  The bitmap stays exact
    either way.  The heavy kernel is stubbed with the host oracle —
    compile-free, the lane's own bitmap is pinned in the slow-tier test
    above."""
    from tendermint_tpu.crypto import batch as cb
    from tendermint_tpu.crypto import secp256k1 as secp
    from tendermint_tpu.ops import secp as secp_ops

    monkeypatch.setenv("TM_TPU_FORCE_BATCH", "1")
    monkeypatch.setattr(secp_ops, "_lane_override", None)
    routed = []

    def spy(pubs_, msgs_, sigs_):
        routed.append(len(pubs_))
        return np.array([secp.PubKey(p).verify_signature(m, s)
                         for p, m, s in zip(pubs_, msgs_, sigs_)])

    monkeypatch.setattr(secp_ops, "verify_batch_device", spy)

    def run_batch():
        bv = cb.BatchVerifier(tpu_threshold=2)
        want = []
        for i in range(6):
            k = secp.PrivKey.gen_from_secret((0xE200 + i).to_bytes(32,
                                                                   "big"))
            m = b"route optin %d" % i
            s = bytearray(k.sign(m))
            ok = True
            if i == 3:
                s[0] ^= 1
                ok = False
            bv.add(k.pub_key(), m, bytes(s))
            want.append(ok)
        _, bits = bv.verify()
        return want, list(bits)

    # default (no env, no config): routes to the device lane
    monkeypatch.delenv("TM_TPU_SECP_LANE", raising=False)
    want, bits = run_batch()
    assert bits == want and routed == [6]
    # env rollback keeps it on the host C/python lane
    monkeypatch.setenv("TM_TPU_SECP_LANE", "0")
    want, bits = run_batch()
    assert bits == want and routed == [6]
    # config override wins over the env, both directions
    secp_ops.set_lane_enabled(True)
    want, bits = run_batch()
    assert bits == want and routed == [6, 6]
    secp_ops.set_lane_enabled(False)
    monkeypatch.delenv("TM_TPU_SECP_LANE")
    want, bits = run_batch()
    assert bits == want and routed == [6, 6]


@pytest.fixture
def stubbed_kernel(monkeypatch):
    """ops/pallas_secp.verify replaced by a stand-in that notes the tile
    and interpret flag _verify_core hands it and calls every lane valid;
    _verify_core's own jit traces the stand-in (its cache is cleared on
    both sides, so no other test meets the stub), and the launch
    bookkeeping starts empty and is put back afterwards."""
    import jax.numpy as jnp

    from tendermint_tpu.ops import ed25519 as edops
    from tendermint_tpu.ops import pallas_secp as PS
    from tendermint_tpu.ops import secp as secp_ops

    seen = []

    def stub(px, rx, s_digits, e_digits, tile, interpret):
        seen.append((px.shape[1], tile, interpret))
        return jnp.ones(px.shape[1], dtype=bool)

    monkeypatch.setattr(PS, "verify", stub)
    monkeypatch.setattr(edops, "_seen_buckets", set())
    monkeypatch.setattr(edops, "_compiled", set())
    secp_ops._verify_core.clear_cache()
    yield seen
    secp_ops._verify_core.clear_cache()


@pytest.mark.parametrize("n", [1, 40, 100, 300])
def test_lane_record_contract_with_the_kernel_stubbed(stubbed_kernel, n):
    """One launch record a verify_batch_device call, path LANE_PATH
    ("secp-xla", which names the lane and outlives the XLA ladder) and
    nb == bucket_size(n); the kernel gets the whole bucket, the tile
    min(DEFAULT_TILE, nb) and the interpreter off a TPU; warm_bucket(n)
    marks that bucket seen, so the first request's launch is no first
    launch."""
    from tendermint_tpu.crypto import devobs
    from tendermint_tpu.crypto import secp256k1 as secp
    from tendermint_tpu.ops import ed25519 as edops
    from tendermint_tpu.ops import pallas_secp as PS
    from tendermint_tpu.ops import secp as secp_ops

    assert secp_ops.LANE_PATH == "secp-xla"
    nb = edops.bucket_size(n)
    assert devobs.is_enabled()
    seq0 = devobs.last_seq()
    assert secp_ops.warm_bucket(n) == nb
    (warm,) = devobs.records(since_seq=seq0)
    assert (warm["path"], warm["n"], warm["nb"]) == ("secp-xla", 0, nb)
    assert warm["first_launch"] and warm.get("compile_s", 0) > 0
    assert ("secp-xla", nb, 1) in edops._seen_buckets
    assert stubbed_kernel == [(nb, min(PS.DEFAULT_TILE, nb), True)]

    keys = [secp.PrivKey.gen_from_secret(b"contract %d" % (i % 4))
            for i in range(n)]
    msgs = [b"contract row %d" % i for i in range(n)]
    sigs = [k.sign(m) for k, m in zip(keys, msgs)]
    pubs = [k.pub_key().bytes() for k in keys]
    seq0 = devobs.last_seq()
    bits = secp_ops.verify_batch_device(pubs, msgs, sigs)
    (rec,) = devobs.records(since_seq=seq0)
    assert bits.tolist() == [True] * n
    assert (rec["path"], rec["n"], rec["nb"]) == ("secp-xla", n, nb)
    assert not rec["first_launch"] and "compile_s" not in rec
    assert rec["wall_s"] >= rec["stage_s"] > 0
    assert len(stubbed_kernel) == 1  # the warm trace served the request
