"""A validator set in three key schemes (BASELINE config 5) on the normal
path, against the plain reference perfbench/reference/mixed_commit.py:

- `Commit.validate_basic` + `ValidatorSet.verify_commit` on a seeded commit
  of 54 validators (18 a scheme, 5 absent) give the reference's verdict, and
  the bulk bitmap the reference's, for every case `val10k-mixed-commit`'s
  `correct` compares at 10,000 (perfbench/traffic/mixed_commit.py `cases`).
  At 18 rows a scheme no lane reaches the device-lane floor of 32, so this
  is the host lanes' answer, which the guarantees say is the same bitmap;
- the reference itself against the known-answer vectors tests/ already has
  for BIP-340, merlin and ristretto255, against FIPS 202 through hashlib,
  and against the repo's host verifiers on their adversarial encodings;
- `ops/ed25519.prewarm` and LightServe hand the comb ed25519 keys only;
- each lane's `verify_batch_device`, its XLA core stubbed, writes one launch
  record and its spans and compiles a first shape inside `compiling()`.
"""
from __future__ import annotations

import contextlib
import functools
import hashlib

import numpy as np
import pytest

from perfbench.reference import mixed_commit as reference
from perfbench.traffic import mixed_commit as traffic
from tendermint_tpu.crypto import devobs
from tendermint_tpu.crypto import degrade
from tendermint_tpu.crypto import ed25519 as edkeys
from tendermint_tpu.crypto import secp256k1 as secp
from tendermint_tpu.crypto import sr25519 as sr
from tendermint_tpu.libs import trace
from tendermint_tpu.ops import ed25519 as edops

CONFIG = {"name": "tiny54-mixed", "chain_id": "mixed-commit-test",
          "validators": 54, "voting_power": 1, "absent_share": 0.1,
          "key_types": {"ed25519": 18, "secp256k1": 18, "sr25519": 18}}


@functools.lru_cache(maxsize=None)
def world(seed: int = 2**31 + 33):
    w = traffic.setup(CONFIG, {"ring": 1, "expect_launch": []}, seed, 0)
    w["span"] = lambda name: contextlib.nullcontext()
    w["memo"] = {}
    return w


def test_the_set_interleaves_the_schemes():
    w = world()
    kinds = [v.pub_key.type_name for v in w["vset"].validators]
    assert {k: kinds.count(k) for k in set(kinds)} == CONFIG["key_types"]
    assert len({k for k in kinds[:9]}) > 1      # not sorted by scheme
    assert w["vset"]._pub_matrix() == (None, False)
    assert [c[0] for c in traffic.cases(w)] == list(traffic.CASES)


@pytest.mark.parametrize("name", traffic.CASES)
def test_program_and_reference_agree(name):
    w = world()
    (commit, expect), = [(c, e) for n, c, e in traffic.cases(w) if n == name]
    failures, want_bits = traffic.compare(w, name, commit, expect, w["memo"])
    assert failures == []
    if want_bits is not None:
        signed = [i for i, cs in enumerate(commit.signatures)
                  if not cs.is_absent()]
        assert np.array_equal(traffic.bulk_bitmap(w, commit, signed),
                              want_bits)
        if name == "ends-tampered":
            assert int((~want_bits).sum()) == 6


def test_the_error_names_the_first_bad_row():
    w = world()
    (commit, expect), = [(c, e) for n, c, e in traffic.cases(w)
                         if n == "ends-tampered"]
    with pytest.raises(Exception, match=rf"wrong signature \(#{expect[1]}\)"):
        w["vset"].verify_commit(w["chain"], commit.block_id, commit.height,
                                commit)


# -- the reference against what is known ------------------------------------

@pytest.mark.parametrize("msg", [b"", b"abc", b"\xa3" * 200, b"q" * 1000])
def test_reference_keccak_is_fips202s(msg):
    state, rate = bytearray(200), 136          # SHA3-256 as a sponge
    padded = bytearray(msg) + b"\x06"
    padded += bytes(-len(padded) % rate)
    padded[-1] |= 0x80
    for off in range(0, len(padded), rate):
        for i in range(rate):
            state[i] ^= padded[off + i]
        reference.keccak_f1600(state)
    assert bytes(state[:32]) == hashlib.sha3_256(msg).digest()


def test_reference_merlin_conformance_vector():
    """merlin's own test (tests/test_multikey.py has it for _strobe)."""
    t = reference.Transcript(b"test protocol")
    t.append_message(b"some label", b"some data")
    assert t.challenge_bytes(b"challenge", 32).hex() == (
        "d5a21972d0d5fe320c0d263fac7fffb8145aa640af6e9bca177c03c7efcf0615")


def test_reference_ristretto_rfc9496_vectors():
    base = reference._ed_base()
    for k, want in ((0, "00" * 32),
                    (1, "e2f2ae0a6abc4e71a884a961c500515f"
                        "58e30b6aa582dd8db6a65945e08d2d76"),
                    (2, "6a493210f7499cd17fecb510ae0cea23"
                        "a110e8d5b901f8acadd3095c73a3b919")):
        enc = reference.ristretto_encode(reference._ed_mul(k, base))
        assert enc.hex() == want
        assert enc == reference.ristretto_encode(reference._ed_mul_base(k))
        assert reference.ristretto_equal(reference.ristretto_decode(enc),
                                         reference._ed_mul(k, base))
    p = reference.ED_P
    for bad in (3, p + 2, 2 + (1 << 255)):     # negative, >= p, bit 255
        assert reference.ristretto_decode(bad.to_bytes(32, "little")) is None


def test_reference_bip340_vector_0():
    """BIP-340's test vector 0 (tests/test_multikey.py has it for the
    repo's signer): secret key 3, message and auxiliary bytes zero."""
    pub_x = bytes.fromhex(
        "F9308A019258C31049344F85F89D5229B531C845836F99B08601F113BCE036F9")
    sig = bytes.fromhex(
        "E907831F80848D1069A5371B402410364BDF1C5F8307B0084C55F1CE2DCA8215"
        "25F66A4A85EA8B71E482A74F382D2CE5EBEEE8FDB2172F477DF4900D310536C0")
    assert reference.bip340_verify(pub_x, bytes(32), sig)
    assert not reference.bip340_verify(pub_x, b"\x01" + bytes(31), sig)
    assert not reference.bip340_verify(pub_x, bytes(32),
                                       sig[:63] + bytes([sig[63] ^ 1]))


def test_reference_signers_are_the_repos():
    """Same keys and the same deterministic signatures as the repo's own
    signers give from the same secret: the generator's traffic is what a
    node of this repo would sign."""
    seed, msg = b"\x5a" * 32, b"a precommit's sign bytes"
    for scheme, priv in (
            ("ed25519", edkeys.PrivKey(seed)),
            ("secp256k1", secp.PrivKey.gen_from_secret(seed)),
            ("sr25519", sr.PrivKey(seed))):
        key = reference.Key(scheme, seed)
        assert key.pub_bytes == priv.pub_key().bytes()
        assert key.sign(msg) == priv.sign(msg)
        assert reference.VERIFIERS[scheme](key.pub_bytes, msg, key.sign(msg))
        assert not reference.VERIFIERS[scheme](key.pub_bytes, msg + b"!",
                                               key.sign(msg))


def _secp_adversarial():
    from test_native_ec import _secp_adversarial_cases
    from test_secp_lane import _secp_adversarial_vectors
    return _secp_adversarial_cases() + _secp_adversarial_vectors()


def _sr_adversarial():
    from test_native_ec import _sr_adversarial_cases
    return _sr_adversarial_cases()


@pytest.mark.parametrize("scheme,vectors,host", [
    ("secp256k1", _secp_adversarial,
     lambda p, m, s: secp.PubKey(p).verify_signature(m, s)),
    ("sr25519", _sr_adversarial, sr.verify)])
def test_reference_agrees_with_the_host_verifier(scheme, vectors, host):
    verdicts = [(reference.VERIFIERS[scheme](p, m, s), host(p, m, s))
                for p, m, s in vectors()]
    assert all(ours == theirs for ours, theirs in verdicts), verdicts
    assert {ours for ours, _ in verdicts} == {True, False}


# -- the comb is asked about ed25519 keys only --------------------------------

@pytest.fixture
def fresh_runtime():
    from tendermint_tpu.libs.metrics import Registry
    rt = degrade.configure(registry=Registry("mixed_commit"))
    edops.table_cache_clear()
    yield rt
    edops.table_cache_clear()
    degrade.reset()


def test_prewarm_declines_a_list_that_holds_a_33_byte_key(fresh_runtime):
    keys = [v.pub_key.bytes() for v in world()["vset"].validators]
    assert {len(k) for k in keys} == {32, 33}
    assert edops.prewarm(keys) is False          # and raised nothing
    assert fresh_runtime.metrics.msm_route.items()[("comb", "declined")] == 1
    assert len(edops._table_cache) == 0


class _Stores:
    """What LightServe._prewarm_latest reads: the newest height and the
    set that signs the next one."""

    def __init__(self, vset):
        self.vset = vset

    def height(self):
        return 3

    def load_validators(self, height):
        return self.vset


def test_lightserve_hands_the_comb_the_ed25519_keys_alone(
        monkeypatch, fresh_runtime):
    """An sr25519 key is 32 bytes too: it is told apart by type.  What
    the service hands over builds tables for the ed25519 keys alone."""
    from test_comb import _stub_kernels
    from tendermint_tpu.light.service import LightServe
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import ValidatorSet

    vals = [v for v in world()["vset"].validators
            if v.pub_key.type_name != "secp256k1"]
    vset = ValidatorSet([Validator.new(v.pub_key, 1) for v in vals])
    assert {len(v.pub_key.bytes()) for v in vset.validators} == {32}
    stores = _Stores(vset)
    svc = LightServe(stores, stores, "mixed-commit-test", prewarm=True)
    handed = []
    monkeypatch.setattr(edops, "prewarm_async",
                        lambda keys: handed.append(list(keys)))
    svc._prewarm_latest()
    want = sorted(v.pub_key.bytes() for v in vset.validators
                  if v.pub_key.type_name == "ed25519")
    assert len(want) == 18 and sorted(handed[0]) == want
    rec = {}
    _stub_kernels(monkeypatch, record=rec)
    monkeypatch.setattr(edops, "_comb_min_override", 1)
    assert edops.prewarm(handed[0], warm_kernel=False)
    (set_hash,) = edops._table_cache.keys()
    assert sorted(edops._table_cache.peek(set_hash).index) == want


# -- the lanes write launch records and spans ---------------------------------

class _StubCore:
    """Stands in for a lane's jitted `_verify_core`: every lane true, and
    it notes whether its ahead-of-time compile ran inside `compiling()`."""

    def __init__(self, inside):
        self.inside, self.compiled_inside, self.calls = inside, [], 0

    def lower(self, *operands):
        self.compiled_inside.append(bool(self.inside))
        return self

    def compile(self):
        return self

    def __call__(self, *operands):
        import jax.numpy as jnp
        self.calls += 1
        return jnp.ones(max(operands[2].shape), dtype=bool)


def _lane_rows(scheme: str, n: int):
    keys = [reference.Key(scheme, bytes([i]) * 32) for i in range(n)]
    msgs = [b"lane row %d" % i for i in range(n)]
    return ([k.pub_bytes for k in keys], msgs,
            [k.sign(m) for k, m in zip(keys, msgs)])


@pytest.mark.parametrize("scheme,module,path,bad_sig", [
    ("secp256k1", "secp", "secp-xla",
     lambda s: s[:32] + (reference.SECP_N + 5).to_bytes(32, "big")),
    ("sr25519", "sr25519", "sr25519-xla",
     lambda s: s[:63] + bytes([s[63] & 0x7F]))])
def test_a_lane_launch_is_recorded_and_spanned(monkeypatch, scheme, module,
                                               path, bad_sig):
    import importlib
    lane = importlib.import_module("tendermint_tpu.ops." + module)
    inside = []

    @contextlib.contextmanager
    def compiling():
        inside.append(True)
        try:
            yield
        finally:
            inside.pop()

    monkeypatch.setattr(degrade, "compiling", compiling)
    core = _StubCore(inside)
    monkeypatch.setattr(lane, "_verify_core", core)
    assert lane.LANE_PATH == path
    pubs, msgs, sigs = _lane_rows(scheme, 5)
    sigs[3] = bad_sig(sigs[3])               # refused by the host screens
    was_on = trace.is_enabled()
    trace.enable()
    try:
        seq0, span0 = devobs.last_seq(), trace.last_seq()
        bits = lane.verify_batch_device(pubs, msgs, sigs)
        again = lane.verify_batch_device(pubs, msgs, sigs)
        spans = [r for r in trace.snapshot() if r["seq"] > span0]
    finally:
        if not was_on:
            trace.disable()
    assert bits.tolist() == again.tolist() == [True, True, True, False, True]
    first, second = devobs.records(since_seq=seq0)
    for rec in (first, second):
        assert (rec["path"], rec["n"], rec["nb"]) == (path, 5, 64)
        assert rec["wall_s"] >= rec["stage_s"] > 0
        assert "stage_cpu_s" in rec and "compute_s" in rec
    assert first["first_launch"] and first.get("compile_s", 0) > 0
    assert not second["first_launch"] and "compile_s" not in second
    assert core.compiled_inside == [True] and core.calls == 2
    assert f"{path}/nb=64" in {
        f"{e['path']}/nb={e['nb']}" for e in devobs.compile_inventory()}
    short = "secp" if scheme == "secp256k1" else "sr25519"
    names = [r["name"] for r in spans]
    assert names.count(f"ops.{short}.verify_batch") == 2
    assert names.count(f"{short}.stage") == 2
    outer = [r for r in spans if r["name"] == f"ops.{short}.verify_batch"]
    assert all(r["attrs"]["n"] == 5 and r["attrs"]["nb"] == 64
               and r["attrs"]["path"] == path for r in outer)
    # the warm-up's direct call takes the same bucket and counts no row
    seq0 = devobs.last_seq()
    assert lane.warm_bucket(40) == 64
    (rec,) = devobs.records(since_seq=seq0)
    assert (rec["path"], rec["n"], rec["nb"]) == (path, 0, 64)
    assert not rec["first_launch"]
