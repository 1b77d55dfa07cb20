"""Chaos matrix over the device verify lane (crypto/degrade.py).

Counterpart of tests/test_crash_matrix.py for the NON-fatal failure
classes: instead of killing the process at indexed fail points, each
case arms a libs/fail.py mode at the device-lane seams and asserts the
degradation runtime's contract — BatchVerifier.verify() returns the
EXACT bitmap of the pure-host path (no hang, no crash, no exception)
under every injected failure class, and the circuit breaker demonstrably
opens, backs off, and re-closes (ISSUE 1 acceptance criteria).

The device lane here is the XLA-composed kernel forced onto CPU
(TM_TPU_FORCE_BATCH=1, same trick as the sr25519 lane tests): the
degradation runtime sits strictly above the kernel, so the failure
plumbing exercised is exactly what runs against real hardware.
"""
from __future__ import annotations

import random

import numpy as np
import pytest

from tendermint_tpu.crypto import batch as cb
from tendermint_tpu.crypto import degrade
from tendermint_tpu.crypto import ed25519 as edkeys
from tendermint_tpu.libs import fail
from tendermint_tpu.libs.metrics import Registry

rng = random.Random(77)


@pytest.fixture(autouse=True)
def _force_device(monkeypatch):
    monkeypatch.setenv("TM_TPU_FORCE_BATCH", "1")
    monkeypatch.delenv("TM_TPU_DISABLE_BATCH", raising=False)
    fail.reset()
    yield
    fail.reset()
    degrade.reset()


def _runtime(clk=None, **kw):
    cfg = degrade.DegradeConfig(
        failure_threshold=kw.pop("failure_threshold", 3),
        launch_timeout_s=kw.pop("launch_timeout_s", 120.0),
        backoff_base_s=10.0, backoff_max_s=100.0, backoff_jitter=0.0)
    return degrade.configure(cfg, clock=clk or (lambda: 0.0),
                             registry=Registry("chaos"))


def _mixed_batch(n=24, bad=(3, 11, 17)):
    """n ed25519 triples, `bad` lanes invalid (flipped sig byte, one
    truncated) — the bitmap must attribute failures exactly."""
    privs = [edkeys.PrivKey(bytes([i + 1]) * 32) for i in range(n)]
    msgs = [b"chaos vote %d" % i for i in range(n)]
    sigs = [p.sign(m) for p, m in zip(privs, msgs)]
    for i in bad:
        sigs[i] = (sigs[i][:50] if i == bad[-1]
                   else bytes([sigs[i][0] ^ 1]) + sigs[i][1:])
    return privs, msgs, sigs


def _verify(privs, msgs, sigs, threshold=4):
    bv = cb.BatchVerifier(tpu_threshold=threshold)
    for p, m, s in zip(privs, msgs, sigs):
        bv.add(p.pub_key(), m, s)
    return bv.verify()


def _host_baseline(privs, msgs, sigs, monkeypatch):
    monkeypatch.setenv("TM_TPU_DISABLE_BATCH", "1")
    try:
        _, bits = _verify(privs, msgs, sigs)
    finally:
        monkeypatch.delenv("TM_TPU_DISABLE_BATCH")
    return bits


# (site, mode, failure-class counter the case must increment)
CASES = [
    (None, None, None),                                   # control
    ("ops.ed25519.verify_batch", "raise", "raise"),       # device raises
    ("ops.ed25519.verify_batch", "latency:25", None),     # slow, in budget
    ("batch.ed25519", "corrupt-bitmap", "integrity"),     # garbage bitmap
]


@pytest.mark.parametrize("site,mode,reason", CASES,
                         ids=["control", "raise", "latency", "corrupt"])
def test_bitmap_identical_to_host_under_injection(monkeypatch, site,
                                                  mode, reason):
    rt = _runtime()
    privs, msgs, sigs = _mixed_batch()
    base = _host_baseline(privs, msgs, sigs, monkeypatch)
    assert not base.all() and base.sum() == len(privs) - 3
    if site:
        fail.set_mode(site, mode)
    ok, bits = _verify(privs, msgs, sigs)
    assert (bits == base).all(), (mode, bits, base)
    assert ok == bool(base.all())
    if mode:
        assert fail.fired(site, mode) >= 1, "injection never triggered"
    if reason:
        assert rt.metrics.device_failures.value(
            site="batch.ed25519", reason=reason) == 1
        assert rt.metrics.host_fallbacks.value(
            site="batch.ed25519", reason=reason) == 1


def test_sr25519_lane_chaos_raise_bitmap_exact():
    """The ristretto lane's chaos seam (ops.sr25519.verify_batch — a
    registered site in libs/fail.REGISTERED_SITES, asserted exercised
    by tests/test_lint.py): an injected raise at the lane entry
    degrades to host re-verify with the exact per-sig bitmap.  The
    injection fires at function entry BEFORE any staging or kernel
    dispatch, so this spends no XLA compile budget on the sr kernel."""
    from tendermint_tpu.crypto import sr25519 as srpy

    rt = _runtime()
    n = 6
    minis = [(0xBEE0 + i).to_bytes(32, "little") for i in range(n)]
    msgs = [b"sr chaos %d" % i for i in range(n)]
    sigs = [srpy.sign(minis[i], msgs[i]) for i in range(n)]
    sigs[2] = bytes([sigs[2][0] ^ 1]) + sigs[2][1:]  # tamper R
    pubs = [srpy.PrivKey(m).pub_key() for m in minis]
    fail.set_mode("ops.sr25519.verify_batch", "raise")
    bv = cb.BatchVerifier(tpu_threshold=4)
    for p, m, s in zip(pubs, msgs, sigs):
        bv.add(p, m, s)
    ok, bits = bv.verify()
    assert not ok
    assert bits.tolist() == [True, True, False, True, True, True]
    assert fail.fired("ops.sr25519.verify_batch", "raise") >= 1
    assert rt.metrics.device_failures.value(
        site="batch.sr25519", reason="raise") == 1
    assert rt.metrics.host_fallbacks.value(
        site="batch.sr25519", reason="raise") == 1


def _secp_batch(n=6, bad=(2,)):
    from tendermint_tpu.crypto import secp256k1 as secp

    privs = [secp.PrivKey.gen_from_secret((0xC500 + i).to_bytes(32, "big"))
             for i in range(n)]
    msgs = [b"secp chaos %d" % i for i in range(n)]
    sigs = [p.sign(m) for p, m in zip(privs, msgs)]
    for i in bad:
        sigs[i] = bytes([sigs[i][0] ^ 1]) + sigs[i][1:]
    return [p.pub_key() for p in privs], msgs, sigs


def test_secp_device_lane_chaos_raise_bitmap_exact():
    """The secp256k1 lane is default-on (ADR-015) and its chaos seam
    (ops.secp.verify_batch, registered in libs/fail.REGISTERED_SITES,
    asserted exercised by tests/test_lint.py) degrades to the host C
    lane with the exact per-sig bitmap.  Like the sr25519 twin above,
    the injection fires at function entry BEFORE any staging or kernel
    dispatch — no compile budget spent on the secp kernel."""
    rt = _runtime()
    pubs, msgs, sigs = _secp_batch()
    fail.set_mode("ops.secp.verify_batch", "raise")
    bv = cb.BatchVerifier(tpu_threshold=4)
    for p, m, s in zip(pubs, msgs, sigs):
        bv.add(p, m, s)
    ok, bits = bv.verify()
    assert not ok
    assert bits.tolist() == [True, True, False, True, True, True]
    assert fail.fired("ops.secp.verify_batch", "raise") >= 1
    assert rt.metrics.device_failures.value(
        site="batch.secp256k1", reason="raise") == 1
    assert rt.metrics.host_fallbacks.value(
        site="batch.secp256k1", reason="raise") == 1


def test_secp_lane_latency_timeout_and_corrupt_bitmap(monkeypatch):
    """The remaining secp failure classes — a stalled launch past its
    deadline and a garbage bitmap caught by the host spot check — with
    the kernel stubbed by the host oracle: the degrade plumbing under
    test sits strictly ABOVE the kernel, and running the real 64-step
    complete-add ladder would cost a multi-minute XLA-on-CPU compile
    (its own bitmap is pinned in test_secp_lane's slow tier)."""
    from tendermint_tpu.crypto import secp256k1 as secp
    from tendermint_tpu.ops import secp as secp_ops

    def stub(pubs_, msgs_, sigs_):
        # batch.py hands the device verifier raw key bytes
        fail.inject("ops.secp.verify_batch")
        return np.array([secp.PubKey(bytes(p)).verify_signature(m, s)
                         for p, m, s in zip(pubs_, msgs_, sigs_)])

    monkeypatch.setattr(secp_ops, "verify_batch_device", stub)
    pubs, msgs, sigs = _secp_batch()

    def run(rt):
        bv = cb.BatchVerifier(tpu_threshold=4)
        for p, m, s in zip(pubs, msgs, sigs):
            bv.add(p, m, s)
        return bv.verify()

    # timeout class: stalled past the launch deadline -> quarantine +
    # host re-verify, bitmap exact
    rt = _runtime(launch_timeout_s=0.05)
    fail.set_mode("ops.secp.verify_batch", "latency:400")
    ok, bits = run(rt)
    assert bits.tolist() == [True, True, False, True, True, True]
    assert rt.metrics.device_failures.value(
        site="batch.secp256k1", reason="timeout") == 1
    fail.clear()

    # integrity class: corrupt bitmap at the degrade seam -> spot check
    # catches it -> host re-verify, bitmap exact
    monkeypatch.setattr(cb, "verified_sigs", cb.SigCache())
    rt = _runtime()
    fail.set_mode("batch.secp256k1", "corrupt-bitmap")
    ok, bits = run(rt)
    assert bits.tolist() == [True, True, False, True, True, True]
    assert fail.fired("batch.secp256k1", "corrupt-bitmap") >= 1
    assert rt.metrics.device_failures.value(
        site="batch.secp256k1", reason="integrity") == 1
    assert rt.metrics.host_fallbacks.value(
        site="batch.secp256k1", reason="integrity") == 1


def test_lanepool_chaos_all_modes_bitmap_exact():
    """The host-lane pool's chaos seam (lanepool.verify, ADR-015):
    raise, latency and corrupt-bitmap each degrade to the serial
    in-caller C path with the exact per-index bitmap.  No device, no
    kernels — this is pure host-pool plumbing."""
    from tendermint_tpu.crypto import lanepool
    from tendermint_tpu.libs import native

    if native.get_lib() is None:
        pytest.skip("no C toolchain: native lane unavailable")
    pubs, msgs, sigs = _secp_batch(n=32, bad=(3, 19))
    pb = [p.bytes() for p in pubs]
    want = [p.verify_signature(m, s)
            for p, m, s in zip(pubs, msgs, sigs)]
    # pin the pool size: corrupt-bitmap only fires on the POOLED path
    # (the chunked merge), and a 1-core runner would otherwise resolve
    # pool() to None and never exercise it
    lanepool.set_workers(2)
    try:
        for mode in ("raise", "latency:20", "corrupt-bitmap"):
            fail.reset()
            fail.set_mode("lanepool.verify", mode)
            got = lanepool.verify_sharded("secp256k1", pb, msgs, sigs)
            assert got is not None and got.tolist() == want, mode
            assert fail.fired("lanepool.verify", mode) >= 1, \
                "injection never triggered"
    finally:
        lanepool.set_workers(None)


def test_latency_past_deadline_times_out_bitmap_exact(monkeypatch):
    """The timeout class: a launch stalled past its wall-clock budget is
    abandoned and the batch re-verifies host-side — same bitmap, no
    hang.  Warm the kernel first so the tight deadline measures the
    injected stall, not jit compile."""
    rt = _runtime(launch_timeout_s=120.0)
    privs, msgs, sigs = _mixed_batch()
    base = _host_baseline(privs, msgs, sigs, monkeypatch)
    _verify(privs, msgs, sigs)  # warmup/compile through the device lane
    assert rt.breaker.state == degrade.CLOSED
    rt.cfg.launch_timeout_s = 0.05
    fail.set_mode("ops.ed25519.verify_batch", "latency:400")
    ok, bits = _verify(privs, msgs, sigs)
    assert (bits == base).all()
    assert rt.metrics.device_failures.value(
        site="batch.ed25519", reason="timeout") == 1
    # the quarantined worker must not poison the next launch
    rt.cfg.launch_timeout_s = 120.0
    fail.clear()
    ok, bits = _verify(privs, msgs, sigs)
    assert (bits == base).all()


def test_breaker_opens_backs_off_and_recloses(monkeypatch):
    """The acceptance-criteria lifecycle, through the production verify
    seam: N consecutive device faults open the breaker (everything
    host-side, no device launches), the open interval backs off, a
    post-deadline probe re-closes it, and the bitmap is host-exact at
    every step."""
    clk_t = [0.0]
    rt = _runtime(clk=lambda: clk_t[0], failure_threshold=2)
    trans = []
    rt.breaker.add_listener(lambda o, n, r: trans.append((o, n)))
    privs, msgs, sigs = _mixed_batch()
    base = _host_baseline(privs, msgs, sigs, monkeypatch)

    fail.set_mode("ops.ed25519.verify_batch", "raise")
    for _ in range(2):
        ok, bits = _verify(privs, msgs, sigs)
        assert (bits == base).all()
    assert rt.breaker.state == degrade.OPEN
    launches_when_open = rt.metrics.device_launches.value(
        site="batch.ed25519")

    # open: host-routed, zero new device launches, bitmap exact
    ok, bits = _verify(privs, msgs, sigs)
    assert (bits == base).all()
    assert rt.metrics.device_launches.value(site="batch.ed25519") == \
        launches_when_open
    assert rt.metrics.host_fallbacks.value(
        site="batch.ed25519", reason="breaker_open") == 1

    # before the backoff deadline the probe is still denied
    clk_t[0] = 9.9
    _verify(privs, msgs, sigs)
    assert rt.breaker.state == degrade.OPEN

    # device healthy again + deadline passed -> half-open probe -> close
    fail.clear()
    clk_t[0] = 10.1
    ok, bits = _verify(privs, msgs, sigs)
    assert (bits == base).all()
    assert rt.breaker.state == degrade.CLOSED
    assert (degrade.OPEN, degrade.HALF_OPEN) in trans
    assert (degrade.HALF_OPEN, degrade.CLOSED) in trans

    # and the re-closed lane actually serves from the device again
    before = rt.metrics.device_launches.value(site="batch.ed25519")
    ok, bits = _verify(privs, msgs, sigs)
    assert (bits == base).all()
    assert rt.metrics.device_launches.value(site="batch.ed25519") == \
        before + 1


def test_chaos_sweep_bulk_seam(monkeypatch):
    """Same sweep through verify_sigs_bulk (the whole-commit path, raw
    pubkey matrix — no per-key objects) — every injected class must
    yield the host-exact bitmap."""
    _runtime()
    privs, msgs, sigs = _mixed_batch(n=16, bad=(2, 9))
    pubs = np.stack([np.frombuffer(p.pub_key().bytes(), np.uint8)
                     for p in privs])
    sig_list = [bytes(s) for s in sigs]
    monkeypatch.setenv("TM_TPU_DISABLE_BATCH", "1")
    base = cb.verify_sigs_bulk(pubs, msgs, sig_list, tpu_threshold=4)
    monkeypatch.delenv("TM_TPU_DISABLE_BATCH")
    assert base.sum() == 14
    for site, mode in ((None, None),
                       ("ops.ed25519.verify_batch", "raise"),
                       ("bulk.ed25519", "corrupt-bitmap")):
        fail.reset()
        degrade.configure(degrade.DegradeConfig(backoff_jitter=0.0),
                          registry=Registry("chaos2"))
        if site:
            fail.set_mode(site, mode)
        bits = cb.verify_sigs_bulk(pubs, msgs, sig_list, tpu_threshold=4)
        assert (bits == base).all(), (mode, bits, base)
        if site:
            assert fail.fired(site, mode) >= 1
