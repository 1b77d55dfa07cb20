"""crypto/degrade.py unit tests: circuit-breaker lifecycle, backend
probing with backoff, launch timeout/quarantine, and host-fallback
plumbing — all with a deterministic injected clock and a private metrics
registry (the runtime under test never touches the process-global one).
"""
from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from tendermint_tpu.crypto import degrade
from tendermint_tpu.libs import fail
from tendermint_tpu.libs.metrics import Registry


@pytest.fixture(autouse=True)
def _clean():
    fail.reset()
    yield
    fail.reset()
    degrade.reset()


def _cfg(**kw):
    base = dict(failure_threshold=3, launch_timeout_s=5.0,
                backoff_base_s=10.0, backoff_max_s=100.0,
                backoff_jitter=0.0)
    base.update(kw)
    return degrade.DegradeConfig(**base)


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_breaker_opens_after_consecutive_failures_only():
    clk = Clock()
    br = degrade.CircuitBreaker(_cfg(), clock=clk)
    for _ in range(2):
        assert br.try_acquire()
        br.record_failure("x")
    assert br.state == degrade.CLOSED
    # a success resets the consecutive count
    assert br.try_acquire()
    br.record_success()
    for _ in range(2):
        assert br.try_acquire()
        br.record_failure("x")
    assert br.state == degrade.CLOSED
    assert br.try_acquire()
    br.record_failure("x")
    assert br.state == degrade.OPEN
    assert not br.try_acquire()


def test_breaker_probe_backoff_and_reclose():
    clk = Clock()
    trans = []
    br = degrade.CircuitBreaker(_cfg(failure_threshold=1), clock=clk)
    br.add_listener(lambda o, n, r: trans.append((o, n)))
    assert br.try_acquire()
    br.record_failure("boom")
    assert br.state == degrade.OPEN
    # before the deadline: denied; no half-open transition
    clk.t = 9.9
    assert not br.try_acquire()
    # deadline passed: exactly ONE probe is granted
    clk.t = 10.1
    assert br.try_acquire()
    assert br.state == degrade.HALF_OPEN
    assert not br.try_acquire()  # concurrent callers stay host-side
    # failed probe -> re-open with the delay doubled
    br.record_failure("still down")
    assert br.state == degrade.OPEN
    clk.t = 10.1 + 19.9
    assert not br.try_acquire()
    clk.t = 10.1 + 20.1
    assert br.try_acquire()
    br.record_success()
    assert br.state == degrade.CLOSED
    assert trans == [(degrade.CLOSED, degrade.OPEN),
                     (degrade.OPEN, degrade.HALF_OPEN),
                     (degrade.HALF_OPEN, degrade.OPEN),
                     (degrade.OPEN, degrade.HALF_OPEN),
                     (degrade.HALF_OPEN, degrade.CLOSED)]
    # backoff resets after the re-close: next open waits base_s again
    assert br.try_acquire()
    br.record_failure("y")
    assert br.state == degrade.OPEN
    t_open = clk.t
    clk.t = t_open + 10.1
    assert br.try_acquire()


def test_breaker_backoff_caps():
    clk = Clock()
    br = degrade.CircuitBreaker(_cfg(failure_threshold=1,
                                     backoff_base_s=40.0,
                                     backoff_max_s=60.0), clock=clk)
    assert br.try_acquire()
    br.record_failure("a")
    clk.t += 40.1
    assert br.try_acquire()  # probe
    br.record_failure("b")   # doubles to min(80, 60) = 60
    t0 = clk.t
    clk.t = t0 + 59.9
    assert not br.try_acquire()
    clk.t = t0 + 60.1
    assert br.try_acquire()


def test_listener_unsubscribe():
    br = degrade.CircuitBreaker(_cfg(failure_threshold=1), clock=Clock())
    got = []
    unsub = br.add_listener(lambda o, n, r: got.append(n))
    br.try_acquire()
    br.record_failure("x")
    assert got == [degrade.OPEN]
    unsub()
    br.record_success()
    assert got == [degrade.OPEN]


def test_runtime_run_success_failure_and_breaker_open():
    clk = Clock()
    rt = degrade.DeviceLaneRuntime(_cfg(failure_threshold=2), clock=clk,
                                   registry=Registry("t"))
    ok = rt.run("site", lambda: np.array([True, True]),
                host_fn=lambda: np.array([False, False]))
    assert ok.all()
    assert rt.metrics.device_launches.value(site="site") == 1

    host = np.array([True, False])
    for i in range(2):
        out = rt.run("site", lambda: 1 / 0, host_fn=lambda: host)
        assert (out == host).all()
    assert rt.breaker.state == degrade.OPEN
    assert rt.metrics.device_failures.value(site="site",
                                            reason="raise") == 2
    # breaker open: host_fn without a device attempt
    out = rt.run("site", lambda: np.array([True, True]),
                 host_fn=lambda: host)
    assert (out == host).all()
    assert rt.metrics.host_fallbacks.value(site="site",
                                           reason="breaker_open") == 1
    assert rt.metrics.device_launches.value(site="site") == 3


def test_runtime_timeout_quarantines_and_recovers():
    clk = Clock()
    rt = degrade.DeviceLaneRuntime(
        _cfg(failure_threshold=10, launch_timeout_s=0.05), clock=clk,
        registry=Registry("t"))
    release = threading.Event()

    def wedged():
        release.wait(5.0)
        return np.array([True])

    host = np.array([True])
    out = rt.run("site", wedged, host_fn=lambda: host)
    assert (out == host).all()
    assert rt.metrics.device_failures.value(site="site",
                                            reason="timeout") == 1
    release.set()
    # the wedged worker was quarantined: a fresh launch must NOT queue
    # behind it and must succeed promptly
    rt.cfg.launch_timeout_s = 5.0
    out = rt.run("site", lambda: np.array([False]),
                 host_fn=lambda: np.array([True]))
    assert not out[0]
    assert rt.breaker.state == degrade.CLOSED


def test_task_raised_timeouterror_is_raise_not_wait_timeout():
    """A TimeoutError raised BY the device fn (e.g. a timeout inside
    the runtime) is a device raise; only an expired result-wait counts as
    the timeout class and quarantines the worker."""
    rt = degrade.DeviceLaneRuntime(
        _cfg(failure_threshold=10, launch_timeout_s=5.0), clock=Clock(),
        registry=Registry("t"))

    def sock_timeout():
        raise TimeoutError("runtime read timed out")

    host = np.array([True])
    out = rt.run("site", sock_timeout, host_fn=lambda: host)
    assert (out == host).all()
    assert rt.metrics.device_failures.value(site="site",
                                            reason="raise") == 1
    assert rt.metrics.device_failures.value(site="site",
                                            reason="timeout") == 0


def test_runtime_spot_check_rejects_corrupt_device_result():
    rt = degrade.DeviceLaneRuntime(_cfg(failure_threshold=10),
                                   clock=Clock(), registry=Registry("t"))
    host = np.array([True, True])
    out = rt.run("site", lambda: np.array([False, False]),
                 host_fn=lambda: host,
                 spot_check=lambda bits: bool(bits[0]))
    assert (out == host).all()
    assert rt.metrics.device_failures.value(site="site",
                                            reason="integrity") == 1


def test_runtime_injection_sites():
    """fail.py modes reach the device fn through submit()'s wrapper.
    Ad-hoc sites must be registered before arming (a typo'd site in a
    chaos test would otherwise never fire, tmlint TM305)."""
    rt = degrade.DeviceLaneRuntime(_cfg(failure_threshold=10),
                                   clock=Clock(), registry=Registry("t"))
    host = np.array([True])
    with pytest.raises(ValueError, match="not registered"):
        fail.set_mode("site", "raise")
    fail.register("site")
    fail.set_mode("site", "raise")
    out = rt.run("site", lambda: np.array([False]), host_fn=lambda: host)
    assert (out == host).all()
    assert fail.fired("site", "raise") == 1
    assert rt.metrics.device_failures.value(site="site", reason="raise") \
        == 1
    fail.set_mode("site", "corrupt-bitmap")
    out = rt.run("site", lambda: np.array([False]), host_fn=lambda: host,
                 spot_check=lambda bits: not bits[0])
    # device said False, corruption flipped to True, spot check expected
    # False -> integrity failure -> host result
    assert (out == host).all()
    assert fail.fired("site", "corrupt-bitmap") == 1


def test_backend_probe_backoff(monkeypatch):
    """An init failure is retried after backoff instead of being cached
    forever (the _backend_ok regression this runtime replaces)."""
    clk = Clock()
    rt = degrade.DeviceLaneRuntime(_cfg(backoff_base_s=10.0), clock=clk,
                                   registry=Registry("t"))
    calls = []

    class FakeJax:
        @staticmethod
        def default_backend():
            calls.append(clk.t)
            if len(calls) < 3:
                raise RuntimeError("Unable to initialize backend")
            return "tpu"

    import sys
    monkeypatch.setitem(sys.modules, "jax", FakeJax())
    assert not rt.backend_available()
    # cached-negative until the probe deadline — no probe storm
    assert not rt.backend_available()
    assert len(calls) == 1
    clk.t = 10.1
    assert not rt.backend_available()
    assert len(calls) == 2
    # second retry backs off 20s from the failed probe
    clk.t = 10.1 + 20.1
    assert rt.backend_available()
    assert len(calls) == 3
    # a live backend is stable: no further probes
    clk.t += 1000
    assert rt.backend_available()
    assert len(calls) == 3


def test_env_failpoints_parsing(monkeypatch):
    # a typo'd env key must fail loudly at the first inject, not
    # silently never fire (same contract as the set_mode guard)
    monkeypatch.setenv("TM_TPU_FAILPOINTS", "a.typo=raise")
    with pytest.raises(ValueError, match="not registered"):
        fail.inject("anything.at.all")
    fail.register("a.site")
    fail.register("b.site")
    monkeypatch.setenv("TM_TPU_FAILPOINTS",
                       "a.site=raise; b.site=latency:1")
    with pytest.raises(fail.InjectedFault):
        fail.inject("a.site")
    t0 = time.monotonic()
    fail.inject("b.site")
    assert time.monotonic() - t0 < 1.0
    fail.inject("c.site")  # unarmed: no-op
    # programmatic arming wins and wildcard matches
    fail.set_mode("*", "raise")
    with pytest.raises(fail.InjectedFault):
        fail.inject("c.site")


# ---------------------------------------------------------------------------
# the one-time trace + compile of a kernel shape runs outside the launch
# deadline (measured on the v5e: 75-88 s cold per ladder bucket against
# the 60 s deadline — PERF.md "Chip bring-up")
# ---------------------------------------------------------------------------

def _timeouts(rt):
    return rt.metrics.device_failures.value(site="site", reason="timeout")


def test_compile_time_is_not_charged_to_the_launch_deadline():
    rt = degrade.DeviceLaneRuntime(
        _cfg(failure_threshold=10, launch_timeout_s=0.5), clock=Clock(),
        registry=Registry("t"))
    dev = np.array([False])          # distinguishable from the host's

    def cold_launch():
        with degrade.compiling():
            time.sleep(1.2)          # over two deadlines' worth of compiler
        time.sleep(0.05)             # the launch itself: inside one
        return dev

    out = rt.run("site", cold_launch, host_fn=lambda: np.array([True]))
    assert not out[0] and _timeouts(rt) == 0

    # the same wall outside compiling() is device time: it times out
    def slow_launch():
        time.sleep(1.25)
        return dev

    out = rt.run("site", slow_launch, host_fn=lambda: np.array([True]))
    assert out[0] and _timeouts(rt) == 1


def test_launch_queued_behind_a_compile_waits_it_out():
    """The lane worker is one thread: a compile delays the launch
    queued behind it just the same, and must not fail it either."""
    rt = degrade.DeviceLaneRuntime(
        _cfg(failure_threshold=10, launch_timeout_s=0.5), clock=Clock(),
        registry=Registry("t"))
    dev = np.array([False])

    def cold_launch():
        with degrade.compiling():
            time.sleep(1.2)
        return dev

    f1 = rt.submit("site", cold_launch)
    f2 = rt.submit("site", lambda: dev)
    host = lambda: np.array([True])  # noqa: E731
    assert not rt.collect("site", f2, host)[0]
    assert not rt.collect("site", f1, host)[0]
    assert _timeouts(rt) == 0


def test_a_compile_has_its_own_bound(monkeypatch):
    monkeypatch.setattr(degrade, "COMPILE_TIMEOUT_S", 0.2)
    rt = degrade.DeviceLaneRuntime(
        _cfg(failure_threshold=10, launch_timeout_s=5.0), clock=Clock(),
        registry=Registry("t"))
    release = threading.Event()

    def dead_compiler():
        with degrade.compiling():
            release.wait(5.0)
        return np.array([False])

    out = rt.run("site", dead_compiler, host_fn=lambda: np.array([True]))
    release.set()
    assert out[0] and _timeouts(rt) == 1


def test_compiling_off_the_lane_worker_is_a_noop():
    with degrade.compiling():        # prewarm, a direct kernel call
        with degrade.compiling():
            pass


def test_launch_kernel_compiles_ahead_once_and_records_it():
    import jax
    import jax.numpy as jnp

    from tendermint_tpu.ops import ed25519 as edops

    traces = []

    @jax.jit
    def kernel(x):
        traces.append(1)
        return x + 1

    x = jnp.arange(8)
    edops._compile_tls.s = 0.0
    assert int(edops.launch_kernel(kernel, x)[0]) == 1
    assert traces == [1] and edops._compile_tls.s > 0.0
    spent = edops._compile_tls.s
    assert int(edops.launch_kernel(kernel, x)[7]) == 8
    assert traces == [1] and edops._compile_tls.s == spent   # warm: no-op
    # a new shape is a new compile; a plain callable is just called
    edops.launch_kernel(kernel, jnp.arange(16))
    assert traces == [1, 1] and edops._compile_tls.s > spent
    assert edops.launch_kernel(lambda a, k=0: a + k, 1, k=2) == 3
    edops._compile_tls.s = 0.0
