"""Device-lane degradation runtime: the resilience layer between the
batch verifier's routing policy (crypto/batch.py) and the accelerator.

The TPU lane is the consensus hot path's fast plane, but the device is
the least reliable component in the node: the backend may fail to
initialize, a launch may wedge (runtime fault) or raise, and a flaky
device must never stall or kill consensus.
This module implements the degradation ladder

    device -> [launch timeout / raise -> host re-verify, failure counted]
           -> breaker OPEN (everything host-side)
           -> half-open probe with exponential backoff + jitter
           -> re-close on a successful launch

with three guarantees the callers rely on:

  1. exact bitmap semantics: every fallback re-verifies the SAME triples
     on the host OpenSSL path, so callers observe the identical
     per-triple bitmap whether the device worked, timed out, raised, or
     the breaker was open.
  2. bounded wall clock: a launch that misses its deadline is abandoned
     (its worker is quarantined; a fresh lane thread takes over) and the
     batch is re-verified host-side immediately.  The deadline
     (launch_timeout_s) bounds device work only.  The one-time trace +
     compile of a kernel shape the process has not launched before runs
     ahead of the launch on the lane worker and stops the deadline's
     clock (compiling(), PR 21): a caller whose launch needs a new
     shape, or queues behind one that does — the CONSENSUS class
     included — waits that compile out with no host answer, each
     compile bounded by COMPILE_TIMEOUT_S.  The bound per launch is
     therefore launch_timeout_s plus COMPILE_TIMEOUT_S for every new
     shape compiled ahead of it; measured on the v5e, one ladder bucket
     is 75-88 s with an empty persistent cache and 37-47 s with a warm
     one (PERF.md "Chip bring-up").  Before PR 21 such a launch missed
     the 60 s deadline, was host-verified and counted as a failure, and
     three of them opened the breaker.  Nothing warms the buckets at
     node start yet (ROADMAP Speed 2).
  3. no cached doom: the old `_backend_ok` one-shot probe cached a
     transient init failure forever; backend probing here re-evaluates
     with exponential backoff, so a backend that comes back is found.

Observability: breaker transitions fire listener callbacks (node.py and
the consensus receive-loop coalescer log them) and every launch/failure/
fallback/probe increments libs/metrics counters.  Chaos tests force each
failure class deterministically through libs/fail.py injection sites
(see docs/adr/adr-010-device-lane-degradation.md).
"""
from __future__ import annotations

import concurrent.futures as _cf
import os
import queue as _queue
import random
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Callable, List, Optional

import numpy as np

from tendermint_tpu.libs import fail
from tendermint_tpu.libs import slo
from tendermint_tpu.libs import trace

# breaker states (rendered into the tendermint_crypto_breaker_state
# gauge as 0 / 0.5 / 1)
CLOSED = "closed"
HALF_OPEN = "half_open"
OPEN = "open"

_STATE_GAUGE = {CLOSED: 0.0, HALF_OPEN: 0.5, OPEN: 1.0}


class DeviceLaneError(RuntimeError):
    """A device launch failed (raise, timeout, or integrity mismatch)."""


@dataclass
class DegradeConfig:
    """Knobs for the resilience runtime.  Env-overridable so operators
    can tune a deployed node without code changes."""
    failure_threshold: int = 3     # consecutive failures that open
    # per-launch wall clock; the one-time trace + compile of a new
    # kernel shape is excluded (compiling() below, COMPILE_TIMEOUT_S)
    launch_timeout_s: float = 60.0
    backoff_base_s: float = 1.0    # first re-probe delay after opening
    backoff_max_s: float = 120.0
    backoff_jitter: float = 0.2    # +/- fraction applied to each delay
    spot_check: bool = True        # host-re-verify one lane per launch

    @classmethod
    def from_env(cls) -> "DegradeConfig":
        c = cls()
        env = os.environ.get
        c.failure_threshold = int(env("TM_TPU_BREAKER_THRESHOLD",
                                      c.failure_threshold))
        c.launch_timeout_s = float(env("TM_TPU_DEVICE_TIMEOUT_S",
                                       c.launch_timeout_s))
        c.backoff_base_s = float(env("TM_TPU_BREAKER_BACKOFF_S",
                                     c.backoff_base_s))
        c.backoff_max_s = float(env("TM_TPU_BREAKER_BACKOFF_MAX_S",
                                    c.backoff_max_s))
        c.spot_check = env("TM_TPU_SPOT_CHECK", "1") != "0"
        return c


class CircuitBreaker:
    """Consecutive-failure circuit breaker with half-open probing.

    CLOSED: launches flow.  After `failure_threshold` consecutive
    failures the breaker OPENs: try_acquire() denies everything until
    the backoff deadline, then grants exactly ONE caller a HALF_OPEN
    trial.  A successful trial re-closes (and resets the backoff); a
    failed trial re-opens with the delay doubled (capped, jittered).

    Thread-safe.  `clock` is injectable so tests drive the backoff
    schedule deterministically."""

    def __init__(self, cfg: Optional[DegradeConfig] = None,
                 clock: Callable[[], float] = time.monotonic,
                 metrics=None):
        self.cfg = cfg or DegradeConfig.from_env()
        self._clock = clock
        self._lock = threading.Lock()
        self._state = CLOSED
        self._consecutive = 0
        self._backoff = self.cfg.backoff_base_s
        self._probe_at = 0.0
        self._listeners: List[Callable[[str, str, str], None]] = []
        self._metrics = metrics
        self.opened_total = 0

    # -- observation -------------------------------------------------------

    @property
    def state(self) -> str:
        with self._lock:
            return self._state

    def add_listener(self, fn: Callable[[str, str, str], None]):
        """fn(old_state, new_state, reason) on every transition; returns
        an unsubscribe callable (listeners are process-global, so every
        subscriber — node, consensus loop, tests — must detach on
        stop)."""
        with self._lock:
            self._listeners.append(fn)

        def _unsub():
            with self._lock:
                if fn in self._listeners:
                    self._listeners.remove(fn)
        return _unsub

    def _transition(self, new: str, reason: str):
        # lock held by caller: mutate state only.  Metrics, the trace
        # instant AND the listener callbacks all run in the returned
        # closure, which every caller invokes AFTER releasing _lock —
        # publishing takes the metric/trace leaf locks and listener
        # callbacks are arbitrary subscriber code (node logging), none
        # of which belongs under the breaker lock (tmlint TM201/TM202
        # discipline; callers invoke the closure before returning, so
        # the gauge is current by the time any caller observes the
        # transition).
        old, self._state = self._state, new
        if new == OPEN:
            self.opened_total += 1
        listeners = list(self._listeners)

        def _notify():
            if self._metrics is not None:
                # gauge publishes the CURRENT state, not this
                # transition's: two racing transitions may run their
                # closures out of order (A: ->OPEN preempted, B:
                # ->HALF_OPEN publishes, A resumes) and a stale `new`
                # would leave the gauge wrong until the next
                # transition.  The counter is commutative, so labeling
                # it with this transition's target is exact regardless
                # of closure order.
                self._metrics.breaker_state.set(
                    _STATE_GAUGE[self.state])
                self._metrics.breaker_transitions.inc(to=new)
            trace.instant("breaker.transition", to=new, reason=reason,
                          **{"from": old})
            for fn in listeners:
                fn(old, new, reason)
        return _notify

    # -- the gate ----------------------------------------------------------

    def try_acquire(self) -> bool:
        """May this launch go to the device?  Every grant MUST be settled
        by exactly one record_success/record_failure."""
        notify = None
        try:
            with self._lock:
                if self._state == CLOSED:
                    return True
                if self._state == OPEN and \
                        self._clock() >= self._probe_at:
                    notify = self._transition(HALF_OPEN, "probe due")
                    return True
                return False  # OPEN before deadline, or trial in flight
        finally:
            if notify is not None:
                notify()

    def record_success(self):
        notify = None
        with self._lock:
            self._consecutive = 0
            if self._state != CLOSED:
                self._backoff = self.cfg.backoff_base_s
                notify = self._transition(CLOSED, "device launch ok")
        if notify is not None:
            notify()

    def record_failure(self, reason: str):
        notify = None
        with self._lock:
            self._consecutive += 1
            reopen = self._state == HALF_OPEN
            if reopen or (self._state == CLOSED and
                          self._consecutive >= self.cfg.failure_threshold):
                if reopen:  # failed probe: back off harder
                    self._backoff = min(self._backoff * 2,
                                        self.cfg.backoff_max_s)
                delay = self._backoff
                if self.cfg.backoff_jitter:
                    delay *= 1 + self.cfg.backoff_jitter * \
                        random.uniform(-1.0, 1.0)
                self._probe_at = self._clock() + delay
                notify = self._transition(OPEN, reason)
        if notify is not None:
            notify()


# A kernel shape the process has not launched before is traced and
# compiled ahead of its first launch (ops/ed25519.launch_kernel): tens of
# seconds of host work per lane bucket for the unrolled ladder, before
# the persistent cache can even be consulted, and before the device is
# touched.  The launch deadline bounds device work, so that time runs on
# its own bound — a constant, not a knob: a compiler that takes this
# long is as dead to consensus as a wedged device.  3.4x the slowest
# cold compile of a default path measured on the v5e (88 s, PERF.md).
COMPILE_TIMEOUT_S = 300.0
_COMPILE_POLL_S = 0.25

_lane_tls = threading.local()


class _CompileClock:
    """Seconds a runtime's lane worker has spent inside compiling().
    One per runtime, not per launch: the lane worker is one thread, so
    a compile delays every launch queued behind it just the same."""

    def __init__(self):
        self._lock = threading.Lock()
        self._total = 0.0
        self._since: Optional[float] = None

    def begin(self) -> bool:
        """False when a compile is already running (a nested
        compiling(): the outer one owns the interval)."""
        with self._lock:
            if self._since is not None:
                return False
            self._since = time.monotonic()
            return True

    def end(self):
        with self._lock:
            self._total += time.monotonic() - self._since
            self._since = None

    def read(self) -> "tuple[float, float]":
        """(seconds compiled so far, seconds the running compile has
        taken — 0.0 when none is running)."""
        with self._lock:
            cur = 0.0 if self._since is None \
                else time.monotonic() - self._since
            return self._total + cur, cur


@contextmanager
def compiling():
    """Stop the launch deadline's clock for the enclosed one-time trace
    + compile.  Only means something on a lane worker thread (submit()
    arms it); anywhere else — prewarm, a direct kernel call — nobody is
    waiting on a deadline and this is a no-op."""
    clk = getattr(_lane_tls, "clock", None)
    if clk is None or not clk.begin():
        yield
        return
    try:
        yield
    finally:
        clk.end()


class _LaneWorker:
    """Single-thread task runner for device launches — the
    ThreadPoolExecutor(max_workers=1) shape, but with a DAEMON thread.
    Python 3.9+ executor threads are non-daemon and an idle lane worker
    would outlive every test (and show up in the conftest thread-leak
    guard) and block interpreter shutdown behind a wedged device call;
    the lane worker must never keep the process alive."""

    def __init__(self, name: str = "batch-device-lane"):
        self._q: "_queue.SimpleQueue" = _queue.SimpleQueue()
        self._closed = False
        self._thread = threading.Thread(target=self._run, name=name,
                                        daemon=True)
        self._thread.start()

    def submit(self, fn: Callable) -> _cf.Future:
        if self._closed:
            raise RuntimeError("lane worker is shut down")
        f: _cf.Future = _cf.Future()
        self._q.put((fn, f))
        return f

    def _run(self):
        while True:
            item = self._q.get()
            if item is None:
                return
            fn, f = item
            if not f.set_running_or_notify_cancel():
                continue
            # when the worker took the task up: a lane queued behind
            # another's launch began HERE, not at its submit (the lane
            # report's wall brackets, crypto/batch._device_lane_wall)
            f.started_at = time.monotonic()
            try:
                f.set_result(fn())
            except BaseException as e:  # noqa: BLE001 - future carries it
                f.set_exception(e)

    def shutdown(self, wait: bool = False):
        """Same contract as executor.shutdown(wait=False): stop accepting
        work, wake the worker.  A wedged in-flight call keeps its (daemon)
        thread; quarantine relies on exactly that — abandon, don't join."""
        self._closed = True
        self._q.put(None)
        if wait:
            self._thread.join(timeout=2.0)


class DeviceLaneRuntime:
    """Owns the device-lane worker pool, the circuit breaker, and the
    backend probe.  crypto/batch.py routes every device dispatch through
    submit()/collect() (overlapped lanes) or run() (synchronous)."""

    def __init__(self, cfg: Optional[DegradeConfig] = None,
                 clock: Callable[[], float] = time.monotonic,
                 registry=None):
        from tendermint_tpu.libs.metrics import CryptoMetrics

        self.cfg = cfg or DegradeConfig.from_env()
        self.metrics = CryptoMetrics(registry)
        self.breaker = CircuitBreaker(self.cfg, clock=clock,
                                      metrics=self.metrics)
        self._clock = clock
        self._compile_clock = _CompileClock()
        self._pool_lock = threading.Lock()
        self._pool: Optional[_LaneWorker] = None
        # backend probe state: None = never probed, True = accelerator,
        # False-stable = plain-CPU backend (a fixed property of the
        # process), False-transient = init raised, re-probe after backoff
        self._backend_lock = threading.Lock()
        self._backend: Optional[bool] = None
        self._backend_stable = False
        self._backend_next_probe = 0.0
        self._backend_backoff = self.cfg.backoff_base_s

    # -- worker pool -------------------------------------------------------

    def _get_pool(self) -> _LaneWorker:
        with self._pool_lock:
            if self._pool is None:
                self._pool = _LaneWorker()
            return self._pool

    def _quarantine_pool(self):
        """A launch missed its deadline: the worker may be wedged on the
        device, so later launches must not queue behind it.  Abandon the
        executor (its thread finishes or wedges on its own) and lazily
        build a fresh one."""
        with self._pool_lock:
            pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=False)

    def close(self):
        """Shut down the lane worker (configure()/reset() call this on
        the runtime they replace so tests don't accumulate idle lane
        threads)."""
        with self._pool_lock:
            pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=True)

    # -- backend probing (replaces batch.py's one-shot _backend_ok) --------

    def backend_available(self) -> bool:
        """True once jax reports a non-CPU default backend.  An init
        FAILURE is treated as transient: re-probed after an exponential
        backoff instead of being cached forever."""
        with self._backend_lock:
            if self._backend is not None and \
                    (self._backend or self._backend_stable):
                return self._backend
            if self._backend is not None and \
                    self._clock() < self._backend_next_probe:
                return False
        try:
            import jax
            ok = jax.default_backend() != "cpu"
            with self._backend_lock:
                self._backend = ok
                self._backend_stable = True   # a live backend is fixed
                self._backend_backoff = self.cfg.backoff_base_s
            self.metrics.backend_probes.inc(
                result="accelerator" if ok else "cpu")
            # a successful probe is the one moment the device topology
            # can have changed under a latched mesh plane (the backend
            # came up after the plane's first look) — let the plane
            # rebuild itself against the live device list (ADR-027)
            try:
                from tendermint_tpu.parallel import sharding
                sharding.invalidate_on_topology_change()
            except Exception:  # noqa: BLE001 - plane upkeep must not
                pass            # fail a backend probe
            return ok
        except Exception:
            with self._backend_lock:
                self._backend = False
                self._backend_stable = False
                self._backend_next_probe = \
                    self._clock() + self._backend_backoff
                self._backend_backoff = min(
                    self._backend_backoff * 2, self.cfg.backoff_max_s)
            self.metrics.backend_probes.inc(result="error")
            return False

    # -- launch plumbing ---------------------------------------------------

    def try_acquire(self) -> bool:
        return self.breaker.try_acquire()

    def submit(self, site: str, fn: Callable, *args) -> _cf.Future:
        """Dispatch a device launch on the lane worker.  The fail-point
        injection runs INSIDE the worker so `latency:` modes are subject
        to the launch deadline exactly like real device stalls.  Caller
        must settle via collect() — submit itself never raises (a
        dispatch failure comes back as a failed future), so an acquired
        breaker grant can always be settled."""
        self.metrics.device_launches.inc(site=site)
        # the launch runs on the lane worker thread: capture the caller's
        # span id HERE so the worker's span links into the caller's tree
        # (the thread-local stack doesn't cross the pool boundary)
        parent = trace.current_id()
        submitted = time.monotonic()

        def _launch():
            _lane_tls.clock = self._compile_clock
            # queued_ns: submit to the lane worker taking the launch up
            # (the started_at it stamps a moment before this runs): the
            # wait behind another lane's launch, on no other record
            with trace.span("device.launch", parent=parent, site=site,
                            queued_ns=int(
                                (time.monotonic() - submitted) * 1e9)):
                fail.inject(site)
                return fn(*args)
        try:
            return self._get_pool().submit(_launch)
        except Exception as e:  # noqa: BLE001 - e.g. pool at shutdown
            f = _cf.Future()
            f.set_exception(e)
            return f

    def _await(self, fut: _cf.Future):
        """fut.result() under the launch deadline, its clock stopped
        while the lane worker is inside compiling() — for this launch
        or one queued ahead of it.  A single compile is bounded by
        COMPILE_TIMEOUT_S instead.  Wall-clock time throughout, like
        Future.result's own timeout (self._clock drives the breaker's
        backoff schedule and may be a test's fake)."""
        t0 = time.monotonic()
        compiled0, _ = self._compile_clock.read()
        while True:
            compiled, running = self._compile_clock.read()
            left = self.cfg.launch_timeout_s - \
                (time.monotonic() - t0 - (compiled - compiled0))
            if running > COMPILE_TIMEOUT_S or (not running and left <= 0):
                raise _cf.TimeoutError()
            # short slices even when no compile is running: one may
            # start a moment after this look at the clock
            try:
                return fut.result(timeout=_COMPILE_POLL_S if running
                                  else min(left, _COMPILE_POLL_S))
            except (_cf.TimeoutError, TimeoutError):
                if fut.done():
                    raise

    def collect(self, site: str, fut: _cf.Future,
                host_fn: Callable[[], np.ndarray],
                spot_check: Optional[Callable[[np.ndarray], bool]] = None,
                ) -> np.ndarray:
        """Settle a launch: bounded wait, integrity check, breaker
        bookkeeping — and on ANY device failure re-verify the batch
        through host_fn so the caller's bitmap is exact regardless."""
        with trace.span("device.collect", site=site) as sp:
            # launch-seconds bracket via the Histogram.time helper;
            # observed manually (success only — a degraded launch's
            # wall belongs to the failure counters, not this histogram)
            launch_timer = self.metrics.device_launch_seconds.time(
                clock=self._clock, site=site)
            reason = None
            try:
                out = self._await(fut)
                out = fail.corrupt_bitmap(site, out)
                if spot_check is not None and self.cfg.spot_check \
                        and not spot_check(np.asarray(out)):
                    raise DeviceLaneError(
                        f"{site}: device bitmap disagrees with host "
                        f"spot check")
            except (_cf.TimeoutError, TimeoutError):
                # on 3.11+ futures.TimeoutError IS builtin TimeoutError,
                # so a TimeoutError raised by the device fn itself (e.g.
                # a timeout inside the runtime) lands here too: only a
                # future that is genuinely still running means the WAIT
                # timed out and the worker may be wedged — anything else
                # is a device raise
                if fut.done():
                    reason = "raise"
                else:
                    reason = "timeout"
                    self._quarantine_pool()
                    fut.cancel()
            except Exception as e:  # noqa: BLE001 - any fault degrades
                reason = "integrity" if isinstance(e, DeviceLaneError) \
                    else "raise"
            if reason is None:
                launch_timer.observe()
                self.breaker.record_success()
                sp.add(outcome="ok")
                return np.asarray(out)
            self.metrics.device_failures.inc(site=site, reason=reason)
            self.breaker.record_failure(f"{site}: {reason}")
            sp.add(outcome=reason)
            return self.host_fallback(site, reason, host_fn)

    def host_fallback(self, site: str, reason: str,
                      host_fn: Callable[[], np.ndarray]) -> np.ndarray:
        self.metrics.host_fallbacks.inc(site=site, reason=reason)
        with trace.span("device.host_fallback", site=site, reason=reason):
            return host_fn()

    def run(self, site: str, device_fn: Callable[[], np.ndarray],
            host_fn: Callable[[], np.ndarray],
            spot_check: Optional[Callable[[np.ndarray], bool]] = None,
            ) -> np.ndarray:
        """Synchronous wrapper: breaker gate + launch + settle.  The
        whole-commit path (crypto/batch.verify_sigs_bulk) uses this; the
        mixed-batch path uses submit()/collect() to overlap the device
        lane with its host lanes."""
        if not self.try_acquire():
            return self.host_fallback(site, "breaker_open", host_fn)
        return self.collect(site, self.submit(site, device_fn), host_fn,
                            spot_check=spot_check)


# ---------------------------------------------------------------------------
# process-global runtime (one device per process, like the lane pool it
# replaces); tests swap it out via configure()/reset()
# ---------------------------------------------------------------------------

_runtime: Optional[DeviceLaneRuntime] = None
_runtime_lock = threading.Lock()


def runtime() -> DeviceLaneRuntime:
    global _runtime
    with _runtime_lock:
        if _runtime is None:
            _runtime = DeviceLaneRuntime()
        return _runtime


def runtime_if_installed() -> Optional[DeviceLaneRuntime]:
    """The runtime IF one already exists — never constructs.  The
    best-effort metric bridges below use this so publishing from a
    sub-threshold path (which BatchVerifier deliberately keeps
    runtime-free: the breaker lock is shared across reactor threads)
    can never build the runtime just for a gauge."""
    with _runtime_lock:
        return _runtime


def configure(cfg: Optional[DegradeConfig] = None,
              clock: Callable[[], float] = time.monotonic,
              registry=None) -> DeviceLaneRuntime:
    """Install a fresh runtime (tests: deterministic clock / private
    metrics registry; node assembly: config-derived thresholds)."""
    global _runtime
    new = DeviceLaneRuntime(cfg, clock=clock, registry=registry)
    with _runtime_lock:
        old, _runtime = _runtime, new
    if old is not None:
        old.close()
    # return the runtime THIS call installed — re-reading the global
    # here could hand back None (concurrent reset) or another call's
    # runtime (concurrent configure)
    return new


def reset():
    """Drop the global runtime (next access rebuilds from env)."""
    global _runtime
    with _runtime_lock:
        old, _runtime = _runtime, None
    if old is not None:
        old.close()


def publish_route(path, outcome, n=None, nb=None, compile_s=None):
    """The ONE bridge from a dispatch-route decision (ops/ed25519
    _record_launch and the routes' declines) into CryptoMetrics: route
    counter at set time (labeled by outcome, so a declined route is
    never mistaken for one that launched), lane occupancy, and
    the first-launch compile split.  Swallows everything —
    observability must never break verification."""
    try:
        m = runtime().metrics
        m.msm_route.inc(path=str(path), outcome=str(outcome))
        if nb and n is not None:  # never fabricate a perfect ratio
            m.batch_occupancy.set(n / nb)
        if compile_s is not None:
            m.device_compile_seconds.observe(compile_s, site=str(path))
    except Exception:  # noqa: BLE001 - metrics are best-effort here
        pass


def publish_compile(site, compile_s):
    """A trace + compile that belongs to no launch record (the comb
    table build's), into the same crypto_device_compile_seconds.
    Swallows everything, like publish_route."""
    try:
        runtime().metrics.device_compile_seconds.observe(
            compile_s, site=str(site))
    except Exception:  # noqa: BLE001 - metrics are best-effort here
        pass


def publish_host_pool(depth=None, tasks=None):
    """Bridge from the host-lane pool (crypto/lanepool.py, ADR-015)
    into CryptoMetrics: admitted-task depth gauge and per-kind task
    counters — ``tasks`` is an iterable of (kind, outcome, count).
    Swallows everything, same contract as publish_route: the pool must
    keep verifying even when metrics are broken or mid-reconfigure.
    No-op until a runtime exists (runtime_if_installed): the pool also
    serves sub-threshold batches that must never construct one."""
    try:
        rt = runtime_if_installed()
        if rt is None:
            return
        m = rt.metrics
        if depth is not None:
            m.host_pool_depth.set(float(depth))
        for kind, outcome, count in tasks or ():
            if count:
                m.host_pool_tasks.inc(count, kind=kind, outcome=outcome)
    except Exception:  # noqa: BLE001 - metrics are best-effort here
        pass


def publish_lane_overlap(ratio):
    """Bridge for the per-batch lane-overlap ratio (crypto/batch.py and
    crypto/scheduler.py publish it after a multi-lane window settles:
    1 - wall/sum(lane walls); 0 = fully serial lanes).  Swallowing and
    non-constructing, see publish_host_pool."""
    try:
        rt = runtime_if_installed()
        if rt is not None:
            rt.metrics.lane_overlap.set(float(ratio))
    except Exception:  # noqa: BLE001 - metrics are best-effort here
        pass


def publish_request_latency(priority: str, path: str, e2e_s: float):
    """Bridge for the direct verify path's end-to-end latency
    (crypto/batch.BatchVerifier.verify stamps entry and publishes at
    return; the scheduler publishes its own richer lifecycle through
    its metrics handle).  Swallowing, and it reads the runtime global
    WITHOUT the install lock: the tiny-batch direct path is the
    consensus vote-window hot path, deliberately runtime-free, and
    publishing one gauge must not serialize every reactor thread on
    the rank-5 install lock (a plain global read is atomic in
    CPython).  The SLO estimator is fed regardless — its disabled
    path is a guaranteed sub-microsecond no-op."""
    try:
        slo.observe(priority, e2e_s)
        rt = _runtime
        if rt is not None:
            rt.metrics.verify_e2e_latency.observe(
                e2e_s, priority=priority, path=path)
    except Exception:  # noqa: BLE001 - metrics are best-effort here
        pass


def publish_table_cache(bytes_=None, hit=None, evicted=None):
    """Bridge from the comb table cache (ops/ed25519, ADR-013) into
    CryptoMetrics: resident bytes gauge, hit/eviction counters.  Comb
    LAUNCHES need no bridge of their own — they dispatch through the
    same _record_launch/publish_route seam (path=comb), under the same
    breaker/timeout/host-fallback lane as every other device launch.
    Swallows everything, same contract as publish_route."""
    try:
        m = runtime().metrics
        if bytes_ is not None:
            m.table_cache_bytes.set(float(bytes_))
        if hit:
            m.table_hits.inc()
        if evicted:
            m.table_evictions.inc()
    except Exception:  # noqa: BLE001 - metrics are best-effort here
        pass
