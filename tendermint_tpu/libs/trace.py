"""Flight recorder: low-overhead span tracing for the vote -> verify ->
commit hot path (docs/adr/adr-011-flight-recorder.md).

The node has counters (libs/metrics.py) and a profiler (libs/pprof.py),
but neither answers "where did THIS batch spend its time, and which path
did it take" — the question round 5's unmeasured perf thesis needed.
This module is the third observability surface: a process-global tracer
holding a bounded ring buffer of spans (start + wall duration + the
thread's CPU time, parent linkage, key=value attrs), exported in the
Chrome-trace / Perfetto JSON event format so any trace viewer renders
the timeline.

Design constraints, in order:

  1. ON by default, like its siblings (crypto/devobs, consensus/
     observatory, p2p/netobs): a recorder that is off when the incident
     happens has recorded nothing.  That is affordable only while a
     span stands at a batch, launch, block or window boundary and NEVER
     per vote or per signature: an enabled span costs ~5 us (PERF.md
     section 6 has the measured cost per benchmark cell), so 300 of
     them in a 43 ms height would be 3% of it.  ``TM_TPU_TRACE=0``
     turns it off, and every call site goes through ``span()`` /
     ``instant()`` unconditionally, so the disabled path must cost less
     than a microsecond (one enabled check, one singleton return — no
     allocation beyond the kwargs dict, no locks, no clock reads).
  2. Bounded memory.  A ring buffer (default 8192 finished spans)
     overwrites the oldest records; a wedged exporter or a forgotten
     enable can never OOM the node.  This is why it is a flight
     recorder, not a log: the buffer always holds the most recent
     window, which is exactly what a post-incident look needs.
  3. Causal linkage across threads.  Spans nest per-thread via a
     thread-local stack; cross-thread handoffs (the device-lane worker,
     crypto/degrade.py) pass the parent span id explicitly, so the
     coalesce -> launch -> verdict chain is one connected tree even
     though it crosses the lane-worker boundary.

One clock: spans stamp ``time.perf_counter_ns()``, the clock of every
other wall bracket in the program and of the benchmark's own rows, so a
span can be laid beside them without a conversion.  Beside its wall
duration a span keeps ``cpu_ns``, the CPU time of ITS thread between
enter and exit (``time.thread_time_ns()``): wall minus CPU is the time
the thread was off the processor, waiting for the GIL, a lock, a queue,
the device or the disk.  That holds where the clock is cheap to read
(0.3 us on a plain Linux host).  Under a sandboxed kernel it is not: on
the benchmark's hosts one read measured 6 us alone, more in a threaded
process, and moved in ticks of 10 ms, which made 14 spans cost 2% of a
43 ms height for a number only a sum over hundreds of ms could use.  So
the module times the clock once (``_cheap_cpu_clock``), and where a read
costs over 2 us spans go without: ``cpu_ns`` is None there.  And while
jax is loaded every span also enters
a ``jax.profiler.TraceAnnotation`` of its own name, so inside a profiler
session it lies in the profile, on the profile's clock, beside the
device's operations.  This module never imports jax: it takes
``jax.profiler`` from ``sys.modules`` once something else has loaded it.

Switches: ``TM_TPU_TRACE=0`` in the environment or ``trace.disable()``
turn it off, ``trace.enable()`` on again (capacity override:
``TM_TPU_TRACE_CAPACITY``).  Read it back three ways:
``GET /debug/trace?since=<seq>`` on the pprof listener (libs/pprof.py),
the ``debug-trace`` CLI (cmd/__main__.py), or the per-config artifact
bench.py writes next to its JSON line.
"""
from __future__ import annotations

import collections
import itertools
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional


_UNSET = object()  # sentinel: "inherit parent from the thread's stack"

# ---------------------------------------------------------------------------
# the span-name registry (tmlint TM306).  Every literal name passed to
# trace.span()/trace.timed()/trace.instant() must appear here: trace
# consumers (the benchmark's per-layer readers under perfbench/layers,
# the debug-trace CLI, the acceptance tests walking span trees) key on
# these strings, so an
# unregistered name is either a typo or an undocumented contract.
# Grouped by subsystem; keep alphabetical within a group.
# ---------------------------------------------------------------------------

KNOWN_SPANS = frozenset({
    # crypto/batch.py — the BatchVerifier coalesce window
    "batch.host_lane", "batch.verdict", "batch.verify",
    # blocksync/replay.py — the root of one replayed window (path =
    # pipelined / coalesced / strict) and the block store's share of
    # each apply
    "blocksync.replay_window", "store.save_block",
    # bench.py
    "bench.host_baseline", "bench.pass", "bench.propose",
    # crypto/degrade.py — breaker + device lane lifecycle
    "breaker.transition", "device.collect", "device.host_fallback",
    "device.launch",
    # libs/control.py — adaptive control plane decision periods
    # (ADR-023)
    "control.decide",
    # crypto/lanepool.py — sharded native C host verify (ADR-015)
    "lanepool.verify",
    # light/service.py — the light serving plane (ADR-026):
    # light.serve wraps one drained worker batch, light.coalesce wraps
    # one SHARED certificate verification (waiters = how many requests
    # it settles)
    "light.coalesce", "light.serve",
    # light/verifier.py — the root of one stateless header verification
    # (attr `outcome`: ok / cant_trust / error)
    "light.verify",
    # light/client.py + light/store.py — the light client proper:
    # light.client.verify is the root of one request (attrs target,
    # anchor, hops, refused_skips, fetched, saved), light.fetch one
    # block from the primary, light.detect the witness cross-check, and
    # the store's load / save (attr bytes) / prune (attr deleted); inside
    # a save its encode and inside a load its decode, one a block (attr
    # record: columns / generic / legacy, light/record.py)
    "light.client.verify", "light.detect", "light.fetch",
    "light.store.decode", "light.store.encode",
    "light.store.load", "light.store.prune", "light.store.save",
    # types/validator_set.py + light/verifier.py — the host work around
    # a commit's one batched launch, each ONE span per call: the set's
    # merkle hash (attr `memo`: answered by the memo on the validators
    # list, nothing computed), the commit's structural checks
    # (Commit.validate_basic itself, whoever calls it), the
    # trusting path's match by address, the >2/3 tally, and the filter,
    # sign-bytes + pubkey and signature rows up to the call of
    # verify_sigs_bulk; commit.columns (types/commit._columns, attrs
    # rows, fields) is one read of a commit's rows into numpy columns,
    # inside whichever of the others asked for it: what is left of the
    # per-row Python
    "commit.collect", "commit.columns", "commit.match", "commit.prefix",
    "commit.validate_basic", "valset.hash",
    # networks/ — the in-process multi-node harness (ADR-019)
    "harness.scenario", "harness.step", "vnet.deliver",
    # p2p/netobs.py — the gossip observatory's deferred drain (ADR-025)
    "netobs.drain",
    # mempool/ingress.py — overload-safe admission (ADR-018)
    "ingress.admit", "ingress.batch", "ingress.checktx",
    "ingress.recheck",
    # consensus/state.py
    "consensus.finalize_commit", "consensus.preverify",
    "consensus.quorum", "consensus.step", "consensus.vote",
    # ops/ — kernel routing: comb.resolve is verify_batch looking the
    # batch's keys up in the comb's tables ahead of the launch bracket
    # (attrs n, outcome: resident / built / declined / unknown, and
    # early: true when the batch left by the bound on its distinct keys,
    # ops/ed25519._comb_over_cap, ahead of the distinct-key sort)
    "comb.prewarm_failed", "comb.resolve", "ops.ed25519.verify_batch",
    "table_build",
    # ops/secp.py, ops/sr25519.py — the other two schemes' device lanes,
    # ONE span a launch (attrs n, nb, path from its launch record), and
    # inside it the lane's host staging: challenges, range / encoding
    # screens, limb and digit packing (attr n)
    "ops.secp.verify_batch", "ops.sr25519.verify_batch",
    "secp.stage", "sr25519.stage",
    # state/pipeline.py — the block application pipeline (ADR-017)
    "pipeline.apply", "pipeline.commit", "pipeline.drain",
    "pipeline.stage", "pipeline.wait_staged",
    # crypto/scheduler.py — the VerifyScheduler pipeline
    "sched.coalesce", "sched.deadline_miss", "sched.host_lane",
    "sched.launch", "sched.resolve", "sched.shed", "sched.submit",
    # state/execution.py — the budgeted propose decomposition
    # (ADR-024) plus block apply
    "propose.assemble", "propose.prepare", "propose.reap",
    "propose.split",
    "state.apply_block", "state.validate_block",
    # statesync/ — the fast-join fetch/verify/apply pipeline and the
    # bounded chunk server (ADR-022)
    "statesync.fetch", "statesync.apply", "statesync.serve",
})


class _NoopSpan:
    """The disabled path: one shared instance, every method a no-op."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def add(self, **attrs):
        return self

    span_id = None


_NOOP = _NoopSpan()


class _Stopwatch(_NoopSpan):
    """timed() while the recorder is off: the clock pair, no record."""

    __slots__ = ("_t0", "dur_ns")

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.dur_ns = time.perf_counter_ns() - self._t0
        return False


_CPU_READ_BUDGET_NS = 2000


def _cheap_cpu_clock():
    """time.thread_time_ns where one read of it costs under the budget
    (best of five, so a loaded host does not misjudge it), else None."""
    best = None
    for _ in range(5):
        t0 = time.perf_counter_ns()
        time.thread_time_ns()
        cost = time.perf_counter_ns() - t0
        best = cost if best is None else min(best, cost)
    return time.thread_time_ns if best < _CPU_READ_BUDGET_NS else None


_CPU_CLOCK = _cheap_cpu_clock()

_annotation = None  # jax.profiler.TraceAnnotation, once jax is loaded


def _profile_annotation(name: str):
    """An entered jax.profiler.TraceAnnotation of `name`, or None while
    nothing in the process has imported jax (this module never does)."""
    global _annotation
    if _annotation is None:
        prof = sys.modules.get("jax.profiler")
        if prof is None:
            return None
        _annotation = prof.TraceAnnotation
    ann = _annotation(name)
    ann.__enter__()
    return ann


class _Span:
    """A live span.  Created only while the tracer is enabled; records
    itself into the ring on __exit__ (even if the tracer was disabled
    mid-span — the span was paid for, keep it).  After the exit
    `dur_ns` and `cpu_ns` hold what was recorded (`cpu_ns` None where
    the host's thread CPU clock is too dear to read, _cheap_cpu_clock)."""

    __slots__ = ("_tracer", "name", "attrs", "span_id", "parent_id",
                 "_t0", "_c0", "_ann", "_tid", "_tname", "dur_ns",
                 "cpu_ns")

    def __init__(self, tracer: "Tracer", name: str, parent, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self.parent_id = parent
        self.span_id = None

    def add(self, **attrs):
        self.attrs.update(attrs)
        return self

    def __enter__(self):
        tr = self._tracer
        self.span_id = next(tr._ids)
        t = threading.current_thread()
        self._tid = t.ident
        self._tname = t.name
        stack = tr._stack()
        if self.parent_id is _UNSET:
            self.parent_id = stack[-1].span_id if stack else None
        stack.append(self)
        self._ann = _profile_annotation(self.name)
        # the CPU bracket lies inside the wall bracket, so cpu_ns <=
        # dur_ns holds whatever the two reads themselves cost
        self._t0 = time.perf_counter_ns()
        self._c0 = _CPU_CLOCK() if _CPU_CLOCK is not None else None
        return self

    def __exit__(self, etype, evalue, tb):
        self.cpu_ns = time.thread_time_ns() - self._c0 \
            if self._c0 is not None else None
        self.dur_ns = time.perf_counter_ns() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        stack = self._tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:  # mis-nested exit: drop up to and incl. self
            del stack[stack.index(self):]
        if etype is not None:
            self.attrs["error"] = etype.__name__
        self._tracer._record(self.name, "X", self._t0, self.dur_ns,
                             self.cpu_ns, self._tid, self._tname,
                             self.span_id, self.parent_id, self.attrs)
        return False


class Tracer:
    def __init__(self, capacity: Optional[int] = None,
                 enabled: Optional[bool] = None):
        if enabled is None:
            enabled = os.environ.get("TM_TPU_TRACE", "") != "0"
        if capacity is None:  # env tunes the DEFAULT only — an explicit
            # constructor argument (private test tracers) always wins.
            # A malformed value falls back: the module is imported by
            # every hot-path module, so a bad env var must never keep
            # the node from starting
            try:
                capacity = int(os.environ.get("TM_TPU_TRACE_CAPACITY",
                                              8192))
            except (ValueError, TypeError):
                capacity = 8192
        capacity = max(1, capacity)
        self._enabled = enabled
        self._lock = threading.Lock()
        self._buf: "collections.deque" = collections.deque(maxlen=capacity)
        self._seq = 0
        self._dropped = 0          # spans lost to ring wraparound
        self._drop_counter = None  # lazy TraceMetrics handle
        self._ids = itertools.count(1)
        self._tls = threading.local()

    # -- state -------------------------------------------------------------

    @property
    def capacity(self) -> int:
        return self._buf.maxlen or 0

    def is_enabled(self) -> bool:
        return self._enabled

    def enable(self, capacity: Optional[int] = None):
        with self._lock:
            if capacity is not None and capacity != self._buf.maxlen:
                self._buf = collections.deque(self._buf, maxlen=capacity)
        self._enabled = True

    def disable(self):
        self._enabled = False

    def reset(self):
        """Drop buffered spans.  seq stays monotonic so `since` cursors
        held by pollers remain valid across a reset."""
        with self._lock:
            self._buf.clear()

    # -- recording ---------------------------------------------------------

    def _stack(self) -> List[_Span]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def span(self, name: str, parent=_UNSET, **attrs):
        """Context manager for a timed span.  `parent` overrides the
        thread-local nesting (pass a span id for cross-thread linkage;
        None for an explicit root)."""
        if not self._enabled:
            return _NOOP
        return _Span(self, name, parent, attrs)

    def timed(self, name: str, parent=_UNSET, **attrs):
        """A span whose `dur_ns` the caller reads after the exit, with
        the recorder on or off: for a site that feeds an operator
        metric from the same bracket, so that the metric and the span
        are one clock pair and can never disagree."""
        if not self._enabled:
            return _Stopwatch()
        return _Span(self, name, parent, attrs)

    def instant(self, name: str, parent=_UNSET, **attrs):
        """A zero-duration marker event (Chrome-trace ph="i");
        `parent` as for span()."""
        if not self._enabled:
            return
        t = threading.current_thread()
        if parent is _UNSET:
            stack = self._stack()
            parent = stack[-1].span_id if stack else None
        self._record(name, "i", time.perf_counter_ns(), 0, 0, t.ident,
                     t.name, next(self._ids), parent, attrs)

    def current(self):
        """The innermost live span on this thread (no-op span when
        tracing is disabled or no span is open) — call sites deeper in
        the stack attach attrs to it (e.g. the device route picked
        inside ops/)."""
        if not self._enabled:
            return _NOOP
        stack = self._stack()
        return stack[-1] if stack else _NOOP

    def current_id(self) -> Optional[int]:
        """Span id to hand a worker thread as explicit parent."""
        if not self._enabled:
            return None
        stack = self._stack()
        return stack[-1].span_id if stack else None

    def _record(self, name, ph, t0_ns, dur_ns, cpu_ns, tid, tname,
                span_id, parent_id, attrs):
        with self._lock:
            self._seq += 1
            wrapped = len(self._buf) == self._buf.maxlen
            if wrapped:
                self._dropped += 1
            self._buf.append({
                "seq": self._seq, "name": name, "ph": ph, "ts_ns": t0_ns,
                "dur_ns": dur_ns, "cpu_ns": cpu_ns, "tid": tid,
                "tname": tname,
                "id": span_id, "parent": parent_id, "attrs": attrs,
            })
        if wrapped:
            # counter inc AFTER releasing: the metric locks rank BELOW
            # the tracer lock (lockorder 80/84 < 90), so publishing
            # under self._lock would be a real inversion
            self._publish_drop()

    def _publish_drop(self):
        c = self._drop_counter
        if c is None:
            try:
                from tendermint_tpu.libs.metrics import TraceMetrics
                c = TraceMetrics().dropped_spans
            except Exception:  # noqa: BLE001 - observability of the
                c = False       # observer must never take down a span
            self._drop_counter = c
        if c is not False:
            try:
                c.inc()
            except Exception:  # noqa: BLE001
                pass

    def dropped(self) -> int:
        """Spans lost to ring wraparound since construction (a wrapped
        ring can no longer masquerade as a quiet system)."""
        with self._lock:
            return self._dropped

    # -- export ------------------------------------------------------------

    def snapshot(self, since: int = 0) -> List[Dict[str, Any]]:
        """Finished records with seq > since, oldest first (copies — the
        ring keeps mutating underneath)."""
        return self._snapshot(since)[0]

    def _snapshot(self, since: int):
        """(records, seq) read in ONE critical section: a poller's next
        `since` cursor must equal the seq of the newest record it was
        actually handed, or spans recorded between two separate lock
        acquisitions would be skipped forever."""
        with self._lock:
            return ([dict(r, attrs=dict(r["attrs"]))
                     for r in self._buf if r["seq"] > since], self._seq)

    def last_seq(self) -> int:
        with self._lock:
            return self._seq

    def chrome_trace(self, since: int = 0) -> Dict[str, Any]:
        """The buffer as a Chrome-trace / Perfetto JSON object
        (chrome://tracing, ui.perfetto.dev).  `last_seq` lets pollers
        fetch incrementally via ?since=."""
        pid = os.getpid()
        records, last = self._snapshot(since)
        events = []
        for r in records:
            args = dict(r["attrs"])
            args["id"] = r["id"]
            if r["parent"] is not None:
                args["parent"] = r["parent"]
            args["seq"] = r["seq"]
            if r["tname"]:
                args["thread"] = r["tname"]
            ev = {"name": r["name"], "ph": r["ph"], "pid": pid,
                  "tid": r["tid"], "ts": r["ts_ns"] / 1000.0, "args": args}
            if r["ph"] == "X":
                ev["dur"] = r["dur_ns"] / 1000.0
                if r["cpu_ns"] is not None:
                    args["cpu_us"] = r["cpu_ns"] / 1000.0
            else:
                ev["s"] = "t"
            events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "last_seq": last, "dropped_spans": self.dropped()}

    def export_file(self, path: str, since: int = 0) -> str:
        """Write the Chrome-trace JSON to `path`; returns `path`.
        Attr values are stringified when not JSON-native, so a span that
        stashed an odd object can never make the artifact unwritable."""
        with open(path, "w") as f:
            json.dump(self.chrome_trace(since), f, default=str)
        return path


# ---------------------------------------------------------------------------
# the process-global tracer (one node per process, same convention as
# libs/metrics.DEFAULT); tests may build private Tracer instances
# ---------------------------------------------------------------------------

TRACER = Tracer()


def span(name: str, parent=_UNSET, **attrs):
    t = TRACER
    if not t._enabled:
        return _NOOP
    return _Span(t, name, parent, attrs)


def timed(name: str, parent=_UNSET, **attrs):
    return TRACER.timed(name, parent, **attrs)


def instant(name: str, parent=_UNSET, **attrs):
    if TRACER._enabled:
        TRACER.instant(name, parent, **attrs)


def is_enabled() -> bool:
    return TRACER._enabled


def enable(capacity: Optional[int] = None):
    TRACER.enable(capacity)


def disable():
    TRACER.disable()


def reset():
    TRACER.reset()


def current():
    return TRACER.current()


def current_id() -> Optional[int]:
    return TRACER.current_id()


def snapshot(since: int = 0):
    return TRACER.snapshot(since)


def last_seq() -> int:
    return TRACER.last_seq()


def dropped() -> int:
    return TRACER.dropped()


def chrome_trace(since: int = 0):
    return TRACER.chrome_trace(since)


def export_file(path: str, since: int = 0) -> str:
    return TRACER.export_file(path, since)
