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

Three things beside spans.  A loop that may not have a span a turn
(``VoteSet.add_vote``) keeps a tally and a batch boundary samples it with
``counter()`` (Chrome-trace ph "C"): two samples differenced read the loop
from inside.  What no span covers is a number, not a guess:
``self_times`` / ``unnamed_ns`` over a snapshot (the roll-up of
``/debug/trace?rollup=1``), with ``ENVELOPES`` naming the spans whose
self time counts as unnamed.  And the ring forgets, so a request-level
span (``KEPT_SPANS``) that takes 8 times its usual is copied, with what
every thread recorded while it ran, into a FIFO of the last 8 incidents
that the wrap does not touch (``incidents()``); a collection of the
cyclic collector of 1 ms or more is a ``gc.pause`` span, so that a pause
has a name when it is one.

Switches: ``TM_TPU_TRACE=0`` in the environment or ``trace.disable()``
turn it off, ``trace.enable()`` on again (capacity override:
``TM_TPU_TRACE_CAPACITY``).  Read it back three ways:
``GET /debug/trace?since=<seq>`` on the pprof listener (libs/pprof.py;
``?rollup=1`` and ``?incidents=1`` for the two reductions),
the ``debug-trace`` CLI (cmd/__main__.py), or the per-config artifact
bench.py writes next to its JSON line.
"""
from __future__ import annotations

import collections
import gc
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
# trace.span()/trace.timed()/trace.instant()/trace.counter() must appear
# here: trace consumers (the benchmark's per-layer readers under
# perfbench/layers, the debug-trace CLI, the acceptance tests walking
# span trees) key on these strings, so an unregistered name is either a
# typo or an undocumented contract.
# Grouped by subsystem; keep alphabetical within a group.
# ---------------------------------------------------------------------------

KNOWN_SPANS = frozenset({
    # crypto/batch.py — the BatchVerifier coalesce window; batch.items
    # is verify_sigs_bulk's list-path loop of bv.add (attr n), ahead of it
    "batch.host_lane", "batch.items", "batch.verdict", "batch.verify",
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
    # consensus/state.py — consensus.screen is the screening loop of one
    # drained batch inside consensus.preverify (attr items), and `votes`
    # the counter record sampled at the same boundary: the process-wide
    # tally types/vote_set.add_vote keeps (calls, wall_ns, cache_hits,
    # host_verifies, refused), read by differencing two samples
    "consensus.finalize_commit", "consensus.preverify",
    "consensus.quorum", "consensus.screen", "consensus.step", "votes",
    # libs/trace.py itself — one collection of the cyclic collector that
    # took a millisecond or more (attrs generation, collected)
    "gc.pause",
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
    # crypto/scheduler.py — the VerifyScheduler pipeline; sched.wait is
    # a submitter blocked in VerifyFuture.result (attrs n, priority): a
    # named wait on the submitter's thread
    "sched.coalesce", "sched.deadline_miss", "sched.host_lane",
    "sched.launch", "sched.resolve", "sched.shed", "sched.submit",
    "sched.wait",
    # state/execution.py — the budgeted propose decomposition
    # (ADR-024) plus block apply
    "propose.assemble", "propose.prepare", "propose.reap",
    "propose.split",
    "state.apply_block", "state.validate_block",
    # statesync/ — the fast-join fetch/verify/apply pipeline and the
    # bounded chunk server (ADR-022)
    "statesync.fetch", "statesync.apply", "statesync.serve",
})


# The registered spans that stand for a request or a hand-off and not for
# a piece of work: a root of one request (light.verify,
# light.client.verify, blocksync.replay_window, consensus.preverify), a
# coalesce window's root (batch.verify), or the bracket one thread holds
# open around another's work (sched.launch, device.launch, and the three
# kernel dispatches, whose launch brackets are launch records, not
# spans).  What such a span does OUTSIDE its children has no name yet,
# so its self time is "unnamed" (unnamed_ns below).  A span that is a
# named wait on its thread (device.collect, pipeline.wait_staged,
# pipeline.drain, sched.wait) or a named piece of work that happens to
# have children (commit.collect, state.apply_block, light.store.save)
# is not one: its self time is what its name says.
ENVELOPES = frozenset({
    "batch.verify", "blocksync.replay_window", "consensus.preverify",
    "device.launch", "light.client.verify", "light.verify",
    "ops.ed25519.verify_batch", "ops.secp.verify_batch",
    "ops.sr25519.verify_batch", "sched.launch",
})

# The request-level spans whose usual duration the tracer knows, so that
# one that takes INCIDENT_FACTOR times it is kept with everything that
# overlapped it (Tracer._keep_incident) instead of being overwritten by
# the ring's wrap.
KEPT_SPANS = frozenset({
    "batch.verify", "blocksync.replay_window", "consensus.preverify",
    "device.collect", "light.client.verify", "light.verify",
})
INCIDENT_FACTOR = 8    # times the usual: a stall, not a slow request
INCIDENT_WARMUP = 16   # of a name seen before any is judged: a process's
#                        first launches compile, and are not incidents
INCIDENT_KEEP = 8      # incidents held, oldest out first
GC_PAUSE_MIN_NS = 1_000_000


# ---------------------------------------------------------------------------
# reading a snapshot: self time and the unnamed remainder.  Pure functions
# over snapshot() records; the roll-up of /debug/trace, the incidents and
# the benchmark's entry.unspanned_ms all rest on these two.
# ---------------------------------------------------------------------------

def _end(r) -> int:
    return r["ts_ns"] + r["dur_ns"]


def _segments(records, tid, t0_ns: int, t1_ns: int):
    """[(start, end, the innermost finished span open on thread `tid`
    then, or None)] covering [t0_ns, t1_ns) without overlap.  Spans of one
    thread nest in time (they are context managers), so one sweep with a
    stack does it; a span that straddles an edge is clipped to it."""
    spans = sorted((r for r in records
                    if r["ph"] == "X" and r["tid"] == tid
                    and r["ts_ns"] < t1_ns and _end(r) > t0_ns),
                   key=lambda r: (r["ts_ns"], -r["dur_ns"], -r["seq"]))
    out, stack, cur = [], [], t0_ns

    def upto(t):
        nonlocal cur
        t = min(t, t1_ns)
        if t > cur:
            out.append((cur, t, stack[-1] if stack else None))
            cur = t

    for r in spans:
        while stack and _end(stack[-1]) <= r["ts_ns"]:
            upto(_end(stack[-1]))
            stack.pop()
        upto(r["ts_ns"])
        stack.append(r)
    while stack:
        upto(_end(stack[-1]))
        stack.pop()
    upto(t1_ns)
    return out


def _by_thread(records) -> dict:
    """{tid: its records}."""
    out: Dict[Any, list] = {}
    for r in records:
        out.setdefault(r["tid"], []).append(r)
    return out


def self_times(records) -> Dict[int, int]:
    """{span id: self ns}: a span's duration less the part that the spans
    inside it ON ITS OWN THREAD cover (the choosing-metrics guide's self
    time).  Work a span caused on another thread is that thread's."""
    out = {r["id"]: 0 for r in records if r["ph"] == "X"}
    for tid, recs in _by_thread(records).items():
        spans = [r for r in recs if r["ph"] == "X"]
        if not spans:
            continue
        lo = min(r["ts_ns"] for r in spans)
        hi = max(_end(r) for r in spans)
        for a, b, inner in _segments(spans, tid, lo, hi):
            if inner is not None:
                out[inner["id"]] += b - a
    return out


def unnamed_ns(records, tid, t0_ns: int, t1_ns: int) -> int:
    """The part of [t0_ns, t1_ns) on thread `tid` whose innermost open
    span is an envelope (ENVELOPES) or nothing at all: time no name
    accounts for."""
    return sum(b - a for a, b, inner in _segments(records, tid, t0_ns, t1_ns)
               if inner is None or inner["name"] in ENVELOPES)


def _largest_gap(records, t0_ns: int, t1_ns: int):
    """The longest stretch of [t0_ns, t1_ns), on any thread, that an
    envelope held open with none of its children running: where a stall
    hides.  With the envelope's name and the spans that ended at the
    gap's start and began at its end.  (A worker thread with NOTHING open
    is idle between tasks, not stalled; its wait shows as queue_wait_ns /
    queued_ns on the span that follows.)"""
    best = None
    for tid, recs in _by_thread(records).items():
        for a, b, inner in _segments(recs, tid, t0_ns, t1_ns):
            if inner is None or inner["name"] not in ENVELOPES:
                continue
            if best is None or b - a > best["dur_ns"]:
                before = [r["name"] for r in recs if r["ph"] == "X"
                          and _end(r) == a and r is not inner]
                after = [r["name"] for r in recs if r["ph"] == "X"
                         and r["ts_ns"] == b and r is not inner]
                best = {"tid": tid, "tname": inner["tname"], "ts_ns": a,
                        "dur_ns": b - a, "inside": inner["name"],
                        "before": before[0] if before else None,
                        "after": after[0] if after else None}
    return best


def rollup(records) -> Dict[str, Any]:
    """What an operator asks a slow node: per span name its count, total
    and self time; per thread the unnamed remainder between its first and
    last record; the newest sample of each counter."""
    selfs = self_times(records)
    spans: Dict[str, Dict[str, int]] = {}
    counters: Dict[str, Any] = {}
    for r in records:
        if r["ph"] == "C":
            counters[r["name"]] = dict(r["attrs"])
        if r["ph"] != "X":
            continue
        row = spans.setdefault(r["name"],
                               {"count": 0, "total_ns": 0, "self_ns": 0})
        row["count"] += 1
        row["total_ns"] += r["dur_ns"]
        row["self_ns"] += selfs[r["id"]]
    threads = []
    for tid, recs in _by_thread(records).items():
        lo = min(r["ts_ns"] for r in recs)
        hi = max(_end(r) for r in recs)
        threads.append({"tid": tid, "tname": recs[-1]["tname"],
                        "window_ns": hi - lo,
                        "unnamed_ns": unnamed_ns(recs, tid, lo, hi)})
    return {"spans": spans, "threads": threads, "counters": counters}


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
        # kept, not overwritten: per kept name [seen, usual ns, the
        # first INCIDENT_WARMUP durations until the usual is set]
        self._usual = {name: [0, 0, []] for name in KEPT_SPANS}
        self._incidents: "collections.deque" = collections.deque(
            maxlen=INCIDENT_KEEP)
        self._incident_seq = 0
        self._incident_counter = None  # lazy TraceMetrics handle
        # collections of 1 ms or more, stashed by the gc hook WITHOUT the
        # lock (a collection can start inside _record, under it) and
        # turned into records by whoever takes the lock next
        self._gc_pending: "collections.deque" = collections.deque()

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
        """Drop buffered spans, kept incidents and what the tracer took
        for usual.  seq stays monotonic so `since` cursors held by
        pollers remain valid across a reset."""
        with self._lock:
            self._buf.clear()
            self._gc_pending.clear()
            self._incidents.clear()
            self._usual = {name: [0, 0, []] for name in KEPT_SPANS}

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

    def counter(self, name: str, **values):
        """A sample of cumulative values (Chrome-trace ph="C"): what a
        loop that may not have a span a turn has added up so far, taken
        at a batch boundary; two samples differenced read the loop from
        inside."""
        if not self._enabled:
            return
        t = threading.current_thread()
        self._record(name, "C", time.perf_counter_ns(), 0, 0, t.ident,
                     t.name, next(self._ids), None, values)

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
        usual = None
        with self._lock:
            wrapped = self._drain_gc_locked() if self._gc_pending else 0
            rec = {
                "seq": 0, "name": name, "ph": ph, "ts_ns": t0_ns,
                "dur_ns": dur_ns, "cpu_ns": cpu_ns, "tid": tid,
                "tname": tname,
                "id": span_id, "parent": parent_id, "attrs": attrs,
            }
            wrapped += self._append_locked(rec)
            if ph == "X":
                # one look-up for a name that is not kept, one compare
                # more for one that is and took its usual
                st = self._usual.get(name)
                if st is not None:
                    usual = self._observe_locked(st, dur_ns)
        for _ in range(wrapped):
            # counter inc AFTER releasing: the metric locks rank BELOW
            # the tracer lock (lockorder 80/84 < 90), so publishing
            # under self._lock would be a real inversion
            self._publish("_drop_counter", "dropped_spans")
        if usual is not None:
            self._keep_incident(rec, usual)

    def _append_locked(self, rec) -> int:
        """Append under the lock; 1 when the ring dropped its oldest."""
        self._seq += 1
        rec["seq"] = self._seq
        wrapped = len(self._buf) == self._buf.maxlen
        if wrapped:
            self._dropped += 1
        self._buf.append(rec)
        return int(wrapped)

    def _publish(self, slot: str, metric: str):
        c = getattr(self, slot)
        if c is None:
            try:
                from tendermint_tpu.libs.metrics import TraceMetrics
                c = getattr(TraceMetrics(), metric)
            except Exception:  # noqa: BLE001 - observability of the
                c = False       # observer must never take down a span
            setattr(self, slot, c)
        if c is not False:
            try:
                c.inc()
            except Exception:  # noqa: BLE001
                pass

    # -- kept, not overwritten ---------------------------------------------

    @staticmethod
    def _observe_locked(st, dur_ns):
        """One more duration of a kept name: the usual (a running median:
        the true one of the first INCIDENT_WARMUP, then a step of a 16th
        towards each newcomer) when this one took INCIDENT_FACTOR times
        it, else None.  No allocation once the usual is set."""
        st[0] += 1
        if st[0] <= INCIDENT_WARMUP:
            st[2].append(dur_ns)
            if st[0] == INCIDENT_WARMUP:
                st[1] = sorted(st[2])[INCIDENT_WARMUP // 2]
                st[2] = None
            return None
        usual = st[1]
        if dur_ns >= INCIDENT_FACTOR * usual:
            return usual  # and the usual stays what it was
        step = max(usual >> 4, 1)
        st[1] = usual + step if dur_ns > usual else usual - step
        return None

    def _drain_gc_locked(self) -> int:
        wrapped = 0
        while self._gc_pending:
            wrapped += self._append_locked(self._gc_pending.popleft())
        return wrapped

    def _gc_pause(self, t0_ns: int, dur_ns: int, info: dict):
        t = threading.current_thread()
        stack = self._stack()
        self._gc_pending.append({
            "seq": 0, "name": "gc.pause", "ph": "X", "ts_ns": t0_ns,
            "dur_ns": dur_ns, "cpu_ns": None, "tid": t.ident,
            "tname": t.name, "id": next(self._ids),
            "parent": stack[-1].span_id if stack else None,
            "attrs": {"generation": info.get("generation"),
                      "collected": info.get("collected")}})

    def _keep_incident(self, rec, usual_ns: int):
        """`rec` took INCIDENT_FACTOR times its name's usual: copy what
        every thread recorded while it ran out of the ring's reach.  Paid
        only by a request that has already lost a hundred times this."""
        t0, t1 = rec["ts_ns"], _end(rec)
        with self._lock:
            last = self._incidents[-1] if self._incidents else None
            if last is not None and last["ts_ns"] >= t0 \
                    and last["ts_ns"] + last["dur_ns"] <= t1:
                # the span around one already kept: the same stall
                last["within"].append(rec["name"])
                return
            tail = []
            for r in reversed(self._buf):
                # records are appended as they END, so the first one that
                # ended before the span began closes the walk (2 ms of
                # slack for threads that raced to the lock)
                if _end(r) < t0 - 2_000_000:
                    break
                if r["ts_ns"] < t1 and _end(r) >= t0:
                    tail.append(dict(r, attrs=dict(r["attrs"])))
            tail.reverse()
            self._incident_seq += 1
            seq = self._incident_seq
        gap = _largest_gap(tail, t0, t1)
        incident = {
            "incident": seq, "name": rec["name"], "ts_ns": t0,
            "dur_ns": rec["dur_ns"], "usual_ns": usual_ns,
            "tid": rec["tid"], "tname": rec["tname"], "within": [],
            "unnamed_ns": {
                str(recs[-1]["tname"] or tid): unnamed_ns(recs, tid, t0, t1)
                for tid, recs in _by_thread(tail).items()},
            "gap": gap, "records": tail}
        with self._lock:
            self._incidents.append(incident)
        self._publish("_incident_counter", "incidents")
        try:
            from tendermint_tpu.libs import log as tmlog
            g = gap or {}
            tmlog.logger("trace").warn(
                "a request stalled: kept by the flight recorder",
                span=rec["name"], ms=rec["dur_ns"] / 1e6,
                usual_ms=usual_ns / 1e6, records=len(tail),
                gap_ms=g.get("dur_ns", 0) / 1e6, gap_in=g.get("inside"),
                gap_thread=g.get("tname"), gap_after=g.get("before"),
                gap_before=g.get("after"))
        except Exception:  # noqa: BLE001 - a log line, nothing more
            pass

    def incidents(self) -> List[Dict[str, Any]]:
        """The last INCIDENT_KEEP kept incidents, oldest first (copies)."""
        with self._lock:
            return [dict(i, records=[dict(r) for r in i["records"]],
                         within=list(i["within"]))
                    for i in self._incidents]

    def incident_count(self) -> int:
        with self._lock:
            return self._incident_seq

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
            if self._gc_pending:
                self._drain_gc_locked()
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
            if r["ph"] == "C":
                # a counter event's args are its series, nothing else
                events.append({"name": r["name"], "ph": "C", "pid": pid,
                               "tid": r["tid"], "ts": r["ts_ns"] / 1000.0,
                               "args": dict(r["attrs"])})
                continue
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


def counter(name: str, **values):
    if TRACER._enabled:
        TRACER.counter(name, **values)


def is_enabled() -> bool:
    return TRACER._enabled


def enable(capacity: Optional[int] = None):
    TRACER.enable(capacity)


def disable():
    TRACER.disable()


def reset():
    TRACER.reset()


def rollup_snapshot(since: int = 0) -> Dict[str, Any]:
    """rollup() of the process-wide recorder's records."""
    return rollup(TRACER.snapshot(since))


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


def incidents():
    return TRACER.incidents()


def chrome_trace(since: int = 0):
    return TRACER.chrome_trace(since)


def export_file(path: str, since: int = 0) -> str:
    return TRACER.export_file(path, since)


# ---------------------------------------------------------------------------
# so that a pause has a name when it is one: the cyclic collector's start
# and stop (gc.callbacks), a `gc.pause` span for a collection of 1 ms or
# more.  Two clock reads a collection; nothing is recorded from inside the
# hook (a collection can begin under the tracer's lock).
# ---------------------------------------------------------------------------

_gc_t0 = 0


def _gc_hook(phase: str, info: dict):
    global _gc_t0
    if phase == "start":
        _gc_t0 = time.perf_counter_ns() if TRACER._enabled else 0
    elif _gc_t0:
        t0, _gc_t0 = _gc_t0, 0
        dur = time.perf_counter_ns() - t0
        if dur >= GC_PAUSE_MIN_NS:
            TRACER._gc_pause(t0, dur, info)


if _gc_hook not in gc.callbacks:
    gc.callbacks.append(_gc_hook)
