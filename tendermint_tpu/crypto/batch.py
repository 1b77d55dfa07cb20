"""BatchVerifier — the TPU signature-verification data plane.

The reference at v0.34.20 has no batch verifier; every call site verifies
serially through crypto.PubKey.VerifySignature (reference
crypto/crypto.go:22-28, hot loops types/validator_set.go:680-702 and
blocksync/reactor.go:375).  This is the new component the build introduces:
call sites enqueue (pubkey, msg, sig) triples and get back an exact
per-triple validity bitmap, computed in one batched TPU kernel launch
(one signature per vector lane; see ops/ed25519.py).

Routing policy (BASELINE.md config 5 / SURVEY.md §7 hard part 5): tiny
batches are latency-bound and stay on the host CPU (OpenSSL); batches of at
least `tpu_threshold` go to the device kernel.  Mixed key types dispatch
per-scheme sub-batches and merge bitmaps by original index.
"""
from __future__ import annotations

import os
import random
import time
from dataclasses import dataclass, field
from functools import partial
from typing import List, Optional, Sequence, Tuple

import numpy as np

from tendermint_tpu.libs import trace
from . import PubKey
from . import degrade
from . import ed25519 as ed
from . import lanepool


def _use_device() -> bool:
    """Route to the device kernel only when an accelerator is attached.
    When jax's default backend is plain host CPU the serial OpenSSL path is
    strictly faster than the jitted ladder, so the batch stays on the host
    (TM_TPU_FORCE_BATCH=1 overrides, for kernel tests on CPU).  Backend
    probing lives in the degradation runtime: an init FAILURE is re-probed
    with backoff instead of cached forever, and the circuit breaker (which
    gates each launch separately, in try_acquire) still applies under
    FORCE_BATCH so chaos tests exercise it on CPU."""
    if os.environ.get("TM_TPU_DISABLE_BATCH", "") == "1":
        return False
    if os.environ.get("TM_TPU_FORCE_BATCH", "") == "1":
        return True
    return degrade.runtime().backend_available()


def _spot_check(n, triple_at):
    """Integrity guard closure for a device lane: re-verify ONE random
    triple on the host and require the device's bit to agree — one host
    verify per launch, and a device returning garbage bitmaps (chaos
    mode "corrupt-bitmap", a real silent-corruption class) is degraded
    instead of trusted.  `triple_at(j) -> (pub, msg, sig)` with pub a
    PubKey object."""
    def check(bits: np.ndarray) -> bool:
        if n == 0 or len(bits) != n:
            return len(bits) == n
        j = random.randrange(n)
        try:
            pub, msg, sig = triple_at(j)
            host = pub.verify_signature(msg, sig)
        except Exception:  # noqa: BLE001 - malformed input = invalid
            host = False
        return bool(bits[j]) == bool(host)
    return check


def _spot_check_items(items):
    return _spot_check(len(items),
                       lambda j: (items[j].pub, items[j].msg, items[j].sig))


@dataclass
class _Item:
    pub: PubKey
    msg: bytes
    sig: bytes


class SigCache:
    """Bounded LRU cache of signatures that ALREADY verified valid.

    This is the seam between the consensus live-vote coalescing window and
    VoteSet's serial add path (SURVEY §7 hard part 2): the receive loop
    batch-verifies every vote waiting in its queue in one kernel launch
    (populating this cache), then applies the votes in arrival order —
    VoteSet's per-vote verify becomes a cache hit instead of a host
    signature check.  Only valid triples are ever inserted, so a hit is
    exactly as strong as a fresh verification.

    Shared mutable state across the consensus receive loop, the
    VerifyScheduler's stage/execute workers, and every reactor thread
    that re-checks serially: add/hit are lock-guarded, and eviction is
    true LRU (a hit refreshes recency), so the hot live-vote window
    survives a background bulk insert of the same capacity."""

    def __init__(self, capacity: int = 1 << 16):
        import collections
        import threading
        self.capacity = capacity
        self._set: "collections.OrderedDict[bytes, None]" = \
            collections.OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    @staticmethod
    def key(pub_bytes: bytes, msg: bytes, sig: bytes) -> bytes:
        import hashlib
        h = hashlib.sha256()
        h.update(pub_bytes)
        h.update(sig)
        h.update(msg)
        return h.digest()

    def add(self, pub_bytes: bytes, msg: bytes, sig: bytes) -> None:
        self.add_key(self.key(pub_bytes, msg, sig))

    def add_key(self, k: bytes) -> None:
        """Insert by precomputed key (the scheduler hashes each triple
        once at staging and reuses the digest for dedupe, the hit check,
        and this insert)."""
        with self._lock:
            self._set[k] = None
            self._set.move_to_end(k)  # re-insert refreshes recency too
            while len(self._set) > self.capacity:
                self._set.popitem(last=False)

    def hit(self, pub_bytes: bytes, msg: bytes, sig: bytes) -> bool:
        return self.hit_key(self.key(pub_bytes, msg, sig))

    def hit_key(self, k: bytes) -> bool:
        with self._lock:
            ok = k in self._set
            if ok:
                self._set.move_to_end(k)  # LRU: a hit is a use
                self.hits += 1
            else:
                self.misses += 1
            return ok

    def __len__(self) -> int:
        with self._lock:
            return len(self._set)


verified_sigs = SigCache()


class BatchVerifier:
    """Collect (pubkey, msg, sig) triples; verify them in one batch.

    Semantics match the reference's check-all commit verification
    (types/validator_set.go:657-661): every triple is verified exactly and
    independently — no early exit, no probabilistic batch equation — so the
    returned bitmap identifies offenders directly.
    """

    def __init__(self, tpu_threshold: int = 32):
        self._items: List[_Item] = []
        self.tpu_threshold = tpu_threshold

    def __len__(self) -> int:
        return len(self._items)

    def add(self, pub: PubKey, msg: bytes, sig: bytes) -> None:
        self._items.append(_Item(pub, bytes(msg), bytes(sig)))

    def verify(self) -> Tuple[bool, np.ndarray]:
        """Returns (all_valid, per-item bool bitmap, in insertion order)."""
        n = len(self._items)
        if n == 0:
            return True, np.zeros(0, dtype=bool)
        # lifecycle origin of the DIRECT path (ADR-016): verify() entry
        # is this request's "submit", and the e2e bracket lands in the
        # same verify_e2e_latency histogram the scheduler publishes,
        # labeled path="direct" at the caller's context priority
        t_submit = time.monotonic()
        # flight-recorder root of the coalesce window: the lane spans
        # (device.launch on the worker, device.collect, verdict
        # application) all link under this span, so an exported trace
        # shows where one batch spent its time and which route it took
        with trace.span("batch.verify", n=n,
                        threshold=self.tpu_threshold) as sp:
            ok, bits = self._verify(n, sp, t_submit)
        degrade.publish_request_latency(
            _context_priority_name(), "direct",
            time.monotonic() - t_submit)
        return ok, bits

    def _verify(self, n: int, sp,
                t_submit: Optional[float] = None) -> Tuple[bool, np.ndarray]:
        out = np.zeros(n, dtype=bool)
        # dispatch per key scheme; the device (ed25519) lane runs in a
        # worker thread OVERLAPPED with the host C lanes — the device
        # lane mostly waits on the launch and the ctypes batch
        # verifiers release the GIL, so a mixed batch costs
        # ~max(device lane, host lanes) instead of their sum
        by_type: dict = {}
        for i, it in enumerate(self._items):
            by_type.setdefault(it.pub.type_name, []).append(i)
        # tiny-batch hot path (a consensus vote window): below the
        # threshold no per-scheme lane can reach the device either, so
        # skip the _use_device()/degrade.runtime() dance entirely — the
        # runtime's breaker lock is shared across reactor threads and
        # pure contention for batches that could never dispatch
        rt = degrade.runtime() if n >= self.tpu_threshold else None
        device_lanes = []  # [(tname, idxs, items, future, t0, done_at)]
        host_lanes = []
        for tname, idxs in by_type.items():
            items = [self._items[i] for i in idxs]
            verifier = _device_verifier(tname) if rt is not None else None
            if (verifier is not None and _use_device()
                    and len(items) >= self.tpu_threshold):
                if rt.try_acquire():
                    t0 = time.monotonic()
                    fut = rt.submit(
                        f"batch.{tname}", verifier,
                        [it.pub.bytes() for it in items],
                        [it.msg for it in items],
                        [it.sig for it in items])
                    done_at = _lane_done_stamp(fut)
                    device_lanes.append((tname, idxs, items, fut, t0,
                                         done_at))
                    continue
                # breaker open: this lane WOULD have gone to the device
                rt.metrics.host_fallbacks.inc(site=f"batch.{tname}",
                                              reason="breaker_open")
            host_lanes.append((tname, idxs, items))
        if trace.is_enabled():
            sp.add(schemes=",".join(f"{t}:{len(ix)}"
                                    for t, ix in by_type.items()),
                   device_lanes=len(device_lanes),
                   host_lanes=len(host_lanes),
                   device_eligible=rt is not None)
        lane_times: List[Tuple[str, str, float, float]] = []
        try:
            # host lanes run CONCURRENTLY on the lane pool (and the
            # device lanes are already in flight on their workers), so
            # a mixed batch costs max over lanes, not their sum
            _run_host_lanes(host_lanes, out, "batch.host_lane",
                            sp.span_id, lane_times=lane_times,
                            t_submit=t_submit)
        finally:
            # always settle EVERY device lane: a host-lane exception must
            # not abandon an in-flight device RPC or leave the breaker's
            # acquire unbalanced.  collect() never raises — a launch that
            # times out, raises, or fails the host spot check is counted
            # against the breaker and the lane re-verifies through the
            # host path, preserving the exact per-triple bitmap.
            for tname, idxs, items, fut, t0, done_at in device_lanes:
                out[np.asarray(idxs)] = rt.collect(
                    f"batch.{tname}", fut,
                    host_fn=partial(_host_verify_items, tname, items),
                    spot_check=_spot_check_items(items))
                lane_times.append(_device_lane_wall(tname, fut, t0, done_at))
        _publish_lane_report(lane_times, sp, rt is not None)
        # remember the valid ones so later serial re-checks are cache hits
        with trace.span("batch.verdict") as vsp:
            for i, it in enumerate(self._items):
                if out[i]:
                    verified_sigs.add(it.pub.bytes(), it.msg, it.sig)
            if trace.is_enabled():
                vsp.add(valid=int(out.sum()), n=n)
        return bool(out.all()), out


def _run_host_lanes(host_lanes, out: np.ndarray, span_name: str, parent,
                    assume_miss: bool = False, lane_times=None,
                    t_submit: Optional[float] = None):
    """Run the per-scheme host lanes CONCURRENTLY through the host-lane
    pool (crypto/lanepool.py, ADR-015) — the host side of a mixed batch
    costs max over lanes instead of their sum.  When the pool is
    disabled or saturated, unadmitted lanes run serially in the caller
    (the pre-ADR-015 loop).  `parent` is the caller's span id, linking
    each lane span under the batch span across the pool's thread
    boundary; `lane_times` (when given) collects (scheme, kind, t0, t1)
    wall brackets for the overlap gauge and bench decomposition;
    `t_submit` is the request's lifecycle origin (ADR-016), threaded
    through so every lane span — even on a pool worker thread —
    carries the request's age when the lane started."""
    if not host_lanes:
        return

    def lane(tname, items):
        t0 = time.monotonic()
        with trace.span(span_name, parent=parent, scheme=tname,
                        n=len(items)) as lsp:
            if t_submit is not None and trace.is_enabled():
                lsp.add(since_submit_s=round(t0 - t_submit, 6))
            bits = _host_verify_items(tname, items,
                                      assume_miss=assume_miss,
                                      t_submit=t_submit)
        if lane_times is not None:
            lane_times.append((tname, "host", t0, time.monotonic()))
        return bits

    # lane-level pooling needs at least MIN_CHUNK items across the
    # lanes: a tiny mixed vote window (a few signatures) must not
    # construct the pool or pay future handoffs on the consensus hot
    # path — the serial walk is already microseconds there
    if len(host_lanes) > 1 and \
            sum(len(items) for _, _, items in host_lanes) \
            >= lanepool.MIN_CHUNK:
        results = lanepool.run_lanes(
            [partial(lane, tname, items)
             for tname, _idxs, items in host_lanes])
    else:
        results = [lane(tname, items)
                   for tname, _idxs, items in host_lanes]
    for (tname, idxs, items), bits in zip(host_lanes, results):
        out[np.asarray(idxs)] = bits


def _lane_done_stamp(fut) -> list:
    """Timestamp box filled when a device-lane future completes.  The
    lane's wall bracket must end when the DEVICE finished, not when the
    caller got around to collect() (which runs after every host lane —
    using collect-return would inflate the device wall by the host-lane
    wait and make the overlap gauge read concurrency that never
    happened).  A launch that never completes (timeout/quarantine)
    leaves the box empty and the bracket falls back to collect-return,
    which then genuinely includes the host re-verify that settled the
    lane."""
    done_at: list = []

    def _stamp(_f):
        done_at.append(time.monotonic())
    fut.add_done_callback(_stamp)
    return done_at


def _device_lane_wall(tname: str, fut, t0: float, done_at: list) -> tuple:
    """A settled device lane's (scheme, kind, start, end) for the lane
    report.  It starts when the lane worker took it up, not at its
    submit: the worker is one thread, so with a device lane a scheme
    (a set in three key schemes) the second and third wait in its queue
    behind the first, and a bracket opened at submit would count that
    wait as the lane's own wall and read lanes run one after another as
    overlapped.  `t0` (the submit) stands in for a lane that never
    started."""
    return (tname, "device", getattr(fut, "started_at", t0),
            done_at[0] if done_at else time.monotonic())


_last_lanes: dict = {}


def last_lane_report() -> dict:
    """Wall-time decomposition of the most recent multi-lane verify:
    {"lanes": [{"scheme", "kind", "wall_s"}, ...], "wall_s", "sum_s",
    "overlap_ratio"} — overlap_ratio = 1 - wall/sum is 0 for serial
    lanes and (k-1)/k for k perfectly overlapped ones.  Read by
    BENCH_MIXED=1 bench.py and scripts/bench_report config 5."""
    return _last_lanes


def _publish_lane_report(lane_times, sp, publish_metrics: bool):
    """Fold per-lane wall brackets into the lane report + the
    crypto_lane_overlap_ratio gauge.  Skips the gauge for tiny batches
    (publish_metrics False): they never touch degrade.runtime() and
    publishing would construct it just for a metric.  Returns THIS
    call's report dict (None when there were no lanes): the scheduler
    embeds it in its window's latency report, and re-reading the
    process-global last_lane_report() there could hand back a
    concurrent direct batch's lanes instead."""
    global _last_lanes
    if not lane_times:
        return None
    wall = max(t1 for _, _, _, t1 in lane_times) - \
        min(t0 for _, _, t0, _ in lane_times)
    total = sum(t1 - t0 for _, _, t0, t1 in lane_times)
    overlap = 0.0
    if len(lane_times) > 1 and total > 0 and wall > 0:
        overlap = max(0.0, 1.0 - wall / total)
    report = {
        "lanes": [{"scheme": s, "kind": k, "wall_s": round(t1 - t0, 6)}
                  for s, k, t0, t1 in lane_times],
        "wall_s": round(wall, 6),
        "sum_s": round(total, 6),
        "overlap_ratio": round(overlap, 4),
    }
    _last_lanes = report
    if len(lane_times) > 1:
        if trace.is_enabled():
            sp.add(lane_overlap=round(overlap, 4))
        if publish_metrics:
            degrade.publish_lane_overlap(overlap)
    return report


def _context_priority_name() -> str:
    """Priority label for the direct path's e2e latency: the caller's
    scheduler priority context when one is set (light client under
    priority_context(COMMIT), blocksync replay, ...), COMMIT otherwise.
    Lazy import — scheduler imports this module at load."""
    try:
        from tendermint_tpu.crypto import scheduler as vsched
        return vsched.context_priority(
            vsched.Priority.COMMIT)[0].name.lower()
    except Exception:  # noqa: BLE001 - a label must never break verify
        return "commit"


def _device_verifier(tname: str):
    """The TPU lane for a key scheme, or None if that scheme stays on the
    host.  ed25519: the comb / fused ladder routes (ops/ed25519.py);
    sr25519: same curve, ristretto lane (ops/sr25519.py); secp256k1:
    the Pallas Straus lane (ops/secp.py), default-on since ADR-015 —
    TM_TPU_SECP_LANE=0 / [batch_verifier] secp_lane=false is the
    rollback switch back to the host C lane."""
    if tname == ed.KEY_TYPE:
        return verify_ed25519_batch
    if tname == "sr25519":
        def _sr(pubs, msgs, sigs):
            from tendermint_tpu.ops import sr25519 as srlane
            return srlane.verify_batch_device(pubs, msgs, sigs)
        return _sr
    if tname == "secp256k1":
        from tendermint_tpu.ops import secp as secp_ops
        if secp_ops.use_lane():
            def _secp(pubs, msgs, sigs):
                return secp_ops.verify_batch_device(pubs, msgs, sigs)
            return _secp
    return None


def _host_verify_items(tname: str, items, assume_miss: bool = False,
                       t_submit: Optional[float] = None) -> np.ndarray:
    """Host lane: SigCache hits first; cache misses batch through the
    native C verifiers for secp256k1/sr25519 (native/ecverify.c — the
    pure-Python bignum path costs ~5 ms/sig, the C lanes ~0.1-0.2 ms),
    sharded across the host pool's cores by lanepool.verify_sharded;
    per-item Python remains the no-toolchain fallback and handles
    malformed-length inputs.  `assume_miss` skips the cache pre-pass
    when the caller already filtered hits (the scheduler's stager hashed
    every triple once and resolved hits without lanes — re-hashing here
    could only re-prove misses)."""
    n = len(items)
    bits = np.zeros(n, dtype=bool)
    if assume_miss:
        miss = list(range(n))
    else:
        miss = []
        for i, it in enumerate(items):
            if verified_sigs.hit(it.pub.bytes(), it.msg, it.sig):
                bits[i] = True
            else:
                miss.append(i)
    if not miss:
        return bits
    # EVERY miss count takes the C lane, including a single cache miss
    # (which previously fell to the ~5 ms/sig pure-Python path); big
    # miss lists are sharded across the host pool's cores
    sub = lanepool.verify_sharded(
        tname,
        [items[i].pub.bytes() for i in miss],
        [items[i].msg for i in miss],
        [items[i].sig for i in miss],
        t_submit=t_submit)
    if sub is None:
        sub = [items[i].pub.verify_signature(items[i].msg, items[i].sig)
               for i in miss]
    bits[np.asarray(miss)] = sub
    return bits


def verify_sigs_bulk(pubs: Sequence[PubKey], msgs, sigs: Sequence[bytes],
                     tpu_threshold: int = 32) -> np.ndarray:
    """Bitmap for n (pub, msg, sig) triples without per-item _Item objects
    — the whole-commit path (types/validator_set.py), where n can be 100k+
    and BatchVerifier's per-item add/dispatch bookkeeping would cost more
    than the verification itself.  `msgs` may be a RaggedBytes (the batched
    sign-bytes assembler's output) or any sequence of bytes.

    Routing matches BatchVerifier: device kernel for big all-ed25519
    batches, per-item host verify otherwise.  Skips the SigCache (a 100k
    commit would evict the live-vote window; callers that need cache
    population use BatchVerifier).

    When the process-global VerifyScheduler is running, list-input
    batches up to its max_batch route through it instead (at the
    caller's priority context, default COMMIT) so concurrent consumers
    coalesce into shared device launches.  Two shapes keep the direct
    path: batches above max_batch (a window that size saturates the
    device alone), and the (n, 32) raw-pubkey-matrix input — that is
    the validator-set per-block hot path whose device-resident pubkey
    cache ships 96 B/sig with zero per-key objects (ADR-008), and
    coalescing could only add copies and restage resident keys."""
    n = len(pubs)
    sch = None
    if n and not isinstance(pubs, np.ndarray):
        from tendermint_tpu.crypto import scheduler as vsched
        sch = vsched.running()
    if sch is not None and n <= sch.max_batch:
        try:
            items = [(pubs[i], msgs[i], sigs[i]) for i in range(n)]
            prio, deadline = vsched.context_priority(
                vsched.Priority.COMMIT)
            return sch.submit(items, prio, deadline=deadline,
                              populate_cache=False).result(
                                  timeout=sch.sync_timeout())
        except (vsched.SchedulerError, TimeoutError):
            pass  # fall through to the direct path below
    rt = degrade.runtime()
    if isinstance(pubs, np.ndarray):
        # (n, 32) raw ed25519 pubkey matrix — the validator-set fast
        # path (types/validator_set._pub_matrix): no per-key objects
        if n >= tpu_threshold and _use_device():
            return rt.run(
                "bulk.ed25519",
                partial(verify_ed25519_batch, pubs, msgs, sigs,
                        cache_pubs=True),
                host_fn=partial(_host_bulk_ed25519, pubs, msgs, sigs),
                spot_check=_spot_check_bulk(pubs, msgs, sigs))
        pubs = [ed.PubKey(bytes(p)) for p in pubs]
        if isinstance(sigs, np.ndarray):
            sigs = [bytes(s) for s in sigs]
    if (n >= tpu_threshold and _use_device()
            and all(p.type_name == ed.KEY_TYPE for p in pubs)):
        # cache_pubs: a validator set's keys recur every block, so the
        # device keeps them resident and each commit ships 96 B/sig
        return rt.run(
            "bulk.ed25519",
            partial(verify_ed25519_batch, [p.bytes() for p in pubs],
                    msgs, sigs, cache_pubs=True),
            host_fn=partial(_host_bulk_ed25519, pubs, msgs, sigs),
            spot_check=_spot_check_bulk(pubs, msgs, sigs))
    bv = BatchVerifier(tpu_threshold=tpu_threshold)
    # one span for the loop, never one a row (ADR-011): n _Items built
    # ahead of batch.verify
    with trace.span("batch.items", n=n):
        for i in range(n):
            bv.add(pubs[i], msgs[i], sigs[i])
    _, bits = bv.verify()
    return bits


def _as_ed_pub(p) -> PubKey:
    return p if isinstance(p, PubKey) else ed.PubKey(bytes(p))


def _host_bulk_ed25519(pubs, msgs, sigs) -> np.ndarray:
    """Host re-verification of a whole-commit batch — the degradation
    target when the device lane times out, raises, or the breaker is
    open.  Same per-triple semantics as the device path: malformed
    lengths are simply invalid, never exceptions."""
    n = len(pubs)
    bits = np.zeros(n, dtype=bool)
    for i in range(n):
        try:
            bits[i] = _as_ed_pub(pubs[i]).verify_signature(
                bytes(msgs[i]), bytes(sigs[i]))
        except Exception:  # noqa: BLE001 - malformed input = invalid
            bits[i] = False
    return bits


def _spot_check_bulk(pubs, msgs, sigs):
    return _spot_check(
        len(pubs),
        lambda j: (_as_ed_pub(pubs[j]), bytes(msgs[j]), bytes(sigs[j])))


def verify_ed25519_batch(pubkeys: Sequence[bytes], msgs: Sequence[bytes],
                         sigs: Sequence[bytes],
                         cache_pubs: bool = False) -> np.ndarray:
    """Raw-bytes ed25519 batch verify on the device (malformed lengths are
    rejected host-side without poisoning the batch)."""
    n = len(pubkeys)
    # an (n, 32) key matrix and an (n, 64) signature matrix
    # (types/validator_set._collect_batch hands both) are
    # shape-guaranteed: nothing to screen a row at a time
    ok_len = np.ones(n, dtype=bool)
    if not isinstance(pubkeys, np.ndarray):
        ok_len &= np.fromiter(map(len, pubkeys), dtype=np.int64,
                              count=n) == 32
    if not isinstance(sigs, np.ndarray):
        ok_len &= np.fromiter(map(len, sigs), dtype=np.int64,
                              count=n) == 64
    if not ok_len.all():
        good = np.flatnonzero(ok_len)
        if good.size == 0:
            return ok_len
        sub = verify_ed25519_batch([pubkeys[i] for i in good],
                                   [msgs[i] for i in good],
                                   [sigs[i] for i in good],
                                   cache_pubs=cache_pubs)
        out = np.zeros(n, dtype=bool)
        out[good] = sub
        return out
    return ed_ops_verify(pubkeys, msgs, sigs, cache_pubs=cache_pubs)


def ed_ops_verify(pubkeys, msgs, sigs, cache_pubs: bool = False) -> np.ndarray:
    from tendermint_tpu.ops import ed25519 as edops
    return edops.verify_batch(pubkeys, msgs, sigs, cache_pubs=cache_pubs)
