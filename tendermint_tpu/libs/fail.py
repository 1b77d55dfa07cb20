"""Fault injection: indexed crash points + named chaos modes.

Two mechanisms share this module:

1. Indexed fail points (reference libs/fail/fail.go:28-39): call sites
   are numbered in execution order by a process-global counter; when the
   counter reaches $FAIL_TEST_INDEX the process dies immediately.  Used
   by crash/recovery tests to die between WAL-fsync, block-save and
   app-commit (reference consensus/state.go:1653-1733,
   state/execution.go).

2. Named, mode-keyed injection for the device-lane chaos matrix
   (crypto/degrade.py, tests/test_chaos_matrix.py).  A site like
   "ops.ed25519.verify_batch" calls inject(site) on entry; an armed mode
   forces one failure class deterministically:

       raise          raise InjectedFault at the site
       latency:<ms>   sleep <ms> before proceeding (drives the launch
                      deadline in the degradation runtime)
       corrupt-bitmap invert the device result bitmap (exercises the
                      runtime's host spot-check integrity guard)
       exit           os._exit(77), the crash-matrix convention

   Armed programmatically (set_mode / clear) for in-process tests, or
   via $TM_TPU_FAILPOINTS="site=mode;site2=mode" for subprocess tests;
   site "*" matches every site.  fired() exposes hit counts so tests
   can assert the injection actually triggered.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Dict, Optional, Tuple

_counter = 0
_lock = threading.Lock()

_modes: Dict[str, str] = {}
_fired: Dict[Tuple[str, str], int] = {}

# ---------------------------------------------------------------------------
# the chaos-site registry (tmlint TM305 + tests/test_lint.py coverage
# gate).  Every fail.inject/corrupt_bitmap call site must be reachable
# from this registry: literal sites appear in REGISTERED_SITES, and
# dynamic sites (crypto/degrade.py injects at the caller-supplied lane
# site, "batch.<scheme>" / "sched.<scheme>" / "bulk.<scheme>") must
# match a DYNAMIC_SITE_PREFIXES family.  set_mode() refuses to arm an
# unregistered site, so a typo'd chaos test fails loudly instead of
# silently never injecting — and the coverage test can assert every
# registered site is actually exercised by the chaos suites.
# Tests register throwaway sites with register().
# ---------------------------------------------------------------------------

REGISTERED_SITES = frozenset({
    # device-kernel entry seams (ops/)
    "ops.ed25519.verify_batch",   # the ladder/comb dispatch seam
    "ops.ed25519.comb",           # the fixed-base comb route (ADR-013)
    "ops.sr25519.verify_batch",   # the ristretto lane seam
    "ops.secp.verify_batch",      # the secp256k1 Straus lane seam
    #                               (default-on since ADR-015)
    # mesh data-plane seams (parallel/sharding.py, ADR-027): the
    # overlapped per-shard staging of the local compact path and the
    # mesh comb dispatch — a raise at either degrades that batch to
    # the next-inner path (single-device ladder / single-device comb)
    # with exact bitmaps, caught inside ops/ed25519 rather than
    # escaping to the degrade runtime
    "sharding.mesh_stage",
    "sharding.mesh_comb",
    # degradation-runtime lane sites (crypto/degrade.py submit/run):
    # one per (consumer, scheme) lane family — enumerated so the chaos
    # coverage gate can demand at least one exercised site per family
    "batch.ed25519", "batch.sr25519", "batch.secp256k1",
    "sched.ed25519", "sched.sr25519", "sched.secp256k1",
    "bulk.ed25519",
    # host-lane pool (crypto/lanepool.py, ADR-015): the sharded native
    # C verify — raise/latency/corrupt-bitmap all degrade to the
    # serial in-caller path with exact bitmaps
    "lanepool.verify",
    # block application pipeline (state/pipeline.py, ADR-017): the
    # stage worker's per-block entry, the async storage writer's
    # group-commit entry, and the GroupCommitDB write seam.  raise at
    # any of them drains the pipeline and degrades the window to the
    # strict sequential path; latency exercises handoff backpressure
    "pipeline.stage",
    "pipeline.commit",
    "kvdb.group_commit",
    # mempool ingress gate (mempool/ingress.py, ADR-018): the submit
    # seam (raise = fall back to synchronous in-caller admission with
    # identical ResponseCheckTx results; latency = queue-wait), the
    # worker's batched CheckTx stage (raise = per-tx synchronous
    # fallback inside the worker), and the post-block recheck
    # scheduling seam (raise = recheck runs synchronously in update()
    # on the commit path, exactly the pre-gate behavior)
    "ingress.admit",
    "ingress.checktx",
    "ingress.recheck",
    # in-process virtual network + scenario harness (networks/,
    # ADR-019): vnet.deliver fires on every submitted frame (raise =
    # the frame is dropped as chaos loss, counted under reason=chaos),
    # vnet.reorder fires whenever a reorder decision triggers,
    # vnet.partition fires on every partition/heal transition, and
    # harness.step fires at each scenario-step boundary (raise = the
    # scenario fails and dumps its stitched trace artifact)
    "vnet.deliver",
    "vnet.partition",
    "vnet.reorder",
    "harness.step",
    # consensus observatory (consensus/observatory.py, ADR-020): fires
    # on every stamp/receipt.  raise = the recording sheds (counted in
    # consensus_observatory_shed_total{reason=chaos}) while consensus
    # proceeds untouched — lifecycle telemetry must never be able to
    # take down the state machine it observes
    "observatory.record",
    # device observatory (crypto/devobs.py, ADR-021): fires on every
    # launch-record store.  raise = the record sheds (counted in
    # crypto_devobs_shed_total{reason=chaos}) while the device launch
    # and its bitmap proceed untouched; latency is absorbed into the
    # recording — the same contract observatory.record proved
    "devobs.record",
    # statesync fast-join (statesync/, ADR-022): statesync.fetch fires
    # per chunk-fetch attempt on a fetcher thread (raise = transport
    # fault charged to the picked peer's per-peer budget; latency =
    # slow fetch driving the per-chunk deadline / slow-peer
    # quarantine; corrupt-chunk = the fetched bytes are flipped so the
    # pre-app digest check must catch them, ban the sender and refetch
    # elsewhere), statesync.verify fires at the fetch-thread integrity
    # check (raise = verification machinery fault — retried like a
    # transport error, the app NEVER sees the chunk),
    # statesync.apply fires before each app apply_snapshot_chunk
    # (raise = app-layer restore failure, the snapshot is rejected),
    # and statesync.serve fires in the serving side's worker (raise =
    # the request is answered busy-with-retry-after, the server stays
    # up)
    "statesync.fetch",
    "statesync.verify",
    "statesync.apply",
    "statesync.serve",
    # adaptive control plane (libs/control.py, ADR-023): fires at the
    # top of every decision period.  raise = the WHOLE period's
    # decisions are skipped (counted under knob=period,
    # direction=skipped) and every knob reverts to its static
    # configured value — a malfunctioning controller must fail static,
    # never fail steering; latency is absorbed into the period
    "control.decide",
    # proposer fast path (ADR-024): propose.reap fires inside the
    # budgeted reap stage of create_proposal_block (raise = the
    # proposal degrades to an EMPTY tx list instead of stalling the
    # round; latency:<ms> consumes the reap budget so a deadline-aware
    # mempool returns a short reap), propose.parts fires at the
    # streaming part-set construction seam shared by the proposer and
    # blocksync (raise = fall back to the serial PartSet.from_data,
    # byte-identical parts), and merkle.bulk_hash fires inside the
    # pooled leaf-layer branch of the bulk digest (raise = the whole
    # leaf layer recomputes serially in the caller, identical digests)
    "propose.reap",
    "propose.parts",
    "merkle.bulk_hash",
    # bench backend probe (bench.py _probe_once, ISSUE 8): forces the
    # dead-backend (raise) and wedged-backend (latency:<ms> past the
    # probe timeout) classes deterministically, so the opportunistic
    # probe-retry window and the rc=0 host-fallback line are testable
    # without a real dead backend
    "bench.probe",
    # gossip observatory (p2p/netobs.py, ADR-025): fires on every
    # flow/rtt/receipt recording.  raise = the sample sheds (counted
    # in p2p_netobs_shed_total{reason=chaos}) while the frame's
    # delivery proceeds untouched — the same contract
    # observatory.record / devobs.record proved for their planes
    "netobs.record",
    # light serving plane (light/service.py, ADR-026): light.serve
    # fires at the top of LightServe.submit (raise = the request
    # degrades to the synchronous in-caller direct path — the exact
    # verification the caller would run without the service, identical
    # verdicts); light.coalesce fires before the worker groups a
    # batch's certificate verifications (raise = the batch degrades to
    # per-request direct certificate checks with no dedupe, identical
    # verdicts)
    "light.serve",
    "light.coalesce",
})

# families for sites assembled at runtime ONLY (f"batch.{scheme}" in
# crypto/batch.py, f"sched.{scheme}" in crypto/scheduler.py).
# lanepool.verify is deliberately NOT a prefix family: its one site is
# a static literal, and registering a prefix would let a typo'd
# "lanepool.verfy" arm silently — the exact failure the registry
# exists to prevent.  test_lint's coverage gate requires such literal
# non-ops sites to be armed individually instead.
DYNAMIC_SITE_PREFIXES = frozenset({"batch.", "sched.", "bulk."})

_extra_sites: set = set()


def register(site: str) -> str:
    """Register an ad-hoc site (tests, experiments).  Returns it."""
    with _lock:
        _extra_sites.add(site)
    return site


def is_registered(site: str) -> bool:
    if site == "*" or site in REGISTERED_SITES:
        return True
    with _lock:
        if site in _extra_sites:
            return True
    return any(site.startswith(p) for p in DYNAMIC_SITE_PREFIXES)


class InjectedFault(RuntimeError):
    """A chaos-injected device fault (mode "raise")."""


def _target() -> int:
    v = os.environ.get("FAIL_TEST_INDEX")
    return int(v) if v else -1


def fail_point(_site_id: int = 0):
    """Die (os._exit) if this is the $FAIL_TEST_INDEX-th fail point hit."""
    global _counter
    t = _target()
    if t < 0:
        return
    with _lock:
        current = _counter
        _counter += 1
    if current == t:
        os._exit(77)


def reset():
    """Back to a pristine state: counter, modes, hit counts, AD-HOC
    site registrations and the env-validation cache.  Clearing
    _extra_sites matters for the unregistered-site guard: a site one
    test registered must not let a later test's typo of the same name
    arm silently."""
    global _counter, _env_validated
    with _lock:
        _counter = 0
        _modes.clear()
        _fired.clear()
        _extra_sites.clear()
    _env_validated = None


# ---------------------------------------------------------------------------
# named chaos modes
# ---------------------------------------------------------------------------

def set_mode(site: str, mode: Optional[str]):
    """Arm (or with mode=None disarm) an injection mode at a named site.
    The mode stays armed until cleared — chaos tests drive the breaker
    through open/backoff/re-close by arming, verifying repeatedly, then
    disarming.  Arming an UNREGISTERED site raises: a typo'd site name
    would otherwise never fire and the chaos test would silently pass
    without injecting anything (register ad-hoc test sites with
    register())."""
    if mode is not None and not is_registered(site):
        raise ValueError(
            f"fail site {site!r} is not registered (REGISTERED_SITES / "
            f"DYNAMIC_SITE_PREFIXES in libs/fail.py, or fail.register)")
    with _lock:
        if mode is None:
            _modes.pop(site, None)
        else:
            _modes[site] = mode


def clear(site: Optional[str] = None):
    with _lock:
        if site is None:
            _modes.clear()
        else:
            _modes.pop(site, None)


def fired(site: str, mode: str) -> int:
    with _lock:
        return _fired.get((site, mode), 0)


_env_validated: Optional[str] = None


def _validate_env(env: str):
    """Every TM_TPU_FAILPOINTS key must be a registered site: a typo'd
    key would otherwise never match and the chaos subprocess would run
    green without ever injecting — the same silent failure set_mode()
    refuses.  Validated once per distinct env value, at the first
    inject() that reads it, so the error surfaces loudly inside the
    armed process."""
    global _env_validated
    if env == _env_validated:
        return
    for entry in env.split(";"):
        k, _, v = entry.partition("=")
        k = k.strip()
        if v and k and k != "*" and not is_registered(k):
            raise ValueError(
                f"TM_TPU_FAILPOINTS site {k!r} is not registered "
                f"(REGISTERED_SITES / DYNAMIC_SITE_PREFIXES in "
                f"libs/fail.py)")
    _env_validated = env


def _mode_for(site: str) -> Optional[str]:
    with _lock:
        m = _modes.get(site) or _modes.get("*")
    if m is not None:
        return m
    env = os.environ.get("TM_TPU_FAILPOINTS", "")
    if not env:
        return None
    _validate_env(env)
    for entry in env.split(";"):
        k, _, v = entry.partition("=")
        if v and k.strip() in (site, "*"):
            return v.strip()
    return None


def _count(site: str, mode: str):
    with _lock:
        _fired[(site, mode)] = _fired.get((site, mode), 0) + 1


# result-transform modes: no-ops at the entry hook, applied by their
# dedicated result helpers (corrupt_bitmap / corrupt_bytes)
_RESULT_MODES = frozenset({"corrupt-bitmap", "corrupt-chunk"})


def inject(site: str):
    """Entry hook of a named fail-point site: raise / stall / die per the
    armed mode.  Result-transform modes ("corrupt-bitmap",
    "corrupt-chunk") are no-ops here (see corrupt_bitmap /
    corrupt_bytes)."""
    mode = _mode_for(site)
    if mode is None or mode in _RESULT_MODES:
        return
    if mode == "raise":
        _count(site, mode)
        raise InjectedFault(f"injected fault at {site}")
    if mode.startswith("latency:"):
        _count(site, mode)
        time.sleep(float(mode.split(":", 1)[1]) / 1000.0)
        return
    if mode == "exit":
        _count(site, mode)
        os._exit(77)
    raise ValueError(f"unknown fail mode {mode!r} at {site}")


def corrupt_bitmap(site: str, bits):
    """Result hook of a device-lane site: under "corrupt-bitmap" return
    the inverted bitmap (a device replying with garbage), which the
    degradation runtime's host spot check must catch."""
    if _mode_for(site) == "corrupt-bitmap":
        import numpy as np
        _count(site, "corrupt-bitmap")
        return ~np.asarray(bits, dtype=bool)
    return bits


def corrupt_bytes(site: str, data: bytes) -> bytes:
    """Result hook of a byte-stream site: under "corrupt-chunk" flip
    the first byte (a peer serving garbage), which the statesync
    fetch-thread digest check must catch BEFORE the app sees it."""
    if _mode_for(site) == "corrupt-chunk":
        _count(site, "corrupt-chunk")
        if not data:
            return b"\xff"
        return bytes([data[0] ^ 0xFF]) + bytes(data[1:])
    return data
