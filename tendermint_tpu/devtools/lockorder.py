"""The declared lock-order table for the verify stack.

Five PRs of concurrency (degradation runtime, VerifyScheduler,
DeviceLRU, comb table index, flight recorder) left ~25 locks in the
core modules.  This table makes the acquisition order an explicit,
machine-checked contract: tmlint's static pass builds the
acquires-while-holding graph from the AST and the lockset monitor
(TM_TPU_LOCKSAN=1) records the real acquisition order at runtime —
both fail on an edge that acquires a LOWER-ranked lock while holding a
HIGHER-ranked one.

Rules of the table:

  * A lock id is "<path>:<Class>.<attr>" for instance locks and
    "<path>:<name>" for module-level locks, with <path> relative to the
    repo root.  tmlint derives the same ids from creation sites
    (`self._x = threading.Lock()` / `_x = threading.Lock()`), so adding
    a lock without a row here fails the TM203 rule in core modules, and
    a row whose creation site disappeared fails TM204 (no table rot in
    either direction).
  * Lower rank = acquired FIRST.  While holding rank r, only locks of
    rank > r may be acquired.  Two locks that are never nested may sit
    anywhere relative to each other; give every new lock its own value
    so a future nesting has a defined verdict.
  * Utility locks everything calls into (metrics, trace ring) rank
    HIGHEST: they must always be acquired last and hold nothing.
  * Condition variables rank like locks; waiting on the condition you
    hold is allowed (wait releases it), waiting on anything else under
    a lock is a blocking-call finding (TM202).

Intended nestings this table encodes:

  degrade._runtime_lock (5)  -> metrics Registry/_Metric (80/84):
      runtime() constructs CryptoMetrics under the install lock.
  VerifyScheduler._cond (20) -> _stats_lock (28):
      submit/evict update pipeline stats while holding the queue cond.
  ed25519._table_key_lock (44) -> DeviceLRU._lock (48):
      eviction repointing peeks surviving cache entries while holding
      the key index.
"""
from __future__ import annotations

# rank by lock id; see module docstring for the id grammar
LOCK_ORDER = {
    # -- light client trusted-state advance (light/, ADR-026): the
    # client lock serializes the store read -> verify -> save path and
    # is held across the verifier (scheduler _cond 20) and the trusted
    # store (kvdb 65-69), so it must rank below both
    "tendermint_tpu/light/client.py:Client._lock": 8,

    # -- light serving plane (light/service.py, ADR-026): ingress
    # discipline — _cond guards the admission queue + coalesce groups
    # ONLY (bookkeeping); the verifier, scheduler (20), stores and
    # metrics are all called with it released.  _rl_lock (per-client
    # token buckets), _cur_lock (follow cursors; block-store reads run
    # with it released) and _stats_lock are leaves taken alone.
    "tendermint_tpu/light/service.py:LightServe._cond": 21,
    "tendermint_tpu/light/service.py:LightServe._rl_lock": 23,
    "tendermint_tpu/light/service.py:LightServe._cur_lock": 25,
    "tendermint_tpu/light/service.py:LightServe._stats_lock": 37,

    # -- process-global installers (held while constructing the world) --
    "tendermint_tpu/crypto/degrade.py:_runtime_lock": 5,
    "tendermint_tpu/crypto/scheduler.py:_global_lock": 10,
    "tendermint_tpu/crypto/lanepool.py:_install_lock": 12,
    "tendermint_tpu/state/pipeline.py:_install_lock": 13,

    # -- block application pipeline (ADR-017): _busy serializes whole
    # windows and is taken before everything the window touches (the
    # _cond bookkeeping, scheduler 20, kvdb 67-69); _cond itself is
    # held only for bookkeeping — stores, scheduler and metrics are
    # all called outside it
    "tendermint_tpu/state/pipeline.py:BlockPipeline._busy": 14,
    "tendermint_tpu/state/pipeline.py:BlockPipeline._cond": 16,

    # -- mempool ingress gate (ADR-018): _cond guards the admission +
    # recheck queues only (bookkeeping); the mempool, scheduler (20),
    # app and metrics are all called with it released.  _rl_lock
    # (token buckets) and _stats_lock are leaves taken alone.
    "tendermint_tpu/mempool/ingress.py:IngressGate._cond": 17,
    "tendermint_tpu/mempool/ingress.py:IngressGate._rl_lock": 18,
    "tendermint_tpu/mempool/ingress.py:IngressGate._stats_lock": 19,

    # -- network harness (networks/, ADR-019): the harness lock (11)
    # wraps scenario bookkeeping and may drive vnet fault APIs; the
    # vnet engine condition (15) guards heap/policies/pending and is
    # released before inbox pushes and reactor dispatch; each endpoint
    # inbox condition (22) is taken alone (a dispatcher holding 22 must
    # never acquire 15 — it reads the running flag lock-free instead)
    "tendermint_tpu/networks/harness.py:NetHarness._lock": 11,
    "tendermint_tpu/networks/vnet.py:VirtualNetwork._cond": 15,
    "tendermint_tpu/networks/vnet.py:_Endpoint._cond": 22,

    # -- VerifyScheduler pipeline --
    "tendermint_tpu/crypto/scheduler.py:VerifyScheduler._cond": 20,
    "tendermint_tpu/crypto/scheduler.py:VerifyScheduler._res_lock": 24,
    "tendermint_tpu/crypto/scheduler.py:VerifyScheduler._stats_lock": 28,

    # -- statesync fast-join (statesync/, ADR-022): the metrics-
    # bundle install lock (27) constructs StateSyncMetrics under it
    # (Registry 80); the syncer discovery lock (31), the per-peer
    # book (33; its ban callback runs with the lock RELEASED) and the
    # reactor's response-routing / serve-queue conditions (34/35) are
    # bookkeeping leaves — app calls, peer sends and metrics all
    # happen outside them.  The restore ledger (63) buffers chunk
    # writes through GroupCommitDB (67) while held; group COMMITS run
    # with it released (commit_mutex 65)
    "tendermint_tpu/statesync/syncer.py:_metrics_lock": 27,
    "tendermint_tpu/statesync/syncer.py:_cfg_lock": 29,
    "tendermint_tpu/statesync/syncer.py:Syncer._lock": 31,
    "tendermint_tpu/statesync/syncer.py:_PeerBook._lock": 33,
    "tendermint_tpu/statesync/reactor.py:StateSyncReactor._chunks_cv": 34,
    "tendermint_tpu/statesync/reactor.py:StateSyncReactor._serve_cv": 35,
    # _commit_lock is held across a whole take_group+commit_group unit
    # (nests GroupCommitDB._commit_mutex 65 / _lock 67) so groups land
    # strictly in take order under concurrent fetcher threads; the
    # buffer lock (63) is never held while committing
    "tendermint_tpu/statesync/ledger.py:RestoreLedger._commit_lock": 61,
    "tendermint_tpu/statesync/ledger.py:RestoreLedger._lock": 63,

    # -- batch verifier / caches --
    "tendermint_tpu/crypto/lanepool.py:HostLanePool._lock": 30,
    "tendermint_tpu/crypto/batch.py:SigCache._lock": 32,

    # -- degradation runtime --
    "tendermint_tpu/crypto/degrade.py:CircuitBreaker._lock": 36,
    "tendermint_tpu/crypto/degrade.py:DeviceLaneRuntime._pool_lock": 38,
    "tendermint_tpu/crypto/degrade.py:DeviceLaneRuntime._backend_lock": 40,
    # compile-time bookkeeping of the lane worker: a leaf — compiling()
    # and collect()'s deadline loop each take it alone, holding nothing
    "tendermint_tpu/crypto/degrade.py:_CompileClock._lock": 42,

    # -- device-resident caches and launch bookkeeping (ops/) --
    "tendermint_tpu/ops/ed25519.py:_table_key_lock": 44,
    "tendermint_tpu/ops/ed25519.py:DeviceLRU._lock": 48,
    "tendermint_tpu/ops/ed25519.py:_base_comb_lock": 52,
    "tendermint_tpu/ops/ed25519.py:_launch_lock": 54,
    "tendermint_tpu/parallel/sharding.py:_PLANE_LOCK": 57,
    "tendermint_tpu/parallel/sharding.py:_DataPlane._lock": 58,

    # -- libs/ leaves --
    "tendermint_tpu/libs/service.py:BaseService._mtx": 60,
    "tendermint_tpu/libs/fail.py:_lock": 62,
    "tendermint_tpu/libs/log.py:_lock": 64,
    "tendermint_tpu/libs/native.py:_lock": 66,
    # GroupCommitDB: _commit_mutex is held across a whole group commit
    # (membership check -> inner write_batch -> removal) and so nests
    # the buffer lock and the wrapped DB's lock; the buffer lock (_lock)
    # itself is never held while calling the inner DB
    "tendermint_tpu/libs/kvdb.py:GroupCommitDB._commit_mutex": 65,
    "tendermint_tpu/libs/kvdb.py:GroupCommitDB._lock": 67,
    "tendermint_tpu/libs/kvdb.py:MemDB._lock": 68,
    "tendermint_tpu/libs/kvdb.py:SQLiteDB._lock": 69,
    "tendermint_tpu/libs/autofile.py:Group._lock": 70,
    "tendermint_tpu/libs/flowrate.py:Monitor._lock": 72,
    # gossip observatory table (p2p/netobs.py, ADR-025): a leaf —
    # every recorder takes it alone (fail.inject runs BEFORE
    # acquisition) and may be called under the vnet engine condition
    # (15) or a consensus seam, so it must outrank both;
    # publish_pending() releases it before touching slo (76) or the
    # metrics locks (80/84)
    "tendermint_tpu/p2p/netobs.py:NetObs._lock": 73,
    # consensus observatory ring (consensus/observatory.py, ADR-020):
    # a leaf — stamp()/receipt() take it alone (fail.inject runs
    # BEFORE acquisition), and publish_pending() releases it before
    # touching slo (76) or the metrics locks (80/84)
    "tendermint_tpu/consensus/observatory.py:Observatory._lock": 74,
    # SLO estimator ring (libs/slo.py, ADR-016): a leaf like the
    # metrics locks — observe() takes it alone, and the read side
    # (stream_report) sorts a snapshot OUTSIDE it
    "tendermint_tpu/libs/slo.py:SloEstimator._lock": 76,
    # device observatory ring (crypto/devobs.py, ADR-021): a leaf —
    # record()/ledger_* take it alone (fail.inject runs BEFORE
    # acquisition), and publish_pending() releases it before touching
    # slo (76... metrics 80/84 — publication runs with the ring lock
    # dropped, so the lower slo rank is never acquired under it)
    "tendermint_tpu/crypto/devobs.py:DevObs._lock": 78,
    # adaptive control plane (libs/control.py, ADR-023): the install
    # lock ranks with the other process-global install locks (it holds
    # is_running()'s _mtx 60 check under it); Controller._lock is a
    # LEAF — registry/ring/bookkeeping only, every knob setter (which
    # acquires pipeline 14/16, ingress 18, scheduler 20...) and every
    # metrics/trace publication runs with it RELEASED
    "tendermint_tpu/libs/control.py:_global_lock": 26,
    "tendermint_tpu/libs/control.py:Controller._lock": 79,

    # -- observability: always acquired last, hold nothing --
    "tendermint_tpu/libs/metrics.py:Registry._lock": 80,
    "tendermint_tpu/libs/metrics.py:_Metric._lock": 84,
    "tendermint_tpu/libs/trace.py:Tracer._lock": 90,
}


def rank(lock_id: str):
    """Declared rank of a lock id, or None when unranked."""
    return LOCK_ORDER.get(lock_id)
