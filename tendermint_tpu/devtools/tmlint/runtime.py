"""tmlint runtime sanitizers: the dynamic twins of the static passes.

CompileSentinel — compile-shape discipline at runtime.  The static
TM101 pass can only prove that sizes flow through bucket helpers; the
sentinel proves what actually happened: it snapshots the launch-bucket
set (ops/ed25519._seen_buckets, fed by every _record_launch) and the
jit-cache sizes of the registered kernel entries before a test, and
fails the test if a launch landed in a padded lane count outside the
known bucket shapes or a watched entry compiled more than expected.
Used as the opt-in `compile_sentinel` fixture (tests/conftest.py).

LockSanitizer — the lockset monitor.  Under TM_TPU_LOCKSAN=1 (or the
`locksan` pytest marker) threading.Lock/RLock/Condition are patched so
locks CREATED by tendermint_tpu modules are wrapped: each acquisition
records the per-thread held set and an acquisition that takes a
lower-ranked lock while holding a higher-ranked one (per
devtools/lockorder.py) is recorded as a violation the fixture fails
the test with.  Locks created by foreign code (jax, stdlib queues) get
the real primitive — zero overhead outside our modules.

This module may import jax-adjacent modules lazily (it reads
sys.modules, never forces an import); the static passes must NOT
import it.
"""
from __future__ import annotations

import linecache
import os
import re
import sys
import threading
from typing import Dict, List, Optional, Set, Tuple

from tendermint_tpu.devtools import lockorder


# ---------------------------------------------------------------------------
# compile sentinel
# ---------------------------------------------------------------------------

# jitted kernel entries watched for cache growth, per module.  Only
# modules ALREADY imported are inspected — the sentinel never forces a
# kernel module (and its compile cost) into a test that didn't use it.
ENTRY_NAMES = [
    ("tendermint_tpu.ops.ed25519", "verify_kernel"),
    ("tendermint_tpu.ops.ed25519", "comb_kernel"),
    ("tendermint_tpu.ops.ed25519", "comb_build_kernel"),
    ("tendermint_tpu.ops.sr25519", "_verify_core"),
    ("tendermint_tpu.ops.secp", "_verify_core"),
]


class CompileSentinel:
    """Per-test XLA bucket/compile accounting.

    start() snapshots; check() raises AssertionError when a NEW launch
    bucket's padded lane count is outside the known bucket set, and
    returns a report dict ({"new_buckets", "compiles"}) either way.
    `max_new_compiles` (default None = unlimited) additionally bounds
    total watched-entry cache growth — a test that reuses the shared
    nb=64 bucket passes with max_new_compiles=0.
    """

    def __init__(self, extra_entries=None,
                 max_new_compiles: Optional[int] = None):
        self.extra_entries = list(extra_entries or [])
        self.max_new_compiles = max_new_compiles
        self._buckets0: Set[tuple] = set()
        self._caches0: Dict[str, int] = {}

    # -- plumbing ------------------------------------------------------

    @staticmethod
    def _edops():
        return sys.modules.get("tendermint_tpu.ops.ed25519")

    def _entries(self):
        for mod, attr in ENTRY_NAMES:
            m = sys.modules.get(mod)
            fn = getattr(m, attr, None) if m is not None else None
            if fn is not None and hasattr(fn, "_cache_size"):
                yield f"{mod}.{attr}", fn
        for label, fn in self.extra_entries:
            if hasattr(fn, "_cache_size"):
                yield label, fn

    @staticmethod
    def _seen_buckets() -> Set[tuple]:
        ed = CompileSentinel._edops()
        if ed is None:
            return set()
        with ed._launch_lock:
            return set(ed._seen_buckets)

    @staticmethod
    def bucket_allowed(nb: int, shards: int = 1) -> bool:
        """Is `nb` a known padded-lane shape?  Power-of-two lane
        buckets (ops/ed25519.bucket_size) up to MAX_CHUNK, multiples
        of SPLIT_CHUNK or SPLIT_CHUNK_SMALL (the split path's two
        chunks), MAX_CHUNK multiples (pipelined sub-batching), and on
        the mesh the per-shard rounding of any of those."""
        ed = CompileSentinel._edops()
        if ed is None:  # no kernel module imported -> nothing launched
            return True
        if nb <= 0:
            return False
        if shards > 1:
            if nb % shards:
                return False
            # mesh paths round the bucket UP to a shard multiple; the
            # underlying per-shard shape still obeys the lane buckets
            per = nb // shards
            return CompileSentinel.bucket_allowed(per) or \
                CompileSentinel.bucket_allowed(nb)
        if nb == ed.bucket_size(nb) and nb <= ed.MAX_CHUNK:
            return True
        return any(nb % chunk == 0 for chunk in (
            ed.SPLIT_CHUNK_SMALL, ed.SPLIT_CHUNK, ed.MAX_CHUNK))

    # -- lifecycle -----------------------------------------------------

    def start(self) -> "CompileSentinel":
        self._buckets0 = self._seen_buckets()
        self._caches0 = {label: fn._cache_size()
                         for label, fn in self._entries()}
        return self

    def check(self) -> dict:
        new = self._seen_buckets() - self._buckets0
        bad = []
        for rec in sorted(new):
            path, nb, shards = rec[0], rec[1], rec[2] if len(rec) > 2 \
                else 1
            if not self.bucket_allowed(nb, shards):
                bad.append(rec)
        compiles = {}
        for label, fn in self._entries():
            grew = fn._cache_size() - self._caches0.get(label, 0)
            if grew > 0:
                compiles[label] = grew
        report = {"new_buckets": sorted(new), "compiles": compiles}
        assert not bad, (
            f"compile sentinel: launch bucket(s) outside the known "
            f"shape set: {bad} — pad through ops/ed25519.bucket_size / "
            f"chunk constants (report: {report})")
        if self.max_new_compiles is not None:
            total = sum(compiles.values())
            assert total <= self.max_new_compiles, (
                f"compile sentinel: {total} new kernel compile(s) "
                f"(> {self.max_new_compiles} allowed): {compiles} — "
                f"reuse the shared lane buckets (report: {report})")
        return report


# ---------------------------------------------------------------------------
# lockset monitor
# ---------------------------------------------------------------------------

_ASSIGN_RE = re.compile(r"^\s*(?:self\.)?(\w+)\s*[:=]")


def _creation_lock_id(frame) -> Optional[str]:
    """Derive the lockorder id for a lock created at `frame`:
    path from the executing code object, attr name from the source
    line, class from self's MRO (handles BaseService._mtx constructed
    while self is a subclass)."""
    fname = frame.f_code.co_filename
    root = _repo_root()
    try:
        rel = os.path.relpath(fname, root).replace(os.sep, "/")
    except ValueError:
        return None
    if rel.startswith(".."):
        return None
    line = linecache.getline(fname, frame.f_lineno)
    m = _ASSIGN_RE.match(line)
    if not m:
        return None
    attr = m.group(1)
    slf = frame.f_locals.get("self")
    if slf is not None:
        for klass in type(slf).__mro__:
            cand = f"{rel}:{klass.__name__}.{attr}"
            if cand in lockorder.LOCK_ORDER:
                return cand
        return f"{rel}:{type(slf).__name__}.{attr}"
    return f"{rel}:{attr}"


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))


class _SanLock:
    """Wraps a real Lock/RLock; reports acquisitions to the sanitizer.
    Implements the Condition lock protocol (_release_save /
    _acquire_restore / _is_owned) so a wrapped RLock can back a
    threading.Condition."""

    def __init__(self, inner, lock_id: Optional[str], san:
                 "LockSanitizer"):
        self._inner = inner
        self.lock_id = lock_id
        self.rank = lockorder.rank(lock_id) if lock_id else None
        self._san = san

    # -- core protocol -------------------------------------------------

    def acquire(self, *a, **kw):
        got = self._inner.acquire(*a, **kw)
        if got:
            self._san._on_acquire(self)
        return got

    def release(self):
        self._san._on_release(self)
        self._inner.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def locked(self):
        return self._inner.locked()

    # -- Condition lock protocol ----------------------------------------

    def _release_save(self):
        self._san._on_release(self)
        if hasattr(self._inner, "_release_save"):
            return self._inner._release_save()
        self._inner.release()
        return None

    def _acquire_restore(self, state):
        if hasattr(self._inner, "_acquire_restore"):
            self._inner._acquire_restore(state)
        else:
            self._inner.acquire()
        self._san._on_acquire(self)

    def _is_owned(self):
        if hasattr(self._inner, "_is_owned"):
            return self._inner._is_owned()
        # plain-Lock fallback (threading.Condition's own heuristic)
        if self._inner.acquire(False):
            self._inner.release()
            return False
        return True

    def __repr__(self):
        return f"<SanLock {self.lock_id or '?'} rank={self.rank}>"


class LockSanitizer:
    """Patch threading lock factories; record per-thread lock order.

    install()/uninstall() bracket a test.  Only locks whose creation
    frame executes a file under this repo are wrapped — foreign code
    gets the real primitives.  Violations (lower rank acquired under
    higher rank) collect in .violations; the observed acquired-while-
    holding edge set in .edges.
    """

    def __init__(self, include_paths: Tuple[str, ...] =
                 ("tendermint_tpu/",),
                 rank_overrides: Optional[Dict[str, int]] = None):
        self.include_paths = include_paths
        self.rank_overrides = dict(rank_overrides or {})
        self.violations: List[str] = []
        self.edges: Set[Tuple[str, str]] = set()
        self._tls = threading.local()
        self._mtx = threading.Lock()  # guards violations/edges
        self._orig = None
        self._enabled = False

    # -- wrapping ------------------------------------------------------

    def _should_wrap(self, frame) -> bool:
        fname = frame.f_code.co_filename
        root = _repo_root()
        try:
            rel = os.path.relpath(fname, root).replace(os.sep, "/")
        except ValueError:
            return False
        if rel.startswith("tendermint_tpu/devtools/"):
            return False  # never instrument the instrumentation
        return any(rel.startswith(p) for p in self.include_paths)

    def _wrap(self, inner, frame):
        lock_id = _creation_lock_id(frame)
        w = _SanLock(inner, lock_id, self)
        if lock_id in self.rank_overrides:
            w.rank = self.rank_overrides[lock_id]
        return w

    def _caller_frame(self):
        f = sys._getframe(2)
        # skip our own factory frames (Condition() -> RLock())
        while f is not None and f.f_code.co_filename == __file__:
            f = f.f_back
        return f

    def install(self):
        assert self._orig is None, "LockSanitizer already installed"
        self._orig = (threading.Lock, threading.RLock,
                      threading.Condition)
        orig_lock, orig_rlock, orig_cond = self._orig
        san = self

        def make(factory):
            def _factory():
                inner = factory()
                f = san._caller_frame()
                if f is not None and san._should_wrap(f):
                    return san._wrap(inner, f)
                return inner
            return _factory

        def cond_factory(lock=None):
            if lock is None:
                inner = orig_rlock()
                f = san._caller_frame()
                if f is not None and san._should_wrap(f):
                    lock = san._wrap(inner, f)
                else:
                    lock = inner
            return orig_cond(lock)

        threading.Lock = make(orig_lock)
        threading.RLock = make(orig_rlock)
        threading.Condition = cond_factory
        self._enabled = True
        return self

    def uninstall(self):
        if self._orig is not None:
            (threading.Lock, threading.RLock,
             threading.Condition) = self._orig
            self._orig = None
        self._enabled = False  # surviving wrapped locks go quiet

    def __enter__(self):
        return self.install()

    def __exit__(self, *exc):
        self.uninstall()
        return False

    # -- tracking ------------------------------------------------------

    def _stack(self) -> List[_SanLock]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _on_acquire(self, lock: _SanLock):
        if not self._enabled:
            return
        st = self._stack()
        reentrant = any(h is lock for h in st)
        if not reentrant and lock.rank is not None:
            for held in st:
                if held.rank is None or held is lock:
                    continue
                if held.rank >= lock.rank:
                    with self._mtx:
                        self.violations.append(
                            f"acquired {lock.lock_id} (rank "
                            f"{lock.rank}) while holding "
                            f"{held.lock_id} (rank {held.rank}) on "
                            f"thread {threading.current_thread().name}")
        if not reentrant:
            with self._mtx:
                for held in st:
                    if held.lock_id and lock.lock_id and \
                            held is not lock:
                        self.edges.add((held.lock_id, lock.lock_id))
        st.append(lock)

    def _on_release(self, lock: _SanLock):
        if not self._enabled:
            return
        st = self._stack()
        for i in range(len(st) - 1, -1, -1):
            if st[i] is lock:
                del st[i]
                return
