"""tmlint: the tier-1 gate plus rule/sanitizer self-tests (ISSUE 6,
docs/adr/adr-014-tmlint.md).

Three layers:

  1. the gate — the static suite over the real tree must be clean
     against devtools/lint_baseline.json (which is empty: violations
     get fixed, not baselined), and docs/lint.md must be current;
  2. rule self-tests — every rule is exercised on small positive AND
     negative fixture snippets, so a rule regression (a pass that
     silently stops matching) fails loudly here, not months later;
  3. sanitizer proofs — the compile sentinel fails a deliberately
     bucket-violating launch record and passes the real nb=64 suite
     (tests/test_batch_verifier.py carries the fixture), and the
     lockset monitor detects a seeded inversion and runs green over a
     real scheduler round trip.
"""
from __future__ import annotations

import ast
import json
import os
import threading

import pytest

from tendermint_tpu.devtools import lockorder
from tendermint_tpu.devtools.tmlint import core
from tendermint_tpu.devtools.tmlint import passes_hygiene
from tendermint_tpu.devtools.tmlint import passes_locks
from tendermint_tpu.devtools.tmlint import passes_shape
from tendermint_tpu.devtools.tmlint.core import Corpus, SourceFile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def corpus_of(**files) -> Corpus:
    """Corpus from inline snippets; keys use __ for / (keyword-arg
    friendly) or pass a dict via files_."""
    c = Corpus(root="/nonexistent")
    for path, src in files.items():
        path = path.replace("__", "/")
        try:
            tree, err = ast.parse(src), None
        except SyntaxError as e:
            tree, err = None, str(e)
        c.files[path] = SourceFile(path, src, tree, err)
    return c


def hits(findings, rule, path=None):
    return [f for f in findings
            if f.rule == rule and (path is None or f.path == path)]


# ---------------------------------------------------------------------------
# 1. the gate
# ---------------------------------------------------------------------------

def test_tree_is_clean_against_baseline():
    """The tier-1 tmlint gate: zero unbaselined findings on the tree.
    THE static invariants — bucket discipline, lock order, daemon
    threads, optional deps, chaos/trace/metric registries — hold."""
    findings = core.run_lint(root=ROOT)
    baseline = core.load_baseline(
        os.path.join(ROOT, "devtools", "lint_baseline.json"))
    new = [f for f in findings if f.key() not in baseline]
    assert not new, "tmlint found unbaselined violations:\n" + \
        "\n".join(f.render() for f in new)
    stale = set(baseline) - {f.key() for f in findings}
    assert not stale, f"stale baseline entries: {sorted(stale)}"


def test_docs_lint_md_current():
    """scripts/metricsgen.py-style staleness gate for docs/lint.md."""
    with open(os.path.join(ROOT, "docs", "lint.md"),
              encoding="utf-8") as f:
        assert f.read() == core.generate_docs(), (
            "docs/lint.md is stale; run "
            "python -m tendermint_tpu.devtools.tmlint --docs")


def test_cli_json_and_report(tmp_path, capsys):
    """--json output is consumable by scripts/lint_report.py."""
    rc = core.main(["--json", "--baseline",
                    "devtools/lint_baseline.json"])
    out = capsys.readouterr().out
    data = json.loads(out)
    assert rc == 0 and data["new"] == []
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "lint_report", os.path.join(ROOT, "scripts", "lint_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    p = tmp_path / "lint.json"
    p.write_text(out)
    rc = mod.main([str(p)])
    rep = capsys.readouterr().out
    assert rc == 0 and "tmlint report" in rep


# ---------------------------------------------------------------------------
# 2. rule self-tests (positive fixture = detected, negative = clean)
# ---------------------------------------------------------------------------

def test_rule_tm101_raw_shape():
    bad = corpus_of(**{"tendermint_tpu/ops/fx.py": """
import jax, jax.numpy as jnp
verify_kernel = jax.jit(lambda x: x)
def route(xs):
    n = len(xs)
    buf = jnp.zeros(n)
    return verify_kernel(buf)
"""})
    f = hits(passes_shape.check(bad), "TM101")
    # two findings: the raw-sized constructor AND the tainted buffer
    # reaching the jit entry
    assert any("jnp.zeros" in x.msg for x in f)
    assert any("verify_kernel" in x.msg for x in f)

    good = corpus_of(**{"tendermint_tpu/ops/fx.py": """
import jax, jax.numpy as jnp
verify_kernel = jax.jit(lambda x: x)
def bucket_size(n):
    return max(64, 1 << (n - 1).bit_length())
def route(xs):
    n = len(xs)
    nb = bucket_size(n)
    buf = jnp.zeros(nb)
    return verify_kernel(buf)
"""})
    assert not hits(passes_shape.check(good), "TM101")


def test_rule_tm101_jit_entry_argument():
    bad = corpus_of(**{"tendermint_tpu/ops/fx.py": """
import jax
verify_kernel = jax.jit(lambda x: x)
def route(xs, arr):
    return verify_kernel(arr[:len(xs)])
"""})
    f = hits(passes_shape.check(bad), "TM101")
    assert len(f) == 1 and "verify_kernel" in f[0].msg
    # padding with a blessed width is the sanctioned idiom
    good = corpus_of(**{"tendermint_tpu/ops/fx.py": """
import jax
import numpy as np
verify_kernel = jax.jit(lambda x: x)
def route(xs, arr):
    n = len(xs)
    nb = bucket_size(n)
    arr = np.pad(arr, (0, nb - n))
    return verify_kernel(arr)
"""})
    assert not hits(passes_shape.check(good), "TM101")


def test_rule_tm102_uncached_jit():
    bad = corpus_of(**{"tendermint_tpu/ops/fx.py": """
import jax
def route(g, x):
    return jax.jit(g)(x)
"""})
    assert len(hits(passes_shape.check(bad), "TM102")) == 1
    good = corpus_of(**{"tendermint_tpu/ops/fx.py": """
import jax
class P:
    def fn(self, g, key):
        f = jax.jit(g)
        self._fns.setdefault(key, f)
        return self._fns[key]
"""})
    assert not hits(passes_shape.check(good), "TM102")


LOCK_FIXTURE = """
import threading
import time
_global_lock = threading.Lock()
class VerifyScheduler:
    def __init__(self):
        self._cond = threading.Condition()
    def bad_order(self):
        with self._cond:
            with _global_lock:
                pass
    def bad_block(self):
        with self._cond:
            time.sleep(0.1)
    def ok_wait(self):
        with self._cond:
            self._cond.wait(0.1)
"""


def test_rule_tm201_lock_order_inversion():
    """Seeded inversion: the fixture reuses the DECLARED ids
    (crypto/scheduler.py _cond rank 20, _global_lock rank 10), nested
    the wrong way round."""
    c = corpus_of(**{"tendermint_tpu__crypto__scheduler.py": LOCK_FIXTURE})
    f = hits(passes_locks.check(c), "TM201")
    assert len(f) == 1 and "_global_lock" in f[0].msg \
        and f[0].qual == "VerifyScheduler.bad_order"
    # error-recovery paths are NOT blind spots: the same inversion
    # nested only inside an except handler is still found
    only_except = corpus_of(**{"tendermint_tpu/crypto/scheduler.py": """
import threading
_global_lock = threading.Lock()
class VerifyScheduler:
    def __init__(self):
        self._cond = threading.Condition()
    def recover(self):
        with self._cond:
            try:
                pass
            except Exception:
                with _global_lock:
                    pass
"""})
    f2 = hits(passes_locks.check(only_except), "TM201")
    assert len(f2) == 1 and f2[0].qual == "VerifyScheduler.recover"


def test_rule_tm202_blocking_and_condition_wait():
    c = corpus_of(**{"tendermint_tpu__crypto__scheduler.py": LOCK_FIXTURE})
    f = hits(passes_locks.check(c), "TM202")
    # time.sleep under _cond flagged; _cond.wait under _cond is NOT
    assert len(f) == 1 and f[0].qual == "VerifyScheduler.bad_block"
    assert ".sleep()" in f[0].msg


def test_rule_tm203_tm204_table_parity():
    c = corpus_of(**{"tendermint_tpu/crypto/fx.py": """
import threading
_mystery_lock = threading.Lock()
"""})
    findings = passes_locks.check(c)
    f = hits(findings, "TM203")
    assert len(f) == 1 and "_mystery_lock" in f[0].msg
    # every declared id is absent from this tiny corpus -> TM204 keeps
    # the table honest in the other direction
    assert len(hits(findings, "TM204")) == len(lockorder.LOCK_ORDER)


def test_rule_tm301_thread_daemon():
    bad = corpus_of(**{"tendermint_tpu/libs/fx.py": """
import threading
def spawn():
    threading.Thread(target=print).start()
"""})
    assert len(hits(passes_hygiene.check(bad), "TM301")) == 1
    good = corpus_of(**{"tendermint_tpu/libs/fx.py": """
import threading
def spawn():
    threading.Thread(target=print, daemon=True).start()
def spawn_joined():
    ts = [threading.Thread(target=print)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
"""})
    assert not hits(passes_hygiene.check(good), "TM301")
    # a STRING join in the same function must not satisfy the
    # joined-by-creator exemption
    strjoin = corpus_of(**{"tendermint_tpu/libs/fx.py": """
import threading
def spawn(parts):
    label = ", ".join(parts)
    threading.Thread(target=print, name=label).start()
"""})
    assert len(hits(passes_hygiene.check(strjoin), "TM301")) == 1


def test_rule_tm302_optional_import():
    bad = corpus_of(**{"tendermint_tpu/libs/fx.py": "import grpc\n"})
    assert len(hits(passes_hygiene.check(bad), "TM302")) == 1
    good = corpus_of(**{"tendermint_tpu/libs/fx.py": """
try:
    import grpc
except ImportError:
    grpc = None
"""})
    assert not hits(passes_hygiene.check(good), "TM302")


def test_rule_tm303_backslash_fstring():
    """The py3.10 breakage class: backslash inside a replacement field.
    Detected from TOKENS — on 3.10 ast.parse refuses the file outright
    (which is also asserted: the snippet must stay a SyntaxError here,
    or this rule's motivation changed under our feet)."""
    src = 'x = 1\ny = f"{x\\t}"\n'
    found = passes_hygiene.find_fstring_backslashes(src)
    assert len(found) == 1 and found[0][0] == 2
    c = corpus_of(**{"tendermint_tpu/libs/fx.py": src})
    findings = passes_hygiene.check(c)
    assert len(hits(findings, "TM303")) == 1
    # literal-part escapes are FINE on 3.10 and must not be flagged
    ok = 'y = f"a\\n{x}b\\t"\nz = f"{{literal}}\\n"\n'
    assert not passes_hygiene.find_fstring_backslashes(ok)
    # and the rule reports where the interpreter would reject the file
    import sys
    if sys.version_info < (3, 12):
        with pytest.raises(SyntaxError):
            ast.parse(src)


def test_rule_tm304_except_pass():
    bad = corpus_of(**{"tendermint_tpu/ops/fx.py": """
def f():
    try:
        g()
    except Exception:
        pass
"""})
    assert len(hits(passes_hygiene.check(bad), "TM304")) == 1
    good = corpus_of(**{"tendermint_tpu/ops/fx.py": """
def f():
    try:
        g()
    except Exception:  # noqa: BLE001 - probe failure is not fatal
        pass
"""})
    assert not hits(passes_hygiene.check(good), "TM304")
    # outside the hot-path scope the rule does not apply
    elsewhere = corpus_of(**{"tendermint_tpu/rpc/fx.py": """
def f():
    try:
        g()
    except Exception:
        pass
"""})
    assert not hits(passes_hygiene.check(elsewhere), "TM304")


FAIL_REGISTRY = """
REGISTERED_SITES = frozenset({"good.site"})
DYNAMIC_SITE_PREFIXES = frozenset({"lane."})
"""


def test_rule_tm305_fail_sites():
    c = corpus_of(**{
        "tendermint_tpu__libs__fail.py": FAIL_REGISTRY,
        "tendermint_tpu__ops__fx.py": """
from tendermint_tpu.libs import fail
def f():
    fail.inject("bad.site")
    fail.inject("good.site")
    fail.inject("lane.anything")
    fail.inject(dynamic_name)
""",
    })
    f = hits(passes_hygiene.check(c), "TM305")
    assert len(f) == 1 and "bad.site" in f[0].msg


def test_rule_tm306_trace_spans():
    c = corpus_of(**{
        "tendermint_tpu__libs__trace.py":
            'KNOWN_SPANS = frozenset({"known.span"})\n',
        "tendermint_tpu__ops__fx.py": """
from tendermint_tpu.libs import trace
def f():
    with trace.span("known.span"):
        trace.instant("rogue.span")
""",
    })
    f = hits(passes_hygiene.check(c), "TM306")
    assert len(f) == 1 and "rogue.span" in f[0].msg


def test_rule_tm307_metric_attrs():
    c = corpus_of(**{
        "tendermint_tpu__libs__metrics.py": """
class CryptoMetrics:
    def __init__(self, reg):
        self.known_total = reg.counter("c", "known_total", "")
""",
        "tendermint_tpu__crypto__fx.py": """
def f(rt):
    rt.metrics.known_total.inc()
    rt.metrics.tyop_total.inc()
""",
    })
    f = hits(passes_hygiene.check(c), "TM307")
    assert len(f) == 1 and "tyop_total" in f[0].msg


# ---------------------------------------------------------------------------
# registries stay honest in BOTH directions
# ---------------------------------------------------------------------------

CHAOS_TEST_FILES = ("test_chaos_matrix.py", "test_comb.py",
                    "test_control.py", "test_degrade.py",
                    "test_devobs.py", "test_ingress.py",
                    "test_latency_observatory.py",
                    "test_light_serve.py", "test_mesh_sweep.py",
                    "test_netharness.py", "test_netobs.py",
                    "test_observatory.py",
                    "test_pipeline.py", "test_propose_fastpath.py",
                    "test_scheduler.py", "test_statesync.py")


def _armed_sites() -> set:
    """Every registered-site literal appearing in the chaos suites.
    Sites are armed either directly (fail.set_mode("ops...", mode)) or
    through parametrized case tables (the CASES tuples in
    test_chaos_matrix.py feed set_mode via a variable), so the honest
    static signal is: the literal site name occurs in the file at all —
    combined with the suites' own `fail.fired(site, mode) >= 1`
    assertions, which prove the injection actually triggered."""
    from tendermint_tpu.libs import fail

    armed = set()
    for name in CHAOS_TEST_FILES:
        with open(os.path.join(ROOT, "tests", name),
                  encoding="utf-8") as fh:
            tree = ast.parse(fh.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Constant) and \
                    isinstance(node.value, str) and \
                    fail.is_registered(node.value) and \
                    node.value != "*":
                armed.add(node.value)
    return armed


def test_every_registered_chaos_site_is_exercised():
    """The coverage gate the registry exists for: each static inject
    site (ops.*) must be armed by a chaos test, and each dynamic lane
    family (batch./sched./bulk. — one shared degrade.submit seam per
    family) must have at least one armed member.  Chaos coverage can't
    silently rot when a new site is registered."""
    from tendermint_tpu.libs import fail

    armed = _armed_sites()
    static = {s for s in fail.REGISTERED_SITES
              if s.startswith(("ops.", "sharding."))}
    missing = static - armed
    assert not missing, (
        f"registered chaos sites never armed by {CHAOS_TEST_FILES}: "
        f"{sorted(missing)}")
    for prefix in fail.DYNAMIC_SITE_PREFIXES:
        assert any(s.startswith(prefix) for s in armed), (
            f"no chaos test arms any '{prefix}*' lane site")
    # registered non-ops sites either belong to a dynamic family or are
    # standalone literals (lanepool.verify) that must each be armed —
    # a literal site is its own family of one
    for s in fail.REGISTERED_SITES - static:
        if not any(s.startswith(p) for p in fail.DYNAMIC_SITE_PREFIXES):
            assert s in armed, (
                f"literal chaos site {s!r} never armed by "
                f"{CHAOS_TEST_FILES}")


def test_set_mode_refuses_unregistered_site():
    from tendermint_tpu.libs import fail

    with pytest.raises(ValueError, match="not registered"):
        fail.set_mode("definitely.not.registered", "raise")
    site = fail.register("tmlint.selftest.site")
    try:
        fail.set_mode(site, "raise")
        with pytest.raises(fail.InjectedFault):
            fail.inject(site)
    finally:
        fail.clear(site)


def test_known_spans_all_appear_in_tree():
    """Reverse direction of TM306: a KNOWN_SPANS name nothing emits is
    registry rot."""
    from tendermint_tpu.libs import trace

    corpus = core.load_corpus(ROOT)
    blob = "\n".join(f.src for f in corpus.files.values())
    dead = [s for s in trace.KNOWN_SPANS if f'"{s}"' not in blob]
    assert not dead, f"KNOWN_SPANS entries no call site emits: {dead}"


# ---------------------------------------------------------------------------
# 3. sanitizer proofs
# ---------------------------------------------------------------------------

def test_compile_sentinel_flags_foreign_bucket():
    """A launch bucket outside the known shape set must fail check().
    Seeded via the same _seen_buckets seam _record_launch feeds, so no
    XLA compile is spent proving it."""
    from tendermint_tpu.devtools.tmlint.runtime import CompileSentinel
    from tendermint_tpu.ops import ed25519 as edops

    s = CompileSentinel().start()
    key = ("tmlint-selftest", 100, 1)  # nb=100: not a bucket shape
    with edops._launch_lock:
        edops._seen_buckets.add(key)
    try:
        with pytest.raises(AssertionError, match="outside the known"):
            s.check()
    finally:
        with edops._launch_lock:
            edops._seen_buckets.discard(key)
    # nb=64 (the shared lane bucket) and chunk multiples pass
    assert CompileSentinel.bucket_allowed(64)
    assert CompileSentinel.bucket_allowed(edops.SPLIT_CHUNK * 7)
    # 6,667 rows of the split path: 7 small chunks, no power of two
    assert CompileSentinel.bucket_allowed(edops.SPLIT_CHUNK_SMALL * 7)
    assert not CompileSentinel.bucket_allowed(
        edops.SPLIT_CHUNK_SMALL * 7 + edops.PALLAS_TILE)
    assert CompileSentinel.bucket_allowed(edops.MAX_CHUNK * 2)
    assert not CompileSentinel.bucket_allowed(100)
    assert not CompileSentinel.bucket_allowed(0)


def test_compile_sentinel_counts_watched_entry_compiles():
    """Cache growth on a watched jit entry is counted, and
    max_new_compiles=0 turns it into a failure (the 'no new compile
    budget' contract tests opt into)."""
    import jax
    import jax.numpy as jnp

    from tendermint_tpu.devtools.tmlint.runtime import CompileSentinel

    probe = jax.jit(lambda x: x + 1)
    s = CompileSentinel(extra_entries=[("probe", probe)],
                        max_new_compiles=0).start()
    probe(jnp.ones(3))  # trivial host-CPU compile, milliseconds
    with pytest.raises(AssertionError, match="new kernel compile"):
        s.check()
    s2 = CompileSentinel(extra_entries=[("probe", probe)],
                         max_new_compiles=0).start()
    probe(jnp.ones(3))  # cache hit: same shape
    assert s2.check()["compiles"] == {}


def test_locksan_detects_seeded_inversion():
    from tendermint_tpu.devtools.tmlint.runtime import LockSanitizer

    san = LockSanitizer(include_paths=("tests/",),
                        rank_overrides={"tests/test_lint.py:lo": 10,
                                        "tests/test_lint.py:hi": 20})
    with san:
        lo = threading.Lock()
        hi = threading.Lock()
        with hi:
            with lo:  # rank 10 under rank 20: inversion
                pass
        with lo:
            with hi:  # declared order: clean
                pass
    assert len(san.violations) == 1
    assert "tests/test_lint.py:lo" in san.violations[0]
    assert ("tests/test_lint.py:hi", "tests/test_lint.py:lo") in san.edges


def test_locksan_condition_protocol():
    """A sanitized Condition (wrapped RLock underneath) must keep the
    full wait/notify protocol working, and wait() must not corrupt the
    held-set tracking."""
    from tendermint_tpu.devtools.tmlint.runtime import LockSanitizer

    san = LockSanitizer(include_paths=("tests/",))
    with san:
        cond = threading.Condition()
        fired = []

        def waiter():
            with cond:
                fired.append(cond.wait(timeout=5.0))

        t = threading.Thread(target=waiter, daemon=True)
        with cond:
            t.start()
        # let the waiter take the condition and park
        import time
        time.sleep(0.05)
        with cond:
            cond.notify_all()
        t.join(timeout=5.0)
    assert fired == [True]
    assert not san.violations


@pytest.mark.locksan
def test_locksan_green_on_real_scheduler_roundtrip():
    """The acceptance run, in-process: a fresh degradation runtime and
    VerifyScheduler built UNDER the monitor (so every lock they create
    is wrapped), driven through a real submit -> coalesce -> host-lane
    -> resolve round trip.  The declared order holds — this is the same
    check TM_TPU_LOCKSAN=1 applies to the whole suite (the locksan
    marker arms the conftest fixture, which fails the test on any
    recorded inversion)."""
    from tendermint_tpu.crypto import batch as cb
    from tendermint_tpu.crypto import degrade
    from tendermint_tpu.crypto import ed25519 as edkeys
    from tendermint_tpu.crypto import scheduler as vsched
    from tendermint_tpu.libs.metrics import Registry

    degrade.configure(registry=Registry("locksan"))
    try:
        s = vsched.VerifyScheduler(window_s=0.001, max_batch=64,
                                   tpu_threshold=1 << 30)
        s.start()
        try:
            privs = [edkeys.PrivKey(bytes([i + 1]) * 32)
                     for i in range(8)]
            items = [(p.pub_key(), b"locksan %d" % i, p.sign(
                b"locksan %d" % i)) for i, p in enumerate(privs)]
            fut = s.submit(items, vsched.Priority.CONSENSUS)
            bits = fut.result(timeout=30.0)
            assert bits.all()
            # shed path: metrics/trace settle OUTSIDE _cond now
            tiny = vsched.VerifyScheduler(window_s=5.0, max_batch=4,
                                          max_pending=4,
                                          tpu_threshold=1 << 30)
            tiny.start()
            try:
                f1 = tiny.submit(items[:4], vsched.Priority.MEMPOOL)
                f2 = tiny.submit(items[:4], vsched.Priority.MEMPOOL)
                with pytest.raises(vsched.SchedulerShedError):
                    f2.result(timeout=5.0)
                tiny.flush()
                assert f1.result(timeout=30.0).all()
            finally:
                tiny.stop()
        finally:
            s.stop()
    finally:
        degrade.reset()
        cb.verified_sigs = cb.SigCache()
