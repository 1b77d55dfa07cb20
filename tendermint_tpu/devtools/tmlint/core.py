"""tmlint core: corpus loading, the rule registry, baseline handling
and the CLI driver.

The static passes are pure-AST (ast + tokenize from the stdlib, no jax,
no import of the modules under analysis), so the whole suite runs in
well under a second over the tree and is safe as a tier-1 gate on a
machine with no accelerator stack.

Findings are keyed WITHOUT line numbers — (rule, path, enclosing
qualname, detail) — so a baseline survives unrelated edits to the same
file.  Policy (docs/adr/adr-014-tmlint.md): the baseline starts and
stays empty unless a finding is consciously accepted with a written
justification; real violations get fixed, not baselined.
"""
from __future__ import annotations

import argparse
import ast
import json
import os
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional


def repo_root() -> str:
    """The directory holding tendermint_tpu/ (three levels up)."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))


@dataclass
class Finding:
    rule: str           # "TM101"
    path: str           # repo-relative, "/"-separated
    line: int
    qual: str           # enclosing "Class.func" / "<module>"
    msg: str

    def key(self) -> str:
        """Stable identity for baselining: no line number (edits above
        a finding must not churn the baseline)."""
        return f"{self.rule}|{self.path}|{self.qual}|{self.msg}"

    def as_dict(self) -> dict:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "qual": self.qual, "msg": self.msg, "key": self.key()}

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} [{self.qual}] " \
            f"{self.msg}"


@dataclass
class SourceFile:
    path: str           # repo-relative
    src: str
    tree: Optional[ast.AST]
    parse_error: Optional[str] = None


@dataclass
class Corpus:
    root: str
    files: Dict[str, SourceFile] = field(default_factory=dict)

    def in_scope(self, *prefixes: str) -> List[SourceFile]:
        return [f for p, f in sorted(self.files.items())
                if any(p.startswith(pre) for pre in prefixes)]


# directories under the repo root that tmlint walks.  tests/ is
# deliberately excluded (fixtures contain seeded violations); the
# devtools package itself IS linted — the linter must hold its own bar.
LINT_ROOTS = ("tendermint_tpu", "scripts")
LINT_FILES = ("bench.py", "__graft_entry__.py")


def collect_paths(root: str) -> List[str]:
    out: List[str] = []
    for top in LINT_ROOTS:
        for dirpath, dirnames, filenames in os.walk(
                os.path.join(root, top)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    rel = os.path.relpath(os.path.join(dirpath, fn), root)
                    out.append(rel.replace(os.sep, "/"))
    for fn in LINT_FILES:
        if os.path.exists(os.path.join(root, fn)):
            out.append(fn)
    return sorted(out)


def load_corpus(root: Optional[str] = None,
                paths: Optional[List[str]] = None) -> Corpus:
    root = root or repo_root()
    corpus = Corpus(root=root)
    for rel in paths if paths is not None else collect_paths(root):
        full = os.path.join(root, rel)
        try:
            with open(full, "r", encoding="utf-8") as f:
                src = f.read()
        except OSError as e:
            corpus.files[rel] = SourceFile(rel, "", None, str(e))
            continue
        try:
            tree = ast.parse(src, filename=rel)
            err = None
        except SyntaxError as e:
            tree, err = None, f"{e.msg} (line {e.lineno})"
        corpus.files[rel] = SourceFile(rel, src, tree, err)
    return corpus


# ---------------------------------------------------------------------------
# rule registry — one row per rule; docs/lint.md is generated from this
# table (scripts/metricsgen.py-style: edit here, regenerate, a tier-1
# test fails when the doc is stale)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Rule:
    id: str
    name: str
    scope: str
    description: str


RULES = [
    Rule("TM100", "parse-error", "all linted files",
         "The file does not parse under the container's Python (3.10). "
         "Backslash-in-f-string-expression breakage lands here when the "
         "interpreter itself rejects the file."),
    Rule("TM101", "raw-shape-at-kernel-seam", "ops/, parallel/",
         "A jnp array construction, np/jnp.pad, or jitted-kernel call "
         "whose size derives from a raw `len(batch)` instead of the "
         "registered bucket helpers (bucket_size, _comb_k_pad, "
         "chunk constants).  Every such site mints a fresh "
         "XLA shape class per batch size and silently burns the tier-1 "
         "compile budget."),
    Rule("TM102", "uncached-jit-in-function", "ops/, parallel/",
         "jax.jit / shard_map / pl.pallas_call invoked inside a "
         "function body without caching the result (module constant, "
         "attribute/subscript store, closure factory).  A per-call jit "
         "recompiles — or at best re-traces — on every invocation."),
    Rule("TM201", "lock-order-inversion", "crypto/, ops/, libs/, parallel/",
         "The static acquires-while-holding graph contains an edge that "
         "acquires a lower-ranked lock while holding a higher-ranked "
         "one (or a cycle), against devtools/lockorder.py."),
    Rule("TM202", "blocking-call-under-lock", "crypto/, ops/, libs/, parallel/",
         "A blocking call (queue get/put, future.result, thread join, "
         "sleep, wait on a different primitive, device kernel entry) "
         "made while holding a ranked lock.  Condition.wait on the "
         "condition itself is allowed (wait releases it)."),
    Rule("TM203", "undeclared-lock", "crypto/, ops/, libs/, parallel/",
         "A threading.Lock/RLock/Condition creation site in the core "
         "modules with no rank in devtools/lockorder.py.  Every core "
         "lock must take a position in the declared order."),
    Rule("TM204", "stale-lock-declaration", "devtools/lockorder.py",
         "A lockorder.py row whose creation site no longer exists — "
         "the table must not rot as locks are removed or renamed."),
    Rule("TM301", "non-daemon-thread", "all linted files",
         "threading.Thread created without daemon=True outside "
         "libs/service.BaseService and never joined in the creating "
         "function.  A stray non-daemon thread blocks interpreter "
         "shutdown behind whatever it is wedged on (the conftest "
         "thread-leak guard is the runtime twin of this rule)."),
    Rule("TM302", "unconditional-optional-import", "all linted files",
         "Top-level import of an optional dependency (cryptography, "
         "grpc) outside try/except ImportError.  The container bakes "
         "neither in; a hard import makes the whole module unusable "
         "instead of degrading the one feature that needs it."),
    Rule("TM303", "backslash-in-fstring-expression", "all linted files",
         "A backslash inside an f-string replacement field.  Python "
         "3.10 rejects the file at parse time (the seed-era breakage "
         "that blocked every metrics-importing module); this rule "
         "catches it from the tokens even where newer interpreters "
         "would accept it."),
    Rule("TM304", "silent-except-pass", "ops/, crypto/",
         "`except Exception:`/bare `except:` whose body is only `pass` "
         "with no justifying comment, in verify hot-path modules.  A "
         "swallowed device fault is how bitmaps rot silently."),
    Rule("TM305", "unregistered-fail-site", "all linted files",
         "fail.inject/corrupt_bitmap called with a literal site name "
         "that is not in libs/fail.py REGISTERED_SITES.  Unregistered "
         "sites dodge the chaos-coverage gate."),
    Rule("TM306", "unregistered-trace-span", "all linted files",
         "trace.span/trace.timed/trace.instant called with a literal "
         "name that is "
         "not in libs/trace.py KNOWN_SPANS.  The registry is what lets "
         "trace consumers (bench report, debug-trace CLI) rely on span "
         "names."),
    Rule("TM307", "unknown-metric-attr", "all linted files",
         "An attribute read on a metrics bundle (``*.metrics.X``, "
         "``self._metrics().X``) that no bundle class in "
         "libs/metrics.py registers.  Catches typo'd metric names that "
         "would otherwise AttributeError only on the failure path."),
    Rule("TM308", "undeclared-knob-envelope", "all linted files",
         "A KnobSpec(...) declaration (libs/control.py, ADR-023) whose "
         "safe_range is not a literal finite (lo, hi) tuple with "
         "lo <= hi, whose step is not a literal > 0, or whose signal "
         "does not name a metric registered by a bundle class in "
         "libs/metrics.py.  The adaptive control plane only moves "
         "knobs inside ranges a human declared and reviews, steering "
         "on published signals only."),
]

RULES_BY_ID = {r.id: r for r in RULES}


def run_lint(root: Optional[str] = None,
             corpus: Optional[Corpus] = None) -> List[Finding]:
    """Run every static pass; returns findings sorted by path/line."""
    from . import passes_hygiene, passes_locks, passes_shape

    corpus = corpus or load_corpus(root)
    findings: List[Finding] = []
    for f in corpus.files.values():
        if f.parse_error is not None:
            findings.append(Finding("TM100", f.path, 1, "<module>",
                                    f"does not parse: {f.parse_error}"))
    findings += passes_shape.check(corpus)
    findings += passes_locks.check(corpus)
    findings += passes_hygiene.check(corpus)
    findings.sort(key=lambda x: (x.path, x.line, x.rule))
    return findings


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------

def load_baseline(path: str) -> Dict[str, str]:
    """{finding key -> justification}; missing file = empty baseline."""
    try:
        with open(path, "r", encoding="utf-8") as f:
            data = json.load(f)
    except FileNotFoundError:
        return {}
    return {e["key"]: e.get("justification", "")
            for e in data.get("findings", [])}


def write_baseline(path: str, findings: List[Finding]):
    data = {
        "comment": ("tmlint baseline — accepted findings with written "
                    "justifications.  Policy: fix violations, don't "
                    "baseline them; this file should stay empty."),
        "findings": [{"key": f.as_dict()["key"],
                      "justification": "TODO: justify or fix"}
                     for f in findings],
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")


# ---------------------------------------------------------------------------
# docs generation (docs/lint.md; staleness-gated in tests/test_lint.py)
# ---------------------------------------------------------------------------

def generate_docs() -> str:
    lines = [
        "# tmlint rules",
        "",
        "Static-analysis rules and runtime sanitizers enforcing the "
        "verify-stack",
        "invariants (docs/adr/adr-014-tmlint.md).  GENERATED by "
        "`python -m",
        "tendermint_tpu.devtools.tmlint --docs` from the rule table in",
        "`tendermint_tpu/devtools/tmlint/core.py` — edit the table, "
        "then",
        "regenerate; `tests/test_lint.py` fails when this file is "
        "stale.",
        "",
        "Run: `python -m tendermint_tpu.devtools.tmlint --baseline "
        "devtools/lint_baseline.json`",
        "",
        "| Rule | Name | Scope | What it enforces |",
        "|---|---|---|---|",
    ]
    for r in RULES:
        desc = " ".join(r.description.split())
        lines.append(f"| `{r.id}` | {r.name} | {r.scope} | {desc} |")
    lines += [
        "",
        "## Runtime sanitizers",
        "",
        "| Sanitizer | Arming | What it enforces |",
        "|---|---|---|",
        "| compile sentinel | `compile_sentinel` fixture "
        "(tests/conftest.py) | No test may land a device-launch bucket "
        "whose padded lane count is outside the known bucket set "
        "(power-of-two >= MIN_BUCKET capped at MAX_CHUNK, or "
        "chunk-aligned), and watched jit entries must not grow their "
        "compile caches unexpectedly. |",
        "| lockset monitor | `TM_TPU_LOCKSAN=1` (all tests) or the "
        "`locksan` marker | Locks created in tendermint_tpu modules "
        "are wrapped; acquiring a lower-ranked lock while holding a "
        "higher-ranked one (per devtools/lockorder.py) fails the "
        "test. |",
        "",
    ]
    return "\n".join(lines)


def docs_path(root: Optional[str] = None) -> str:
    return os.path.join(root or repo_root(), "docs", "lint.md")


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tendermint_tpu.devtools.tmlint",
        description="invariant-enforcing static analysis for the "
                    "tendermint_tpu verify stack")
    ap.add_argument("--baseline", default=None,
                    help="baseline JSON (devtools/lint_baseline.json); "
                         "keyed findings listed there are accepted")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit findings as JSON (scripts/lint_report.py "
                         "consumes this)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write the current findings to --baseline and "
                         "exit 0 (bootstrap only; justify every entry)")
    ap.add_argument("--docs", action="store_true",
                    help="regenerate docs/lint.md from the rule table")
    ap.add_argument("--check-docs", action="store_true",
                    help="exit 1 when docs/lint.md is stale")
    ap.add_argument("--dump-locks", action="store_true",
                    help="print every lock creation site id (for "
                         "maintaining devtools/lockorder.py)")
    ap.add_argument("--root", default=None, help=argparse.SUPPRESS)
    ap.add_argument("paths", nargs="*",
                    help="restrict to these repo-relative files")
    args = ap.parse_args(argv)

    root = args.root or repo_root()
    if args.docs or args.check_docs:
        text = generate_docs()
        dp = docs_path(root)
        if args.check_docs:
            try:
                with open(dp, "r", encoding="utf-8") as f:
                    cur = f.read()
            except FileNotFoundError:
                cur = ""
            if cur != text:
                print("docs/lint.md is stale; run python -m "
                      "tendermint_tpu.devtools.tmlint --docs",
                      file=sys.stderr)
                return 1
            print("docs/lint.md is current")
            return 0
        with open(dp, "w", encoding="utf-8") as f:
            f.write(text)
        print(f"wrote {dp}")
        return 0

    corpus = load_corpus(root, paths=args.paths or None)
    if args.dump_locks:
        from . import passes_locks
        for site in passes_locks.lock_creation_sites(corpus):
            print(f"{site.lock_id}  ({site.kind}, line {site.line})")
        return 0

    findings = run_lint(root=root, corpus=corpus)

    if args.write_baseline:
        if not args.baseline:
            print("--write-baseline requires --baseline", file=sys.stderr)
            return 2
        write_baseline(os.path.join(root, args.baseline), findings)
        print(f"wrote {len(findings)} finding(s) to {args.baseline}")
        return 0

    baseline = load_baseline(os.path.join(root, args.baseline)) \
        if args.baseline else {}
    new = [f for f in findings if f.key() not in baseline]
    stale = set(baseline) - {f.key() for f in findings}

    if args.as_json:
        print(json.dumps({
            "findings": [f.as_dict() for f in findings],
            "new": [f.as_dict() for f in new],
            "baselined": len(findings) - len(new),
            "stale_baseline_keys": sorted(stale),
        }, indent=2))
    else:
        for f in new:
            print(f.render())
        for k in sorted(stale):
            print(f"stale baseline entry (finding no longer exists): {k}",
                  file=sys.stderr)
        n_files = len(corpus.files)
        print(f"tmlint: {n_files} files, {len(findings)} finding(s), "
              f"{len(findings) - len(new)} baselined, {len(new)} new")
    return 1 if new else 0
