"""TM301-TM308 — hygiene rules and registry checks.

Each rule encodes one invariant that previously lived only as prose in
CHANGES.md / ADRs:

  TM301  every thread is a daemon (or joined by its creator) — the
         conftest thread-leak guard's static twin
  TM302  optional deps (cryptography, grpc) import guarded
  TM303  no backslash inside an f-string replacement field (py3.10)
  TM304  no silent `except Exception: pass` in ops/ and crypto/
  TM305  fail.inject sites registered in libs/fail.REGISTERED_SITES
  TM306  trace span/instant/counter names registered in libs/trace.KNOWN_SPANS
  TM307  metrics-bundle attribute reads name registered metrics
  TM308  every KnobSpec declares a literal finite safe_range and a
         signal naming a registered metric (ADR-023 control plane)

The registries are read by AST, not import — the pass must work with
no package import at all (and libs/fail.py stays enforceable even when
it is itself the file being edited).
"""
from __future__ import annotations

import ast
import io
import tokenize
from typing import Dict, List, Optional, Set, Tuple

from .core import Corpus, Finding, SourceFile
from .passes_shape import _call_name

OPTIONAL_DEPS = {"cryptography", "grpc"}
HOT_SCOPE = ("tendermint_tpu/ops/", "tendermint_tpu/crypto/")


# ---------------------------------------------------------------------------
# TM301 — non-daemon threads
# ---------------------------------------------------------------------------

def _fn_joins_threads(node: ast.AST) -> bool:
    """Does this function contain an X.join(...)/X.join() call that
    plausibly joins threads?  String `sep.join(iterable)` must NOT
    count (a ", ".join() in the same function would otherwise suppress
    the rule): a Constant receiver is always a string join, and a
    thread join takes no positional arg (or a timeout keyword)."""
    for sub in ast.walk(node):
        if not (isinstance(sub, ast.Call)
                and isinstance(sub.func, ast.Attribute)
                and sub.func.attr == "join"):
            continue
        if isinstance(sub.func.value, ast.Constant):
            continue  # ", ".join(...)
        if len(sub.args) == 0 or (
                len(sub.args) == 1
                and isinstance(sub.args[0], ast.Constant)
                and isinstance(sub.args[0].value, (int, float))):
            return True  # t.join() / t.join(2.0)
        if any(k.arg == "timeout" for k in sub.keywords):
            return True
    return False


def _fn_sets_daemon(node: ast.AST) -> bool:
    """X.daemon = True somewhere in the function."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Assign) and \
                isinstance(sub.value, ast.Constant) and \
                sub.value.value is True:
            for t in sub.targets:
                if isinstance(t, ast.Attribute) and t.attr == "daemon":
                    return True
    return False


def _check_threads(f: SourceFile, findings: List[Finding]):
    if f.tree is None or f.path == "tendermint_tpu/libs/service.py":
        return  # BaseService.spawn IS the sanctioned daemon-thread owner

    def check_fn(node, qual):
        joined = daemon_fixup = None  # computed lazily, once
        for sub in ast.walk(node):
            if not (isinstance(sub, ast.Call)
                    and _call_name(sub.func) == "Thread"):
                continue
            kw = {k.arg: k.value for k in sub.keywords}
            d = kw.get("daemon")
            if isinstance(d, ast.Constant) and d.value is True:
                continue
            if d is None:
                if joined is None:
                    joined = _fn_joins_threads(node)
                    daemon_fixup = _fn_sets_daemon(node)
                if joined or daemon_fixup:
                    continue  # joined by the creator / t.daemon = True
            findings.append(Finding(
                "TM301", f.path, sub.lineno, qual,
                "threading.Thread without daemon=True and never "
                "joined here — a wedged non-daemon thread blocks "
                "interpreter shutdown (use daemon=True or "
                "libs/service.BaseService.spawn)"))

    for node in f.tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            check_fn(node, node.name)
        elif isinstance(node, ast.ClassDef):
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                    check_fn(sub, f"{node.name}.{sub.name}")


# ---------------------------------------------------------------------------
# TM302 — unconditional optional-dep imports
# ---------------------------------------------------------------------------

def _check_optional_imports(f: SourceFile, findings: List[Finding]):
    if f.tree is None:
        return
    for node in f.tree.body:  # module level only; function-local or
        # try-guarded imports are exactly the sanctioned patterns
        mods: List[str] = []
        if isinstance(node, ast.Import):
            mods = [a.name.split(".")[0] for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.module:
            mods = [node.module.split(".")[0]]
        for m in mods:
            if m in OPTIONAL_DEPS:
                findings.append(Finding(
                    "TM302", f.path, node.lineno, "<module>",
                    f"unconditional top-level import of optional "
                    f"dependency '{m}' — guard with try/except "
                    "ImportError and degrade the feature, not the "
                    "module"))


# ---------------------------------------------------------------------------
# TM303 — backslash inside an f-string replacement field
# ---------------------------------------------------------------------------

def find_fstring_backslashes(src: str) -> List[Tuple[int, str]]:
    """[(line, token_head)] for every f-string whose {...} expression
    part contains a backslash — the class Python 3.10 rejects at parse
    time.

    On <= 3.11 an f-string is one STRING token and the brace-tracking
    scan below applies.  On 3.12+ (PEP 701) f-strings tokenize as
    FSTRING_START/MIDDLE/END with the expression parts as ordinary
    tokens, and the breakage class appears as a STRING token carrying a
    backslash escape INSIDE an open f-string (e.g. the seed-era
    f"{chr(10).join(...)}" written as f"{'\\n'.join(...)}") — tracked
    via fstring depth so the rule still fires for a developer editing
    on a newer interpreter than the 3.10 container.  A BARE backslash
    in the expression part (f"{x\t}") is no token at all there: the
    tokenizer stops with "unexpected character after line continuation
    character", and an error at a position inside an open f-string is
    the finding."""
    out: List[Tuple[int, str]] = []
    fstart = getattr(tokenize, "FSTRING_START", None)
    fend = getattr(tokenize, "FSTRING_END", None)
    tokens = []
    try:
        for tok in tokenize.generate_tokens(io.StringIO(src).readline):
            tokens.append(tok)
    except tokenize.TokenError as e:
        open_fstrings = sum((t.type == fstart) - (t.type == fend)
                            for t in tokens)
        if fstart is not None and open_fstrings > 0 and len(e.args) > 1 \
                and "line continuation" in str(e.args[0]):
            line = e.args[1][0]
            out.append((line, src.splitlines()[line - 1].strip()[:40]))
        return out
    except (IndentationError, SyntaxError):
        return out
    fdepth = 0
    for tok in tokens:
        if fstart is not None:
            if tok.type == fstart:
                fdepth += 1
                continue
            if tok.type == fend:
                fdepth = max(0, fdepth - 1)
                continue
            if fdepth > 0 and tok.type == tokenize.STRING and \
                    "\\" in tok.string:
                out.append((tok.start[0], tok.string[:40]))
                continue
        if tok.type != tokenize.STRING:
            continue
        s = tok.string
        q = s.find('"')
        qq = s.find("'")
        qpos = min(x for x in (q, qq) if x >= 0) if max(q, qq) >= 0 \
            else -1
        if qpos <= 0:
            continue
        prefix = s[:qpos].lower()
        if "f" not in prefix:
            continue
        body = s[qpos:]
        if body[:3] in ('"""', "'''"):
            body = body[3:-3]
        else:
            body = body[1:-1]
        depth = 0
        i = 0
        while i < len(body):
            c = body[i]
            if depth == 0:
                if c == "\\":
                    i += 2  # escape in the literal part: fine, skip
                    continue
                if c == "{":
                    if body[i:i + 2] == "{{":
                        i += 2
                        continue
                    depth = 1
                elif c == "}" and body[i:i + 2] == "}}":
                    i += 2
                    continue
            else:
                if c == "\\":
                    out.append((tok.start[0], s[:40]))
                    break
                if c == "{":
                    depth += 1
                elif c == "}":
                    depth -= 1
            i += 1
    return out


def _check_fstrings(f: SourceFile, findings: List[Finding]):
    for line, head in find_fstring_backslashes(f.src):
        findings.append(Finding(
            "TM303", f.path, line, "<module>",
            f"backslash inside an f-string replacement field ({head!r}) "
            "— Python 3.10 rejects this at parse time (the seed-era "
            "metrics breakage); hoist the escape into a variable"))


# ---------------------------------------------------------------------------
# TM304 — silent except-pass in hot paths
# ---------------------------------------------------------------------------

def _check_except_pass(f: SourceFile, findings: List[Finding]):
    if f.tree is None or not f.path.startswith(HOT_SCOPE):
        return
    lines = f.src.splitlines()
    for node in ast.walk(f.tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        bare = node.type is None
        plain_exc = isinstance(node.type, ast.Name) and \
            node.type.id == "Exception"
        if not (bare or plain_exc):
            continue
        if not (len(node.body) == 1 and isinstance(node.body[0],
                                                   ast.Pass)):
            continue
        span = range(node.lineno - 1,
                     min(node.body[0].lineno, len(lines)))
        if any("#" in lines[i] for i in span if i < len(lines)):
            continue  # a written justification is the accepted escape
        findings.append(Finding(
            "TM304", f.path, node.lineno, "<module>",
            "silent `except Exception: pass` in a verify hot path — "
            "justify with a comment or handle the failure"))


# ---------------------------------------------------------------------------
# registry extraction (AST-level, no imports)
# ---------------------------------------------------------------------------

def _literal_strings(node: ast.AST) -> Set[str]:
    return {n.value for n in ast.walk(node)
            if isinstance(n, ast.Constant) and isinstance(n.value, str)}


def registered_fail_sites(corpus: Corpus) -> Tuple[Set[str], Set[str]]:
    """(exact sites, dynamic prefixes) from libs/fail.py."""
    f = corpus.files.get("tendermint_tpu/libs/fail.py")
    exact: Set[str] = set()
    prefixes: Set[str] = set()
    if f is None or f.tree is None:
        return exact, prefixes
    for node in f.tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name):
            if node.targets[0].id == "REGISTERED_SITES":
                exact |= _literal_strings(node.value)
            elif node.targets[0].id == "DYNAMIC_SITE_PREFIXES":
                prefixes |= _literal_strings(node.value)
    return exact, prefixes


def known_trace_spans(corpus: Corpus) -> Set[str]:
    f = corpus.files.get("tendermint_tpu/libs/trace.py")
    if f is None or f.tree is None:
        return set()
    for node in f.tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name) and \
                node.targets[0].id == "KNOWN_SPANS":
            return _literal_strings(node.value)
    return set()


def registered_metric_attrs(corpus: Corpus) -> Set[str]:
    f = corpus.files.get("tendermint_tpu/libs/metrics.py")
    out: Set[str] = set()
    if f is None or f.tree is None:
        return out
    for cls in f.tree.body:
        if not isinstance(cls, ast.ClassDef):
            continue
        for node in ast.walk(cls):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Attribute) \
                    and isinstance(node.targets[0].value, ast.Name) \
                    and node.targets[0].value.id == "self" \
                    and isinstance(node.value, ast.Call) \
                    and _call_name(node.value.func) in (
                        "counter", "gauge", "histogram"):
                out.add(node.targets[0].attr)
    return out


# ---------------------------------------------------------------------------
# TM305 — fail.inject literal sites
# ---------------------------------------------------------------------------

def _site_registered(site: str, exact: Set[str],
                     prefixes: Set[str]) -> bool:
    return site in exact or any(site.startswith(p) for p in prefixes)


def _check_fail_sites(f: SourceFile, exact: Set[str],
                      prefixes: Set[str], findings: List[Finding]):
    if f.tree is None or f.path == "tendermint_tpu/libs/fail.py":
        return
    for node in ast.walk(f.tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node.func)
        if name not in ("inject", "corrupt_bitmap", "set_mode",
                        "fired"):
            continue
        recv = getattr(node.func, "value", None)
        if not (isinstance(recv, ast.Name) and recv.id == "fail"):
            continue
        if not node.args:
            continue
        a0 = node.args[0]
        if not (isinstance(a0, ast.Constant) and
                isinstance(a0.value, str)):
            continue  # dynamic sites are enforced at runtime (set_mode)
        if a0.value == "*":
            continue
        if not _site_registered(a0.value, exact, prefixes):
            findings.append(Finding(
                "TM305", f.path, node.lineno, "<module>",
                f"fail site '{a0.value}' is not in libs/fail.py "
                "REGISTERED_SITES / DYNAMIC_SITE_PREFIXES — register "
                "it so chaos coverage can be asserted"))


# ---------------------------------------------------------------------------
# TM306 — trace span names
# ---------------------------------------------------------------------------

def _check_trace_spans(f: SourceFile, known: Set[str],
                       findings: List[Finding]):
    if f.tree is None or f.path == "tendermint_tpu/libs/trace.py":
        return
    for node in ast.walk(f.tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node.func)
        if name not in ("span", "timed", "instant", "counter"):
            continue
        recv = getattr(node.func, "value", None)
        if not (isinstance(recv, ast.Name) and recv.id == "trace"):
            continue
        if not node.args:
            continue
        a0 = node.args[0]
        if not (isinstance(a0, ast.Constant) and
                isinstance(a0.value, str)):
            continue
        if a0.value not in known:
            findings.append(Finding(
                "TM306", f.path, node.lineno, "<module>",
                f"trace span '{a0.value}' is not in libs/trace.py "
                "KNOWN_SPANS — register the name so trace consumers "
                "can rely on it"))


# ---------------------------------------------------------------------------
# TM308 — KnobSpec declarations (adaptive control plane, ADR-023)
# ---------------------------------------------------------------------------

_KNOBSPEC_PARAMS = ("name", "safe_range", "step", "direction",
                    "signal", "mode", "labels")


def _knobspec_arg(call: ast.Call, param: str) -> Optional[ast.AST]:
    idx = _KNOBSPEC_PARAMS.index(param)
    if idx < len(call.args):
        return call.args[idx]
    for kw in call.keywords:
        if kw.arg == param:
            return kw.value
    return None


def _numeric_const(node: Optional[ast.AST]) -> Optional[float]:
    """The value of a literal int/float (incl. unary minus), else None."""
    if isinstance(node, ast.UnaryOp) and \
            isinstance(node.op, ast.USub) and \
            isinstance(node.operand, ast.Constant) and \
            isinstance(node.operand.value, (int, float)):
        return -float(node.operand.value)
    if isinstance(node, ast.Constant) and \
            isinstance(node.value, (int, float)) and \
            not isinstance(node.value, bool):
        return float(node.value)
    return None


def _check_knob_specs(f: SourceFile, metric_attrs: Set[str],
                      findings: List[Finding]):
    """Every KnobSpec(...) call must DECLARE its envelope as literals:
    a finite (lo, hi) safe_range with lo <= hi, a literal step > 0,
    and a literal signal string naming a metric some bundle class in
    libs/metrics.py registers.  The governor only ever moves a knob
    inside a range a human wrote down and reviews — a computed range
    or a typo'd steering signal is a lint error, not a 3am incident."""
    if f.tree is None:
        return
    import math as _math
    for node in ast.walk(f.tree):
        if not (isinstance(node, ast.Call)
                and _call_name(node.func) == "KnobSpec"):
            continue
        name_node = _knobspec_arg(node, "name")
        label = name_node.value \
            if isinstance(name_node, ast.Constant) \
            and isinstance(name_node.value, str) else "<dynamic>"
        rng = _knobspec_arg(node, "safe_range")
        ok_range = False
        if isinstance(rng, (ast.Tuple, ast.List)) and \
                len(rng.elts) == 2:
            lo = _numeric_const(rng.elts[0])
            hi = _numeric_const(rng.elts[1])
            ok_range = (lo is not None and hi is not None
                        and _math.isfinite(lo) and _math.isfinite(hi)
                        and lo <= hi)
        if not ok_range:
            findings.append(Finding(
                "TM308", f.path, node.lineno, "<module>",
                f"KnobSpec {label!r}: safe_range must be a LITERAL "
                "finite (lo, hi) tuple with lo <= hi — the governor's "
                "envelope is declared and reviewed, never computed"))
        step = _numeric_const(_knobspec_arg(node, "step"))
        if step is None or not (_math.isfinite(step) and step > 0):
            findings.append(Finding(
                "TM308", f.path, node.lineno, "<module>",
                f"KnobSpec {label!r}: step must be a literal finite "
                "number > 0"))
        sig = _knobspec_arg(node, "signal")
        if not (isinstance(sig, ast.Constant)
                and isinstance(sig.value, str)
                and sig.value in metric_attrs):
            got = sig.value if isinstance(sig, ast.Constant) else None
            findings.append(Finding(
                "TM308", f.path, node.lineno, "<module>",
                f"KnobSpec {label!r}: signal {got!r} must be a literal "
                "string naming a metric registered by a bundle class "
                "in libs/metrics.py — the control plane steers on "
                "PUBLISHED signals only"))


# ---------------------------------------------------------------------------
# TM307 — metric attribute reads
# ---------------------------------------------------------------------------

def _metrics_receiver(expr: ast.AST, local_metric_names: Set[str]) -> bool:
    if isinstance(expr, ast.Attribute) and expr.attr == "metrics":
        return True
    if isinstance(expr, ast.Call) and \
            _call_name(expr.func) == "_metrics":
        return True
    if isinstance(expr, ast.Name) and expr.id in local_metric_names:
        return True
    return False


def _check_metric_attrs(f: SourceFile, attrs: Set[str],
                        findings: List[Finding]):
    if f.tree is None or f.path == "tendermint_tpu/libs/metrics.py":
        return
    for fn in ast.walk(f.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        local: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and _metrics_receiver(node.value, set()):
                local.add(node.targets[0].id)
        for node in ast.walk(fn):
            if isinstance(node, ast.Attribute) and \
                    _metrics_receiver(node.value, local):
                if node.attr not in attrs:
                    findings.append(Finding(
                        "TM307", f.path, node.lineno, fn.name,
                        f"metric attribute '{node.attr}' is not "
                        "registered by any bundle class in "
                        "libs/metrics.py — typo, or register the "
                        "metric"))
    return


def check(corpus: Corpus) -> List[Finding]:
    findings: List[Finding] = []
    exact, prefixes = registered_fail_sites(corpus)
    spans = known_trace_spans(corpus)
    metric_attrs = registered_metric_attrs(corpus)
    for f in corpus.files.values():
        _check_threads(f, findings)
        _check_optional_imports(f, findings)
        _check_fstrings(f, findings)
        _check_except_pass(f, findings)
        _check_fail_sites(f, exact, prefixes, findings)
        _check_trace_spans(f, spans, findings)
        _check_metric_attrs(f, metric_attrs, findings)
        _check_knob_specs(f, metric_attrs, findings)
    return findings
