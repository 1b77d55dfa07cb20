"""TM101/TM102 — compile-shape discipline for the kernel modules.

The whole 870 s tier-1 compile budget rests on one invariant: the
jitted kernels see FEW distinct shapes, because every batch is padded
into a registered bucket (ops/ed25519.bucket_size powers of two,
MAX_CHUNK sub-launches, SPLIT_CHUNK multiples, _comb_k_pad validator
buckets) before it reaches a kernel.  A new route that pads to
`len(batch)` — or passes a raw-sized array straight into a jit entry —
compiles one XLA executable per batch size and the budget is gone
before any test fails functionally.

This pass is a per-function taint analysis over the kernel modules
(ops/, parallel/):

  * taint source: `len(...)` — the raw batch size — and names assigned
    from tainted expressions;
  * blessing: a call to a registered bucket helper, a module-level
    ALL_CAPS constant (MAX_CHUNK, PALLAS_TILE, ... — compile-time
    fixed), or an existing array's `.shape` (no new shape class can
    come from a shape that already exists on-device);
  * sinks: jnp array constructors' shape argument, np/jnp.pad widths,
    and EVERY argument of a jitted-entry call (module-level names bound
    to jax.jit(...), @jax.jit functions, pl.pallas_call, shard_map,
    plus the cross-module entry list below).

An expression reaching a sink is flagged when it is tainted and not
blessed.  Blessing wins: `nb - n` with nb = bucket_size(n) is the
canonical pad width.  The helpers themselves are exempt (they ARE the
discipline).

TM102 separately flags jax.jit/shard_map/pallas_call invoked inside a
function body whose result is not cached (module constant, attribute/
subscript store e.g. ``self._fns[key] = f``, closure factory, or
returned) — a per-call jit re-traces every invocation.
"""
from __future__ import annotations

import ast
from typing import List, Optional, Set

from .core import Corpus, Finding

SCOPE = ("tendermint_tpu/ops/", "tendermint_tpu/parallel/")

# the registered bucket helpers: deriving a size THROUGH one of these
# is the sanctioned way to go from len(batch) to a compile shape.
# (Keep in sync with docs/adr/adr-014-tmlint.md when adding a helper.)
BUCKET_HELPERS = {
    "bucket_size",        # ops/ed25519: pow2 lane bucket, floor MIN_BUCKET
    "_comb_k_pad",        # ops/ed25519: validator-axis pow2 bucket
    "_pad_dev",           # ops/ed25519: pad staged dict to a bucket
    "_split_chunk",       # ops/ed25519: lanes per split-path launch
}

# jit entries callable across module boundaries (module-local entries
# are auto-detected from `NAME = jax.jit(...)` / @jax.jit).
CROSS_MODULE_ENTRIES = {
    "verify_kernel", "comb_kernel", "comb_build_kernel",
    "verify_packed_pallas", "verify_packed_split_pallas",
    "verify_staged", "comb_verify_staged",
    "pallas_call", "shard_map",
}

# device-allocating constructors: only the jnp namespace — host-side
# np staging buffers are padded into buckets before any kernel seam,
# and *_like constructors inherit an existing array's shape class
JNP_CONSTRUCTORS = {"zeros", "ones", "full", "empty", "arange"}


def _call_name(func: ast.AST) -> Optional[str]:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def _is_jit_factory(call: ast.Call) -> bool:
    """jax.jit(...) / jit(...) / partial(jax.jit, ...)(...) /
    @partial(jax.jit, ...)."""
    f = call.func
    if _call_name(f) == "jit":
        return True
    # partial(jax.jit, ...)(...) — outer call whose func is a call to
    # partial with jit as first arg
    if isinstance(f, ast.Call) and _call_name(f.func) == "partial" \
            and f.args and _call_name(f.args[0]) == "jit":
        return True
    return False


def _decorated_jit(fn: ast.AST) -> bool:
    for d in getattr(fn, "decorator_list", []):
        if _call_name(d) == "jit":
            return True
        if isinstance(d, ast.Call):
            if _call_name(d.func) == "jit":
                return True
            if _call_name(d.func) == "partial" and d.args \
                    and _call_name(d.args[0]) == "jit":
                return True
    return False


def module_constants(tree: ast.AST) -> Set[str]:
    """Module-level ALL_CAPS names: compile-time-fixed sizes."""
    out: Set[str] = set()
    for node in tree.body:
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign) and node.target is not None:
            targets = [node.target]
        for t in targets:
            if isinstance(t, ast.Name) and t.id.upper() == t.id \
                    and any(c.isalpha() for c in t.id):
                out.add(t.id)
    return out


def module_jit_entries(tree: ast.AST) -> Set[str]:
    out: Set[str] = set()
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and isinstance(node.value, ast.Call) \
                and _is_jit_factory(node.value):
            out.add(node.targets[0].id)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and _decorated_jit(node):
            out.add(node.name)
    return out


class _FnShapeCheck:
    """Single-function (plus nested defs, one shared namespace) taint
    walk in source order."""

    def __init__(self, path: str, qual: str, constants: Set[str],
                 entries: Set[str], findings: List[Finding]):
        self.path = path
        self.qual = qual
        self.constants = constants
        self.entries = entries
        self.findings = findings
        self.tainted: Set[str] = set()
        self.blessed: Set[str] = set()

    # -- expression classification ------------------------------------

    def _expr_flags(self, expr: ast.AST):
        """(tainted, blessed) for an expression subtree.  Blessing WINS
        at use sites: `nb - n` with nb = bucket_size(n) is the
        canonical pad width."""
        tainted = blessed = False
        for node in ast.walk(expr):
            if isinstance(node, ast.Call):
                name = _call_name(node.func)
                if name == "len":
                    tainted = True
                elif name in BUCKET_HELPERS:
                    blessed = True
            elif isinstance(node, ast.Name):
                if node.id in self.tainted:
                    tainted = True
                if node.id in self.constants or node.id in self.blessed:
                    blessed = True
            elif isinstance(node, ast.Attribute) and node.attr == "shape":
                blessed = True
        return tainted, blessed

    def _is_raw(self, expr: ast.AST) -> bool:
        tainted, blessed = self._expr_flags(expr)
        return tainted and not blessed

    def _flag(self, node: ast.AST, msg: str):
        self.findings.append(Finding(
            "TM101", self.path, getattr(node, "lineno", 1), self.qual,
            msg))

    # -- walk ----------------------------------------------------------

    def run(self, fn: ast.AST):
        for stmt in fn.body:
            self._stmt(stmt)

    def _assign_target(self, target: ast.AST, value: ast.AST):
        if not isinstance(target, ast.Name):
            return
        tainted, blessed = self._expr_flags(value)
        if blessed:
            self.blessed.add(target.id)
            self.tainted.discard(target.id)
        elif tainted:
            self.tainted.add(target.id)
            self.blessed.discard(target.id)
        else:
            self.tainted.discard(target.id)
            self.blessed.discard(target.id)

    def _stmt(self, stmt: ast.AST):
        if isinstance(stmt, ast.Assign):
            self._visit_expr(stmt.value)
            for t in stmt.targets:
                if isinstance(t, ast.Tuple):
                    for el in t.elts:
                        self._assign_target(el, stmt.value)
                else:
                    self._assign_target(t, stmt.value)
            return
        if isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
            if stmt.value is not None:
                self._visit_expr(stmt.value)
                self._assign_target(stmt.target, stmt.value)
            return
        if isinstance(stmt, ast.For):
            self._visit_expr(stmt.iter)
            self._assign_target(stmt.target, stmt.iter)
            for s in stmt.body + stmt.orelse:
                self._stmt(s)
            return
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # nested def: shared namespace (closures read the enclosing
            # function's bucket locals)
            for s in stmt.body:
                self._stmt(s)
            return
        for child in ast.iter_child_nodes(stmt):
            if isinstance(child, ast.expr):
                self._visit_expr(child)
            else:
                # stmt or non-stmt container (ExceptHandler,
                # match_case): recurse either way so fallback paths in
                # except blocks stay under shape discipline
                self._stmt(child)

    def _visit_expr(self, expr: ast.AST):
        for node in ast.walk(expr):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node.func)
            if name in JNP_CONSTRUCTORS and \
                    isinstance(node.func, ast.Attribute) and \
                    isinstance(node.func.value, ast.Name) and \
                    node.func.value.id == "jnp":
                if node.args and self._is_raw(node.args[0]):
                    self._flag(node, f"jnp.{name} shape derives from a "
                               "raw len(batch); route it through a "
                               "bucket helper (bucket_size, "
                               "_comb_k_pad, chunk constants)")
            elif name == "pad" and len(node.args) >= 2:
                if self._is_raw(node.args[1]):
                    self._flag(node, "pad width derives from a raw "
                               "len(batch); pad to a registered bucket "
                               "(bucket_size/_comb_k_pad/chunk "
                               "constants) instead")
            elif name in self.entries:
                for arg in list(node.args) + \
                        [kw.value for kw in node.keywords]:
                    if self._is_raw(arg):
                        self._flag(node, f"jit entry {name}() receives "
                                   "an argument sized by a raw "
                                   "len(batch) — this compiles one XLA "
                                   "shape class per batch size")
                        break


def _check_tm102(path: str, qual: str, fn: ast.AST,
                 findings: List[Finding]):
    """jit factories invoked inside a function body must cache their
    result."""
    # names that escape into a cache: attribute/subscript stores,
    # setdefault args, returns, or use inside a nested def (factory)
    escaped: Set[str] = set()
    nested_names: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node is not fn:
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name):
                    nested_names.add(sub.id)
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, (ast.Attribute, ast.Subscript)) \
                        and isinstance(node.value, ast.Name):
                    escaped.add(node.value.id)
        elif isinstance(node, ast.Return) and \
                isinstance(node.value, ast.Name):
            escaped.add(node.value.id)
        elif isinstance(node, ast.Call) and \
                _call_name(node.func) == "setdefault":
            for a in node.args:
                if isinstance(a, ast.Name):
                    escaped.add(a.id)

    for node in ast.walk(fn):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node is not fn:
            continue
        if not (isinstance(node, ast.Call) and _is_jit_factory(node)):
            continue
        parent_assign = None
        for st in ast.walk(fn):
            if isinstance(st, ast.Assign) and st.value is node:
                parent_assign = st
                break
        ok = False
        if parent_assign is not None:
            t = parent_assign.targets[0]
            if isinstance(t, (ast.Attribute, ast.Subscript)):
                ok = True  # stored straight into a cache slot
            elif isinstance(t, ast.Name) and (
                    t.id in escaped or t.id in nested_names):
                ok = True  # cached later / closed over by a factory
        else:
            # bare `return jax.jit(...)` or `cache[k] = jax.jit(...)`
            for st in ast.walk(fn):
                if isinstance(st, ast.Return) and st.value is node:
                    ok = True
                if isinstance(st, ast.Assign) and st.value is node and \
                        isinstance(st.targets[0],
                                   (ast.Attribute, ast.Subscript)):
                    ok = True
        if not ok:
            findings.append(Finding(
                "TM102", path, node.lineno, qual,
                "jax.jit/shard_map built inside a function without "
                "caching the result — this re-traces (and may "
                "recompile) on every call; hoist to module level or "
                "store in a keyed cache"))


def check(corpus: Corpus) -> List[Finding]:
    findings: List[Finding] = []
    for f in corpus.in_scope(*SCOPE):
        if f.tree is None:
            continue
        constants = module_constants(f.tree)
        entries = module_jit_entries(f.tree) | CROSS_MODULE_ENTRIES
        # top-level functions and class methods only: nested defs are
        # walked WITHIN their parent (shared bucket-local namespace),
        # never re-checked standalone with the taint context lost
        tops = [(n.name, n) for n in f.tree.body
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        for cls in f.tree.body:
            if isinstance(cls, ast.ClassDef):
                tops += [(f"{cls.name}.{n.name}", n) for n in cls.body
                         if isinstance(n, (ast.FunctionDef,
                                           ast.AsyncFunctionDef))]
        for qual, node in tops:
            if node.name in BUCKET_HELPERS:
                continue
            _FnShapeCheck(f.path, qual, constants, entries,
                          findings).run(node)
            _check_tm102(f.path, qual, node, findings)
    return findings
