"""AST-plane driver: file discovery, parsing, rule dispatch, suppression.

The engine is deliberately free of jax imports so `make lint-fixtures`
stays a sub-second pure-Python pass; the jaxpr plane lives in
`lint.jaxpr_sweep` and is imported only when requested.
"""

from __future__ import annotations

import ast
import os
from typing import Callable, Dict, Iterable, List, Optional, Sequence

from . import rules as rules_mod
from .findings import Finding
from .suppress import scan as scan_suppressions

RuleFn = Callable[["ModuleCtx"], Iterable[Finding]]


class ModuleCtx:
    """Parsed module + shared derived facts handed to every rule."""

    def __init__(self, path: str, text: str, tree: ast.Module):
        self.path = path
        self.text = text
        self.tree = tree
        self.parents: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node
        # module aliases: local name -> dotted module (import time as t,
        # import numpy as np, from os import environ, ...)
        self.mod_aliases: Dict[str, str] = {}
        self.from_imports: Dict[str, str] = {}   # local name -> "mod.attr"
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.asname:
                        # `import numpy.random as npr` binds the full
                        # dotted module to the alias
                        self.mod_aliases[a.asname] = a.name
                    else:
                        # `import os.path` binds only `os` — recording
                        # 'os.path' under key 'os' would shadow the root
                        # module and blind R2 to os.environ reads
                        root = a.name.split(".")[0]
                        self.mod_aliases[root] = root
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    self.from_imports[a.asname or a.name] = \
                        f"{node.module}.{a.name}"
        self._traced = None   # lazy (rules.R2/R3 both need it)

    def ancestors(self, node: ast.AST):
        cur = self.parents.get(node)
        while cur is not None:
            yield cur
            cur = self.parents.get(cur)

    def enclosing_function(self, node: ast.AST) -> Optional[ast.AST]:
        for anc in self.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                return anc
        return None

    def dotted(self, node: ast.AST) -> str:
        """Best-effort dotted name of a Name/Attribute chain ('' if not)."""
        parts: List[str] = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if isinstance(node, ast.Name):
            parts.append(node.id)
            return ".".join(reversed(parts))
        return ""

    @property
    def traced(self):
        if self._traced is None:
            self._traced = rules_mod.find_traced_functions(self)
        return self._traced


RULES: Sequence[RuleFn] = (
    rules_mod.rule_r1_lock_discipline,
    rules_mod.rule_r2_trace_capture,
    rules_mod.rule_r3_pallas_tiling,
    rules_mod.rule_r4_callback_gating,
    rules_mod.rule_r5_artifact_honesty,
    rules_mod.rule_r6_site_derivation,
)


def lint_source(path: str, text: str,
                rules: Sequence[RuleFn] = RULES,
                _depth: int = 0) -> List[Finding]:
    """Lint one module's source.  Syntax errors are findings, not crashes
    (a half-written file must not take CI down with a traceback)."""
    sup = scan_suppressions(path, text)
    out: List[Finding] = list(sup.errors)
    try:
        tree = ast.parse(text, filename=path)
    except SyntaxError as e:
        out.append(Finding("R0", path, e.lineno or 1,
                           f"syntax error: {e.msg}"))
        return out
    ctx = ModuleCtx(path, text, tree)
    for rule in rules:
        for f in rule(ctx):
            hit, reason = sup.lookup(f.code, f.line)
            if hit:
                f = Finding(f.code, f.path, f.line, f.message,
                            suppressed=True, suppress_reason=reason)
            out.append(f)
    if _depth == 0:
        # child-script templates (tools that bank headline artifacts
        # from `python -c <SRC>` strings) are shipped code too:
        # lint any module-level string that parses as a Python script
        for name, start, src in _embedded_sources(tree):
            for f in lint_source(path, src, rules, _depth=1):
                out.append(Finding(
                    # embedded line 1 IS the string's start line, so the
                    # file line is start + line - 1 (off-by-one found by
                    # the round review)
                    f.code, f.path, start + f.line - 1,
                    f"[embedded {name}] {f.message}",
                    suppressed=f.suppressed,
                    suppress_reason=f.suppress_reason))
    return sorted(out, key=lambda f: (f.path, f.line, f.code))


def _embedded_sources(tree: ast.Module):
    """(name, start_line, source) for module-level string constants that
    look like embedded Python child scripts."""
    for node in tree.body:
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)):
            continue
        src = node.value.value
        if len(src) < 80 or "\n" not in src:
            continue
        try:
            sub = ast.parse(src)
        except (SyntaxError, ValueError):
            continue
        # a docstring-like constant parses to a bare Expr; a script has
        # real statements
        if any(not isinstance(s, ast.Expr) for s in sub.body):
            yield node.targets[0].id, node.value.lineno, src


def lint_paths(paths: Iterable[str]) -> List[Finding]:
    out: List[Finding] = []
    for p in paths:
        with open(p, "r", encoding="utf-8") as fh:
            text = fh.read()
        out.extend(lint_source(p, text))
    return out


def default_targets(repo_root: str) -> List[str]:
    """The lintable tree: the package, tools/, the bench drivers and the
    examples — NOT tests/ (fixtures there are deliberately bad, and test
    bodies poke stats internals on purpose)."""
    targets: List[str] = []
    for sub in ("fpga_ai_nic_tpu", "tools", "examples"):
        base = os.path.join(repo_root, sub)
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__", "csrc")]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    targets.append(os.path.join(dirpath, fn))
    for fn in ("bench_collective.py", "bench_common.py"):
        p = os.path.join(repo_root, fn)
        if os.path.exists(p):
            targets.append(p)
    return targets
