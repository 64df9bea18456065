"""AST policy linter: repo invariants ruff's rule set cannot express.

Three rules, each born from a real breakage mode in this codebase:

- **compat-only-experimental** — ``jax.experimental`` (and
  ``shard_map`` in particular) may be imported ONLY in
  ``runtime/compat.py``: jax moves experimental APIs between releases
  (``jax.experimental.shard_map`` -> ``jax.shard_map``), and one module
  absorbs the next move.  The Pallas kernels are exempt —
  ``jax.experimental.pallas`` *is* their API surface.
- **core-lazy-jax** — no module-top ``jax`` import anywhere under
  ``core/``: the planning layer (partitioner, scheduler, cost models) is
  pure numpy/python by design, importable in schedulers, CI linters and
  notebook tooling without pulling in a multi-second jax import (or any
  accelerator runtime at all).  Function-local imports are fine — that
  is the sanctioned lazy pattern.  ``if TYPE_CHECKING:`` blocks are
  exempt.
- **guarded-placement-extrema** — in ``core/schedule.py``, ``max()`` /
  ``min()`` over a placements-derived iterable must either pass
  ``default=`` or sit in a scope that first guards the empty case
  (``if not ...: raise/return``): an empty-schedule edge case once
  turned into a bare ``ValueError: max() arg is an empty sequence``
  three layers from the actual bug.

CLI: ``python -m repro.analysis.lint [paths...]`` (default: ``src``,
``tests``, ``benchmarks`` under the repo root).  Exit 0 when clean.
"""
from __future__ import annotations

import ast
import dataclasses
import pathlib
import sys

RULES = ("compat-only-experimental", "core-lazy-jax",
         "guarded-placement-extrema")

#: modules allowed to touch jax.experimental / shard_map directly —
#: the compat shim itself, plus runtime/sharding.py (the PartitionSpec
#: rule tables sit next to the sharding entry points it re-exports)
COMPAT_MODULES = ("runtime/compat.py", "runtime/sharding.py")
#: subtrees exempt from the compat rule (pallas IS the kernel API)
KERNEL_PREFIX = "kernels/"


@dataclasses.dataclass(frozen=True)
class LintFinding:
    rule: str
    path: str
    line: int
    detail: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.detail}"


def _repro_relpath(path: pathlib.Path) -> str | None:
    """Path relative to the ``repro`` package root, or None outside it."""
    parts = path.as_posix().split("/")
    if "repro" in parts:
        return "/".join(parts[parts.index("repro") + 1:])
    return None


def _imported_modules(node: ast.AST):
    if isinstance(node, ast.Import):
        for alias in node.names:
            yield alias.name
    elif isinstance(node, ast.ImportFrom) and node.module and not node.level:
        yield node.module


class _FileLinter(ast.NodeVisitor):
    def __init__(self, path: pathlib.Path, rel: str | None):
        self.path, self.rel = path, rel
        self.findings: list[LintFinding] = []
        self._func_depth = 0
        self._type_checking = 0

    def flag(self, rule: str, node: ast.AST, detail: str):
        self.findings.append(
            LintFinding(rule, str(self.path), node.lineno, detail))

    # ---- scope tracking ------------------------------------------------
    def visit_FunctionDef(self, node):
        self._func_depth += 1
        self.generic_visit(node)
        self._func_depth -= 1

    visit_AsyncFunctionDef = visit_FunctionDef
    visit_Lambda = visit_FunctionDef

    def visit_If(self, node):
        is_tc = isinstance(node.test, ast.Name) and \
            node.test.id == "TYPE_CHECKING"
        self._type_checking += is_tc
        self.generic_visit(node)
        self._type_checking -= is_tc

    # ---- rule 1 + 2: import policy -------------------------------------
    def _check_import(self, node):
        in_core = self.rel is not None and self.rel.startswith("core/")
        exempt_compat = self.rel is None or self.rel in COMPAT_MODULES \
            or (self.rel or "").startswith(KERNEL_PREFIX)
        for mod in _imported_modules(node):
            root = mod.split(".")[0]
            if not exempt_compat and (
                    mod.startswith("jax.experimental")
                    or (isinstance(node, ast.ImportFrom)
                        and mod == "jax"
                        and any(a.name == "experimental"
                                for a in node.names))):
                self.flag(
                    "compat-only-experimental", node,
                    f"import of {mod!r}: jax.experimental/shard_map may "
                    "only be imported via repro.runtime.compat (kernels "
                    "exempt)")
            if in_core and root == "jax" and self._func_depth == 0 \
                    and not self._type_checking:
                self.flag(
                    "core-lazy-jax", node,
                    "module-top jax import under core/ — the planning "
                    "layer must import without jax; move it inside the "
                    "function that needs it")
        self.generic_visit(node)

    visit_Import = _check_import
    visit_ImportFrom = _check_import


def _mentions_placements(node: ast.AST) -> bool:
    return any((isinstance(n, ast.Name) and "placement" in n.id)
               or (isinstance(n, ast.Attribute) and "placement" in n.attr)
               for n in ast.walk(node))


def _scope_nodes(scope: ast.AST):
    """Walk a scope's own statements, not those of nested functions
    (each nested def is analyzed as its own scope)."""
    stack = list(ast.iter_child_nodes(scope))
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)):
            continue
        yield n
        stack.extend(ast.iter_child_nodes(n))


def _has_empty_guard(scope: ast.AST) -> bool:
    """An ``if`` mentioning placements whose body raises or returns —
    the sanctioned empty-schedule guard pattern."""
    for n in _scope_nodes(scope):
        if isinstance(n, ast.If) and _mentions_placements(n.test) and any(
                isinstance(s, (ast.Raise, ast.Return))
                for b in n.body for s in ast.walk(b)):
            return True
    return False


def _check_extrema(tree: ast.AST, path: pathlib.Path
                   ) -> list[LintFinding]:
    findings = []
    scopes = [tree] + [n for n in ast.walk(tree)
                       if isinstance(n, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))]
    for scope in scopes:
        guarded = _has_empty_guard(scope)
        for n in _scope_nodes(scope):
            if not (isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
                    and n.func.id in ("max", "min")):
                continue
            if len(n.args) != 1 or any(k.arg == "default"
                                       for k in n.keywords):
                continue        # max(a, b) / max(..., default=...) are fine
            if not _mentions_placements(n.args[0]):
                continue
            if guarded:
                continue
            findings.append(LintFinding(
                "guarded-placement-extrema", str(path), n.lineno,
                f"bare {n.func.id}() over a placements-derived iterable "
                "with no default= and no empty-schedule guard in scope "
                "(empty schedules raise a bare ValueError here)"))
    return findings


def lint_file(path: pathlib.Path) -> list[LintFinding]:
    rel = _repro_relpath(path)
    try:
        tree = ast.parse(path.read_text(), filename=str(path))
    except SyntaxError as e:
        return [LintFinding("parse", str(path), e.lineno or 0, str(e))]
    linter = _FileLinter(path, rel)
    linter.visit(tree)
    findings = linter.findings
    if rel == "core/schedule.py":
        findings += _check_extrema(tree, path)
    return findings


def lint_paths(paths) -> list[LintFinding]:
    findings: list[LintFinding] = []
    for p in paths:
        p = pathlib.Path(p)
        files = sorted(p.rglob("*.py")) if p.is_dir() else [p]
        for f in files:
            findings.extend(lint_file(f))
    return findings


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv:
        root = pathlib.Path(__file__).resolve().parents[3]
        argv = [str(root / d) for d in ("src", "tests", "benchmarks")
                if (root / d).is_dir()]
    findings = lint_paths(argv)
    for f in findings:
        print(f)
    print(f"policy lint: {len(findings)} finding(s) in "
          f"{len(argv)} path(s)")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
