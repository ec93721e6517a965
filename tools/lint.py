"""Stdlib lint gate (`make lint`).

The reference CI enforces `clippy -D warnings` + rustfmt + cargo-sort
(/root/reference/Makefile:37-53). This environment ships no ruff/mypy and
installs are off-limits, so the gate is a from-scratch AST linter covering
the highest-signal subset:

  F401  unused import
  F403  `from x import *`
  F811  redefinition of an imported name by another import
  F601  duplicate key in a dict literal
  E101  tab indentation / CRLF line endings
  E501  line longer than MAX_LINE columns
  W291  trailing whitespace
  B006  mutable default argument (list/dict/set literals)
  C901  bare `except:` (use `except Exception` at minimum)

Zero findings is the bar: the tree is kept clean and CI (make lint) fails
on any regression. Exit code = number of findings (capped 125).
"""

from __future__ import annotations

import ast
import re
import sys
from pathlib import Path

MAX_LINE = 100
_IDENT = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")

# names a module re-exports on purpose (import kept for its side effect or
# for package API) — the linter honors `__all__` and `# noqa` instead of a
# config file
NOQA = "# noqa"


def iter_py_files(roots: list[str]) -> list[Path]:
    out: list[Path] = []
    for r in roots:
        p = Path(r)
        if p.is_file() and p.suffix == ".py":
            out.append(p)
        elif p.is_dir():
            out.extend(sorted(p.rglob("*.py")))
        else:
            # a vanished root must FAIL the gate, not quietly narrow it
            raise SystemExit(f"lint: root does not exist: {r}")
    # pb/ holds protoc codegen — machine-formatted, not held to hand-written
    # style (the reference likewise lints source, not generated stubs)
    return [p for p in out
            if "__pycache__" not in p.parts and "pb" not in p.parts]


class ImportVisitor(ast.NodeVisitor):
    """Collect imported names + every identifier/attribute usage."""

    def __init__(self) -> None:
        self.imports: dict[str, tuple[int, str]] = {}  # name -> (line, code)
        self.used: set[str] = set()
        self.stars: list[int] = []          # lineno of each `import *`
        self.redefs: list[tuple[str, int]] = []  # (name, lineno) reimports
        self._depth = 0                     # function/class nesting
        self._module_imports: set[str] = set()

    def _record(self, name: str, lineno: int) -> None:
        # F811 only for MODULE-level redefinition — re-importing inside a
        # function body is deliberate scoping (lazy imports), not shadowing
        if self._depth == 0:
            if name in self._module_imports:
                self.redefs.append((name, lineno))
            self._module_imports.add(name)
        self.imports[name] = (lineno, "F401")

    def _scoped(self, node: ast.AST) -> None:
        self._depth += 1
        self.generic_visit(node)
        self._depth -= 1

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._mark_annotation(node.returns)
        self._scoped(node)

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        self._mark_annotation(node.returns)
        self._scoped(node)

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._scoped(node)

    def visit_Try(self, node: ast.Try) -> None:
        # the try/except ImportError fallback-import idiom re-imports the
        # same name by design — not an F811 redefinition
        self._scoped(node)

    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            self._record(a.asname or a.name.split(".")[0], node.lineno)
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module == "__future__":
            self.generic_visit(node)
            return
        for a in node.names:
            if a.name == "*":
                self.stars.append(node.lineno)
                continue
            self._record(a.asname or a.name, node.lineno)
        self.generic_visit(node)

    def visit_Name(self, node: ast.Name) -> None:
        if isinstance(node.ctx, ast.Load):
            self.used.add(node.id)
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        # `np.foo` marks `np` used via the Name child; nothing extra needed
        self.generic_visit(node)

    def _mark_annotation(self, ann: ast.expr | None) -> None:
        """Quoted annotations (`x: "PathLike"`, the TYPE_CHECKING idiom)
        are plain strings in the AST; count their identifier tokens as
        usages so F401 doesn't fire on them. Docstrings deliberately do
        NOT count — only annotation positions."""
        if ann is None:
            return
        for sub in ast.walk(ann):
            if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
                self.used.update(_IDENT.findall(sub.value))

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        self._mark_annotation(node.annotation)
        self.generic_visit(node)

    def visit_arg(self, node: ast.arg) -> None:
        self._mark_annotation(node.annotation)
        self.generic_visit(node)


def lint_file(path: Path) -> list[str]:
    findings: list[str] = []
    raw = path.read_bytes()
    text = raw.decode("utf-8", errors="replace")
    # split on \n only: ast.parse counts only \n/\r\n as line breaks, and
    # splitlines() would also split on \f/\v/ , desyncing linenos
    lines = text.split("\n")

    def flagged(lineno: int) -> bool:
        return 0 < lineno <= len(lines) and NOQA in lines[lineno - 1]

    if b"\r\n" in raw:
        findings.append(f"{path}:1: E101 CRLF line endings")
    for i, line in enumerate(lines, 1):
        if NOQA in line:
            continue
        if line.rstrip("\n") != line.rstrip():
            findings.append(f"{path}:{i}: W291 trailing whitespace")
        if "\t" in line.split("#")[0]:
            findings.append(f"{path}:{i}: E101 tab in source")
        if len(line) > MAX_LINE:
            findings.append(
                f"{path}:{i}: E501 line too long ({len(line)} > {MAX_LINE})"
            )

    try:
        tree = ast.parse(text, filename=str(path))
    except SyntaxError as e:
        return [f"{path}:{e.lineno}: E999 syntax error: {e.msg}"]

    # names listed in the module __all__ count as used (re-exports)
    exported: set[str] = set()
    for node in tree.body:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "__all__":
                    if isinstance(node.value, (ast.List, ast.Tuple)):
                        for elt in node.value.elts:
                            if isinstance(elt, ast.Constant):
                                exported.add(str(elt.value))

    v = ImportVisitor()
    v.visit(tree)
    is_init = path.name == "__init__.py"
    for name, (lineno, _code) in v.imports.items():
        if name in v.used or name in exported or name.startswith("_"):
            continue
        if is_init:  # packages re-export via imports by design
            continue
        if flagged(lineno):
            continue
        findings.append(f"{path}:{lineno}: F401 unused import: {name}")
    for lineno in v.stars:
        if not flagged(lineno):
            findings.append(f"{path}:{lineno}: F403 star import")
    for name, lineno in v.redefs:
        if not flagged(lineno):
            findings.append(
                f"{path}:{lineno}: F811 import redefines earlier "
                f"import: {name}"
            )

    for node in ast.walk(tree):
        if isinstance(node, ast.Dict):
            seen: set = set()
            for k in node.keys:
                if isinstance(k, ast.Constant):
                    if k.value in seen and not flagged(k.lineno):
                        findings.append(
                            f"{path}:{k.lineno}: F601 duplicate dict key: "
                            f"{k.value!r}"
                        )
                    seen.add(k.value)
        elif isinstance(node, ast.ExceptHandler):
            if node.type is None and not flagged(node.lineno):
                findings.append(f"{path}:{node.lineno}: C901 bare except")
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for d in node.args.defaults + node.args.kw_defaults:
                if isinstance(d, (ast.List, ast.Dict, ast.Set)) \
                        and not flagged(d.lineno):
                    findings.append(
                        f"{path}:{d.lineno}: B006 mutable default argument "
                        f"in {node.name}()"
                    )
    return findings


def main() -> None:
    roots = sys.argv[1:] or [
        "horaedb_tpu", "tests", "benchmarks", "tools",
        "bench.py", "chip_smoke.py", "__graft_entry__.py",
    ]
    files = iter_py_files(roots)
    all_findings: list[str] = []
    for f in files:
        all_findings.extend(lint_file(f))
    for line in all_findings:
        print(line)
    n = len(all_findings)
    print(f"lint: {n} finding(s) in {len(files)} files")
    raise SystemExit(min(n, 125))


if __name__ == "__main__":
    main()
