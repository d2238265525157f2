"""The program's module graph, read from source with ``ast``; nothing is imported.

Every ``repro`` import must resolve: the module exists under ``src/repro``
and every name taken from it is defined there.  That covers imports inside
functions, which run only when their function does (in ``chipbench/``, only
on the chip), so a deleted module or name otherwise surfaces late.  A name
is defined by a module-level ``def``, ``class``, assignment or import (also
under ``if``/``try``/``with``/``for``), or is a submodule.

Every module under ``src/repro`` must also lie on no cycle of the import
graph, function-level imports included.  An edge goes to each module an
import names: ``import P.Q`` and ``from P.Q import attr`` point at ``P.Q``;
``from P import sub`` points at the submodule ``P.sub`` alone.  The package
``__init__`` that Python runs before any of its submodules is not an edge,
so a package re-exporting its submodules is not a cycle.
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Set, Tuple

import pytest

ROOT = Path(__file__).resolve().parents[1]
SRC = ROOT / "src"

# (module imported, names taken from it or None for ``import m``, line)
ImportRecord = Tuple[str, Optional[List[str]], int]


def _module_name(path: Path) -> str:
    parts = list(path.relative_to(SRC).with_suffix("").parts)
    if parts[-1] == "__init__":
        parts.pop()
    return ".".join(parts)


def _parse(path: Path) -> ast.Module:
    return ast.parse(path.read_text(), filename=str(path))


MODULES: Dict[str, Path] = {
    _module_name(p): p
    for p in sorted((SRC / "repro").rglob("*.py"))
    if "__pycache__" not in p.parts
}
# namespace packages (no __init__.py) are modules too
PACKAGES: Set[str] = {"repro"} | {
    ".".join(d.relative_to(SRC).parts)
    for d in (SRC / "repro").rglob("*")
    if d.is_dir() and d.name != "__pycache__"
}
TREES: Dict[Path, ast.Module] = {p: _parse(p) for p in MODULES.values()}


def _is_module(name: str) -> bool:
    return name in MODULES or name in PACKAGES


def _repro_imports(tree: ast.Module, path: Path) -> Iterator[ImportRecord]:
    """Every import of ``repro`` in the file, at any depth, made absolute."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.split(".")[0] == "repro":
                    yield alias.name, None, node.lineno
        elif isinstance(node, ast.ImportFrom):
            module = node.module or ""
            if node.level:
                package = _module_name(path).split(".")
                if path.name != "__init__.py":
                    package.pop()
                package = package[: len(package) - (node.level - 1)]
                module = ".".join(package + ([module] if module else []))
            if module.split(".")[0] == "repro":
                yield module, [a.name for a in node.names], node.lineno


def _bound_names(stmts: List[ast.stmt]) -> Iterator[str]:
    """Names a block binds at its own level, descending into compound
    statements that do not open a scope."""
    for s in stmts:
        if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            yield s.name
        elif isinstance(s, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = s.targets if isinstance(s, ast.Assign) else [s.target]
            for t in targets:
                for n in ast.walk(t):
                    if isinstance(n, ast.Name):
                        yield n.id
        elif isinstance(s, (ast.Import, ast.ImportFrom)):
            for a in s.names:
                yield a.asname or a.name.split(".")[0]
        else:
            if isinstance(s, (ast.For, ast.AsyncFor)):
                yield from (n.id for n in ast.walk(s.target) if isinstance(n, ast.Name))
            for field in ("body", "orelse", "finalbody"):
                yield from _bound_names(getattr(s, field, []))
            for handler in getattr(s, "handlers", []):
                yield from _bound_names(handler.body)


DEFINED: Dict[str, Set[str]] = {
    name: set(_bound_names(TREES[path].body)) for name, path in MODULES.items()
}


def _unresolved(path: Path, tree: ast.Module) -> List[str]:
    errors = []
    for module, names, line in _repro_imports(tree, path):
        where = f"{path.relative_to(ROOT)}:{line}"
        if not _is_module(module):
            errors.append(f"{where}: no module {module}")
            continue
        for n in names or []:
            if n == "*" or n in DEFINED.get(module, ()) or _is_module(f"{module}.{n}"):
                continue
            errors.append(f"{where}: {module} defines no {n}")
    return errors


def _edges(name: str) -> Set[str]:
    path = MODULES[name]
    out = set()
    for module, names, _ in _repro_imports(TREES[path], path):
        subs = [f"{module}.{n}" for n in names or [] if _is_module(f"{module}.{n}")]
        out.update(subs)
        if names is None or len(subs) < len(names):
            out.add(module)
    out.discard(name)
    return out & MODULES.keys()


GRAPH: Dict[str, Set[str]] = {name: _edges(name) for name in MODULES}


def _on_cycle(start: str) -> Optional[List[str]]:
    """A path start -> ... -> start in GRAPH, or None."""
    stack = [(start, [start])]
    seen: Set[str] = set()
    while stack:
        node, trail = stack.pop()
        for nxt in GRAPH[node]:
            if nxt == start:
                return trail + [start]
            if nxt not in seen:
                seen.add(nxt)
                stack.append((nxt, trail + [nxt]))
    return None


def _imports_repro(path: Path) -> bool:
    return any(True for _ in _repro_imports(_parse(path), path))


OUTSIDE: List[Path] = [
    p
    for p in sorted(
        [ROOT / "chip_smoke.py"]
        + [p for d in ("examples", "benchmarks", "chipbench") for p in (ROOT / d).rglob("*.py")]
    )
    if "__pycache__" not in p.parts and _imports_repro(p)
]


@pytest.mark.parametrize("name", sorted(MODULES), ids=lambda n: str(MODULES[n].relative_to(ROOT)))
def test_repro_module_imports_resolve_and_form_no_cycle(name):
    path = MODULES[name]
    errors = _unresolved(path, TREES[path])
    assert not errors, "\n".join(errors)
    cycle = _on_cycle(name)
    assert cycle is None, " -> ".join(cycle)


@pytest.mark.parametrize("path", OUTSIDE, ids=lambda p: str(p.relative_to(ROOT)))
def test_repro_imports_outside_src_resolve(path):
    errors = _unresolved(path, _parse(path))
    assert not errors, "\n".join(errors)
