"""Dead-code report: which ``repro`` modules nothing reachable imports.

A static import graph over ``src/repro`` plus the executable roots
(``launch/*``, ``benchmarks/``, ``examples/``, ``scripts/``), walked from
those roots.  Modules reachable only through a package ``__init__``
re-export (a weak edge) or only from ``tests/`` are classified
``TEST_ONLY``; modules reachable from nothing are ``DEAD``.  Both require
an entry in ``quarantine.txt`` (same directory as this file) naming why
they stay -- delete the module or write the tracking note, the gate
accepts nothing in between.

The walker is deliberately simple (top-level + function-local ``import``
statements, no importlib tricks); its job is drift detection on THIS
repo's plain import style, not general Python resolution.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, List, Set, Tuple

from repro.analysis.report import Violation

QUARANTINE_FILE = os.path.join(os.path.dirname(__file__), "quarantine.txt")


def _module_name(path: str, src_root: str) -> str:
    rel = os.path.relpath(path, src_root)
    mod = rel[:-3].replace(os.sep, ".")
    return mod[: -len(".__init__")] if mod.endswith(".__init__") else mod


def _imports_of(path: str) -> Set[str]:
    """Every dotted module mentioned in import statements, best effort.

    ``importlib.import_module(f"pkg.prefix.{name}")`` registers as the
    wildcard ``pkg.prefix.*`` -- the config registry's dynamic loading
    keeps its per-architecture modules alive.
    """
    with open(path) as f:
        try:
            tree = ast.parse(f.read(), filename=path)
        except SyntaxError:
            return set()
    found: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                found.add(alias.name)
        elif isinstance(node, ast.ImportFrom) and node.module and node.level == 0:
            found.add(node.module)
            # ``from pkg import name`` may bind the submodule pkg.name.
            for alias in node.names:
                found.add(f"{node.module}.{alias.name}")
        elif isinstance(node, ast.Call):
            fn = node.func
            is_import_module = (
                isinstance(fn, ast.Attribute) and fn.attr == "import_module"
            ) or (isinstance(fn, ast.Name) and fn.id == "import_module")
            if is_import_module and node.args:
                arg = node.args[0]
                if (
                    isinstance(arg, ast.JoinedStr)
                    and arg.values
                    and isinstance(arg.values[0], ast.Constant)
                ):
                    found.add(str(arg.values[0].value).rstrip(".") + ".*")
                elif isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                    found.add(arg.value)
    return found


def build_graph(
    repo_root: str,
) -> Tuple[Dict[str, str], Dict[str, Set[str]], Dict[str, Set[str]]]:
    """(module -> file, module -> deps, module -> strong deps) over
    src/repro.  Strong deps are the dynamic-import wildcards: real
    call-path dependencies even when they sit in a package ``__init__``
    whose plain re-export edges the walker treats as weak."""
    src_root = os.path.join(repo_root, "src")
    files: Dict[str, str] = {}
    for dirpath, _dirnames, filenames in os.walk(os.path.join(src_root, "repro")):
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                path = os.path.join(dirpath, fn)
                files[_module_name(path, src_root)] = path
    edges: Dict[str, Set[str]] = {}
    strong: Dict[str, Set[str]] = {}
    for mod, path in files.items():
        raw = _imports_of(path)
        deps = _resolve(raw, files)
        strong[mod] = _resolve({n for n in raw if n.endswith(".*")}, files)
        # importing any submodule imports its parent packages first
        parent = mod.rsplit(".", 1)[0]
        if parent in files:
            deps.add(parent)
        edges[mod] = deps - {mod}
    return files, edges, strong


def _resolve(names: Set[str], files: Dict[str, str]) -> Set[str]:
    """Map raw import names to known modules: longest known prefix wins
    (pkg.sub.attr -> pkg.sub); ``pkg.prefix.*`` wildcards fan out to every
    module under the prefix; stdlib/third-party names drop out."""
    deps: Set[str] = set()
    for name in names:
        if name.endswith(".*"):
            prefix = name[:-1]  # keep the trailing dot
            deps.update(m for m in files if m.startswith(prefix))
            continue
        parts = name.split(".")
        for cut in range(len(parts), 0, -1):
            cand = ".".join(parts[:cut])
            if cand in files:
                deps.add(cand)
                break
    return deps


def _dir_imports(dirs, files: Dict[str, str]) -> Set[str]:
    """repro modules imported by loose .py files in the given directories."""
    found: Set[str] = set()
    for d in dirs:
        if not os.path.isdir(d):
            continue
        for fn in sorted(os.listdir(d)):
            if fn.endswith(".py"):
                found |= _resolve(_imports_of(os.path.join(d, fn)), files)
    return found


def _reach(
    seeds: Set[str],
    edges: Dict[str, Set[str]],
    weak: Set[str],
    strong: Dict[str, Set[str]],
) -> Set[str]:
    """Transitive closure.  Out of weak (package ``__init__``) nodes only
    the strong (dynamic-import) edges are followed: a module reachable
    only because a package re-exports it is not pulled in by real
    call-path imports, but a registry that ``import_module``s its
    submodules genuinely loads them."""
    seen: Set[str] = set()
    todo = list(seeds)
    while todo:
        mod = todo.pop()
        if mod in seen:
            continue
        seen.add(mod)
        if mod in weak and mod not in seeds:
            todo.extend(strong.get(mod, ()))
            continue
        todo.extend(edges.get(mod, ()))
    return seen


def load_quarantine(path: str = QUARANTINE_FILE) -> Dict[str, str]:
    """``<module> <reason...>`` lines; '#' comments and blanks skipped."""
    entries: Dict[str, str] = {}
    if not os.path.exists(path):
        return entries
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            mod, _, reason = line.partition(" ")
            entries[mod] = reason.strip()
    return entries


def dead_modules(repo_root: str) -> Dict[str, str]:
    """module -> classification ('DEAD' | 'TEST_ONLY') for unreachable code.

    Roots: the ``launch`` entry points (the CLI surface), plus everything
    ``chip_smoke.py``, ``benchmarks/``, ``examples/`` and ``scripts/``
    import.  ``analysis`` is its own root (this tool and CI invoke it
    directly).
    """
    files, edges, strong = build_graph(repo_root)
    weak = {m for m, p in files.items() if p.endswith("__init__.py")}
    seeds = {m for m in files if m.startswith(("repro.launch", "repro.analysis"))}
    seeds |= _dir_imports(
        (os.path.join(repo_root, d) for d in ("benchmarks", "examples", "scripts")),
        files,
    )
    smoke = os.path.join(repo_root, "chip_smoke.py")
    if os.path.isfile(smoke):
        seeds |= _resolve(_imports_of(smoke), files)
    reachable = _reach(seeds, edges, weak, strong)
    test_seeds = _dir_imports((os.path.join(repo_root, "tests"),), files)
    test_reach = _reach(test_seeds | seeds, edges, set(), strong)
    out: Dict[str, str] = {}
    for mod in sorted(files):
        if mod in reachable:
            continue
        out[mod] = "TEST_ONLY" if mod in test_reach else "DEAD"
    return out


def report_dead(repo_root: str) -> Tuple[List[Violation], Dict[str, str]]:
    """Gate form: unreachable modules missing a quarantine entry are
    violations; returns (violations, full classification map)."""
    quarantine = load_quarantine()
    classes = dead_modules(repo_root)
    errors: List[Violation] = []
    for mod, kind in classes.items():
        if mod in quarantine:
            continue
        errors.append(
            Violation(
                "DEAD001",
                mod.replace(".", "/") + ".py",
                0,
                f"{kind}: no executable root imports this module -- delete "
                "it or add a tracked entry to analysis/quarantine.txt",
            )
        )
    for mod in quarantine:
        if mod not in classes:
            errors.append(
                Violation(
                    "DEAD002",
                    "src/repro/analysis/quarantine.txt",
                    0,
                    f"stale quarantine entry {mod!r}: the module is now "
                    "reachable (or gone) -- remove the entry",
                )
            )
    return errors, classes
