"""The repository hangs together: imports resolve, documents name what exists.

Several callers import parts of the package lazily, inside ``try: ... except
Exception`` — a module that is removed or renamed then degrades them in
silence, and no behavioural test notices. The documents rot the same way.
These tests walk the sources (no subprocess, no JAX program) and fail with
file and line.
"""

import ast
import fnmatch
import functools
import glob
import importlib
import importlib.util
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PKG = "deepspeed_tpu"


def _rel(*parts):
    return os.path.join(REPO, *parts)


def _listed(*parts):
    return sorted(e for e in os.listdir(_rel(*parts)) if e != "__pycache__")


# ------------------------------------------------------------------ imports
# one unit per script, per sub-package directory, and one for the package's
# top-level modules
_SCRIPTS = (
    [f"tools/{f}" for f in _listed("tools") if f.endswith(".py")]
    + [f"bin/{f}" for f in _listed("bin")]
    + ["chip_smoke.py", "__graft_entry__.py"])
_SUBPACKAGES = [f"{PKG}/{d}" for d in _listed(PKG) if os.path.isdir(_rel(PKG, d))]
IMPORT_UNITS = _SCRIPTS + _SUBPACKAGES + [f"{PKG}/*.py"]


def _unit_files(unit):
    if unit in _SUBPACKAGES:
        return sorted(os.path.join(dirpath, f)
                      for dirpath, _dirs, files in os.walk(_rel(unit))
                      for f in files if f.endswith(".py"))
    return sorted(glob.glob(_rel(unit)))


def _package_of(path):
    """Dotted package a file's relative imports resolve against."""
    rel = os.path.relpath(os.path.dirname(path), REPO)
    return rel.replace(os.sep, ".") if rel.startswith(PKG) else None


@functools.lru_cache(maxsize=None)
def _missing_module(name):
    """None when ``name`` can be found, else why not."""
    try:
        return None if importlib.util.find_spec(name) else f"no module {name}"
    except ImportError as e:  # a parent package is missing or fails to import
        return f"no module {name} ({e})"


@functools.lru_cache(maxsize=None)
def _has_name(module, name):
    return (hasattr(importlib.import_module(module), name)
            or _missing_module(f"{module}.{name}") is None)


def _unresolved(path):
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    where = os.path.relpath(path, REPO)
    for node in ast.walk(tree):  # any depth: function bodies, try blocks
        if isinstance(node, ast.Import):
            modules, names = [a.name for a in node.names], []
        elif isinstance(node, ast.ImportFrom):
            module = node.module or ""
            if node.level:
                module = importlib.util.resolve_name(
                    "." * node.level + module, _package_of(path))
            modules, names = [module], [a.name for a in node.names if a.name != "*"]
        else:
            continue
        for module in modules:
            if module.split(".")[0] != PKG:
                continue
            why = _missing_module(module)
            if why:
                yield f"{where}:{node.lineno}: {why}"
                continue
            for name in names:
                if not _has_name(module, name):
                    yield f"{where}:{node.lineno}: {module} has no {name}"


@pytest.mark.parametrize("unit", IMPORT_UNITS)
def test_imports_resolve(unit):
    files = _unit_files(unit)
    assert files, f"{unit} names no file"
    problems = [p for path in files for p in _unresolved(path)]
    assert not problems, "\n".join(problems)


# ---------------------------------------------------------------- documents
# PERF.md, CHANGES.md and ROADMAP.md are histories and name what is gone
DOCS = ["README.md"] + [f"docs/{f}" for f in _listed("docs") if f.endswith(".md")]
_PATH_PREFIXES = ("deepspeed_tpu/", "tools/", "benchmarks/", "tests/", "bin/", "perf/")
_TICKED = re.compile(r"`([^`\n]+)`")
_BARE_BENCH = re.compile(r"(?<!\w)bench\.py(?!\w)")


@pytest.mark.parametrize("doc", DOCS)
def test_docs_name_only_paths_that_exist(doc):
    with open(_rel(doc), encoding="utf-8") as f:
        lines = f.read().splitlines()
    problems = []
    for n, line in enumerate(lines, 1):
        if _BARE_BENCH.search(line):
            problems.append(f"{doc}:{n}: names bench.py")
        for token in _TICKED.findall(line):
            if not token.startswith(_PATH_PREFIXES):
                continue
            path = token.split()[0].split(":")[0]
            if re.search(r"[<>{}]", path):  # a pattern, not a path
                continue
            if not glob.glob(_rel(path)):
                problems.append(f"{doc}:{n}: `{path}` does not exist")
    assert not problems, "\n".join(problems)


# --------------------------------------------------------------------- root
def test_root_has_no_run_artifacts():
    """Speed is recorded by ``benchmarks/`` in ``PERF_LEDGER.jsonl`` and
    nowhere else: no per-round run artifact, no second benchmark, no second
    ledger (read from the directory, so an unpacked copy without ``.git``
    is judged too)."""
    entries = os.listdir(REPO)
    artifacts = sorted(e for e in entries
                       if fnmatch.fnmatch(e, "*_r[0-9][0-9].json")
                       or fnmatch.fnmatch(e, "*_r[0-9][0-9].log"))
    assert not artifacts
    assert "bench.py" not in entries and "perf" not in entries
