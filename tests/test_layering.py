"""Import direction of the package, read from the source with ``ast``.

Nothing is imported and no JAX starts: each case parses one file.

``src/repro`` is layered, lowest first::

    compat < obs < kernels < core < queries < resilience < cluster
           < dynamic < data < launch

(``repro/__init__.py`` and ``compat.py`` form the lowest layer.)  A
module may import its own layer or one below it.  The imports that
point upward today are named in ``BACK_EDGES``; the list may only
shrink, so an entry that no import uses fails too.

The benchmark harness in ``bench/`` checks the program against a
reference of its own: only ``bench/run.py`` imports ``repro``, and only
the serving surface it drives.
"""

import ast
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SRC = ROOT / "src" / "repro"
BENCH = ROOT / "bench"

LAYERS = ("compat", "obs", "kernels", "core", "queries", "resilience",
          "cluster", "dynamic", "data", "launch")

# (importing module, layer it reaches up to): the upward edges the
# package still has, each a debt to repay, not a pattern to follow
BACK_EDGES = {
    # the one-call facade builds cluster and dynamic engines and runs
    # the query classes' host paths from the core layer
    ("core/api.py", "cluster"),
    ("core/api.py", "dynamic"),
    ("core/api.py", "queries"),
    # the device engine returns the query classes' results, drives their
    # kNN search, and calls the fault-injection hooks
    ("core/engine.py", "queries"),
    ("core/engine.py", "resilience"),
    # polygon RangeReach answers through the query classes' host descent
    ("core/polygon.py", "queries"),
    # the exactness auditor checks answers against the BFS oracle
    ("obs/audit.py", "core"),
    # the profiler reads the kernels' tile sizes
    ("obs/profiler.py", "kernels"),
}

# the serving surface the harness drives; every other bench module is
# independent of the program
BENCH_RUN_IMPORTS = {"repro.cluster", "repro.core", "repro.core.graph",
                     "repro.obs"}


def _rel(path: Path) -> str:
    return path.relative_to(SRC).as_posix()


def _module_name(path: Path) -> str:
    parts = ["repro", *path.relative_to(SRC).with_suffix("").parts]
    return ".".join(parts[:-1] if parts[-1] == "__init__" else parts)


def _exists(name: str) -> bool:
    """``repro.a.b`` is ``src/repro/a/b.py`` or a directory ``a/b``
    (a package, or a directory of modules with no ``__init__.py``)."""
    p = SRC.joinpath(*name.split(".")[1:])
    return p.with_suffix(".py").is_file() or p.is_dir()


def _repro_imports(path: Path, package: str):
    """Every ``repro`` module the file imports, at any depth of its
    code, as ``(line, dotted name)``.  ``from X import y`` names
    ``X.y`` where that is a module, else ``X``."""
    for node in ast.walk(ast.parse(path.read_text(), filename=str(path))):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name.split(".")[0] == "repro":
                    yield node.lineno, a.name
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                parts = package.split(".")
                base = parts[:len(parts) - node.level + 1]
                mod = ".".join(base + ([node.module] if node.module
                                       else []))
            else:
                mod = node.module or ""
            if mod.split(".")[0] != "repro":
                continue
            for a in node.names:
                sub = f"{mod}.{a.name}"
                yield node.lineno, (sub if _exists(sub) else mod)


def _layer(name: str) -> str:
    parts = name.split(".")
    return "compat" if len(parts) == 1 else parts[1]


SRC_FILES = sorted(SRC.rglob("*.py"))
BENCH_FILES = sorted(BENCH.glob("*.py"))


@pytest.mark.parametrize("path", SRC_FILES, ids=_rel)
def test_imports_point_down(path):
    rel = _rel(path)
    name = _module_name(path)
    package = name if path.name == "__init__.py" else name.rpartition(".")[0]
    own = _layer(name)
    assert own in LAYERS, f"{rel}: not in any layer"
    bad, used = [], set()
    for line, target in _repro_imports(path, package):
        if not _exists(target):
            bad.append(f"{rel}:{line} imports {target}, which does not exist")
            continue
        layer = _layer(target)
        if layer not in LAYERS:
            bad.append(f"{rel}:{line} imports {target}, in no layer")
        elif LAYERS.index(layer) > LAYERS.index(own):
            if (rel, layer) in BACK_EDGES:
                used.add((rel, layer))
            else:
                bad.append(f"{rel}:{line} imports {target}, above {own}")
    bad += [f"{rel}: allowed back-edge to {layer} is no longer used; "
            f"remove it from BACK_EDGES"
            for mod, layer in sorted(BACK_EDGES - used) if mod == rel]
    bad += [f"BACK_EDGES names {mod}, which does not exist"
            for mod, _ in sorted(BACK_EDGES) if not (SRC / mod).is_file()]
    assert not bad, "\n".join(bad)


@pytest.mark.parametrize("path", BENCH_FILES, ids=lambda p: p.name)
def test_bench_harness_imports(path):
    allowed = BENCH_RUN_IMPORTS if path.name == "run.py" else set()
    got = {name for _, name in _repro_imports(path, "bench")}
    assert got <= allowed, f"bench/{path.name} imports {sorted(got - allowed)}"
