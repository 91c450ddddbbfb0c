"""The package's drawing, held as a test.

``mpit_tpu`` is almost a layered graph: ``obs`` at the bottom, then
``utils``/``aio``/``comm``, ``ft`` over ``comm``, the device side
(``ops`` under ``optim`` and ``parallel``, ``dplane`` under ``models`` and
``lm``), ``ps`` over all of these, ``train`` on top.  Nothing used to say
so, and a neighbour reached round the server's interface until the
server was written to let it (the multi-cell fabric: ROADMAP D11).  The
tables below say which package may import which, at module level and
inside a function; an edge that is in neither fails here, so a new reach
upward is an edit to this file that a reviewer sees.

The walk parses the files with ``ast`` and imports neither jax nor
``mpit_tpu``.
"""

import ast
import functools
import pathlib

import pytest

PKG = pathlib.Path(__file__).resolve().parent.parent / "mpit_tpu"
ROOT = "<root>"  # mpit_tpu/__init__.py and any module beside it

ANY = None  # train is the top: it may import every package

#: package -> the packages its modules may import at module level
#: (``if TYPE_CHECKING`` blocks and ``try`` bodies at the top of a file
#: count as module level).
ALLOWED = {
    "obs": set(),
    "utils": {"obs"},
    "aio": {"obs"},
    "comm": {"obs", "utils"},
    "ft": {"comm", "obs", "utils"},
    "ops": set(),
    "data": set(),
    "analysis": set(),
    "optim": {"obs", "ops"},
    "parallel": {"ops", "optim"},
    "dplane": {"obs", "optim", "utils"},
    "models": {"dplane", "ops", "parallel"},
    "ps": {"aio", "comm", "dplane", "ft", "obs", "optim", "shardctl",
           "utils"},
    "shardctl": {"aio", "ft", "obs", "ps", "utils"},
    "agg": {"aio", "comm", "dplane", "ft", "obs", "ps", "utils"},
    "lm": {"data", "dplane", "models", "obs", "optim", "utils"},
    "train": ANY,
    ROOT: {"utils"},  # mpit_tpu/__init__.py re-exports Config
}

#: Imports made inside a function, beyond what ALLOWED already grants:
#: each entry is an arrow that points back up the drawing, and a debt
#: with a name (ROADMAP D18).
LAZY = {
    "dplane": {"ps", "shardctl", "parallel"},
    "ft": {"train"},
    "lm": {"shardctl"},
    "obs": {"comm", "utils"},
    "parallel": {"utils"},
}

#: Module-level cycles that exist today.  ``ps`` <-> ``shardctl``: the
#: server and the client speak the shard map's wire format, and the
#: controller is built from the server's pieces (ROADMAP D11, D18).
KNOWN_CYCLES = {frozenset({"ps", "shardctl"})}


def _package_of(path: pathlib.Path) -> str:
    rel = path.relative_to(PKG)
    return rel.parts[0] if len(rel.parts) > 1 else ROOT


def _target(node: ast.AST, path: pathlib.Path):
    """The ``mpit_tpu`` packages one import statement names."""
    if isinstance(node, ast.Import):
        names = [a.name for a in node.names]
    elif node.level == 0:
        names = [node.module or ""]
        if node.module == "mpit_tpu":  # from mpit_tpu import obs, ps
            names = [f"mpit_tpu.{a.name}" for a in node.names]
    else:  # relative: resolve against the file's own package
        base = path.relative_to(PKG.parent).parent.parts
        base = base[:len(base) - (node.level - 1)]
        stem = ".".join(base + ((node.module,) if node.module else ()))
        names = ([stem] if node.module
                 else [f"{stem}.{a.name}" for a in node.names])
    for name in names:
        parts = name.split(".")
        if parts[0] != "mpit_tpu":
            continue
        if len(parts) > 1 and (PKG / parts[1]).is_dir():
            yield parts[1]
        else:
            yield ROOT


@functools.lru_cache(maxsize=None)  # parsed by the first test, not at import
def _edges():
    """{package: ({module-level targets}, {function-level targets})},
    each target with one ``file:line`` that shows it."""
    out = {}
    for path in sorted(PKG.rglob("*.py")):
        pkg = _package_of(path)
        top, lazy = out.setdefault(pkg, ({}, {}))
        tree = ast.parse(path.read_text(encoding="utf-8"))

        def walk(node, inside):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.Import, ast.ImportFrom)):
                    where = f"{path.relative_to(PKG.parent)}:{child.lineno}"
                    for tgt in _target(child, path):
                        if tgt != pkg:
                            (lazy if inside else top).setdefault(tgt, where)
                walk(child, inside or isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda)))

        walk(tree, False)
    return out


def test_the_table_names_every_package():
    on_disk = {p.name for p in PKG.iterdir()
               if p.is_dir() and any(p.glob("*.py"))} | {ROOT}
    assert set(ALLOWED) == on_disk
    assert len(on_disk) == 18  # 17 packages and the root modules
    assert set(LAZY) <= set(ALLOWED)


@pytest.mark.parametrize(
    "pkg", sorted(p for p, row in ALLOWED.items() if row is not ANY))
def test_package_imports_only_what_its_row_allows(pkg):
    allowed = ALLOWED[pkg]
    top, lazy = _edges().get(pkg, ({}, {}))
    bad = {t: w for t, w in top.items() if t not in allowed}
    assert not bad, (
        f"mpit_tpu/{pkg} imports at module level what its row does not "
        f"allow: {bad}")
    bad = {t: w for t, w in lazy.items()
           if t not in allowed | LAZY.get(pkg, set())}
    assert not bad, (
        f"mpit_tpu/{pkg} imports inside a function what neither table "
        f"allows: {bad}")
    # a row that allows what nothing imports has gone stale
    unused = (allowed - set(top) - set(lazy)) | (
        LAZY.get(pkg, set()) - set(lazy))
    assert not unused, f"mpit_tpu/{pkg}: the tables allow unused {unused}"


def test_no_module_level_cycle_but_the_known_ones():
    graph = {pkg: set(top) for pkg, (top, _lazy) in _edges().items()}

    def reach(start):
        seen, todo = set(), [start]
        while todo:
            for nxt in graph.get(todo.pop(), ()):
                if nxt not in seen:
                    seen.add(nxt)
                    todo.append(nxt)
        return seen

    reached = {pkg: reach(pkg) for pkg in graph}
    cycles = {frozenset(b for b in reached[a] if a in reached.get(b, ()))
              for a in graph if a in reached[a]}
    assert cycles == KNOWN_CYCLES, sorted(map(sorted, cycles))
