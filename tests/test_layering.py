"""The layer map of ``ompi_release_tpu``, held as a test.

Every module of the package is parsed with ``ast`` (imports inside
functions count like those at the top) and each sub-package's set of
imported sibling packages is held against ``LAYERS``: a package may
import from any layer strictly below its own. An import that points
sideways or upward has to stand in ``KNOWN_UPWARD`` with the modules
that make it. A case fails when its package gains such an edge (or a
known edge gains a module), and when an entry of ``KNOWN_UPWARD`` is no
longer made by anything — so the set can only shrink.

The order is the one with the fewest upward edges that keeps ``obs``
below the packages that record through it: 20. (Placing ``obs`` above
``btl`` and ``ops`` gives 19, at the price of calling ``btl``'s three
uses of ``obs.record`` the exception and ``obs/__main__.py``'s imports
of what it self-tests the rule.) README.md's layer map is this table.
"""

import ast
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = "ompi_release_tpu"

#: bottom up; packages of one layer do not import each other
LAYERS = (
    ("utils",),
    ("mca",),
    ("native", "datatype"),
    ("obs",),
    ("request", "ops", "btl"),
    ("tuning",),
    ("runtime",),
    ("io",),
    ("ft",),
    ("coll", "p2p"),
    ("topo", "parallel", "service"),
    ("comm",),
    ("osc", "testing", "models"),
    ("oshmem", "tools"),
)

#: (importer, imported) -> the modules that make the edge today
KNOWN_UPWARD = {
    ("utils", "mca"): {"utils/memchecker.py", "utils/output.py"},
    ("obs", "btl"): {"obs/__main__.py"},
    ("obs", "ops"): {"obs/__main__.py"},
    ("obs", "tuning"): {"obs/__main__.py"},
    ("obs", "coll"): {"obs/__main__.py"},
    ("obs", "parallel"): {"obs/__main__.py"},
    ("obs", "osc"): {"obs/__main__.py"},
    ("obs", "tools"): {"obs/__main__.py", "obs/watchdog.py"},
    ("obs", "runtime"): {"obs/__init__.py", "obs/export.py",
                         "obs/sentinel.py"},
    ("obs", "ft"): {"obs/sampler.py", "obs/sentinel.py"},
    ("ops", "parallel"): {"ops/pallas_op.py"},
    ("tuning", "coll"): {"tuning/db.py", "tuning/retune.py"},
    ("tuning", "testing"): {"tuning/retune.py"},
    ("runtime", "ft"): {"runtime/coordinator.py", "runtime/runtime.py",
                        "runtime/wire.py"},
    ("runtime", "service"): {"runtime/wire.py"},
    ("runtime", "comm"): {"runtime/runtime.py", "runtime/wire.py"},
    ("ft", "comm"): {"ft/errmgr.py", "ft/ulfm.py"},
    ("coll", "comm"): {"coll/hier.py"},
    ("service", "tools"): {"service/daemon.py"},
    ("comm", "tools"): {"comm/spawn.py"},
}

LEVEL = {p: i for i, layer in enumerate(LAYERS) for p in layer}
PACKAGES = sorted(LEVEL)


def _targets(node, modparts, is_pkg):
    """First-level sub-package names an import node reaches."""
    if isinstance(node, ast.Import):
        return [a.name.split(".")[1] for a in node.names
                if a.name.startswith(PKG + ".")]
    if not isinstance(node, ast.ImportFrom):
        return []
    mod = node.module.split(".") if node.module else []
    if node.level:
        here = modparts if is_pkg else modparts[:-1]
        full = here[:len(here) - (node.level - 1)] + mod
    elif mod[:1] == [PKG]:
        full = mod[1:]
    else:
        return []
    if full:
        return [full[0]]
    return [a.name for a in node.names]  # from .. import obs, ops


def edges_of(root):
    """{(importer, imported): {module, ...}} over every module under
    ``root``, sibling sub-packages only."""
    pkgs = {d for d in os.listdir(root)
            if os.path.isfile(os.path.join(root, d, "__init__.py"))}
    out = {}
    for pk in sorted(pkgs):
        for dirpath, _, files in os.walk(os.path.join(root, pk)):
            for f in files:
                if not f.endswith(".py"):
                    continue
                path = os.path.join(dirpath, f)
                rel = os.path.relpath(path, root).replace(os.sep, "/")
                parts = rel[:-3].split("/")
                is_pkg = parts[-1] == "__init__"
                if is_pkg:
                    parts = parts[:-1]
                with open(path) as fh:
                    tree = ast.parse(fh.read(), path)
                for node in ast.walk(tree):
                    for t in _targets(node, parts, is_pkg):
                        if t in pkgs and t != pk:
                            out.setdefault((pk, t), set()).add(rel)
    return out


def violations(edges, pkg):
    """What ``pkg``'s imports hold against the table, as messages."""
    bad = []
    for (a, b), mods in sorted(edges.items()):
        if a != pkg or LEVEL[b] < LEVEL[a]:
            continue
        extra = mods - KNOWN_UPWARD.get((a, b), set())
        if extra:
            bad.append(f"{a} -> {b} (layer {LEVEL[a]} -> {LEVEL[b]}) "
                       f"is new in {sorted(extra)}")
    for (a, b), mods in sorted(KNOWN_UPWARD.items()):
        if a != pkg:
            continue
        gone = mods - edges.get((a, b), set())
        if LEVEL[b] < LEVEL[a]:
            bad.append(f"KNOWN_UPWARD holds {a} -> {b}, which points "
                       "down: drop the entry")
        elif gone:
            bad.append(f"{a} -> {b} is no longer made by {sorted(gone)}:"
                       " drop them from KNOWN_UPWARD")
    return bad


@pytest.fixture(scope="module")
def edges():
    return edges_of(os.path.join(REPO, PKG))


def test_table_names_every_sub_package():
    root = os.path.join(REPO, PKG)
    have = sorted(d for d in os.listdir(root)
                  if os.path.isfile(os.path.join(root, d, "__init__.py")))
    assert have == PACKAGES
    assert len(PACKAGES) == sum(len(layer) for layer in LAYERS)


@pytest.mark.parametrize("pkg", PACKAGES)
def test_imports_point_down(edges, pkg):
    bad = violations(edges, pkg)
    assert not bad, "\n".join(bad)


def test_checker_catches_a_planted_upward_import(tmp_path):
    """A function-level ``from ..comm import`` planted in ``utils`` is
    seen, as is an entry of KNOWN_UPWARD nothing makes any more."""
    root = tmp_path / PKG
    for pk in PACKAGES:
        (root / pk).mkdir(parents=True)
        (root / pk / "__init__.py").write_text("")
    (root / "comm" / "group.py").write_text(
        "from ..utils import errors\nimport ompi_release_tpu.mca.var\n")
    assert violations(edges_of(str(root)), "comm") == [
        "comm -> tools is no longer made by ['comm/spawn.py']: drop "
        "them from KNOWN_UPWARD"]
    (root / "utils" / "errors.py").write_text(
        "def f():\n    from ..comm import group\n    return group\n")
    got = violations(edges_of(str(root)), "utils")
    assert any(m.startswith("utils -> comm") and "utils/errors.py" in m
               for m in got), got


def test_readme_draws_this_table():
    """README.md's "Import layers" rows are LAYERS, in order."""
    import re

    with open(os.path.join(REPO, "README.md")) as f:
        rows = re.findall(r"^\| (\d+) \| ((?:`\w+/`(?:, )?)+) \|", f.read(),
                          flags=re.M)
    drawn = tuple(tuple(re.findall(r"`(\w+)/`", cell)) for _, cell in rows)
    assert [int(n) for n, _ in rows] == list(range(len(LAYERS)))
    assert drawn == LAYERS
