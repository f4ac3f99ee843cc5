"""README.md names only what is there: repository paths, cvars and
pvars, and ``python -m`` entry points are checked against the tree and
the registries. A finding is repaired in the README."""

import glob
import importlib
import importlib.util
import os
import pkgutil
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = "ompi_release_tpu"

#: prefixes the cvar/pvar registries use; a backticked bare word that
#: starts with one has to be registered
PREFIXES = ("coll_", "wire_", "btl_", "hier_", "obs_", "osc_", "shmem_",
            "progress_", "io_", "ess_", "plan_", "pml_")
#: backticked words with such a prefix that are no cvar or pvar, and
#: what each is; held against the package's source instead
OTHER_NAMES = {
    "coll_ops": "sampler series", "coll_bytes": "sampler series",
    "coll_seconds": "sampler series",
    "hier_rounds": "postmortem field",
    "hier_send": "journal span", "hier_recv": "journal span",
}
#: where a short path of the README may be rooted
ROOTS = ("", PKG, "native")
#: file names the README's examples give to what a user writes or a
#: command leaves behind
USERS_OWN = {"app.py", "prog.py", "train.py", "your_job.py", "hosts",
             "rules.conf", "trace.json", "t.json", "merged.jsonl"}


@pytest.fixture(scope="module")
def readme():
    with open(os.path.join(REPO, "README.md")) as f:
        return f.read()


def _inline(text):
    """Backticked spans outside fenced blocks."""
    prose = re.sub(r"```.*?```", "", text, flags=re.S)
    return re.findall(r"`([^`\n]+)`", prose.replace("\n", " "))


def _exists(path):
    """``a/b.py``, ``a/b.py:12``, ``a/b.py::name``, ``a/`` and the
    short form ``a/b.name`` (module ``a/b.py`` defining ``name``)."""
    path = re.sub(r"(::?[\w.\[\]-]+)+$", "", path)
    for root in ROOTS:
        if glob.glob(os.path.join(REPO, root, path)):
            return True
        mod, _, attr = path.rpartition(".")
        src = os.path.join(REPO, root, mod + ".py")
        if "/" in mod and os.path.isfile(src):
            with open(src) as f:
                if re.search(r"^\s*(def|class)\s+%s\b|^%s\s*[:=]"
                             % (attr, attr), f.read(), flags=re.M):
                    return True
    return False


def test_backticked_repository_paths_exist(readme):
    tops = set(os.listdir(REPO)) | set(os.listdir(os.path.join(REPO, PKG)))
    tokens = set(_inline(readme))
    for block in re.findall(r"```.*?```", readme, flags=re.S):
        tokens.update(re.findall(
            r"[\w./-]+/[\w.-]+\.(?:py|json|jsonl|md|conf|cc|h)\b", block))
    missing = []
    for tok in sorted(tokens):
        if not re.fullmatch(r"[\w.*/-]+(::?[\w.\[\]-]+)*", tok):
            continue  # prose, placeholders, absolute and shell paths
        first = tok.split("/")[0]
        if "/" in tok:
            ours = first in tops and not tok.startswith("/")
        else:
            ours = (re.search(r"\.(py|md|json|jsonl|cc|h|conf)$", tok)
                    and tok not in USERS_OWN and "*" not in tok)
        if ours and not _exists(tok):
            missing.append(tok)
    assert not missing, missing


@pytest.fixture(scope="module")
def registered():
    """Every cvar and pvar name: all modules imported, the runtime
    initialised (components register theirs when a framework opens)."""
    import jax.numpy as jnp

    import ompi_release_tpu as mpi
    from ompi_release_tpu.mca import pvar, var

    for m in pkgutil.walk_packages(mpi.__path__, mpi.__name__ + "."):
        if not m.name.endswith("__main__"):
            importlib.import_module(m.name)
    world = mpi.init()
    # per-operation and per-transport pvars register at first use
    mpi.obs.enable()
    try:
        world.allreduce(jnp.ones((world.size, 4), jnp.float32))
        world.send(jnp.ones(4), 1, tag=3, rank=0)
        world.recv(source=0, tag=3, rank=1)
    finally:
        mpi.obs.disable()
    return set(var.VARS.names()) | set(pvar.PVARS.read_all())


def test_named_cvars_and_pvars_are_registered(readme, registered):
    names = set(re.findall(r"--mca\s+([a-z][\w]*)", readme))
    names.update(re.findall(r"OMPITPU_MCA_([a-z]\w*)", readme))
    for tok in _inline(readme):
        if tok.startswith(PREFIXES) and \
                re.fullmatch(r"[a-z][\w<>*{},]*", tok):
            names.add(tok)
    unknown = []
    for name in sorted(names - set(OTHER_NAMES)):
        # `coll_<op>_latency`, `wire_native_stall_*`: a family
        pat = re.sub(r"<\w+>|\*|\{[\w,]+\}", r"\\w+", name)
        if not any(re.fullmatch(pat, r) for r in registered):
            unknown.append(name)
    assert not unknown, unknown
    src = "".join(open(p).read() for p in glob.glob(
        os.path.join(REPO, PKG, "**", "*.py"), recursive=True))
    stale = [n for n in OTHER_NAMES if not re.search(r"\b%s\b" % n, src)]
    assert not stale, stale


def test_python_dash_m_modules_import(readme):
    mods = set(re.findall(r"python3?\s+-m\s+(%s[\w.]*)" % PKG,
                          readme.replace("\n", " ")))
    assert mods
    for mod in sorted(mods):
        m = importlib.import_module(mod)
        if hasattr(m, "__path__"):  # a package runs its __main__
            assert importlib.util.find_spec(mod + ".__main__"), mod
