"""Unit tests for the MCA variable system (mca/var.py).

Mirrors the reference's precedence contract: override > env > file >
default (``opal/mca/base/mca_base_var.c``).
"""

import os

import pytest

from ompi_release_tpu.mca import var as mca_var
from ompi_release_tpu.mca.var import ENV_PREFIX, VarScope, VarSource, parse_size


def test_register_and_default(fresh_mca):
    v = fresh_mca.register("btl_tpu_eager_limit", "size", "64K",
                           "eager/rendezvous switch point")
    assert v.value == 64 * 1024
    assert v.source is VarSource.DEFAULT
    assert fresh_mca.get("btl_tpu_eager_limit") == 65536


def test_types(fresh_mca):
    assert fresh_mca.register("a_int", "int", "42").value == 42
    assert fresh_mca.register("a_float", "float", "2.5").value == 2.5
    assert fresh_mca.register("a_bool", "bool", "yes").value is True
    assert fresh_mca.register("a_str", "str", 7).value == "7"
    assert fresh_mca.register("a_list", "list", "tcp, self").value == ["tcp", "self"]
    v = fresh_mca.register("a_enum", "enum", "ring",
                           choices=["ring", "recursive_doubling"])
    assert v.value == "ring"
    with pytest.raises(ValueError):
        fresh_mca.register("bad_enum", "enum", "nope", choices=["a", "b"])


def test_parse_size():
    assert parse_size("8") == 8
    assert parse_size("4k") == 4096
    assert parse_size("64K") == 65536
    assert parse_size("1M") == 1 << 20
    assert parse_size("2GB") == 2 << 30
    with pytest.raises(ValueError):
        parse_size("lots")


def test_env_precedence(fresh_mca, monkeypatch):
    monkeypatch.setenv(ENV_PREFIX + "coll_tuned_algorithm", "ring")
    v = fresh_mca.register("coll_tuned_algorithm", "str", "auto")
    assert v.value == "ring"
    assert v.source is VarSource.ENV


def test_file_and_override_precedence(fresh_mca, monkeypatch, tmp_path):
    p = tmp_path / "params.conf"
    p.write_text("# comment\nfoo_bar = 10\nbaz = hello # trailing\n")
    assert fresh_mca.load_param_file(str(p)) == 2
    v = fresh_mca.register("foo_bar", "int", 1)
    assert v.value == 10 and v.source is VarSource.FILE

    # env beats file
    monkeypatch.setenv(ENV_PREFIX + "foo_bar", "20")
    fresh_mca.refresh_from_env()
    assert v.value == 20 and v.source is VarSource.ENV

    # override beats env
    fresh_mca.set_value("foo_bar", 30)
    assert v.value == 30 and v.source is VarSource.OVERRIDE

    fresh_mca.unset("foo_bar")
    assert v.value == 20 and v.source is VarSource.ENV


def test_cli_pairs(fresh_mca):
    v = fresh_mca.register("pml_tpu_pipeline_depth", "int", 2)
    fresh_mca.apply_cli([("pml_tpu_pipeline_depth", "8")])
    assert v.value == 8 and v.source is VarSource.OVERRIDE


def test_readonly_scope(fresh_mca):
    fresh_mca.register("const_thing", "int", 5, scope=VarScope.READONLY)
    with pytest.raises(PermissionError):
        fresh_mca.set_value("const_thing", 6)


def test_reregistration_idempotent(fresh_mca):
    a = fresh_mca.register("dup", "int", 1)
    b = fresh_mca.register("dup", "int", 99)
    assert a is b and b.value == 1
    with pytest.raises(ValueError):
        fresh_mca.register("dup", "str", "x")


def test_synonyms(fresh_mca, monkeypatch):
    monkeypatch.setenv(ENV_PREFIX + "old_name", "7")
    v = fresh_mca.register("new_name", "int", 0, synonyms=["old_name"])
    assert v.value == 7


def test_describe_all(fresh_mca):
    fresh_mca.register("zz", "int", 1, "help text")
    descs = fresh_mca.describe_all()
    assert any(d["name"] == "zz" and d["help"] == "help text" for d in descs)


def test_readonly_not_leaked_via_refresh(fresh_mca):
    """A rejected set_value must not apply on a later resolve."""
    v = fresh_mca.register("ro_var", "int", 5, scope=VarScope.READONLY)
    with pytest.raises(PermissionError):
        fresh_mca.set_value("ro_var", 6)
    fresh_mca.refresh_from_env()
    assert v.value == 5


def test_invalid_env_does_not_half_register(fresh_mca, monkeypatch):
    monkeypatch.setenv(ENV_PREFIX + "half_reg", "garbage")
    with pytest.raises(ValueError):
        fresh_mca.register("half_reg", "int", 5)
    assert fresh_mca.lookup("half_reg") is None
    monkeypatch.delenv(ENV_PREFIX + "half_reg")
    assert fresh_mca.register("half_reg", "int", 5).value == 5


def test_apply_cli_skips_readonly(fresh_mca):
    v = fresh_mca.register("ro2", "int", 5, scope=VarScope.READONLY)
    w = fresh_mca.register("rw2", "int", 1)
    fresh_mca.apply_cli([("ro2", "9"), ("rw2", "2")])
    assert v.value == 5 and w.value == 2


def test_readonly_launch_time_override_applies(fresh_mca):
    """CLI/env overrides recorded BEFORE registration are launch-time
    config and legitimately set READONLY vars (reference semantics);
    only post-registration writes are rejected."""
    fresh_mca.apply_cli([("early_ro", "9")])
    v = fresh_mca.register("early_ro", "int", 5, scope=VarScope.READONLY)
    assert v.value == 9
    with pytest.raises(PermissionError):
        fresh_mca.set_value("early_ro", 10)


def test_rejected_set_value_does_not_poison_registry(fresh_mca):
    """A set_value rejected by enum validation must roll back: the
    stored bad override would otherwise make every later get() raise
    (observed as cross-test contamination before the fix)."""
    import pytest

    from ompi_release_tpu.mca import var as mca_var

    mca_var.register("poison_probe", "enum", "a",
                     "rollback probe", choices=("a", "b"))
    mca_var.set_value("poison_probe", "b")
    with pytest.raises(ValueError, match="not in enum"):
        mca_var.set_value("poison_probe", "zz")
    # prior override survives the rejected set
    assert mca_var.get("poison_probe") == "b"
    mca_var.VARS.unset("poison_probe")
    with pytest.raises(ValueError):
        mca_var.set_value("poison_probe", "zz")
    assert mca_var.get("poison_probe") == "a"  # default restored
    # TypeError path (int([1,2])) must roll back too
    mca_var.register("poison_int", "int", 5, "rollback probe 2")
    with pytest.raises((TypeError, ValueError)):
        mca_var.set_value("poison_int", [1, 2])
    assert mca_var.get("poison_int") == 5


_CENSUS = r"""
import importlib, json, pkgutil, sys
sys.path.insert(0, %(repo)r)
import ompi_release_tpu as mpi
from ompi_release_tpu.mca import pvar, var
for m in pkgutil.walk_packages(mpi.__path__, mpi.__name__ + "."):
    if not m.name.endswith("__main__"):
        importlib.import_module(m.name)
mpi.init()  # the frameworks' components register theirs when opened
cvars = var.VARS.describe_all()
print("CENSUS " + json.dumps({
    "cvars": len(cvars),
    "on_off": sorted(d["name"] for d in cvars if d["type"] == "bool"),
    "pvars": len(pvar.PVARS.read_all())}))
mpi.finalize()
"""


def test_census_of_options_and_counters():
    """What a user can set, counted: every module of the package
    imported and the runtime initialised, in a process of its own (a
    worker that ran other tests has registered theirs). A PR that adds
    or removes a cvar, an on/off cvar or a pvar changes a literal here,
    so the count ROADMAP asks of every PR is a line of its diff."""
    import json
    import subprocess
    import sys

    from conftest import subprocess_env

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = subprocess_env()
    for k in [k for k in env if k.startswith(ENV_PREFIX)]:
        del env[k]
    r = subprocess.run([sys.executable, "-c", _CENSUS % {"repo": repo}],
                       env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    got = json.loads(r.stdout.split("CENSUS ", 1)[1].splitlines()[0])
    assert got["cvars"] == 104
    assert len(got["on_off"]) == 17, got["on_off"]
    # the five osc_home_*_seconds came last but two; the last two are
    # the device-form alltoallv's (vcoll_alltoallv_device_calls,
    # vcoll_alltoallv_host_bytes); hier_reduce_scatter_halved counts the
    # reduce_scatter_block calls that ran Rabenseifner's halving alone
    assert got["pvars"] == 126
