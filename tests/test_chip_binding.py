"""One chip per ``tpurun`` rank, and one compile cache per checkout.

Both are decided without a chip in sight, so both are testable here:

  - the launcher exports a per-slot chip binding (distinct per local
    slot of a host), never initialises a jax backend itself, and a
    worker that declares itself a host rank (``JAX_PLATFORMS=cpu``)
    behaves as it always did; a bound slot with no chip behind it
    fails by name instead of landing on the CPU;
  - the compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says,
    and code then sets nothing; unset, at one fixed in-checkout path
    that every process agrees on.
"""

import os
import subprocess
import sys
import textwrap

import pytest

from conftest import subprocess_env
from ompi_release_tpu.tools.tpurun import HostSpec, Job, chip_binding_env
from ompi_release_tpu.utils import compile_cache
from ompi_release_tpu.utils.errors import MPIError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _job(n, **kw):
    job = Job(n, ["true"], [], **kw)
    job.hnp = type("H", (), {"port": 1})()
    return job


class TestLauncherBinding:
    def test_one_chip_topology_per_slot(self):
        env = chip_binding_env(2)
        assert env["TPU_VISIBLE_CHIPS"] == "2"
        assert env["OMPITPU_LOCAL_SLOT"] == "2"
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"

    def test_inherited_confinement_is_kept(self, monkeypatch):
        """A launcher that was itself given some of the host's chips
        (a user's or scheduler's TPU_VISIBLE_CHIPS) hands slot k the
        k-th of THOSE, never a chip outside the list; a slot past the
        list gets an index no host has, so it fails at init by name.
        Another host's chips are not the launcher's to renumber."""
        monkeypatch.setenv("TPU_VISIBLE_CHIPS", "4, 5,6,7")
        job = _job(5)
        chips = [job._ompitpu_env(n)["TPU_VISIBLE_CHIPS"]
                 for n in (1, 2, 3, 4, 5)]
        assert chips[:4] == ["4", "5", "6", "7"]
        assert chips[4] not in ("0", "1", "2", "3", "4", "5", "6", "7")
        assert job._ompitpu_env(5)["OMPITPU_LOCAL_SLOT"] == "4"
        job = _job(2, hosts=[HostSpec("nodeB", 2)])
        assert [job._ompitpu_env(n)["TPU_VISIBLE_CHIPS"]
                for n in (1, 2)] == ["0", "1"]

    def test_slots_are_distinct_per_host(self):
        chips = [_job(4)._ompitpu_env(n)["TPU_VISIBLE_CHIPS"]
                 for n in (1, 2, 3, 4)]
        assert chips == ["0", "1", "2", "3"]
        # slots count per HOST: two hosts each start again at chip 0
        job = _job(4, hosts=[HostSpec("nodeA", 2), HostSpec("nodeB", 2)])
        assert [(job.rank_hosts[i].name,
                 job._ompitpu_env(i + 1)["TPU_VISIBLE_CHIPS"])
                for i in range(4)] == [("nodeA", "0"), ("nodeA", "1"),
                                       ("nodeB", "0"), ("nodeB", "1")]
        job = _job(4, hosts=[HostSpec("nodeA", 2), HostSpec("nodeB", 2)],
                   map_by="node")
        assert [job._ompitpu_env(n)["TPU_VISIBLE_CHIPS"]
                for n in (1, 2, 3, 4)] == ["0", "0", "1", "1"]

    def test_moved_rank_takes_a_free_chip_of_its_new_host(self):
        job = _job(3, hosts=[HostSpec("nodeA", 2), HostSpec("nodeB", 2)])
        assert job.rank_slots == [0, 1, 0]  # A0 A1 B0
        job._excluded_hosts.add("nodeA")
        job._remap_rank(2)  # world rank 1 leaves nodeA
        assert job.rank_hosts[1].name == "nodeB"
        assert job._ompitpu_env(2)["TPU_VISIBLE_CHIPS"] == "1"
        assert job._ompitpu_env(3)["TPU_VISIBLE_CHIPS"] == "0"

    def test_launcher_stays_off_the_backend_and_host_ranks_run(
            self, tmp_path):
        """A real 2-rank job launched with a platform jax cannot
        initialise: a launcher that touched a backend would die on it.
        The workers declare themselves host ranks before importing
        jax, see distinct slots, and come up exactly as before."""
        app = tmp_path / "app.py"
        app.write_text(textwrap.dedent("""
            import os, sys
            sys.path.insert(0, %r)
            os.environ["JAX_PLATFORMS"] = "cpu"  # declared host rank
            import jax
            import ompi_release_tpu as mpi
            world = mpi.init()
            print("BOUND", os.environ["OMPITPU_NODE_ID"],
                  os.environ["TPU_VISIBLE_CHIPS"],
                  jax.devices()[0].platform, world.size, flush=True)
            mpi.finalize()
        """ % REPO))
        r = subprocess.run(
            [sys.executable, "-m", "ompi_release_tpu.tools.tpurun",
             "-n", "2", sys.executable, str(app)],
            cwd=REPO, env=subprocess_env(JAX_PLATFORMS="no_such_platform",
                                         XLA_FLAGS=""),
            capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "BOUND 1 0 cpu 2" in r.stdout
        assert "BOUND 2 1 cpu 2" in r.stdout


class TestWorkerHoldsTheBinding:
    @pytest.fixture
    def slot_env(self, monkeypatch):
        monkeypatch.setenv("OMPITPU_LOCAL_SLOT", "3")
        monkeypatch.setenv("OMPITPU_NODE_ID", "4")
        monkeypatch.setenv("OMPITPU_HOST", "nodeA")

    def test_declared_host_rank_is_untouched(self, slot_env):
        from ompi_release_tpu.runtime.ess import _bound_platform

        assert _bound_platform() == "cpu"  # conftest pins cpu

    def test_slot_without_a_chip_fails_by_name(self, slot_env):
        """JAX_PLATFORMS unset is how jax silently falls back to the
        CPU when no chip answers — the bound rank must not follow."""
        import jax

        from ompi_release_tpu.runtime.ess import _bound_platform

        jax.config.update("jax_platforms", "")
        try:
            with pytest.raises(MPIError) as e:
                _bound_platform()
        finally:
            jax.config.update("jax_platforms", "cpu")
        assert "local slot 3 on host nodeA" in str(e.value)
        assert "rank 3" in str(e.value)
        assert "no TPU chip" in str(e.value)


class TestCompileCachePlacement:
    def test_env_set_code_sets_nothing(self, tmp_path, monkeypatch):
        import jax

        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))

        def refuse(*a, **k):
            raise AssertionError("code set a compile-cache option "
                                 "although the env var names the place")

        monkeypatch.setattr(jax.config, "update", refuse)
        assert compile_cache.ensure() == str(tmp_path)

    def test_unset_is_one_fixed_path_in_the_checkout(self):
        """This process and a fresh one that goes through mpi.init()
        agree on <checkout>/.jax_cache."""
        assert compile_cache.DEFAULT_DIR == os.path.join(REPO, ".jax_cache")
        env = subprocess_env()
        env.pop(compile_cache.ENV_VAR, None)
        r = subprocess.run(
            [sys.executable, "-c", textwrap.dedent("""
                import jax
                import ompi_release_tpu as mpi
                mpi.init()
                print("CACHE", jax.config.jax_compilation_cache_dir)
            """)], cwd=REPO, env=env, capture_output=True, text=True,
            timeout=120)
        assert r.returncode == 0, r.stderr
        assert f"CACHE {compile_cache.DEFAULT_DIR}" in r.stdout
        if not os.environ.get(compile_cache.ENV_VAR):
            assert compile_cache.ensure() == compile_cache.DEFAULT_DIR
