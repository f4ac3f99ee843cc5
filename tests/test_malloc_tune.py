"""``utils/malloc_tune``: the C allocator keeps freed staging memory once
the wire forms. Each case runs in a process of its own: ``mallopt`` is for
the life of a process and must not leak into the test runner."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, %r)
    from ompi_release_tpu.utils import malloc_tune
    first = malloc_tune.ensure()
    print(json.dumps({"first": first, "again": malloc_tune.ensure(),
                      "env": malloc_tune.tuned_by_environment()}))
""") % ROOT


def probe(**env):
    full = {k: v for k, v in os.environ.items()
            if not (k.startswith("MALLOC_") or k == "GLIBC_TUNABLES")}
    full.update(env)
    out = subprocess.run([sys.executable, "-c", PROBE], env=full, text=True,
                         capture_output=True, timeout=120, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_defaults_keep_a_gibibyte_and_take_32_mib_blocks_from_the_heap():
    got = probe()
    assert got["env"] is False
    assert got["first"] == {"M_MMAP_THRESHOLD": 32 << 20,
                            "M_TOP_PAD": 64 << 20,
                            "M_TRIM_THRESHOLD": 1 << 30}
    assert got["again"] == got["first"]  # once per process


@pytest.mark.parametrize("env", [
    {"MALLOC_TRIM_THRESHOLD_": "1048576"},
    {"MALLOC_MMAP_THRESHOLD_": "65536"},
    {"GLIBC_TUNABLES": "glibc.malloc.mmap_max=4"},
])
def test_the_users_own_tuning_wins(env):
    got = probe(**env)
    assert got["env"] is True
    assert got["first"] == {} and got["again"] == {}


def test_a_tunable_of_another_subsystem_does_not_count():
    assert probe(GLIBC_TUNABLES="glibc.pthread.rseq=0")["first"]


APP = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, %r)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import ompi_release_tpu as mpi
    from ompi_release_tpu.utils import malloc_tune
    world = mpi.init()
    me = world.local_comm_ranks[0]
    with open(os.path.join(sys.argv[1], "rank%%d.json" %% me), "w") as f:
        json.dump(malloc_tune._applied, f)
    mpi.finalize()
""") % ROOT


def test_a_spanning_world_applies_it_and_a_one_process_job_does_not(tmp_path):
    from ompi_release_tpu.tools.tpurun import Job

    app = tmp_path / "app.py"
    app.write_text(APP)
    env = {k: v for k, v in os.environ.items()
           if not (k.startswith("MALLOC_") or k == "GLIBC_TUNABLES")}
    (tmp_path / "one").mkdir()
    subprocess.run([sys.executable, str(app), str(tmp_path / "one")],
                   env=env, check=True, timeout=240)
    assert json.load(open(tmp_path / "one" / "rank0.json")) == {}
    (tmp_path / "two").mkdir()
    job = Job(2, [sys.executable, str(app), str(tmp_path / "two")], [])
    assert job.run(timeout_s=240) == 0
    for r in (0, 1):
        assert json.load(open(tmp_path / "two" / ("rank%d.json" % r))) == {
            "M_MMAP_THRESHOLD": 32 << 20, "M_TOP_PAD": 64 << 20,
            "M_TRIM_THRESHOLD": 1 << 30}
