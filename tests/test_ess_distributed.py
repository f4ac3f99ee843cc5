"""ess/distributed — the jax.distributed multi-controller bootstrap
(``orte/mca/ess/pmi`` analogue): two REAL OS processes form one jax
runtime through the coordination service, mpi.init() selects the
distributed ESS from the OMPITPU_* env contract, and collectives run
through the SPMD driver path (per-process local shards in, one
compiled program across controllers, local shards out).
"""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cpu_multiprocess_gap() -> str:
    """Empty when the installed jax can run cross-controller
    collectives on the CPU backend; otherwise the missing capability,
    named.

    The multi-controller bootstrap itself always works (it is our own
    coordination service), but the compiled collective needs the CPU
    client to be built WITH a cross-process collectives implementation
    (gloo) and jax to expose the config option that selects it —
    without both, XLA raises "Multiprocess computations aren't
    implemented on the CPU backend" at the first collective."""
    import jax
    import jaxlib

    if not hasattr(jax.config, "jax_cpu_collectives_implementation"):
        return ("this jax has no jax_cpu_collectives_implementation "
                "option")
    try:
        from jaxlib import _jax
    except ImportError as e:
        return f"jaxlib {jaxlib.__version__} has no _jax module ({e})"
    if not hasattr(_jax, "make_gloo_tcp_collectives"):
        return (f"jaxlib {jaxlib.__version__} is built without gloo "
                "CPU collectives")
    return ""


_GAP = _cpu_multiprocess_gap()

#: evaluated at collection time: where the installed jaxlib's CPU
#: backend cannot run cross-controller collectives, skip with the
#: capability named instead of failing tier-1
pytestmark = pytest.mark.skipif(
    bool(_GAP), reason=f"ess/distributed needs multiprocess CPU "
                       f"collectives: {_GAP}")

WORKER = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, %r)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    # the capability this test is gated on: cross-controller CPU
    # collectives need an explicit implementation selected
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    import numpy as np
    import ompi_release_tpu as mpi
    from ompi_release_tpu.runtime.runtime import Runtime
    from ompi_release_tpu.runtime import ess as ess_mod

    # the distributed ESS must be the selected component (env contract)
    sel = ess_mod.ESS_FRAMEWORK.select()
    assert sel.NAME == "distributed", sel.NAME

    world = mpi.init()
    rt = Runtime.current()
    pid = jax.process_index()
    assert rt.bootstrap["process_count"] == 2
    assert world.size == 8, world.size  # 2 controllers x 4 devices
    # endpoints carry each device's OWNING controller
    owners = sorted({e.process_index for e in rt.endpoints})
    assert owners == [0, 1], owners

    # SPMD collective path: this controller passes ITS 4 ranks' slices
    my_ranks = [e.rank for e in rt.endpoints if e.process_index == pid]
    x = np.stack([np.arange(8, dtype=np.int32) + r for r in my_ranks])
    out = world.allreduce(x)
    want = sum(np.arange(8, dtype=np.int32) + r for r in range(8))
    out = np.asarray(out)
    assert out.shape == (4, 8), out.shape
    for row in out:
        np.testing.assert_array_equal(row, want)

    # a second op on the same comm reuses the compiled program
    out2 = np.asarray(world.allreduce(2 * x))
    np.testing.assert_array_equal(out2[0], 2 * want)
    print(f"DIST-OK {pid}")
    mpi.finalize()
""" % REPO)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_jax_distributed_bootstrap(tmp_path):
    app = tmp_path / "dist_worker.py"
    app.write_text(WORKER)
    port = _free_port()
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.update({
            "OMPITPU_COORDINATOR": f"127.0.0.1:{port}",
            "OMPITPU_PROCESS_ID": str(pid),
            "OMPITPU_NUM_PROCESSES": "2",
        })
        procs.append(subprocess.Popen(
            [sys.executable, str(app)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        ))
    outs = []
    for pid, p in enumerate(procs):
        try:
            out, err = p.communicate(timeout=150)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        assert p.returncode == 0, f"process {pid}:\n{err[-3000:]}"
        outs.append(out)
    assert "DIST-OK 0" in outs[0]
    assert "DIST-OK 1" in outs[1]
