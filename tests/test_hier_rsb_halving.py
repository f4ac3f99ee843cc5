"""reduce_scatter_block on a spanning comm as Rabenseifner's
recursive-halving half alone: real ``tpurun`` jobs.

Where the allreduce pick is ``rabenseifner`` (a power of two of
processes, a commutative op with an identity, ``hier_small_message``
bytes or more), no leader tier, and each process owns an equal run of
consecutive ranks, process ``j``'s chunk of the flat partial after the
halving rounds is exactly its ranks' blocks of the sum. The call then
stops there and places only that chunk. What is held here, at 2 and 4
processes of one and two ranks: the result is numpy's, and bit for bit
the block of the (unchanged) Rabenseifner allreduce of the same data,
which is what the whole-allreduce path returned; per call one
``hier_reduce_scatter_halved``, (P-1)/P of the partial's bytes sent
and log2 P schedule rounds, on the recorded call and the native fires
alike. Every other case keeps the whole combine and ticks nothing:
the ring at three processes, recursive doubling below
``hier_small_message``, a pair op, ranks interleaved across processes.
"""

import math
import os
import sys
import textwrap

import pytest

from ompi_release_tpu.tools.tpurun import Job

from test_native_exec import needs_native

pytestmark = needs_native

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: ``%(rpp)d`` CPU devices a process: its ranks
PRELUDE = """
import os, sys
sys.path.insert(0, %(repo)r)
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=%(rpp)d"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import ompi_release_tpu as mpi
from ompi_release_tpu.mca import pvar, var as mca_var
from ompi_release_tpu.runtime.runtime import Runtime

def _pv(name):
    p = pvar.PVARS.lookup(name)
    return float(p.read()) if p is not None else 0.0

world = mpi.init()
rt = Runtime.current()
me = rt.bootstrap["process_index"]
off = rt.local_rank_offset
n = world.size
mca_var.set_value("hier_inter_algorithm", "auto")
COUNTERS = ("hier_reduce_scatter_halved", "hier_inter_bytes",
            "hier_schedule_rounds", "plan_native_fires")

def call(comm, x, op):
    # counters of the call alone; the barrier keeps every process on
    # the same call
    c0 = [_pv(c) for c in COUNTERS]
    out = np.asarray(comm.reduce_scatter_block(x, op))
    d = [_pv(c) - b for c, b in zip(COUNTERS, c0)]
    comm.barrier()
    return out, d

def exact_rows(per, salt):  # small whole numbers: every sum is exact
    return np.stack([((np.arange(per) * (r + 3) + salt) %% 509 - 254.0)
                     .astype(np.float32) for r in range(n)])

def random_rows(per, salt):  # sums whose bits depend on the fold order
    return np.random.default_rng(salt).standard_normal(
        (n, per)).astype(np.float32)
"""

#: elements of one rank's buffer: 64 KiB and 1 MiB of float32 and a
#: little, divisible by every rank count used here
SIZES = {"64k": 16392, "1m": 262152}


def _job(tmp_path, capfd, body, procs, rpp, timeout=120):
    app = tmp_path / "app.py"
    app.write_text(PRELUDE % {"repo": REPO, "rpp": rpp}
                   + textwrap.dedent(body))
    job = Job(procs, [sys.executable, str(app)], [],
              heartbeat_s=0.5, miss_limit=8)
    rc = job.run(timeout_s=timeout)
    out = capfd.readouterr()
    return rc, out.out + out.err


@pytest.mark.parametrize("op", ["sum", "max"])
@pytest.mark.parametrize("size", ["64k", "1m"])
@pytest.mark.parametrize("rpp", [1, 2])
@pytest.mark.parametrize("procs", [2, 4])
def test_halving_alone_bitwise_and_counted(tmp_path, capfd, procs, rpp,
                                           size, op):
    """The recorded call, the one that lowers the plan, then eight
    native fires on exact and on random data: numpy's result, the
    allreduce's bits, and the counters of the halving rounds alone."""
    per = SIZES[size]
    rounds = int(math.log2(procs))
    sent = (procs - 1) * per * 4 // procs
    rc, out = _job(tmp_path, capfd, """
    PER, P, RPP = %(per)d, %(procs)d, %(rpp)d
    op = {"sum": mpi.ops.SUM, "max": mpi.ops.MAX}[%(op)r]
    red = np.sum if %(op)r == "sum" else np.max
    rows = exact_rows(PER, 1)
    for _ in range(2):
        # the recorded call, then the one that lowers the plan: its
        # probes run the schedule body twice more, wire-free (rounds
        # counted, nothing sent, no call counted)
        got, d = call(world, rows[off:off + RPP], op)
        np.testing.assert_array_equal(
            got, red(rows, axis=0).reshape(n, -1)[off:off + RPP])
        assert d[:2] == [1, %(sent)d], d
    for salt in (0, 7, 0, 7):
        rows = exact_rows(PER, salt)
        got, d = call(world, rows[off:off + RPP], op)
        want = red(rows, axis=0).reshape(n, -1)[off:off + RPP]
        np.testing.assert_array_equal(got, want)
        assert d == [1, %(sent)d, %(rounds)d, 1], d
        rows = random_rows(PER, salt)
        got, d = call(world, rows[off:off + RPP], op)
        assert d == [1, %(sent)d, %(rounds)d, 1], d
        whole = np.asarray(world.allreduce(rows[off:off + RPP], op))[0]
        np.testing.assert_array_equal(  # BITWISE
            got, whole.reshape(n, -1)[off:off + RPP])
        np.testing.assert_allclose(
            got, red(rows, axis=0).reshape(n, -1)[off:off + RPP],
            rtol=1e-5, atol=1e-5)
        world.barrier()
    print(f"HALVED-OK {me}", flush=True)
    mpi.finalize()
    """ % {"per": per, "procs": procs, "rpp": rpp, "op": op,
           "sent": sent, "rounds": rounds}, procs, rpp)
    assert rc == 0, out
    for p in range(procs):
        assert f"HALVED-OK {p}" in out


def _fallback(tmp_path, capfd, procs, rpp, body):
    rc, out = _job(tmp_path, capfd, body + """
    print(f"WHOLE-OK {me}", flush=True)
    mpi.finalize()
    """, procs, rpp)
    assert rc == 0, out
    for p in range(procs):
        assert f"WHOLE-OK {p}" in out


#: the whole combine, checked as above with no halving tick
WHOLE = """
    PER = %(per)d
    for salt in (0, 7, 0):
        rows = exact_rows(PER, salt)
        got, d = call(comm, rows[mine], mpi.ops.SUM)
        np.testing.assert_array_equal(
            got, rows.sum(axis=0).reshape(n, -1)[mine])
        assert d[0] == 0, d
        rows = random_rows(PER, salt)
        got, d = call(comm, rows[mine], mpi.ops.SUM)
        assert d[0] == 0, d
        whole = np.asarray(comm.allreduce(rows[mine], mpi.ops.SUM))[0]
        np.testing.assert_array_equal(  # BITWISE
            got, whole.reshape(n, -1)[mine])
"""


@pytest.mark.parametrize("rpp", [1, 2])
def test_ring_at_three_processes_runs_whole(tmp_path, capfd, rpp):
    """Three processes pick the ring: the whole combine, cut on the
    host."""
    _fallback(tmp_path, capfd, 3, rpp, """
    comm, mine = world, slice(off, off + %d)
    """ % rpp + WHOLE % {"per": 16392 // 6 * 6})


def test_below_small_message_runs_recursive_doubling(tmp_path, capfd):
    """4 KiB a rank at four processes: recursive doubling, which folds
    once in process order."""
    _fallback(tmp_path, capfd, 4, 1, """
    comm, mine = world, slice(off, off + 1)
    """ + WHOLE % {"per": 1024})


def test_pair_op_runs_whole(tmp_path, capfd):
    """MINLOC at four processes, 32 KiB of values and of indexes: a
    pair op has no chunked schedule."""
    _fallback(tmp_path, capfd, 4, 1, """
    PER = 8192
    vals = exact_rows(PER, 3)
    idxs = np.broadcast_to(np.arange(n, dtype=np.int32)[:, None],
                           vals.shape).copy()
    c0 = _pv("hier_reduce_scatter_halved")
    for _ in range(2):
        cv, ci = world.reduce_scatter_block(
            (vals[off:off + 1], idxs[off:off + 1]), mpi.ops.MINLOC)
        k = np.argmin(vals, axis=0)  # the lowest index wins a tie
        np.testing.assert_array_equal(
            np.asarray(cv), vals.min(axis=0).reshape(n, -1)[off:off + 1])
        np.testing.assert_array_equal(
            np.asarray(ci), k.reshape(n, -1)[off:off + 1])
        world.barrier()
    assert _pv("hier_reduce_scatter_halved") == c0
    """)


def test_interleaved_ranks_run_whole(tmp_path, capfd):
    """A split whose ranks alternate between two processes: process
    j's chunk of the partial is not its ranks' blocks, so the call
    runs the whole allreduce and cuts each block out."""
    _fallback(tmp_path, capfd, 2, 2, """
    # world ranks 0, 1 on process 0 and 2, 3 on process 1 become comm
    # ranks 0, 2 and 1, 3
    comm = world.split([0] * 4, [0, 2, 1, 3])[off]
    assert comm.spans_processes
    mine = [0, 2] if off == 0 else [1, 3]
    """ + WHOLE % {"per": 16392})
