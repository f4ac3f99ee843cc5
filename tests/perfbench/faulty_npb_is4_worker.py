"""The benchmark's worker with the device-form alltoallv broken underneath
``npb_is4``; the harness is untouched. PERFBENCH_FAULT names the fault:

    dropped       the exchange loses one key: rank 1 is told it received
                  one key less from rank 0 than was sent
    swapped       ranks 0 and 1 get each other's receive rows
    chip0         from the window on, every receive buffer lands on the
                  first chip, as the list form used to leave them (the
                  ranking's program then refuses them: a call that raises
                  in set-up would end the run without a line)
    truncated     a receive buffer half the size asked for, cut without a
                  word: the counts claim only what it holds
    no_counters   the library has no device-form alltoallv counters, as
                  the commit before it: the configuration's ``requires``
                  ends the run; a collective made all the same leaves a
                  file behind (PERFBENCH_COLLECTIVE_MADE)
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    from perfbench import worker

    fault = os.environ["PERFBENCH_FAULT"]
    import jax
    import jax.numpy as jnp

    from ompi_release_tpu.coll import vcoll
    from ompi_release_tpu.comm.communicator import Communicator
    from ompi_release_tpu.mca import pvar

    sound, window, timed = vcoll.alltoallv_device, worker.Bench.window, []

    def timed_window(self, *a, **kw):
        timed.append(True)
        return window(self, *a, **kw)

    worker.Bench.window = timed_window

    def broken(comm, x, sendcounts, recv_capacity):
        if fault == "truncated":
            half = recv_capacity // 2
            recv, counts = sound(comm, x, sendcounts, half)
            recv = jnp.pad(recv, ((0, 0), (0, recv_capacity - half)))
            land = jnp.cumsum(counts, axis=1) - counts
            return recv, jnp.clip(half - land, 0, counts)
        recv, counts = sound(comm, x, sendcounts, recv_capacity)
        if fault == "dropped":
            return recv, counts.at[1, 0].add(-1)
        if fault == "swapped":
            return recv[jnp.array([1, 0] + list(range(2, comm.size)))], counts
        if fault == "chip0":
            if not timed:
                return recv, counts
            return jax.device_put(recv, jax.devices()[0]), counts
        raise SystemExit(f"unknown fault {fault!r}")

    if fault == "no_counters":
        lookup = pvar.PVARS.lookup
        pvar.PVARS.lookup = lambda name: (
            None if name.startswith("vcoll_alltoallv_") else lookup(name))
        for op in ("allreduce", "alltoall", "alltoallv"):
            def made(self, *a, _sound=getattr(Communicator, op), **kw):
                open(os.environ["PERFBENCH_COLLECTIVE_MADE"], "w").close()
                return _sound(self, *a, **kw)

            setattr(Communicator, op, made)
    else:
        vcoll.alltoallv_device = broken
    return worker.main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
