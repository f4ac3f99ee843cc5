"""ring_oshmem_c.c analogue: a token circles the PEs via one-sided
puts + wait_until instead of send/recv.

Each PE waits until its symmetric flag holds the lap count its left
neighbour put there, then decrements (PE 0) and puts onward — the
put/wait_until pattern of ``examples/ring_oshmem_c.c``.

A flag is one word of the symmetric heap, so every operation names its
address: ``index=0`` for the AMOs (``shmem_int_swap(&flag[0], ...)``),
``offset=0`` for the put (``shmem_putmem(&flag[0], ...)``).

Run:  python examples/ring_oshmem_tpu.py   (driver mode, virtual PEs:
one controller plays every PE in turn, so ``pe=`` is explicit and
``ctx.my_pe`` is None; under ``tpurun`` each process is one PE,
``ctx.my_pe`` names it and ``wait_until`` defaults to it)
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import ompi_release_tpu as mpi
from ompi_release_tpu.oshmem import shmem


def main() -> int:
    mpi.init()
    ctx = shmem.shmem_init()
    n = ctx.n_pes
    laps = 3
    # symmetric flag per PE: -1 = empty, >=0 = token with value
    flag = ctx.malloc((1,), np.int32)
    ctx.barrier_all()
    for pe in range(n):
        ctx.put(flag, np.full(1, -1, np.int32), pe=pe)
    ctx.quiet()

    passes = 0
    ctx.atomic_set(flag, laps, 0, index=0)  # seed at PE 0
    token = laps
    pe = 0
    while True:
        ctx.wait_until(flag, "ge", 0, pe=pe)
        # take the token and leave the flag empty, in one AMO
        token = int(ctx.atomic_swap(flag, -1, pe, index=0))
        passes += 1
        if pe == 0 and passes > 1:
            token -= 1
            print(f"PE 0: {token} laps to go")
        if token == 0 and pe == n - 1:
            break
        ctx.put(flag, np.int32([token]), (pe + 1) % n, offset=0)
        ctx.quiet()
        pe = (pe + 1) % n
    ctx.barrier_all()
    flag.free()
    shmem.shmem_finalize()
    mpi.finalize()
    print(f"ring_oshmem complete: {passes} passes over {n} PEs")
    return 0


if __name__ == "__main__":
    sys.exit(main())
