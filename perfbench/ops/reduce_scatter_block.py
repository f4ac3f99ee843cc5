"""MPI_Reduce_scatter_block, SUM: rank r ends with block r of the sum."""

KIND = "reduce"


def elems(n, nbytes, itemsize):
    return -(-max(1, nbytes // itemsize) // n) * n  # n equal blocks


def call(world, x, cfg):
    from ompi_release_tpu import ops

    return world.reduce_scatter_block(x, getattr(ops, cfg["reduce_op"]))


def expected(x, cfg, sums):
    n = x.shape[0]
    total, mags = (a.reshape(n, -1) for a in sums(x))
    return (lambda r: total[r]), (lambda r: mags[r])


def least_bytes(n, s):
    # n-1 of a chip's n blocks are reduced elsewhere; it reads its
    # buffer and writes one block
    return (n - 1) * s / n, s + s / n
