"""MPI_Allreduce, SUM: every rank ends with the sum of all send buffers."""

KIND = "reduce"


def elems(n, nbytes, itemsize):
    return max(1, nbytes // itemsize)


def call(world, x, cfg):
    from ompi_release_tpu import ops

    return world.allreduce(x, getattr(ops, cfg["reduce_op"]))


def expected(x, cfg, sums):
    """(row, scale): rank r's expected buffer, and for a reduction the
    sum of magnitudes behind each element, which rounding is measured
    against."""
    total, mags = sums(x)
    return (lambda r: total), (lambda r: mags)


def least_bytes(n, s):
    """(bytes the busiest chip must send over its links, bytes it must
    read and write in its memory) for one call with s bytes per rank."""
    return 2 * (n - 1) * s / n, 2 * s
