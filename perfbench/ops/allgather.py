"""MPI_Allgather: every rank ends with all send buffers, in rank order."""

KIND = "move"


def elems(n, nbytes, itemsize):
    return max(1, nbytes // itemsize)


def call(world, x, cfg):
    return world.allgather(x)


def expected(x, cfg, sums):
    flat = x.reshape(-1)
    return (lambda r: flat), None


def least_bytes(n, s):
    # a chip receives n-1 buffers, so some chip sends as many; it reads
    # its own buffer and writes all n
    return (n - 1) * s, (n + 1) * s
