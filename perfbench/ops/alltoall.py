"""MPI_Alltoall: rank r ends with block r of every rank's buffer, in rank order."""

KIND = "move"


def elems(n, nbytes, itemsize):
    return -(-max(1, nbytes // itemsize) // n) * n  # n equal blocks


def call(world, x, cfg):
    return world.alltoall(x)


def expected(x, cfg, sums):
    n = x.shape[0]
    blocks = x.reshape(n, n, -1)  # [source, destination, block]
    return (lambda r: blocks[:, r, :].reshape(-1)), None


def least_bytes(n, s):
    # one of a chip's n blocks stays; it reads its buffer and writes as much
    return (n - 1) * s / n, 2 * s
