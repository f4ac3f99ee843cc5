"""MPI_Bcast from the configuration's root: every rank ends with the root's buffer."""

KIND = "move"


def elems(n, nbytes, itemsize):
    return max(1, nbytes // itemsize)


def call(world, x, cfg):
    return world.bcast(x, root=cfg["bcast_root"])


def expected(x, cfg, sums):
    root = x[cfg["bcast_root"]]
    return (lambda r: root), None


def least_bytes(n, s):
    # the root reads s to send it; every other rank writes s
    return s, s
