"""What the three OSU pt2pt operations share: the two ranks, the window,
and the jitted programs that make a call's messages and join its arrivals.

Every timed send is of an array the device has JUST produced. jax may keep
the host copy of a device array it has fetched once, so a loop that sent
the benchmark's input buffer again and again would move nothing from the
chip after its first send. ``fresh(x, parts)`` is ONE launch per call: a
copy of the rank's buffer cut into its messages, new buffers every time,
compiled by the first call (in warm-up, before the first exchange).

The operations call nothing of the library but the public API, as the
process's own rank: ``world.send/recv/isend/irecv`` and
``request.wait_all`` (MPI_Waitall: ``Request.wait`` on each).

The deployment is measured WITH the library's p2p counters (the
configuration's ``requires``: ``msgs_per_call`` audits that a window left
as 64 messages, ``unexpected_pct`` that the receives were posted): a
library without them cannot run it, and ``require`` ends the run at the
first call, before anything is sent.
"""

import functools

WINDOW = 64  # osu_bw's and osu_bibw's default window: sends outstanding


def require(cfg):
    """End the run unless the library has every counter the configuration
    names under ``requires``: checked once, at the first (warm-up) call."""
    _require(tuple(cfg["requires"]["pvars"]))


@functools.lru_cache(maxsize=None)
def _require(names):
    import ompi_release_tpu.p2p.pml  # noqa: F401  (registers its counters)
    from ompi_release_tpu.mca import pvar

    absent = [n for n in names if pvar.PVARS.lookup(n) is None]
    if absent:
        raise SystemExit(
            "perfbench: osu_pt2pt needs the library's p2p counters "
            f"{absent}: this library has none, so it cannot run the "
            "configuration (nothing was sent)")


def ranks(world):
    """(this process's rank, its peer): two ranks, one in each process."""
    me = world.local_comm_ranks[0]
    return me, 1 - me


def elems(n, nbytes, itemsize):
    return max(1, nbytes // itemsize)


def window_elems(n, nbytes, itemsize):
    """A call's buffer is WINDOW distinct messages of ``nbytes``: its
    bytes are OSU's bytes per iteration (window x message size)."""
    return WINDOW * elems(n, nbytes, itemsize)


@functools.lru_cache(maxsize=None)
def _programs():
    import jax
    import jax.numpy as jnp

    def cut(x, parts):
        # a copy of each piece: jit hands an output that IS its input back
        # unchanged, and one piece would be the buffer that was sent before
        return tuple(jnp.copy(p) for p in jnp.split(x, parts, axis=1))

    return {"cut": jax.jit(cut, static_argnums=1),
            "head": jax.jit(lambda x: jnp.copy(x[:, :1])),
            "join": jax.jit(lambda *parts: jnp.concatenate(parts, axis=1))}


def fresh(x, parts):
    """The rank's (1, elems) buffer as ``parts`` new device arrays."""
    return _programs()["cut"](x, parts)


def head(x):
    """The buffer's first element, a new (1, 1) device array: osu_bw's
    4-byte acknowledgement."""
    return _programs()["head"](x)


def join(arrivals):
    """The arrivals side by side in the order given, one (1, elems)
    device array: what the harness keeps and compares."""
    return _programs()["join"](*arrivals)


def wait_all(requests):
    from ompi_release_tpu.request import wait_all as mpi_waitall

    return mpi_waitall(requests)
