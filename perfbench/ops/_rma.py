"""What the two OSU one-sided operations share: the two ranks, the window
of 64 operations per synchronisation, and one library window per
(operation, size), made and locked once and kept, as OSU allocates its
window per size outside its loop.

The operations call nothing of the library but the public API, as the
process's own rank: ``osc.win_allocate`` / ``win_create``,
``win.lock(1, LOCK_SHARED)``, ``win.put`` / ``win.get`` with ``disp=``,
``win.flush(1)``, ``win.read()`` and, for the per-call notice,
``world.send`` / ``world.recv``.

The deployment is measured WITH the library's RMA wire counters (the
configuration's ``requires``: ``wire_mb`` audits that a get ships blocks,
not slots): a library without them has no ranged RMA and cannot run it,
and ``require`` ends the run at the first call, on every rank, before any
window is made — so nothing is left hanging in a collective.
"""

import functools

from perfbench.ops import _pt2pt

WINDOW = 64  # osu_put_bw's and osu_get_bw's operations per synchronisation
TAG = 200    # the per-call notice
TARGET = 1   # OSU's target rank: the host rank of the 'D H' placement

ranks = _pt2pt.ranks
elems = _pt2pt.window_elems
fresh = _pt2pt.fresh
head = _pt2pt.head


def require(cfg):
    """End the run unless the library has every counter the configuration
    names under ``requires``: checked once, at the first (warm-up) call."""
    _require(tuple(cfg["requires"]["pvars"]))


@functools.lru_cache(maxsize=None)
def _require(names):
    from ompi_release_tpu.mca import pvar
    from ompi_release_tpu.osc import wire_win  # noqa: F401  (its counters)

    absent = [n for n in names if pvar.PVARS.lookup(n) is None]
    if absent:
        raise SystemExit(
            "perfbench: osu_rma needs the library's RMA wire counters "
            f"{absent}: this library has none (no RMA on a block at a "
            "displacement), so it cannot run the configuration (no window "
            "was made)")


_windows = {}


def window(world, key, make, over=None):
    """The window of one (operation, size) and the number of this call on
    it, counted from 0. Made by every rank in its first call (creation is
    collective), then rank 0 takes a shared lock on the target and keeps
    it: every later call is puts or gets and a flush inside that one
    passive epoch, as in OSU's ``-s flush`` loop. Nothing unlocks or frees
    it: the harness makes no call after its window in which both ranks
    could (``free`` is collective), so the epoch and the window last until
    ``mpi.finalize()`` stops the window service with the runtime.
    ``over``: the buffer a window was created over; when the harness
    draws its data anew (``--extra-seeds``) the window is made anew."""
    from ompi_release_tpu.osc import LOCK_SHARED

    entry = _windows.get(key)
    if entry is None or entry[2] is not over:
        win = make()
        if ranks(world)[0] != TARGET:
            win.lock(TARGET, LOCK_SHARED)
        entry = _windows[key] = [win, -1, over]
    entry[1] += 1
    return entry[:2]


def notify(world, me, peer, note=None):
    """OSU has one barrier per size; the harness needs every call's
    result on every rank: the origin sends a 4-byte notice once its flush
    has returned, the target waits for it before it reads."""
    if note is not None:
        world.send(note, peer, TAG, rank=me)
    else:
        world.recv(peer, TAG, rank=me)


@functools.lru_cache(maxsize=None)
def _programs():
    import jax
    import jax.numpy as jnp

    def join(*blocks):
        return jnp.concatenate(blocks)[None, :]

    def unswap(slot):
        # neighbouring blocks back in each other's place (odd calls)
        return slot.reshape(1, WINDOW // 2, 2, -1)[:, :, ::-1].reshape(1, -1)

    return {"join": jax.jit(join), "unswap": jax.jit(unswap)}


def join(values):
    """The read values (1-D blocks) side by side in the order given, one
    (1, elems) array on the rank's device."""
    return _programs()["join"](*values)


def unswap(slot):
    return _programs()["unswap"](slot)
