"""What the four OSU OpenSHMEM operations share: the two PEs, the 64
posted operations per ``quiet`` and the 16 blocking ones per call, one
``ShmemCtx`` and one symmetric allocation per (operation, size), made in
the first warm-up call and kept (OSU allocates its symmetric buffer once,
outside its loop), the number of the call on it, and how the atomics'
indices and values follow from the seeded data.

The operations call nothing of the library but its public OpenSHMEM API,
as the process's own PE (``ctx.my_pe``): ``shmem_init``, ``malloc``,
``put`` / ``get`` at an ``offset``, the ``atomic_*`` calls on an
``index``, ``quiet``, ``barrier_all``, ``sym.local(my_pe)`` and, for the
per-call notice, ``world.send`` / ``world.recv`` (``_rma.notify``: the
same handshake as ``osu_rma``, so the two deployments differ in the layer
under test).

The deployment is measured WITH the library's OpenSHMEM counters (the
configuration's ``requires``): a library without them has no ``put`` at an
offset and no AMO on one word and cannot run it, and ``require`` ends the
run at the first call, on every rank, BEFORE any allocation is made
(``malloc`` is collective) — so nothing is left hanging.
"""

import functools

import numpy as np

from perfbench.ops import _pt2pt, _rma

WINDOW = 64    # puts or posted AMOs per quiet (osu_oshm_put_mr's window)
BLOCKING = 16  # blocking operations (gets, fetching AMOs) per call
TARGET = 1     # the partner PE: the host rank of the 'D H' placement
HOT = 8        # the AMOs' indices fall on the table's first words

fresh = _pt2pt.fresh
head = _pt2pt.head
notify = _rma.notify
join = _rma.join
unswap = _rma.unswap
table_elems = _pt2pt.elems


def require(cfg):
    """End the run unless the library has every counter the configuration
    names under ``requires``: checked once, at the first (warm-up) call."""
    _require(tuple(cfg["requires"]["pvars"]))


@functools.lru_cache(maxsize=None)
def _require(names):
    import ompi_release_tpu.oshmem.shmem  # noqa: F401  (its counters)
    from ompi_release_tpu.mca import pvar

    absent = [n for n in names if pvar.PVARS.lookup(n) is None]
    if absent:
        raise SystemExit(
            "perfbench: osu_shmem needs the library's OpenSHMEM counters "
            f"{absent}: this library has none (no put or get at an offset, "
            "no AMO on one word), so it cannot run the configuration (no "
            "symmetric allocation was made)")


_kept = {}


def allocation(world, key, elems, dtype, over=None, renew=None):
    """(ctx, the symmetric allocation of one (operation, size), the number
    of this call on it counted from 0, what ``renew`` handed back). Made by
    every PE in its first call (``malloc`` is collective) and kept; nothing
    frees it: the harness makes no call after its window in which both PEs
    could (``free`` is collective), so it lasts until ``mpi.finalize()``.
    ``renew(ctx, sym)`` runs in the first call and again whenever ``over``
    (the rank's buffer) is another object: the harness drew its data anew
    (``--extra-seeds``)."""
    from ompi_release_tpu.oshmem import shmem

    ctx = shmem.shmem_init(world)
    entry = _kept.get(key)
    if entry is None:
        entry = _kept[key] = [ctx.malloc((elems,), dtype), -1, None, None]
    if renew is not None and entry[2] is not over:
        entry[2:] = [over, renew(ctx, entry[0])]
    entry[1] += 1
    return ctx, entry[0], entry[1], entry[3]


def origin_plan(make, x):
    """``renew=`` of the AMO operations: PE 0 reads its row once and keeps
    what ``make`` derives from it as host integers; PE 1 issues nothing."""
    return lambda ctx, sym: None if ctx.my_pe == TARGET else make(x)


def small(word):
    """A value of -128 .. 127 from a word of the seeded data."""
    return (int(word) & 0xFF) - 128


def spot(word, elems):
    """An index among the table's first ``HOT`` words: they repeat."""
    return (int(word) >> 8) % min(elems, HOT)


def words(row, count):
    """``count`` words of a PE's row as host integers (a table shorter
    than that gives its words again)."""
    row = np.asarray(row).reshape(-1)
    return [int(row[j % row.size]) for j in range(count)]


def posted_plan(row):
    """[(index, value)] of a call's WINDOW posted AMOs from PE 0's row:
    the first half are ``atomic_add(value)``, the second ``atomic_inc``
    (value 1)."""
    elems = np.asarray(row).size
    ws = words(row, WINDOW)
    half = WINDOW // 2
    return ([(spot(w, elems), small(w)) for w in ws[:half]]
            + [(spot(w, elems), 1) for w in ws[half:]])


def fetching_plan(row):
    """[(kind, index, value, cond)] of a call's BLOCKING fetching AMOs
    from PE 0's row: four on one word at a time — fetch_add, fetch_inc,
    swap, compare_swap — so that the word holds the swapped value when the
    compare comes: every other compare is of that value (it hits), the
    rest of the value plus one (they miss)."""
    elems = np.asarray(row).size
    ws = words(row, BLOCKING)
    plan = []
    for g in range(BLOCKING // 4):
        w = ws[4 * g:4 * g + 4]
        i, held = spot(w[0], elems), small(w[2])
        plan += [("fetch_add", i, small(w[1]), None),
                 ("fetch_inc", i, 1, None),
                 ("swap", i, held, None),
                 ("cswap", i, small(w[3]), held + g % 2)]
    return plan


@functools.lru_cache(maxsize=None)
def _programs():
    import jax
    import jax.numpy as jnp

    return {
        # a new array every call (PR 28's reason: jax may keep the host
        # copy of an array it has fetched once), the call's parity added
        "base": jax.jit(lambda x, parity: x + parity),
        "unbase": jax.jit(lambda t, parity: (t - parity).reshape(1, -1)),
        "row": jax.jit(lambda t: t.reshape(1, -1)),
        "stack": jax.jit(lambda *v: jnp.stack(v)[None, :])}


def base(x, parity):
    """The rank's (1, elems) table anew, every word raised by ``parity``."""
    return _programs()["base"](x, parity)


def unbase(table, parity):
    """A PE's table as the (1, elems) array the harness keeps, the parity
    taken off again."""
    return _programs()["unbase"](table, parity)


def row(slot):
    return _programs()["row"](slot)


def stack(values):
    """Scalars side by side in the order given, one (1, n) array."""
    return _programs()["stack"](*values)
