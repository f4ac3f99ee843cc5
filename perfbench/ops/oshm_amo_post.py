"""osu_oshm_atomics, the routines that fetch nothing (``shmem_int_add``,
``shmem_int_inc``): the table is ``size`` bytes of int32 on PE 1. A call
of PE 0: one posted ``put`` of the table's base (its own row, made anew on
the chip), then WINDOW // 2 ``atomic_add(v, index=i)`` and WINDOW // 2
``atomic_inc(index=i)`` on indices and values that follow from its row
(``_shm.posted_plan``: read once, in the first warm-up call, and kept as
host integers; they fall on the table's first words and repeat — a hot
word is the point), one ``quiet``, the 4-byte notice. PE 1 waits for the
notice and returns its table: the base plus every contribution, whatever
the number of calls before (OSU does not re-put; a comparison needs a
known state).

Every call leaves the same table, so a ``quiet`` that returned early would
leave it looking right (the last call's). Hence on odd calls every word of
the base goes in raised by one, and PE 1, which counts its calls too, takes
the one off what it returns. (``put_bw`` trades neighbouring blocks; here
that would move the contributions with the words, and the indices have to
stay where they are: a frozen batch is keyed by them.)"""

import numpy as np

from perfbench.ops import _shm

KIND = "move"
elems = _shm.table_elems


def call(world, x, cfg):
    _shm.require(cfg)
    ctx, sym, k, plan = _shm.allocation(
        world, ("oshm_amo_post", x.shape[1]), x.shape[1], x.dtype, over=x,
        renew=_shm.origin_plan(_shm.posted_plan, x))
    me = ctx.my_pe
    if me != _shm.TARGET:
        ctx.put(sym, _shm.base(x, k & 1), _shm.TARGET, offset=0)
        half = _shm.WINDOW // 2
        for i, v in plan[:half]:
            ctx.atomic_add(sym, v, _shm.TARGET, index=i)
        for i, _ in plan[half:]:
            ctx.atomic_inc(sym, _shm.TARGET, index=i)
        ctx.quiet()
        note = _shm.head(x)
        _shm.notify(world, me, _shm.TARGET, note)
        return note
    _shm.notify(world, me, 1 - me)
    return _shm.unbase(sym.local(me), k & 1)


def expected(x, cfg, sums):
    table = x[0].astype(np.int64)
    for i, v in _shm.posted_plan(x[0]):
        table[i] += v
    table = table.astype(np.int32)  # wraps as the int32 sums do
    return (lambda r: table if r == _shm.TARGET else x[0][:1]), None


def least_bytes(n, s):
    # the chip reads the table to put it; the AMOs' own bytes are host
    # scalars and never touch it
    return s, s
