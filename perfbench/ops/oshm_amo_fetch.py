"""osu_oshm_atomics, the routines that fetch (``shmem_int_fadd``,
``shmem_int_finc``, ``shmem_int_swap``, ``shmem_int_cswap``): the same
table of ``size`` bytes of int32 on PE 1 and the same posted ``put`` of
its base, then BLOCKING blocking AMOs, four of each kind, on indices and
values that follow from PE 0's row (``_shm.fetching_plan``; half of the
compares hit). Each returns the word's value from before it. PE 0 returns
the BLOCKING old values side by side on its chip, after the 4-byte notice;
PE 1 waits for the notice and returns its table.

No call looks right by being late here: every one of the AMOs hands a
value back, and each follows the ``put`` that set its word (operations of
one origin on one PE apply in the order issued), so the table needs no
parity (``oshm_amo_post`` has one)."""

import numpy as np

from perfbench.ops import _shm

KIND = "move"
elems = _shm.table_elems


def call(world, x, cfg):
    _shm.require(cfg)
    ctx, sym, _, plan = _shm.allocation(
        world, ("oshm_amo_fetch", x.shape[1]), x.shape[1], x.dtype, over=x,
        renew=_shm.origin_plan(_shm.fetching_plan, x))
    me, pe = ctx.my_pe, _shm.TARGET
    if me != pe:
        ctx.put(sym, _shm.base(x, 0), pe, offset=0)
        old = []
        for kind, i, v, cond in plan:
            if kind == "fetch_add":
                old.append(ctx.atomic_fetch_add(sym, v, pe, index=i))
            elif kind == "fetch_inc":
                old.append(ctx.atomic_fetch_inc(sym, pe, index=i))
            elif kind == "swap":
                old.append(ctx.atomic_swap(sym, v, pe, index=i))
            else:
                old.append(ctx.atomic_compare_swap(sym, cond, v, pe,
                                                   index=i))
        out = _shm.stack(old)
        _shm.notify(world, me, pe, _shm.head(x))
        return out
    _shm.notify(world, me, 1 - me)
    return _shm.row(sym.local(me))


def expected(x, cfg, sums):
    table, old = x[0].astype(np.int64), []
    for kind, i, v, cond in _shm.fetching_plan(x[0]):
        old.append(table[i])
        if kind in ("fetch_add", "fetch_inc"):
            # wraps as the int32 sum does
            table[i] = np.int64(table[i] + v).astype(np.int32)
        elif kind == "swap" or table[i] == cond:
            table[i] = v
    table, old = table.astype(np.int32), np.asarray(old, np.int32)
    return (lambda r: table if r == _shm.TARGET else old), None


def least_bytes(n, s):
    # the chip reads the table to put it and writes the values it got;
    # the AMOs' own operands are host scalars
    return s + 4 * _shm.BLOCKING, s + 4 * _shm.BLOCKING
