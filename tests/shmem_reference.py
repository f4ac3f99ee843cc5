"""A plain numpy model of the symmetric heap: the reference the OpenSHMEM
tests hold ``oshmem/shmem.py`` to. It imports nothing of the library.

One allocation of ``elems`` elements per PE, zeros at first (``shmem_malloc``
through ``win_allocate``). An operation is a tuple, ``pe`` the PE it acts
on, ``offset``/``index`` flat into that PE's array:

    ("put", pe, offset, values)      ("get", pe, offset, nelems)
    ("add", pe, index, value)        ("fetch_add", pe, index, value)
    ("inc", pe, index)               ("fetch_inc", pe, index)
    ("set", pe, index, value)        ("swap", pe, index, value)
    ("fetch", pe, index)             ("cswap", pe, index, cond, value)
    ("quiet",)

Operations are applied in the order of the list, which is the order one
origin issued them in (several origins: the caller joins their lists in the
order their phases ran). ``quiet`` changes nothing here: completion is the
library's to keep, the model applies at once. ``run`` returns every fetched
value in order (a ``get`` as an array, an AMO as a scalar) and every PE's
final array.
"""

import numpy as np

FETCHING = ("get", "fetch_add", "fetch_inc", "swap", "cswap", "fetch")


def run(n_pes, elems, dtype, ops):
    heap = [np.zeros(elems, dtype) for _ in range(n_pes)]
    fetched = []
    for op in ops:
        kind = op[0]
        if kind == "quiet":
            continue
        mem, at = heap[op[1]], op[2]
        if kind == "put":
            values = np.asarray(op[3], dtype).reshape(-1)
            mem[at:at + values.size] = values
        elif kind == "get":
            fetched.append(mem[at:at + op[3]].copy())
        else:
            old = mem[at].copy()
            if kind in ("add", "fetch_add"):
                mem[at:at + 1] += np.asarray(op[3], dtype)
            elif kind in ("inc", "fetch_inc"):
                mem[at:at + 1] += np.asarray(1, dtype)
            elif kind in ("set", "swap"):
                mem[at] = op[3]
            elif kind == "cswap":
                if old == np.asarray(op[3], dtype):
                    mem[at] = op[4]
            elif kind != "fetch":
                raise ValueError(f"no such operation: {kind}")
            if kind in FETCHING:
                fetched.append(old)
    return fetched, heap


def random_ops(rng, n_pes, elems, count, dtype, pes=None):
    """``count`` seeded operations on an allocation of ``elems`` elements:
    every kind for an integer type, puts and gets alone for a float (its
    AMOs would round in an order the model does not fix). Indices come
    from a few hot words, so operations meet; a ``quiet`` falls in now and
    then. ``pes``: the PEs to act on (all by default)."""
    dtype = np.dtype(dtype)
    pes = list(range(n_pes)) if pes is None else list(pes)
    kinds = ["put", "get", "quiet"]
    if dtype.kind == "i":
        kinds += ["add", "inc", "set", "fetch_add", "fetch_inc", "swap",
                  "cswap", "fetch"]
    hot = rng.integers(0, elems, size=4)

    def values(n):
        if dtype.kind == "i":
            return rng.integers(-1000, 1000, size=n).astype(dtype)
        return rng.standard_normal(n).astype(dtype)

    ops = []
    for _ in range(count):
        kind = kinds[rng.integers(len(kinds))]
        pe = int(pes[rng.integers(len(pes))])
        if kind == "quiet":
            ops.append(("quiet",))
        elif kind in ("put", "get"):
            n = int(rng.integers(1, max(2, elems // 2)))
            at = int(rng.integers(0, elems - n + 1))
            ops.append(("put", pe, at, values(n)) if kind == "put"
                       else ("get", pe, at, n))
        else:
            at = int(hot[rng.integers(len(hot))])
            if kind in ("inc", "fetch_inc", "fetch"):
                ops.append((kind, pe, at))
            elif kind == "cswap":
                # half of the compares are of a value the word may hold
                cond = 0 if rng.integers(2) else int(values(1)[0])
                ops.append((kind, pe, at, cond, int(values(1)[0])))
            else:
                ops.append((kind, pe, at, int(values(1)[0])))
    return ops


def play(ctx, sym, ops):
    """The same list through a ``ShmemCtx`` (the library's API is all this
    touches): every fetched value in order, as numpy. The caller reads the
    final arrays where it can see them."""
    fetched = []
    for op in ops:
        kind = op[0]
        if kind == "quiet":
            ctx.quiet()
        elif kind == "put":
            ctx.put(sym, op[3], op[1], offset=op[2])
        elif kind == "get":
            fetched.append(np.asarray(
                ctx.get(sym, op[1], offset=op[2], nelems=op[3])))
        elif kind == "add":
            ctx.atomic_add(sym, op[3], op[1], index=op[2])
        elif kind == "inc":
            ctx.atomic_inc(sym, op[1], index=op[2])
        elif kind == "set":
            ctx.atomic_set(sym, op[3], op[1], index=op[2])
        elif kind == "fetch_add":
            fetched.append(np.asarray(
                ctx.atomic_fetch_add(sym, op[3], op[1], index=op[2])))
        elif kind == "fetch_inc":
            fetched.append(np.asarray(
                ctx.atomic_fetch_inc(sym, op[1], index=op[2])))
        elif kind == "swap":
            fetched.append(np.asarray(
                ctx.atomic_swap(sym, op[3], op[1], index=op[2])))
        elif kind == "cswap":
            fetched.append(np.asarray(ctx.atomic_compare_swap(
                sym, op[3], op[4], op[1], index=op[2])))
        elif kind == "fetch":
            fetched.append(np.asarray(
                ctx.atomic_fetch(sym, op[1], index=op[2])))
        else:
            raise ValueError(f"no such operation: {kind}")
    ctx.quiet()
    return fetched
