"""A plain numpy model of an RMA window: one 1-D array per rank's slot.

Operations apply in program order. Each names a target rank and one of
three extents: the whole slot, one element (``index``) or ``count``
consecutive elements from flat offset ``disp`` of the slot flattened in
C order. ``put``, ``accumulate`` and ``compare_and_swap`` change the
extent; ``get``, ``get_accumulate`` and ``compare_and_swap`` hand back
what it held before. Nothing of the library is imported here.
"""

import numpy as np

OPS = {
    "sum": lambda old, new: old + new,
    "max": np.maximum,
    "min": np.minimum,
    "replace": lambda old, new: new,
}


class Model:
    def __init__(self, slots):
        """``slots``: (ranks, ...) initial contents; a rank's slot is
        its row, flattened."""
        slots = np.asarray(slots)
        self.shape = slots.shape[1:]
        self.slots = [row.reshape(-1).copy() for row in slots]

    def _extent(self, target, index=None, disp=None, count=None):
        slot = self.slots[target]
        if index is not None:
            return slot[index:index + 1]
        if disp is not None:
            if disp < 0 or count < 1 or disp + count > slot.size:
                raise IndexError((disp, count, slot.size))
            return slot[disp:disp + count]
        return slot

    def _shaped(self, old, index, disp):
        if index is not None:
            return old[0]
        return old if disp is not None else old.reshape(self.shape)

    def _value(self, data, ext):
        return np.broadcast_to(
            np.asarray(data, ext.dtype).reshape(-1)
            if np.size(data) == ext.size else np.asarray(data, ext.dtype),
            ext.shape)

    def put(self, data, target, index=None, disp=None):
        count = None if disp is None else np.size(data)
        ext = self._extent(target, index, disp, count)
        ext[...] = self._value(data, ext)

    def get(self, target, disp=None, count=None):
        ext = self._extent(target, None, disp, count)
        return self._shaped(ext.copy(), None, disp)

    def accumulate(self, data, target, op="sum", index=None, disp=None):
        self.get_accumulate(data, target, op, index, disp)

    def get_accumulate(self, data, target, op="sum", index=None, disp=None):
        count = None if disp is None else np.size(data)
        ext = self._extent(target, index, disp, count)
        old = ext.copy()
        ext[...] = OPS[op](old, self._value(data, ext))
        return self._shaped(old, index, disp)

    def compare_and_swap(self, value, compare, target, index=None,
                         disp=None):
        count = None if disp is None else np.size(value)
        ext = self._extent(target, index, disp, count)
        old = ext.copy()
        ext[...] = np.where(old == self._value(compare, ext),
                            self._value(value, ext), old)
        return self._shaped(old, index, disp)

    def read(self):
        """(ranks, ...): every slot as the window would show it."""
        return np.stack([s.reshape(self.shape) for s in self.slots])


# ---------------------------------------------------------------------------
# seeded random epochs, as plain descriptions: what the tests issue to a
# window of the library and apply to the model, in the same order
# ---------------------------------------------------------------------------

KINDS = ("put", "get", "accumulate", "get_accumulate", "compare_and_swap")
COUNTS = (1, 3, 8)  # few distinct block sizes: few programs to compile


def random_epoch(rng, targets, shape, n_ops):
    """``n_ops`` operations on ``targets``, each a dict: ``kind``,
    ``target``, one extent (``index``, or ``disp`` and ``count``, or
    neither: the whole slot) and, by kind, ``data``, ``op``,
    ``compare``. Values are small whole numbers in float32, so every
    sum is exact; ranges overlap often (the slot is small)."""
    size = int(np.prod(shape))
    ops = []
    for _ in range(n_ops):
        kind = KINDS[rng.integers(len(KINDS))]
        op = {"kind": kind, "target": int(targets[rng.integers(len(targets))])}
        extent = rng.integers(4)  # 0: slot, 1: element, 2-3: range
        if extent == 1 and kind != "get":
            op["index"] = int(rng.integers(size))
            n, shaped = 1, ()
        elif extent >= 2:
            n = int(COUNTS[rng.integers(len(COUNTS))])
            op["disp"] = int(rng.integers(size - n + 1))
            op["count"] = n
            shaped = (n,)
        else:
            n, shaped = size, tuple(shape)
        if kind != "get":
            op["data"] = rng.integers(0, 6, shaped).astype(np.float32)
        if kind in ("accumulate", "get_accumulate"):
            op["op"] = ("sum", "max", "min", "replace")[rng.integers(4)]
        if kind == "compare_and_swap":
            op["compare"] = rng.integers(0, 6, shaped).astype(np.float32)
        ops.append(op)
    return ops


def extent_of(op):
    return {k: op[k] for k in ("index", "disp") if k in op}


def apply(model, ops):
    """The epoch on the model, in order: one entry per operation, the
    value it reads back or None."""
    out = []
    for op in ops:
        kind, t, ext = op["kind"], op["target"], extent_of(op)
        if kind == "put":
            out.append(model.put(op["data"], t, **ext))
        elif kind == "get":
            out.append(model.get(t, **{k: op[k] for k in ("disp", "count")
                                       if k in op}))
        elif kind == "accumulate":
            out.append(model.accumulate(op["data"], t, op["op"], **ext))
        elif kind == "get_accumulate":
            out.append(model.get_accumulate(op["data"], t, op["op"], **ext))
        else:
            out.append(model.compare_and_swap(op["data"], op["compare"], t,
                                              **ext))
    return out
