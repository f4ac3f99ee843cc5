"""The plain reference, the comparison that decides ``correct``, and its
control. numpy only: nothing of the library is imported here, and the
reference takes nothing the library has made (the inputs are the
benchmark's own, drawn from the seed).

What is compared is what timed calls of the window returned: for every
(operation, size) of the cell one call drawn from the seed, every local
rank's whole buffer. Each number has a limit of its own in limits.json:

    sum_err_ulp      reductions: the worst |got - sum| over all elements,
                     in units of eps(f32) x the sum of the magnitudes
                     that went into that element
    moved_mismatch   bcast / allgather / alltoall: elements that differ
                     from the reference (exact: limit 0)
    misplaced        results that are not device arrays on exactly the
                     communicator's devices, or have the wrong shape or
                     dtype (limit 0)
    missing          (operation, size) pairs with no result to compare,
                     and calls that raised (limit 0)
    window_compiles  programs compiled inside the measured window
                     (limit 0)
"""

import json
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from perfbench import manifest

EPS32 = float(np.finfo(np.float32).eps)
BROKEN = float(np.finfo(np.float32).max)  # no rounding error reads this
THREADS = 8  # numpy and the fetch from a device both run outside the GIL


def limits():
    return manifest.load_json(os.path.join(manifest.HERE, "limits.json"))


class Sums:
    """The f32-rounded sum over ranks of one input and the sum of its
    magnitudes, accumulated in float64 block by block and kept, since
    allreduce and reduce_scatter_block of one size share them."""

    def __init__(self):
        self._kept = {}

    def __call__(self, x):
        if id(x) not in self._kept:
            total = np.empty(x.shape[1], np.float32)
            mags = np.empty(x.shape[1], np.float32)
            step = 1 << 20

            def block(lo):
                blk = x[:, lo:lo + step]
                total[lo:lo + step] = blk.sum(0, dtype=np.float64)
                mags[lo:lo + step] = np.abs(blk).sum(0, dtype=np.float64)

            with ThreadPoolExecutor(THREADS) as pool:
                list(pool.map(block, range(0, x.shape[1], step)))
            self._kept[id(x)] = (x, total, mags)  # x kept: its id stays its own
        return self._kept[id(x)][1:]


def lower_precision(want):
    """The control: what a rank would hold had its result gone through
    the nearest precision below the stated one: bfloat16 for float32,
    the high 16 bits for int32."""
    import ml_dtypes

    if want.dtype == np.int32:
        return want & np.int32(-65536)
    return want.astype(ml_dtypes.bfloat16).astype(np.float32)


def compare_row(got, want, scale):
    """One rank's buffer against the reference: (err_ulp, or None for
    moved data; mismatched elements), block by block so that no temporary
    is larger than a block. A reduction of the wrong shape or type, or
    with an element that is not a number, reads BROKEN."""
    if got.shape != want.shape or got.dtype != want.dtype:
        return (None if scale is None else BROKEN), int(want.size)
    worst, bad, step = 0.0, 0, 1 << 20
    for lo in range(0, want.size, step):
        g, w = got[lo:lo + step], want[lo:lo + step]
        if scale is None:
            bad += int(np.count_nonzero(g != w))
            continue
        err = np.abs(g - w)
        err /= scale[lo:lo + step]
        top = float(err.max())
        if not np.isfinite(top):  # a NaN would lose every max() after it
            return BROKEN, int(want.size)
        worst = max(worst, top)
    return (None if scale is None else worst / EPS32), bad


def compare(op, cfg, x, rows, sums, control=False):
    """``rows``: (rank, buffer as the timed call returned it, or a call
    that fetches it) pairs, compared side by side: one thread fetches
    and compares one rank's buffer. Returns the numbers of this
    (operation, input): err_ulp (reductions), mismatch, elements."""
    row, scale = manifest.operation(op).expected(x, cfg, sums)

    def one(pair):
        r, got = pair
        want = np.ascontiguousarray(row(r))
        got = lower_precision(want) if control else (
            got() if callable(got) else got)
        return compare_row(np.asarray(got).reshape(-1), want,
                           None if scale is None else scale(r)) + (want.size,)

    rows = list(rows)
    with ThreadPoolExecutor(min(THREADS, max(1, len(rows)))) as pool:
        parts = list(pool.map(one, rows))
    errs = [err for err, _, _ in parts if err is not None]
    return {"err_ulp": max(errs) if errs else None,
            "mismatch": sum(miss for _, miss, _ in parts),
            "elements": sum(size for _, _, size in parts)}


def verdict(numbers, lim=None):
    """{name: {"value", "limit"}} and whether every number keeps to its
    limit. A number that is absent (no reduction in the cell) is not
    compared."""
    lim = limits() if lim is None else lim
    out = {k: {"value": v, "limit": lim[k]} for k, v in numbers.items()
           if v is not None}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in out.values())
    return out, ok


def lines(compared):
    """The numbers beside their limits, one short line each."""
    return [f"perfbench compared {k} = {json.dumps(c['value'])} "
            f"(limit {json.dumps(c['limit'])})" for k, c in compared.items()]
