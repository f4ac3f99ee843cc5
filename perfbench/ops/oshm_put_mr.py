"""osu_oshm_put_mr: PE 0 puts WINDOW distinct blocks the device has just
produced, block j at offset ``j * elems`` of PE 1's symmetric allocation,
calls ``quiet`` (when it has returned every put is complete at PE 1 and
visible to PE 1 reading its own allocation), then sends the 4-byte notice.
PE 1 waits for the notice and returns its own allocation, which has to
hold PE 0's row, every block at its offset.

Every call writes the same bytes to the same places, so a block that was
dropped, or a ``quiet`` that returned early, would leave the allocation
looking right (the last call's bytes). Hence on odd calls neighbouring
blocks trade places on the way in — block ``j ^ 1`` goes to offset
``j * elems`` — and PE 1, which counts its calls too, puts them back in
what it returns (``put_bw``'s way, for its reason)."""

from perfbench.ops import _pt2pt, _shm

KIND = "move"
elems = _pt2pt.window_elems


def call(world, x, cfg):
    _shm.require(cfg)
    ctx, sym, k, _ = _shm.allocation(world, ("oshm_put_mr", x.shape[1]),
                                     x.shape[1], x.dtype)
    me = ctx.my_pe
    n = x.shape[1] // _shm.WINDOW
    if me != _shm.TARGET:
        blocks = _shm.fresh(x, _shm.WINDOW)
        for j in range(_shm.WINDOW):
            ctx.put(sym, blocks[j ^ (k & 1)], _shm.TARGET, offset=j * n)
        ctx.quiet()
        note = _shm.head(x)
        _shm.notify(world, me, _shm.TARGET, note)
        return note
    _shm.notify(world, me, 1 - me)
    slot = sym.local(me)
    return _shm.unswap(slot) if k & 1 else _shm.row(slot)


def expected(x, cfg, sums):
    return (lambda r: x[0] if r == _shm.TARGET else x[0][:1]), None


def least_bytes(n, s):
    # the chip reads a window's worth (s = WINDOW blocks) to put it
    return s, s
