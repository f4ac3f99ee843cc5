"""osu_put_bw: rank 0 puts WINDOW distinct blocks the device has just
produced, block j at displacement ``j * elems`` of rank 1's slot, flushes
(when ``flush(1)`` has returned every put is complete at the target), then
sends the 4-byte notice. Rank 1 waits for the notice and returns its own
slot, which has to hold rank 0's row, every block at its displacement.

Every call writes the same bytes to the same places, so a block that was
dropped, or a flush that returned early, would leave the slot looking right
(the last call's bytes). Hence on odd calls neighbouring blocks trade
places on the way in — block ``j ^ 1`` goes to displacement ``j * elems``
— and rank 1, which counts its calls too, puts them back in what it
returns: a slot left over from the call before is then wrong in every
block."""

from perfbench.ops import _rma

KIND = "move"
elems = _rma.elems


def call(world, x, cfg):
    _rma.require(cfg)
    from ompi_release_tpu import osc

    me, peer = _rma.ranks(world)
    win, k = _rma.window(world, ("put_bw", x.shape[1]), lambda:
                         osc.win_allocate(world, (x.shape[1],), x.dtype))
    n = x.shape[1] // _rma.WINDOW
    if me != _rma.TARGET:
        blocks = _rma.fresh(x, _rma.WINDOW)
        for j in range(_rma.WINDOW):
            win.put(blocks[j ^ (k & 1)], peer, disp=j * n)
        win.flush(peer)
        note = _rma.head(x)
        _rma.notify(world, me, peer, note)
        return note
    _rma.notify(world, me, peer)
    slot = win.read()
    return _rma.unswap(slot) if k & 1 else slot


def expected(x, cfg, sums):
    return (lambda r: x[0] if r == _rma.TARGET else x[0][:1]), None


def least_bytes(n, s):
    # the chip reads a window's worth (s = WINDOW blocks) to put it
    return s, s
