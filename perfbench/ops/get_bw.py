"""osu_get_bw: rank 0 gets WINDOW blocks of rank 1's slot, block j from
displacement ``j * elems``, flushes (every get then holds exactly what the
target's slot held), joins the values in the order of its requests on its
chip and sends the 4-byte notice. The window is made with ``win_create``
from each rank's own row and never written. Rank 1 waits for the notice
and returns the head of its buffer."""

from perfbench.ops import _rma

KIND = "move"
elems = _rma.elems


def call(world, x, cfg):
    _rma.require(cfg)
    from ompi_release_tpu import osc

    me, peer = _rma.ranks(world)
    win, _ = _rma.window(world, ("get_bw", x.shape[1]),
                         lambda: osc.win_create(world, x), over=x)
    n = x.shape[1] // _rma.WINDOW
    if me != _rma.TARGET:
        reqs = [win.get(peer, disp=j * n, count=n)
                for j in range(_rma.WINDOW)]
        win.flush(peer)
        out = _rma.join([r.value for r in reqs])
        _rma.notify(world, me, peer, _rma.head(x))
        return out
    _rma.notify(world, me, peer)
    return _rma.head(x)


def expected(x, cfg, sums):
    return (lambda r: x[1][:1] if r == _rma.TARGET else x[1]), None


def least_bytes(n, s):
    # the chip writes a window's worth (s = WINDOW blocks) to hold what
    # it got
    return s, s
