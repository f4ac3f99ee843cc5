"""osu_bibw: both ranks post WINDOW receives, then WINDOW sends of distinct
messages, then wait on all; each returns its arrivals in the order of its
requests. ``span_algbw`` counts what ONE rank sends in a call: OSU's
osu_bibw figure, both directions together, is twice it."""

from perfbench.ops import _pt2pt

KIND = "move"
TAG = 10  # osu_bibw's
elems = _pt2pt.window_elems


def call(world, x, cfg):
    _pt2pt.require(cfg)
    me, peer = _pt2pt.ranks(world)
    msgs = _pt2pt.fresh(x, _pt2pt.WINDOW)
    recvs = [world.irecv(peer, TAG, rank=me) for _ in range(_pt2pt.WINDOW)]
    sends = [world.isend(m, peer, TAG, rank=me) for m in msgs]
    _pt2pt.wait_all(recvs + sends)
    return _pt2pt.join([r.value for r in recvs])


def expected(x, cfg, sums):
    return (lambda r: x[1 - r]), None


def least_bytes(n, s):
    # the chip reads the window it sends and writes the one it receives
    return s, 2 * s
