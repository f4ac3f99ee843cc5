"""osu_bw: rank 1 posts WINDOW receives, rank 0 WINDOW sends of distinct
messages in order, both wait on all; rank 1 then sends a 4-byte
acknowledgement (the first element of its own buffer) and rank 0 receives
it. Rank 1 returns the arrivals in the order of its requests (MPI's
non-overtaking order is part of the comparison), rank 0 the
acknowledgement."""

from perfbench.ops import _pt2pt

KIND = "move"
TAG = 100  # osu_bw's
elems = _pt2pt.window_elems


def call(world, x, cfg):
    _pt2pt.require(cfg)
    me, peer = _pt2pt.ranks(world)
    if me == 0:
        msgs = _pt2pt.fresh(x, _pt2pt.WINDOW)
        _pt2pt.wait_all([world.isend(m, peer, TAG, rank=me) for m in msgs])
        ack, _ = world.recv(peer, TAG, rank=me)
        return ack
    ack = _pt2pt.head(x)
    reqs = [world.irecv(peer, TAG, rank=me) for _ in range(_pt2pt.WINDOW)]
    _pt2pt.wait_all(reqs)
    world.send(ack, peer, TAG, rank=me)
    return _pt2pt.join([r.value for r in reqs])


def expected(x, cfg, sums):
    return (lambda r: x[0] if r == 1 else x[1][:1]), None


def least_bytes(n, s):
    # the chip reads the whole window to send it (s = WINDOW messages);
    # the acknowledgement it writes is one element
    return s, s
