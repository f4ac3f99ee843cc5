"""osu_latency: a blocking ping-pong. Rank 0 sends and then receives, rank 1
receives and then sends its own buffer; each returns what it received. One
call is one round trip, two messages: OSU prints half of it."""

from perfbench.ops import _pt2pt

KIND = "move"
TAG = 1  # osu_latency's
elems = _pt2pt.elems


def call(world, x, cfg):
    _pt2pt.require(cfg)
    me, peer = _pt2pt.ranks(world)
    (msg,) = _pt2pt.fresh(x, 1)
    if me == 0:
        world.send(msg, peer, TAG, rank=me)
        got, _ = world.recv(peer, TAG, rank=me)
    else:
        got, _ = world.recv(peer, TAG, rank=me)
        world.send(msg, peer, TAG, rank=me)
    return got


def expected(x, cfg, sums):
    return (lambda r: x[1 - r]), None


def least_bytes(n, s):
    # the chip reads the s it sends and writes the s it receives
    return s, 2 * s
