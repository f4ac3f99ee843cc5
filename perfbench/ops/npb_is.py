"""NAS IS (Integer Sort), one iteration of ``rank()`` on the device: local
bucketing, ``world.allreduce`` of the bucket sizes, NAS's contiguous cut
of the buckets over the ranks, the send counts and the keys through the
device form of ``world.alltoallv`` (its program sends each rank its
counts first: NAS's ``MPI_Alltoall``), and each rank's count of what it
received over its range of key values, summed up (``key_buff1``).
Nothing is read on the host between the steps.

The keys are IS's: each the mean of four uniform draws on ``[0,
MAX_KEY)``, a bell over the range, so the counts of the exchange are
uneven and change with the seed. They come from the generator's uniform
int32 words, one word a key (``_npb.keys_of``), on the chips in the first
call on an input (a warm-up call: NAS does not time key generation
either) and are kept for the calls after it. Each call buckets them
afresh, so the send buffer and its counts are new arrays every call.
"""

import numpy as np

from perfbench.ops import _npb

KIND = "move"


def elems(n, nbytes, itemsize):
    return max(1, nbytes // itemsize)  # the keys a rank holds


_drawn = {}  # the input whose keys are drawn, and its keys: the last one


def call(world, x, cfg):
    _npb.require(cfg)
    p = _npb.params(world.size, x.shape[1], cfg)
    draw, bucket, cut, rank = _npb.programs(world.submesh, p)
    if _drawn.get("x") is not x:
        _drawn.update(x=x, keys=draw(x))
    keys, local = bucket(_drawn["keys"])
    totals = world.allreduce(local)
    send, span = cut(local, totals)
    recv, counts = world.alltoallv(keys, send, recv_capacity=p.cap)
    return rank(recv, counts, span)


def expected(x, cfg, sums):
    n, m = x.shape
    p = _npb.params(n, m, cfg)
    return _npb.nas_rank(_npb.keys_of(x, p.max_key_log2, np), p), None


def least_bytes(n, s):
    # links: the keys that leave the chip, (n-1)/n of its own. Memory: the
    # bucketing reads and writes them, the exchange reads and writes them,
    # the ranking reads them and writes its histogram over MAX_KEY values
    # (TOTAL_KEYS/16 of them: n*s/16 bytes)
    return (n - 1) * s / n, 5 * s + n * s / _npb.KEYS_PER_MAX_KEY
