"""What NAS IS's operation needs beside its entry points: the class's
constants from the keys a rank holds, the configuration's ``requires``,
the rank-local device programs of one ``rank()`` iteration, and the numpy
reference of the same iteration.

NAS IS (``IS/is.c``, MPI version): every rank buckets its keys by their
top bits, the bucket sizes are summed over the ranks, the buckets are cut
into contiguous ranges so that each rank holds about ``TOTAL_KEYS/n``
keys, the send counts go round in an alltoall, the keys in an alltoallv,
and each rank counts what it received over its range of key values and
takes the prefix sum (``key_buff1``). The device programs below are the
steps without communication; the collectives are the library's.
"""

import functools
from typing import NamedTuple

import numpy as np

KEYS_PER_MAX_KEY = 16  # TOTAL_KEYS / MAX_KEY, NAS's ratio in classes A..D
FOUR = 4               # a key is the mean of four uniform draws
GOLDEN = 0x9E3779B9    # what tells the four draws of one key apart


class Params(NamedTuple):
    n: int            # ranks
    keys: int         # keys a rank holds (NUM_KEYS)
    max_key_log2: int
    shift: int        # a key's bucket is key >> shift
    buckets: int      # NUM_BUCKETS
    cap: int          # receive capacity of a rank, in keys

    @property
    def max_key(self):
        return 1 << self.max_key_log2


def params(n, keys, cfg):
    """The class's constants from the keys a rank holds: MAX_KEY is
    TOTAL_KEYS/16, so a rehearsal's smaller sizes keep the shape, and the
    buckets are the configuration's 2**num_buckets_log2 (or MAX_KEY, where
    that is fewer)."""
    total = n * keys
    max_key = total // KEYS_PER_MAX_KEY
    if max_key < 2 or max_key & (max_key - 1) or total % KEYS_PER_MAX_KEY:
        raise SystemExit(f"perfbench: npb_is needs a power of two of keys "
                         f"of at least 32 in all, got {n} x {keys}")
    log2 = max_key.bit_length() - 1
    nb_log2 = min(int(cfg["num_buckets_log2"]), log2)
    cap = int(keys * float(cfg["recv_capacity"]))
    return Params(n, keys, log2, log2 - nb_log2, 1 << nb_log2, cap)


def require(cfg):
    """End the run unless the library has every counter the configuration
    names under ``requires``: checked once, at the first (warm-up) call,
    before anything collective is made."""
    _require(tuple(cfg["requires"]["pvars"]))


@functools.lru_cache(maxsize=None)
def _require(names):
    from ompi_release_tpu.coll import vcoll  # noqa: F401  (its counters)
    from ompi_release_tpu.mca import pvar

    absent = [n for n in names if pvar.PVARS.lookup(n) is None]
    if absent:
        raise SystemExit(
            "perfbench: npb_is4 needs the library's device-form alltoallv "
            f"and its counters {absent}: this library has none, so it "
            "cannot run the configuration (nothing collective was made)")


def keys_of(bits, max_key_log2, xp):
    """IS's keys from uniform 32-bit words, one word a key: four draws
    ``u_t`` uniform on ``[0, MAX_KEY)``, each the top bits of murmur3's
    32-bit finaliser (a bijection) of the word mixed with ``t``, and the
    key their mean, ``(u_1+u_2+u_3+u_4) >> 2``. Integer work alone, so
    ``xp`` (``jax.numpy`` on the chips, ``numpy`` for the reference)
    gives the same keys from the same words."""
    u = bits.astype(xp.uint32)
    acc = xp.zeros(u.shape, xp.uint32)
    for t in range(FOUR):
        h = u ^ xp.uint32((t * GOLDEN) & 0xFFFFFFFF)
        h = h ^ (h >> 16)
        h = h * xp.uint32(0x85EBCA6B)
        h = h ^ (h >> 13)
        h = h * xp.uint32(0xC2B2AE35)
        h = h ^ (h >> 16)
        acc = acc + (h >> (32 - max_key_log2))
    return (acc >> 2).astype(xp.int32)


# ---------------------------------------------------------------------------
# the rank-local device programs
# ---------------------------------------------------------------------------

def _local(mesh, fn):
    """``fn`` on each rank's own rows, with no communication: shard_map
    over the rank axis, jitted."""
    import jax
    from jax.sharding import PartitionSpec as P

    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("rank"),
                                 out_specs=P("rank")))


@functools.lru_cache(maxsize=None)
def programs(mesh, p):
    """(draw, bucket, cut, rank): the keys from the words, and the three
    local steps of one iteration."""
    import jax.numpy as jnp
    from jax import lax

    edges = jnp.arange(p.buckets + 1, dtype=jnp.int32) << p.shift

    def draw(bits):
        return keys_of(bits, p.max_key_log2, jnp)

    def bucket(x):
        # keys in bucket order (sorted: within a bucket the order is free,
        # the ranking does not see it) and the local bucket sizes
        s = lax.sort(x[0])
        return s[None], jnp.diff(jnp.searchsorted(s, edges))[None]

    def cut(local, totals):
        # NAS's contiguous cut: rank j's last bucket is the first at which
        # the running total reaches (j+1)*NUM_KEYS, at most one cut a bucket
        t = jnp.cumsum(totals[0])
        j = jnp.arange(p.n, dtype=jnp.int32)
        first = jnp.searchsorted(t, (j + 1) * p.keys).astype(jnp.int32)
        last = lax.cummax(first - j) + j
        made = last < p.buckets
        last = jnp.where(made, last, 0)
        start = jnp.where(made, jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), last[:-1] + 1]), 1)
        upto = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                jnp.cumsum(local[0])])
        send = jnp.where(made, upto[last + 1] - upto[start], 0)
        me = lax.axis_index("rank")
        lo = start[me] << p.shift
        width = (last[me] + 1 - start[me]) << p.shift
        return send[None], jnp.stack([lo, width])[None]

    grid = p.n * p.max_key

    def shifted(a, by):
        # a moved by ``by`` places (right where by > 0), -1 where it opens
        gap = jnp.full((abs(by),), -1, a.dtype)
        if by > 0:
            return jnp.concatenate([gap, a[:-by]])
        return jnp.concatenate([a[-by:], gap])

    def move(a, bits, right):
        # every entry >= 0 moves by its own value, one bit a pass: to the
        # right from the top bit down, to the left from the lowest bit up.
        # Where the targets keep the entries' order and the moves never
        # shrink along it, no two entries meet in any pass
        order = range(bits - 1, -1, -1) if right else range(bits)
        for b in order:
            step = 1 << b
            come = shifted(a, step if right else -step)
            come = jnp.where((come >= 0) & ((come >> b) & 1 == 1), come, -1)
            stay = (a >= 0) & ((a >> b) & 1 == 0)
            a = jnp.where(stay, a, come)
        return a

    def rank(recv, counts, span):
        # key_buff1 at value lo+v: how many received keys are at or below
        # it; zero past the range. The row holds one sorted run a source,
        # in source order, so run k's key at e = k*MAX_KEY + key - lo is
        # ascending along the row. Merged with one query a value x of the
        # grid (after the keys equal to it), key i sits at i + e_i and
        # query x at x + G(x), G(x) the keys at or below x: the keys move
        # there, a running count over the merged row gives each query its
        # G, and the queries move back left by it. Run k's count at v is
        # G(k*MAX_KEY + v) less the keys of the runs before it. Dense
        # passes only: a scatter of every key, or a search of every value,
        # costs a memory access of 9 to 20 ns a key on a v5e chip
        lo, width = span[0, 0], span[0, 1]
        rc = counts[0]
        ends = jnp.cumsum(rc)
        i = jnp.arange(p.cap, dtype=jnp.int32)
        run = sum((i >= ends[k]).astype(jnp.int32) for k in range(p.n))
        e = jnp.where(run < p.n, run * p.max_key + recv[0] - lo, -1)
        keys = move(jnp.pad(e, (0, grid), constant_values=-1),
                    (grid - 1).bit_length(), right=True) >= 0
        seen = jnp.cumsum(keys, dtype=jnp.int32)
        g = move(jnp.where(keys, -1, seen), p.cap.bit_length(),
                 right=False)[:grid]
        before = jnp.sum(jnp.minimum(ends - rc, p.cap))
        buff = g.reshape(p.n, p.max_key).sum(0) - before
        return jnp.where(jnp.arange(p.max_key) < width, buff, 0)[None]

    return (_local(mesh, draw), _local(mesh, bucket), _local(mesh, cut),
            _local(mesh, rank))


# ---------------------------------------------------------------------------
# the numpy reference (nothing of the library)
# ---------------------------------------------------------------------------

def nas_cut(totals, p):
    """NAS's loop as it is written: (first bucket, last bucket) of each
    rank."""
    start, last = [0] + [1] * (p.n - 1), [0] * p.n
    acc, j = 0, 0
    for i in range(p.buckets):
        acc += int(totals[i])
        if j < p.n and acc >= (j + 1) * p.keys:
            if j:
                start[j] = last[j - 1] + 1
            last[j] = i
            j += 1
    return start, last


def nas_rank(x, p):
    """key_buff1 of every rank after one iteration on the keys ``x``,
    each padded with zeros to MAX_KEY: what the buckets of its range
    hold, counted per key value and summed up."""
    bucket = x >> p.shift
    totals = np.bincount(bucket.reshape(-1), minlength=p.buckets)
    start, last = nas_cut(totals, p)

    def row(r):
        lo = start[r] << p.shift
        width = max(0, (last[r] + 1 - start[r]) << p.shift)
        mine = x[(bucket >= start[r]) & (bucket <= last[r])]
        out = np.zeros(p.max_key, np.int32)
        out[:width] = np.cumsum(np.bincount(mine - lo, minlength=width))
        return out

    return row
