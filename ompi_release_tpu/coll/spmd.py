"""SPMD collective algorithm kernels — the data plane.

These are pure jax functions meant to run *inside* ``shard_map`` over a
1-D mesh axis: each function sees one rank's block and communicates via
``lax.ppermute``/``lax.psum``/... over the axis. They serve both users
(call them inside your own pjit/shard_map programs — the performance
path) and the host driver API (``coll/driver.py`` wraps them per
communicator — the MPI-semantic path).

Algorithm parity with the reference's tuned component
(``ompi/mca/coll/tuned/coll_tuned_allreduce.c:46-54`` enum):
ring + recursive_doubling + segmented_ring for allreduce, binomial
bcast/reduce (``coll_tuned_bcast.c``), ring/recursive-doubling
allgather, pairwise alltoall, recursive-doubling scan/barrier. Each
hand-written algorithm is expressed as static-shape ppermute rounds —
the TPU-native equivalent of tuned's isend/irecv schedules
(``coll_tuned_util.c:50-59``) — so XLA can overlap compute with ICI
transfers inside one compiled program.

All step counts/permutations are static (mesh size known at trace
time); only data is traced. No data-dependent Python control flow.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.op import Op


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _axis_size(axis_name: str) -> int:
    return lax.psum(1, axis_name)  # static under trace


def _ring_perm(n: int):
    return [(i, (i + 1) % n) for i in range(n)]


def _pad_to(x: jax.Array, total: int, fill) -> jax.Array:
    flat = x.reshape(-1)
    pad = total - flat.shape[0]
    if pad == 0:
        return flat
    return jnp.concatenate(
        [flat, jnp.full((pad,), fill, dtype=flat.dtype)]
    )


# ---------------------------------------------------------------------------
# allreduce family
# ---------------------------------------------------------------------------

def allreduce_lax(x: jax.Array, op: Op, axis_name: str) -> jax.Array:
    """XLA-native allreduce: the compiler emits its own ICI schedule.

    SUM/MAX/MIN map to fused psum/pmax/pmin; everything else gathers
    and reduces locally (still one fused program).
    """
    if op.lax_collective == "psum":
        return lax.psum(x, axis_name)
    if op.lax_collective == "pmax":
        return lax.pmax(x, axis_name)
    if op.lax_collective == "pmin":
        return lax.pmin(x, axis_name)
    g = lax.all_gather(x, axis_name, axis=0)  # (n, ...)
    return _tree_reduce_axis0(g, op)


def allreduce_pair_lax(vals: jax.Array, idxs: jax.Array, op: Op,
                       axis_name: str) -> tuple:
    """MINLOC/MAXLOC allreduce over (value, index) arrays."""
    gv = lax.all_gather(vals, axis_name, axis=0)
    gi = lax.all_gather(idxs, axis_name, axis=0)
    accv, acci = gv[0], gi[0]
    for i in range(1, gv.shape[0]):
        accv, acci = op((accv, acci), (gv[i], gi[i]))
    return accv, acci


def _tree_reduce_axis0(g: jax.Array, op: Op) -> jax.Array:
    """Fixed-order pairwise tree reduce over leading axis (deterministic)."""
    n = g.shape[0]
    while n > 1:
        half = n // 2
        even = g[: 2 * half : 2]
        odd = g[1 : 2 * half : 2]
        merged = op(even, odd)
        if n % 2:
            merged = jnp.concatenate([merged, g[2 * half : n]], axis=0)
        g = merged
        n = g.shape[0]
    return g[0]


def allreduce_recursive_doubling(x: jax.Array, op: Op,
                                 axis_name: str, n: int) -> jax.Array:
    """Recursive doubling (coll_tuned_allreduce.c:144), any n.

    Non-power-of-two handled with the standard fold/unfold: the first
    ``2*rem`` ranks pair up so ``p2`` effective ranks run the doubling,
    then results unfold back. Every round is one static ppermute.
    """
    rank = lax.axis_index(axis_name)
    shape, dtype = x.shape, x.dtype
    xf = x.reshape(-1)

    def combine(mine, theirs, their_rank_is_lower):
        """Non-commutative ops need lower-rank operand on the left
        (matches the reference rd's ordering guarantee)."""
        if op.commutative:
            return op(mine, theirs)
        return jnp.where(
            their_rank_is_lower, op(theirs, mine), op(mine, theirs)
        )

    p2 = 1 << (n.bit_length() - 1)
    if p2 == n:
        for d in (2 ** k for k in range(int(math.log2(n)))):
            perm = [(i, i ^ d) for i in range(n)]
            recv = lax.ppermute(xf, axis_name, perm)
            xf = combine(xf, recv, (rank & d) != 0)
        return xf.reshape(shape).astype(dtype)

    rem = n - p2
    # fold: even rank r < 2*rem sends to r+1 (sender is the lower rank)
    perm = [(2 * i, 2 * i + 1) for i in range(rem)]
    recv = lax.ppermute(xf, axis_name, perm)
    is_odd_low = (rank < 2 * rem) & (rank % 2 == 1)
    xf = jnp.where(is_odd_low, combine(xf, recv, True), xf)

    # effective rank for the doubling phase (-1 = idle even-low rank)
    def eff(r: int) -> int:
        if r < 2 * rem:
            return r // 2 if r % 2 == 1 else -1
        return r - rem

    def actual(e: int) -> int:
        return 2 * e + 1 if e < rem else e + rem

    participating = (rank >= 2 * rem) | (rank % 2 == 1)
    my_eff = jnp.where(rank < 2 * rem, rank // 2, rank - rem)
    for d in (2 ** k for k in range(int(math.log2(p2)))):
        perm = []
        for r in range(n):
            e = eff(r)
            if e >= 0:
                perm.append((r, actual(e ^ d)))
        recv = lax.ppermute(xf, axis_name, perm)
        xf = jnp.where(
            participating, combine(xf, recv, (my_eff & d) != 0), xf
        )

    # unfold: odd rank r < 2*rem sends result to r-1
    perm = [(2 * i + 1, 2 * i) for i in range(rem)]
    recv = lax.ppermute(xf, axis_name, perm)
    is_even_low = (rank < 2 * rem) & (rank % 2 == 0)
    xf = jnp.where(is_even_low, recv, xf)
    return xf.reshape(shape).astype(dtype)


def _ring_passes(chunks: jax.Array, op: Op, axis_name: str,
                 n: int) -> jax.Array:
    """The two ring passes (reduce-scatter + allgather) over a
    pre-chunked ``(n, ...)`` buffer. A chunk row's accumulation order
    is fixed by its row index alone — which is what lets the pipelined
    wrapper (``coll/pipeline.py``) segment WITHIN rows and stay
    bitwise-identical to the monolithic ring."""
    rank = lax.axis_index(axis_name)
    perm = _ring_perm(n)

    # reduce-scatter: after n-1 steps, chunk (rank+1) mod n is complete
    def rs_step(chunks, k):
        send_idx = (rank - k) % n
        send = jnp.take(chunks, send_idx, axis=0)
        recv = lax.ppermute(send, axis_name, perm)
        recv_idx = (rank - k - 1) % n
        cur = jnp.take(chunks, recv_idx, axis=0)
        return lax.dynamic_update_index_in_dim(
            chunks, op(cur, recv), recv_idx, 0
        ), None

    chunks, _ = lax.scan(rs_step, chunks, jnp.arange(n - 1))

    # allgather: circulate completed chunks around the ring
    def ag_step(chunks, k):
        send_idx = (rank - k + 1) % n
        send = jnp.take(chunks, send_idx, axis=0)
        recv = lax.ppermute(send, axis_name, perm)
        recv_idx = (rank - k) % n
        return lax.dynamic_update_index_in_dim(chunks, recv, recv_idx, 0), None

    chunks, _ = lax.scan(ag_step, chunks, jnp.arange(n - 1))
    return chunks


def allreduce_ring(x: jax.Array, op: Op, axis_name: str, n: int) -> jax.Array:
    """Ring allreduce: reduce-scatter pass + allgather pass
    (coll_tuned_allreduce.c:361). Bandwidth-optimal: 2(n-1)/n · size
    over the ICI ring.
    """
    if n == 1:
        return x
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    total = flat.shape[0]
    chunk = -(-total // n)  # ceil
    ident = op.identity_for(dtype)
    chunks = _pad_to(flat, chunk * n, ident).reshape(n, chunk)
    chunks = _ring_passes(chunks, op, axis_name, n)
    return chunks.reshape(-1)[:total].reshape(shape).astype(dtype)


def allreduce_segmented_ring(x: jax.Array, op: Op, axis_name: str, n: int,
                             segsize_elems: int) -> jax.Array:
    """Segmented ring (coll_tuned_allreduce.c:636): the ring pipelined
    over ~1 MiB segments, bounding the per-step working set (VMEM
    pressure) for very large buffers.

    Reduction-order note: each segment is ring-reduced independently,
    so an element's summation order is fixed by its chunk index
    *within its segment*. That order is deterministic and pinned by
    ``tests/test_bitwise_parity.py`` — but it is NOT bitwise-identical
    to plain ring (whose chunk index derives from the whole buffer)
    except when the whole buffer fits one segment; a ring chunk's
    accumulation order inherently depends on its chunk index, so no
    segmentation can preserve plain-ring bit patterns.
    """
    if n == 1:
        return x
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    total = flat.shape[0]
    seg = max(segsize_elems, n)
    nseg = -(-total // seg)
    if nseg <= 1:
        return allreduce_ring(x, op, axis_name, n)
    ident = op.identity_for(dtype)
    padded = _pad_to(flat, nseg * seg, ident).reshape(nseg, seg)
    out = lax.map(
        lambda s: allreduce_ring(s, op, axis_name, n), padded
    )
    return out.reshape(-1)[:total].reshape(shape).astype(dtype)


def allreduce_basic_linear(x: jax.Array, op: Op, axis_name: str,
                           n: int) -> jax.Array:
    """Reference linear algorithm (coll/basic): gather-to-all + local
    sequential reduce in rank order — the parity yardstick: its
    reduction order is the canonical rank order."""
    g = lax.all_gather(x, axis_name, axis=0)
    acc = g[0]
    for i in range(1, n):
        acc = op(acc, g[i])
    return acc


def allreduce_nonoverlapping(x: jax.Array, op: Op, axis_name: str,
                             n: int, root: int = 0) -> jax.Array:
    """Reduce-to-root then bcast (tuned's nonoverlapping,
    coll_tuned_allreduce.c): the fallback for non-commutative ops at
    sizes where recursive doubling is too chatty."""
    red = reduce_binomial(x, op, axis_name, n, root)
    return bcast_binomial(red, axis_name, n, root)


# ---------------------------------------------------------------------------
# bcast / reduce
# ---------------------------------------------------------------------------

def bcast_binomial(x: jax.Array, axis_name: str, n: int,
                   root: int = 0) -> jax.Array:
    """Binomial-tree broadcast (coll_tuned_bcast.c): ceil(log2 n) rounds."""
    if n == 1:
        return x
    rank = lax.axis_index(axis_name)
    rank_of = lambda v: (v + root) % n
    v = (rank - root) % n  # virtual rank: root -> 0
    rounds = (n - 1).bit_length()
    for k in range(rounds):
        d = 1 << k
        perm = [
            (rank_of(vs), rank_of(vs + d)) for vs in range(min(d, n - d))
        ]
        recv = lax.ppermute(x, axis_name, perm)
        is_receiver = (v >= d) & (v < 2 * d)
        x = jnp.where(is_receiver, recv, x)
    return x


def bcast_binary_tree(x: jax.Array, axis_name: str, n: int,
                      root: int = 0) -> jax.Array:
    """Balanced-binary-tree broadcast (``coll_tuned_bcast.c``
    ``bcast_intra_bintree``; stands in for the intermediate-size
    split_bintree pick too — the split-halves+exchange trick
    optimizes bidirectional link use, which the XLA scheduler already
    owns on a compiled torus program, so the plain binary tree is the
    faithful structure here).  Depth ceil(log2(n+1)) levels; each
    level is two static ppermutes (left edges, right edges — one
    parent feeds two children, which a single permutation cannot
    express)."""
    if n == 1:
        return x
    rank = lax.axis_index(axis_name)
    rank_of = lambda vv: (vv + root) % n
    v = (rank - root) % n
    depth = n.bit_length()  # heap levels 0..depth-1
    for lvl in range(depth):
        for side in (1, 2):  # left child 2v+1, right child 2v+2
            perm = [
                (rank_of(vs), rank_of(2 * vs + side))
                for vs in range(n)
                if (vs + 1).bit_length() - 1 == lvl
                and 2 * vs + side < n
            ]
            if not perm:
                continue
            recv = lax.ppermute(x, axis_name, perm)
            # receivers: children of this level's parents — parity
            # identifies the side (left children odd, right even>0),
            # the static level bounds identify the depth
            child_par = (v % 2 == 1) if side == 1 else \
                (v % 2 == 0) & (v > 0)
            child_lvl = (v + 1 >= (1 << (lvl + 1))) & \
                (v + 1 < (1 << (lvl + 2)))
            x = jnp.where(child_par & child_lvl, recv, x)
    return x


def bcast_chain(x: jax.Array, axis_name: str, n: int,
                root: int = 0) -> jax.Array:
    """Chain broadcast (``coll_tuned_bcast.c`` chain fanout=1): the
    value forwards rank-to-rank, n-1 hops."""
    if n == 1:
        return x
    rank = lax.axis_index(axis_name)
    rank_of = lambda v: (v + root) % n
    v = (rank - root) % n
    for hop in range(n - 1):
        perm = [(rank_of(hop), rank_of(hop + 1))]
        recv = lax.ppermute(x, axis_name, perm)
        x = jnp.where(v == hop + 1, recv, x)
    return x


def bcast_pipeline(x: jax.Array, axis_name: str, n: int, root: int,
                   seg_elems: int) -> jax.Array:
    """Pipelined (segmented chain) broadcast (``coll_tuned_bcast.c``
    ``bcast_intra_pipeline``): the flat buffer splits into S segments
    that stream down the rank chain, one hop per tick — S + n - 2
    ticks total, the GPipe schedule shape (parallel/pp.py uses the
    same loop).  Segment s reaches vrank v at tick s + v; every tick
    is ONE static ppermute of a segment-sized buffer plus traced
    dynamic slicing."""
    if n == 1:
        return x
    flat = x.reshape(-1)
    total = flat.shape[0]
    S = max(1, -(-total // max(1, seg_elems)))
    pad = S * seg_elems - total
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    segs = flat.reshape(S, seg_elems)
    rank = lax.axis_index(axis_name)
    v = (rank - root) % n
    perm = [((i + root) % n, (i + 1 + root) % n) for i in range(n - 1)]

    def tick(t, buf):
        # each rank forwards the segment it received at tick t-1:
        # rank v sends segment t - v (if it holds it)
        sidx = jnp.clip(t - v, 0, S - 1)
        outgoing = jnp.take(buf, sidx, axis=0)
        recv = lax.ppermute(outgoing, axis_name, perm)
        # receiver v stores segment t - (v - 1) at that index
        ridx = jnp.clip(t - (v - 1), 0, S - 1)
        valid = (t - (v - 1) >= 0) & (t - (v - 1) < S) & (v > 0)
        cur = jnp.take(buf, ridx, axis=0)
        new = jnp.where(valid, recv, cur)
        return lax.dynamic_update_index_in_dim(buf, new, ridx, 0)

    segs = lax.fori_loop(0, S + n - 2, tick, segs)
    out = segs.reshape(-1)[:total]
    return out.reshape(x.shape)


def bcast_masked_psum(x: jax.Array, op_dtype, axis_name: str,
                      root: int = 0) -> jax.Array:
    """One-collective bcast: zero all non-root contributions and psum.

    Integer-exact; float-exact too (adding zeros), except it does not
    preserve -0.0 vs +0.0 distinctions. Used by the xla component where
    a single fused collective beats log-round trees.
    """
    rank = lax.axis_index(axis_name)
    contrib = jnp.where(rank == root, x, jnp.zeros_like(x))
    if jnp.issubdtype(x.dtype, jnp.floating) or jnp.issubdtype(
        x.dtype, jnp.complexfloating
    ) or jnp.issubdtype(x.dtype, jnp.integer):
        return lax.psum(contrib, axis_name)
    # bool etc: max works as OR-select
    return lax.pmax(contrib.astype(jnp.int32), axis_name).astype(x.dtype)


def reduce_binomial(x: jax.Array, op: Op, axis_name: str, n: int,
                    root: int = 0) -> jax.Array:
    """Binomial-tree reduce toward root; non-root ranks end with
    partial values (MPI leaves their recv buffers undefined)."""
    if n == 1:
        return x
    rank = lax.axis_index(axis_name)
    vrank_of = lambda r: (r - root) % n
    rank_of = lambda v: (v + root) % n
    rounds = (n - 1).bit_length()
    v = vrank_of(rank)
    for k in range(rounds):
        d = 1 << k
        # senders: v where v mod 2d == d ; receivers: v - d
        perm = []
        for vs in range(d, n, 2 * d):
            perm.append((rank_of(vs), rank_of(vs - d)))
        recv = lax.ppermute(x, axis_name, perm)
        is_receiver = (v % (2 * d) == 0) & (v + d < n)
        x = jnp.where(is_receiver, op(x, recv), x)
    return x


def reduce_in_order_binary(x: jax.Array, op: Op, axis_name: str,
                           n: int, root: int = 0) -> jax.Array:
    """In-order binary-tree reduce (``coll_tuned_reduce.c``
    ``reduce_intra_in_order_binary``): the noncommutative-safe rooted
    reduce.  Unlike :func:`reduce_binomial` (whose root-relative
    vranks ROTATE the operand order when root != 0), this tree merges
    contiguous TRUE-rank ranges — every combine is
    ``op(lower range, upper range)``, so operands keep strict rank
    order 0..n-1; only the grouping is balanced (allowed: MPI requires
    associativity, never commutation).  The result lands on rank 0
    and takes one final hop to a non-zero root."""
    if n == 1:
        return x
    rank = lax.axis_index(axis_name)
    # at root 0, reduce_binomial's vranks ARE true ranks and its
    # op(lower, upper) combines are already contiguous-range in-order
    # merges — reuse that schedule, then hop to a non-zero root
    x = reduce_binomial(x, op, axis_name, n, root=0)
    if root != 0:
        moved = lax.ppermute(x, axis_name, [(0, root)])
        x = jnp.where(rank == root, moved, x)
    return jnp.where(rank == root, x, jnp.zeros_like(x))


def reduce_linear(x: jax.Array, op: Op, axis_name: str, n: int,
                  root: int = 0) -> jax.Array:
    """Linear reduce (``reduce_intra_basic_linear``): the canonical
    rank-order left fold of :func:`allreduce_basic_linear`, kept at
    root only — ONE definition of the strict sequential order."""
    acc = allreduce_basic_linear(x, op, axis_name, n)
    rank = lax.axis_index(axis_name)
    return jnp.where(rank == root, acc, jnp.zeros_like(acc))


# ---------------------------------------------------------------------------
# allgather / gather / scatter
# ---------------------------------------------------------------------------

def gather_linear(x: jax.Array, axis_name: str, n: int,
                  root: int = 0) -> jax.Array:
    """Linear gather (``coll_tuned_gather.c`` basic_linear; also the
    xla component's body): one fused allgather, root keeps it."""
    g = lax.all_gather(x, axis_name, axis=0)
    g = g.reshape((-1,) + g.shape[2:])
    rank = lax.axis_index(axis_name)
    return jnp.where(rank == root, g, jnp.zeros_like(g))


def scatter_linear(x: jax.Array, axis_name: str, n: int,
                   root: int = 0) -> jax.Array:
    """Linear scatter (basic_linear; also the xla component's body):
    bcast root's buffer, take the own chunk."""
    full = bcast_masked_psum(x, x.dtype, axis_name, root)
    chunks = full.reshape((n, -1) + full.shape[1:])
    rank = lax.axis_index(axis_name)
    return jnp.take(chunks, rank, axis=0)


def gather_binomial(x: jax.Array, axis_name: str, n: int,
                    root: int = 0) -> jax.Array:
    """Binomial-tree gather (``coll_tuned_gather.c``
    ``gather_intra_binomial``): log2(n) rounds; at round k the ranks
    whose root-relative vrank has LOWEST set bit k forward their
    accumulated k-block range to vrank - k.  Each round moves exactly
    k blocks (STATIC slice size at a traced, clamped base — true
    binomial volume, not a full-buffer echo); clamped window entries
    outside the sender's own range are masked to zero and receivers
    merge additively into a read-modify-write of the same window, so
    non-power-of-two edge ranks stay correct.  Non-root ranks end
    masked to zeros (MPI leaves them undefined).  Returns (n*block,)
    on root's slice, rank order."""
    rank = lax.axis_index(axis_name)
    v = (rank - root) % n
    out = jnp.zeros((n,) + x.shape, x.dtype)
    out = lax.dynamic_update_index_in_dim(out, x, v, 0)
    k = 1
    while k < n:
        is_sender = (v & (2 * k - 1)) == k  # lowest set bit == k
        s_send = jnp.minimum(v, n - k)      # clamped own-range base
        window = lax.dynamic_slice_in_dim(out, s_send, k, 0)
        valid = ((s_send + jnp.arange(k)) >= v).reshape(
            (k,) + (1,) * (out.ndim - 1))
        contrib = jnp.where(is_sender & valid, window,
                            jnp.zeros_like(window))
        # only the true sender set is on the wire (the sender set is
        # static in vrank space): non-listed ranks ship NOTHING and
        # non-targets receive zeros — k blocks per edge, (n/2k) edges,
        # the real binomial volume
        rank_of = lambda vv: (vv + root) % n
        perm = [(rank_of(vs), rank_of(vs - k))
                for vs in range(n) if (vs & (2 * k - 1)) == k]
        recv = lax.ppermute(contrib, axis_name, perm)
        # the child's base min(v_child, n-k) = min(v + k, n - k)
        s_recv = jnp.minimum(v + k, n - k)
        cur = lax.dynamic_slice_in_dim(out, s_recv, k, 0)
        out = lax.dynamic_update_slice_in_dim(out, cur + recv,
                                              s_recv, 0)
        k *= 2
    # vrank-space -> rank order: result[i] = out[(i - root) % n];
    # root is STATIC, so this is a static roll
    out = jnp.roll(out, shift=root, axis=0)
    flat = out.reshape((-1,) + x.shape[1:])
    return jnp.where(rank == root, flat, jnp.zeros_like(flat))


def scatter_binomial(x: jax.Array, axis_name: str, n: int,
                     root: int = 0) -> jax.Array:
    """Binomial-tree scatter (``coll_tuned_scatter.c``
    ``scatter_intra_binomial``): the mirror of binomial gather —
    root starts with all n blocks; at round k (descending) every
    range holder passes its upper-half k blocks to vrank + k, again
    as a STATIC-size slice at a clamped traced base with masked
    overlap and additive merge (k blocks per round, true binomial
    volume).  ``x`` is the root's (n*block,) buffer; returns own
    block."""
    rank = lax.axis_index(axis_name)
    v = (rank - root) % n
    blocks = x.reshape((n,) + (x.shape[0] // n,) + x.shape[1:])
    # vrank-index the buffer (static roll by -root) and zero non-root
    buf = jnp.where(rank == root,
                    jnp.roll(blocks, shift=-root, axis=0),
                    jnp.zeros_like(blocks))
    k = 1 << max(0, (n - 1).bit_length() - 1)
    while k >= 1:
        # the child vrank v + k must exist (non-power-of-two n)
        is_sender = ((v % (2 * k)) == 0) & (v + k < n)
        s_send = jnp.minimum(v + k, n - k)  # upper-half base, clamped
        window = lax.dynamic_slice_in_dim(buf, s_send, k, 0)
        valid = ((s_send + jnp.arange(k)) >= v + k).reshape(
            (k,) + (1,) * (buf.ndim - 1))
        contrib = jnp.where(is_sender & valid, window,
                            jnp.zeros_like(window))
        # static sender set only (see gather_binomial): true binomial
        # wire volume
        rank_of = lambda vv: (vv + root) % n
        perm = [(rank_of(vs), rank_of(vs + k))
                for vs in range(n)
                if vs % (2 * k) == 0 and vs + k < n]
        recv = lax.ppermute(contrib, axis_name, perm)
        # own-range base: the parent's upper half IS [v, v + k)
        s_recv = jnp.minimum(v, n - k)
        cur = lax.dynamic_slice_in_dim(buf, s_recv, k, 0)
        buf = lax.dynamic_update_slice_in_dim(buf, cur + recv,
                                              s_recv, 0)
        k //= 2
    return jnp.take(buf, v, axis=0)


def allgather_lax(x: jax.Array, axis_name: str) -> jax.Array:
    return lax.all_gather(x, axis_name, axis=0)


def allgather_bruck(x: jax.Array, axis_name: str, n: int) -> jax.Array:
    """Bruck allgather (``coll_tuned_allgather.c``
    ``allgather_intra_bruck``): ceil(log2 n) doubling rounds for ANY
    n, then a final rotation.

    Local position i holds block (rank + i) mod n throughout; round k
    appends ``min(cnt, n - cnt)`` blocks received from rank + cnt, so
    every round's slice sizes are STATIC (the python loop unrolls into
    the compiled program) while the final re-index by rank is the only
    traced-value gather."""
    rank = lax.axis_index(axis_name)
    out = jnp.zeros((n,) + x.shape, x.dtype)
    out = lax.dynamic_update_index_in_dim(out, x, 0, 0)
    cnt = 1
    while cnt < n:
        send_cnt = min(cnt, n - cnt)
        # data flows r -> r - cnt (mod n): each rank receives the
        # leading send_cnt blocks of rank + cnt, which are that
        # rank's blocks (rank + cnt + j) = our blocks cnt + j
        perm = [(i, (i - cnt) % n) for i in range(n)]
        recv = lax.ppermute(out[:send_cnt], axis_name, perm)
        out = lax.dynamic_update_slice_in_dim(out, recv, cnt, axis=0)
        cnt += send_cnt
    # local order is (rank, rank+1, ...): rotate to index order
    idx = (jnp.arange(n) - rank) % n
    return jnp.take(out, idx, axis=0)


def allgather_recursive_doubling(x: jax.Array, axis_name: str,
                                 n: int) -> jax.Array:
    """Recursive-doubling allgather (``coll_tuned_allgather.c``
    ``allgather_intra_recursivedoubling``): power-of-two n only, like
    the reference (callers decline otherwise). After round k every
    rank holds its 2^(k+1)-aligned group's blocks at their NATURAL
    indices, so no final rotation is needed; the per-round exchanged
    region has static size 2^k at a traced (rank-aligned) base."""
    if n & (n - 1):
        raise ValueError(f"recursive-doubling allgather needs "
                         f"power-of-two ranks, got {n}")
    rank = lax.axis_index(axis_name)
    out = jnp.zeros((n,) + x.shape, x.dtype)
    out = lax.dynamic_update_index_in_dim(out, x, rank, 0)
    k = 1
    while k < n:
        base = (rank // k) * k  # start of our filled k-block group
        mine = lax.dynamic_slice_in_dim(out, base, k, axis=0)
        perm = [(i, i ^ k) for i in range(n)]
        recv = lax.ppermute(mine, axis_name, perm)
        # partner's group sits at the bit-k mirrored base
        out = lax.dynamic_update_slice_in_dim(out, recv, base ^ k,
                                              axis=0)
        k *= 2
    return out


def allgather_ring(x: jax.Array, axis_name: str, n: int) -> jax.Array:
    """Neighbor-exchange ring allgather (coll_tuned_allgather.c ring)."""
    rank = lax.axis_index(axis_name)
    out = jnp.zeros((n,) + x.shape, x.dtype)
    out = lax.dynamic_update_index_in_dim(out, x, rank, 0)
    perm = _ring_perm(n)

    def step(carry, k):
        out, cur = carry
        recv = lax.ppermute(cur, axis_name, perm)
        idx = (rank - k - 1) % n
        out = lax.dynamic_update_index_in_dim(out, recv, idx, 0)
        return (out, recv), None

    (out, _), _ = lax.scan(step, (out, x), jnp.arange(n - 1))
    return out


# a 32-bit TPU array is tiled (8, 128): a 1-D block of whole tiles
# lies in memory exactly as its (rows, 128) view does
_LANES = 128
_TILE = 8 * _LANES


def reduce_scatter_lax(x: jax.Array, op: Op, axis_name: str,
                       n: int) -> jax.Array:
    """reduce_scatter_block: x is (n*chunk, ...) per rank; rank i gets
    the reduced i-th chunk. SUM runs psum_scatter tiled on the block as
    it lies: a (n, chunk) view of it is a relayout on the TPU. A 1-D
    block of whole tiles goes as 128-lane rows, the same bytes, which
    XLA:TPU reduce-scatters; on the flat vector it runs a whole
    all-reduce and slices it (PERF.md §5)."""
    chunk = x.shape[0] // n
    if op.lax_collective == "psum":
        rows = (x.reshape(-1, _LANES)
                if x.ndim == 1 and x.shape[0] % (n * _TILE) == 0 else x)
        out = lax.psum_scatter(rows, axis_name, scatter_dimension=0,
                               tiled=True)
        return out.reshape((chunk,) + x.shape[1:])
    blocks = x.reshape((n, chunk) + x.shape[1:])
    # generic: allreduce then take own chunk
    red = allreduce_lax(blocks, op, axis_name)
    rank = lax.axis_index(axis_name)
    return jnp.take(red, rank, axis=0)


def reduce_scatter_ring(x: jax.Array, op: Op, axis_name: str,
                        n: int) -> jax.Array:
    """Ring reduce-scatter (the first phase of ring allreduce)."""
    if n == 1:
        return x
    rank = lax.axis_index(axis_name)
    chunk = x.shape[0] // n
    chunks = x.reshape((n, chunk) + x.shape[1:])
    perm = _ring_perm(n)

    def rs_step(chunks, k):
        # indices chosen so chunk c completes exactly at rank c
        send_idx = (rank - k - 1) % n
        send = jnp.take(chunks, send_idx, axis=0)
        recv = lax.ppermute(send, axis_name, perm)
        recv_idx = (rank - k - 2) % n
        cur = jnp.take(chunks, recv_idx, axis=0)
        return lax.dynamic_update_index_in_dim(
            chunks, op(cur, recv), recv_idx, 0
        ), None

    chunks, _ = lax.scan(rs_step, chunks, jnp.arange(n - 1))
    return jnp.take(chunks, rank, axis=0)


# ---------------------------------------------------------------------------
# alltoall
# ---------------------------------------------------------------------------

def alltoall_lax(x: jax.Array, axis_name: str, n: int) -> jax.Array:
    """x: (n, chunk...) per rank; out[j] = what rank j sent me."""
    return lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)


def alltoall_bruck(blocks: jax.Array, axis_name: str, n: int) -> jax.Array:
    """Bruck alltoall (``coll_tuned_alltoall.c``
    ``alltoall_intra_bruck``): log2(n) store-and-forward phases moving
    n/2 blocks each — latency-optimal for small blocks at large n,
    at the cost of forwarding.

    Invariant: after the initial rotation, position j at rank r holds
    a block destined to rank r + j; phase k moves every position
    whose index has bit k set FORWARD by k ranks (stored at the same
    position), so a block starting at offset j arrives after its
    set-bit hops exactly at its destination, at position j.  The
    phase masks are STATIC (python loop, static index lists); only
    the first/last rotations index by the traced rank."""
    rank = lax.axis_index(axis_name)
    idx = (rank + jnp.arange(n)) % n
    local = jnp.take(blocks, idx, axis=0)  # local[j] -> dest rank+j
    k = 1
    while k < n:
        idxs = [j for j in range(n) if j & k]
        sel = local[jnp.array(idxs)]
        perm = [(i, (i + k) % n) for i in range(n)]
        recv = lax.ppermute(sel, axis_name, perm)
        local = local.at[jnp.array(idxs)].set(recv)
        k *= 2
    # position j now holds the block FROM rank - j (destined here)
    out_idx = (rank - jnp.arange(n)) % n
    return jnp.take(local, out_idx, axis=0)


def alltoall_pairwise(x: jax.Array, axis_name: str, n: int) -> jax.Array:
    """Pairwise-exchange alltoall (coll_tuned_alltoall.c pairwise):
    n-1 rounds; round k exchanges with rank±k."""
    rank = lax.axis_index(axis_name)
    out = jnp.zeros_like(x)
    # own block stays
    own = jnp.take(x, rank, axis=0)
    out = lax.dynamic_update_index_in_dim(out, own, rank, 0)
    for k in range(1, n):
        dst = [(i, (i + k) % n) for i in range(n)]
        # send the block destined for rank+k
        send = jnp.take(x, (rank + k) % n, axis=0)
        recv = lax.ppermute(send, axis_name, dst)
        src = (rank - k) % n
        out = lax.dynamic_update_index_in_dim(out, recv, src, 0)
    return out


# ---------------------------------------------------------------------------
# scan / barrier
# ---------------------------------------------------------------------------

def scan_recursive_doubling(x: jax.Array, op: Op, axis_name: str,
                            n: int, exclusive: bool = False) -> jax.Array:
    """Inclusive/exclusive prefix reduction over ranks (MPI_Scan/Exscan),
    log2-round recursive doubling (libnbc's iscan schedule shape)."""
    rank = lax.axis_index(axis_name)
    acc = x
    d = 1
    while d < n:
        perm = [(i, i + d) for i in range(n - d)]
        recv = lax.ppermute(acc, axis_name, perm)
        use = rank >= d
        acc = jnp.where(use, op(recv, acc), acc)
        d *= 2
    if not exclusive:
        return acc
    # exscan: shift inclusive results up by one rank; rank 0 undefined -> 0
    perm = [(i, i + 1) for i in range(n - 1)]
    shifted = lax.ppermute(acc, axis_name, perm)
    return jnp.where(rank == 0, jnp.zeros_like(acc), shifted)


def allreduce_two_level(x: jax.Array, op: Op, intra_axis: str,
                        inter_axis: str, intra_n: int) -> jax.Array:
    """Hierarchical allreduce (coll/ml + bcol + sbgp analogue,
    SURVEY §2.3): reduce-scatter inside the fast domain (ICI slice /
    shared-memory node), allreduce the owned chunk across the slow
    domain (DCN / inter-node), allgather back inside.

    Inter-domain traffic drops to 1/intra_n of the payload — exactly
    why the reference builds ml on top of per-level bcol primitives.
    """
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    total = flat.shape[0]
    chunk = -(-total // intra_n)
    ident = op.identity_for(dtype)
    padded = _pad_to(flat, chunk * intra_n, ident)

    # level 1: reduce-scatter within the fast domain (takes the flat
    # buffer and blocks it internally)
    mine = reduce_scatter_ring(padded, op, intra_axis, intra_n)
    # level 2: allreduce owned chunks across the slow domain
    mine = allreduce_lax(mine, op, inter_axis)
    # level 3: allgather within the fast domain
    out = lax.all_gather(mine, intra_axis, axis=0, tiled=True)
    return out[:total].reshape(shape).astype(dtype)


def bcast_two_level(x: jax.Array, intra_axis: str, inter_axis: str,
                    root: int, intra_n: int) -> jax.Array:
    """Hierarchical bcast: root -> its inter peers (one per fast
    domain) -> everyone inside each fast domain."""
    root_node, root_local = divmod(root, intra_n)
    # select root's value, then one fused two-level masked reduction
    rank_local = lax.axis_index(intra_axis)
    rank_node = lax.axis_index(inter_axis)
    is_root = (rank_node == root_node) & (rank_local == root_local)
    contrib = jnp.where(is_root, x, jnp.zeros_like(x))
    # one fused reduction over both axes delivers the bcast
    return lax.psum(lax.psum(contrib, intra_axis), inter_axis)


def reduce_two_level(x: jax.Array, op: Op, intra_axis: str,
                     inter_axis: str, root: int, intra_n: int
                     ) -> jax.Array:
    """Hierarchical rooted reduce: the two-level allreduce (which
    already cuts inter-domain traffic to 1/intra_n) masked to the
    root's position — the ml compose of bcol reduce primitives."""
    red = allreduce_two_level(x, op, intra_axis, inter_axis, intra_n)
    root_node, root_local = divmod(root, intra_n)
    is_root = ((lax.axis_index(inter_axis) == root_node)
               & (lax.axis_index(intra_axis) == root_local))
    return jnp.where(is_root, red, jnp.zeros_like(red))


def allgather_two_level(x: jax.Array, intra_axis: str, inter_axis: str
                        ) -> jax.Array:
    """Hierarchical allgather: gather inside the fast domain first,
    then exchange the per-domain aggregates across the slow domain —
    inter-domain messages carry whole-domain blocks (intra_n ranks per
    message instead of one), the recursive-doubling-on-aggregates
    shape of ml's allgather. Returns (n, chunk...) in rank order
    (rank = node * intra_n + local, node-major like run_sharded2d)."""
    g_local = lax.all_gather(x, intra_axis, axis=0)   # (intra_n, ...)
    g = lax.all_gather(g_local, inter_axis, axis=0)   # (inter_n, intra_n, ...)
    return g.reshape((-1,) + g.shape[2:])


def reduce_scatter_two_level(x: jax.Array, op: Op, intra_axis: str,
                             inter_axis: str, intra_n: int, n: int
                             ) -> jax.Array:
    """Hierarchical reduce_scatter_block: two-level allreduce, then
    each rank keeps its own chunk. Inter traffic = the allreduce's
    1/intra_n-reduced volume."""
    red = allreduce_two_level(x, op, intra_axis, inter_axis, intra_n)
    rank = (lax.axis_index(inter_axis) * intra_n
            + lax.axis_index(intra_axis))
    chunks = red.reshape((n, -1) + red.shape[1:])
    return jnp.take(chunks, rank, axis=0)


def alltoall_two_level(blocks: jax.Array, intra_axis: str,
                       inter_axis: str, intra_n: int, inter_n: int
                       ) -> jax.Array:
    """Hierarchical alltoall: factor the all-pairs exchange into an
    inter-domain alltoall of whole-domain super-blocks followed by an
    intra-domain alltoall — each slow-domain message aggregates
    intra_n**2 rank-pair blocks (the xhc/ml aggregation idea).

    ``blocks``: (n, chunk...) — row j is this rank's block for comm
    rank j (node-major rank order). Returns (n, chunk...) with row i =
    the block rank i sent to this rank.
    """
    b = blocks.reshape((inter_n, intra_n) + blocks.shape[1:])
    # exchange super-blocks across nodes: dim0 becomes SOURCE node
    b = lax.all_to_all(b, inter_axis, split_axis=0, concat_axis=0)
    # exchange within the fast domain: dim1 becomes SOURCE local rank
    b = lax.all_to_all(b, intra_axis, split_axis=1, concat_axis=1)
    return b.reshape(blocks.shape)


def barrier_psum(axis_name: str) -> jax.Array:
    """Barrier = 0-byte allreduce; completion of the program is the sync."""
    return lax.psum(jnp.zeros((), jnp.int32), axis_name)
