"""coll components: ``xla`` (compiler-scheduled), ``tuned`` (named
algorithms + decision rules), ``basic`` (linear reference), ``self``
(size-1 fast path).

Priorities mirror the reference's layering logic: the hardware-offload
component outranks tuned outranks basic (reference: fca/hcoll > tuned 30
> basic 10), and ``self`` claims only size-1 communicators
(``ompi/mca/coll/self``).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..mca import component as mca_component
from ..mca import var as mca_var
from ..ops.op import Op
from ..utils import output
from . import dynamic_rules, hier_schedules, pipeline, spmd  # noqa: F401
from .base import COLL_FRAMEWORK
from .driver import run_sharded

_log = output.stream("coll")

AXIS = "rank"  # every comm submesh uses this axis name


def _per_rank_bytes(x) -> int:
    per_rank = x[0] if hasattr(x, "shape") else x
    return int(per_rank.size * per_rank.dtype.itemsize)


def _resolve_op(op: Op, x) -> Op:
    """Accelerated-kernel resolution for the local-reduction step of a
    hand-scheduled algorithm (the ``ompi/mca/op`` select): the pallas
    component claims large contiguous f32/bf16 SUMs, everything else
    stays on the XLA combiner. Resolution returns a DISTINCT op object
    (``sum[pallas]``), so the compiled-program cache keys — which embed
    the op itself — never mix the two kernels."""
    from ..ops import op as op_mod

    if op.is_pair_op or not hasattr(x, "dtype"):
        return op
    return op_mod.resolve(op, x.dtype, _per_rank_bytes(x))


# ---------------------------------------------------------------------------
# xla component — lower straight to XLA collectives
# ---------------------------------------------------------------------------

class _XlaModule:
    """Collectives as single fused XLA ops; the compiler plans the ICI
    schedule. This is the default data plane (BASELINE.json coll/xla)."""

    def __init__(self, comm) -> None:
        self.comm = comm

    def fns(self) -> Dict[str, Callable]:
        return {
            "allreduce": self.allreduce,
            "reduce": self.reduce,
            "bcast": self.bcast,
            "allgather": self.allgather,
            "gather": self.gather,
            "scatter": self.scatter,
            "reduce_scatter_block": self.reduce_scatter_block,
            "alltoall": self.alltoall,
            "scan": self.scan,
            "exscan": self.exscan,
            "barrier": self.barrier,
            "ibarrier": self.ibarrier,
            "alltoallv": self.alltoallv,
            "allgatherv": self.allgatherv,
            "gatherv": self.gatherv,
            "scatterv": self.scatterv,
            "reduce_scatter": self.reduce_scatter,
        }

    # each driver fn: key identifies the compiled program; all static
    # parameters must be part of the key — the op as an OBJECT (frozen,
    # hashable): keying by name would hand a same-named user op another
    # op's baked-in combiner
    def allreduce(self, comm, x, op: Op):
        if op.is_pair_op:
            vals, idxs = x
            return run_sharded(
                comm, ("xla", "allreduce_pair", op),
                lambda v, i: spmd.allreduce_pair_lax(v, i, op, AXIS),
                vals, extra_arrays=(idxs,),
            )
        return run_sharded(
            comm, ("xla", "allreduce", op),
            lambda xb: spmd.allreduce_lax(xb, op, AXIS), x,
        )

    def reduce(self, comm, x, op: Op, root: int):
        if op.is_pair_op:
            # MPI_Reduce with MINLOC/MAXLOC — THE canonical pair-op
            # call (global extremum + its location at the root)
            vals, idxs = x

            def pair_body(vb, ib):
                rv, ri = spmd.allreduce_pair_lax(vb, ib, op, AXIS)
                rank = lax.axis_index(AXIS)
                return (jnp.where(rank == root, rv, jnp.zeros_like(rv)),
                        jnp.where(rank == root, ri, jnp.zeros_like(ri)))

            return run_sharded(
                comm, ("xla", "reduce_pair", op, root),
                pair_body, vals, extra_arrays=(idxs,),
            )

        def body(xb):
            red = spmd.allreduce_lax(xb, op, AXIS)
            rank = lax.axis_index(AXIS)
            return jnp.where(rank == root, red, jnp.zeros_like(red))

        return run_sharded(comm, ("xla", "reduce", op, root), body, x)

    def bcast(self, comm, x, root: int):
        return run_sharded(
            comm, ("xla", "bcast", root),
            lambda xb: spmd.bcast_masked_psum(xb, xb.dtype, AXIS, root), x,
        )

    def allgather(self, comm, x):
        # tiled: the rank's (N, ...) block gathers straight into the
        # (nN, ...) result, 7 % faster on v5e than gathering (n, N, ...)
        # and reshaping it (PERF.md §6)
        return run_sharded(
            comm, ("xla", "allgather"),
            lambda xb: lax.all_gather(xb, AXIS, axis=0, tiled=True), x,
        )

    def gather(self, comm, x, root: int):
        return run_sharded(
            comm, ("xla", "gather", root),
            lambda xb: spmd.gather_linear(xb, AXIS, comm.size, root), x,
        )

    def scatter(self, comm, x, root: int):
        # x: root's slice holds n chunks back-to-back
        return run_sharded(
            comm, ("xla", "scatter", root),
            lambda xb: spmd.scatter_linear(xb, AXIS, comm.size, root), x,
        )

    def reduce_scatter_block(self, comm, x, op: Op):
        n = comm.size
        if op.is_pair_op:
            vals, idxs = x

            def pair_body(vb, ib):
                rv, ri = spmd.allreduce_pair_lax(vb, ib, op, AXIS)
                rank = lax.axis_index(AXIS)
                cv = rv.reshape((n, -1) + rv.shape[1:])
                ci = ri.reshape((n, -1) + ri.shape[1:])
                return (jnp.take(cv, rank, axis=0),
                        jnp.take(ci, rank, axis=0))

            return run_sharded(
                comm, ("xla", "rsb_pair", op),
                pair_body, vals, extra_arrays=(idxs,),
            )
        return run_sharded(
            comm, ("xla", "reduce_scatter_block", op),
            lambda xb: spmd.reduce_scatter_lax(xb, op, AXIS, n), x,
        )

    def alltoall(self, comm, x):
        # tiled on the rank's block: its j-th 1/n goes to rank j, and
        # rank j's block for us lands in the j-th 1/n
        return run_sharded(
            comm, ("xla", "alltoall"),
            lambda xb: lax.all_to_all(xb, AXIS, split_axis=0,
                                      concat_axis=0, tiled=True), x,
        )

    def scan(self, comm, x, op: Op, *, exclusive: bool = False):
        n = comm.size
        if op.is_pair_op:
            # MPI_Scan with MINLOC/MAXLOC: associative_scan runs the
            # pair combiner over the gathered (value, index) pytree;
            # the rank-0 exscan slice is zeros (MPI leaves it
            # undefined)
            vals, idxs = x

            def pair_body(vb, ib):
                gv = lax.all_gather(vb, AXIS, axis=0)
                gi = lax.all_gather(ib, AXIS, axis=0)
                sv, si = lax.associative_scan(op, (gv, gi), axis=0)
                rank = lax.axis_index(AXIS)
                if exclusive:
                    pv = jnp.take(sv, jnp.maximum(rank - 1, 0), axis=0)
                    pi = jnp.take(si, jnp.maximum(rank - 1, 0), axis=0)
                    return (jnp.where(rank == 0, jnp.zeros_like(pv), pv),
                            jnp.where(rank == 0, jnp.zeros_like(pi), pi))
                return (jnp.take(sv, rank, axis=0),
                        jnp.take(si, rank, axis=0))

            return run_sharded(
                comm, ("xla", "scan_pair", op, exclusive),
                pair_body, vals, extra_arrays=(idxs,),
            )
        # the gather-based scan stages the WHOLE comm's buffers on
        # every rank (O(n * size) memory): past the limit, decline so
        # the chain falls to tuned's recursive-doubling scan, which
        # keeps per-rank memory O(size)
        if _per_rank_bytes(x) > int(mca_var.get(
                "coll_xla_scan_gather_limit", 1 << 20)):
            return None

        def body(xb):
            g = lax.all_gather(xb, AXIS, axis=0)  # (n, ...)
            s = lax.associative_scan(op, g, axis=0)
            rank = lax.axis_index(AXIS)
            if exclusive:
                prev = jnp.take(
                    s, jnp.maximum(rank - 1, 0), axis=0
                )
                return jnp.where(
                    rank == 0, jnp.zeros_like(prev), prev
                )
            return jnp.take(s, rank, axis=0)

        return run_sharded(
            comm, ("xla", "scan", op, exclusive), body, x
        )

    def exscan(self, comm, x, op: Op):
        return self.scan(comm, x, op, exclusive=True)

    def barrier(self, comm):
        jax.block_until_ready(self.ibarrier(comm))

    def ibarrier(self, comm):
        """Nonblocking barrier: dispatch the compiled barrier program
        and return its (future) output WITHOUT blocking — the libnbc
        round schedule (``nbc.c``) is the compiled program itself and
        XLA's async dispatch is the progress engine. The caller wraps
        the result in a Request whose readiness is the array's."""
        return run_sharded(
            comm, ("xla", "barrier"),
            lambda xb: spmd.barrier_psum(AXIS) + xb,
            jnp.zeros((comm.size,), jnp.int32),
        )

    # -- v-variants (padded lax kernels, counts at the driver edge) --------
    def alltoallv(self, comm, sendbufs, sendcounts, **device_form):
        # device_form: recv_capacity — the buffer and the counts stay on
        # the chips (vcoll.alltoallv_device)
        from . import vcoll

        return vcoll.alltoallv(comm, sendbufs, sendcounts, kernel="lax",
                               **device_form)

    def allgatherv(self, comm, sendbufs):
        from . import vcoll

        return vcoll.allgatherv(comm, sendbufs, kernel="lax")

    def gatherv(self, comm, sendbufs, root: int):
        from . import vcoll

        return vcoll.gatherv(comm, sendbufs, root, kernel="lax")

    def scatterv(self, comm, sendbuf, counts, root: int):
        from . import vcoll

        return vcoll.scatterv(comm, sendbuf, counts, root)

    def reduce_scatter(self, comm, x, recvcounts, op: Op):
        from . import vcoll

        return vcoll.reduce_scatter(comm, x, recvcounts, op, kernel="lax")


class XlaCollComponent(mca_component.Component):
    NAME = "xla"
    PRIORITY = 100

    def register_vars(self) -> None:
        mca_var.register(
            "coll_xla_scan_gather_limit", "size", 1 << 20,
            "Per-rank bytes above which the xla scan/exscan (all_gather"
            " + associative_scan, O(n*size) staged per rank) defers to "
            "tuned's recursive-doubling scan",
        )

    def query(self, ctx=None):
        if ctx is None:
            return (self.priority, self)
        if getattr(ctx, "spans_processes", False):
            return None  # cross-process comms belong to coll/hier
        return (self.priority, _XlaModule(ctx))


# ---------------------------------------------------------------------------
# tuned component — named algorithms + fixed decision rules
# ---------------------------------------------------------------------------

ALLREDUCE_ALGORITHMS = (
    # mirror of the enum coll_tuned_allreduce.c:46-54
    "auto", "basic_linear", "nonoverlapping", "recursive_doubling",
    "ring", "segmented_ring",
)
BCAST_ALGORITHMS = (
    # coll_tuned_bcast.c menu; split_bintree maps to binary_tree (the
    # split-halves+exchange trick optimizes bidirectional link use,
    # which the XLA scheduler owns on a compiled program); basic_linear
    # is masked_psum's one-shot
    "auto", "binomial", "binary_tree", "chain", "pipeline",
    "masked_psum",
)
ALLGATHER_ALGORITHMS = (
    # mirror of coll_tuned_allgather.c's menu (two_procs is subsumed
    # by bruck at n=2 — one round, identical exchange; the
    # even-n neighbor_exchange large-message case maps to ring, whose
    # structure IS the neighbor pass — substitutions documented in
    # the decision fn)
    "auto", "ring", "bruck", "recursive_doubling", "lax",
)
ALLTOALL_ALGORITHMS = (
    # coll_tuned_alltoall.c menu: basic_linear (all exchanges posted
    # at once = the one-shot fused lax.all_to_all here; two_procs is
    # its n=2 case), bruck (log-phase store-and-forward), pairwise
    "auto", "pairwise", "bruck", "basic_linear", "lax",
)
# coll_tuned_{gather,scatter}.c menus; both linear_sync branches map
# to linear (the sync round-trip protects an eager receiver from
# overrun — no analogue in a compiled SPMD exchange)
GATHER_ALGORITHMS = ("auto", "binomial", "linear")
SCATTER_ALGORITHMS = ("auto", "binomial", "linear")
# coll_tuned_reduce.c menu: binomial (commutative; the segmented
# binomial/pipeline picks keep its structure — segmentation is the
# compiler's domain in a compiled program), in_order_binary
# (noncommutative-safe contiguous-rank-range tree), linear (strict
# left fold)
REDUCE_ALGORITHMS = ("auto", "binomial", "in_order_binary", "linear")

# the collectives a dynamic rule file may target, with their legal
# algorithm names (consumed by coll/dynamic_rules.py at load time)
dynamic_rules.RULE_COLLECTIVES.update({
    "allreduce": ALLREDUCE_ALGORITHMS,
    "bcast": BCAST_ALGORITHMS,
    "allgather": ALLGATHER_ALGORITHMS,
    "alltoall": ALLTOALL_ALGORITHMS,
    "reduce": REDUCE_ALGORITHMS,
    "gather": GATHER_ALGORITHMS,
    "scatter": SCATTER_ALGORITHMS,
})
# (the hier_<coll> namespaces — the INTER-process schedules of
# spanning collectives — register themselves in coll/hier_schedules,
# which imports standalone; see hier_schedules.ALGORITHMS)


class _TunedModule:
    """Hand-written ppermute schedules with tuned's decision rules.

    Decision constants are the reference's
    (``coll_tuned_decision_fixed.c:51-83``): <10 kB → recursive
    doubling; commutative && count > comm_size → ring, segmented ring
    past comm_size × 1 MiB; otherwise nonoverlapping.
    """

    def __init__(self, comm) -> None:
        self.comm = comm

    def fns(self) -> Dict[str, Callable]:
        return {
            "allreduce": self.allreduce,
            "bcast": self.bcast,
            "reduce": self.reduce,
            "allgather": self.allgather,
            "gather": self.gather,
            "scatter": self.scatter,
            "reduce_scatter_block": self.reduce_scatter_block,
            "alltoall": self.alltoall,
            "scan": self.scan,
            "exscan": self.exscan,
            "barrier": self.barrier,
            "alltoallv": self.alltoallv,
            "allgatherv": self.allgatherv,
            "gatherv": self.gatherv,
            "scatterv": self.scatterv,
            "reduce_scatter": self.reduce_scatter,
        }

    # -- allreduce --------------------------------------------------------
    def _pick_allreduce(self, x, op: Op) -> str:
        forced = mca_var.get("coll_tuned_allreduce_algorithm", "auto")
        if forced != "auto":
            return forced
        n = self.comm.size
        count = x[0].size
        block_dsize = _per_rank_bytes(x)
        dyn = dynamic_rules.lookup("allreduce", n, block_dsize)
        if dyn is not None:
            if dyn in ("ring", "segmented_ring") and (
                    not op.commutative or op.identity is None):
                # a rule file cannot waive MPI semantics (same guard
                # as reduce below): ring's reduce-scatter folds chunks
                # in rotating ring order and pads with the identity —
                # downgrade to the rank-ordered fallback
                dyn = "nonoverlapping"
            return dyn
        if block_dsize < mca_var.get("coll_tuned_small_message", 10000):
            return "recursive_doubling"
        if op.commutative and count > n and op.identity is not None:
            seg = mca_var.get("coll_tuned_segment_size", 1 << 20)
            if n * seg >= block_dsize:
                return "ring"
            return "segmented_ring"
        return "nonoverlapping"

    def allreduce(self, comm, x, op: Op):
        if op.is_pair_op:
            return None  # pair ops stay with xla's gather path
        alg = self._pick_allreduce(x, op)
        if alg in ("ring", "segmented_ring") and (
                not op.commutative or op.identity is None):
            # mirrors reduce()'s order-invariant enforcement: the fixed
            # constants never pick ring here and a dynamic rule is
            # downgraded in the picker, so this catches operator forcing
            from ..utils.errors import ErrorCode, MPIError

            raise MPIError(
                ErrorCode.ERR_ARG,
                "ring allreduce folds chunks in rotating ring order and "
                "pads with the op identity; use nonoverlapping or "
                "recursive_doubling for this op",
            )
        op = _resolve_op(op, x)  # accelerated local-reduction kernel
        n = comm.size
        segsize = mca_var.get("coll_tuned_segment_size", 1 << 20)
        seg_elems = max(1, segsize // x.dtype.itemsize)
        bodies = {
            "basic_linear": lambda xb: spmd.allreduce_basic_linear(
                xb, op, AXIS, n
            ),
            "nonoverlapping": lambda xb: spmd.allreduce_nonoverlapping(
                xb, op, AXIS, n
            ),
            "recursive_doubling": lambda xb: spmd.allreduce_recursive_doubling(
                xb, op, AXIS, n
            ),
            "ring": lambda xb: spmd.allreduce_ring(xb, op, AXIS, n),
            "segmented_ring": lambda xb: spmd.allreduce_segmented_ring(
                xb, op, AXIS, n, seg_elems
            ),
        }
        if alg == "ring":
            # pipelined segmentation (coll/pipeline.py): above the
            # segsize the ring runs as double-buffered column segments
            # of the same chunk matrix — bitwise-identical to the
            # monolithic ring, keyed by segment count in the plan cache
            block_dsize = _per_rank_bytes(x)
            nseg = pipeline.segment_count("allreduce", n, block_dsize)
            if nseg > 1:
                _log.verbose(3, f"{comm.name}: tuned allreduce -> "
                                f"ring pipelined x{nseg}")
                return pipeline.run_pipelined(
                    comm, ("tuned", "allreduce", "ring", op),
                    lambda xb: pipeline.allreduce_ring_pipelined(
                        xb, op, AXIS, n, nseg),
                    x, nseg=nseg, nbytes=block_dsize,
                    opname="allreduce",
                )
        _log.verbose(3, f"{comm.name}: tuned allreduce -> {alg}")
        # the segment size is baked into the compiled program, so it
        # must be part of the cache key or later var changes would be
        # silently ignored
        key = ("tuned", "allreduce", alg, op) + (
            (seg_elems,) if alg == "segmented_ring" else ()
        )
        return run_sharded(comm, key, bodies[alg], x)

    # -- others -----------------------------------------------------------
    def _pick_bcast(self, x) -> tuple:
        """coll_tuned_decision_fixed.c bcast_intra_dec_fixed: < 2048 B
        -> binomial; < 370728 B -> split_bintree@1k (binary_tree
        here); larger -> pipeline with the segment size chosen by the
        reference's regression lines (128/64/16/8 KiB as the comm
        grows relative to a_pXX * msg + b_pXX). Returns
        (algorithm, segment_bytes)."""
        forced = mca_var.get("coll_tuned_bcast_algorithm", "auto")
        if forced != "auto":
            return forced, int(mca_var.get(
                "coll_tuned_bcast_segment_size", 128 << 10))
        n = self.comm.size
        msg = _per_rank_bytes(x)
        dyn = dynamic_rules.lookup("bcast", n, msg)
        if dyn is not None:
            return dyn, int(mca_var.get(
                "coll_tuned_bcast_segment_size", 128 << 10))
        if msg < 2048:
            return "binomial", 0
        if msg < 370728:
            return "binary_tree", 1 << 10
        if n < 1.6134e-6 * msg + 2.1102:   # a_p128/b_p128
            return "pipeline", 128 << 10
        if n < 13:
            return "binary_tree", 8 << 10
        if n < 2.3679e-6 * msg + 1.1787:   # a_p64/b_p64
            return "pipeline", 64 << 10
        if n < 3.2118e-6 * msg + 8.7936:   # a_p16/b_p16
            return "pipeline", 16 << 10
        return "pipeline", 8 << 10

    def bcast(self, comm, x, root: int):
        alg, segbytes = self._pick_bcast(x)
        n = comm.size
        # floor at one element: a misconfigured segment size of 0
        # must degrade to per-element streaming, not a negative-pad
        # reshape crash inside the kernel
        seg_elems = max(1, segbytes // x.dtype.itemsize) \
            if hasattr(x, "dtype") else 1
        bodies = {
            "binomial": lambda xb: spmd.bcast_binomial(xb, AXIS, n, root),
            "binary_tree": lambda xb: spmd.bcast_binary_tree(
                xb, AXIS, n, root),
            "chain": lambda xb: spmd.bcast_chain(xb, AXIS, n, root),
            "pipeline": lambda xb: spmd.bcast_pipeline(
                xb, AXIS, n, root, seg_elems),
            "masked_psum": lambda xb: spmd.bcast_masked_psum(
                xb, xb.dtype, AXIS, root),
        }
        if alg == "binomial" and hasattr(x, "dtype"):
            # segmented binomial bcast (coll/pipeline.py): trivially
            # bitwise-equal (no reduction); segments double-buffer
            # down the tree
            msg = _per_rank_bytes(x)
            nseg = pipeline.segment_count("bcast", n, msg)
            if nseg > 1:
                return pipeline.run_pipelined(
                    comm, ("tuned", "bcast", "binomial", root),
                    lambda xb: pipeline.bcast_binomial_pipelined(
                        xb, AXIS, n, root, nseg),
                    x, nseg=nseg, nbytes=msg, opname="bcast",
                )
        # the segment size is baked into the compiled pipeline
        key = ("tuned", "bcast", alg, root) + (
            (seg_elems,) if alg == "pipeline" else ()
        )
        return run_sharded(comm, key, bodies[alg], x)

    def _pick_reduce(self, x, op: Op) -> str:
        """coll_tuned_decision_fixed.c reduce_intra_dec_fixed:
        noncommutative -> linear when small (< 12 ranks and < 2 kB)
        else in_order_binary; commutative -> linear for tiny
        (< 8 ranks, < 512 B), binomial otherwise (the reference's
        segmented binomial/pipeline picks keep binomial's structure —
        segmentation is the compiler's scheduling domain here)."""
        forced = mca_var.get("coll_tuned_reduce_algorithm", "auto")
        if forced != "auto":
            return forced
        n = self.comm.size
        msg = _per_rank_bytes(x)
        dyn = dynamic_rules.lookup("reduce", n, msg)
        if dyn is not None:
            if not op.commutative and dyn == "binomial":
                dyn = "in_order_binary"  # rule may not break order
            return dyn
        if not op.commutative:
            if n < 12 and msg < 2048:
                return "linear"
            return "in_order_binary"
        if n < 8 and msg < 512:
            return "linear"
        return "binomial"

    def reduce(self, comm, x, op: Op, root: int):
        if op.is_pair_op:
            return None  # pair ops stay with xla's gather path
        n = comm.size
        alg = self._pick_reduce(x, op)
        if alg == "binomial" and not op.commutative:
            from ..utils.errors import ErrorCode, MPIError

            raise MPIError(
                ErrorCode.ERR_ARG,
                "binomial reduce rotates operand order by root; use "
                "in_order_binary or linear for a noncommutative op",
            )
        op = _resolve_op(op, x)

        def binom(xb):
            red = spmd.reduce_binomial(xb, op, AXIS, n, root)
            rank = lax.axis_index(AXIS)
            return jnp.where(rank == root, red, jnp.zeros_like(red))

        bodies = {
            "binomial": binom,
            "in_order_binary": lambda xb: spmd.reduce_in_order_binary(
                xb, op, AXIS, n, root),
            "linear": lambda xb: spmd.reduce_linear(
                xb, op, AXIS, n, root),
        }
        if alg == "binomial":
            # segmented binomial reduce (coll/pipeline.py): the tree's
            # per-element combine order ignores element position, so
            # the segmented result is bitwise-identical
            msg = _per_rank_bytes(x)
            nseg = pipeline.segment_count("reduce", n, msg)
            if nseg > 1:
                def pipe_binom(xb):
                    red = pipeline.reduce_binomial_pipelined(
                        xb, op, AXIS, n, root, nseg)
                    rank = lax.axis_index(AXIS)
                    return jnp.where(rank == root, red,
                                     jnp.zeros_like(red))

                return pipeline.run_pipelined(
                    comm, ("tuned", "reduce", "binomial", op, root),
                    pipe_binom, x, nseg=nseg, nbytes=msg,
                    opname="reduce",
                )
        return run_sharded(comm, ("tuned", "reduce", alg, op, root),
                           bodies[alg], x)

    def _pick_allgather(self, x) -> str:
        """coll_tuned_decision_fixed.c:537-567: total < 50 kB ->
        recursive doubling (power-of-two n) else bruck; larger ->
        ring. (The reference's large/even-n pick, neighbor_exchange,
        maps to ring here — ring's step IS the neighbor pass; its
        n==2 special case, two_procs, is bruck's one round.)"""
        forced = mca_var.get("coll_tuned_allgather_algorithm", "auto")
        if forced != "auto":
            return forced
        n = self.comm.size
        total = _per_rank_bytes(x) * n
        dyn = dynamic_rules.lookup("allgather", n, total)
        if dyn is not None:
            return dyn
        if total < mca_var.get("coll_tuned_allgather_small_total",
                               50_000):
            return "recursive_doubling" if n & (n - 1) == 0 else "bruck"
        return "ring"

    def allgather(self, comm, x):
        alg = self._pick_allgather(x)
        n = comm.size
        if alg not in ALLGATHER_ALGORITHMS or alg == "auto":
            from ..utils.errors import ErrorCode, MPIError

            raise MPIError(
                ErrorCode.ERR_ARG,
                f"unknown allgather algorithm '{alg}' "
                f"(choices: {ALLGATHER_ALGORITHMS})",
            )
        if alg == "recursive_doubling" and n & (n - 1):
            from ..utils.errors import ErrorCode, MPIError

            raise MPIError(
                ErrorCode.ERR_ARG,
                f"recursive_doubling allgather needs power-of-two "
                f"ranks (got {n}); use bruck",
            )

        def flat(fn):
            def body(xb):
                g = fn(xb)
                return g.reshape((-1,) + g.shape[2:])
            return body

        bodies = {
            "ring": flat(lambda xb: spmd.allgather_ring(xb, AXIS, n)),
            "bruck": flat(lambda xb: spmd.allgather_bruck(xb, AXIS, n)),
            "recursive_doubling": flat(
                lambda xb: spmd.allgather_recursive_doubling(xb, AXIS, n)
            ),
            "lax": flat(lambda xb: spmd.allgather_lax(xb, AXIS)),
        }
        return run_sharded(comm, ("tuned", "allgather", alg),
                           bodies[alg], x)

    def reduce_scatter_block(self, comm, x, op: Op):
        n = comm.size
        if not op.commutative:
            return None
        op = _resolve_op(op, x)

        # reduce_scatter_ring blocks the flat per-rank buffer itself
        def body(xb):
            return spmd.reduce_scatter_ring(xb, op, AXIS, n)

        return run_sharded(
            comm, ("tuned", "reduce_scatter_block", op), body, x
        )

    # -- gather / scatter (coll_tuned_{gather,scatter}.c) -----------------
    def _pick_gather(self, x) -> str:
        """coll_tuned_decision_fixed.c:677-734: block > 6000 B ->
        linear (the reference's two linear_SYNC branches — the sync
        round-trip protects an eager receiver from overrun, which a
        compiled SPMD exchange has no analogue of, so both map to
        linear here, documented); n > 60, or n > 10 with block
        < 1024 B -> binomial; else basic linear."""
        forced = mca_var.get("coll_tuned_gather_algorithm", "auto")
        if forced != "auto":
            return forced
        n = self.comm.size
        block = _per_rank_bytes(x)
        dyn = dynamic_rules.lookup("gather", n, block)
        if dyn is not None:
            return dyn
        if block > 6000:
            return "linear"
        if n > 60 or (n > 10 and block < 1024):
            return "binomial"
        return "linear"

    def gather(self, comm, x, root: int):
        alg = self._pick_gather(x)
        n = comm.size
        if alg == "binomial":
            body = lambda xb: spmd.gather_binomial(xb, AXIS, n, root)
        else:
            body = lambda xb: spmd.gather_linear(xb, AXIS, n, root)
        return run_sharded(comm, ("tuned", "gather", alg, root), body, x)

    def _pick_scatter(self, x) -> str:
        """coll_tuned_decision_fixed.c:744-770: n > 10 with block
        < 300 B -> binomial; else basic linear. Block size is the
        per-destination chunk of root's buffer."""
        forced = mca_var.get("coll_tuned_scatter_algorithm", "auto")
        if forced != "auto":
            return forced
        n = self.comm.size
        block = _per_rank_bytes(x) // max(1, n)
        dyn = dynamic_rules.lookup("scatter", n, block)
        if dyn is not None:
            return dyn
        return "binomial" if (n > 10 and block < 300) else "linear"

    def scatter(self, comm, x, root: int):
        n = comm.size
        alg = self._pick_scatter(x)
        if alg == "binomial":
            body = lambda xb: spmd.scatter_binomial(xb, AXIS, n, root)
        else:
            body = lambda xb: spmd.scatter_linear(xb, AXIS, n, root)
        return run_sharded(comm, ("tuned", "scatter", alg, root),
                           body, x)

    def _pick_alltoall(self, x) -> str:
        """coll_tuned_decision_fixed.c:124-133: per-destination block
        < 200 B at n > 12 -> bruck; block < 3000 B -> basic_linear;
        else pairwise."""
        forced = mca_var.get("coll_tuned_alltoall_algorithm", "auto")
        if forced != "auto":
            return forced
        n = self.comm.size
        block = _per_rank_bytes(x) // max(1, n)
        dyn = dynamic_rules.lookup("alltoall", n, block)
        if dyn is not None:
            return dyn
        if block < 200 and n > 12:
            return "bruck"
        if block < 3000:
            return "basic_linear"
        return "pairwise"

    def alltoall(self, comm, x):
        alg = self._pick_alltoall(x)
        if alg not in ALLTOALL_ALGORITHMS:
            from ..utils.errors import ErrorCode, MPIError

            raise MPIError(
                ErrorCode.ERR_ARG,
                f"unknown alltoall algorithm '{alg}' "
                f"(choices: {ALLTOALL_ALGORITHMS})",
            )
        n = comm.size
        fn = {
            "lax": spmd.alltoall_lax,
            "basic_linear": spmd.alltoall_lax,  # one-shot posted set
            "bruck": spmd.alltoall_bruck,
            "pairwise": spmd.alltoall_pairwise,
        }[alg]

        def body(xb):
            blocks = xb.reshape((n, -1) + xb.shape[1:])
            return fn(blocks, AXIS, n).reshape(xb.shape)

        return run_sharded(comm, ("tuned", "alltoall", alg), body, x)

    def scan(self, comm, x, op: Op):
        if op.is_pair_op:
            return None  # pair scans stay with xla's gather path
        n = comm.size
        return run_sharded(
            comm, ("tuned", "scan", op),
            lambda xb: spmd.scan_recursive_doubling(xb, op, AXIS, n), x,
        )

    def exscan(self, comm, x, op: Op):
        if op.is_pair_op:
            return None  # pair scans stay with xla's gather path
        n = comm.size
        return run_sharded(
            comm, ("tuned", "exscan", op),
            lambda xb: spmd.scan_recursive_doubling(
                xb, op, AXIS, n, exclusive=True
            ), x,
        )

    def barrier(self, comm):
        out = run_sharded(
            comm, ("tuned", "barrier"),
            lambda xb: spmd.barrier_psum(AXIS) + xb,
            jnp.zeros((comm.size,), jnp.int32),
        )
        jax.block_until_ready(out)

    # -- v-variants: tuned's hand schedules on the padded kernels ----------
    def alltoallv(self, comm, sendbufs, sendcounts, **device_form):
        from . import vcoll

        return vcoll.alltoallv(comm, sendbufs, sendcounts,
                               kernel="pairwise", **device_form)

    def allgatherv(self, comm, sendbufs):
        from . import vcoll

        return vcoll.allgatherv(comm, sendbufs, kernel="ring")

    def gatherv(self, comm, sendbufs, root: int):
        from . import vcoll

        return vcoll.gatherv(comm, sendbufs, root, kernel="ring")

    def scatterv(self, comm, sendbuf, counts, root: int):
        from . import vcoll

        return vcoll.scatterv(comm, sendbuf, counts, root)

    def reduce_scatter(self, comm, x, recvcounts, op: Op):
        if not op.commutative or op.identity is None:
            return None  # xla's allreduce+slice path handles these
        from . import vcoll

        return vcoll.reduce_scatter(comm, x, recvcounts, op, kernel="ring")


class TunedCollComponent(mca_component.Component):
    NAME = "tuned"
    PRIORITY = 50

    def register_vars(self) -> None:
        mca_var.register(
            "coll_tuned_allreduce_algorithm", "enum", "auto",
            "Force a specific allreduce algorithm",
            choices=ALLREDUCE_ALGORITHMS,
        )
        mca_var.register(
            "coll_tuned_bcast_algorithm", "enum", "auto",
            "Force a specific bcast algorithm", choices=BCAST_ALGORITHMS,
        )
        mca_var.register(
            "coll_tuned_allgather_algorithm", "enum", "auto",
            "Force a specific allgather algorithm",
            choices=ALLGATHER_ALGORITHMS,
        )
        mca_var.register(
            "coll_tuned_alltoall_algorithm", "enum", "auto",
            "Force a specific alltoall algorithm",
            choices=ALLTOALL_ALGORITHMS,
        )
        mca_var.register(
            "coll_tuned_small_message", "size", 10000,
            "Below this many bytes per rank, allreduce uses recursive "
            "doubling (coll_tuned_decision_fixed.c:51)",
        )
        mca_var.register(
            "coll_tuned_segment_size", "size", 1 << 20,
            "Ring segment size (coll_tuned_decision_fixed.c:71)",
        )
        mca_var.register(
            "coll_tuned_reduce_algorithm", "enum", "auto",
            "Force a specific reduce algorithm",
            choices=REDUCE_ALGORITHMS,
        )
        mca_var.register(
            "coll_tuned_bcast_segment_size", "size", 128 << 10,
            "Segment size for a FORCED pipeline bcast (auto mode uses "
            "the reference's regression-picked 8-128 KiB)",
        )
        mca_var.register(
            "coll_tuned_gather_algorithm", "enum", "auto",
            "Force a specific gather algorithm",
            choices=GATHER_ALGORITHMS,
        )
        mca_var.register(
            "coll_tuned_scatter_algorithm", "enum", "auto",
            "Force a specific scatter algorithm",
            choices=SCATTER_ALGORITHMS,
        )
        mca_var.register(
            "coll_tuned_allgather_small_total", "size", 50_000,
            "Below this many TOTAL bytes, allgather uses recursive "
            "doubling (power-of-two ranks) or bruck "
            "(coll_tuned_decision_fixed.c:544-559)",
        )
        mca_var.register(
            "coll_tuned_use_dynamic_rules", "bool", False,
            "Consult the dynamic rule file between operator forcing "
            "and the fixed decision constants "
            "(coll_tuned_dynamic_file.c)",
        )
        mca_var.register(
            "coll_tuned_dynamic_rules_filename", "str", "",
            "Rule file: 'collective min_comm_size min_msg_bytes "
            "algorithm' lines, last match wins (see "
            "coll/dynamic_rules.py)",
        )

    def query(self, ctx=None):
        if ctx is None:
            return (self.priority, self)
        if getattr(ctx, "spans_processes", False):
            return None  # cross-process comms belong to coll/hier
        return (self.priority, _TunedModule(ctx))


# ---------------------------------------------------------------------------
# basic component — linear/log reference algorithms (always correct)
# ---------------------------------------------------------------------------

class _BasicModule:
    """Linear algorithms (``ompi/mca/coll/basic``): the correctness
    yardstick. (tuned's reduce also handles non-commutative ops now,
    via in_order_binary/linear — this module remains the
    always-correct fallback, not the only safe path.)"""

    def __init__(self, comm) -> None:
        self.comm = comm

    def fns(self) -> Dict[str, Callable]:
        return {
            "allreduce": self.allreduce,
            "reduce": self.reduce,
            "scatter": self.scatter,
            "gather": self.gather,
        }

    def allreduce(self, comm, x, op: Op):
        if op.is_pair_op:
            return None
        n = comm.size
        op = _resolve_op(op, x)
        return run_sharded(
            comm, ("basic", "allreduce", op),
            lambda xb: spmd.allreduce_basic_linear(xb, op, AXIS, n), x,
        )

    def reduce(self, comm, x, op: Op, root: int):
        n = comm.size
        op = _resolve_op(op, x)

        def body(xb):
            red = spmd.allreduce_basic_linear(xb, op, AXIS, n)
            rank = lax.axis_index(AXIS)
            return jnp.where(rank == root, red, jnp.zeros_like(red))

        return run_sharded(comm, ("basic", "reduce", op, root), body, x)

    def scatter(self, comm, x, root: int):
        n = comm.size

        def body(xb):
            full = spmd.bcast_masked_psum(xb, xb.dtype, AXIS, root)
            chunks = full.reshape((n, -1) + full.shape[1:])
            rank = lax.axis_index(AXIS)
            return jnp.take(chunks, rank, axis=0)

        return run_sharded(comm, ("basic", "scatter", root), body, x)

    def gather(self, comm, x, root: int):
        def body(xb):
            g = lax.all_gather(xb, AXIS, axis=0)
            g = g.reshape((-1,) + g.shape[2:])
            rank = lax.axis_index(AXIS)
            return jnp.where(rank == root, g, jnp.zeros_like(g))

        return run_sharded(comm, ("basic", "gather", root), body, x)


class BasicCollComponent(mca_component.Component):
    NAME = "basic"
    PRIORITY = 10

    def query(self, ctx=None):
        if ctx is None:
            return (self.priority, self)
        if getattr(ctx, "spans_processes", False):
            return None  # cross-process comms belong to coll/hier
        return (self.priority, _BasicModule(ctx))


# ---------------------------------------------------------------------------
# self component — size-1 communicators never touch the mesh
# ---------------------------------------------------------------------------

class _SelfModule:
    def __init__(self, comm) -> None:
        self.comm = comm

    def fns(self) -> Dict[str, Callable]:
        import numpy as _np

        def identity(comm, x, *a, **k):
            return jnp.asarray(x)

        def allreduce(comm, x, op):
            return jnp.asarray(x)

        return {
            "allreduce": allreduce,
            "reduce": lambda comm, x, op, root: jnp.asarray(x),
            "bcast": lambda comm, x, root: jnp.asarray(x),
            "allgather": identity,
            "gather": lambda comm, x, root: jnp.asarray(x),
            "scatter": lambda comm, x, root: jnp.asarray(x),
            "reduce_scatter_block": lambda comm, x, op: jnp.asarray(x),
            "alltoall": identity,
            "scan": lambda comm, x, op: jnp.asarray(x),
            "exscan": lambda comm, x, op: jnp.zeros_like(jnp.asarray(x)),
            "barrier": lambda comm: None,
            # v-variants on one rank: local identities, but with the
            # SAME validation + 1-D flattening contract as the vcoll
            # path so callers see identical shapes on any comm size
            "alltoallv": self._alltoallv,
            "allgatherv": self._allgatherv,
            "gatherv": lambda comm, bufs, root: self._allgatherv(comm, bufs),
            "scatterv": self._scatterv,
            "reduce_scatter": self._reduce_scatter,
        }

    @staticmethod
    def _alltoallv(comm, bufs, counts, recv_capacity=None):
        from . import vcoll

        if recv_capacity is not None:  # the device form, as on n ranks
            return vcoll.alltoallv_device(comm, bufs, counts, recv_capacity)
        b = vcoll._as_1d_arrays(bufs, 1, "alltoallv")
        c = vcoll._counts_matrix(counts, 1)
        if b[0].shape[0] != int(c[0, 0]):
            from ..utils.errors import ErrorCode, MPIError

            raise MPIError(
                ErrorCode.ERR_COUNT,
                f"alltoallv buffer has {b[0].shape[0]} elements, count "
                f"is {int(c[0, 0])}",
            )
        return [jnp.asarray(b[0])]

    @staticmethod
    def _allgatherv(comm, bufs):
        from . import vcoll

        return jnp.asarray(vcoll._as_1d_arrays(bufs, 1, "allgatherv")[0])

    @staticmethod
    def _scatterv(comm, buf, counts, root):
        import numpy as _np

        from ..utils.errors import ErrorCode, MPIError

        if root != 0:
            raise MPIError(ErrorCode.ERR_ROOT, f"bad root {root}")
        flat = _np.asarray(buf).reshape(-1)
        counts = [int(k) for k in counts]
        if len(counts) != 1 or flat.shape[0] != counts[0]:
            raise MPIError(
                ErrorCode.ERR_COUNT,
                f"scatterv needs 1 count matching the buffer length",
            )
        return [jnp.asarray(flat)]

    @staticmethod
    def _reduce_scatter(comm, x, counts, op):
        import numpy as _np

        from ..utils.errors import ErrorCode, MPIError

        flat = _np.asarray(x).reshape(-1)
        counts = [int(k) for k in counts]
        if len(counts) != 1 or flat.shape[0] != counts[0]:
            raise MPIError(
                ErrorCode.ERR_COUNT,
                "reduce_scatter on a self comm needs x of shape "
                "(1, recvcounts[0])",
            )
        return [jnp.asarray(flat)]


class SelfCollComponent(mca_component.Component):
    NAME = "self"
    PRIORITY = 0

    def query(self, ctx=None):
        if ctx is None:
            return (self.priority, self)
        if getattr(ctx, "spans_processes", False):
            return None  # a size-1 spanning comm has no local member
        if ctx.size == 1:
            return (1000, _SelfModule(ctx))  # claim size-1 comms outright
        return None


# ---------------------------------------------------------------------------
# ml component — hierarchical two-level collectives (ml/bcol/sbgp)
# ---------------------------------------------------------------------------

def _discover_hierarchy(comm) -> Optional[tuple]:
    """sbgp-style subgroup discovery: split the comm's ranks into fast
    domains (same host process / slice — ``ompi/mca/sbgp`` socket/UMA
    grouping). Returns (inter, intra) when ranks form equal-size
    contiguous groups, else None. The ``coll_ml_local_size`` variable
    overrides discovery (for CI, where every virtual device shares one
    process)."""
    forced = int(mca_var.get("coll_ml_local_size", 0))
    n = comm.size
    if forced > 1:
        return (n // forced, forced) if n % forced == 0 else None
    eps = {e.rank: e for e in comm.runtime.endpoints}
    keys = []
    for i in range(n):
        e = eps.get(comm.group.world_rank(i))
        if e is None:
            return None
        keys.append((e.process_index, e.slice_index))
    groups: Dict[tuple, list] = {}
    for i, k in enumerate(keys):
        groups.setdefault(k, []).append(i)
    sizes = {len(v) for v in groups.values()}
    if len(groups) < 2 or len(sizes) != 1:
        return None
    intra = sizes.pop()
    if intra < 2:
        return None
    # groups must be contiguous rank blocks for the 2-D factorization
    for members in groups.values():
        if members != list(range(members[0], members[0] + intra)):
            return None
    return (len(groups), intra)


class _MlModule:
    """Two-level algorithms over the (node, local) decomposition."""

    def __init__(self, comm, inter: int, intra: int) -> None:
        self.comm = comm
        self.inter = inter
        self.intra = intra

    def fns(self) -> Dict[str, Callable]:
        return {
            "allreduce": self.allreduce,
            "reduce": self.reduce,
            "bcast": self.bcast,
            "allgather": self.allgather,
            "reduce_scatter_block": self.reduce_scatter_block,
            "alltoall": self.alltoall,
            "barrier": self.barrier,
        }

    def _reducible(self, op: Op) -> bool:
        return not (op.is_pair_op or op.identity is None
                    or not op.commutative)

    def allreduce(self, comm, x, op: Op):
        if not self._reducible(op):
            return None  # defer to lower-priority providers
        from .driver import run_sharded2d

        op = _resolve_op(op, x)
        body = lambda xb: spmd.allreduce_two_level(
            xb, op, "local", "node", self.intra
        )
        return run_sharded2d(
            comm, ("ml", "allreduce", op, self.inter, self.intra),
            body, x, inter=self.inter, intra=self.intra,
        )

    def reduce(self, comm, x, op: Op, root: int):
        if not self._reducible(op):
            return None
        from .driver import run_sharded2d

        op = _resolve_op(op, x)
        body = lambda xb: spmd.reduce_two_level(
            xb, op, "local", "node", root, self.intra
        )
        return run_sharded2d(
            comm, ("ml", "reduce", op, root, self.inter, self.intra),
            body, x, inter=self.inter, intra=self.intra,
        )

    def allgather(self, comm, x):
        from .driver import run_sharded2d

        def body(xb):
            g = spmd.allgather_two_level(xb, "local", "node")
            return g.reshape((-1,) + g.shape[2:])

        return run_sharded2d(
            comm, ("ml", "allgather", self.inter, self.intra),
            body, x, inter=self.inter, intra=self.intra,
        )

    def reduce_scatter_block(self, comm, x, op: Op):
        if not self._reducible(op):
            return None
        from .driver import run_sharded2d

        op = _resolve_op(op, x)
        n = comm.size
        body = lambda xb: spmd.reduce_scatter_two_level(
            xb, op, "local", "node", self.intra, n
        )
        return run_sharded2d(
            comm,
            ("ml", "reduce_scatter_block", op, self.inter,
             self.intra),
            body, x, inter=self.inter, intra=self.intra,
        )

    def alltoall(self, comm, x):
        from .driver import run_sharded2d

        n = comm.size

        def body(xb):
            blocks = xb.reshape((n, -1) + xb.shape[1:])
            out = spmd.alltoall_two_level(
                blocks, "local", "node", self.intra, self.inter
            )
            return out.reshape(xb.shape)

        return run_sharded2d(
            comm, ("ml", "alltoall", self.inter, self.intra),
            body, x, inter=self.inter, intra=self.intra,
        )

    def bcast(self, comm, x, root: int):
        from .driver import run_sharded2d

        body = lambda xb: spmd.bcast_two_level(
            xb, "local", "node", root, self.intra
        )
        return run_sharded2d(
            comm, ("ml", "bcast", root, self.inter, self.intra),
            body, x, inter=self.inter, intra=self.intra,
        )

    def barrier(self, comm):
        from .driver import run_sharded2d

        out = run_sharded2d(
            comm, ("ml", "barrier", self.inter, self.intra),
            lambda xb: spmd.barrier_psum("local")
            + spmd.barrier_psum("node") + xb,
            jnp.zeros((comm.size,), jnp.int32),
            inter=self.inter, intra=self.intra,
        )
        jax.block_until_ready(out)


class MlCollComponent(mca_component.Component):
    """Hierarchical collectives; wins only when selected (coll=ml) or
    its priority is raised, and declines comms with no hierarchy."""

    NAME = "ml"
    PRIORITY = 40

    def register_vars(self) -> None:
        mca_var.register(
            "coll_ml_local_size", "int", 0,
            "Force the fast-domain (intra) size for hierarchical "
            "collectives; 0 = discover from endpoint process/slice ids",
        )

    def query(self, ctx=None):
        if ctx is None:
            return (self.priority, self)
        if getattr(ctx, "spans_processes", False):
            return None  # cross-process comms belong to coll/hier
        h = _discover_hierarchy(ctx)
        if h is None:
            return None
        return (self.priority, _MlModule(ctx, *h))


from .hier import HierCollComponent  # noqa: E402  (registration order)

COLL_FRAMEWORK.register(XlaCollComponent())
COLL_FRAMEWORK.register(TunedCollComponent())
COLL_FRAMEWORK.register(MlCollComponent())
COLL_FRAMEWORK.register(BasicCollComponent())
COLL_FRAMEWORK.register(SelfCollComponent())
COLL_FRAMEWORK.register(HierCollComponent())
