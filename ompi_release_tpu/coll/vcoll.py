"""v-variant collectives: per-rank counts with static-shape kernels.

The reference implements MPI_Alltoallv/Allgatherv/Gatherv/Scatterv and
general MPI_Reduce_scatter as count/displacement-driven send/recv loops
(``ompi/mca/coll/tuned/coll_tuned_alltoallv.c``, ``coll_base``
linear variants). XLA needs static shapes, so the TPU-native design
splits each v-collective in two:

  driver edge (here, host numpy)   ragged per-rank buffers <-> one
                                   padded rectangular array (pad to the
                                   max count; op identity as filler)
  compiled kernel (coll/spmd.py)   the equal-block collective on the
                                   padded array — one persistent
                                   program per (n, cmax, dtype), counts
                                   NOT baked in

so arbitrary count matrices reuse one compiled program per padded
shape: changing counts changes only the edge slicing, never triggers a
retrace (the "no per-call retrace" north-star requirement applies to
varying ragged workloads too — this is why counts live at the edge).

``alltoallv`` has a second, device form (:func:`alltoallv_device`)
for counts that only the device knows: one rank-sharded buffer and
count matrix in, the exchange, its offsets and the receive buffer's
compaction in one program, nothing on the host.

Driver-mode conventions (matching ``comm/communicator.py``):
rank-dependent inputs/outputs are Python lists indexed by rank (ragged
lengths make a leading-axis array impossible); results identical on
every rank are returned once.
"""

from __future__ import annotations

import functools
import time as _time
from typing import List, NamedTuple, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.op import Op
from ..utils.errors import ErrorCode, MPIError
from . import spmd
from .driver import run_sharded

AXIS = "rank"

from .. import obs as _obs  # noqa: E402
from ..mca import pvar as _pvar  # noqa: E402
from ..obs import spans as _spans  # noqa: E402

_padded_elems = _pvar.counter(
    "vcoll_alltoallv_padded_elems",
    "elements moved by the padded alltoallv kernel",
)
_overflow_elems = _pvar.counter(
    "vcoll_alltoallv_overflow_elems",
    "hot-pair tail elements delivered host-side at the driver edge "
    "(skew mitigation; these bypass the kernel)",
)
_device_calls = _pvar.counter(
    "vcoll_alltoallv_device_calls",
    "alltoallv calls of the device form: a rank-sharded buffer and count "
    "matrix in, the receive buffer and counts out, nothing on the host",
)
_host_bytes = _pvar.counter(
    "vcoll_alltoallv_host_bytes",
    "bytes alltoallv moved through host memory: the list form's send "
    "buffers read, its padded array put and fetched, its receive buffers "
    "put (0 on the device form)",
)


def _as_1d_arrays(bufs, n: int, what: str) -> List[np.ndarray]:
    if len(bufs) != n:
        raise MPIError(
            ErrorCode.ERR_COUNT,
            f"{what} needs one buffer per rank ({n}), got {len(bufs)}",
        )
    out = [np.asarray(b).reshape(-1) for b in bufs]
    dtypes = {a.dtype for a in out}
    if len(dtypes) != 1:
        raise MPIError(
            ErrorCode.ERR_TYPE,
            f"{what} buffers must share one dtype, got {sorted(map(str, dtypes))}",
        )
    if out:
        # check the ORIGINAL dtype here: the padded staging array is
        # jnp-converted before run_sharded's own narrowing check can
        # see the user's 64-bit buffer
        from .driver import _check_no_narrowing

        _check_no_narrowing(out[0])
    return out


def _counts_matrix(counts, n: int) -> np.ndarray:
    c = np.asarray(counts, dtype=np.int64)
    if c.shape != (n, n) or (c < 0).any():
        raise MPIError(
            ErrorCode.ERR_COUNT,
            f"need a non-negative ({n},{n}) count matrix, got {c.shape}",
        )
    return c


# ---------------------------------------------------------------------------
# alltoallv
# ---------------------------------------------------------------------------

def _skew_cap(c: np.ndarray) -> int:
    """Padding cap for a skewed count matrix.

    The padded kernel moves n·n·cmax elements regardless of counts, so
    ONE hot (rank, rank) pair makes every pair pay cmax. When cmax
    exceeds ``coll_alltoallv_skew_factor`` × the median nonzero count,
    the kernel's pad is capped at the 90th-percentile count and the
    few hot pairs' tails travel pairwise instead (the reference's
    linear send/recv loop pays per-pair counts natively; this hybrid
    recovers that property for the outliers while the bulk stays one
    compiled program)."""
    from ..mca import var as mca_var

    nz = c[c > 0]
    if nz.size <= 1:
        return int(c.max()) if c.size else 1
    cmax = int(nz.max())
    factor = int(mca_var.get("coll_alltoallv_skew_factor", 4))
    med = max(1, int(np.median(nz)))
    if factor > 0 and cmax > factor * med:
        return max(1, int(np.quantile(nz, 0.9)))
    return cmax


def alltoallv(comm, sendbufs: Sequence, sendcounts, *,
              kernel: str = "lax", recv_capacity=None):
    """Every rank sends ``sendcounts[i][j]`` elements to rank j.

    ``sendbufs[i]`` = rank i's send buffer: the chunks for ranks
    0..n-1 back to back (MPI's sdispls are implicit/contiguous; pass
    pre-sliced data for the general displacement case). Returns
    ``recv[i]`` = concatenation of chunks from ranks 0..n-1 in source
    order — exactly MPI_Alltoallv's receive layout — on rank i's
    device.

    With ``recv_capacity`` the call is the device form
    (:func:`alltoallv_device`): ``sendbufs`` is one rank-sharded
    ``(n, m)`` array and ``sendcounts`` an ``(n, n)`` one, and
    ``kernel`` does not apply.

    Skewed count matrices are mitigated (see :func:`_skew_cap`): the
    padded kernel's cap is bounded at a count quantile and hot pairs'
    overflow tails are delivered host-side at the driver edge
    (numpy slices concatenated into the receive buffers — they never
    traverse a kernel or transport), accounted in the
    ``vcoll_alltoallv_overflow_elems`` pvar. Every byte of this form
    passes through the host (``vcoll_alltoallv_host_bytes``).
    """
    if recv_capacity is not None:
        return alltoallv_device(comm, sendbufs, sendcounts, recv_capacity)
    rec = _obs.enabled  # capture once: flag may flip mid-call
    t_edge = _time.perf_counter() if rec else 0.0
    n = comm.size
    bufs = _as_1d_arrays(sendbufs, n, "alltoallv")
    c = _counts_matrix(sendcounts, n)
    for i in range(n):
        if bufs[i].shape[0] != int(c[i].sum()):
            raise MPIError(
                ErrorCode.ERR_COUNT,
                f"alltoallv rank {i}: buffer has {bufs[i].shape[0]} "
                f"elements, counts sum to {int(c[i].sum())}",
            )
    cap = _skew_cap(c)
    dtype = bufs[0].dtype
    base_c = np.minimum(c, cap)
    padded = np.zeros((n, n, cap), dtype=dtype)
    offs = np.concatenate(
        [np.zeros((n, 1), np.int64), np.cumsum(c, axis=1)], axis=1
    )
    overflow: dict = {}
    overflow_elems = 0
    for i in range(n):
        for j in range(n):
            k = int(c[i, j])
            kb = int(base_c[i, j])
            if kb:
                padded[i, j, :kb] = bufs[i][offs[i, j]:offs[i, j] + kb]
            if k > kb:  # hot pair: tail travels pairwise
                overflow[(i, j)] = bufs[i][offs[i, j] + kb:offs[i, j] + k]
                overflow_elems += k - kb

    body = (spmd.alltoall_lax if kernel == "lax"
            else spmd.alltoall_pairwise)
    out = run_sharded(
        comm, (kernel, "alltoallv", n, cap, str(dtype)),
        lambda xb: body(xb, AXIS, n), jnp.asarray(padded),
    )
    _padded_elems.add(n * n * cap)
    _overflow_elems.add(overflow_elems)
    out = np.asarray(out)  # (n, n, cap); out[i, j] = chunk j -> i
    devices = list(comm.submesh.devices.flat)
    recv, moved = [], sum(b.nbytes for b in bufs) + 2 * padded.nbytes
    for i in range(n):
        parts = []
        for j in range(n):
            kb = int(base_c[j, i])
            part = out[i, j, :kb]
            tail = overflow.get((j, i))
            if tail is not None:
                part = np.concatenate([part, tail])
            parts.append(part)
        got = np.concatenate(parts) if parts else np.zeros((0,), dtype)
        moved += got.nbytes
        recv.append(jax.device_put(got, devices[i]))
    _host_bytes.add(moved)
    if rec:
        # whole-edge span (pad + kernel + overflow delivery); the
        # kernel's own coll-layer span nests inside it in the trace
        _obs.record(
            "alltoallv", "vcoll", t_edge, _time.perf_counter() - t_edge,
            nbytes=int((n * n * cap + overflow_elems) * dtype.itemsize),
            comm_id=comm.cid,
        )
    return recv


# ---------------------------------------------------------------------------
# alltoallv, device form: buffer and counts never leave the chips
# ---------------------------------------------------------------------------

#: elements of one row of the exchange: ``lax.ragged_all_to_all`` on
#: XLA:TPU lays its leading (ragged) axis out one 128-lane tile per
#: index, so a flat buffer would take 128 times its size; in rows of
#: 128 it moves as laid out, and a chunk travels as the rows that hold it
GRANULE = 128


class Operands(NamedTuple):
    """One rank's side of a device-form exchange. The first four are
    ``lax.ragged_all_to_all``'s, in rows of :data:`GRANULE`; the rest
    are what the receiver keeps to compact its rows."""

    input_offsets: jax.Array   # (n,) first row of this rank's chunk for j
    send_sizes: jax.Array      # (n,) rows of it that j takes
    output_offsets: jax.Array  # (n,) where those rows land on j
    recv_sizes: jax.Array      # (n,) rows this rank takes from source k
    recv_rows: jax.Array       # (n,) where source k's rows land here
    start: jax.Array           # (n,) element of k's chunk in those rows
    at: jax.Array              # (n,) element where k's chunk lands in recv
    held: jax.Array            # (n,) elements of k's chunk recv holds
    recvcounts: jax.Array      # (n,) elements each source sent, uncut
    dropped: jax.Array         # () elements recv could not hold


def stage_rows(n: int, cap: int) -> int:
    """Rows of the staging buffer: every source's chunk may straddle
    two rows it shares with its neighbours."""
    return -(-cap // GRANULE) + 2 * n


def exchange_operands(sendcounts, cap: int) -> Operands:
    """The operands of one rank's exchange, inside the program: both
    device bodies build them here. ``sendcounts``: this rank's row of
    the count matrix. Each source tells its peers, in one
    ``all_to_all`` (MPI_Alltoall of the counts), how many elements it
    sends and where in its first row they start; each receiver decides
    what its ``cap`` elements hold (each source's chunk in source
    order, cut where the buffer ends) and where the rows holding it
    land, and tells each source in a second one, so sender and
    receiver agree on every size by construction.
    """
    r = GRANULE
    sc = sendcounts.astype(jnp.int32)
    off = jnp.cumsum(sc) - sc
    hdr = lax.all_to_all(jnp.stack([sc, off % r], axis=1), AXIS, 0, 0,
                         tiled=True)
    rc, head = hdr[:, 0], hdr[:, 1]
    land = jnp.cumsum(rc) - rc
    held = jnp.maximum(jnp.minimum(rc, cap - land), 0)
    rows = jnp.where(held > 0, (head + held + r - 1) // r, 0)
    recv_rows = jnp.cumsum(rows) - rows
    back = lax.all_to_all(jnp.stack([recv_rows, rows], axis=1), AXIS, 0, 0,
                          tiled=True)
    return Operands(off // r, back[:, 1], back[:, 0], rows, recv_rows,
                    recv_rows * r + head, jnp.minimum(land, cap), held, rc,
                    jnp.sum(rc - held))


def _as_rows(xb):
    """The rank's buffer as rows of :data:`GRANULE` (zero-filled to a
    whole row)."""
    tail = -xb.shape[0] % GRANULE
    if tail:
        xb = jnp.concatenate([xb, jnp.zeros((tail,), xb.dtype)])
    return xb.reshape(-1, GRANULE)


def _compact(stage, o: Operands, cap: int):
    """The receive buffer from the staged rows: source k's ``held``
    elements from ``start[k]`` of the rows to ``at[k]``, zero past the
    last one."""
    flat = stage.reshape(-1)
    flat = jnp.concatenate([flat, jnp.zeros((cap,), flat.dtype)])
    t = jnp.arange(cap)
    out = jnp.zeros((cap,), flat.dtype)
    for k in range(o.at.shape[0]):
        piece = lax.dynamic_slice(flat, (o.start[k] - o.at[k],), (cap,))
        out = jnp.where((t >= o.at[k]) & (t < o.at[k] + o.held[k]), piece,
                        out)
    return out


def _ragged_body(xb, sc, dropped, *, cap: int):
    """TPU: ``lax.ragged_all_to_all`` moves the rows that hold the
    counted elements, and nothing else."""
    o = exchange_operands(sc, cap)
    stage = lax.ragged_all_to_all(
        _as_rows(xb), jnp.zeros((stage_rows(sc.shape[0], cap), GRANULE),
                                xb.dtype),
        o.input_offsets, o.send_sizes, o.output_offsets, o.recv_sizes,
        axis_name=AXIS)
    return _compact(stage, o, cap), o.recvcounts, dropped + o.dropped


def _padded_body(xb, sc, dropped, *, cap: int):
    """Every other backend (XLA:CPU has no ragged-all-to-all): the same
    operands, each pair's rows padded to the pair capacity, the tiled
    ``all_to_all`` of the ``xla`` component, and the rows staged in
    source order, each chunk over the zero tail of the one before."""
    o = exchange_operands(sc, cap)
    n, rows = sc.shape[0], _as_rows(xb)
    s = stage_rows(n, cap)
    p = min(rows.shape[0], s)
    padded = jnp.concatenate([rows, jnp.zeros((p, GRANULE), rows.dtype)])
    send = jnp.stack([lax.dynamic_slice(padded, (o.input_offsets[j], 0),
                                        (p, GRANULE)) for j in range(n)])
    keep = jnp.arange(p)[None, :] < o.send_sizes[:, None]
    send = jnp.where(keep[:, :, None], send, 0)
    got = lax.all_to_all(send, AXIS, 0, 0, tiled=True)  # [k]: from k
    stage = jnp.zeros((s + p, GRANULE), xb.dtype)
    for k in range(n):
        stage = lax.dynamic_update_slice(stage, got[k], (o.recv_rows[k], 0))
    return _compact(stage[:s], o, cap), o.recvcounts, dropped + o.dropped


def _ragged_on(comm) -> bool:
    return next(iter(comm.submesh.devices.flat)).platform == "tpu"


def truncated(comm):
    """Elements each rank's receive buffer could not hold, over every
    device-form call on ``comm`` so far: a rank-sharded ``(n,)`` int32
    device array that the exchange itself keeps (MPI's ERR_TRUNCATE
    without a host sync; read it when the caller syncs anyway)."""
    t = getattr(comm, "_alltoallv_truncated", None)
    if t is None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        t = jax.device_put(np.zeros((comm.size,), np.int32),
                           NamedSharding(comm.submesh, P(AXIS)))
        comm._alltoallv_truncated = t
    return t


def alltoallv_device(comm, x, sendcounts, recv_capacity: int):
    """MPI_Alltoallv with the buffer and the counts on the chips.

    ``x``: rank-sharded ``(n, m)``, row i rank i's send buffer with its
    chunks for ranks 0..n-1 back to back (MPI's contiguous sdispls).
    ``sendcounts``: rank-sharded ``(n, n)`` int32, row i rank i's; the
    program sends each column to its rank (MPI_Alltoall of the counts).
    Returns ``(recv, counts)`` on the communicator's devices: ``recv``
    is ``(n, recv_capacity)``, row j the chunks from each source in
    source order, zero past rank j's total; ``counts`` row j what each
    source sent to j. A total past ``recv_capacity`` is cut where the
    buffer ends, ``counts`` still says what was sent, and
    :func:`truncated` counts what was cut.

    No count is read on the host and nothing is fetched: the call
    returns once its program is enqueued, and one compiled program
    serves every count matrix of one ``(n, m, recv_capacity, dtype)``:
    the ragged body on a TPU, the padded one elsewhere.
    """
    n, cap = comm.size, int(recv_capacity)
    shape = tuple(getattr(x, "shape", ()))
    if len(shape) != 2 or shape[0] != n or cap < 1:
        raise MPIError(
            ErrorCode.ERR_COUNT,
            f"device-form alltoallv needs an ({n}, m) buffer and a "
            f"recv_capacity of at least 1, got {shape} and {cap}",
        )
    if tuple(getattr(sendcounts, "shape", ())) != (n, n):
        raise MPIError(
            ErrorCode.ERR_COUNT,
            f"device-form alltoallv needs ({n}, {n}) sendcounts, got "
            f"{getattr(sendcounts, 'shape', type(sendcounts).__name__)}",
        )
    body = _ragged_body if _ragged_on(comm) else _padded_body
    return _exchange(comm, x, sendcounts, cap, body)


def _exchange(comm, x, sendcounts, cap, body):
    """One device-form exchange through ``body`` (the caller has
    checked the arguments)."""
    with _obs.span(_spans.VCOLL_ALLTOALLV, journal=("alltoallv", "vcoll"),
                   bytes=_spans.nbytes(x), cid=comm.cid):
        recv, counts, cut = run_sharded(
            comm, ("xla", "alltoallv_device", body.__name__, x.shape[1],
                   cap, str(x.dtype)),
            functools.partial(body, cap=cap), x,
            extra_arrays=(sendcounts, truncated(comm)))
        comm._alltoallv_truncated = cut
        _device_calls.add()
    return recv, counts


# ---------------------------------------------------------------------------
# allgatherv / gatherv
# ---------------------------------------------------------------------------

def allgatherv(comm, sendbufs: Sequence, *, kernel: str = "lax"):
    """Concatenate every rank's (ragged) buffer in rank order; the
    result is identical on all ranks, returned once."""
    rec = _obs.enabled
    t_edge = _time.perf_counter() if rec else 0.0
    n = comm.size
    bufs = _as_1d_arrays(sendbufs, n, "allgatherv")
    counts = [b.shape[0] for b in bufs]
    cmax = max(1, max(counts))
    dtype = bufs[0].dtype
    padded = np.zeros((n, cmax), dtype=dtype)
    for i, b in enumerate(bufs):
        padded[i, : counts[i]] = b

    if kernel == "ring":
        body = lambda xb: spmd.allgather_ring(xb, AXIS, n)
    else:
        body = lambda xb: lax.all_gather(xb, AXIS, axis=0)
    out = run_sharded(
        comm, (kernel, "allgatherv", n, cmax, str(dtype)), body,
        jnp.asarray(padded),
    )
    # (n, n, cmax): row r is rank r's gathered copy; all rows identical
    # — fetch only rank 0's shard, not n replicated copies
    g = np.asarray(out[0])
    result = jnp.asarray(
        np.concatenate([g[i, : counts[i]] for i in range(n)])
    )
    if rec:
        _obs.record("allgatherv", "vcoll", t_edge,
                    _time.perf_counter() - t_edge,
                    nbytes=int(n * cmax * dtype.itemsize),
                    comm_id=comm.cid)
    return result


def gatherv(comm, sendbufs: Sequence, root: int, *, kernel: str = "lax"):
    """Root receives the rank-order concatenation (other ranks' recv
    buffers are undefined in MPI).

    Root-respecting cost model: the reference's gatherv is LINEAR —
    non-root ranks send exactly their own buffer and only root receives
    (``coll_base_gatherv`` linear variant); no rank pays an allgather.
    Driver mode's analogue of "root receives rank i's message" is a
    host-side read of each rank's (already rank-local) buffer, so the
    correct implementation is edge concatenation with a completion
    barrier — NO compiled all-to-all-style collective, and no
    per-rank O(total) receive buffers. ``kernel`` is accepted for API
    symmetry with :func:`allgatherv` but unused.
    """
    n = comm.size
    if not 0 <= root < n:
        raise MPIError(ErrorCode.ERR_ROOT, f"bad root {root}")
    bufs = _as_1d_arrays(sendbufs, n, "gatherv")
    comm.barrier()
    return jnp.asarray(np.concatenate(bufs))


# ---------------------------------------------------------------------------
# scatterv
# ---------------------------------------------------------------------------

def scatterv(comm, sendbuf, counts: Sequence[int], root: int) -> List:
    """Root's buffer split into ``counts[i]`` elements for rank i."""
    n = comm.size
    if not 0 <= root < n:
        raise MPIError(ErrorCode.ERR_ROOT, f"bad root {root}")
    counts = [int(k) for k in counts]
    if len(counts) != n or any(k < 0 for k in counts):
        raise MPIError(
            ErrorCode.ERR_COUNT,
            f"scatterv needs {n} non-negative counts, got {counts}",
        )
    buf = np.asarray(sendbuf).reshape(-1)
    if buf.shape[0] != sum(counts):
        raise MPIError(
            ErrorCode.ERR_COUNT,
            f"scatterv root buffer has {buf.shape[0]} elements, counts "
            f"sum to {sum(counts)}",
        )
    cmax = max(1, max(counts) if counts else 1)
    dtype = buf.dtype
    # only root's slice carries data (bcast-masked under the hood)
    padded = np.zeros((n, n, cmax), dtype=dtype)
    off = 0
    for j, k in enumerate(counts):
        padded[root, j, :k] = buf[off:off + k]
        off += k

    def body(xb):
        full = spmd.bcast_masked_psum(xb, xb.dtype, AXIS, root)
        rank = lax.axis_index(AXIS)
        return jnp.take(full, rank, axis=0)

    out = run_sharded(
        comm, ("xla", "scatterv", n, cmax, str(dtype), root), body,
        jnp.asarray(padded),
    )
    out = np.asarray(out)  # (n, cmax)
    return [jnp.asarray(out[i, : counts[i]]) for i in range(n)]


# ---------------------------------------------------------------------------
# reduce_scatter (general, per-rank counts)
# ---------------------------------------------------------------------------

def reduce_scatter(comm, x, recvcounts: Sequence[int], op: Op, *,
                   kernel: str = "lax") -> List:
    """General MPI_Reduce_scatter: reduce the full buffer, rank i keeps
    the segment of length ``recvcounts[i]``.

    ``x``: (size, total) — per-rank contribution rows,
    total = sum(recvcounts). Returns one array per rank. MINLOC/MAXLOC
    pairs are accepted: ``x = (values, indices)`` and each returned
    segment is a (values, indices) pair.
    """
    n = comm.size
    recvcounts = [int(k) for k in recvcounts]
    if len(recvcounts) != n or any(k < 0 for k in recvcounts):
        raise MPIError(
            ErrorCode.ERR_COUNT,
            f"reduce_scatter needs {n} non-negative counts",
        )
    if op.is_pair_op:
        vals, idxs = x
        vals, idxs = np.asarray(vals), np.asarray(idxs)
        total = sum(recvcounts)
        for nm, a in (("values", vals), ("indices", idxs)):
            if a.shape[0] != n or a.reshape(n, -1).shape[1] != total:
                raise MPIError(
                    ErrorCode.ERR_COUNT,
                    f"reduce_scatter needs {nm} shaped ({n}, {total}), "
                    f"got {a.shape}",
                )
        # the pair allreduce kernel does the reduction; segments are
        # sliced at the driver edge (ragged counts never retrace)
        rv, ri = comm.allreduce((vals.reshape(n, total),
                                 idxs.reshape(n, total)), op)
        rv0, ri0 = np.asarray(rv)[0], np.asarray(ri)[0]
        offs = np.concatenate([[0], np.cumsum(recvcounts)])
        return [
            (jnp.asarray(rv0[offs[i]:offs[i] + recvcounts[i]]),
             jnp.asarray(ri0[offs[i]:offs[i] + recvcounts[i]]))
            for i in range(n)
        ]
    x = np.asarray(x)
    total = sum(recvcounts)
    if x.shape[0] != n or x.reshape(n, -1).shape[1] != total:
        raise MPIError(
            ErrorCode.ERR_COUNT,
            f"reduce_scatter needs x shaped (size, {total}), got {x.shape}",
        )
    x = x.reshape(n, total)
    cmax = max(1, max(recvcounts) if recvcounts else 1)
    dtype = x.dtype
    ident = op.identity_for(dtype) if op.identity is not None else 0
    padded = np.full((n, n, cmax), ident, dtype=dtype)
    offs = np.concatenate([[0], np.cumsum(recvcounts)])
    for r in range(n):
        for j, k in enumerate(recvcounts):
            if k:
                padded[r, j, :k] = x[r, offs[j]:offs[j] + k]

    if kernel == "ring" and op.commutative and op.identity is not None:
        def body(xb):
            return spmd.reduce_scatter_ring(
                xb.reshape(-1), op, AXIS, n
            )
    else:
        def body(xb):
            red = spmd.allreduce_lax(xb, op, AXIS)
            rank = lax.axis_index(AXIS)
            return jnp.take(red, rank, axis=0)

    out = run_sharded(
        comm, (kernel, "reduce_scatter", op, n, cmax, str(dtype)),
        body, jnp.asarray(padded),
    )
    out = np.asarray(out).reshape(n, cmax)
    return [jnp.asarray(out[i, : recvcounts[i]]) for i in range(n)]
