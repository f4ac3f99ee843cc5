"""OpenSHMEM — symmetric heap + put/get/AMO + collectives.

The reference's OSHMEM stack (SURVEY §1.4): ``memheap`` (symmetric
heap over ``sshmem`` segments), ``spml`` (put/get over the OMPI BTLs —
``spml/yoda``), ``atomic`` (AMOs), ``scoll`` (collectives, including
the delegate-to-MPI ``scoll/mpi`` component). TPU-native recast:

- The symmetric heap is per-PE HBM: a symmetric allocation is one
  device array with a leading PE axis (slice i in PE i's HBM) — the
  same "address" (python handle) is valid for every PE, which is the
  whole symmetric-heap contract (``oshmem/mca/memheap``). An address
  inside it is a flat element ``offset`` (put/get: ``dest + offset``)
  or ``index`` (AMOs: ``&x[i]``); left out, an operation acts on the
  whole slot (AMOs elementwise: a documented extension).
- Who the origin is: in driver mode one controller plays every PE, so
  ``pe=`` is always explicit and ``my_pe`` is ``None``. Under
  ``tpurun`` each process is the origin of its own calls: with one PE
  per process ``my_pe`` is the caller's rank, ``wait_until``/``test``
  default to it, and ``local(pe)`` refuses a PE of another process
  (no load/store path: use ``get``).
- put/get queue onto the underlying RMA window machinery (the spml →
  BTL path, here spml → osc) and complete at ``quiet``/``barrier_all``
  — OpenSHMEM's own completion rule. Puts and non-fetching AMOs are
  posted; ``get`` and the fetching AMOs are blocking: they drain the
  allocation's queue, issue their one request and flush its target.
- the **planned bulk path**: posted puts/AMOs between
  ``quiet()``/``fence()`` boundaries are batched
  per symmetric allocation as light host-side tuples — no per-call
  window queueing — and drained as ONE window epoch: the drain hands
  each source to the window as it is (a device array stays the
  caller's object, an AMO's host operand stays on the host until the
  batch frame or the epoch program takes it: ``osc/window._payload``),
  which the osc access-plan machinery (``osc/plan``)
  closes as one fused device program per (allocation, signature), or,
  for a PE in another process, ships as one batch to its home.
  Posted ops therefore follow ``shmem_put_nbi`` source-buffer rules:
  the source is reusable after ``quiet()``. Blocking calls (get,
  fetch AMOs, ``wait_until``, ``local``) drain first, so per-call
  ordering is unchanged.
- scoll delegates to the coll framework over the same communicator
  (exactly what ``scoll/mpi`` does to OMPI).
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs as _obs
from .. import ops as ops_mod
from ..mca import pvar
from ..obs import spans as _spans
from ..osc.window import Window
from ..utils import output
from ..utils.errors import ErrorCode, MPIError

_log = output.stream("shmem")

_heap_bytes = pvar.highwatermark(
    "shmem_heap_bytes", "symmetric heap bytes allocated"
)
_bulk_ops = pvar.counter(
    "shmem_bulk_ops",
    "posted SHMEM ops deferred into the per-allocation bulk queue",
)
_bulk_flushes = pvar.counter(
    "shmem_bulk_flushes",
    "bulk-queue drains (one planned window epoch per allocation)",
)
_ops = pvar.counter(
    "shmem_ops",
    "SHMEM puts, gets and AMOs issued, posted or blocking",
)
_blocking_ops = pvar.counter(
    "shmem_blocking_ops",
    "blocking SHMEM operations: gets and fetching AMOs (one request "
    "and one flush of its target each)",
)
_quiets = pvar.counter(
    "shmem_quiets", "quiet() calls (fence and barrier_all included)"
)

_CMPS = {
    "eq": np.equal, "ne": np.not_equal,
    "gt": np.greater, "ge": np.greater_equal,
    "lt": np.less, "le": np.less_equal,
}


class SymmetricArray:
    """One symmetric allocation: ``shape`` per PE, PE i's block in PE
    i's HBM. The handle itself is the symmetric address."""

    def __init__(self, ctx: "ShmemCtx", win: Window) -> None:
        self._ctx = ctx
        self._win = win
        win.lock_all()  # SHMEM has no epochs: one standing passive epoch

    @property
    def shape(self) -> Tuple[int, ...]:
        return self._win.shape

    @property
    def dtype(self):
        return self._win.dtype

    def _row(self, pe: int) -> Optional[int]:
        """The row of this process's storage that holds PE ``pe``, or
        None where ``pe`` lives in another controller process."""
        comm = self._win.comm
        if not getattr(comm, "spans_processes", False):
            return pe
        lr = list(comm.local_comm_ranks)
        return lr.index(pe) if pe in lr else None

    def local(self, pe: int) -> jax.Array:
        """PE ``pe``'s local view (shmem_ptr analogue; driver mode sees
        every PE). On a unified multi-controller world only
        same-process PEs are addressable — the reference's shmem_ptr
        returns NULL for PEs without a load/store path
        (``oshmem/shmem/c/shmem_ptr.c``); use :meth:`ShmemCtx.get`
        for remote PEs."""
        self._ctx._drain(self)
        row = self._row(pe)
        if row is None:
            raise MPIError(
                ErrorCode.ERR_RMA_SHARED,
                f"shmem_ptr: PE {pe} lives in another controller "
                "process (no load/store path); use get()",
            )
        return self._win.read()[row]

    def free(self) -> None:
        self._ctx._drain(self)  # posted ops must land, not vanish
        self._win.unlock_all()
        self._win.free()
        self._ctx._allocs.pop(self, None)


class ShmemCtx:
    """The OpenSHMEM world (``shmem_init`` state)."""

    def __init__(self, comm) -> None:
        self.comm = comm
        # in allocation order: ``finalize`` frees collectively, so every
        # process has to walk its allocations in the same order
        self._allocs: Dict["SymmetricArray", None] = {}
        # planned bulk path: per-allocation queues of light
        # (kind, pe, data, op, index, disp) tuples — window queueing
        # is deferred to the drain, where the whole batch closes as
        # ONE planned window epoch
        self._bulk: Dict["SymmetricArray", List[Tuple]] = {}

    # -- setup / query (shmem.h accessors) ---------------------------------
    @property
    def n_pes(self) -> int:
        return self.comm.size

    @property
    def my_pe(self) -> Optional[int]:
        """shmem_my_pe: the caller's rank on the communicator when each
        process is one PE (``tpurun``); None in driver mode, where one
        controller plays every PE and ``pe=`` stays explicit."""
        comm = self.comm
        if getattr(comm, "spans_processes", False):
            lr = comm.local_comm_ranks
            if len(lr) == 1:
                return int(lr[0])
        return None

    def malloc(self, shape: Tuple[int, ...], dtype=jnp.float32
               ) -> SymmetricArray:
        """shmem_malloc: symmetric allocation (memheap analogue)."""
        from ..osc.window import win_allocate

        win = win_allocate(self.comm, tuple(shape), dtype)
        arr = SymmetricArray(self, win)
        self._allocs[arr] = None
        _heap_bytes.add(
            int(np.prod(shape)) * jnp.dtype(dtype).itemsize * self.n_pes
        )
        return arr

    # -- the planned bulk path ---------------------------------------------
    def _post(self, sym: SymmetricArray, kind: str, pe: int, data,
              op, index, disp=None) -> None:
        """Defer one posted op into ``sym``'s bulk queue (nbi
        semantics: the source lands at the next drain). The tuple
        carries the frozen Op OBJECT — the drain replays it through
        the window queue, so osc/plan keys the fused program by the
        object, never by an op name — and where the op has one its
        element ``index`` or the displacement ``disp`` of its range. A
        host scalar stays one until the drain, and on the host after
        it: the window queues it as a 0-d host array."""
        self._bulk.setdefault(sym, []).append(
            (kind, pe, data, op, index, disp))
        _bulk_ops.add()
        _ops.add()

    def _drain(self, sym: SymmetricArray) -> None:
        """Replay ``sym``'s bulk queue as one window epoch and flush:
        the whole batch closes as one fused device program per
        (allocation, signature) via the osc access-plan cache."""
        q = self._bulk.pop(sym, None)
        if not q:
            return
        win = sym._win
        with _obs.span(_spans.SHMEM_DRAIN,
                       journal=("shmem_bulk_flush", "osc"), ops=len(q),
                       bytes=sum(_spans.nbytes(t[2]) for t in q),
                       cid=win.comm.cid):
            for kind, pe, data, op, index, disp in q:
                if kind == "put":
                    win.put(data, pe, index=index, disp=disp)
                else:  # acc
                    win.accumulate(data, pe, op=op, index=index)
            win.flush_all()
        _bulk_flushes.add()

    @staticmethod
    def _in_range(sym: SymmetricArray, offset, nelems) -> Tuple[int, int]:
        """``nelems`` elements from flat ``offset`` lie inside the
        allocation, or the window's own ``ERR_RMA_RANGE``: raised at
        the call, before anything is queued or drained."""
        offset, nelems = int(offset), int(nelems)
        sym._win._check_range(offset, nelems)
        return offset, nelems

    # -- data movement (spml put/get) --------------------------------------
    def put(self, sym: SymmetricArray, data, pe: int,
            offset: Optional[int] = None) -> None:
        """shmem_put: posted; completes at quiet/barrier_all. With
        ``offset``, shmem_putmem at ``dest + offset``: ``data.size``
        consecutive elements of the allocation (flattened in C order)
        from that flat offset; without, the whole slot."""
        if offset is not None:
            if not hasattr(data, "size"):
                data = np.asarray(data)
            offset, _ = self._in_range(sym, offset, data.size)
        self._post(sym, "put", pe, data, None, None, offset)

    def get(self, sym: SymmetricArray, pe: int,
            offset: Optional[int] = None,
            nelems: Optional[int] = None) -> jax.Array:
        """shmem_get: blocking (pending ops of the allocation land
        first). With ``offset`` and ``nelems``, shmem_getmem at
        ``src + offset``: that many consecutive elements, 1-D; without,
        the whole slot."""
        if offset is not None and nelems is not None:
            offset, nelems = self._in_range(sym, offset, nelems)
        win = sym._win
        _ops.add()
        _blocking_ops.add()
        with _obs.span(_spans.SHMEM_GET, bytes=win.dtype.itemsize * (
                win._slot_elems() if nelems is None else nelems)):
            self._drain(sym)
            req = win.get(pe, disp=offset, count=nelems)
            win.flush(pe)
            return req.value

    def put_elem(self, sym: SymmetricArray, value, index, pe: int) -> None:
        """Scalar put at a flat index (shmem_p): a true single-element
        posted put — O(1) staged bytes, no read-modify-write of the
        whole slot."""
        self._post(sym, "put", pe, value, None, self._at(sym, index))

    # -- atomics (oshmem/mca/atomic) ---------------------------------------
    # ``index`` given: ONE element at that flat index of the allocation,
    # as OpenSHMEM's AMOs act (``shmem_int_fadd(&x[i], v, pe)``), and
    # ``value`` a scalar; left out: the whole slot, elementwise.
    def _at(self, sym: SymmetricArray, index) -> Optional[int]:
        """The element index checked like a range of one, at the call."""
        return None if index is None else self._in_range(sym, index, 1)[0]

    def atomic_add(self, sym: SymmetricArray, value, pe: int,
                   index: Optional[int] = None) -> None:
        self._post(sym, "acc", pe, value, ops_mod.SUM, self._at(sym, index))

    def atomic_inc(self, sym: SymmetricArray, pe: int,
                   index: Optional[int] = None) -> None:
        """shmem_inc: add 1 (the counter idiom)."""
        self.atomic_add(sym, 1, pe, index)

    def atomic_set(self, sym: SymmetricArray, value, pe: int,
                   index: Optional[int] = None) -> None:
        """shmem_atomic_set: unconditional replace (no fetch)."""
        self._post(sym, "acc", pe, value, ops_mod.REPLACE,
                   self._at(sym, index))

    def _fetching(self, sym: SymmetricArray, kind: str, pe: int, index,
                  issue) -> jax.Array:
        """A fetching AMO: earlier posted ops of the allocation land,
        then ``issue(window, index)`` queues the one request and its
        target is flushed once; the value from before it comes back."""
        index = self._at(sym, index)
        _ops.add()
        _blocking_ops.add()
        with _obs.span(_spans.SHMEM_AMO, kind=kind):
            self._drain(sym)
            req = issue(sym._win, index)
            sym._win.flush(pe)
            return req.value

    def atomic_fetch_add(self, sym: SymmetricArray, value, pe: int,
                         index: Optional[int] = None) -> jax.Array:
        return self._fetching(
            sym, "fetch_add", pe, index, lambda win, i: win.fetch_and_op(
                value, pe, op=ops_mod.SUM, index=i))

    def atomic_fetch_inc(self, sym: SymmetricArray, pe: int,
                         index: Optional[int] = None) -> jax.Array:
        return self.atomic_fetch_add(sym, 1, pe, index)

    def atomic_fetch(self, sym: SymmetricArray, pe: int,
                     index: Optional[int] = None) -> jax.Array:
        """shmem_atomic_fetch: an atomic read = fetch_add(0)."""
        return self.atomic_fetch_add(sym, 0, pe, index)

    def atomic_swap(self, sym: SymmetricArray, value, pe: int,
                    index: Optional[int] = None) -> jax.Array:
        return self._fetching(
            sym, "swap", pe, index, lambda win, i: win.fetch_and_op(
                value, pe, op=ops_mod.REPLACE, index=i))

    def atomic_compare_swap(self, sym: SymmetricArray, cond, value,
                            pe: int, index: Optional[int] = None
                            ) -> jax.Array:
        return self._fetching(
            sym, "cswap", pe, index, lambda win, i: win.compare_and_swap(
                value, cond, pe, index=i))

    # -- point-to-point synchronization (shmem_wait_until) -----------------
    def wait_until(self, sym: SymmetricArray, cmp: str, value, *,
                   pe: Optional[int] = None, timeout_s: float = 30.0,
                   poll_s: float = 0.001) -> jax.Array:
        """Block until pe's symmetric variable satisfies the
        comparison — the SHMEM p2p synchronization primitive
        (``shmem_wait_until``; cmp in eq/ne/gt/ge/lt/le). ``pe``
        defaults to the caller's own PE (``my_pe``); one controller
        that plays several PEs names it. A PE this process owns is
        polled in its local slot (``local(pe)``: no RMA request; in
        driver mode the poll drains, so posted ops land); a PE of
        another process through ``get``."""
        if cmp not in _CMPS:
            raise MPIError(ErrorCode.ERR_ARG,
                           f"wait_until cmp must be one of {list(_CMPS)}")
        if pe is None:
            pe = self.my_pe
        if pe is None:
            raise MPIError(
                ErrorCode.ERR_ARG,
                "wait_until: pe= is required where one process plays "
                "several PEs (my_pe is None)")
        owned = sym._row(pe) is not None
        deadline = time.monotonic() + timeout_s
        while True:
            cur = np.asarray(sym.local(pe) if owned else self.get(sym, pe))
            if bool(np.all(_CMPS[cmp](cur, value))):
                return jnp.asarray(cur)
            if time.monotonic() > deadline:
                raise MPIError(
                    ErrorCode.ERR_PENDING,
                    f"wait_until({cmp}, {value}) timed out; last "
                    f"value {cur!r}",
                )
            time.sleep(poll_s)

    def test(self, sym: SymmetricArray, cmp: str, value, *,
             pe: Optional[int] = None) -> bool:
        """Nonblocking wait_until (shmem_test)."""
        try:
            self.wait_until(sym, cmp, value, pe=pe, timeout_s=0.0)
            return True
        except MPIError as e:
            if e.code is ErrorCode.ERR_PENDING:  # just not yet
                return False
            raise  # real failures (freed window, bad pe) must surface

    # -- ordering (shmem_quiet / shmem_fence) ------------------------------
    def quiet(self) -> None:
        """Complete all outstanding puts/AMOs (shmem_quiet): drain
        every allocation's bulk queue (one planned epoch each) and
        flush anything queued outside the bulk path. An allocation
        with nothing queued and nothing pending costs nothing."""
        _quiets.add()
        with _obs.span(_spans.SHMEM_QUIET) as sp:
            allocs = ops = 0
            for a in list(self._allocs):
                q = self._bulk.get(a)
                if q:
                    allocs += 1
                    ops += len(q)
                    self._drain(a)
                elif a._win._pending:
                    allocs += 1
                    a._win.flush_all()
            sp.set_metadata(allocs=allocs, ops=ops)

    def fence(self) -> None:
        """Ordering only; operations of one origin apply in the order
        issued, so fence == quiet here (stronger is allowed)."""
        self.quiet()

    def barrier_all(self) -> None:
        self.quiet()
        self.comm.barrier()

    # -- collectives (scoll -> coll framework, the scoll/mpi path) ---------
    def broadcast(self, x, root: int = 0):
        return self.comm.bcast(x, root=root)

    def fcollect(self, x):
        """shmem_fcollect: concatenation of every PE's block."""
        return self.comm.allgather(x)

    def alltoall(self, x):
        return self.comm.alltoall(x)

    def collect(self, bufs):
        """shmem_collect: ragged per-PE blocks concatenated in PE
        order (fcollect's equal-size constraint lifted) — rides the
        v-variant allgatherv kernel."""
        return self.comm.allgatherv(bufs)

    def sum_to_all(self, x):
        return self.comm.allreduce(x, ops_mod.SUM)

    def prod_to_all(self, x):
        return self.comm.allreduce(x, ops_mod.PROD)

    def max_to_all(self, x):
        return self.comm.allreduce(x, ops_mod.MAX)

    def min_to_all(self, x):
        return self.comm.allreduce(x, ops_mod.MIN)

    def and_to_all(self, x):
        return self.comm.allreduce(x, ops_mod.BAND)

    def or_to_all(self, x):
        return self.comm.allreduce(x, ops_mod.BOR)

    def xor_to_all(self, x):
        return self.comm.allreduce(x, ops_mod.BXOR)

    # -- distributed locks (shmem_set_lock/clear_lock/test_lock) -----------
    def lock_create(self) -> SymmetricArray:
        """A SHMEM lock: a symmetric word, 0 = free, pe+1 = held by pe
        (``shmem.h.in:167`` lock surface; the reference's
        ``oshmem/mca/atomic`` backs its locks with the same AMOs).
        The lock word lives on its home PE (0), as in the reference's
        home-PE queue discipline — contenders CAS the home copy."""
        lk = self.malloc((1,), jnp.int32)
        return lk

    def set_lock(self, lock: SymmetricArray, *, pe: int,
                 timeout_s: float = 30.0) -> None:
        """Acquire: spin CAS(0 -> pe+1) on the home PE with backoff.
        Deadlock-by-self (re-acquiring a held lock) raises instead of
        hanging — driver mode can detect it, so it does."""
        me = int(pe) + 1
        deadline = time.monotonic() + timeout_s
        delay = 0.0005
        while True:
            old = int(np.asarray(
                self.atomic_compare_swap(lock, 0, me, pe=0)
            ).reshape(-1)[0])
            if old == 0:
                return
            if old == me:
                raise MPIError(
                    ErrorCode.ERR_OTHER,
                    f"PE {pe} already holds this lock (shmem locks are "
                    "not recursive)",
                )
            if time.monotonic() > deadline:
                raise MPIError(
                    ErrorCode.ERR_PENDING,
                    f"set_lock: PE {old - 1} held the lock for "
                    f">{timeout_s}s",
                )
            time.sleep(delay)
            delay = min(delay * 2, 0.01)

    def test_lock(self, lock: SymmetricArray, *, pe: int) -> bool:
        """One CAS attempt; True = acquired (shmem_test_lock's 0)."""
        old = int(np.asarray(
            self.atomic_compare_swap(lock, 0, int(pe) + 1, pe=0)
        ).reshape(-1)[0])
        return old == 0

    def clear_lock(self, lock: SymmetricArray, *, pe: int) -> None:
        """Release; only the holder may clear (erroneous otherwise in
        OpenSHMEM — detected here rather than corrupting the word)."""
        me = int(pe) + 1
        old = int(np.asarray(
            self.atomic_compare_swap(lock, me, 0, pe=0)
        ).reshape(-1)[0])
        if old != me:
            raise MPIError(
                ErrorCode.ERR_OTHER,
                f"clear_lock by PE {pe} but the lock is "
                + ("free" if old == 0 else f"held by PE {old - 1}"),
            )

    def finalize(self) -> None:
        for a in list(self._allocs):
            a.free()


_ctx: Optional[ShmemCtx] = None


def shmem_init(comm=None) -> ShmemCtx:
    """shmem_init: reuses the runtime (OSHMEM sits beside OMPI on the
    same ORTE, SURVEY §1.4)."""
    global _ctx
    if _ctx is not None:
        return _ctx
    if comm is None:
        from ..runtime import runtime as rt_mod

        comm = rt_mod.init()
    _ctx = ShmemCtx(comm)
    return _ctx


def shmem_finalize() -> None:
    global _ctx
    if _ctx is not None:
        _ctx.finalize()
        _ctx = None
