"""OpenSHMEM — symmetric heap + put/get/AMO + collectives.

The reference's OSHMEM stack (SURVEY §1.4): ``memheap`` (symmetric
heap over ``sshmem`` segments), ``spml`` (put/get over the OMPI BTLs —
``spml/yoda``), ``atomic`` (AMOs), ``scoll`` (collectives, including
the delegate-to-MPI ``scoll/mpi`` component). TPU-native recast:

- The symmetric heap is per-PE HBM: a symmetric allocation is one
  device array with a leading PE axis (slice i in PE i's HBM) — the
  same "address" (python handle) is valid for every PE, which is the
  whole symmetric-heap contract (``oshmem/mca/memheap``).
- put/get queue onto the underlying RMA window machinery (the spml →
  BTL path, here spml → osc) and complete at ``quiet``/``barrier_all``
  — OpenSHMEM's own completion rule. Fetch AMOs and get are blocking
  (they flush), put/add are posted.
- the **planned bulk path**: posted puts/AMOs between
  ``quiet()``/``fence()`` boundaries are batched
  per symmetric allocation as light host-side tuples — no per-call
  ``jnp.asarray``, no per-call window queueing — and drained as ONE
  window epoch, which the osc access-plan machinery (``osc/plan``)
  closes as one fused device program per (allocation, signature).
  Posted ops therefore follow ``shmem_put_nbi`` source-buffer rules:
  the source is reusable after ``quiet()``. Blocking calls (get,
  fetch AMOs, ``wait_until``, ``local``) drain first, so per-call
  ordering is unchanged.
- scoll delegates to the coll framework over the same communicator
  (exactly what ``scoll/mpi`` does to OMPI).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs as _obs
from .. import ops as ops_mod
from ..mca import pvar
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

    def local(self, pe: int) -> jax.Array:
        """PE ``pe``'s local view (shmem_ptr analogue; driver mode sees
        every PE). On a unified multi-controller world only
        same-process PEs are addressable — the reference's shmem_ptr
        returns NULL for PEs without a load/store path
        (``oshmem/shmem/c/shmem_ptr.c``); use :meth:`ShmemCtx.get`
        for remote PEs."""
        self._ctx._drain(self)
        self._win.flush_all()
        comm = self._win.comm
        if getattr(comm, "spans_processes", False):
            lr = list(comm.local_comm_ranks)
            if pe not in lr:
                raise MPIError(
                    ErrorCode.ERR_RMA_SHARED,
                    f"shmem_ptr: PE {pe} lives in another controller "
                    "process (no load/store path); use get()",
                )
            return self._win.read()[lr.index(pe)]
        return self._win.read()[pe]

    def free(self) -> None:
        self._ctx._drain(self)  # posted ops must land, not vanish
        self._win.unlock_all()
        self._win.free()
        self._ctx._allocs.discard(self)


class ShmemCtx:
    """The OpenSHMEM world (``shmem_init`` state)."""

    def __init__(self, comm) -> None:
        self.comm = comm
        self._allocs: set = set()
        # planned bulk path: per-allocation queues of light
        # (kind, pe, data, op, index) tuples — jnp.asarray and window
        # queueing are deferred to the drain, where the whole batch
        # closes as ONE planned window epoch
        self._bulk: Dict["SymmetricArray", List[Tuple]] = {}

    # -- setup / query (shmem.h accessors) ---------------------------------
    @property
    def n_pes(self) -> int:
        return self.comm.size

    def malloc(self, shape: Tuple[int, ...], dtype=jnp.float32
               ) -> SymmetricArray:
        """shmem_malloc: symmetric allocation (memheap analogue)."""
        from ..osc.window import win_allocate

        win = win_allocate(self.comm, tuple(shape), dtype)
        arr = SymmetricArray(self, win)
        self._allocs.add(arr)
        _heap_bytes.add(
            int(np.prod(shape)) * jnp.dtype(dtype).itemsize * self.n_pes
        )
        return arr

    # -- the planned bulk path ---------------------------------------------
    def _post(self, sym: SymmetricArray, kind: str, pe: int, data,
              op, index) -> None:
        """Defer one posted op into ``sym``'s bulk queue (nbi
        semantics: the source lands at the next drain). The tuple
        carries the frozen Op OBJECT — the drain replays it through
        the window queue, so osc/plan keys the fused program by the
        object, never by an op name."""
        self._bulk.setdefault(sym, []).append((kind, pe, data, op, index))
        _bulk_ops.add()

    def _drain(self, sym: SymmetricArray) -> None:
        """Replay ``sym``'s bulk queue as one window epoch and flush:
        the whole batch closes as one fused device program per
        (allocation, signature) via the osc access-plan cache."""
        q = self._bulk.pop(sym, None)
        if not q:
            return
        rec = _obs.enabled
        t0 = time.perf_counter() if rec else 0.0
        win = sym._win
        for kind, pe, data, op, index in q:
            if kind == "put":
                win.put(jnp.asarray(data), pe, index=index)
            else:  # acc
                win.accumulate(jnp.asarray(data), pe, op=op, index=index)
        win.flush_all()
        _bulk_flushes.add()
        if rec and _obs.enabled:
            _obs.record(
                "shmem_bulk_flush", "osc", t0,
                time.perf_counter() - t0, nbytes=sum(
                    int(getattr(d, "nbytes", 0) or 0)
                    for _, _, d, _, _ in q),
                comm_id=win.comm.cid)

    # -- data movement (spml put/get) --------------------------------------
    def put(self, sym: SymmetricArray, data, pe: int) -> None:
        """shmem_put: posted; completes at quiet/barrier_all."""
        self._post(sym, "put", pe, data, None, None)

    def get(self, sym: SymmetricArray, pe: int) -> jax.Array:
        """shmem_get: blocking (flushes pending ops first)."""
        self._drain(sym)
        sym._win.flush_all()
        req = sym._win.get(pe)
        sym._win.flush_all()
        return req.value

    def put_elem(self, sym: SymmetricArray, value, index, pe: int) -> None:
        """Scalar put at a flat index (shmem_p): a true single-element
        posted put — O(1) staged bytes, no read-modify-write of the
        whole slot."""
        self._post(sym, "put", pe, value, None, int(index))

    # -- atomics (oshmem/mca/atomic) ---------------------------------------
    def atomic_add(self, sym: SymmetricArray, value, pe: int) -> None:
        self._post(sym, "acc", pe, value, ops_mod.SUM, None)

    def atomic_fetch_add(self, sym: SymmetricArray, value, pe: int
                         ) -> jax.Array:
        self._drain(sym)  # fetch observes earlier posted ops
        req = sym._win.fetch_and_op(jnp.asarray(value), pe, op=ops_mod.SUM)
        sym._win.flush(pe)
        return req.value

    def atomic_swap(self, sym: SymmetricArray, value, pe: int) -> jax.Array:
        self._drain(sym)
        req = sym._win.fetch_and_op(jnp.asarray(value), pe,
                                    op=ops_mod.REPLACE)
        sym._win.flush(pe)
        return req.value

    def atomic_compare_swap(self, sym: SymmetricArray, cond, value, pe: int
                            ) -> jax.Array:
        self._drain(sym)
        req = sym._win.compare_and_swap(jnp.asarray(value),
                                        jnp.asarray(cond), pe)
        sym._win.flush(pe)
        return req.value

    def atomic_inc(self, sym: SymmetricArray, pe: int) -> None:
        """shmem_inc: add 1 (the counter idiom)."""
        self.atomic_add(sym, jnp.ones(sym.shape, sym.dtype), pe)

    def atomic_fetch_inc(self, sym: SymmetricArray, pe: int) -> jax.Array:
        return self.atomic_fetch_add(
            sym, jnp.ones(sym.shape, sym.dtype), pe
        )

    def atomic_set(self, sym: SymmetricArray, value, pe: int) -> None:
        """shmem_atomic_set: unconditional replace (no fetch)."""
        self._post(sym, "acc", pe, value, ops_mod.REPLACE, None)

    def atomic_fetch(self, sym: SymmetricArray, pe: int) -> jax.Array:
        """shmem_atomic_fetch: an atomic read = fetch_add(0)."""
        return self.atomic_fetch_add(
            sym, jnp.zeros(sym.shape, sym.dtype), pe
        )

    # -- point-to-point synchronization (shmem_wait_until) -----------------
    def wait_until(self, sym: SymmetricArray, cmp: str, value, *,
                   pe: int, timeout_s: float = 30.0,
                   poll_s: float = 0.001) -> jax.Array:
        """Block until pe's symmetric variable satisfies the
        comparison — the SHMEM p2p synchronization primitive
        (``shmem_wait_until``; cmp in eq/ne/gt/ge/lt/le). ``pe`` is
        explicit because one controller plays every PE in driver mode
        (in a per-process deployment it would default to the caller's
        own PE). Progress comes from other ranks' posted puts/AMOs
        being flushed (the poll flushes so posted ops land)."""
        import time as _time

        import numpy as _np

        cmps = {
            "eq": _np.equal, "ne": _np.not_equal,
            "gt": _np.greater, "ge": _np.greater_equal,
            "lt": _np.less, "le": _np.less_equal,
        }
        if cmp not in cmps:
            raise MPIError(ErrorCode.ERR_ARG,
                           f"wait_until cmp must be one of {list(cmps)}")
        target_pe = pe
        deadline = _time.monotonic() + timeout_s
        while True:
            cur = _np.asarray(self.get(sym, target_pe))
            if bool(_np.all(cmps[cmp](cur, value))):
                return jnp.asarray(cur)
            if _time.monotonic() > deadline:
                raise MPIError(
                    ErrorCode.ERR_PENDING,
                    f"wait_until({cmp}, {value}) timed out; last "
                    f"value {cur!r}",
                )
            _time.sleep(poll_s)

    def test(self, sym: SymmetricArray, cmp: str, value, *,
             pe: int) -> bool:
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
        flush anything queued outside the bulk path."""
        for a in list(self._allocs):
            self._drain(a)
            a._win.flush_all()

    def fence(self) -> None:
        """Ordering only; driver mode applies in submission order, so
        fence == quiet here (stronger is allowed)."""
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
        import time as _time

        me = int(pe) + 1
        deadline = _time.monotonic() + timeout_s
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
            if _time.monotonic() > deadline:
                raise MPIError(
                    ErrorCode.ERR_PENDING,
                    f"set_lock: PE {old - 1} held the lock for "
                    f">{timeout_s}s",
                )
            _time.sleep(delay)
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
