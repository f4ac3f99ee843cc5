"""MPI_Win windows over device buffers.

The reference's osc framework (``ompi/mca/osc/osc.h:205-338``: put/get/
accumulate/CAS/fetch-op + fence/PSCW/lock epochs, ``osc/rdma`` data
movement) recast for a single-controller device mesh:

- A window is a device-resident array with a leading rank axis — slice
  i lives in rank i's HBM (NamedSharding over the comm's sub-mesh), the
  MPI_Win_allocate memory model.
- RMA calls during an epoch queue; closing the epoch (fence, unlock,
  complete, flush) applies them in submission order as ONE jitted
  sharded program per epoch — the MPI completion rule ("RMA completes
  at synchronization") is the natural XLA execution model, and the
  epoch batch is the osc/rdma "aggregate and issue at sync" strategy.
- get/get_accumulate/fetch_and_op/compare_and_swap return Requests
  whose values materialize at epoch close.
- A payload waits in the queue where the caller had it: a
  ``jax.Array`` is queued as the object that was passed, anything on
  the host (a Python scalar, a numpy scalar or array, a list) as a
  host snapshot of the shape and dtype ``jnp.asarray`` gives it —
  it reaches a device as an argument of the epoch program, or never,
  when its target's home is another process (``osc/wire_win``).

Epoch rules enforced (``ompi/win/win.c`` access-epoch checks): RMA
outside any epoch raises; fence/lock/PSCW cannot be mixed.
"""

from __future__ import annotations

import enum
import itertools
import math
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import obs as _obs
from ..mca import pvar
from ..obs import spans as _spans
from ..ops.op import Op, REPLACE, SUM
from ..request.request import Request, Status
from ..utils import output
from ..utils.errors import ErrorCode, MPIError

_log = output.stream("osc")

_epoch_count = pvar.counter("osc_epochs", "RMA epochs closed")
_rma_ops = pvar.counter("osc_rma_ops", "RMA operations issued")
_host_payloads = pvar.counter(
    "osc_host_payloads",
    "RMA payloads (data and compare values) queued from host memory: "
    "they never touched a device at the origin",
)
_epoch_programs = pvar.counter(
    "osc_epoch_programs", "distinct compiled epoch-close programs"
)
_epoch_dispatches = pvar.counter(
    "osc_epoch_dispatches", "epoch-close program invocations"
)

#: compiled epoch-close programs, keyed by (op count padded to a power
#: of two, window shape, dtype, ordered distinct (kind, op, indexed)
#: branches, scalar-payload mode) — padding keeps the cache O(log n)
#: per branch set across varying epoch lengths
_program_cache: Dict[Tuple, object] = {}

#: one epoch program compiles/executes at a time, PROCESS-wide: two
#: threads driving first-call jit compilation/execution concurrently
#: (distinct windows, so the per-window _op_lock does not serialize
#: them) deadlock inside this jaxlib — both park in prog() forever
#: (reproduced ~1 in 3 by test_shmem_topo's lock-contention test, the
#: flight recorder's own thread stacks pinpointed it). Epoch programs
#: are sub-ms on driver-mode windows, so serializing dispatch costs
#: nothing measurable.
_dispatch_lock = threading.Lock()

LOCK_EXCLUSIVE = 1
LOCK_SHARED = 2


class _EpochKind(enum.Enum):
    NONE = "none"
    FENCE = "fence"
    LOCK = "lock"
    PSCW = "pscw"


class _PendingOp:
    __slots__ = ("kind", "target", "data", "op", "request", "compare",
                 "index", "status_rank", "disp", "count")

    def __init__(self, kind, target, data=None, op=None, request=None,
                 compare=None, index=None, status_rank=None,
                 disp=None, count=None) -> None:
        self.kind = kind
        self.target = target
        self.data = data
        self.op = op
        self.request = request
        self.compare = compare
        # flat element offset within the target slot (MPI target_disp
        # for single-element ops); None = whole-slot operation
        self.index = index
        # a RANGED operation: ``count`` consecutive elements of the
        # target slot (flattened in C order) from flat offset ``disp``
        # (MPI target_disp + target_count); None = not ranged
        self.disp = disp
        self.count = count
        # the COMM rank to report in the request's Status when target
        # has been remapped to a storage row (spanning windows)
        self.status_rank = status_rank


def _payload(x):
    """An origin payload as it waits in the queue. A ``jax.Array`` is
    the caller's own object: no copy, no fetch, no launch. Anything
    else is on the host and stays there, as a numpy array of the shape
    and dtype ``jnp.asarray`` gives it (jax's dtype rules: a Python
    int is int32 and one outside it raises, int64 becomes int32 with
    x64 off) — never a bare Python scalar, which an epoch signature
    would key by VALUE (``coll/plan.arg_desc``). It is a snapshot
    taken at the call: a caller may rewrite their buffer before the
    epoch closes."""
    if isinstance(x, jax.Array):
        return x
    # the leaves and the dtype as ``jnp.array`` finds them
    leaves = jax.tree_util.tree_leaves(
        x, is_leaf=lambda v: not isinstance(v, (list, tuple)))
    if any(v is None or isinstance(v, jax.Array) for v in leaves):
        # a None (it raises) or a list of device arrays (joined there)
        return jnp.asarray(x)
    return np.array(x, dtype=jax.dtypes.result_type(*(leaves or [float])))


# predefined window attributes (mpi.h MPI_WIN_BASE..MPI_WIN_MODEL)
WIN_BASE = "win_base"
WIN_SIZE = "win_size"
WIN_DISP_UNIT = "win_disp_unit"
WIN_CREATE_FLAVOR = "win_create_flavor"
WIN_MODEL = "win_model"
# create flavors (MPI_WIN_FLAVOR_*)
FLAVOR_CREATE = 1
FLAVOR_ALLOCATE = 2
FLAVOR_DYNAMIC = 3
FLAVOR_SHARED = 4
# memory models: driver mode is one address space with epoch-close
# visibility = MPI_WIN_UNIFIED semantics
MODEL_SEPARATE = 1
MODEL_UNIFIED = 2


class Window:
    def __init__(self, comm, base: jax.Array, name: str = "") -> None:
        if getattr(comm, "spans_processes", False):
            # guard against silent mis-sharding: comm.submesh covers
            # only LOCAL members on a spanning comm, so placing
            # comm.size rows over it would scatter remote ranks' slices
            # onto local devices — the wire window stores local slices
            # and ships remote RMA to its home (osc/wire_win.py)
            raise MPIError(
                ErrorCode.ERR_WIN,
                f"{comm.name} spans controller processes; construct "
                "windows through win_create/win_allocate (wire-window "
                "path), not Window() directly",
            )
        if base.shape[0] != comm.size:
            raise MPIError(
                ErrorCode.ERR_WIN,
                f"window base leading axis {base.shape[0]} != comm size "
                f"{comm.size}",
            )
        self._init_state(comm, base, name)

    def _init_state(self, comm, base, name: str) -> None:
        """Shared field setup (subclasses with a different leading-axis
        contract — the spanning-comm wire window — reuse this so new
        fields cannot silently diverge)."""
        self.comm = comm
        self.name = name or f"win{id(self):x}"
        self._shard = NamedSharding(comm.submesh, P("rank"))
        self._data = jax.device_put(jnp.asarray(base), self._shard)
        self._epoch = _EpochKind.NONE
        self._locked: Dict[int, int] = {}  # target -> lock type
        self._pending: List[_PendingOp] = []
        # one controller, possibly many threads (a producer thread
        # posting AMOs while a waiter polls with get/flush): the
        # pending queue and its apply/commit must be atomic or
        # concurrent flushes lose ops
        import threading as _threading

        self._op_lock = _threading.RLock()
        self._group_exposed = None  # PSCW exposure group
        self._freed = False
        self._flavor = FLAVOR_CREATE  # constructors override
        self._attrs: Dict[int, object] = {}  # user keyvals (win_keyval)
        # frozen per-epoch-signature access plans and precomposed
        # remote-batch wire frames (osc/plan); evicted at free()
        self._access_plans: Dict[Tuple, Any] = {}
        self._batch_templates: Dict[Tuple, Any] = {}

    # -- queries -----------------------------------------------------------
    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self._data.shape[1:])

    @property
    def dtype(self):
        return self._data.dtype

    def read(self) -> jax.Array:
        """Local loads of the whole window (valid outside access epochs
        or after a flush; driver mode sees every rank's slice)."""
        return self._data

    def set_attr(self, keyval, value) -> None:
        """MPI_Win_set_attr with a user keyval (the same Keyval
        objects ``comm.create_keyval`` mints — ``win.c`` shares one
        attribute machinery across comm/win/datatype)."""
        if self._freed:
            raise MPIError(ErrorCode.ERR_WIN, f"{self.name} freed")
        self._attrs[keyval.id] = value

    def delete_attr(self, keyval) -> None:
        from ..comm.communicator import _keyval_table

        kv = _keyval_table.get(keyval.id)
        value = self._attrs.pop(keyval.id, None)
        if kv is not None and kv.delete_fn is not None and value is not None:
            kv.delete_fn(self, kv, value, kv.extra_state)

    def get_attr(self, key):
        """MPI_Win_get_attr: predefined string attributes
        (``ompi/win/win.c`` WIN_BASE..WIN_MODEL) or a user Keyval;
        returns (found, value).  MPI's view is per-process: WIN_SIZE /
        WIN_DISP_UNIT describe ONE rank's window (block bytes,
        element size).  WIN_BASE in driver mode is the whole
        (comm.size, ...) storage — one controller plays every rank,
        so "the local base" is ``base[rank]``; sizes are metadata
        only (no device access)."""
        import math

        if not isinstance(key, str):  # user keyval
            if key.id in self._attrs:
                return True, self._attrs[key.id]
            return False, None
        if key == WIN_BASE:
            return True, self._data
        if key == WIN_SIZE:
            n = math.prod(self._data.shape[1:])
            return True, int(n * self._data.dtype.itemsize)
        if key == WIN_DISP_UNIT:
            return True, int(self._data.dtype.itemsize)
        if key == WIN_CREATE_FLAVOR:
            return True, self._flavor
        if key == WIN_MODEL:
            return True, MODEL_UNIFIED
        return False, None

    def shared_query(self, rank: int):
        """MPI_Win_shared_query (``osc/sm``): (size_bytes, disp_unit,
        block) for ``rank``'s segment of a shared window.  The block
        is a SNAPSHOT as of the last epoch close (arrays are
        immutable; every flush rebinds the window storage), so unlike
        the reference's baseptr it does not observe later stores —
        re-query after a flush, same discipline as :meth:`read`.
        ``rank=-1`` (MPI_PROC_NULL convention) answers for the lowest
        rank."""
        if not getattr(self, "_shared", False):
            raise MPIError(
                ErrorCode.ERR_RMA_SHARED,
                f"{self.name} was not created by win_allocate_shared",
            )
        if rank == -1:
            rank = 0
        if not 0 <= rank < self.comm.size:
            raise MPIError(ErrorCode.ERR_RANK,
                           f"shared_query rank {rank} out of range")
        blk = self._data[rank]
        return int(blk.size * blk.dtype.itemsize), \
            int(blk.dtype.itemsize), blk

    # -- epoch state machine ----------------------------------------------
    def _require(self, *kinds: _EpochKind) -> None:
        if self._freed:
            raise MPIError(ErrorCode.ERR_WIN, f"{self.name} freed")
        if self._epoch not in kinds:
            raise MPIError(
                ErrorCode.ERR_RMA_SYNC,
                f"operation requires epoch {[k.value for k in kinds]}, "
                f"window is in '{self._epoch.value}'",
            )

    def fence(self, _barrier: bool = True) -> None:
        """Open/continue a fence epoch; applies queued ops (MPI fence
        both closes the previous access epoch and opens the next).
        ``_barrier=False`` is for composite windows (DynamicWindow)
        that fan one fence over many regions and barrier ONCE."""
        self._require(_EpochKind.NONE, _EpochKind.FENCE)
        self._apply_pending()
        self._epoch = _EpochKind.FENCE
        if _barrier:
            self.comm.barrier()

    def fence_end(self, _barrier: bool = True) -> None:
        """Final fence (MPI_MODE_NOSUCCEED): close the epoch."""
        self._require(_EpochKind.FENCE)
        self._apply_pending()
        self._epoch = _EpochKind.NONE
        if _barrier:
            self.comm.barrier()

    def lock(self, target: int, lock_type: int = LOCK_EXCLUSIVE) -> None:
        self._require(_EpochKind.NONE, _EpochKind.LOCK)
        if target in self._locked:
            raise MPIError(ErrorCode.ERR_RMA_SYNC,
                           f"target {target} already locked")
        self._locked[target] = lock_type
        self._epoch = _EpochKind.LOCK

    def lock_all(self) -> None:
        self._require(_EpochKind.NONE)
        for t in range(self.comm.size):
            self._locked[t] = LOCK_SHARED
        self._epoch = _EpochKind.LOCK

    def unlock(self, target: int) -> None:
        self._require(_EpochKind.LOCK)
        if target not in self._locked:
            raise MPIError(ErrorCode.ERR_RMA_SYNC,
                           f"target {target} not locked")
        self._apply_pending(only_target=target)
        del self._locked[target]
        if not self._locked:
            self._epoch = _EpochKind.NONE

    def unlock_all(self) -> None:
        self._require(_EpochKind.LOCK)
        self._apply_pending()
        self._locked.clear()
        self._epoch = _EpochKind.NONE

    def flush(self, target: int) -> None:
        """Complete pending ops to one target inside a passive epoch."""
        self._require(_EpochKind.LOCK)
        self._apply_pending(only_target=target)

    def flush_all(self) -> None:
        self._require(_EpochKind.LOCK)
        self._apply_pending()

    def flush_local(self, target: int) -> None:
        """MPI_Win_flush_local: local completion only. Buffers here are
        immutable arrays (reusable the moment the op is queued), so
        local completion is implied — but MPI still requires the epoch
        check, and completing remotely too is allowed (stronger)."""
        self.flush(target)

    def flush_local_all(self) -> None:
        self.flush_all()

    def sync(self) -> None:
        """MPI_Win_sync: synchronize public/private window copies. The
        driver-mode window is MPI_WIN_UNIFIED with one storage array —
        there is no second copy to reconcile (get_attr WIN_MODEL)."""
        self._require(_EpochKind.FENCE, _EpochKind.LOCK,
                      _EpochKind.PSCW, _EpochKind.NONE)

    # PSCW (generalized active target)
    def post(self, group) -> None:
        """Exposure epoch: this window's slices may be targeted by the
        ranks of ``group`` (driver mode keeps one state machine)."""
        self._require(_EpochKind.NONE)
        self._group_exposed = group
        self._epoch = _EpochKind.PSCW

    def start(self, group) -> None:
        self._require(_EpochKind.NONE, _EpochKind.PSCW)
        self._epoch = _EpochKind.PSCW

    def complete(self) -> None:
        """Close the access side of a PSCW epoch (MPI_Win_complete)."""
        self._require(_EpochKind.PSCW)
        self._apply_pending()
        self._epoch = _EpochKind.NONE

    def wait(self) -> None:
        """Close the exposure side (MPI_Win_wait). The single driver
        state machine conflates access/exposure, so wait() after the
        origin's complete() must succeed — it applies anything still
        pending and clears the exposure group. A bare start() access
        epoch has no exposure to wait on and is rejected."""
        if self._group_exposed is None:
            raise MPIError(ErrorCode.ERR_RMA_SYNC,
                           "wait() without a matching post()")
        if self._epoch is _EpochKind.PSCW:
            self._apply_pending()
            self._epoch = _EpochKind.NONE
        self._group_exposed = None

    def test(self) -> bool:
        """MPI_Win_test: nonblocking wait(). Single controller: every
        origin's complete() has necessarily run by the time test() is
        reachable, so a posted exposure tests complete (and closes,
        like wait)."""
        if self._group_exposed is None:
            raise MPIError(ErrorCode.ERR_RMA_SYNC,
                           "test() without a matching post()")
        self.wait()
        return True

    def free(self) -> None:
        if self._pending:
            raise MPIError(ErrorCode.ERR_RMA_SYNC,
                           "free with unsynchronized RMA operations")
        # MPI_Win_free runs the attribute delete callbacks for every
        # still-attached user keyval — the same shared attribute
        # machinery Communicator.free() drains (win.c keyval contract)
        from ..comm.communicator import _keyval_table

        for kv_id, value in list(self._attrs.items()):
            kv = _keyval_table.get(kv_id)
            if kv and kv.delete_fn:
                kv.delete_fn(self, kv, value, kv.extra_state)
        self._attrs.clear()
        # a freed window must not pin fused epoch programs or frame
        # templates (osc/plan eviction contract)
        self._access_plans.clear()
        self._batch_templates.clear()
        self._freed = True

    # -- RMA operations ----------------------------------------------------
    def _origin(self, x):
        """An origin buffer as an epoch-program argument. A device-
        resident one arrives COMMITTED to its own device while the
        program runs where the window lives, and jit refuses the mix —
        replicate it over the window's devices. Host buffers and
        arrays already placed there pass through."""
        if (getattr(x, "committed", False) and
                x.sharding.device_set != self._data.sharding.device_set):
            return jax.device_put(x, NamedSharding(self._shard.mesh, P()))
        return x

    def _slot_elems(self) -> int:
        return math.prod(self.shape)

    def _check_range(self, disp: int, count: int) -> None:
        """``count`` elements from flat offset ``disp`` lie inside a
        slot, or ``ERR_RMA_RANGE``."""
        slot_elems = self._slot_elems()
        if disp < 0 or count < 1 or disp + count > slot_elems:
            raise MPIError(
                ErrorCode.ERR_RMA_RANGE,
                f"RMA range [{disp}, {disp + count}) leaves the slot of "
                f"{slot_elems} elements",
            )

    def _queue(self, op: _PendingOp) -> Optional[Request]:
        self._require(_EpochKind.FENCE, _EpochKind.LOCK, _EpochKind.PSCW)
        if (self._epoch is _EpochKind.LOCK
                and op.target not in self._locked):
            raise MPIError(ErrorCode.ERR_RMA_SYNC,
                           f"target {op.target} not locked")
        if not 0 <= op.target < self.comm.size:
            raise MPIError(ErrorCode.ERR_RANK,
                           f"RMA target {op.target} out of range")
        if op.index is not None:
            slot_elems = self._slot_elems()
            if not 0 <= op.index < slot_elems:
                raise MPIError(
                    ErrorCode.ERR_ARG,
                    f"RMA element index {op.index} out of range for "
                    f"slot of {slot_elems} elements",
                )
        _rma_ops.add()
        host = sum(isinstance(x, np.ndarray) for x in (op.data, op.compare))
        if host:
            _host_payloads.add(host)
        with self._op_lock:
            self._pending.append(op)
        return op.request

    def _op(self, kind, target, data=None, op=None, request=None,
            compare=None, index=None, disp=None, count=None
            ) -> Optional[Request]:
        """Queue one operation: on the whole slot, on one element
        (``index``) or on ``count`` consecutive elements of the slot
        (flattened in C order) from flat offset ``disp`` — the
        payload's element count when it has one, ``count`` for a get.
        A range that leaves the slot is refused HERE, at the call
        site, before anything is queued."""
        if disp is None:
            if count is not None:
                raise MPIError(ErrorCode.ERR_ARG,
                               "count= needs disp= (a ranged get)")
            return self._queue(_PendingOp(kind, target, data, op, request,
                                          compare, index=index))
        if index is not None:
            raise MPIError(ErrorCode.ERR_ARG,
                           "index= (one element) and disp= (a range) "
                           "exclude each other")
        if data is not None:
            count = int(data.size)
        elif count is None:
            raise MPIError(ErrorCode.ERR_ARG,
                           "a ranged get needs count= beside disp=")
        disp, count = int(disp), int(count)
        self._check_range(disp, count)
        if compare is not None and int(compare.size) not in (1, count):
            raise MPIError(
                ErrorCode.ERR_ARG,
                f"ranged compare_and_swap: {compare.size} compare "
                f"values for {count} elements",
            )
        return self._queue(_PendingOp(kind, target, data, op, request,
                                      compare, disp=disp, count=count))

    def _rma_request(self, target: int) -> Request:
        """A Request completable by ``wait()`` ALONE: its block_fn
        flushes the op's target (``_apply_pending(only_target)``), the
        per-op completion MPI 3.1 gives request-based RMA inside a
        passive epoch (``osc.h:341-366`` — MPI_Wait on an Rput/Rget
        request has flush semantics for that operation). Without this,
        wait() before the epoch close raised 'wait() would deadlock'
        even though the spec promises completion. Flushing the whole
        target is stronger than one op — allowed, same-origin ordering
        makes it indistinguishable."""
        return Request(
            block_fn=lambda: self._apply_pending(only_target=target)
        )

    def put(self, data, target: int, index: Optional[int] = None,
            disp: Optional[int] = None) -> None:
        """Put a whole slot, or (``index`` given) a single element at a
        flat offset within the slot, or (``disp`` given) ``data.size``
        consecutive elements from that flat offset (MPI target_disp
        addressing). Until the epoch closes ``data`` waits where it
        was: a ``jax.Array`` as the object passed, a host value as a
        host snapshot taken now (:func:`_payload`) — so is every
        payload and compare value of the calls below."""
        self._op("put", target, _payload(data), REPLACE, index=index,
                 disp=disp)

    def get(self, target: int, disp: Optional[int] = None,
            count: Optional[int] = None) -> Request:
        """The whole slot, or (``disp``, ``count``) that many
        consecutive elements from the flat offset, 1-D, as they were
        when the operation applied."""
        req = self._rma_request(target)
        self._op("get", target, request=req, disp=disp, count=count)
        return req

    def accumulate(self, data, target: int, op: Op = SUM,
                   index: Optional[int] = None,
                   disp: Optional[int] = None) -> None:
        self._op("acc", target, _payload(data), op, index=index,
                 disp=disp)

    def get_accumulate(self, data, target: int, op: Op = SUM,
                       index: Optional[int] = None,
                       disp: Optional[int] = None) -> Request:
        req = self._rma_request(target)
        self._op("get_acc", target, _payload(data), op, req,
                 index=index, disp=disp)
        return req

    def fetch_and_op(self, value, target: int, op: Op = SUM,
                     index: Optional[int] = None,
                     disp: Optional[int] = None) -> Request:
        """MPI_Fetch_and_op: single element when ``index`` is given
        (the MPI call is defined on ONE element at target_disp —
        ``osc.h:310``); whole-slot elementwise otherwise."""
        return self.get_accumulate(value, target, op, index=index,
                                   disp=disp)

    # -- request-based RMA (MPI-3 MPI_Rput/Rget/Raccumulate) ---------------
    # Each returns a Request completable INSIDE the epoch (wait =
    # per-op flush semantics, osc.h:341-366). get/get_accumulate are
    # already request-based; the R-forms of put/accumulate attach a
    # request that completes when the op applies (epoch close or
    # flush), carrying the pre-op slice (the pre-op BLOCK of a ranged
    # operation) like the reference's origin-completion semantics
    # allow.
    def rput(self, data, target: int, index: Optional[int] = None,
             disp: Optional[int] = None) -> Request:
        req = self._rma_request(target)
        self._op("put", target, _payload(data), REPLACE, req,
                 index=index, disp=disp)
        return req

    def raccumulate(self, data, target: int, op: Op = SUM,
                    index: Optional[int] = None,
                    disp: Optional[int] = None) -> Request:
        req = self._rma_request(target)
        self._op("acc", target, _payload(data), op, req, index=index,
                 disp=disp)
        return req

    def rget(self, target: int, disp: Optional[int] = None,
             count: Optional[int] = None) -> Request:
        return self.get(target, disp=disp, count=count)

    def rget_accumulate(self, data, target: int, op: Op = SUM,
                        index: Optional[int] = None,
                        disp: Optional[int] = None) -> Request:
        return self.get_accumulate(data, target, op, index=index,
                                   disp=disp)

    def compare_and_swap(self, value, compare, target: int,
                         index: Optional[int] = None,
                         disp: Optional[int] = None) -> Request:
        """MPI_Compare_and_swap. With ``index``, true single-element
        CAS at a flat offset (MPI semantics, ``osc.h:324``); with
        ``disp``, elementwise over ``value.size`` elements from that
        offset; with neither, an elementwise CAS over the whole slot
        (a documented whole-block extension)."""
        req = self._rma_request(target)
        self._op("cas", target, _payload(value), None, req,
                 compare=_payload(compare), index=index, disp=disp)
        return req

    # -- application -------------------------------------------------------
    @staticmethod
    def _branch_key(p: _PendingOp) -> Tuple[str, Any, bool]:
        indexed = p.index is not None
        if p.kind in ("acc", "get_acc"):
            # the op OBJECT (frozen, hashable), not its name: branch
            # keys feed the epoch program cache sig, and a same-named
            # op with a different combiner must get its own branch
            return ("acc", p.op, indexed)
        return (p.kind, "", indexed)

    @staticmethod
    def _branch_fn(key: Tuple[str, Any, bool], op: Optional[Op]):
        """One lax.switch branch: (cur, payload, compare, idx) ->
        (new_slice, pre_op_read). ``payload``/``compare`` may be
        scalars (scalar-payload epochs) or full slices; indexed
        branches operate on the single element at flat offset ``idx``
        (single-element MPI semantics — the read-back element is
        extracted host-side from the pre-op slice)."""
        kind, _, indexed = key

        def elem(pay, idx):
            # scalar payload, or a slice broadcast from one — any
            # element of the flattened broadcast is the scalar
            return (pay if jnp.ndim(pay) == 0
                    else pay.reshape(-1)[idx])

        if kind == "noop":
            return lambda cur, pay, cmp, idx: (cur, cur)
        if kind == "put":
            if indexed:
                return lambda cur, pay, cmp, idx: (
                    cur.reshape(-1).at[idx].set(elem(pay, idx))
                    .reshape(cur.shape), cur)
            return lambda cur, pay, cmp, idx: (
                jnp.broadcast_to(pay, cur.shape), cur)
        if kind == "get":
            return lambda cur, pay, cmp, idx: (cur, cur)
        if kind == "acc":
            if indexed:
                def acc_elem(cur, pay, cmp, idx):
                    flat = cur.reshape(-1)
                    new_e = op(flat[idx], elem(pay, idx))
                    return flat.at[idx].set(new_e).reshape(cur.shape), cur
                return acc_elem
            # ops that ignore cur (REPLACE) return the payload as-is —
            # a scalar in scalar-payload epochs — so pin the branch
            # output to the slice shape or lax.switch rejects the
            # branch set (scalar new vs slice new)
            return lambda cur, pay, cmp, idx: (
                jnp.broadcast_to(op(cur, pay), cur.shape), cur)
        # cas
        if indexed:
            def cas_elem(cur, pay, cmp, idx):
                flat = cur.reshape(-1)
                old = flat[idx]
                new_e = jnp.where(old == elem(cmp, idx),
                                  elem(pay, idx), old)
                return flat.at[idx].set(new_e).reshape(cur.shape), cur
            return cas_elem
        return lambda cur, pay, cmp, idx: (
            jnp.where(cur == cmp, pay, cur), cur
        )

    def _apply_pending(self, only_target: Optional[int] = None) -> None:
        """Apply queued ops in submission order (MPI same-origin
        ordering; driver mode's single queue is globally ordered) as
        ONE compiled program per epoch.

        The program is a ``lax.scan`` over the op list: step i reads
        slice ``targets[i]``, dispatches ``codes[i]`` through a
        ``lax.switch`` over the epoch's distinct (kind, op) branches,
        writes the new slice back, and emits the pre-op value (what
        get/get_acc/cas return). Targets/kinds/payloads are runtime
        DATA, so the compile cache key is only (op count, window
        shape/dtype, branch set): re-closing an epoch with the same
        shape never retraces, and dispatch count is 1 per close
        regardless of how many RMA ops queued (the osc/rdma "aggregate
        and issue at sync" strategy, done as XLA intends it).
        """
        with self._op_lock:
            self._apply_pending_locked(only_target)

    def _take_pending(self, only_target: Optional[int] = None
                      ) -> List[_PendingOp]:
        """Atomically remove (and return) the ops this close covers."""
        if only_target is None:
            todo, self._pending = self._pending, []
        else:
            todo = [p for p in self._pending if p.target == only_target]
            self._pending = [
                p for p in self._pending if p.target != only_target
            ]
        return todo

    def _apply_pending_locked(self, only_target: Optional[int] = None
                              ) -> None:
        if not self._pending:
            return
        _epoch_count.add()
        todo = self._take_pending(only_target)
        if not todo:
            return
        self._run_epoch_program(todo, _t0=time.perf_counter())

    def _run_epoch_program(self, todo: List[_PendingOp],
                           _t0: Optional[float] = None) -> None:
        """Apply ``todo`` (targets = storage row indices) and complete
        its read requests. Callers hold ``_op_lock``."""
        if todo:
            self._complete_reads(todo, self._close(todo, _t0))

    def _close(self, todo: List[_PendingOp], t0: Optional[float] = None
               ) -> List[Any]:
        """Apply ``todo`` and return, as host arrays in op order, the
        pre-op value of every operation that asked for one. A repeated
        epoch replays its frozen access plan (one fused program, no
        per-close branch dispatch); the first close of a new signature
        captures through the interpreted programs. ``t0`` (close-entry
        clock) feeds the shared orchestration timer so both paths are
        measured over identical spans."""
        from . import plan as _osc_plan

        if t0 is None:
            t0 = time.perf_counter()
        reads = _osc_plan.close_epoch(self, todo, t0)
        return self._interpret(todo, t0) if reads is None else reads

    @staticmethod
    def _complete_reads(todo: List[_PendingOp], reads: List[Any]) -> None:
        """Hand each read value to its request, as an array on this
        process's device (``ompi.osc.h2d`` where a profiler listens)."""
        want = [p for p in todo if p.request is not None]
        if not want:
            return
        with _obs.span(_spans.OSC_H2D,
                       bytes=sum(int(v.nbytes) for v in reads)):
            for p, v in zip(want, reads):
                src = (p.target if p.status_rank is None
                       else p.status_rank)
                p.request.complete(value=jnp.asarray(v),
                                   status=Status(source=src))

    def _interpret(self, todo: List[_PendingOp], t0: float) -> List[Any]:
        """The interpreted close: consecutive whole-slot and
        single-element operations run as one scan over slots, each run
        of ranged operations of one count as one scan over blocks of
        that count — so what a run stages and returns follows what it
        moves, not the slot's size. Submission order is kept across
        the runs."""
        from . import plan as _osc_plan

        reads: List[Any] = []
        for (ranged, _count), run in itertools.groupby(
                todo, key=lambda p: (p.disp is not None, p.count)):
            run = list(run)
            staged = (self._stage_ranged(run) if ranged
                      else self._stage_slots(run))
            if t0 is not None:
                _osc_plan.orch_add(time.perf_counter() - t0)
                t0 = None
            reads.extend(self._fire(run, *staged))
        return reads

    def _fire(self, run: List[_PendingOp], sig: Tuple, build, args,
              writes: bool = True) -> List[Any]:
        """One interpreted epoch program: looked up (or built) and
        called under the process-wide dispatch lock, its reads fetched
        as ONE host copy outside it."""
        with _dispatch_lock:
            prog = _program_cache.get(sig)
            if prog is None:
                _epoch_programs.add()
                prog = _program_cache[sig] = jax.jit(build())
            _epoch_dispatches.add()
            with _obs.span(_spans.OSC_PROGRAM, ops=len(run)):
                new_data, reads = prog(self._data, *args)
        # Complete read requests from ONE host copy of the outputs.
        # ``reads[i]`` on the sharded program output would dispatch an
        # eager multi-device gather OUTSIDE _dispatch_lock; a
        # concurrent thread's compiled epoch program then deadlocks
        # jaxlib's cross-program collective rendezvous — each program
        # holds a subset of the per-device threads and neither can
        # assemble its full set (flight-recorder stacks during
        # test_shmem_topo's lock-contention hang pinned one thread in
        # apply_primitive(gather) at this line with two run_ids parked
        # at the rendezvous). Device work stays exclusively under
        # _dispatch_lock; the host fetch is per-shard copies, not a
        # program, and epochs with no read requests skip it entirely.
        out: List[Any] = []
        if reads is not None:
            import numpy as _np

            reads_np = _np.asarray(reads)
            for i, p in enumerate(run):
                if p.request is not None:
                    value = reads_np[i]
                    if p.index is not None:
                        # single-element op: hand back the element
                        value = value.reshape(-1)[p.index]
                    out.append(value)
        if writes:
            self._data = new_data
        return out

    def _branches(self, run: List[_PendingOp]):
        """(padded op count, ordered distinct branch keys, their
        functions, one code per step of the padded run). The op count
        is padded to the next power of two with no-op steps so the
        program cache holds O(log n) programs per branch set instead
        of one per distinct epoch length; the noop branch is ALWAYS
        part of the set so padded and exact-power-of-two epochs share
        one program."""
        n = len(run)
        n_pad = 1 << (n - 1).bit_length() if n > 1 else 1
        branch_keys: List[Tuple[str, Any, bool]] = []
        branch_fns = []
        codes: List[int] = []
        for p in run:
            k = self._branch_key(p)
            if k not in branch_keys:
                branch_keys.append(k)
                branch_fns.append(self._branch_fn(k, p.op))
            codes.append(branch_keys.index(k))
        noop_key = ("noop", "", False)
        if noop_key not in branch_keys:
            branch_keys.append(noop_key)
            branch_fns.append(self._branch_fn(noop_key, None))
        codes.extend([branch_keys.index(noop_key)] * (n_pad - n))
        return (n_pad, branch_keys, branch_fns,
                jnp.asarray(codes, jnp.int32))

    @staticmethod
    def _steps(values: List[int], n_pad: int):
        """One int per step of a padded run (0 for the no-op steps)."""
        return jnp.asarray(values + [0] * (n_pad - len(values)), jnp.int32)

    def _stage_slots(self, run: List[_PendingOp]):
        """Whole-slot and single-element operations: a ``lax.scan``
        over the op list — step i reads slice ``targets[i]``,
        dispatches ``codes[i]`` through a ``lax.switch`` over the
        run's distinct (kind, op) branches, writes the new slice back
        and, where an operation of the run asked for one, emits the
        pre-op value. Targets/kinds/payloads are runtime DATA, so the
        compile cache key is only (op count, window shape/dtype,
        branch set, what is staged)."""
        from jax import lax

        dtype = self._data.dtype
        block = self.shape

        # Scalar-payload epochs (the common AMO pattern: many scalar
        # accumulates/CAS on a large window) keep payloads as (n,)
        # scalars — broadcast happens INSIDE the kernel, so host-side
        # staging is n scalars, not n x slot bytes.
        scalar_mode = all(
            (p.data is None or jnp.ndim(p.data) == 0)
            and (p.compare is None or jnp.ndim(p.compare) == 0)
            for p in run
        ) and block != ()
        # no compare array where no operation compares, no read where
        # no operation asked for one
        has_cmp = any(p.compare is not None for p in run)
        has_read = any(p.request is not None for p in run)

        n_pad, branch_keys, branch_fns, codes_a = self._branches(run)

        pay_shape = () if scalar_mode else block
        zeros = jnp.zeros(pay_shape, dtype)  # shared by all pad slots

        def pay(x):
            if x is None:
                return zeros
            return jnp.broadcast_to(
                jnp.asarray(self._origin(x)).astype(dtype), pay_shape)

        targets_a = self._steps([p.target for p in run], n_pad)
        zero_pad = [None] * (n_pad - len(run))
        payloads = jnp.stack([pay(p.data) for p in run]
                             + [pay(x) for x in zero_pad])
        compares = (jnp.stack([pay(p.compare) for p in run]
                              + [pay(x) for x in zero_pad])
                    if has_cmp else jnp.zeros((n_pad,), dtype))
        indices = self._steps([p.index or 0 for p in run], n_pad)
        sig = (n_pad, block, str(dtype), tuple(branch_keys), scalar_mode,
               has_cmp, has_read)

        def build():
            def close_epoch(data, codes, targets, payloads, compares,
                            indices):
                def step(data, xs):
                    code, tgt, payv, cmpv, idx = xs
                    cur = lax.dynamic_index_in_dim(
                        data, tgt, 0, keepdims=False
                    )
                    new, read = lax.switch(
                        code, branch_fns, cur, payv, cmpv, idx
                    )
                    data = lax.dynamic_update_index_in_dim(
                        data, new, tgt, 0
                    )
                    return data, (read if has_read else None)

                return lax.scan(
                    step, data,
                    (codes, targets, payloads, compares, indices)
                )

            return close_epoch

        return sig, build, (codes_a, targets_a, payloads, compares,
                            indices)

    def _stage_ranged(self, run: List[_PendingOp]):
        """Ranged operations of ONE count ``c``: a ``lax.scan`` whose
        step i slices ``c`` elements of row ``targets[i]`` of the
        flattened window from ``disps[i]``, dispatches through the same
        branches as a slot step (on the block, not the slot) and writes
        the block back. Payloads are (n, c), compares exist only where
        an operation compares, reads only where one was asked for, and
        a run of gets returns no window: nothing but the window itself
        has the slot's size."""
        from jax import lax

        dtype = self._data.dtype
        c = run[0].count
        has_pay = any(p.data is not None for p in run)
        has_cmp = any(p.compare is not None for p in run)
        has_read = any(p.request is not None for p in run)
        writes = any(p.kind != "get" for p in run)
        n_pad, branch_keys, branch_fns, codes_a = self._branches(run)
        zeros = jnp.zeros((c,), dtype)

        def row(x):
            if x is None:
                return zeros
            return jnp.broadcast_to(
                jnp.asarray(self._origin(x)).astype(dtype).reshape(-1),
                (c,))

        pad = [None] * (n_pad - len(run))
        targets_a = self._steps([p.target for p in run], n_pad)
        disps_a = self._steps([p.disp for p in run], n_pad)
        payloads = (jnp.stack([row(p.data) for p in run]
                              + [row(x) for x in pad]) if has_pay else None)
        compares = (jnp.stack([row(p.compare) for p in run]
                              + [row(x) for x in pad]) if has_cmp else None)
        sig = ("ranged", n_pad, c, self.shape, str(dtype),
               tuple(branch_keys), has_pay, has_cmp, has_read, writes)

        def build():
            def close_ranged(data, codes, targets, disps, payloads,
                             compares):
                flat = data.reshape(data.shape[0], -1)

                def apply(flat, xs):
                    code, tgt, disp, payv, cmpv = xs
                    cur = lax.dynamic_slice(flat, (tgt, disp), (1, c))[0]
                    return lax.switch(code, branch_fns, cur, payv, cmpv,
                                      0) + (tgt, disp)

                xs = (codes, targets, disps, payloads, compares)
                if not writes:
                    # gets alone: the window is read, never carried
                    _, reads = lax.scan(
                        lambda _, xs: (None, apply(flat, xs)[1]), None, xs)
                    return None, reads

                def step(flat, xs):
                    new, read, tgt, disp = apply(flat, xs)
                    flat = lax.dynamic_update_slice(flat, new[None],
                                                    (tgt, disp))
                    return flat, (read if has_read else None)

                flat, reads = lax.scan(step, flat, xs)
                return flat.reshape(data.shape), reads

            return close_ranged

        return sig, build, (codes_a, targets_a, disps_a, payloads,
                            compares), writes


def win_create(comm, base, name: str = "") -> Window:
    """MPI_Win_create: wrap existing per-rank buffers (leading rank
    axis; one slice per LOCAL member on a spanning comm)."""
    if getattr(comm, "spans_processes", False):
        from .wire_win import WireWindow

        return WireWindow(comm, jnp.asarray(base), name)
    return Window(comm, jnp.asarray(base), name)


def win_allocate(comm, shape: Tuple[int, ...], dtype=jnp.float32,
                 name: str = "") -> Window:
    """MPI_Win_allocate: fresh zeroed window, one ``shape`` block per
    rank."""
    if getattr(comm, "spans_processes", False):
        from .wire_win import WireWindow

        local_n = len(comm.local_comm_ranks)
        win = WireWindow(
            comm, jnp.zeros((local_n,) + tuple(shape), dtype), name
        )
    else:
        win = Window(
            comm, jnp.zeros((comm.size,) + tuple(shape), dtype), name
        )
    win._flavor = FLAVOR_ALLOCATE
    return win


def win_allocate_shared(comm, shape: Tuple[int, ...],
                        dtype=jnp.float32, name: str = "") -> Window:
    """MPI_Win_allocate_shared (the ``osc/sm`` component's role): a
    window whose ranks' blocks are one CONTIGUOUS allocation (the
    default alloc_shared_noncontig=false layout), so neighbors can
    address each other's memory directly. The window carries
    :meth:`Window.shared_query`; the comm should come from
    ``split_type_shared`` (enforced loosely — driver mode has one
    address space by construction, so every comm qualifies; a real
    multi-host comm would reject here, and the honest check is the
    endpoints' host identity)."""
    if getattr(comm, "spans_processes", False):
        raise MPIError(
            ErrorCode.ERR_RMA_SHARED,
            "win_allocate_shared needs a process-local comm (device "
            "buffers cannot be shared across controller processes); "
            "split with split_type_shared first",
        )
    # direct attribute access ON PURPOSE: a rename in runtime/group
    # must surface as an AttributeError here, not silently turn the
    # multi-host safety gate vacuous
    members = set(comm.group.world_ranks)
    hosts = {ep.host for ep in comm.runtime.endpoints
             if ep.rank in members}
    if len(hosts) > 1:
        raise MPIError(
            ErrorCode.ERR_RMA_SHARED,
            f"win_allocate_shared needs a single-host comm "
            f"(got hosts {sorted(h or '?' for h in hosts)}); split "
            "with split_type_shared first",
        )
    win = win_allocate(comm, shape, dtype, name)
    win._shared = True
    win._flavor = FLAVOR_SHARED
    return win


class DynamicWindow:
    """MPI_Win_create_dynamic + MPI_Win_attach/detach
    (``ompi/mca/osc/rdma`` dynamic-flavor support): a window created
    EMPTY whose memory regions attach and detach while it lives.

    Driver-mode mapping: each :meth:`attach` creates one uniform
    per-rank region (a fresh :class:`Window`) addressed by the
    returned region id — the analogue of the reference's
    absolute-address targeting, with the id playing the attached-base
    role.  Epoch synchronization spans the WHOLE dynamic window:
    fence/lock_all/unlock_all/flush_all fan out to every attached
    region (one comm barrier per fence, not per region) and a region
    attached MID-EPOCH inherits the open epoch, as MPI_Win_attach
    requires.  Per-region RMA goes through the owning region's queue
    (MPI ordering guarantees are per (origin, target) pair).
    Detaching with queued unsynchronized ops is refused, and free()
    refuses atomically — it frees nothing unless EVERY region is
    synchronized.  A lock guards the region table: the documented
    Window threading pattern (producer thread + waiter) extends to
    concurrent attach/detach against epoch fan-outs."""

    def __init__(self, comm, name: str = "") -> None:
        import threading as _threading

        self.comm = comm
        self.name = name or f"dynwin{id(self):x}"
        self._regions: Dict[int, Window] = {}
        self._next_region = 0
        self._flavor = FLAVOR_DYNAMIC
        self._freed = False
        self._open: Optional[str] = None  # None | "fence" | "lock"
        self._lock = _threading.RLock()

    # -- attach / detach ---------------------------------------------------
    def attach(self, shape: Tuple[int, ...], dtype=jnp.float32) -> int:
        """MPI_Win_attach: expose a fresh zeroed per-rank region;
        returns its region id. Legal mid-epoch — the new region joins
        the open epoch."""
        with self._lock:
            if self._freed:
                raise MPIError(ErrorCode.ERR_WIN, f"{self.name} freed")
            rid = self._next_region
            self._next_region += 1
            win = win_allocate(self.comm, shape, dtype,
                               f"{self.name}.r{rid}")
            win._flavor = FLAVOR_DYNAMIC
            if self._open == "fence":
                win.fence(_barrier=False)
            elif self._open == "lock":
                win.lock_all()
            self._regions[rid] = win
            return rid

    def detach(self, region: int) -> None:
        """MPI_Win_detach: the region must have no unsynchronized
        RMA queued (same rule as freeing mid-epoch)."""
        with self._lock:
            win = self._region(region)
            if win._pending:
                raise MPIError(
                    ErrorCode.ERR_RMA_SYNC,
                    f"{self.name}: detach of region {region} with "
                    "unsynchronized RMA operations",
                )
            win._freed = True
            del self._regions[region]

    def _region(self, region: int) -> Window:
        with self._lock:
            if self._freed:
                raise MPIError(ErrorCode.ERR_WIN, f"{self.name} freed")
            w = self._regions.get(region)
            if w is None:
                raise MPIError(
                    ErrorCode.ERR_BASE,
                    f"{self.name}: region {region} is not attached "
                    f"(attached: {sorted(self._regions)})",
                )
            return w

    # -- queries -----------------------------------------------------------
    def get_attr(self, key: str):
        if key == WIN_CREATE_FLAVOR:
            return True, self._flavor
        if key == WIN_MODEL:
            return True, MODEL_UNIFIED
        if key == WIN_BASE:
            # MPI_BOTTOM for dynamic windows: no single base
            return True, None
        if key == WIN_SIZE:
            return True, 0
        if key == WIN_DISP_UNIT:
            return True, 1
        return False, None

    def read(self, region: int) -> jax.Array:
        return self._region(region).read()

    # -- epochs fan out to every attached region ---------------------------
    def fence(self) -> None:
        with self._lock:
            for w in self._regions.values():
                w.fence(_barrier=False)
            self._open = "fence"
        self.comm.barrier()  # ONE barrier per fence, not per region

    def fence_end(self) -> None:
        with self._lock:
            for w in self._regions.values():
                w.fence_end(_barrier=False)
            self._open = None
        self.comm.barrier()

    def lock_all(self) -> None:
        with self._lock:
            for w in self._regions.values():
                w.lock_all()
            self._open = "lock"

    def unlock_all(self) -> None:
        with self._lock:
            for w in self._regions.values():
                w.unlock_all()
            self._open = None

    def flush_all(self) -> None:
        with self._lock:
            for w in self._regions.values():
                w.flush_all()

    # -- RMA: target = (rank, region) --------------------------------------
    def put(self, data, target: int, *, region: int, **kw):
        return self._region(region).put(data, target, **kw)

    def get(self, target: int, *, region: int, **kw):
        return self._region(region).get(target, **kw)

    def accumulate(self, data, target: int, *, region: int, **kw):
        return self._region(region).accumulate(data, target, **kw)

    def get_accumulate(self, data, target: int, *, region: int, **kw):
        return self._region(region).get_accumulate(data, target, **kw)

    def fetch_and_op(self, data, target: int, *, region: int, **kw):
        return self._region(region).fetch_and_op(data, target, **kw)

    def compare_and_swap(self, value, compare, target: int, *,
                         region: int, **kw):
        return self._region(region).compare_and_swap(
            value, compare, target, **kw)

    def free(self) -> None:
        """Atomic: refuses (freeing NOTHING) unless every region is
        synchronized — a partial free would strand pending ops on a
        half-dead window."""
        with self._lock:
            bad = [rid for rid, w in self._regions.items() if w._pending]
            if bad:
                raise MPIError(
                    ErrorCode.ERR_RMA_SYNC,
                    f"{self.name}: free with unsynchronized RMA in "
                    f"region(s) {bad}",
                )
            for w in self._regions.values():
                w.free()
            self._regions.clear()
            self._freed = True


def win_create_dynamic(comm, name: str = "") -> DynamicWindow:
    """MPI_Win_create_dynamic: an empty window; memory attaches
    later (``ompi/mpi/c/win_create_dynamic.c``)."""
    return DynamicWindow(comm, name)
