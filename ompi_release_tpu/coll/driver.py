"""Host-side collective driver: persistent compiled programs per comm.

Wraps the SPMD kernels (``coll/spmd.py``) into MPI-semantic host calls:
inputs/outputs carry a leading ``size`` axis (slice i = rank i's
buffer). Each (comm, operation, algorithm) pair gets ONE persistent
jitted ``shard_map`` program, cached on the communicator — re-invoking
with the same shapes never retraces (the "no per-call retrace"
requirement from SURVEY §6's north star).
"""

from __future__ import annotations

import threading
import time as _time
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .. import obs as _obs
from ..mca import pvar
from ..obs import skew as _skew
from ..obs import spans as _spans

_invoke_count = pvar.counter(
    "coll_invocations", "host-driver collective invocations"
)
_compile_count = pvar.counter(
    "coll_programs_compiled", "distinct compiled collective programs"
)
# per-invocation plan-cache outcome: observe(1) on a cache hit,
# observe(0) on a compile — so sum/count IS the hit ratio
# (coll_programs_compiled vs coll_invocations, as one AGGREGATE)
_plan_cache = pvar.aggregate(
    "coll_plan_cache_hits",
    "plan-cache outcome per driver invocation (1=hit, 0=compile); "
    "sum/count = hit ratio",
)
#: Python time on the collective DISPATCH path — everything between a
#: collective's dispatch entry and the moment the compiled program (or
#: the wire transport) takes over: decision logic, plan/cache lookups,
#: validation, schedule posting. The delta of this timer across a run
#: isolates orchestration from device/wire time (the benchmark's
#: ``dispatch_us.*``). Two clock reads per dispatch — measurement, not
#: policy.
_orch = pvar.timer(
    "coll_orchestration_seconds",
    "Python orchestration seconds on the collective dispatch path "
    "(decision, planning, validation, posting — before the compiled "
    "program or wire transport takes over)",
)

#: capture/attribution state for :mod:`coll.plan` (the compiled
#: whole-schedule layer): ``entries`` records each program dispatch
#: (prog handle, input object, output object) while a capture is
#: active; ``t0`` re-bases the orchestration timer at the OUTER
#: dispatch entry so interpreted and compiled fires time the same span.
_capture_tls = threading.local()


def begin_capture() -> list:
    """Arm program-dispatch capture on this thread; returns the live
    entry list (one dict per ``run_sharded`` program launch)."""
    entries: list = []
    _capture_tls.entries = entries
    return entries


def end_capture() -> None:
    _capture_tls.entries = None


def orch_mark(t0: float) -> None:
    """Re-base the next ``run_sharded`` orchestration interval at
    ``t0`` (the outer dispatch entry), so the timer covers the
    component decision path too, not just the driver prologue."""
    _capture_tls.t0 = t0


def orch_clear() -> None:
    _capture_tls.t0 = None


def orch_add(dt: float) -> None:
    """Credit ``dt`` seconds of Python orchestration directly. The
    wire-replay adapters (PlannedXchg's per-round Python loop, the
    native executor's ctypes entry/exit + pool copies) run BETWEEN
    driver dispatches, where the ``run_sharded`` interval can't see
    them — they self-report here so ``coll_orchestration_seconds``
    keeps meaning "Python time before the compiled program or wire
    transport takes over" on every leg of the steady state."""
    if dt > 0.0:
        _orch.add(dt)


def _orch_t0(default: float) -> float:
    t0 = getattr(_capture_tls, "t0", None)
    if t0 is None:
        return default
    _capture_tls.t0 = None  # one-shot: consumed by this dispatch
    return t0


def _op_name(key: Tuple) -> str:
    """Collective-op label from a program-cache key — keys are
    (component, op, ...) tuples by convention throughout coll/."""
    if isinstance(key, tuple) and len(key) > 1 and isinstance(key[1], str):
        return key[1]
    return str(key[0]) if isinstance(key, tuple) and key else str(key)


def _arr_nbytes(x) -> int:
    try:
        return int(x.size) * int(x.dtype.itemsize)
    except (AttributeError, TypeError):
        return 0


def _launch_span(missed: bool, key: Tuple):
    """The span around a program call, from where ``_orch`` closes to
    the call's return: ``ompi.coll.launch``, or ``ompi.coll.compile``
    when the program-cache lookup missed (that call traces and compiles
    before it launches — it must never fire inside a timed window)."""
    if missed:
        return _obs.span(_spans.COLL_COMPILE, op=_op_name(key))
    return _obs.span(_spans.COLL_LAUNCH)


def _program_cache(comm) -> Dict[Tuple, Callable]:
    cache = getattr(comm, "_coll_programs", None)
    if cache is None:
        cache = {}
        comm._coll_programs = cache
    return cache


def run_sharded2d(comm, key: Tuple, body: Callable, x, *,
                  inter: int, intra: int) -> Any:
    """Like run_sharded but over a 2-D (node, local) factorization of
    the comm's ranks: rank r = node r//intra, local r%intra (the sbgp
    subgrouping). Used by hierarchical (ml) algorithms."""
    import numpy as _np
    from jax.sharding import Mesh

    t_in = _time.perf_counter()
    _invoke_count.add()
    tok = (_skew.begin(_op_name(key), getattr(comm, "cid", -1))
           if _obs.enabled else None)
    if x.shape[0] != comm.size or inter * intra != comm.size:
        from ..utils.errors import ErrorCode, MPIError

        raise MPIError(
            ErrorCode.ERR_COUNT,
            f"2-D driver needs leading axis == size ({comm.size}) and "
            f"inter*intra == size (got {inter}x{intra})",
        )
    cache = _program_cache(comm)
    prog = cache.get(key)
    missed = prog is None
    _plan_cache.observe(0.0 if missed else 1.0)
    if missed:
        _compile_count.add()
        devs = _np.asarray(
            list(comm.submesh.devices.reshape(-1)), dtype=object
        ).reshape(inter, intra)
        mesh2d = Mesh(devs, ("node", "local"))

        def wrapper(xb):
            return body(xb[0])[None]

        prog = jax.jit(
            jax.shard_map(
                wrapper, mesh=mesh2d,
                in_specs=P(("node", "local")),
                out_specs=P(("node", "local")),
            )
        )
        cache[key] = prog
    _orch.add(_time.perf_counter() - t_in)
    if tok is not None:
        _skew.body(tok)
    with _launch_span(missed, key):
        out = prog(jnp.asarray(x))
    if tok is not None:
        _skew.end(tok, _arr_nbytes(x))
    return out


def _local_rank_count(comm) -> int:
    """Ranks of this comm whose device is addressable by THIS
    controller (jax.distributed multi-controller SPMD mode)."""
    pidx = jax.process_index()
    return sum(
        1 for d in comm.submesh.devices.reshape(-1)
        if int(getattr(d, "process_index", 0)) == pidx
    )


def run_sharded_spmd(comm, key: Tuple, body: Callable, local_x) -> Any:
    """Multi-controller SPMD mode (``jax.distributed``): every
    controller passes only ITS ranks' leading-axis slices; the global
    array is assembled from the per-process shards, ONE compiled
    program runs SPMD across all controllers (XLA's cross-host
    collectives ride ICI/DCN), and each controller receives its local
    shard of the result back. This is the collective path the
    single-controller driver cannot provide under ``jax.distributed``
    — the leading-rank-axis array never materializes on one host."""
    import numpy as _np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as _P

    t_in = _time.perf_counter()
    _invoke_count.add()
    tok = (_skew.begin(_op_name(key), getattr(comm, "cid", -1))
           if _obs.enabled else None)
    mesh = comm.submesh
    sharding = NamedSharding(mesh, _P("rank"))
    local_x = _np.asarray(local_x)
    global_shape = (comm.size,) + local_x.shape[1:]
    garr = jax.make_array_from_process_local_data(
        sharding, local_x, global_shape
    )
    cache = _program_cache(comm)
    prog = cache.get(key)
    missed = prog is None
    _plan_cache.observe(0.0 if missed else 1.0)
    if missed:
        _compile_count.add()

        def wrapper(xb):
            out = body(xb[0])
            return jax.tree.map(lambda a: a[None], out)

        prog = jax.jit(
            jax.shard_map(wrapper, mesh=mesh, in_specs=P("rank"),
                          out_specs=P("rank"))
        )
        cache[key] = prog
    _orch.add(_time.perf_counter() - t_in)
    if tok is not None:
        _skew.body(tok)
    with _launch_span(missed, key):
        out = prog(garr)
    if tok is not None:
        _skew.end(tok, _arr_nbytes(local_x))

    def to_local(a):
        shards = sorted(a.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        return _np.concatenate([_np.asarray(s.data) for s in shards],
                               axis=0)

    return jax.tree.map(to_local, out)


def _check_no_narrowing(arr) -> None:
    """MPI_DOUBLE is not MPI_FLOAT: with jax_enable_x64 off (the JAX
    default), ``jnp.asarray`` silently narrows 64-bit host buffers to
    32 bits — a reduction over them would return plausible-but-wrong
    values. Refuse loudly; with x64 enabled the widths pass through
    and this is a no-op."""
    dt = getattr(arr, "dtype", None)
    if dt is None:
        return
    try:
        jt = jax.dtypes.canonicalize_dtype(dt)  # pure metadata, no
    except TypeError:                           # dispatch on the hot path
        return  # non-canonicalizable dtypes fail later with their own error
    if np.dtype(jt).itemsize < np.dtype(dt).itemsize:
        from ..utils.errors import ErrorCode, MPIError

        raise MPIError(
            ErrorCode.ERR_TYPE,
            f"{np.dtype(dt).name} buffer would be silently narrowed "
            f"to {np.dtype(jt).name} (jax_enable_x64 is off) — enable "
            "x64 (jax.config.update('jax_enable_x64', True)) or cast "
            "the buffer explicitly",
        )


def run_sharded(comm, key: Tuple, body: Callable, x, *,
                extra_arrays: Tuple = ()) -> Any:
    """Run ``body(block, *extra_blocks)`` under shard_map over the comm's
    1-D ``rank`` axis. ``x`` has leading axis == comm.size; every extra
    array is sharded the same way. Result keeps the leading rank axis.

    Under a ``jax.distributed`` multi-controller runtime, a buffer
    whose leading axis matches this controller's LOCAL rank count is
    dispatched through :func:`run_sharded_spmd` (per-process shards in,
    per-process shards out) — the single-controller convention cannot
    apply there because no controller holds every rank's slice.
    """
    t_in = _orch_t0(_time.perf_counter())
    _invoke_count.add()
    tok = (_skew.begin(_op_name(key), getattr(comm, "cid", -1))
           if _obs.enabled else None)
    if getattr(comm, "spans_processes", False):
        from ..utils.errors import ErrorCode, MPIError

        # the submesh covers only LOCAL members on a spanning comm:
        # compiling over it with comm.size rows would silently place
        # remote ranks' slices on local devices (wrong results, no
        # error). Everything with a cross-process implementation
        # dispatches through coll/hier or the wire — reaching this
        # compiled in-process path is a capability boundary.
        raise MPIError(
            ErrorCode.ERR_NOT_AVAILABLE,
            f"compiled in-process collective invoked on {comm.name}, "
            "which spans controller processes — this operation has no "
            "cross-process implementation; run it on a process-local "
            "sub-communicator (split_type_shared)",
        )
    if not hasattr(x, "shape"):
        from ..utils.errors import ErrorCode, MPIError

        raise MPIError(
            ErrorCode.ERR_TYPE,
            "driver-mode collectives take a single array with a leading "
            "rank axis; pair-op (value, index) tuples are supported by "
            "allreduce/reduce/reduce_scatter_block/scan/exscan "
            "(MINLOC/MAXLOC)",
        )
    if x.shape[0] != comm.size:
        from ..utils.errors import ErrorCode, MPIError

        if (jax.process_count() > 1 and not extra_arrays
                and x.shape[0] == _local_rank_count(comm)):
            _invoke_count.add(-1)  # the spmd entry counts this call
            return run_sharded_spmd(comm, key, body, x)
        raise MPIError(
            ErrorCode.ERR_COUNT,
            f"driver-mode buffer leading axis {x.shape[0]} != comm size "
            f"{comm.size} (one slice per rank)",
        )
    for arr in (x,) + tuple(extra_arrays):
        _check_no_narrowing(arr)
    cache = _program_cache(comm)
    prog = cache.get(key)
    missed = prog is None
    _plan_cache.observe(0.0 if missed else 1.0)
    if missed:
        _compile_count.add()
        mesh = comm.submesh
        n_extra = len(extra_arrays)

        def wrapper(xb, *eb):
            out = body(xb[0], *[e[0] for e in eb])
            return jax.tree.map(lambda a: a[None], out)

        prog = jax.jit(
            jax.shard_map(
                wrapper,
                mesh=mesh,
                in_specs=tuple([P("rank")] * (1 + n_extra)),
                out_specs=P("rank"),
            )
        )
        cache[key] = prog
    cap = getattr(_capture_tls, "entries", None)
    if cap is not None:
        # coll/plan capture: record the program handle plus the exact
        # input/output OBJECTS — identity against the collective's own
        # argument and return value proves the dispatch was pre/post-
        # processing-free, i.e. safe to re-fire as the program alone
        cap.append({"prog": prog, "x": x, "extra": bool(extra_arrays),
                    "out": None})
    _orch.add(_time.perf_counter() - t_in)
    if tok is not None:
        # skew emit point: wait = arrival -> program launch (cache
        # lookup / compile / validation), body = the dispatch itself
        _skew.body(tok)
    with _launch_span(missed, key):
        out = prog(jnp.asarray(x),
                   *[jnp.asarray(e) for e in extra_arrays])
    if tok is not None:
        _skew.end(tok, _arr_nbytes(x))
    if cap is not None:
        cap[-1]["out"] = out
    return out
