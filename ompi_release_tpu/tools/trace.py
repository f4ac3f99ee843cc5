"""Tracing interposition — the PMPI / libompitrace analogue.

The reference lets tracers interpose on every MPI call without
relinking via weak PMPI symbols (``ompi/mpi/c/init.c:32``) and ships
``libompitrace`` as a minimal example. The same property here: wrap a
communicator in :func:`wrap` and every collective/p2p call is recorded
(name, wall time, payload bytes) to an event list, optional JSONL
sink, and per-operation timing pvars — without touching the wrapped
object or the call sites. ``profiler_trace`` opens a JAX profiler
session (XPlane): the device's timeline with the library's own spans
(``obs/spans.py``) on the same clock.
"""

from __future__ import annotations

import contextlib
import json
import time
from typing import Any, Callable, Dict, List, Optional

from .. import obs as _obs
from ..mca import pvar

#: communicator methods interposed (the PMPI surface built so far)
TRACED = (
    "allreduce", "reduce", "bcast", "allgather", "gather", "scatter",
    "reduce_scatter_block", "alltoall", "scan", "exscan", "barrier",
    "iallreduce", "ireduce", "ibcast", "iallgather", "igather",
    "iscatter", "ireduce_scatter_block", "ireduce_scatter",
    "ialltoall", "iscan", "iexscan", "ibarrier",
    "allreduce_init", "bcast_init", "allgather_init",
    "reduce_scatter_init", "alltoall_init", "barrier_init",
    "send", "recv", "isend", "irecv", "sendrecv", "iprobe",
)


class TraceEvent:
    __slots__ = ("op", "t_start", "dt", "nbytes")

    def __init__(self, op: str, t_start: float, dt: float,
                 nbytes: int) -> None:
        self.op = op
        self.t_start = t_start
        self.dt = dt
        self.nbytes = nbytes

    def asdict(self) -> Dict[str, Any]:
        return {"op": self.op, "t": self.t_start, "dt": self.dt,
                "bytes": self.nbytes}


def _payload_bytes(args, kwargs: Optional[Dict[str, Any]] = None) -> int:
    """Total bytes across positional AND keyword array arguments —
    calls made with keyword buffers (``comm.allreduce(x=buf)``) must
    count the same as positional ones."""
    n = 0
    vals = list(args) + (list(kwargs.values()) if kwargs else [])
    for a in vals:
        sz = getattr(a, "size", None)
        it = getattr(getattr(a, "dtype", None), "itemsize", None)
        if sz is not None and it is not None:
            n += int(sz) * int(it)
    return n


class TracingComm:
    """Transparent proxy: traced methods are timed + recorded, all
    other attribute access passes through."""

    def __init__(self, comm, sink_path: Optional[str] = None) -> None:
        object.__setattr__(self, "_comm", comm)
        object.__setattr__(self, "events", [])
        object.__setattr__(self, "_sink", open(sink_path, "a")
                           if sink_path else None)
        object.__setattr__(self, "_timers", {})

    def _timer(self, op: str):
        t = self._timers.get(op)
        if t is None:
            t = pvar.timer(f"trace_{op}_seconds",
                           f"cumulative time in traced {op}")
            self._timers[op] = t
        return t

    def __getattr__(self, name: str) -> Any:
        attr = getattr(self._comm, name)
        if name not in TRACED or not callable(attr):
            return attr

        def traced(*args, **kw):
            t0 = time.perf_counter()
            try:
                return attr(*args, **kw)
            finally:
                dt = time.perf_counter() - t0
                ev = TraceEvent(name, t0, dt, _payload_bytes(args, kw))
                self.events.append(ev)
                self._timer(name).add(dt)
                if _obs.enabled:
                    # the PMPI proxy feeds the same journal as the
                    # in-framework emit points: one stream
                    _obs.record(name, "pmpi", t0, dt, nbytes=ev.nbytes)
                if self._sink is not None:
                    self._sink.write(json.dumps(ev.asdict()) + "\n")
                    # flush per event: a crashed run must not lose
                    # buffered trace lines
                    self._sink.flush()

        return traced

    def __setattr__(self, name: str, value: Any) -> None:
        setattr(self._comm, name, value)

    def summary(self) -> Dict[str, Dict[str, float]]:
        out: Dict[str, Dict[str, float]] = {}
        for ev in self.events:
            s = out.setdefault(
                ev.op, {"calls": 0, "seconds": 0.0, "bytes": 0}
            )
            s["calls"] += 1
            s["seconds"] += ev.dt
            s["bytes"] += ev.nbytes
        return out

    def close(self) -> None:
        if self._sink is not None:
            self._sink.close()


def wrap(comm, sink_path: Optional[str] = None) -> TracingComm:
    """Interpose on a communicator (PMPI shim analogue)."""
    return TracingComm(comm, sink_path)


@contextlib.contextmanager
def profiler_trace(logdir: str):
    """A JAX profiler session around the ``with`` body (the VampirTrace
    analogue), started with the options the benchmark's traced runs use
    (``python_tracer_level = 0``: no per-Python-call events, so tracing
    does not slow the host path it observes). It writes
    ``<logdir>/plugins/profile/<time>/<host>.xplane.pb``; read it with
    ``jax.profiler.ProfileData.from_file`` or TensorBoard's profile
    plugin. Only the process that holds the chip can trace it.

    While the session is open the library writes these host spans into
    the trace (plane ``/host:CPU``), on the clock of the device planes;
    what varies per call rides as event stats, never in the name:

    - ``ompi.coll.call`` — a user-visible communicator's collective,
      entry to return (``op``, ``cid``, ``bytes``)
    - ``ompi.coll.launch`` — the compiled program's call, host side
    - ``ompi.coll.compile`` — the same call when the program was not
      cached: it traces and compiles first (``op``)
    - ``ompi.vcoll.alltoallv`` — the host side of a device-form
      ``alltoallv``: checks, program lookup and enqueue (``bytes``,
      ``cid``)
    - ``ompi.nbc.wait`` — the caller parked on, or running, a posted
      spanning schedule (``cid``, ``seq``)
    - ``ompi.plan.native_fire`` — a frozen wire plan walked by the C
      executor: the exchange and the wait for the peer (``cid``, ``seq``)
    - ``ompi.plan.xchg`` — one exchange of a schedule round in Python,
      planned or interpreted (``cid``, ``seq``, ``bytes``)
    - ``ompi.hier.d2h`` / ``ompi.hier.h2d`` — a device buffer fetched
      to the host / a host result placed on the device (``bytes``)
    - ``ompi.plan.arrivals`` — one round's arrivals of a native fire
      handed to the schedule: views of the executor's slab, or copies
      out of it where the schedule folds (``cid``, ``seq``, ``bytes``
      copied); ``ompi.hier.pad`` — a reduction's partial made flat,
      writable and divisible before its first round (``bytes`` copied);
      ``ompi.hier.fold`` — one fold of arrivals into the partial
      between two exchanges (``bytes`` of the result): what
      ``ompi.nbc.wait`` holds beside its exchanges
    - ``ompi.hier.assemble`` — the result of a spanning bcast,
      allgather, gather or alltoall built in one pass from the rank's
      own buffer and the arrivals (``bytes`` of the result)
    - ``ompi.wire.stash`` — a sender draining its own inbound ring
      because the peer's is full (``bytes``)
    - ``ompi.pml.send`` — a cross-process ``isend``/``send``, entry to
      return (``bytes``, ``peer``, ``tag``), with ``ompi.pml.d2h`` (the
      payload's fetch, ``bytes``) and ``ompi.wire.p2p_send`` (lane lock,
      envelope, payload; ``bytes``, ``seq``) inside it
    - ``ompi.pml.recv_wait`` — the blocking wait of a cross-process
      receive until its request completes (``source``, ``tag``)
    - ``ompi.wire.p2p_pump`` — one p2p message off its lane, envelope
      to payload complete (the sender's ``seq``, ``bytes``), with
      ``ompi.pml.h2d`` (the arrival's ``device_put``, ``bytes``) inside
    - ``ompi.osc.sync`` — a call that closes or flushes an epoch of a
      window on a spanning communicator: ``flush``, ``unlock``,
      ``fence``, ``complete`` (``cid``, ``win``, ``ops``, ``bytes``),
      with ``ompi.osc.pack`` (one home's batch composed, ``bytes``; the
      fetch of its device payloads is ``ompi.osc.d2h`` inside it),
      ``ompi.osc.request`` (the request to the home, entry to return:
      ``kind``, ``peer``, ``bytes``; from the payload sent to the reply
      routed it is ``ompi.osc.reply_wait``, whose stats split a batch's
      wait by the stamps its home sent back — ``token``, ``turn_us``,
      of it ``recv_us`` and ``program_us``, and, between processes of
      one host, ``out_us`` and ``back_us`` — and the reply's read values
      come off the wire under ``ompi.osc.unpack``) and ``ompi.osc.h2d``
      (the read values placed on this process's device) inside it
    - ``ompi.osc.apply`` — at a window's home, on the service thread: a
      peer's batch from its envelope to its reply sent (``origin`` and
      ``token``, which its ``reply_wait`` at the origin carries too;
      ``ops``, ``bytes``)
    - ``ompi.osc.program`` — the call of an epoch program, interpreted
      or planned, wherever it runs (``ops``)
    - ``ompi.shmem.quiet`` — ``ShmemCtx.quiet`` (``fence`` and
      ``barrier_all`` too), entry to return (``allocs``, ``ops``), with
      one ``ompi.shmem.drain`` per allocation that had posted puts or
      AMOs inside it: its bulk queue replayed into its window and
      flushed (``ops``, ``bytes``; the window's ``ompi.osc.sync`` nests
      in it where the communicator spans processes)
    - ``ompi.shmem.get`` — a blocking ``ShmemCtx.get``, entry to return
      (``bytes``); ``ompi.shmem.amo`` — a fetching AMO, entry to return
      (``kind``)

    ``(cid, seq)`` joins an exchange to its ``ompi.nbc.wait`` when the
    schedule ran on another thread; on one thread nesting is the link.
    """
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
