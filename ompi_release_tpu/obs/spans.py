"""Library spans on the profiler's clock — the one emit helper.

:func:`span` opens a ``jax.profiler.TraceAnnotation`` (a ``TraceMe``)
under one of the names below, so the library's layer boundaries land in
the profiler's own trace (``/host:CPU`` of the XPlane), on the clock
the device planes share. ``TraceMe`` records only while a profiler
session is open: THAT is the gate — no cvar, no environment variable.
With no session a site costs one ``TraceAnnotation`` construction.

When ``obs.enabled`` is true and the site names a ``journal`` pair, the
same interval is also journaled on exit under that existing
``(op, layer)`` name, so such a site needs no hand-written
``rec = _obs.enabled; t0 = ...; _obs.record(...)`` triple.

Names are few and exact (metrics match them with ``^...$`` patterns);
what varies per call — ``op``, ``cid``, ``seq``, ``bytes`` — rides as
keyword stats of the event, never folded into the name:

``ompi.coll.call``         a user-visible communicator's collective,
                           entry to return (``op``, ``cid``, ``bytes``:
                           the buffer handed in by this process)
``ompi.coll.launch``       the compiled program's call, host side: from
                           where ``coll_orchestration_seconds`` closes
                           to the call's return (nested in ``call``)
``ompi.coll.compile``      the same call when the program-cache lookup
                           missed: trace + compile + first launch (``op``)
``ompi.vcoll.alltoallv``   the host side of a device-form ``alltoallv``:
                           checks, program lookup and enqueue, never a
                           wait (``bytes`` of the send buffer, ``cid``)
``ompi.nbc.wait``          the caller parked on (or running inline) a
                           posted spanning schedule (``cid``, ``seq``)
``ompi.plan.native_fire``  one frozen wire plan walked by the C executor:
                           the exchange and the wait for the peer
                           (``cid``, ``seq``)
``ompi.plan.xchg``         one exchange of a schedule round in Python:
                           planned replay or the interpreted adapter
                           (``cid``, ``seq``, ``bytes`` sent)
``ompi.hier.d2h``          a device buffer fetched to the host (``bytes``)
``ompi.hier.h2d``          a host result placed on the device (``bytes``)
``ompi.plan.arrivals``     one round's arrivals of a native fire handed to
                           the schedule body: views of the executor's
                           slab, or copies out of it where the schedule
                           folds (``cid``, ``seq``, ``bytes`` copied)
``ompi.hier.pad``          a reduction's partial made flat and divisible
                           before its first round: identity padding, or
                           the copy of a read-only partial (``bytes``
                           copied)
``ompi.hier.fold``         one fold of arrivals into the partial between
                           two exchanges: the op and the write-back
                           (``bytes`` of the result written)
``ompi.hier.assemble``     the result of a bcast, allgather, gather or
                           alltoall built in one pass from the rank's
                           own buffer and the arrivals, until nothing
                           of it reads the arrivals any more (``bytes``
                           of the result)
``ompi.wire.stash``        a sender draining one of its own inbound rings
                           because the peer's ring is full (``bytes``
                           queued in that ring)
``ompi.pml.send``          a cross-process ``isend`` (so ``send`` too),
                           entry to return: the whole transfer, since
                           the wire leg sends inside the call
                           (``bytes``, ``peer``, ``tag``)
``ompi.pml.d2h``           the fetch of its device payload to the host
                           (``bytes``; nested in ``send``)
``ompi.wire.p2p_send``     ``WireRouter.send_p2p``: lane lock, envelope,
                           payload (``bytes``, ``seq``; nested in
                           ``send``)
``ompi.pml.recv_wait``     the blocking wait of a cross-process receive
                           (``recv``, ``wait``, ``wait_all``) until its
                           request completes (``source``, ``tag``)
``ompi.wire.p2p_pump``     one p2p message off its lane, from the popped
                           envelope to the payload complete (``seq``:
                           the SENDER's, as in its ``p2p_send``;
                           ``bytes``)
``ompi.pml.h2d``           the ``device_put`` of a p2p arrival until it
                           returns (``bytes``; nested in ``p2p_pump``)
``ompi.osc.sync``          a call that closes or flushes an epoch of a
                           window on a spanning communicator (``flush``,
                           ``unlock``, ``fence``, ``complete``), entry to
                           return (``cid``, ``win``, ``ops``, ``bytes``
                           of payload queued)
``ompi.osc.pack``          composing one home's batch: its header and its
                           payloads on the host (``bytes`` of the frame;
                           nested in ``sync``)
``ompi.osc.d2h``           the fetch of the batch's device payloads to
                           the host (``bytes``; nested in ``pack``)
``ompi.osc.request``       ``WinService.request``, entry to return
                           (``kind``, ``peer``, ``bytes`` sent)
``ompi.osc.reply_wait``    inside it, from the payload sent to the reply
                           routed to its slot: both wire legs and the
                           home's turn (``kind``, ``peer``; for a batch
                           also ``token`` and, from the stamps the home
                           sent back on its reply, ``turn_us``, of it
                           ``recv_us`` and ``program_us``, and, between
                           processes of one host, ``out_us`` and
                           ``back_us``)
``ompi.osc.unpack``        the read values of a reply off the wire
                           (``bytes``; runs in whichever thread pumps
                           the reply channel)
``ompi.osc.h2d``           placing read values on the origin's device
                           until it returns (``bytes``)
``ompi.osc.apply``         at the home, on the service thread: a batch
                           from its envelope to its reply sent
                           (``origin``, ``token``: the request's, as on
                           its ``reply_wait``; ``ops``, ``bytes``)
``ompi.osc.program``       the call of an epoch program, interpreted or
                           planned, wherever it runs (``ops``)
``ompi.shmem.quiet``       ``ShmemCtx.quiet`` (so ``fence`` and
                           ``barrier_all`` too), entry to return
                           (``allocs`` that had something to complete,
                           ``ops`` taken off their bulk queues)
``ompi.shmem.drain``       one allocation's bulk queue replayed into its
                           window and flushed (``ops``, ``bytes`` of
                           their payloads, ``cid``; nested in ``quiet``
                           or in the blocking call that drained)
``ompi.shmem.get``         a blocking ``ShmemCtx.get``, entry to return
                           (``bytes`` asked for)
``ompi.shmem.amo``         a fetching AMO, entry to return (``kind``:
                           ``fetch_add``, ``swap`` or ``cswap``)

``seq`` is the posted schedule's ``ScheduledOp.seq`` (process-local): a
schedule may run on another thread than its ``ompi.coll.call``
(progress thread, kick drainer), and ``(cid, seq)`` then joins
``ompi.nbc.wait`` to the exchanges it waited for. On one thread nesting
is the link.
"""

from __future__ import annotations

import math
import time as _time
from typing import Optional, Tuple

COLL_CALL = "ompi.coll.call"
COLL_LAUNCH = "ompi.coll.launch"
COLL_COMPILE = "ompi.coll.compile"
NBC_WAIT = "ompi.nbc.wait"
PLAN_NATIVE_FIRE = "ompi.plan.native_fire"
PLAN_XCHG = "ompi.plan.xchg"
HIER_D2H = "ompi.hier.d2h"
HIER_H2D = "ompi.hier.h2d"
HIER_ASSEMBLE = "ompi.hier.assemble"
PLAN_ARRIVALS = "ompi.plan.arrivals"
HIER_PAD = "ompi.hier.pad"
HIER_FOLD = "ompi.hier.fold"
WIRE_STASH = "ompi.wire.stash"
PML_SEND = "ompi.pml.send"
PML_D2H = "ompi.pml.d2h"
WIRE_P2P_SEND = "ompi.wire.p2p_send"
PML_RECV_WAIT = "ompi.pml.recv_wait"
WIRE_P2P_PUMP = "ompi.wire.p2p_pump"
PML_H2D = "ompi.pml.h2d"
OSC_SYNC = "ompi.osc.sync"
OSC_PACK = "ompi.osc.pack"
OSC_D2H = "ompi.osc.d2h"
OSC_REQUEST = "ompi.osc.request"
OSC_REPLY_WAIT = "ompi.osc.reply_wait"
OSC_UNPACK = "ompi.osc.unpack"
OSC_H2D = "ompi.osc.h2d"
OSC_APPLY = "ompi.osc.apply"
OSC_PROGRAM = "ompi.osc.program"
SHMEM_QUIET = "ompi.shmem.quiet"
SHMEM_DRAIN = "ompi.shmem.drain"
SHMEM_GET = "ompi.shmem.get"
SHMEM_AMO = "ompi.shmem.amo"
VCOLL_ALLTOALLV = "ompi.vcoll.alltoallv"

NAMES = (COLL_CALL, COLL_LAUNCH, COLL_COMPILE, NBC_WAIT,
         PLAN_NATIVE_FIRE, PLAN_XCHG, HIER_D2H, HIER_H2D, WIRE_STASH,
         PML_SEND, PML_D2H, WIRE_P2P_SEND, PML_RECV_WAIT, WIRE_P2P_PUMP,
         PML_H2D, HIER_ASSEMBLE, OSC_SYNC, OSC_PACK, OSC_D2H, OSC_REQUEST,
         OSC_REPLY_WAIT, OSC_UNPACK, OSC_H2D, OSC_APPLY, OSC_PROGRAM,
         SHMEM_QUIET, SHMEM_DRAIN, SHMEM_GET, SHMEM_AMO, PLAN_ARRIVALS,
         HIER_PAD, HIER_FOLD, VCOLL_ALLTOALLV)

#: ``jax.profiler.TraceAnnotation`` and the ``obs`` package, bound on
#: the first span: importing ``obs`` must not import jax (``obs
#: --selftest`` is device-free), and ``obs`` imports this module
_annotation = None
_obs = None


def _bind():
    global _annotation, _obs
    from jax.profiler import TraceAnnotation

    from .. import obs

    _annotation, _obs = TraceAnnotation, obs
    return TraceAnnotation


def nbytes(x) -> int:
    """Bytes of an array from its shape and dtype, 0 for anything else
    (a pair-op tuple, no buffer): the ``bytes`` stat of a hot site,
    where jax's own ``nbytes`` property would cost more than the span."""
    try:
        return math.prod(x.shape) * x.dtype.itemsize
    except AttributeError:
        return 0


class _Journaled:
    """A span that also journals its interval on exit (obs enabled)."""

    __slots__ = ("_ta", "_journal", "_stats", "_t0")

    def __init__(self, ta, journal: Tuple[str, str], stats: dict) -> None:
        self._ta = ta
        self._journal = journal
        self._stats = stats
        self._t0 = 0.0

    def __enter__(self) -> "_Journaled":
        self._ta.__enter__()
        self._t0 = _time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = _time.perf_counter() - self._t0
        self._ta.__exit__(*exc)
        if _obs.enabled and exc[0] is None:
            op, layer = self._journal
            st = self._stats
            _obs.record(op, layer, self._t0, dt,
                        nbytes=int(st.get("bytes", 0)),
                        comm_id=int(st.get("cid", -1)))


def span(name: str, journal: Optional[Tuple[str, str]] = None, **stats):
    """Context manager for one library span (see the module docstring).
    ``name`` is one of this module's constants, ``stats`` the event's
    keyword metadata; ``journal`` is the site's existing journal
    ``(op, layer)`` name, written too while ``obs.enabled``."""
    ta = (_annotation or _bind())(name, **stats)
    if journal is not None and _obs.enabled:
        return _Journaled(ta, journal, stats)
    return ta
