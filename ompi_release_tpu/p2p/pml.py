"""Host PML — dynamic (rank, tag, comm) matching over device transfers.

The ob1 engine's structure (``ompi/mca/pml/ob1/``) kept where it still
carries meaning on TPU, dropped where it does not:

- KEPT: the matching machinery — per-(comm, rank) posted-recv queues
  and unexpected queues with MPI ordering and ANY_SOURCE/ANY_TAG
  wildcards (``pml_ob1_recvfrag.c:106,502,550`` match_one/unexpected);
  protocol selection by message size (eager / rendezvous / pipelined,
  ``pml_ob1_sendreq.c:480,785``) with btl-style size variables.
- REIMAGINED: "wire transfer" is a device-to-device array move managed
  by the runtime (ICI within a slice, DCN across). Eager = move at
  send time (sender's HBM freed early); rendezvous = move only when
  the matching recv posts (receiver-side pull, the RGET analogue);
  pipelined = segmented moves for buffers over max_send so segments
  overlap (``btl_rdma_pipeline`` analogue).
- DROPPED: byte-level fragments/progress polling — jax arrays are
  immutable futures, so completion is array readiness, not FIFO polls.
"""

from __future__ import annotations

import collections
import threading
import time as _time
from typing import Any, Deque, Dict, List, Optional, Tuple

from .. import obs as _obs
from ..mca import component as mca_component
from ..obs import spans as _spans
from ..obs import watchdog as _watchdog
from ..mca import pvar
from ..mca import var as mca_var
from ..request.request import Request, Status
from ..utils import output
from ..utils.errors import ErrorCode, MPIError

_log = output.stream("pml")

ANY_SOURCE = -1
ANY_TAG = -1

_unexpected_count = pvar.counter(
    "pml_unexpected_msgs", "sends queued before a matching recv was posted"
)
_eager_count = pvar.counter("pml_eager_sends", "eager-protocol sends")
_rndv_count = pvar.counter("pml_rndv_sends", "rendezvous-protocol sends")
_pipeline_count = pvar.counter(
    "pml_pipelined_sends", "segmented (pipelined) large sends"
)
# the cross-process leg has no eager/rendezvous choice (the whole
# transfer happens inside isend), so the three above never tick there
_wire_sends = pvar.counter(
    "pml_wire_sends", "sends that crossed a process boundary on the wire"
)
_wire_bytes = pvar.counter(
    "pml_wire_bytes", "payload bytes of those sends"
)
_wire_recvs = pvar.counter(
    "pml_wire_recvs",
    "messages delivered from the wire into the matching queues",
)

PML_FRAMEWORK = mca_component.framework(
    "pml", "point-to-point management (ompi/mca/pml analogue)"
)


def _as_device_payload(data):
    """Convert a send payload to a device array, turning the raw jax
    TypeError for structured/byte-string data into MPI's own answer:
    describe it with a Datatype and pack it to a numeric buffer (the
    reference never sends raw C structs either — ``MPI_Type_struct``
    + pack/unpack is the contract)."""
    import jax.numpy as jnp

    try:
        return jnp.asarray(data)
    except TypeError as e:
        raise MPIError(
            ErrorCode.ERR_TYPE,
            f"p2p payload of type {type(data).__name__} is not a "
            "numeric array; describe structured/byte data with a "
            "datatype and pack it (datatype.pack / Convertor) before "
            f"sending, then unpack at the receiver ({e})",
        )


def register_vars() -> None:
    mca_var.register(
        "pml_eager_limit", "size", 0,
        "Override: messages up to this many bytes move at send time; "
        "0 = use the selected btl endpoint's eager_limit "
        "(btl_tcp_component.c:268 analogue)",
    )
    mca_var.register(
        "pml_max_send_size", "size", 0,
        "Override: messages beyond this many bytes move as overlapping "
        "segments; 0 = use the btl endpoint's max_send_size "
        "(btl.h:802 rdma pipeline)",
    )
    mca_var.register(
        "pml_wire_timeout", "float", 30.0,
        "Seconds a blocking cross-process recv/ssend waits for its "
        "match over the wire before raising ERR_PENDING (raise it for "
        "jobs with long compute phases between communication)",
    )


class _SendEntry:
    """A send awaiting (or delivering to) its match."""

    __slots__ = ("src", "dst", "tag", "data", "request", "sync",
                 "transferred")

    def __init__(self, src, dst, tag, data, request, sync) -> None:
        self.src = src
        self.dst = dst
        self.tag = tag
        self.data = data
        self.request = request
        self.sync = sync  # ssend: complete only on match
        self.transferred = False


class _RecvEntry:
    __slots__ = ("dst", "source", "tag", "request")

    def __init__(self, dst, source, tag, request) -> None:
        self.dst = dst
        self.source = source
        self.tag = tag
        self.request = request


def _tag_match(posted_tag: int, tag: int) -> bool:
    return posted_tag == ANY_TAG or posted_tag == tag


class PmlEngine:
    """Per-communicator matching engine (single-controller: it sees all
    ranks' posts, so matching is a local queue operation; the reference
    does the same work after the wire delivers the MATCH header)."""

    def __init__(self, comm) -> None:
        self.comm = comm
        self._lock = threading.RLock()
        # per destination rank: unexpected sends (FIFO — MPI ordering)
        self._unexpected: Dict[int, Deque[_SendEntry]] = (
            collections.defaultdict(collections.deque)
        )
        # per destination rank: posted recvs (FIFO)
        self._posted: Dict[int, Deque[_RecvEntry]] = (
            collections.defaultdict(collections.deque)
        )
        self._logger = None  # vprotocol message log, when attached
        # per-peer transfer plans through the btl framework (bml/r2)
        from ..btl import BmlR2

        self._bml = BmlR2(comm)

    # -- helpers -----------------------------------------------------------
    def _purge_cancelled(self, dst: int) -> None:
        """Drop cancelled entries so they never match a live message
        (MPI_Cancel semantics: a cancelled recv must not consume a
        send, and vice versa)."""
        self._posted[dst] = collections.deque(
            r for r in self._posted[dst] if not r.request.is_cancelled
        )
        self._unexpected[dst] = collections.deque(
            s for s in self._unexpected[dst] if not s.request.is_cancelled
        )

    def _check_rank(self, r: int, what: str) -> None:
        if not 0 <= r < self.comm.size:
            raise MPIError(
                ErrorCode.ERR_RANK,
                f"{what} rank {r} out of range on {self.comm.name}",
            )

    def _nbytes(self, data) -> int:
        return int(data.size * data.dtype.itemsize)

    def _eager_limit(self, src_rank: int, dst_rank: int) -> int:
        """Per-peer eager threshold: pml override, else the btl
        endpoint's (ob1 reads the btl's eager size the same way)."""
        override = mca_var.get("pml_eager_limit", 0)
        if override:
            return int(override)
        return self._bml.endpoint(src_rank, dst_rank).eager_limit

    def _move(self, data, src_rank: int, dst_rank: int):
        """Transfer through the per-peer BML endpoint: the btl
        framework picks the fabric (self/ici/dcn/host) and segments
        beyond max_send_size so segments overlap in flight."""
        ep = self._bml.endpoint(src_rank, dst_rank)
        max_send = int(mca_var.get("pml_max_send_size", 0)) or None
        return ep.move(data, max_send=max_send,
                       on_pipeline=_pipeline_count.add)

    # -- send --------------------------------------------------------------
    def isend(self, data, dst: int, tag: int = 0, *, src: int,
              sync: bool = False, ready: bool = False) -> Request:
        """Nonblocking send from rank ``src`` to rank ``dst``.

        sync=True  -> ssend: completes only when matched.
        ready=True -> rsend: raises unless a matching recv is posted.
        """
        import jax.numpy as jnp

        self._check_rank(dst, "destination")
        self._check_rank(src, "source")
        data = _as_device_payload(data)
        if _obs.enabled:  # instant emit point: the send posting itself
            _obs.record("isend", "pml", _time.perf_counter(), 0.0,
                        nbytes=self._nbytes(data), peer=dst,
                        comm_id=self.comm.cid)
        req = Request()
        entry = _SendEntry(src, dst, tag, data, req, sync)
        from . import peruse

        peruse.fire(self.comm, peruse.REQ_ACTIVATE, kind="send",
                    src=src, dst=dst, tag=tag)
        with self._lock:
            if self._logger is not None:
                # logged UNDER the matching lock like recv postings:
                # the log's event order must equal the queue order or
                # replay swaps same-(src, tag) deliveries
                self._logger.record(src, dst, tag, data, sync)
            self._purge_cancelled(dst)
            posted = self._posted[dst]
            match = next(
                (r for r in posted
                 if (r.source in (ANY_SOURCE, src))
                 and _tag_match(r.tag, tag)),
                None,
            )
            if match is not None:
                posted.remove(match)
                self._deliver(entry, match)
                return req
            if ready:
                raise MPIError(
                    ErrorCode.ERR_PENDING,
                    f"rsend with no posted recv (src={src} dst={dst} "
                    f"tag={tag})",
                )
            if self._nbytes(data) <= self._eager_limit(src, dst):
                # eager: move now; sender side is complete immediately
                _eager_count.add()
                entry.data = self._move(data, src, dst)
                entry.transferred = True
                if not sync:
                    req.complete(status=Status(source=src, tag=tag))
            else:
                # rendezvous: hold the (immutable) buffer; the move
                # happens when the matching recv posts
                _rndv_count.add()
            _unexpected_count.add()
            self._unexpected[dst].append(entry)
        peruse.fire(self.comm, peruse.MSG_UNEX_INSERT, src=src, dst=dst,
                    tag=tag)
        return req

    def send(self, data, dst: int, tag: int = 0, *, src: int,
             sync: bool = False) -> None:
        """Blocking send. MPI_Send may return once the buffer is
        reusable; jax arrays are immutable so that is ALWAYS true — a
        plain blocking send never blocks (bsend-like), regardless of
        the eager/rendezvous data-movement protocol. Only ssend
        (sync=True) must wait for the match, which in single-controller
        driver mode requires the recv to already be posted.
        """
        req = self.isend(data, dst, tag, src=src, sync=sync)
        if sync:
            req.wait()

    # -- recv --------------------------------------------------------------
    def irecv(self, source: int = ANY_SOURCE, tag: int = ANY_TAG, *,
              dst: int) -> Request:
        """Nonblocking receive posted by rank ``dst``."""
        self._check_rank(dst, "destination")
        if source != ANY_SOURCE:
            self._check_rank(source, "source")
        if _obs.enabled:
            _obs.record("irecv", "pml", _time.perf_counter(), 0.0,
                        peer=source, comm_id=self.comm.cid)
        req = Request()
        entry = _RecvEntry(dst, source, tag, req)
        from . import peruse

        peruse.fire(self.comm, peruse.REQ_ACTIVATE, kind="recv",
                    src=source, dst=dst, tag=tag)
        with self._lock:
            if self._logger is not None:
                # pessimist determinant: logged UNDER the matching
                # lock so the event order equals the match order
                # (concurrent posters would otherwise log in a
                # different order than they match — replay would
                # swap their deliveries); the matched (src, tag) is
                # filled in at completion
                self._logger.record_recv_post(dst, source, tag, req)
            self._purge_cancelled(dst)
            unex = self._unexpected[dst]
            match = next(
                (s for s in unex
                 if (source in (ANY_SOURCE, s.src))
                 and _tag_match(tag, s.tag)),
                None,
            )
            if match is not None:
                unex.remove(match)
                peruse.fire(self.comm, peruse.REQ_MATCH_UNEX,
                            src=match.src, dst=dst, tag=match.tag)
                self._deliver(match, entry)
            else:
                self._posted[dst].append(entry)
        return req

    def recv(self, source: int = ANY_SOURCE, tag: int = ANY_TAG, *,
             dst: int) -> Tuple[Any, Status]:
        req = self.irecv(source, tag, dst=dst)
        st = req.wait()
        return req.value, st

    # -- probe -------------------------------------------------------------
    def iprobe(self, source: int = ANY_SOURCE, tag: int = ANY_TAG, *,
               dst: int) -> Optional[Status]:
        """Nonblocking probe of the unexpected queue (MPI_Iprobe)."""
        with self._lock:
            self._purge_cancelled(dst)
            for s in self._unexpected[dst]:
                if (source in (ANY_SOURCE, s.src)) and _tag_match(tag, s.tag):
                    return Status(source=s.src, tag=s.tag,
                                  count=int(s.data.size))
        return None

    # -- matched probe (MPI_Mprobe / MPI_Mrecv) ----------------------------
    def improbe(self, source: int = ANY_SOURCE, tag: int = ANY_TAG, *,
                dst: int):
        """Nonblocking matched probe: removes the matched message from
        the unexpected queue and returns a message handle (so a later
        wildcard recv cannot steal it); None when nothing matches."""
        with self._lock:
            self._purge_cancelled(dst)
            unex = self._unexpected[dst]
            match = next(
                (s for s in unex
                 if (source in (ANY_SOURCE, s.src))
                 and _tag_match(tag, s.tag)),
                None,
            )
            if match is None:
                return None
            unex.remove(match)
            if self._logger is not None:
                # improbe IS the nondeterministic match decision the
                # pessimist log exists to capture; without this the
                # restarted consumer would silently be delivered one
                # message fewer
                self._logger.record_matched_recv(
                    dst, source, tag, match.src, match.tag
                )
            return match  # the message handle

    def mrecv(self, message: "_SendEntry", *, dst: int):
        """Receive a message handle returned by improbe."""
        entry = _RecvEntry(dst, message.src, message.tag, Request())
        self._deliver(message, entry)
        return entry.request.value, entry.request.status

    def dump_queues(self, lock_timeout_s: float = 0.5) -> Dict[str, list]:
        """Debugger message-queue dump (the TotalView DLL contract,
        ``ompi/debuggers``): every pending send/recv with its
        match envelope. Lock acquisition is BOUNDED: the flight
        recorder calls this while diagnosing hangs, and a thread
        wedged inside a match-lock critical section (e.g. a
        rendezvous pull whose peer died) must not hang the dump."""
        if not self._lock.acquire(timeout=lock_timeout_s):
            return {"unexpected": [], "posted": [],
                    "error": "match lock held (a thread is wedged "
                             "inside the matching engine)"}
        try:
            for dst in set(self._unexpected) | set(self._posted):
                self._purge_cancelled(dst)
            return {
                "unexpected": [
                    {"src": s.src, "dst": s.dst, "tag": s.tag,
                     "bytes": self._nbytes(s.data),
                     "protocol": "eager" if s.transferred else "rndv"}
                    for q in self._unexpected.values() for s in q
                ],
                "posted": [
                    {"dst": r.dst, "source": r.source, "tag": r.tag}
                    for q in self._posted.values() for r in q
                ],
            }
        finally:
            self._lock.release()

    # -- persistent --------------------------------------------------------
    def send_init(self, data, dst: int, tag: int = 0, *, src: int) -> Request:
        def start(req):
            inner = self.isend(data, dst, tag, src=src)
            inner.on_complete(
                lambda r: req.complete(status=r.status)
            )

        return Request(persistent_start=start)

    def recv_init(self, source: int = ANY_SOURCE, tag: int = ANY_TAG, *,
                  dst: int) -> Request:
        def start(req):
            inner = self.irecv(source, tag, dst=dst)
            inner.on_complete(
                lambda r: req.complete(value=r.value, status=r.status)
            )

        return Request(persistent_start=start)

    # -- delivery ----------------------------------------------------------
    def _deliver(self, send: _SendEntry, recv: _RecvEntry) -> None:
        from . import peruse

        rec = _obs.enabled  # capture once: flag may flip mid-delivery
        t0 = _time.perf_counter() if rec else 0.0
        data = send.data
        if not send.transferred:
            peruse.fire(self.comm, peruse.REQ_XFER_BEGIN, src=send.src,
                        dst=recv.dst, tag=send.tag)
            data = self._move(data, send.src, recv.dst)  # rendezvous pull
        st = Status(source=send.src, tag=send.tag, count=int(data.size))
        recv.request.complete(value=data, status=st)
        send.request.complete(status=Status(source=send.src, tag=send.tag))
        peruse.fire(self.comm, peruse.REQ_XFER_END, src=send.src,
                    dst=recv.dst, tag=send.tag, count=int(data.size))
        peruse.fire(self.comm, peruse.REQ_COMPLETE, src=send.src,
                    dst=recv.dst, tag=send.tag)
        if rec and _obs.enabled:  # matched delivery incl. rndv pull
            _obs.record("deliver", "pml", t0, _time.perf_counter() - t0,
                        nbytes=self._nbytes(data), peer=send.src,
                        comm_id=self.comm.cid)
        _log.verbose(
            3,
            f"{self.comm.name}: delivered src={send.src} dst={send.dst} "
            f"tag={send.tag} n={data.size}",
        )

    # -- teardown ----------------------------------------------------------
    def pending_counts(self) -> Tuple[int, int]:
        with self._lock:
            for dst in set(self._unexpected) | set(self._posted):
                self._purge_cancelled(dst)
            return (
                sum(len(q) for q in self._unexpected.values()),
                sum(len(q) for q in self._posted.values()),
            )


class WirePmlEngine(PmlEngine):
    """PML for communicators spanning controller processes: local pairs
    use the in-process matching machinery unchanged; pairs crossing a
    process boundary ride the runtime's wire router (shm handoff on one
    host, DCN staging across hosts) — the ``btl/tcp``-under-ob1 role,
    with no caller-visible API difference (``btl_tcp_component.c:883``).

    Driver-mode contract: each process acts only as its LOCAL ranks —
    an isend must name a local ``src``, a recv a local ``dst``. Wire
    arrivals are pumped into the normal unexpected queues during
    recv/probe progress, so ordering, ANY_SOURCE/ANY_TAG and matched
    probes keep their MPI semantics across the boundary.
    """

    def __init__(self, comm) -> None:
        super().__init__(comm)
        self._router = comm.runtime.wire
        self._local_set = set(comm.local_comm_ranks)

    def _require_local(self, rank: int, what: str) -> None:
        if rank not in self._local_set:
            owner = self._router.owner_of(self.comm.group.world_rank(rank))
            raise MPIError(
                ErrorCode.ERR_RANK,
                f"{what} rank {rank} on {self.comm.name} is owned by "
                f"process {owner}; each process acts only as its local "
                "ranks (the acting-rank driver convention)",
            )

    # -- send --------------------------------------------------------------
    def isend(self, data, dst: int, tag: int = 0, *, src: int,
              sync: bool = False, ready: bool = False) -> Request:
        self._check_rank(dst, "destination")
        self._check_rank(src, "source")
        self._require_local(src, "acting source")
        if dst in self._local_set:
            return super().isend(data, dst, tag, src=src, sync=sync,
                                 ready=ready)
        with _obs.span(_spans.PML_SEND, bytes=_spans.nbytes(data),
                       peer=dst, tag=tag):
            return self._isend_wire(data, dst, tag, src, sync)

    def _isend_wire(self, data, dst: int, tag: int, src: int,
                    sync: bool) -> Request:
        """The cross-process leg: the whole transfer happens here,
        before the request is returned. rsend legally degrades to a
        standard send (an implementation MAY treat ready mode as
        standard; verifying the remote posted-recv would cost a round
        trip)."""
        data = _as_device_payload(data)
        from . import peruse

        peruse.fire(self.comm, peruse.REQ_ACTIVATE, kind="send",
                    src=src, dst=dst, tag=tag)
        if self._logger is not None:
            with self._lock:
                self._logger.record(src, dst, tag, data, sync)
        import numpy as _np

        nbytes = self._nbytes(data)
        with _obs.span(_spans.PML_D2H, bytes=nbytes):
            host = _np.asarray(data)
        seq = self._router.send_p2p(self.comm, src, dst, tag, host, sync)
        _wire_sends.add()
        _wire_bytes.add(nbytes)
        if not sync:
            req = Request()
            req.complete(status=Status(source=src, tag=tag))
            return req
        # ssend: completes when the receiver's match acks back
        router, cid = self._router, self.comm.cid
        src_world = self.comm.group.world_rank(src)

        def progress(r) -> None:
            router.poll_acks(src_world)
            if router.has_ack(cid, seq):
                router.take_ack(cid, seq)
                r.complete(status=Status(source=src, tag=tag))

        def block() -> None:
            import time as _time

            tok = None
            if _watchdog.enabled:
                tok = _watchdog.arm(
                    "p2p_ssend_ack", comm_id=cid, peer=dst,
                    info={"src": src, "dst": dst, "tag": tag,
                          "seq": seq},
                )
            try:
                limit = float(mca_var.get("pml_wire_timeout", 30.0))
                deadline = _time.monotonic() + limit
                while _time.monotonic() < deadline:
                    router.poll_acks(src_world, timeout_ms=100)
                    if router.take_ack(cid, seq):
                        return
                raise MPIError(
                    ErrorCode.ERR_PENDING,
                    f"ssend to rank {dst} never matched (no ack within "
                    f"{limit}s; pml_wire_timeout raises the limit)",
                )
            finally:
                if tok is not None:
                    _watchdog.disarm(tok)

        req = Request(progress_fn=progress, block_fn=block)
        # the block() completion path reaches Request.wait()'s bare
        # complete(): pre-set the status so both completion paths
        # report the same (source, tag)
        req.status = Status(source=src, tag=tag)
        return req

    # -- recv --------------------------------------------------------------
    def _drain(self, dst: int, timeout_ms: int = 0) -> bool:
        return self._router.drain_p2p(
            self.comm.group.world_rank(dst), timeout_ms=max(1, timeout_ms)
        )

    def irecv(self, source: int = ANY_SOURCE, tag: int = ANY_TAG, *,
              dst: int) -> Request:
        self._check_rank(dst, "destination")
        self._require_local(dst, "receiving")
        may_cross = source == ANY_SOURCE or source not in self._local_set
        if may_cross:
            # pump anything already queued before posting, so an
            # earlier wire arrival matches in order
            while self._drain(dst):
                pass
        req = super().irecv(source, tag, dst=dst)
        if may_cross and not req.is_complete:
            engine = self

            def progress(r) -> None:
                engine._drain(dst)

            def block() -> None:
                with _obs.span(_spans.PML_RECV_WAIT, source=source,
                               tag=tag):
                    wait_matched()

            def wait_matched() -> None:
                import time as _time

                tok = None
                if _watchdog.enabled:
                    tok = _watchdog.arm(
                        "p2p_recv", comm_id=engine.comm.cid,
                        peer=source,
                        info={"source": source, "tag": tag, "dst": dst},
                    )
                try:
                    from ..ft import ulfm as _ulfm
                    from ..runtime.wire import proc_topology

                    comm = engine.comm
                    if source == ANY_SOURCE:
                        ft_peers = list(proc_topology(comm).peers)
                    else:
                        ft_peers = [proc_topology(comm).owner[source]]
                    limit = float(mca_var.get("pml_wire_timeout", 30.0))
                    deadline = _time.monotonic() + limit
                    while (not req.is_complete
                           and _time.monotonic() < deadline):
                        # ULFM bound: a recv whose (possible) sender
                        # died — or whose comm was revoked — raises
                        # the typed error within one drain slice, not
                        # after the full pml_wire_timeout
                        _ulfm.state().check_wait(
                            comm.cid, ft_peers,
                            f"p2p recv(source={source}) awaiting",
                            epoch0=getattr(comm, "_ft_epoch0", 0))
                        engine._drain(dst, timeout_ms=100)
                    if not req.is_complete:
                        raise MPIError(
                            ErrorCode.ERR_PENDING,
                            f"recv(source={source}, tag={tag}) at rank "
                            f"{dst}: no matching message within "
                            f"{limit}s (pml_wire_timeout raises the "
                            "limit)",
                        )
                finally:
                    if tok is not None:
                        _watchdog.disarm(tok)

            req._progress_fn = progress
            req._block_fn = block
        return req

    def iprobe(self, source: int = ANY_SOURCE, tag: int = ANY_TAG, *,
               dst: int):
        self._require_local(dst, "probing")
        while self._drain(dst):
            pass
        return super().iprobe(source, tag, dst=dst)

    def improbe(self, source: int = ANY_SOURCE, tag: int = ANY_TAG, *,
                dst: int):
        self._require_local(dst, "probing")
        while self._drain(dst):
            pass
        return super().improbe(source, tag, dst=dst)

    # -- wire delivery (called by the router's drain) ----------------------
    def _enqueue_wire(self, src_rank: int, dst_rank: int, user_tag: int,
                      data, on_matched=None) -> None:
        """Insert one wire arrival into the matching machinery exactly
        where a local eager send would land (payload already moved, so
        the entry is 'transferred')."""
        from . import peruse

        req = Request()
        if on_matched is not None:
            req.on_complete(on_matched)
        entry = _SendEntry(src_rank, dst_rank, user_tag, data, req, False)
        entry.transferred = True
        _wire_recvs.add()
        with self._lock:
            if self._logger is not None:
                # a wire arrival IS a send landing in this process's
                # queues: log it under the matching lock exactly like a
                # local isend, or pessimist-log replay would deliver
                # fewer messages than the original run
                self._logger.record(src_rank, dst_rank, user_tag, data,
                                    False)
            self._purge_cancelled(dst_rank)
            posted = self._posted[dst_rank]
            match = next(
                (r for r in posted
                 if (r.source in (ANY_SOURCE, src_rank))
                 and _tag_match(r.tag, user_tag)),
                None,
            )
            if match is not None:
                posted.remove(match)
                self._deliver(entry, match)
                return
            _unexpected_count.add()
            self._unexpected[dst_rank].append(entry)
        peruse.fire(self.comm, peruse.MSG_UNEX_INSERT, src=src_rank,
                    dst=dst_rank, tag=user_tag)


class Ob1TpuComponent(mca_component.Component):
    """Default PML component ("ob1" kept as the name users know)."""

    NAME = "ob1"
    PRIORITY = 20

    def register_vars(self) -> None:
        register_vars()

    def query(self, ctx=None):
        if ctx is None:
            return (self.priority, self)
        if getattr(ctx, "spans_processes", False):
            return (self.priority, WirePmlEngine(ctx))
        return (self.priority, PmlEngine(ctx))


PML_FRAMEWORK.register(Ob1TpuComponent())


def comm_select(comm) -> PmlEngine:
    """Install the per-comm PML engine (mca_pml_base_select analogue)."""
    avail = PML_FRAMEWORK.available(comm)
    if not avail:
        raise MPIError(ErrorCode.ERR_NOT_AVAILABLE,
                       "no PML component available")
    _, comp, engine = avail[0]
    _log.verbose(2, f"{comm.name}: pml -> {comp.NAME}")
    return engine
