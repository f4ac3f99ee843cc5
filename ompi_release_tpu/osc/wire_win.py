"""Cross-process one-sided communication — home-process-applies RMA.

The reference's osc framework moves RMA data between ANY two ranks
over the BTLs (``ompi/mca/osc/rdma/osc_rdma_data_move.c``: active and
passive target movement; ``osc/pt2pt`` ships ops as active messages
when no RDMA path exists). Under the unified ``tpurun`` world each
controller process owns only its LOCAL members' window slices, so an
RMA op whose target lives in another process is SHIPPED to that
process (the target's *home*) at synchronization time:

- epoch close partitions the pending queue by target owner; local ops
  run as the normal compiled epoch program over the local submesh,
  remote ops serialize into one batch per owner process;
- the owner's *window service thread* applies an incoming batch into
  its local slices — the same ``lax.scan``/``lax.switch`` epoch
  program — and replies with the pre-op values (get/get_accumulate/
  fetch_and_op/compare_and_swap reads) plus a completion ack, which
  gives ``flush`` its remote-completion meaning;
- passive target is real: the lock state for a target rank lives at
  the target's OWNER process (service-side lock table with waiter
  queues), so origins in different processes contending for an
  exclusive lock serialize without the target's application code ever
  being involved — the osc/rdma passive-target model.

A batch is a ``DssBuffer`` envelope that carries the per-op request
records (JSON: kind, target, op name, index or displacement and count,
read flag, each payload's dtype and shape) followed by the payloads
themselves as raw frames over the wire's payload transports (shm rings
on one host, chunked DCN staging across hosts): consecutive payloads
share a frame while together they fit one wire segment
(``wire_pipeline_segsize``), a larger one travels alone, uncopied. The
reply carries the read values the same way, so a batch of k ranged
operations of c elements ships and returns O(k·c) elements whatever the
slot's size. No pickle, no eval. Only predefined reduction ops may
cross a process boundary (MPI itself restricts MPI_Accumulate to
predefined ops).
"""

from __future__ import annotations

import itertools
import json
import threading
import time
import weakref
from collections import deque
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax

from .. import obs as _obs
from ..mca import pvar
from ..mca import var as mca_var
from ..native import DssBuffer
from ..obs import spans as _spans
from ..obs import watchdog as _watchdog
from ..ops.op import PREDEFINED_OPS
from ..utils import output
from ..utils.errors import ErrorCode, MPIError
from .window import (LOCK_EXCLUSIVE, LOCK_SHARED, Window, _EpochKind,
                     _PendingOp)

_log = output.stream("osc")


def register_vars() -> None:
    mca_var.register(
        "osc_request_timeout_ms", "int", 120_000,
        "Bound in milliseconds on window-service request/reply waits "
        "(batches, lock grants — a grant may legitimately be deferred "
        "behind another holder, hence the generous default). The "
        "effective bound also honors wire_coll_timeout_ms when that "
        "is set higher",
    )
    mca_var.register(
        "osc_abandon_timeout_ms", "int", 10_000,
        "Bound in milliseconds on the best-effort lock-abandon notice "
        "after a timed-out acquire (the home may be unreachable)",
    )
    mca_var.register(
        "osc_pscw_timeout_s", "float", 0.0,
        "Bound in seconds on PSCW start()/wait() notice waits; 0 = "
        "unbounded (MPI's rule — the partner may compute arbitrarily "
        "long before complete()); set it to turn a hung partner into "
        "a diagnosable error",
    )


register_vars()


class OscTuning:
    """One immutable snapshot of the window service's hot-path cvars
    (the ``WireRouter.tuning()`` pattern): per-request registry
    lookups and hard-coded blocking-wait deadlines become attribute
    reads off the current snapshot, re-resolved only when the MCA
    write generation moves — RMA steady state never touches the
    registry."""

    __slots__ = ("gen", "request_timeout_ms", "abandon_timeout_ms",
                 "pscw_timeout_s", "segment")

    def __init__(self) -> None:
        self.gen = mca_var.VARS.generation
        req = int(mca_var.get("osc_request_timeout_ms", 120_000)
                  or 120_000)
        wire = int(mca_var.get("wire_coll_timeout_ms", 60_000)
                   or 60_000)
        # an operator-raised collective wait bound must not be
        # undercut by the RMA default: a deferred lock grant can wait
        # behind a holder for as long as any collective may block
        self.request_timeout_ms = max(req, wire)
        self.abandon_timeout_ms = int(
            mca_var.get("osc_abandon_timeout_ms", 10_000) or 10_000)
        self.pscw_timeout_s = float(
            mca_var.get("osc_pscw_timeout_s", 0) or 0)
        # the wire's own fragment size: consecutive batch payloads
        # share a frame while together they fit one (``_frames_of``)
        self.segment = max(0, int(
            mca_var.get("wire_pipeline_segsize", 1 << 20) or 0))


_win_requests = pvar.counter(
    "osc_wire_requests",
    "cross-process window service requests (batch/lock/abandon)",
)

_wire_bytes = pvar.counter(
    "osc_wire_bytes",
    "bytes of RMA batch frames this process sent (request records and "
    "payloads) plus read frames it received",
)
_wire_ops = pvar.counter(
    "osc_wire_ops",
    "RMA operations shipped to another process's window service",
)

#: The home's turn of a batch, timed at the home and sent back on its
#: reply (``WinService._reply``'s ``turn``); the origin sums
#: them over the replies it was routed. ``out`` and ``back`` compare a
#: stamp of each process and tick only where the two share a clock
#: (:func:`_same_host`); across hosts ``reply_wait - turn`` is their sum.
_home_turn = pvar.timer(
    "osc_home_turn_seconds",
    "seconds the homes of this process's RMA batches spent on them, "
    "request envelope taken to reply composed (stamped at the home)",
)
_home_recv = pvar.timer(
    "osc_home_recv_seconds",
    "of those, from the envelope taken to the batch's payload frames "
    "off the wire and its request records parsed",
)
_home_program = pvar.timer(
    "osc_home_program_seconds",
    "of those, applying the batch: range checks, the window's lock, "
    "the epoch program, the read values on the host",
)
_home_out = pvar.timer(
    "osc_home_out_seconds",
    "seconds from a batch's request envelope sent to the home's "
    "service thread holding it (wire leg, wake-up, queue behind an "
    "earlier batch); ticks only between processes of one host",
)
_home_back = pvar.timer(
    "osc_home_back_seconds",
    "seconds from the home composing a batch's reply to the reply "
    "and its read frames routed at the origin; ticks only between "
    "processes of one host",
)

#: the block of a reply that carries no turn (lock grant, abandon, error)
_NO_TURN = (0, 0, 0, 0)


def _same_host(router, pidx: int) -> bool:
    """Does process ``pidx`` share this one's host, and so its
    ``CLOCK_MONOTONIC``? What ``nativewire._same_host`` compares: the
    two modex cards' ``host``."""
    nw = router._nw
    return nw is not None and nw._same_host(pidx)


#: live window services (one per runtime) for the flight recorder's
#: lock-table contributor — weak so a torn-down runtime's service
#: never pins memory or shows up in dumps
_services: "weakref.WeakSet" = weakref.WeakSet()


def _lock_tables_snapshot() -> List[Dict]:
    """Dump contributor: every live service's passive-target lock
    table + outstanding reply slots (who holds what, who waits).
    Lock acquisition is BOUNDED: the recorder dumps because something
    is hung, possibly a thread wedged inside these very critical
    sections — blocking here would hang the flight recorder itself
    (and, via _dump_lock, every later dump)."""
    out = []
    for svc in list(_services):
        entry: Dict = {"pidx": svc.my_pidx}
        if svc._state_lock.acquire(timeout=0.5):
            try:
                entry["locks"] = [
                    {"cid": k[0], "win_seq": k[1], "target": k[2],
                     "mode": st.mode, "holders": sorted(st.holders),
                     "waiters": [{"origin": w[0], "type": w[1],
                                  "local": w[2] is not None}
                                 for w in st.waiters]}
                    for k, st in svc._locks.items()
                ]
            finally:
                svc._state_lock.release()
        else:
            entry["locks"] = "unavailable: state lock held (a thread " \
                             "is wedged inside the lock table)"
        if svc._reply_guard.acquire(timeout=0.5):
            try:
                entry["outstanding_requests"] = len(svc._reply_slots)
            finally:
                svc._reply_guard.release()
        else:
            entry["outstanding_requests"] = "unavailable: reply guard held"
        out.append(entry)
    return out


_watchdog.add_contributor("window_locks", _lock_tables_snapshot)

#: window-service envelopes (any-source); payloads ride the three
#: sibling channels so an any-source envelope pop can never swallow
#: another sender's payload frame
WIRE_WIN_SERVICE = 5 << 20
WIRE_WIN_DATA = 6 << 20
WIRE_WIN_REPLY = 7 << 20
WIRE_WIN_RDATA = 8 << 20

_WIN_MAGIC = "WWIN"

KIND_BATCH = 1    # arg1 = release_target comm rank (or -1)
KIND_LOCK = 2     # arg1 = target, arg2 = lock type
KIND_ABANDON = 3  # arg1 = target: forget this origin's lock interest
KIND_POST = 4     # one-way: src process posted an exposure epoch
KIND_COMPLETE = 5  # one-way: src process completed its access epoch
KIND_ERROR = 99   # home-side failure applying a request


def _batch_meta(todo: List[_PendingOp]) -> List[Dict]:
    """Per-op request records (the wire header metadata). Shared by
    the per-call pack below and osc/plan's frozen ``BatchTemplate`` so
    the two can never drift. The predefined check is by IDENTITY, not
    name: a user op that merely shares a predefined op's name must be
    refused, or the home would silently apply the predefined one."""
    meta = []
    for p in todo:
        if p.op is not None and PREDEFINED_OPS.get(p.op.name) is not p.op:
            raise MPIError(
                ErrorCode.ERR_OP,
                f"cross-process RMA requires a predefined op, got "
                f"'{p.op.name}' (MPI_Accumulate's own rule)",
            )
        meta.append({
            "k": p.kind,
            "t": int(p.target),
            "o": p.op.name if p.op is not None else "",
            "i": -1 if p.index is None else int(p.index),
            "r": p.request is not None,
            # a ranged op: displacement and count (-1: not ranged)
            "d": -1 if p.disp is None else int(p.disp),
            "n": -1 if p.count is None else int(p.count),
            "hd": p.data is not None,
            "hc": p.compare is not None,
        })
    return meta


def _payloads(todo: List[_PendingOp]) -> List:
    """The batch's payloads in wire order: per op its data, then its
    compare value."""
    return [x for p in todo for x in (p.data, p.compare) if x is not None]


def _descs(arrays) -> List[List]:
    return [[str(a.dtype), [int(d) for d in a.shape]] for a in arrays]


def _frames_of(sizes: List[int], seg: int) -> List[int]:
    """How many consecutive payloads each wire frame holds: payloads
    share a frame while together they fit one wire segment; one that
    does not fit travels alone (and uncopied)."""
    out: List[int] = []
    n = used = 0
    for size in sizes:
        if n and used + size > seg:
            out.append(n)
            n = used = 0
        n += 1
        used += size
    if n:
        out.append(n)
    return out


class Batch:
    """One home's batch as it goes on the wire: the request records
    (``meta``, JSON in ASCII: ops, payload descriptors, payloads per
    frame), the payloads on the host in wire order, and how many of
    them each frame holds."""

    __slots__ = ("meta", "frames", "arrays")

    def __init__(self, meta: str, frames: Tuple[int, ...],
                 arrays: List[np.ndarray]) -> None:
        self.meta = meta
        self.frames = frames
        self.arrays = arrays

    @property
    def nbytes(self) -> int:
        return len(self.meta) + sum(int(a.nbytes) for a in self.arrays)


def _batch_header(todo: List[_PendingOp], seg: int
                  ) -> Tuple[str, Tuple[int, ...]]:
    """(request records as JSON, payloads per frame): everything
    of a batch but the payload bytes, from shapes alone — what a frozen
    ``BatchTemplate`` keeps."""
    parts = _payloads(todo)
    frames = _frames_of([_spans.nbytes(x) for x in parts], seg)
    meta = json.dumps({"ops": _batch_meta(todo), "pay": _descs(parts),
                       "frames": frames}, separators=(",", ":"))
    return meta, tuple(frames)


def _fetch(parts: List) -> List[np.ndarray]:
    """The payloads on the host: every device array's copy is started
    before the first is waited for; a payload queued from the host
    (``window._payload``) is there already and goes as it is."""
    for x in parts:
        if isinstance(x, jax.Array):
            x.copy_to_host_async()
    return [np.asarray(x) for x in parts]


def _pack_batch(todo: List[_PendingOp], seg: int,
                header: Optional[Tuple] = None) -> Batch:
    """A pending-op batch as it goes on the wire. ``header``: the
    signature's frozen ``_batch_header`` (osc/plan), else composed
    here; the payloads are fetched either way (``ompi.osc.d2h``; its
    ``bytes`` are what a device held: a host payload is not fetched)."""
    with _obs.span(_spans.OSC_PACK) as sp:
        meta, frames = header or _batch_header(todo, seg)
        parts = _payloads(todo)
        with _obs.span(_spans.OSC_D2H, bytes=sum(
                _spans.nbytes(x) for x in parts
                if isinstance(x, jax.Array))):
            arrays = _fetch(parts)
        batch = Batch(meta, frames, arrays)
        sp.set_metadata(bytes=batch.nbytes)
    return batch


def _unpack_batch(meta: str, arrays: List[np.ndarray]
                  ) -> List[_PendingOp]:
    """Inverse of :func:`_pack_batch`; payloads stay the host arrays
    the wire delivered, requests are fresh local ones for ops that
    want a read back."""
    from ..request.request import Request

    arrays = iter(arrays)
    todo = []
    for m in json.loads(meta)["ops"]:
        todo.append(_PendingOp(
            m["k"], m["t"],
            data=(next(arrays) if m["hd"] else None),
            op=(PREDEFINED_OPS[m["o"]] if m["o"] else None),
            request=(Request() if m["r"] else None),
            compare=(next(arrays) if m["hc"] else None),
            index=(None if m["i"] < 0 else m["i"]),
            disp=(None if m["d"] < 0 else m["d"]),
            count=(None if m["n"] < 0 else m["n"]),
        ))
    return todo


def _keep_host(arr, _device):
    """``put=`` of a payload receive: the host array as it arrived."""
    return arr


def _send_frames(router, dst_pidx: int, tag: int,
                 arrays: List[np.ndarray], frames) -> None:
    """The payloads as wire frames: one alone goes as it is, several
    are joined byte for byte (together they fit one wire segment)."""
    i = 0
    for n in frames:
        group = arrays[i:i + n]
        i += n
        router._send_payload(
            dst_pidx, tag,
            group[0] if n == 1 else np.concatenate(
                [np.ascontiguousarray(a).reshape(-1).view(np.uint8)
                 for a in group]))


def _recv_frames(router, src_pidx: int, tag: int, descs, frames
                 ) -> List[np.ndarray]:
    """Inverse of :func:`_send_frames`: every frame is taken off the
    wire (whatever it turns out to hold), then cut into the payloads
    ``descs`` describes, as views."""
    raw = [router._recv_payload(tag, src_pidx, put=_keep_host)
           for _ in frames]
    out: List[np.ndarray] = []
    i = 0
    for n, frame in zip(frames, raw):
        buf = np.ascontiguousarray(frame).reshape(-1).view(np.uint8)
        off = 0
        for dtype, shape in descs[i:i + n]:
            dt = np.dtype(dtype)
            size = int(np.prod(shape, dtype=np.int64)) * dt.itemsize
            out.append(buf[off:off + size].view(dt).reshape(shape))
            off += size
        if off != buf.size:
            raise MPIError(
                ErrorCode.ERR_TRUNCATE,
                f"window frame of {buf.size} bytes for {off} bytes of "
                "payload")
        i += n
    return out


class _LockState:
    __slots__ = ("mode", "holders", "waiters")

    def __init__(self) -> None:
        self.mode: Optional[int] = None
        self.holders: set = set()  # origin process indices
        self.waiters: deque = deque()  # (origin, type, event|None)


class WinService:
    """Per-runtime window service: applies incoming RMA batches into
    home windows and arbitrates passive-target locks."""

    def __init__(self, runtime) -> None:
        self.rt = runtime
        self.router = runtime.wire
        self.ep = runtime.wire.ep
        self.my_pidx = int(runtime.bootstrap["process_index"])
        self.windows: Dict[Tuple[int, int], "WireWindow"] = {}
        self._locks: Dict[Tuple[int, int, int], _LockState] = {}
        self._state_lock = threading.Lock()
        # PSCW notice sets per window key: which processes have posted
        # an exposure epoch / completed an access epoch (consumed by
        # start()/wait() respectively)
        self._posts: Dict[Tuple[int, int], set] = {}
        self._completes: Dict[Tuple[int, int], set] = {}
        self._pscw_cv = threading.Condition(self._state_lock)
        #: token-demultiplexed replies: every outstanding request
        #: registers a slot keyed by its token; ONE thread at a time
        #: pumps the shared WIRE_WIN_REPLY channel (``_pump_lock``) and
        #: routes each reply — and its RDATA payload — to its slot, so
        #: any number of threads can have requests in flight and a
        #: deferred grant for one can never block another's reply
        self._reply_slots: Dict[int, dict] = {}
        self._reply_guard = threading.Lock()
        self._pump_lock = threading.Lock()
        #: per-request token echoed in replies: after a timeout, a
        #: LATE reply must not be mistaken for the retry's (same cid/
        #: seq/kind) — tokens make staleness decidable
        self._token = itertools.count(1)
        self._tuning = OscTuning()
        self._stop = threading.Event()
        _services.add(self)  # flight-recorder lock-table visibility
        self._thread = threading.Thread(
            target=self._serve, daemon=True, name="win-service"
        )
        self._thread.start()

    # -- lifecycle ---------------------------------------------------------
    @classmethod
    def ensure(cls, runtime) -> "WinService":
        svc = getattr(runtime, "_win_service", None)
        if svc is None:
            svc = runtime._win_service = cls(runtime)
        return svc

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2)

    # -- tuning snapshot ---------------------------------------------------
    def tuning(self) -> OscTuning:
        """Current tuning snapshot: one generation compare on the hot
        path; any cvar write re-resolves at the next call."""
        t = self._tuning
        if t.gen != mca_var.VARS.generation:
            t = self._tuning = OscTuning()
        return t

    def refresh_tuning(self) -> OscTuning:
        self._tuning = OscTuning()
        return self._tuning

    def register(self, win: "WireWindow") -> None:
        with self._state_lock:
            self.windows[(win.comm.cid, win.win_seq)] = win

    def unregister(self, win: "WireWindow") -> None:
        key = (win.comm.cid, win.win_seq)
        with self._state_lock:
            self.windows.pop(key, None)
            # win_seq is monotone per comm, so a freed window's notice
            # and lock entries can never be consumed again — drop them
            # (late frames for the key are refused by _window())
            self._posts.pop(key, None)
            self._completes.pop(key, None)
            for lk in [k for k in self._locks if k[:2] == key]:
                del self._locks[lk]

    def _window(self, cid: int, seq: int) -> "WireWindow":
        with self._state_lock:
            w = self.windows.get((cid, seq))
        if w is None:
            raise MPIError(
                ErrorCode.ERR_WIN,
                f"window service: no window (cid={cid}, seq={seq}) — "
                "window creation order diverged across processes?",
            )
        return w

    # -- service loop ------------------------------------------------------
    def _serve(self) -> None:
        from ..btl.components import stashed_recv

        while not self._stop.is_set():
            try:
                src_nid, raw = stashed_recv(
                    self.ep, None, WIRE_WIN_SERVICE,
                    time.monotonic() + 0.2,
                )
            except MPIError:
                continue
            except Exception:
                if self._stop.is_set():
                    return
                raise
            h_recv = time.monotonic_ns()
            try:
                self._handle(src_nid - 1, raw, h_recv)
            except Exception as e:
                # NOTHING may kill the service: a malformed frame, a
                # corrupt npz, or a user error surfacing as a jax/numpy
                # exception (bad payload shape) would otherwise silently
                # disable all cross-process RMA for this process
                _log.verbose(1, f"win service: dropping frame from "
                                f"process {src_nid - 1}: "
                                f"{type(e).__name__}: {e}")

    def _handle(self, src_pidx: int, raw: bytes, h_recv: int) -> None:
        """One service frame; ``h_recv``: ``time.monotonic_ns()`` when
        the service thread took it off its channel."""
        env = DssBuffer(raw)
        if env.unpack_string() != _WIN_MAGIC:
            _log.verbose(1, "win service: non-window frame dropped")
            return
        cid, seq, kind, arg1, arg2, token = env.unpack_int64(6)
        token = int(token)
        if kind == KIND_BATCH:
            self._handle_batch(src_pidx, env, len(raw), int(cid),
                               int(seq), int(arg1), int(arg2), token,
                               h_recv)
        elif kind == KIND_LOCK:
            win = self._window(int(cid), int(seq))
            granted = self.acquire(win, int(arg1), src_pidx, int(arg2),
                                   event=None, token=token)
            if granted:
                self._reply(src_pidx, int(cid), int(seq), KIND_LOCK, [],
                            token)
            # else: deferred — release() sends the grant later
        elif kind == KIND_ABANDON:
            win = self._window(int(cid), int(seq))
            self.abandon(win, int(arg1), src_pidx)
            self._reply(src_pidx, int(cid), int(seq), KIND_ABANDON, [],
                        token)
        elif kind == KIND_POST:
            self.pscw_record(self._posts, (int(cid), int(seq)), src_pidx)
        elif kind == KIND_COMPLETE:
            self.pscw_record(self._completes, (int(cid), int(seq)),
                             src_pidx)
        else:
            _log.verbose(1, f"win service: unknown kind {kind}")

    def _handle_batch(self, src_pidx: int, env, limit: int, cid: int,
                      seq: int, release_target: int, n_frames: int,
                      token: int, h_recv: int) -> None:
        """One batch, from its envelope to its reply sent. Its frames
        must be consumed even if applying fails, and the origin must
        get SOME reply or it stalls for the full request timeout —
        failures reply KIND_ERROR (loud at the origin, service stays
        alive). The turn is stamped on the way (``time.monotonic_ns``:
        one clock for every process of a host) and rides back on the
        reply; ``(origin, token)`` joins this span to the origin's
        ``ompi.osc.reply_wait``."""
        rec = _obs.enabled  # capture once: flag may flip mid-apply
        with _obs.span(_spans.OSC_APPLY, origin=src_pidx,
                       token=token) as sp:
            nbytes = 0
            try:
                try:
                    meta = env.unpack_string(limit)
                    head = json.loads(meta)
                    if len(head["frames"]) != n_frames:
                        raise ValueError("frame count")
                except Exception:
                    # unreadable records: take the frames off the wire
                    # all the same, each as whatever it holds
                    for _ in range(n_frames):
                        self.router._recv_payload(WIRE_WIN_DATA, src_pidx,
                                                  put=_keep_host)
                    raise
                arrays = _recv_frames(self.router, src_pidx, WIRE_WIN_DATA,
                                      head["pay"], head["frames"])
                nbytes = len(meta) + sum(int(a.nbytes) for a in arrays)
                win = self._window(cid, seq)
                todo = _unpack_batch(meta, arrays)
                sp.set_metadata(ops=len(todo), bytes=nbytes)
                h_frames = time.monotonic_ns()  # and the program's start
                reads = win._apply_home_batch(todo)
                h_prog1 = time.monotonic_ns()
                if release_target >= 0:
                    self.release(win, release_target, src_pidx)
            except Exception as e:
                _log.verbose(1, f"win service: batch from process "
                                f"{src_pidx} failed: {e}")
                self._reply(src_pidx, cid, seq, KIND_ERROR, [], token)
                return
            h_reply = time.monotonic_ns()
            self._reply(src_pidx, cid, seq, KIND_BATCH, reads, token,
                        turn=(h_recv, h_reply, h_frames - h_recv,
                              h_prog1 - h_frames))
            if rec and _obs.enabled:
                # consumer side of the origin's (origin pidx, token)
                # flow: both values rode the request envelope. The
                # interval is the turn as stamped (``perf_counter``,
                # the journal's clock, is CLOCK_MONOTONIC too)
                _obs.record("win_apply", "osc", h_recv / 1e9,
                            (h_reply - h_recv) / 1e9, nbytes=nbytes,
                            peer=src_pidx, comm_id=cid,
                            flow=_obs.flow_id("win", src_pidx, token),
                            flow_side="t")

    def _reply(self, dst_pidx: int, cid: int, seq: int, kind: int,
               reads: List[np.ndarray], token: int = 0,
               turn: Tuple[int, int, int, int] = _NO_TURN) -> None:
        """``turn``, a batch's: ``[h_recv, h_reply, h_frames - h_recv,
        h_prog1 - h_frames]`` in ns of the home's ``CLOCK_MONOTONIC``,
        behind the reply's own five int64s (in their item: a second one
        would cost the origin a second parse); zeros on every other
        kind of reply, which the origin ignores."""
        env = DssBuffer()
        env.pack_string(_WIN_MAGIC)
        env.pack_int64([cid, seq, kind, len(reads), token, *turn])
        frames: List[int] = []
        if reads:
            frames = _frames_of([int(v.nbytes) for v in reads],
                                self.tuning().segment)
            env.pack_string(json.dumps(
                {"pay": _descs(reads), "frames": frames},
                separators=(",", ":")))
        self.router._retry(
            lambda: self.ep.send(self.router._nid(dst_pidx),
                                 WIRE_WIN_REPLY, env.tobytes()),
            f"window reply to process {dst_pidx}",
        )
        _send_frames(self.router, dst_pidx, WIRE_WIN_RDATA, reads, frames)

    # -- origin-side request/reply -----------------------------------------
    def _send_lock(self, owner_pidx: int) -> threading.Lock:
        """Per-OWNER outbound framing lock (the router's lazily-created
        registry): a request envelope and its payload must land
        back-to-back on the owner's service FIFO, but the lock is held
        only for the SEND — never across the reply wait (the old
        process-wide ``outbound`` lock held through deferred
        lock-grant waits deadlocked a second thread's unlock for up to
        120 s)."""
        return self.router._chan_lock("win_send", owner_pidx)

    def _pump_replies(self, deadline: float) -> None:
        """Pop ONE reply (and its RDATA payload, if any) off the shared
        reply channel and route it to its token's slot, with the home's
        turn block and the instant it was routed. Caller holds
        ``_pump_lock``. Replies whose requester already timed out and
        deregistered are drained and dropped — their RDATA must be
        consumed here or the NEXT read-carrying reply would unpack the
        wrong arrays."""
        from ..btl.components import stashed_recv

        try:
            src_nid, raw = stashed_recv(self.ep, None, WIRE_WIN_REPLY,
                                        deadline)
        except MPIError as e:
            if e.code is ErrorCode.ERR_PENDING:
                return  # nothing within the slice; caller re-checks
            raise  # endpoint closed / link dead: surface it NOW, not
            #        as a misleading 120 s reply timeout
        renv = DssBuffer(raw)
        if renv.unpack_string() != _WIN_MAGIC:
            raise MPIError(ErrorCode.ERR_INTERN,
                           "corrupt window reply envelope")
        rcid, rseq, rkind, n_reads, rtoken, *turn = renv.unpack_int64(9)
        reads: List[np.ndarray] = []
        if int(n_reads) and int(rkind) != KIND_ERROR:
            # the owner's service thread sends a reply's read frames
            # directly behind its envelope, so consuming them HERE
            # (src-matched) keeps the per-owner payload stream aligned
            # no matter which thread's reply this is
            head = json.loads(renv.unpack_string(len(raw)))
            with _obs.span(_spans.OSC_UNPACK) as sp:
                reads = _recv_frames(self.router, src_nid - 1,
                                     WIRE_WIN_RDATA, head["pay"],
                                     head["frames"])
                got = sum(int(v.nbytes) for v in reads)
                sp.set_metadata(bytes=got)
            _wire_bytes.add(got)
        with self._reply_guard:
            slot = self._reply_slots.get(int(rtoken))
            if slot is None:
                _log.verbose(
                    1, f"discarding stale window reply (cid={rcid}, "
                       f"seq={rseq}, kind={rkind}, token={rtoken})")
                return
            slot["cid"], slot["seq"] = int(rcid), int(rseq)
            slot["kind"] = int(rkind)
            slot["reads"] = reads
            slot["turn"] = turn
            slot["routed"] = time.monotonic_ns()
            slot["ev"].set()

    def request(self, win: "WireWindow", owner_pidx: int, kind: int,
                arg1: int, arg2: int,
                payload: Optional[Batch] = None,
                timeout_ms: Optional[int] = None) -> List[np.ndarray]:
        """Send one request to ``owner_pidx`` and await its reply
        (lock grants may be deferred behind another holder, hence the
        generous default bound — ``osc_request_timeout_ms``, read off
        the tuning snapshot, never the registry). ``payload``: a
        batch, whose request records ride the envelope and whose
        frames follow it (``arg2`` then tells the home how many).
        Returns the read arrays.

        Concurrency: the reply channel is demultiplexed by token, so
        any number of threads may have requests outstanding — while a
        thread waits for a deferred lock grant, the thread whose
        unlock PRODUCES that grant proceeds through its own
        request/reply unimpeded (the ADVICE r5 two-thread deadlock)."""
        if timeout_ms is None:
            timeout_ms = self.tuning().request_timeout_ms
        token = next(self._token)
        _win_requests.add()
        nbytes = payload.nbytes if payload is not None else 0
        rec = _obs.enabled  # capture once: flag may flip mid-request
        wd_tok = None
        if _watchdog.enabled:
            wd_tok = _watchdog.arm(
                f"win_request_kind{kind}", comm_id=win.comm.cid,
                peer=owner_pidx,
                info={"win_seq": win.win_seq, "token": token,
                      "arg1": arg1, "arg2": arg2},
            )
        slot = {"ev": threading.Event(), "reads": None, "kind": None,
                "cid": -1, "seq": -1, "turn": _NO_TURN, "routed": 0}
        with self._reply_guard:
            self._reply_slots[token] = slot
        try:
            with _obs.span(_spans.OSC_REQUEST, kind=kind, peer=owner_pidx,
                           bytes=nbytes):
                env = DssBuffer()
                env.pack_string(_WIN_MAGIC)
                if payload is not None:
                    arg2 = len(payload.frames)
                env.pack_int64([win.comm.cid, win.win_seq, kind, arg1,
                                arg2, token])
                if payload is not None:
                    env.pack_string(payload.meta)
                with self._send_lock(owner_pidx):
                    self.router._retry(
                        lambda: self.ep.send(
                            self.router._nid(owner_pidx),
                            WIRE_WIN_SERVICE, env.tobytes()),
                        f"window request to process {owner_pidx}",
                    )
                    t_env = time.monotonic_ns()
                    if payload is not None:
                        _send_frames(self.router, owner_pidx,
                                     WIRE_WIN_DATA, payload.arrays,
                                     payload.frames)
                        _wire_bytes.add(nbytes)
                with _obs.span(_spans.OSC_REPLY_WAIT, kind=kind,
                               peer=owner_pidx) as sp:
                    self._await_reply(slot, kind, owner_pidx, timeout_ms)
                    if slot["turn"][0]:
                        self._count_turn(sp, token, owner_pidx, t_env,
                                         slot)
                if rec and _obs.enabled:
                    # producer side: the home's win_apply span derives
                    # the same (origin pidx, token) id from the
                    # envelope. The interval is the request in flight,
                    # envelope sent to reply routed: the stamps the
                    # timers read
                    _obs.record(
                        "win_request", "osc", t_env / 1e9,
                        (slot["routed"] - t_env) / 1e9, nbytes=nbytes,
                        peer=owner_pidx, comm_id=win.comm.cid,
                        flow=_obs.flow_id("win", self.my_pidx, token),
                        flow_side="s")
        finally:
            if wd_tok is not None:
                _watchdog.disarm(wd_tok)
            with self._reply_guard:
                self._reply_slots.pop(token, None)
        if slot["kind"] == KIND_ERROR:
            raise MPIError(
                ErrorCode.ERR_RMA_SYNC,
                f"window request (kind {kind}) failed at its "
                f"home process {owner_pidx} — bad payload "
                "shape/dtype for the target window?",
            )
        if (slot["cid"], slot["seq"], slot["kind"]) != (
                win.comm.cid, win.win_seq, kind):
            raise MPIError(
                ErrorCode.ERR_INTERN,
                f"window reply token {token} carries "
                f"(cid={slot['cid']}, seq={slot['seq']}, "
                f"kind={slot['kind']}), expected (cid={win.comm.cid}, "
                f"seq={win.win_seq}, kind={kind})",
            )
        return slot["reads"] or []

    def _count_turn(self, sp, token: int, owner_pidx: int, t_env: int,
                    slot: dict) -> None:
        """A batch's reply is routed: its home's turn into the
        ``osc_home_*_seconds`` timers and, as consecutive pieces from
        the wait's own start — the way out, the turn, the way back —
        into the stats of the open ``ompi.osc.reply_wait``."""
        h_recv, h_reply, recv_ns, prog_ns = slot["turn"]
        turn_ns = h_reply - h_recv
        _home_turn.add(turn_ns / 1e9)
        _home_recv.add(recv_ns / 1e9)
        _home_program.add(prog_ns / 1e9)
        stats = {"token": token, "turn_us": turn_ns / 1e3,
                 "recv_us": recv_ns / 1e3, "program_us": prog_ns / 1e3}
        if _same_host(self.router, owner_pidx):
            out = max(0, h_recv - t_env)
            back = max(0, slot["routed"] - h_reply)
            _home_out.add(out / 1e9)
            _home_back.add(back / 1e9)
            stats.update(out_us=out / 1e3, back_us=back / 1e3)
        sp.set_metadata(**stats)

    def _await_reply(self, slot: dict, kind: int, owner_pidx: int,
                     timeout_ms: int) -> None:
        """Until ``slot``'s reply is routed to it. One thread at a
        time pumps the shared channel; the others park on their event
        (woken the instant the pump routes their reply) — whoever
        holds the pump routes EVERY arriving reply to its waiter."""
        deadline = time.monotonic() + timeout_ms / 1000
        while not slot["ev"].is_set():
            if self._pump_lock.acquire(blocking=False):
                try:
                    if slot["ev"].is_set():
                        break
                    self._pump_replies(time.monotonic() + 0.2)
                finally:
                    self._pump_lock.release()
            else:
                slot["ev"].wait(timeout=0.02)
            if slot["ev"].is_set():
                break
            if time.monotonic() >= deadline:
                raise MPIError(
                    ErrorCode.ERR_PENDING,
                    f"window request (kind {kind}) to process "
                    f"{owner_pidx} got no reply within "
                    f"{timeout_ms / 1000:.0f}s",
                )

    # -- PSCW notices (one-way; no reply awaited) --------------------------
    def notify(self, dst_pidx: int, win: "WireWindow", kind: int) -> None:
        env = DssBuffer()
        env.pack_string(_WIN_MAGIC)
        env.pack_int64([win.comm.cid, win.win_seq, kind, 0, 0, 0])
        self.router._retry(
            lambda: self.ep.send(self.router._nid(dst_pidx),
                                 WIRE_WIN_SERVICE, env.tobytes()),
            f"window notice (kind {kind}) to process {dst_pidx}",
        )

    def pscw_record(self, table: Dict, key: Tuple[int, int],
                    pidx: int) -> None:
        with self._pscw_cv:
            table.setdefault(key, set()).add(pidx)
            self._pscw_cv.notify_all()

    def pscw_check(self, table: Dict, key: Tuple[int, int],
                   procs) -> bool:
        """Non-consuming peek: have all of ``procs`` recorded their
        notice? (MPI_Win_test's question.)"""
        with self._pscw_cv:
            return set(procs) <= table.get(key, set())

    def pscw_await(self, table: Dict, key: Tuple[int, int],
                   procs, what: str) -> None:
        """Block until every process in ``procs`` has recorded its
        notice, then CONSUME those notices (the next epoch must wait
        for its own). MPI requires wait() to block as long as it
        takes (the partner may compute arbitrarily long before
        complete()), so the default is unbounded; operators can bound
        it with ``--mca osc_pscw_timeout_s N`` to turn a hung partner
        into a diagnosable error."""
        want = set(procs)
        if not want:  # MPI_GROUP_EMPTY epochs are legal no-ops
            return
        timeout_s = self.tuning().pscw_timeout_s
        deadline = (time.monotonic() + timeout_s) if timeout_s else None
        wd_tok = None
        if _watchdog.enabled:
            wd_tok = _watchdog.arm(
                f"pscw_{what}", comm_id=key[0],
                info=lambda: {"awaiting_procs": sorted(
                    want - table.get(key, set()))},
            )
        try:
            with self._pscw_cv:
                while not want <= table.get(key, set()):
                    if deadline is not None:
                        left = deadline - time.monotonic()
                        if left <= 0:
                            raise MPIError(
                                ErrorCode.ERR_RMA_SYNC,
                                f"PSCW {what} timed out awaiting "
                                f"processes "
                                f"{sorted(want - table.get(key, set()))}",
                            )
                    self._pscw_cv.wait(timeout=1.0)
                table[key] -= want
        finally:
            if wd_tok is not None:
                _watchdog.disarm(wd_tok)

    # -- home-side lock table ----------------------------------------------
    def _lock_key(self, win: "WireWindow", target: int
                  ) -> Tuple[int, int, int]:
        return (win.comm.cid, win.win_seq, target)

    def acquire(self, win: "WireWindow", target: int, origin: int,
                lock_type: int, event: Optional[threading.Event],
                token: int = 0) -> bool:
        """Try to acquire ``target``'s lock for ``origin``. Returns
        True when granted now; otherwise queues the waiter (remote
        origins get their grant reply — echoing ``token`` — from
        :meth:`release`; local ones wait on ``event``)."""
        with self._state_lock:
            st = self._locks.setdefault(self._lock_key(win, target),
                                        _LockState())
            grantable = (
                not st.holders
                or (st.mode == LOCK_SHARED and lock_type == LOCK_SHARED
                    and not st.waiters)  # don't starve a queued EXCL
            )
            if grantable:
                st.mode = lock_type
                st.holders.add(origin)
                return True
            st.waiters.append((origin, lock_type, event, token))
            return False

    def release(self, win: "WireWindow", target: int, origin: int) -> None:
        grants: List[Tuple[int, int]] = []  # (remote origin, token)
        with self._state_lock:
            st = self._locks.get(self._lock_key(win, target))
            if st is None or origin not in st.holders:
                raise MPIError(
                    ErrorCode.ERR_RMA_SYNC,
                    f"unlock of target {target} not held by process "
                    f"{origin}",
                )
            st.holders.discard(origin)
            if not st.holders:
                st.mode = None
                while st.waiters:
                    o, t, ev, tok = st.waiters[0]
                    if st.mode is None:
                        st.mode = t
                    elif not (st.mode == LOCK_SHARED
                              and t == LOCK_SHARED):
                        break
                    st.waiters.popleft()
                    st.holders.add(o)
                    if ev is not None:
                        # LOCAL grant: set the event INSIDE the lock so
                        # a timed-out acquire_blocking can atomically
                        # distinguish "granted" from "still waiting"
                        ev.set()
                    else:
                        grants.append((o, tok))
                    if t == LOCK_EXCLUSIVE:
                        break
        for origin_p, tok in grants:
            self._reply(origin_p, win.comm.cid, win.win_seq,
                        KIND_LOCK, [], tok)

    def abandon(self, win: "WireWindow", target: int, origin: int) -> None:
        """Forget ``origin``'s interest in ``target``'s lock: drop its
        waiter entry, or release a grant it never saw (the origin timed
        out; without this the ghost holder wedges the lock forever)."""
        with self._state_lock:
            st = self._locks.get(self._lock_key(win, target))
            if st is None:
                return
            st.waiters = deque(w for w in st.waiters if w[0] != origin)
            ghost = origin in st.holders
        if ghost:
            self.release(win, target, origin)

    def acquire_blocking(self, win: "WireWindow", target: int,
                         lock_type: int,
                         timeout_s: Optional[float] = None) -> None:
        """Local-origin acquire against the home table (the target is
        owned by THIS process, but remote origins contend through the
        same table). The default wait bound is the snapshot's request
        timeout — local and remote contenders give up on the same
        clock."""
        if timeout_s is None:
            timeout_s = self.tuning().request_timeout_ms / 1000.0
        ev = threading.Event()
        if self.acquire(win, target, self.my_pidx, lock_type, event=ev):
            return
        wd_tok = None
        if _watchdog.enabled:
            wd_tok = _watchdog.arm(
                "win_lock_wait", comm_id=win.comm.cid, peer=target,
                info={"win_seq": win.win_seq, "lock_type": lock_type},
            )
        try:
            granted = ev.wait(timeout=timeout_s)
        finally:
            if wd_tok is not None:
                _watchdog.disarm(wd_tok)
        if granted:
            return
        with self._state_lock:
            if ev.is_set():
                return  # granted in the race window — we hold it
            st = self._locks.get(self._lock_key(win, target))
            if st is not None:
                st.waiters = deque(
                    w for w in st.waiters if w[2] is not ev
                )
        raise MPIError(
            ErrorCode.ERR_RMA_SYNC,
            f"timed out waiting for lock on target {target} "
            f"(held elsewhere for > {timeout_s:.0f}s)",
        )


class WireWindow(Window):
    """A window on a communicator spanning controller processes: this
    process stores one slice per LOCAL member (the hier driver-mode
    convention); RMA to remote targets ships to the target's home at
    synchronization. Creation is collective and synchronizing (like
    MPI_Win_create), so a peer's first batch can never outrun the
    window's existence."""

    def __init__(self, comm, base: jax.Array, name: str = "") -> None:
        rt = comm.runtime
        if getattr(rt, "wire", None) is None:
            raise MPIError(
                ErrorCode.ERR_WIN,
                "spanning-comm window needs the wire router "
                "(runtime_unified_world)",
            )
        from ..runtime.wire import proc_topology

        t = proc_topology(comm)  # the one shared layout derivation
        self.router = t.router
        self.my_pidx = t.my_pidx
        self.owner = t.owner
        self.local_ranks = t.local_ranks
        self.local_n = t.local_n
        if base.shape[0] != self.local_n:
            raise MPIError(
                ErrorCode.ERR_WIN,
                f"spanning-comm window base carries one slice per "
                f"LOCAL member ({self.local_n}), got leading axis "
                f"{base.shape[0]}",
            )
        self._init_state(comm, base, name)  # shared Window field setup
        # collective creation: same per-comm sequence on every process
        self.win_seq = getattr(comm, "_win_seq", 0)
        comm._win_seq = self.win_seq + 1
        self.service = WinService.ensure(rt)
        self.service.register(self)
        comm.barrier()  # MPI_Win_create is collective + synchronizing

    # -- storage indexing --------------------------------------------------
    def _local_pos(self, target: int) -> int:
        return self.local_ranks.index(target)

    def _queue(self, op: _PendingOp):
        """Validate at the CALL SITE what the wire cannot ship: a
        user-defined op bound for a remote home would otherwise raise
        at epoch close, after sibling ops were already dequeued (and a
        piggybacked lock release lost). The check is by op-object
        IDENTITY — a user op that merely shares a predefined name
        would otherwise ship its name and the home would silently
        apply the predefined combiner."""
        if (op.op is not None
                and PREDEFINED_OPS.get(op.op.name) is not op.op
                and self.owner[op.target] != self.my_pidx):
            raise MPIError(
                ErrorCode.ERR_OP,
                f"cross-process RMA requires a predefined op, got "
                f"'{op.op.name}' (MPI_Accumulate's own rule)",
            )
        return super()._queue(op)

    def read(self) -> jax.Array:
        """LOCAL members' slices only (leading axis ``local_n``) — the
        remote slices live in their home processes' HBM."""
        return self._data

    # -- epoch close: split local / per-home batches -----------------------
    def _sync_span(self):
        """``ompi.osc.sync``: a call that closes or flushes an epoch of
        this window, entry to return; ``_sync_stats`` fills in what it
        took off the queue."""
        return _obs.span(_spans.OSC_SYNC, cid=self.comm.cid,
                         win=self.win_seq)

    @staticmethod
    def _sync_stats(sp, ops: List[_PendingOp]) -> None:
        sp.set_metadata(ops=len(ops), bytes=sum(
            _spans.nbytes(x) for x in _payloads(ops)))

    def _apply_pending(self, only_target: Optional[int] = None) -> None:
        from .window import _epoch_count

        with self._sync_span() as sp:
            with self._op_lock:
                if not self._pending:
                    return
                _epoch_count.add()
                todo = self._take_pending(only_target)
                if not todo:
                    return
                self._sync_stats(sp, todo)
                local: List[_PendingOp] = []
                remote: Dict[int, List[_PendingOp]] = {}
                for p in todo:
                    own = self.owner[p.target]
                    if own == self.my_pidx:
                        local.append(p)
                    else:
                        remote.setdefault(own, []).append(p)
                if local:
                    remapped = [
                        _PendingOp(p.kind, self._local_pos(p.target),
                                   data=p.data, op=p.op,
                                   request=p.request, compare=p.compare,
                                   index=p.index, status_rank=p.target,
                                   disp=p.disp, count=p.count)
                        for p in local
                    ]
                    self._run_epoch_program(
                        remapped, _t0=time.perf_counter())
            # ship OUTSIDE _op_lock: holding it while awaiting the
            # peer's ack would deadlock two processes fencing into each
            # other (each service thread needs the lock to apply the
            # other's batch)
            for own in sorted(remote):
                self._ship_batch(own, remote[own], release_target=-1)

    def _ship_batch(self, owner_pidx: int, ops: List[_PendingOp],
                    release_target: int) -> None:
        from . import plan as _osc_plan

        # repeated batches render through the signature's frozen
        # frame template (request records composed once at freeze
        # time); the frames are identical to _pack_batch either way
        _wire_ops.add(len(ops))
        reads = self.service.request(
            self, owner_pidx, KIND_BATCH, release_target, 0,
            payload=_osc_plan.batch_payload(self, ops),
        )
        want = [p for p in ops if p.request is not None]
        if len(want) != len(reads):
            raise MPIError(
                ErrorCode.ERR_INTERN,
                f"window batch reply carried {len(reads)} reads for "
                f"{len(want)} read-requests",
            )
        self._complete_reads(ops, reads)

    def _apply_home_batch(self, todo: List[_PendingOp]
                          ) -> List[np.ndarray]:
        """Service-side: apply a peer's batch into the local slices and
        return the read values in op order, as host arrays. A range
        that leaves this home's slot is refused here too (the origin
        checked it against its own)."""
        for p in todo:
            if self.owner[p.target] != self.my_pidx:
                raise MPIError(
                    ErrorCode.ERR_RANK,
                    f"batch targets rank {p.target}, owned by process "
                    f"{self.owner[p.target]}, not {self.my_pidx}",
                )
            if p.disp is not None:
                self._check_range(p.disp, p.count)
            p.target = self._local_pos(p.target)
        with self._op_lock:
            # incoming batches ride the same access-plan cache: a
            # peer's steady-state epoch replays one fused program here
            return self._close(todo, time.perf_counter())

    # -- passive target over the home lock table ---------------------------
    def lock(self, target: int, lock_type: int = LOCK_EXCLUSIVE) -> None:
        self._require(_EpochKind.NONE, _EpochKind.LOCK)
        if target in self._locked:
            raise MPIError(ErrorCode.ERR_RMA_SYNC,
                           f"target {target} already locked")
        self._acquire(target, lock_type)
        self._locked[target] = lock_type
        self._epoch = _EpochKind.LOCK

    def _acquire(self, target: int, lock_type: int) -> None:
        own = self.owner[target]
        if own == self.my_pidx:
            self.service.acquire_blocking(self, target, lock_type)
            return
        try:
            self.service.request(self, own, KIND_LOCK, target, lock_type)
        except MPIError:
            # timed out awaiting the grant: tell the home to forget us
            # (drops our waiter entry, or releases a grant we never
            # saw) so the lock cannot wedge on a ghost holder
            try:
                self.service.request(
                    self, own, KIND_ABANDON, target, 0,
                    timeout_ms=self.service.tuning().abandon_timeout_ms)
            except MPIError:
                pass  # home unreachable; nothing more to clean
            raise

    def lock_all(self) -> None:
        """Shared lock on every target (remote ones at their homes)."""
        self._require(_EpochKind.NONE)
        for t in range(self.comm.size):
            self._acquire(t, LOCK_SHARED)
            self._locked[t] = LOCK_SHARED
        self._epoch = _EpochKind.LOCK

    def _release_one(self, target: int) -> None:
        own = self.owner[target]
        if own == self.my_pidx:
            self._apply_pending(only_target=target)
            self.service.release(self, target, self.my_pidx)
        else:
            with self._sync_span() as sp:
                with self._op_lock:
                    ops = self._take_pending(only_target=target)
                self._sync_stats(sp, ops)
                # only_target => one owner: the batch (empty or not)
                # carries the release
                self._ship_batch(own, ops, release_target=target)

    def unlock(self, target: int) -> None:
        self._require(_EpochKind.LOCK)
        if target not in self._locked:
            raise MPIError(ErrorCode.ERR_RMA_SYNC,
                           f"target {target} not locked")
        self._release_one(target)
        del self._locked[target]
        if not self._locked:
            self._epoch = _EpochKind.NONE

    def unlock_all(self) -> None:
        self._require(_EpochKind.LOCK)
        for t in sorted(self._locked):
            self._release_one(t)
        self._locked.clear()
        self._epoch = _EpochKind.NONE

    # -- PSCW (generalized active target) across processes -----------------
    # post -> a one-way notice to every accessor process; start blocks
    # for its targets' notices; complete ships+acks the batches THEN
    # notifies each target (service frames from one src are processed
    # in order, so a COMPLETE can never pass its own epoch's data);
    # wait blocks for every accessor process's COMPLETE. This is
    # osc/rdma's PSCW state machine at process granularity (one
    # controller acts as all its local ranks).

    def _procs_of_group(self, group) -> List[int]:
        return sorted({self.router.owner_of(r)
                       for r in group.world_ranks})

    def _key(self) -> Tuple[int, int]:
        return (self.comm.cid, self.win_seq)

    def post(self, group) -> None:
        # PSCW is legal in either order (post-then-start or
        # start-then-post on a process that is both target and
        # origin), so an open PSCW access epoch does not forbid
        # opening the exposure side
        self._require(_EpochKind.NONE, _EpochKind.PSCW)
        if self._group_exposed is not None:
            raise MPIError(ErrorCode.ERR_RMA_SYNC,
                           "post() with an exposure epoch already open")
        self._group_exposed = group
        self._epoch = _EpochKind.PSCW
        for p in self._procs_of_group(group):
            if p == self.my_pidx:
                self.service.pscw_record(self.service._posts,
                                         self._key(), self.my_pidx)
            else:
                self.service.notify(p, self, KIND_POST)

    def start(self, group) -> None:
        self._require(_EpochKind.NONE, _EpochKind.PSCW)
        targets = self._procs_of_group(group)
        self.service.pscw_await(self.service._posts, self._key(),
                                targets, "start")
        self._start_procs = targets
        self._epoch = _EpochKind.PSCW

    def complete(self) -> None:
        self._require(_EpochKind.PSCW)
        self._apply_pending()  # ships + acks every remote batch first
        for p in getattr(self, "_start_procs", []):
            if p == self.my_pidx:
                self.service.pscw_record(self.service._completes,
                                         self._key(), self.my_pidx)
            else:
                self.service.notify(p, self, KIND_COMPLETE)
        self._start_procs = []
        # keep the epoch open while the exposure side is: a fence()
        # slipped between complete() and wait() must still raise
        self._epoch = (_EpochKind.NONE if self._group_exposed is None
                       else _EpochKind.PSCW)

    def wait(self) -> None:
        if self._group_exposed is None:
            raise MPIError(ErrorCode.ERR_RMA_SYNC,
                           "wait() without a matching post()")
        accessors = self._procs_of_group(self._group_exposed)
        self.service.pscw_await(self.service._completes, self._key(),
                                accessors, "wait")
        if self._epoch is _EpochKind.PSCW:
            self._apply_pending()
            self._epoch = _EpochKind.NONE
        self._group_exposed = None

    def test(self) -> bool:
        """MPI_Win_test: True (and the exposure closes, like wait)
        exactly when every accessor process's COMPLETE has arrived —
        a non-consuming peek otherwise."""
        if self._group_exposed is None:
            raise MPIError(ErrorCode.ERR_RMA_SYNC,
                           "test() without a matching post()")
        accessors = self._procs_of_group(self._group_exposed)
        if not self.service.pscw_check(self.service._completes,
                                       self._key(), accessors):
            return False
        self.wait()  # consumes the notices; will not block
        return True

    def free(self) -> None:
        super().free()
        # mirror-image of the creation barrier: peers may still have
        # in-flight release batches bound for this home — unregistering
        # before they land would drop them (no reply -> the peer stalls
        # its full request timeout mid-free)
        self.comm.barrier()
        self.service.unregister(self)

    def shared_query(self, rank: int):
        raise MPIError(
            ErrorCode.ERR_RMA_SHARED,
            "shared windows cannot span controller processes "
            "(device buffers are per-process); use a "
            "split_type_shared communicator",
        )
