"""Async progress engine — the ``opal_progress`` analogue.

The reference hangs its whole comm engine off one loop:
``opal/runtime/opal_progress.c`` registers per-framework callbacks and
every blocked wait spins ``opal_progress()`` until its completion flag
flips, while libnbc (``ompi/mca/coll/libnbc/nbc.c``) advances
nonblocking-collective round schedules from that loop so an
``MPI_Iallreduce`` makes progress off the caller's critical path. This
module is that engine for the TPU runtime:

- a REGISTRY of in-flight scheduled operations (one
  :class:`ScheduledOp` per nonblocking collective on a spanning
  communicator, posted by :mod:`coll.nbc`), executed strictly in
  per-communicator posting order — the MPI same-order-on-every-process
  collective contract — with a per-thread posting ledger so a single
  SPMD program's deferred operations drain in program order;
- an explicit :func:`ProgressEngine.progress` TICK: advances the
  receive side of ``runtime/wire.py`` channels (each op carries a pump
  that reaps completed collective transfers into the router's
  early-transfer queue) and completes in-process async-dispatch
  requests whose device arrays became ready — one tick advances every
  pending request, which is what ``request.wait_all``/``test_all``
  and a bare ``Request.wait()`` call through the shared progress hook;
- an opt-in DEDICATED PROGRESS THREAD (``progress_thread`` cvar,
  default off): when enabled it claims queued schedules and runs them
  off the caller, turning i-collectives into true compute/comm overlap
  (measured by the ``nbc_hidden_seconds`` pvar). The default is the
  polling fallback — operations execute at ``wait()`` in posting order
  on the caller's thread, so tier-1 CPU tests stay deterministic and
  single-threaded.

Execution model: an op is *claimed* (QUEUED -> RUNNING, exactly once)
only when it is the head of its communicator's FIFO — two collectives
on one communicator can never interleave frames on its wire channel,
and posting order is execution order on every process. A blocking
collective on a spanning communicator is expressed as "post + wait"
through this same machinery (``coll/nbc.run_blocking``), so there is
ONE round-advancing code path. Nested collectives issued from inside a
running op (two-phase IO's closing barrier, the hier shadow comm)
bypass the queue and run inline on the executing thread — sequential
on one thread, so frames cannot interleave.

Known limitation (documented, matching the driver-mode reality of one
controller thread per process): in polling mode, deferred i-collectives
posted from MULTIPLE user threads and waited cross-thread in divergent
orders across processes can stall until some thread waits the matching
op; the progress thread mode has no such coupling. Single-threaded SPMD
programs — the repo's driver convention — drain deterministically. A
test()-only completion loop is live in polling mode too: the first
test on a still-queued schedule kicks an on-demand background drainer
(:meth:`ProgressEngine.advance_toward`), because running the whole
schedule inline inside a nonblocking test could park on peers that
have not arrived yet.

Cost discipline: the obs emit sites here are gated on ``_obs.enabled``
(the PR-1 one-attribute-check contract, enforced by
``tests/test_obs_gating.py``), and pvars are module-level zero-cost
counters: ``progress_ticks`` (engine ticks), ``nbc_schedules_inflight``
(posted-but-incomplete schedules), ``nbc_hidden_seconds`` (schedule
run time that overlapped caller compute instead of blocking it).
"""

from __future__ import annotations

import itertools
import threading
import time as _time
import weakref
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from .. import obs as _obs
from ..mca import pvar
from ..mca import var as mca_var
from ..obs import watchdog as _watchdog
from ..request import request as _request
from ..utils import output

_log = output.stream("progress")

_ticks = pvar.counter(
    "progress_ticks",
    "explicit/threaded progress-engine ticks (opal_progress analogue)",
)
_hidden = pvar.timer(
    "nbc_hidden_seconds",
    "nonblocking-schedule run time that overlapped caller compute "
    "(ran before the first wait) instead of blocking the critical path",
)


def register_vars() -> None:
    mca_var.register(
        "progress_thread", "bool", False,
        "Run the dedicated async-progress thread: queued nonblocking "
        "collective schedules execute off the caller (true "
        "compute/comm overlap). Off (default) = polling fallback: "
        "schedules advance when the caller ticks progress() or waits, "
        "in posting order — deterministic for single-threaded tests",
    )
    mca_var.register(
        "progress_poll_us", "int", 500,
        "Idle poll period of the progress thread in microseconds "
        "(bounds the latency between a peer's frame landing and the "
        "engine reaping it when no schedule is runnable)",
    )


register_vars()  # idempotent; cvars must exist before the first post


#: ScheduledOp lifecycle states
QUEUED = "queued"
RUNNING = "running"
DONE = "done"


class ScheduledOp:
    """One in-flight scheduled operation (a libnbc handle analogue).

    ``key`` serializes execution: ops sharing a key (one communicator)
    run strictly in posting order, never concurrently. ``fn`` is the
    whole round schedule — its wire exchanges ride the instrumented
    hier/wire touchpoints, so flow ids, pvars, and watchdog arming are
    identical to the blocking path's. ``pump`` (optional) is the
    nonblocking receive-side tick for the op's wire channel.
    """

    __slots__ = ("seq", "key", "name", "cid", "fn", "args", "kw",
                 "pump", "state", "claimed_by", "poster", "polls",
                 "result", "error", "done", "callbacks", "t_post",
                 "t_start", "t_done", "t_first_wait")

    def __init__(self, key: Any, name: str, fn: Callable, *,
                 cid: int = -1, args: Tuple = (), kw: Optional[Dict] = None,
                 pump: Optional[Callable[[], int]] = None) -> None:
        self.seq = 0  # assigned by post()
        self.key = key
        self.name = name
        self.cid = cid
        self.fn = fn
        self.args = args
        self.kw = kw or {}
        self.pump = pump
        self.state = QUEUED
        self.claimed_by: Optional[int] = None
        self.poster: Optional[int] = None  # assigned by post()
        self.polls = 0  # consecutive test()-style advances (kick gate)
        self.result: Any = None
        self.error: Optional[BaseException] = None
        self.done = threading.Event()
        #: completion callbacks, run BEFORE done is set (a waiter must
        #: observe the bound request already completed-with-value)
        self.callbacks: List[Callable] = []
        self.t_post = _time.perf_counter()
        self.t_start = 0.0
        self.t_done = 0.0
        self.t_first_wait: Optional[float] = None

    def hidden_seconds(self) -> float:
        """The part of this schedule's run the poster spent elsewhere
        (THE overlap accounting — one definition, used by the engine's
        ``nbc_hidden_seconds`` fold and per-pass consumers like
        ``parallel/tree``). Polling mode waits before the run starts
        -> 0; a run finished before the first wait hides its whole
        duration. Meaningful once the op is DONE; 0 before."""
        if not self.t_done:
            return 0.0
        tw = self.t_first_wait
        if tw is not None and tw <= self.t_start:
            return 0.0
        end = self.t_done if tw is None else min(self.t_done, tw)
        return max(0.0, end - self.t_start)

    def describe(self) -> Dict[str, Any]:
        """Postmortem line: THE answer to "which NBC schedule is
        stuck" in a flight-recorder dump."""
        now = _time.perf_counter()
        return {
            "name": self.name, "cid": self.cid, "seq": self.seq,
            "state": self.state, "claimed_by": self.claimed_by,
            "posted_s_ago": round(now - self.t_post, 3),
            "running_s": (round(now - self.t_start, 3)
                          if self.state == RUNNING else 0.0),
            "waited_on": self.t_first_wait is not None,
        }


class ProgressEngine:
    """Process-global progress engine (one per controller process)."""

    def __init__(self) -> None:
        self._lock = threading.RLock()
        self._cond = threading.Condition(self._lock)
        self._seq = itertools.count(1)
        #: key -> FIFO of not-yet-done ops (head = next to run)
        self._queues: Dict[Any, deque] = {}
        #: poster thread id -> ops in posting order (the drain ledger)
        self._posted: Dict[int, List[ScheduledOp]] = {}
        #: seq -> op, every posted-but-incomplete op (the registry the
        #: nbc_schedules_inflight pvar and the watchdog dump read)
        self._inflight: Dict[int, ScheduledOp] = {}
        #: token -> weakref of in-process async-dispatch Requests the
        #: tick completes when their device arrays turn ready (a dict
        #: mutated in place under the lock: completion pops its own
        #: token, so ticks stay O(outstanding) and a tick's sweep can
        #: never resurrect an entry a concurrent completion removed)
        self._poll: Dict[int, weakref.ref] = {}
        #: keys with an active test()-kicked background drainer
        self._kicked: set = set()
        self._tls = threading.local()
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    # -- registry ----------------------------------------------------------
    def inflight_count(self) -> int:
        return len(self._inflight)

    def snapshot(self) -> List[Dict[str, Any]]:
        with self._lock:
            ops = sorted(self._inflight.values(), key=lambda o: o.seq)
        return [op.describe() for op in ops]

    # -- posting -----------------------------------------------------------
    def post(self, op: ScheduledOp) -> ScheduledOp:
        """Enqueue one scheduled op (never blocks, never executes)."""
        tid = threading.get_ident()
        with self._lock:
            op.seq = next(self._seq)
            op.poster = tid
            self._queues.setdefault(op.key, deque()).append(op)
            self._posted.setdefault(tid, []).append(op)
            self._inflight[op.seq] = op
            self._cond.notify_all()
        self.ensure_thread()
        return op

    # -- execution ---------------------------------------------------------
    def executing(self) -> Optional[ScheduledOp]:
        """The op the CURRENT thread is executing, if any (nested
        collective detection)."""
        stack = getattr(self._tls, "stack", None)
        return stack[-1] if stack else None

    def _execute(self, op: ScheduledOp) -> None:
        """Run one claimed op to completion on this thread."""
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        stack.append(op)
        op.t_start = _time.perf_counter()
        rec = _obs.enabled  # capture once: flag may flip mid-run
        try:
            op.result = op.fn(*op.args, **op.kw)
        except BaseException as e:
            op.error = e
        finally:
            stack.pop()
            t_done = op.t_done = _time.perf_counter()
            with self._lock:
                op.state = DONE
                q = self._queues.get(op.key)
                if q:
                    try:
                        q.remove(op)
                    except ValueError:
                        pass
                    if not q:
                        self._queues.pop(op.key, None)
                self._inflight.pop(op.seq, None)
                # drop from the poster's ledger too: an op completed
                # by the progress thread must not pile up in a thread
                # list its poster may never scan again
                ledger = self._posted.get(op.poster)
                if ledger is not None:
                    try:
                        ledger.remove(op)
                    except ValueError:
                        pass
                    if not ledger:
                        self._posted.pop(op.poster, None)
                self._cond.notify_all()
            # hidden time: the op's own accounting (the ONE definition
            # of overlap — see ScheduledOp.hidden_seconds)
            hidden = op.hidden_seconds()
            if hidden > 0:
                _hidden.add(hidden)
            if rec and _obs.enabled:
                _obs.record("nbc_" + op.name, "nbc", op.t_start,
                            t_done - op.t_start, comm_id=op.cid)
            # callbacks BEFORE the event: a thread woken by done must
            # find the bound request already completed with its value
            for cb in list(op.callbacks):
                try:
                    cb(op)
                except Exception as e:  # a callback must not kill the engine
                    _log.verbose(1, f"nbc completion callback failed: {e}")
            op.done.set()

    def _claim_locked(self, op: ScheduledOp) -> bool:
        """Claim ``op`` if it is the QUEUED head of its key's FIFO.
        Caller holds the lock."""
        q = self._queues.get(op.key)
        if not q or q[0] is not op or op.state != QUEUED:
            return False
        op.state = RUNNING
        op.claimed_by = threading.get_ident()
        return True

    def _next_runnable(self, op: ScheduledOp,
                       tid: int) -> Optional[ScheduledOp]:
        """Claim the op this thread should run next on the way to
        ``op``: the head of the queue owning the EARLIEST not-done op
        this thread posted at or before ``op`` (program posting order —
        identical across SPMD processes), else ``op``'s own queue head.
        Returns a CLAIMED op, or None (blocker runs elsewhere)."""
        with self._lock:
            posted = self._posted.get(tid)
            cand = None
            if posted:
                posted[:] = [o for o in posted if o.state != DONE]
                # earliest op this thread posted at or before op is the
                # drain target — but skip ops RUNNING on THIS thread:
                # they sit beneath us on the stack (a nested wait from
                # inside a schedule) and cannot progress until we
                # return, so waiting on them would self-deadlock
                for o in posted:
                    if o.seq > op.seq:
                        break
                    if o.state == RUNNING and o.claimed_by == tid:
                        continue
                    cand = o
                    break
            if cand is None:
                cand = op if op.state != DONE else None
            if cand is None:
                return None
            q = self._queues.get(cand.key)
            head = q[0] if q else None
            if head is not None and self._claim_locked(head):
                return head
            return None

    def wait(self, op: ScheduledOp) -> Any:
        """Complete ``op``: drain earlier same-thread/same-comm ops in
        posting order (polling mode), or park on the completion event
        while another thread — the progress thread, or another waiter —
        runs it. Re-raises the schedule's error; returns its result."""
        if op.t_first_wait is None:
            op.t_first_wait = _time.perf_counter()
        tid = threading.get_ident()
        while not op.done.is_set():
            target = self._next_runnable(op, tid)
            if target is not None:
                self._execute(target)
                continue
            with self._lock:
                evicted = (op.state != DONE
                           and op.seq not in self._inflight)
            if evicted:
                from ..utils.errors import ErrorCode, MPIError

                raise MPIError(
                    ErrorCode.ERR_REQUEST,
                    f"progress engine shut down with schedule "
                    f"'{op.name}' still pending (finalize with "
                    "outstanding nonblocking collectives?)",
                )
            if op.done.wait(0.02):
                break
            self.progress()
        if op.error is not None:
            raise op.error
        return op.result

    def advance_toward(self, op: ScheduledOp) -> int:
        """Nonblocking progress toward ``op`` — the MPI_Test progress
        rule. test() must stay nonblocking (running the whole schedule
        inline could park on peers that have not arrived), yet a
        test-only completion loop must still finish in polling mode
        (the deleted per-comm worker guaranteed background progress).
        So the SECOND consecutive test() on a still-queued schedule
        KICKS an on-demand background drainer for the op's queue —
        execution off the caller, exactly while the caller is
        poll-driven — and every test() also runs the ordinary
        nonblocking (shallow) tick. The second, not the first:
        Request.wait() performs exactly one internal test() before
        blocking, so wait-only users never see a thread (and the
        polling-mode hidden-seconds witness stays exactly 0); only a
        real poll LOOP crosses the threshold."""
        if op.done.is_set():
            return 0
        op.polls += 1
        if op.polls >= 2 and not self.thread_mode() \
                and self.executing() is None:
            self._kick(op)
        return self.progress(deep=False)  # test() must never park

    def _kick(self, op: ScheduledOp) -> None:
        """Ensure one background drainer runs ``op``'s queue until the
        op completes (one drainer per key at a time)."""
        with self._lock:
            if op.state == DONE or op.key in self._kicked:
                return
            self._kicked.add(op.key)
        threading.Thread(target=self._kick_loop, args=(op,),
                         daemon=True,
                         name=f"nbc-kick-{op.name}").start()

    def _kick_loop(self, op: ScheduledOp) -> None:
        try:
            while not op.done.is_set():
                target = None
                with self._lock:
                    if op.state != DONE and op.seq not in self._inflight:
                        return  # evicted (engine shutdown): don't spin
                    q = self._queues.get(op.key)
                    head = q[0] if q else None
                    if head is not None and self._claim_locked(head):
                        target = head
                if target is not None:
                    self._execute(target)
                    continue
                op.done.wait(0.05)
        finally:
            with self._lock:
                self._kicked.discard(op.key)

    def fail_queued(self, key: Any, exc_factory: Callable[[], BaseException]
                    ) -> int:
        """Complete every still-QUEUED op on ``key`` in error WITHOUT
        running it — the ULFM revoke interrupt: schedules posted on a
        revoked communicator must complete in error promptly, and
        running them would only park this process on a poisoned wire
        channel. A RUNNING op is left alone (it owns wire state; its
        own bounded waits surface the revocation within a slice).
        Returns how many ops were failed."""
        failed: List[ScheduledOp] = []
        with self._lock:
            q = self._queues.get(key)
            if not q:
                return 0
            for op in list(q):
                if op.state != QUEUED:
                    continue
                op.state = DONE
                op.error = exc_factory()
                q.remove(op)
                self._inflight.pop(op.seq, None)
                ledger = self._posted.get(op.poster)
                if ledger is not None:
                    try:
                        ledger.remove(op)
                    except ValueError:
                        pass
                    if not ledger:
                        self._posted.pop(op.poster, None)
                failed.append(op)
            if not q:
                self._queues.pop(key, None)
            self._cond.notify_all()
        for op in failed:
            # same completion contract as _execute: callbacks BEFORE
            # the event, so a woken waiter observes the bound request
            # already failed
            for cb in list(op.callbacks):
                try:
                    cb(op)
                except Exception as e:
                    _log.verbose(1, f"nbc completion callback "
                                    f"failed: {e}")
            op.done.set()
        return len(failed)

    def drain_key(self, key: Any) -> None:
        """Complete every posted op on one key, in order (comm free /
        shutdown path: peers participate in the queued collectives, so
        dropping them would strand the fleet). This is a synchronous
        wait: the ops are stamped as waited-on so their runtime never
        counts as hidden (the caller is blocked in free() for exactly
        that duration)."""
        while True:
            with self._lock:
                q = self._queues.get(key)
                head = q[0] if q else None
                if head is None:
                    return
                if head.t_first_wait is None:
                    head.t_first_wait = _time.perf_counter()
                claimed = self._claim_locked(head)
            if claimed:
                self._execute(head)
            else:
                head.done.wait(0.05)

    # -- the tick ----------------------------------------------------------
    def progress(self, deep: bool = True) -> int:
        """One engine tick: complete in-process async requests whose
        arrays became ready and — when ``deep`` — advance the receive
        side of every in-flight op's wire channel (early-transfer
        reap; may ride out one in-flight transfer's tail, which is the
        opal_progress discipline: completing in-flight fragments IS
        the progress). The IMPLICIT hook behind request test()/
        test_all() runs shallow (``deep=False``) so a nonblocking test
        can never park on a mid-stream transfer; deep ticks come from
        explicit calls, the progress thread, and blocked waits, where
        riding a transfer tail is the point. Never executes a schedule
        — execution belongs to wait()/kick drainers (polling) or the
        progress thread — and is reentrancy-safe (a tick from inside a
        tick is a no-op). Returns how many items progressed."""
        if getattr(self._tls, "ticking", False):
            return 0
        self._tls.ticking = True
        rec = _obs.enabled
        t0 = _time.perf_counter() if rec else 0.0
        try:
            _ticks.add()
            n = 0
            if deep:
                with self._lock:
                    pumps = {}
                    for o in self._inflight.values():
                        if o.pump is not None and o.key not in pumps:
                            pumps[o.key] = o.pump
                for fn in pumps.values():
                    try:
                        n += int(fn() or 0)
                    except Exception as e:  # dead channel: not fatal
                        _log.verbose(2, f"progress pump failed: {e}")
            n += self._poll_ready()
            if n and rec and _obs.enabled:
                _obs.record("progress_tick", "nbc", t0,
                            _time.perf_counter() - t0)
            return n
        finally:
            self._tls.ticking = False

    def add_poll(self, req) -> None:
        """Track an in-process async-dispatch Request: ticks (and the
        progress thread) complete it the moment its arrays are ready,
        so completion no longer requires the caller to test(). The
        entry is pruned the moment the request completes through ANY
        path (a bare wait() included) — the registry must not grow
        with collectives that never see a tick."""
        with self._lock:
            token = next(self._seq)
            self._poll[token] = weakref.ref(req)
            self._cond.notify_all()
        req.on_complete(lambda _r: self._discard_poll(token))
        self.ensure_thread()

    def _discard_poll(self, token: int) -> None:
        with self._lock:
            self._poll.pop(token, None)

    def _poll_ready(self) -> int:
        with self._lock:
            items = list(self._poll.items())
        if not items:
            return 0
        completed = 0
        dead = []
        for token, ref in items:
            req = ref()
            done = True  # a collected request needs no more polling
            if req is not None:
                try:
                    done = req.poll()
                except Exception:
                    pass  # surfaced at the request's own wait/test
            if done:
                completed += req is not None
                dead.append(token)
        if dead:
            with self._lock:
                for token in dead:
                    self._poll.pop(token, None)
        return completed

    # -- the opt-in thread -------------------------------------------------
    @staticmethod
    def thread_mode() -> bool:
        return bool(mca_var.get("progress_thread", False))

    def ensure_thread(self) -> None:
        """Start the dedicated progress thread iff the cvar asks for
        one (lazy: posting with the cvar flipped mid-run works; the
        loop retires itself when the cvar flips back off)."""
        if not self.thread_mode():
            return
        with self._lock:
            if self._thread is not None and self._thread.is_alive() \
                    and not self._stop.is_set():
                return
            self._stop = threading.Event()
            self._thread = threading.Thread(
                target=self._thread_loop, args=(self._stop,),
                daemon=True, name="nbc-progress",
            )
            self._thread.start()

    def _claim_next(self) -> Optional[ScheduledOp]:
        with self._lock:
            for op in sorted(self._inflight.values(),
                             key=lambda o: o.seq):
                if self._claim_locked(op):
                    return op
        return None

    def _thread_loop(self, stop: threading.Event) -> None:
        while not stop.is_set():
            if not self.thread_mode():
                break  # cvar flipped off: polling mode resumes
            op = self._claim_next()
            if op is not None:
                self._execute(op)
                continue
            self.progress()
            period = max(0.0002, min(
                0.05, int(mca_var.get("progress_poll_us", 500)) / 1e6))
            with self._cond:
                if not self._inflight and not self._poll:
                    self._cond.wait(period * 20)
                else:
                    self._cond.wait(period)
        with self._lock:
            if self._thread is threading.current_thread():
                self._thread = None

    def shutdown(self, timeout: float = 5.0, drain: bool = True) -> None:
        """Finalize-time teardown: stop the thread, DRAIN queued
        schedules (peers participate in them — a rank that posted an
        i-collective, never waited it, and finalized would otherwise
        strand every peer parked in that collective's reap), give
        RUNNING schedules (which own wire state) a bounded wait, then
        clear. The engine stays usable — a later post() re-arms it."""
        with self._lock:
            self._stop.set()
            self._cond.notify_all()
            t = self._thread
        if t is not None:
            t.join(timeout=timeout)
        if drain:
            while True:
                with self._lock:
                    keys = [k for k, q in self._queues.items() if q]
                if not keys:
                    break
                for key in keys:
                    self.drain_key(key)  # errors land on the ops
        with self._lock:
            running = [o for o in self._inflight.values()
                       if o.state == RUNNING]
        deadline = _time.monotonic() + timeout
        for op in running:
            op.done.wait(max(0.0, deadline - _time.monotonic()))
        with self._lock:
            self._queues.clear()
            self._posted.clear()
            self._inflight.clear()
            self._poll.clear()
            self._thread = None


#: THE engine (opal_progress is process-global; so is this)
ENGINE = ProgressEngine()


def engine() -> ProgressEngine:
    return ENGINE


def executing_seq() -> int:
    """Posting seq of the schedule the CURRENT thread is executing (0:
    none) — what the exchange spans carry so a trace joins them to the
    ``ompi.nbc.wait`` of the same ``(cid, seq)``."""
    op = ENGINE.executing()
    return op.seq if op is not None else 0


pvar.PVARS.register(
    "nbc_schedules_inflight", pvar.PvarClass.LEVEL,
    "nonblocking collective schedules posted but not yet complete",
    getter=lambda: ENGINE.inflight_count(),
)

# one shared tick advances EVERY pending request: wait_all/test_all and
# a bare Request.wait() drive the engine through this hook instead of
# spinning per-request or sleeping. SHALLOW tick: the hook runs inside
# nonblocking test paths, which must never ride a mid-stream wire
# transfer's tail — deep (wire-pumping) ticks come from the progress
# thread and blocked waits.
_request.register_progress_hook(lambda: ENGINE.progress(deep=False))

# flight-recorder contributor: the postmortem names every in-flight
# NBC schedule (op, comm, state, who claimed it, how long) — paired
# with coll/hier's round-state table this answers "which nonblocking
# collective is stuck and on whom"
_watchdog.add_contributor("nbc_inflight", lambda: ENGINE.snapshot())
