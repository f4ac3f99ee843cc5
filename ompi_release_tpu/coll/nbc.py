"""Nonblocking & persistent collectives — the ``coll/libnbc`` analogue.

The reference implements ``MPI_Iallreduce``-class operations as round
schedules advanced by the progress engine (``ompi/mca/coll/libnbc/
nbc.c``: build the schedule, return a handle, progress rounds off the
caller) and MPI-4 persistent collectives (``MPI_Allreduce_init``) as a
schedule built ONCE and fired by ``MPI_Start`` many times. This module
is that layer for the TPU runtime, split by communicator kind:

in-process comms
    XLA async dispatch IS the progress engine: the compiled program is
    the round schedule, dispatch returns future arrays, and
    :func:`async_request` wraps them in a Request whose readiness is
    the arrays' readiness. The request is registered with the
    progress engine's poll list so a tick (or the progress thread)
    completes it off the caller.

spanning comms (``tpurun`` multi-process worlds)
    The hier collective's wire exchanges block, so the whole round
    schedule becomes a :class:`~runtime.progress.ScheduledOp` posted to
    the :mod:`runtime.progress` engine. Dispatch never touches the
    wire (and performs no ``block_until_ready``); execution happens in
    posting order — at ``wait()`` on the caller (polling mode) or off
    the caller on the progress thread (``progress_thread`` cvar). Each
    op carries a wire pump so engine ticks reap the comm's completed
    transfers into the router's early-transfer queue while the
    schedule is still queued or mid-round.

Blocking spanning collectives are expressed through the SAME machinery
— :func:`run_blocking` posts the schedule and waits it — so there is
exactly one round-advancing code path (the old per-comm worker
executor is gone). Persistent collectives build their plan once at
``*_init`` (the dispatch closure: resolved c_coll entry, op object,
bound buffers, memoized plan signature) and ``Request.start()``
re-fires it against the CURRENT buffer contents, the MPI persistent
buffer-reuse contract — through :mod:`coll.plan`'s frozen schedule
plans: in-process starts launch ONE cached compiled XLA program,
spanning starts replay precomposed wire rounds (peer lists, frame
headers, fragment offsets resolved at plan time). Blocking and
i-family collectives ride the same per-(cid, signature) plan cache.

Bitwise parity is structural: the nonblocking path runs the identical
collective function the blocking path runs, only later and possibly on
another thread — same schedules, same exact-order folds, same
non-commutative discipline.
"""

from __future__ import annotations

import time as _time
from typing import Any, Callable, Dict, Optional, Tuple

from .. import obs as _obs
from ..mca import pvar
from ..obs import sentinel as _sentinel
from ..obs import spans as _spans
from ..request.request import Request
from ..runtime import progress as _progress
from ..utils.errors import ErrorCode, MPIError
from . import plan as _plan

_ops_posted = pvar.counter(
    "nbc_ops_posted",
    "nonblocking/blocking collective schedules posted to the progress "
    "engine (spanning comms)",
)
_persistent_starts = pvar.counter(
    "nbc_persistent_starts",
    "persistent-collective start() fires (plans built once at *_init)",
)
# the SAME registered timer coll/driver feeds (registration is
# idempotent): here it covers the spanning POSTING prelude — sentinel
# note, op construction, engine enqueue — the Python-orchestration
# segment before the schedule/wire takes over
_orch = pvar.timer(
    "coll_orchestration_seconds",
    "Python orchestration seconds on the collective dispatch path "
    "(decision, planning, validation, posting — before the compiled "
    "program or wire transport takes over)",
)


def _comm_key(comm) -> Tuple[str, int]:
    return ("comm", comm.cid)


def _make_pump(comm) -> Callable[[], int]:
    """The op's receive-side wire tick: reap completed collective
    transfers on this comm's payload channel into the router's
    early-transfer queue (a no-op once the comm is freed)."""

    def pump() -> int:
        router = getattr(comm.runtime, "wire", None)
        if router is None or getattr(comm, "_freed", False):
            return 0
        return router.coll_pump(comm)

    return pump


def _make_op(comm, name: str, fn: Callable, args: Tuple,
             kw: Optional[Dict]) -> _progress.ScheduledOp:
    return _progress.ScheduledOp(
        _comm_key(comm), name, fn, cid=comm.cid, args=args,
        kw=kw or {}, pump=_make_pump(comm),
    )


def _post(comm, op: _progress.ScheduledOp) -> _progress.ScheduledOp:
    """Hand one fully-wired op to the engine. Completion callbacks
    MUST be attached before this call: with the progress thread on,
    the schedule can run to completion the instant it is posted."""
    _ops_posted.add()
    rec = _obs.enabled  # capture once: flag may flip mid-post
    t0 = _time.perf_counter() if rec else 0.0
    _progress.engine().post(op)
    if rec and _obs.enabled:
        _obs.record("nbc_post", "nbc", t0, _time.perf_counter() - t0,
                    comm_id=comm.cid)
    return op


def _op_request(op: _progress.ScheduledOp) -> Request:
    """Bind one NOT-YET-POSTED schedule to a Request (the callback is
    attached here, before the engine can run the op): test() advances
    the engine one bounded step toward this op (and surfaces a
    schedule error), wait() drives the engine's posting-order drain,
    completion carries the schedule's result."""
    eng = _progress.engine()

    def prog(_r, _op=op, _eng=eng) -> None:
        _eng.advance_toward(_op)
        if _op.done.is_set() and _op.error is not None:
            raise _op.error

    def block(_op=op, _eng=eng) -> None:
        with _obs.span(_spans.NBC_WAIT, cid=_op.cid, seq=_op.seq):
            _eng.wait(_op)  # raises the schedule's error

    req = Request(progress_fn=prog, block_fn=block)
    # expose the schedule handle: per-pass consumers (parallel/tree's
    # hidden-time accounting) read its t_start/t_done/t_first_wait
    req._sched_op = op

    def finish(o, _req=req) -> None:
        if o.error is None:
            _req.complete(value=o.result)

    op.callbacks.append(finish)
    return req


def _inline_tpl(state, sig):
    """Sentinel level 2's precomposed ctl-frame payload, cached on
    the frozen-plan state (one JSON encode per plan signature, not
    per fire) — None when the call is unplannable or unsigned, where
    wrap_inline falls back to the per-fire encoding."""
    if state is None or sig is None:
        return None
    key = (sig.canon, sig.site)
    tpl = state.sentinel_tpl
    if tpl is None or tpl[0] != key:
        state.sentinel_tpl = tpl = (
            key, _sentinel.InlineFrameTemplate(sig.canon, sig.site))
    return tpl[1]


def _resolve(comm, name: str) -> Callable:
    fn = comm.c_coll.get(name)
    if fn is None:
        raise MPIError(
            ErrorCode.ERR_INTERN,
            f"no {name} implementation installed on {comm.name}",
        )
    return fn


# ---------------------------------------------------------------------------
# in-process: XLA async dispatch wrapped as a Request
# ---------------------------------------------------------------------------

def async_request(value) -> Request:
    """Wrap already-dispatched (future) arrays as a Request and hand it
    to the engine's poll list, so completion happens at the next tick —
    caller's or the progress thread's — instead of only at test()."""
    import jax

    arrs = [a for a in jax.tree.leaves(value) if hasattr(a, "is_ready")]
    req = Request(
        ready_fn=lambda: all(a.is_ready() for a in arrs),
        block_fn=lambda: jax.block_until_ready(value),
    )
    req.value = value
    _progress.engine().add_poll(req)
    return req


# ---------------------------------------------------------------------------
# public entry points (Communicator delegates here)
# ---------------------------------------------------------------------------

def _nested_inline(comm, fn, args, kw) -> Optional[Request]:
    """An i-collective issued from INSIDE a running schedule on the
    same comm cannot queue: the outer op owns the queue head until it
    completes, so the nested op could never be claimed and waiting it
    would hang. MPI permits a nonblocking op to complete at
    initiation — run it inline (sequential on this thread, so frames
    cannot interleave; the old per-comm-worker path did the same) and
    return an already-complete Request. None when not nested."""
    cur = _progress.engine().executing()
    if cur is None or cur.key != _comm_key(comm):
        return None
    req = Request()
    req.complete(value=fn(*args, **(kw or {})))
    return req


def icoll(comm, name: str, args: Tuple, kw: Optional[Dict] = None
          ) -> Request:
    """Nonblocking collective: dispatch returns before completion for
    every family (no ``block_until_ready`` on the dispatch path)."""
    t0 = _time.perf_counter()
    comm._check_usable()
    fn = _resolve(comm, name)
    # contract sentinel: the call signature is derived at POSTING time
    # (the user frame is on the stack, the per-comm posting seq is
    # this slot); inline verification, if any, runs at execution
    sig = _sentinel.note(comm, name, args, kw) if _sentinel.enabled \
        else None
    if not comm.spans_processes:
        # steady state: a previously-seen signature fires its frozen
        # compiled program through coll/plan instead of re-running the
        # interpreted decision path
        return async_request(
            _plan.dispatch(comm, name, fn, tuple(args), kw))
    nested = _nested_inline(comm, fn, (comm,) + tuple(args), kw)
    if nested is not None:
        return nested
    state = _plan.spanning_state_for(comm, name, args, kw)
    if sig is not None:
        fn = _sentinel.wrap_inline(comm, sig, fn,
                                   _inline_tpl(state, sig))
    run = _plan.spanning_wrap(state, fn)
    op = _make_op(comm, name, run, (comm,) + tuple(args), kw)
    req = _op_request(op)  # callback wired BEFORE the engine sees it
    _post(comm, op)
    _orch.add(_time.perf_counter() - t0)
    return req


def run_blocking(comm, name: str, fn: Callable, args: Tuple,
                 kw: Optional[Dict] = None) -> Any:
    """A blocking spanning collective = fire the NBC schedule + wait —
    the one round-advancing code path. A collective nested inside a
    running schedule on the SAME comm (two-phase IO's closing barrier)
    runs inline on the executing thread — sequential, so frames on the
    comm's channel cannot interleave and the outer op still owns the
    queue head. A nested call onto a DIFFERENT comm posts through that
    comm's queue like any other (the engine's claim rule is the one
    arbiter of who runs on a channel — an inline run could race a
    progress-thread/kick claim of another schedule on the same cid);
    the drain ledger skips ops running beneath this thread, so the
    nested wait cannot self-deadlock on its own outer op."""
    t0 = _time.perf_counter()
    eng = _progress.engine()
    cur = eng.executing()
    if cur is not None and cur.key == _comm_key(comm):
        return fn(*args, **(kw or {}))
    # the sentinel notes against the USER-FACING args (args[0] is the
    # comm for c_coll entries; note() strips it), and the plan state
    # keys on the same signature the i-family/persistent paths use
    user_args = args[1:] if args and args[0] is comm else args
    state = _plan.spanning_state_for(comm, name, user_args, kw)
    if _sentinel.enabled:
        sig = _sentinel.note(comm, name, user_args, kw)
        if sig is not None:
            fn = _sentinel.wrap_inline(comm, sig, fn,
                                       _inline_tpl(state, sig))
    run = _plan.spanning_wrap(state, fn)
    op = _make_op(comm, name, run, args, kw)
    _post(comm, op)
    _orch.add(_time.perf_counter() - t0)
    with _obs.span(_spans.NBC_WAIT, cid=comm.cid, seq=op.seq):
        return eng.wait(op)


def submit(comm, name: str, fn: Callable, args: Tuple,
           kw: Optional[Dict] = None) -> Request:
    """Nonblocking run of an arbitrary collective-ordered callable on
    the comm's schedule queue (the nonblocking collective-IO path):
    keeps posting order with every other collective on the comm."""
    comm._check_usable()
    nested = _nested_inline(comm, fn, args, kw)
    if nested is not None:
        return nested
    if _sentinel.enabled:
        sig = _sentinel.note(comm, name, args, kw)
        if sig is not None:
            fn = _sentinel.wrap_inline(comm, sig, fn)
    op = _make_op(comm, name, fn, args, kw)
    req = _op_request(op)
    _post(comm, op)
    return req


def drain_comm(comm) -> None:
    """Complete every outstanding schedule on ``comm`` in posting
    order (comm free path: peers participate in the queued
    collectives, so they must run, not vanish)."""
    _progress.engine().drain_key(_comm_key(comm))


# ---------------------------------------------------------------------------
# persistent collectives (MPI_Allreduce_init / MPI_Start)
# ---------------------------------------------------------------------------

def persistent(comm, name: str, args: Tuple, kw: Optional[Dict] = None
               ) -> Request:
    """Build the plan ONCE, fire it per start(): the c_coll entry and
    argument binding resolve now; each ``Request.start()`` re-fires the
    plan against the bound buffers' CURRENT contents (MPI persistent
    buffer reuse) without blocking — a fresh schedule posts to the
    engine (spanning) or a fresh async dispatch launches (in-process,
    where the compiled program cached at first fire IS the plan)."""
    comm._check_usable()
    kw = kw or {}
    if name == "barrier" and not comm.spans_processes:
        ifn = comm.c_coll.get("ibarrier")

        def fire() -> Request:
            if ifn is not None:
                if _sentinel.enabled:
                    _sentinel.note(comm, "barrier")
                return async_request(ifn(comm))
            # provider thread fallback runs comm.barrier(), whose
            # _coll wrapper notes the signature itself — noting here
            # too would double-count the one collective
            return comm.ibarrier()
    else:
        fn = _resolve(comm, name)
        if comm.spans_processes:
            # the frozen wire plan is built ONCE per (cid, signature):
            # the first start() records the round structure, every
            # later start() replays precomposed frames (coll/plan)
            state = _plan.spanning_state_for(comm, name, args, kw)

            def fire() -> Request:
                t0 = _time.perf_counter()
                # each start() is one collective round: it takes its
                # own posting-seq slot in the comm's signature chain
                run = fn
                if _sentinel.enabled:
                    sig = _sentinel.note(comm, name, args, kw)
                    if sig is not None:
                        run = _sentinel.wrap_inline(
                            comm, sig, fn, _inline_tpl(state, sig))
                run = _plan.spanning_wrap(state, run)
                op = _make_op(comm, name, run, (comm,) + tuple(args),
                              kw)
                inner = _op_request(op)
                _post(comm, op)
                _orch.add(_time.perf_counter() - t0)
                return inner
        else:
            sig_box: list = []  # signature computed once, not per start

            def fire() -> Request:
                if _sentinel.enabled:
                    _sentinel.note(comm, name, args, kw)
                # start() fires the signature's frozen compiled
                # program (the MPI-4 "plan built once" promise made
                # literal: one XLA program per plan, cached across
                # starts via coll/plan)
                return async_request(
                    _plan.dispatch(comm, name, fn, tuple(args), kw,
                                   sig_box=sig_box))

    def start(req) -> None:
        _persistent_starts.add()
        req._inner = fire()

    def prog(r) -> None:
        inner = getattr(r, "_inner", None)
        if inner is None:
            return
        done, _st = inner.test()
        if done and not r.is_complete:
            r.complete(value=inner.value, status=inner.status)

    req = Request(progress_fn=prog, persistent_start=start)

    def block() -> None:
        inner = req._inner
        st = inner.wait()
        req.complete(value=inner.value, status=st)

    req._block_fn = block
    req._inner = None
    return req
