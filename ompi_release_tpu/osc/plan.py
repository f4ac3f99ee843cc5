"""Frozen RMA access plans — the one-sided analogue of ``coll/plan``.

``Window._run_epoch_program`` already aggregates an epoch's ops into
one device program, but every close still pays the full Python
orchestration: branch-key derivation, payload staging, pow2 padding,
cache lookups — and the wire window re-serializes every remote batch
header from scratch. Real one-sided workloads (param-server updates,
KV-cache fills, SHMEM counter loops) close the SAME epoch shape over
and over, so this module freezes per-(window, epoch-signature)
**access plans**:

- the signature is the epoch's op sequence as hashable metadata —
  (kind, target, payload shape/dtype, the frozen Op OBJECT, index,
  read-request flag, displacement and count of a ranged op) per op — derived with the same descriptor rules
  ``coll/plan`` uses (``arg_desc``), so a same-named user op can never
  alias a predefined op's program;
- a plan holds ONE fused XLA program for the epoch's local/device
  side: targets, branch kinds, and indices are baked as constants
  into an unrolled program over the window state (no ``lax.scan``
  carry, no ``lax.switch`` dispatch, no per-close staging of code/
  target/index arrays), reusing ``Window._branch_fn`` so planned and
  interpreted closes are BITWISE identical;
- for the remote side, :class:`BatchTemplate` precomposes the wire
  request records (the per-op meta JSON with displacement and count,
  the payload descriptors, the payloads per frame) at freeze time and
  fetches only the payload arrays, identical to ``_pack_batch`` output
  — ``WinService`` sees no difference on the wire;
- plans are generation-stamped against the MCA write generation
  exactly like ``SchedulePlan``: any cvar write re-plans at the next
  epoch close. The first close of a new signature runs the
  interpreted program (the capturing run); replay divergence drops
  the plan loudly and re-records at the next close.

Plans live on the window (``win._access_plans`` /
``win._batch_templates``) and are evicted at ``win.free()`` — a dead
window must not pin fused programs. Callers hold the window's
``_op_lock``; device dispatch itself stays under the process-wide
``_dispatch_lock`` (the jaxlib rendezvous rule in ``window.py``).
"""

from __future__ import annotations

import time as _time
from typing import List, Optional, Tuple

import numpy as np

from .. import obs as _obs
from ..coll.plan import arg_desc
from ..mca import pvar
from ..mca import var as mca_var
from ..obs import ledger as _ledger
from ..obs import spans as _spans
from ..utils import output

_log = output.stream("osc")


def register_vars() -> None:
    mca_var.register(
        "osc_compiled", "bool", True,
        "Freeze per-(window, epoch-signature) RMA access plans: a "
        "repeated epoch replays one fused XLA program plus "
        "precomposed wire frames instead of re-interpreting the "
        "pending queue (osc/plan); false keeps every close on the "
        "interpreted scan/switch program",
    )
    mca_var.register(
        "osc_plan_max_ops", "int", 128,
        "Largest epoch (pending-op count) eligible for a frozen "
        "access plan — the fused program is unrolled, so this bounds "
        "XLA compile size; larger epochs stay interpreted",
    )


register_vars()

_plan_hits = pvar.aggregate(
    "osc_plan_cache_hits",
    "plannable RMA closes served by a frozen access plan (1) vs "
    "capturing/re-freezing runs (0) — sum/count = steady-state ratio",
)
_plans_frozen = pvar.counter(
    "osc_plans_frozen", "RMA access plans frozen (one per new "
    "(window, epoch signature))",
)
_plan_programs = pvar.counter(
    "osc_plan_programs",
    "fused epoch programs compiled (first replay of a frozen plan)",
)
_templates_frozen = pvar.counter(
    "osc_batch_templates",
    "plan-time wire batch templates frozen (precomposed remote "
    "request records)",
)
_orch = pvar.timer(
    "osc_orchestration_seconds",
    "host time from epoch-close entry to device-program handoff "
    "(both the interpreted and the planned path feed it)",
)

#: generation-cached cvar snapshot: (generation, enabled, max_ops) —
#: steady-state closes cost one attribute read + int compare, never a
#: registry lookup (the WireRouter.tuning() pattern)
_conf: Tuple[int, bool, int] = (-1, True, 128)


def _refresh_conf() -> Tuple[int, bool, int]:
    global _conf
    gen = mca_var.VARS.generation
    if _conf[0] != gen:
        _conf = (
            gen,
            bool(mca_var.get("osc_compiled", True)),
            int(mca_var.get("osc_plan_max_ops", 128) or 0),
        )
    return _conf


def orch_add(seconds: float) -> None:
    """Interpreted-path hook: ``_run_epoch_program`` reports its
    orchestration span here so planned and interpreted closes are
    measured identically."""
    _orch.add(seconds)


# ---------------------------------------------------------------------------
# epoch signatures
# ---------------------------------------------------------------------------

def epoch_signature(todo: List) -> Optional[Tuple]:
    """Hashable signature of one epoch's op sequence, or None when any
    op is unplannable (an unhashable user op). The sequence is ordered
    — ops on overlapping targets must replay in submission order
    (MPI same-origin ordering), so order is part of the identity."""
    sig = []
    for p in todo:
        dd = None
        if p.data is not None:
            dd = arg_desc(p.data)
            if dd is None:
                return None
        cd = None
        if p.compare is not None:
            cd = arg_desc(p.compare)
            if cd is None:
                return None
        od = None
        if p.op is not None:
            od = arg_desc(p.op)
            if od is None:
                return None
        sig.append((
            p.kind, int(p.target), dd, od, cd,
            -1 if p.index is None else int(p.index),
            p.request is not None,
            -1 if p.status_rank is None else int(p.status_rank),
            -1 if p.disp is None else int(p.disp),
            -1 if p.count is None else int(p.count),
        ))
    return tuple(sig)


# ---------------------------------------------------------------------------
# the fused device-side plan
# ---------------------------------------------------------------------------

class EpochPlan:
    """One frozen access plan: the epoch's op metadata baked into an
    unrolled fused program over the window state. ``steps`` holds per
    op (kind, target, has_data, has_compare, index, op, has_request,
    disp, count) — everything but the payload bytes, which arrive as
    program arguments at replay."""

    __slots__ = ("gen", "sig", "steps", "prog", "nbytes", "lid",
                 "writes")

    def __init__(self, gen: int, sig: Tuple, todo: List) -> None:
        self.gen = gen
        self.sig = sig
        self.steps = tuple(
            (p.kind, int(p.target), p.data is not None,
             p.compare is not None,
             -1 if p.index is None else int(p.index), p.op,
             p.request is not None,
             -1 if p.disp is None else int(p.disp), p.count)
            for p in todo
        )
        # an epoch of gets alone hands no window back
        self.writes = any(p.kind != "get" for p in todo)
        self.prog = None  # compiled lazily at first replay
        self.nbytes = sum(
            int(getattr(p.data, "nbytes", 0) or 0)
            + int(getattr(p.compare, "nbytes", 0) or 0)
            for p in todo
        )
        self.lid: Optional[int] = None  # ledger plan id, on first
        #                                 observed fire

    def _build(self, win):
        """Compile the fused program: targets/kinds/indices/ranges are
        Python constants, payloads are arguments, each op reuses the
        SAME branch lambda the interpreted ``lax.scan`` programs
        dispatch through — so replays are bitwise-identical to
        captures. A ranged step slices its block out of the flattened
        window and writes it back in place: its payload, its compare
        and its read have the block's size, never the slot's."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from .window import Window

        dtype = win._data.dtype
        block = win.shape
        steps = self.steps
        writes = self.writes
        fns = []
        for (kind, _t, _hd, _hc, index, op, _hr, _d, _c) in steps:
            bkind = "acc" if kind in ("acc", "get_acc") else kind
            fns.append(Window._branch_fn((bkind, op, index >= 0), op))

        def fused(data, *bufs):
            flat = data.reshape(data.shape[0], -1)
            reads = []
            bi = 0
            for fn, (kind, tgt, has_d, has_c, idx, op, has_r, disp,
                     count) in zip(fns, steps):
                shape = block if disp < 0 else (count,)

                def arg(x, shape=shape, ranged=disp >= 0):
                    x = jnp.asarray(x).astype(dtype)
                    return jnp.broadcast_to(
                        x.reshape(-1) if ranged else x, shape)

                pay = cmp = None
                if has_d:
                    pay = arg(bufs[bi])
                    bi += 1
                if has_c:
                    cmp = arg(bufs[bi])
                    bi += 1
                if disp < 0:
                    cur = flat[tgt].reshape(block)
                    zeros = jnp.zeros(block, dtype)
                    new, read = fn(cur, zeros if pay is None else pay,
                                   zeros if cmp is None else cmp,
                                   max(idx, 0))
                    if writes:
                        flat = flat.at[tgt].set(new.reshape(-1))
                else:
                    cur = lax.dynamic_slice(flat, (tgt, disp),
                                            (1, count))[0]
                    new, read = fn(cur, pay, cmp, 0)
                    if writes and kind != "get":
                        flat = lax.dynamic_update_slice(
                            flat, new[None], (tgt, disp))
                if has_r:
                    reads.append(read)
            return (flat.reshape(data.shape) if writes else None,
                    tuple(reads))

        _plan_programs.add()
        return jax.jit(fused)

    def replay(self, win, todo: List, t0: float) -> List:
        """Fire the fused program for one epoch close and return the
        pre-op values the epoch asked for, as host arrays in op order.
        Caller holds ``win._op_lock``; raises on any divergence (the
        caller drops the plan)."""
        from .window import _dispatch_lock, _epoch_dispatches

        prog = self.prog
        if prog is None:
            prog = self.prog = self._build(win)
        args = []
        for p in todo:
            if p.data is not None:
                args.append(win._origin(p.data))
            if p.compare is not None:
                args.append(win._origin(p.compare))
        _orch.add(_time.perf_counter() - t0)
        with _dispatch_lock:
            _epoch_dispatches.add()
            with _obs.span(_spans.OSC_PROGRAM, ops=len(todo)):
                new_data, reads = prog(win._data, *args)
        # read completion mirrors the interpreted path: host copies
        # outside _dispatch_lock (per-shard fetches, not a program —
        # the rendezvous-deadlock rule in window.py)
        out = []
        wants = (p for p in todo if p.request is not None)
        for p, r in zip(wants, reads):
            value = np.asarray(r)
            if p.index is not None:
                value = value.reshape(-1)[p.index]
            out.append(value)
        if self.writes:
            win._data = new_data
        return out


def close_epoch(win, todo: List, t0: float) -> Optional[List]:
    """Close one epoch through the access-plan cache. A list (the
    epoch's read values, host arrays in op order) = a frozen plan
    replayed and ``win._data`` is rebound; None = the caller must run
    the interpreted epoch programs — either plans are off/unplannable,
    or this close is the capturing run of a freshly frozen plan."""
    gen, enabled, max_ops = _refresh_conf()
    if not enabled or not todo or len(todo) > max_ops:
        return None
    sig = epoch_signature(todo)
    if sig is None:
        return None
    plans = win._access_plans
    plan = plans.get(sig)
    if plan is not None and plan.gen == gen:
        try:
            reads = plan.replay(win, todo, t0)
        except Exception as e:
            # divergence: drop the plan LOUDLY and re-record at the
            # next close; this close falls back to the interpreted
            # program (replay is functional — state was not touched)
            plans.pop(sig, None)
            _log.verbose(
                1, f"dropping diverged RMA access plan on {win.name}: "
                   f"{type(e).__name__}: {e}; re-recording")
            return None
        _plan_hits.observe(1)
        if _obs.enabled:
            t1 = _time.perf_counter()
            lid = plan.lid
            if lid is None:
                lid = plan.lid = _ledger.register_rma_plan(
                    win.comm.cid, f"epoch[{len(todo)}]", plan.nbytes,
                    sig)
            _ledger.record_fire(_ledger.KIND_RMA, lid, win.comm.cid,
                                t0, t1)
            _obs.record("rma_epoch_replay", "osc", t0, t1 - t0,
                        nbytes=plan.nbytes, comm_id=win.comm.cid)
        return reads
    # first sight (or stale generation): freeze now, capture via the
    # interpreted program this close
    plans[sig] = EpochPlan(gen, sig, todo)
    _plans_frozen.add()
    _plan_hits.observe(0)
    return None


# ---------------------------------------------------------------------------
# plan-time wire frames (the remote side)
# ---------------------------------------------------------------------------

class BatchTemplate:
    """Precomposed wire header for one remote-batch signature: the
    per-op request records with displacement and count, the payload
    descriptors and how the payloads fall into wire frames (what
    ``_pack_batch`` derives per call from shapes alone) are composed
    ONCE at freeze time; :meth:`render` fetches only the payloads, so
    the batch is identical to ``_pack_batch``'s — ``WinService`` sees
    no difference on the wire."""

    __slots__ = ("gen", "header")

    def __init__(self, gen: int, todo: List, seg: int) -> None:
        from .wire_win import _batch_header

        self.gen = gen
        self.header = _batch_header(todo, seg)

    def render(self, todo: List):
        from .wire_win import _pack_batch

        return _pack_batch(todo, 0, self.header)


def batch_payload(win, todo: List):
    """One remote batch as it goes on the wire (``wire_win.Batch``):
    replay the signature's frozen :class:`BatchTemplate` in steady
    state, else pack interpreted and freeze. The batch is identical
    either way."""
    from .wire_win import _pack_batch

    seg = win.service.tuning().segment
    gen, enabled, max_ops = _refresh_conf()
    if not enabled or len(todo) > max_ops:
        return _pack_batch(todo, seg)
    sig = epoch_signature(todo)
    if sig is None:
        return _pack_batch(todo, seg)
    tpls = win._batch_templates
    tpl = tpls.get(sig)
    if tpl is not None and tpl.gen == gen:
        _plan_hits.observe(1)
        return tpl.render(todo)
    # the interpreted pack runs first: it owns the predefined-op
    # validation, so an unshippable batch raises before any freeze
    payload = _pack_batch(todo, seg)
    tpls[sig] = BatchTemplate(gen, todo, seg)
    _templates_frozen.add()
    _plan_hits.observe(0)
    return payload


# ---------------------------------------------------------------------------
# operator surface
# ---------------------------------------------------------------------------

def cache_stats() -> dict:
    """Operator-visible plan-cache counters (obs --selftest leg).
    Plans live per-window, so totals are the monotone freeze/compile
    counters, not a live cache census."""
    st = _plan_hits.read()
    return {
        "epoch_plans": int(_plans_frozen.read()),
        "batch_templates": int(_templates_frozen.read()),
        "programs": int(_plan_programs.read()),
        "fires": int(st["count"]),
        "hits": int(st["sum"]),
    }


def _reset_for_tests() -> None:
    global _conf
    _conf = (-1, True, 128)
