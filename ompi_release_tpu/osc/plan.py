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
  read-request flag) per op — derived with the same descriptor rules
  ``coll/plan`` uses (``arg_desc``), so a same-named user op can never
  alias a predefined op's program;
- a plan holds ONE fused XLA program for the epoch's local/device
  side: targets, branch kinds, and indices are baked as constants
  into an unrolled program over the window state (no ``lax.scan``
  carry, no ``lax.switch`` dispatch, no per-close staging of code/
  target/index arrays), reusing ``Window._branch_fn`` so planned and
  interpreted closes are BITWISE identical;
- for the remote side, :class:`BatchTemplate` precomposes the wire
  request record (the per-op meta JSON) at freeze time and re-renders
  only the payload arrays, byte-identical to ``_pack_batch`` output —
  ``WinService``, the sentinel, and tpu-doctor are unchanged on the
  wire;
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

import json
import time as _time
from typing import Any, List, Optional, Tuple

import numpy as np

from .. import obs as _obs
from ..coll.plan import arg_desc
from ..mca import pvar
from ..mca import var as mca_var
from ..obs import ledger as _ledger
from ..request.request import Status
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
        ))
    return tuple(sig)


# ---------------------------------------------------------------------------
# the fused device-side plan
# ---------------------------------------------------------------------------

class EpochPlan:
    """One frozen access plan: the epoch's op metadata baked into an
    unrolled fused program over the window state. ``steps`` holds per
    op (kind, target, has_data, has_compare, index, op, status_rank,
    has_request) — everything but the payload bytes, which arrive as
    program arguments at replay."""

    __slots__ = ("gen", "sig", "steps", "prog", "nbytes", "lid")

    def __init__(self, gen: int, sig: Tuple, todo: List) -> None:
        self.gen = gen
        self.sig = sig
        self.steps = tuple(
            (p.kind, int(p.target), p.data is not None,
             p.compare is not None,
             -1 if p.index is None else int(p.index), p.op,
             p.status_rank, p.request is not None)
            for p in todo
        )
        self.prog = None  # compiled lazily at first replay
        self.nbytes = sum(
            int(getattr(p.data, "nbytes", 0) or 0)
            + int(getattr(p.compare, "nbytes", 0) or 0)
            for p in todo
        )
        self.lid: Optional[int] = None  # ledger plan id, on first
        #                                 observed fire

    def _build(self, win):
        """Compile the fused program: targets/kinds/indices are Python
        constants, payloads are arguments, each op reuses the SAME
        branch lambda the interpreted ``lax.scan`` program dispatches
        through — so replays are bitwise-identical to captures."""
        import jax
        import jax.numpy as jnp

        from .window import Window

        dtype = win._data.dtype
        block = win.shape
        steps = self.steps
        fns = []
        for (kind, _t, _hd, _hc, index, op, _sr, _hr) in steps:
            bkind = "acc" if kind in ("acc", "get_acc") else kind
            fns.append(Window._branch_fn((bkind, op, index >= 0), op))

        def fused(data, *bufs):
            zeros = jnp.zeros(block, dtype)
            reads = []
            bi = 0
            for fn, (kind, tgt, has_d, has_c, idx, op, _sr, has_r) in zip(
                    fns, steps):
                if has_d:
                    pay = jnp.broadcast_to(
                        jnp.asarray(bufs[bi]).astype(dtype), block)
                    bi += 1
                else:
                    pay = zeros
                if has_c:
                    cmp = jnp.broadcast_to(
                        jnp.asarray(bufs[bi]).astype(dtype), block)
                    bi += 1
                else:
                    cmp = zeros
                new, read = fn(data[tgt], pay, cmp, max(idx, 0))
                data = data.at[tgt].set(new)
                if has_r:
                    reads.append(read)
            return data, (jnp.stack(reads) if reads else None)

        _plan_programs.add()
        return jax.jit(fused)

    def replay(self, win, todo: List, t0: float) -> None:
        """Fire the fused program for one epoch close and complete its
        read requests. Caller holds ``win._op_lock``; raises on any
        divergence (the caller drops the plan)."""
        import jax.numpy as jnp

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
            new_data, reads = prog(win._data, *args)
        # read completion mirrors the interpreted path: ONE host copy
        # outside _dispatch_lock (per-shard fetches, not a program —
        # the rendezvous-deadlock rule in window.py)
        reads_np = None
        ri = 0
        for p in todo:
            if p.request is not None:
                if reads_np is None:
                    reads_np = np.asarray(reads)
                value = reads_np[ri]
                ri += 1
                if p.index is not None:
                    value = value.reshape(-1)[p.index]
                src = (p.target if p.status_rank is None
                       else p.status_rank)
                p.request.complete(value=jnp.asarray(value),
                                   status=Status(source=src))
        win._data = new_data


def close_epoch(win, todo: List, t0: float) -> bool:
    """Close one epoch through the access-plan cache. True = a frozen
    plan replayed (requests completed, ``win._data`` rebound); False =
    the caller must run the interpreted epoch program — either plans
    are off/unplannable, or this close is the capturing run of a
    freshly frozen plan."""
    gen, enabled, max_ops = _refresh_conf()
    if not enabled or not todo or len(todo) > max_ops:
        return False
    sig = epoch_signature(todo)
    if sig is None:
        return False
    plans = win._access_plans
    plan = plans.get(sig)
    if plan is not None and plan.gen == gen:
        try:
            plan.replay(win, todo, t0)
        except Exception as e:
            # divergence: drop the plan LOUDLY and re-record at the
            # next close; this close falls back to the interpreted
            # program (replay is functional — state was not touched)
            plans.pop(sig, None)
            _log.verbose(
                1, f"dropping diverged RMA access plan on {win.name}: "
                   f"{type(e).__name__}: {e}; re-recording")
            return False
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
        return True
    # first sight (or stale generation): freeze now, capture via the
    # interpreted program this close
    plans[sig] = EpochPlan(gen, sig, todo)
    _plans_frozen.add()
    _plan_hits.observe(0)
    return False


# ---------------------------------------------------------------------------
# plan-time wire frames (the remote side)
# ---------------------------------------------------------------------------

class BatchTemplate:
    """Precomposed wire frame for one remote-batch signature: the
    per-op request records (the meta JSON ``_pack_batch`` builds per
    call) are composed ONCE at freeze time; :meth:`render` re-packs
    only the payload arrays through the same deterministic writer, so
    the frame is byte-identical to ``_pack_batch`` output —
    ``WinService``, the wire sentinel, and tpu-doctor flows are
    unchanged on the wire."""

    __slots__ = ("gen", "meta_arr", "picks")

    def __init__(self, gen: int, todo: List) -> None:
        from .wire_win import _batch_meta

        self.gen = gen
        self.meta_arr = np.frombuffer(
            json.dumps(_batch_meta(todo)).encode(), dtype=np.uint8
        ).copy()
        self.picks = tuple(
            (i, p.data is not None, p.compare is not None)
            for i, p in enumerate(todo)
        )

    def render(self, todo: List) -> np.ndarray:
        from .wire_win import _savez_bytes

        arrays = {}
        for i, has_d, has_c in self.picks:
            p = todo[i]
            if has_d:
                arrays[f"d{i}"] = np.asarray(p.data)
            if has_c:
                arrays[f"c{i}"] = np.asarray(p.compare)
        arrays["meta"] = self.meta_arr
        return np.frombuffer(_savez_bytes(arrays), dtype=np.uint8).copy()


def batch_payload(win, todo: List) -> np.ndarray:
    """Serialize one remote batch: replay the signature's frozen
    :class:`BatchTemplate` in steady state, else pack interpreted and
    freeze. Output bytes are identical either way."""
    from .wire_win import _pack_batch

    gen, enabled, max_ops = _refresh_conf()
    if not enabled or len(todo) > max_ops:
        return _pack_batch(todo)
    sig = epoch_signature(todo)
    if sig is None:
        return _pack_batch(todo)
    tpls = win._batch_templates
    tpl = tpls.get(sig)
    if tpl is not None and tpl.gen == gen:
        _plan_hits.observe(1)
        return tpl.render(todo)
    # the interpreted pack runs first: it owns the predefined-op
    # validation, so an unshippable batch raises before any freeze
    payload = _pack_batch(todo)
    tpls[sig] = BatchTemplate(gen, todo)
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
