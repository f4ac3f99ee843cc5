"""Compiled whole-schedule collectives — frozen :class:`SchedulePlan`s
fired with zero per-round Python work (ROADMAP item 6).

The reference pays its per-collective decision and segmentation cost
once, in C; this reproduction paid it in Python on EVERY call — the
tuned pickers' cvar reads, the per-dispatch body-lambda tables and
cache-key builds in ``coll/components.py``, per-frame header packing
in ``btl/components.py``, and per-message ``mca_var.get`` lookups in
``runtime/wire.py``. This module freezes all of it at plan time:

in-process (device) collectives
    The MPI-4 persistent ``*_init`` path — and, in steady state,
    blocking and i-family calls with a previously-seen signature —
    fire ONE cached compiled XLA program per plan signature. The
    first (capturing) run goes through the full interpreted dispatch;
    :mod:`coll.driver` records the program handle plus the exact
    input/output objects, and identity of those objects against the
    collective's own argument and return value PROVES the dispatch
    was pre/post-processing-free, i.e. the program alone IS the
    collective. Every later fire is ``prog(jnp.asarray(buffer))`` —
    no decision logic, no cvar reads, no cache-key tuples. Bitwise
    parity with the interpreted path is structural: the fired program
    object is the very one the interpreted path compiled and ran.

spanning (wire) collectives
    The first run of a schedule records its ROUND STRUCTURE (peer
    lists, per-round send shapes/dtypes and receive counts) through a
    :class:`RoundRecorder` wrapped around the hier exchange adapter;
    :func:`freeze_wire_plan` then resolves the wire tuning cvars ONCE
    and precomposes every round's SGH2 frame headers and fragment
    offsets (:class:`~..btl.components.FrameTemplate`). Steady-state
    fires replay through :class:`PlannedXchg`: one ULFM check per
    round, memoryview slicing behind precomposed header bytes, the
    arrival-order reap — no per-message dict lookups, tag math, or
    header packing. The wire bytes are byte-identical to the
    interpreted path's, so results are bitwise-identical and the
    receive side needs no changes; FT slicing (PR 9) and sentinel
    hashing (PR 10 — one signature per collective, noted at posting)
    are untouched.

Invalidation: every plan is stamped with the MCA registry's write
GENERATION. Any cvar write bumps it, so the next fire quietly
re-captures with the new values — a mid-job tuning write takes effect
at the next plan, never mid-schedule. A schedule that still diverges
from its frozen plan mid-run (structure mismatch) is a loud typed
error naming the fix, never a silently wrong frame.

Observability is a property of the steady state, not a mode that
replaces it: an observed run KEEPS firing frozen plans. Each observed
compiled fire appends one fixed-size binary record — plan id, posting
seq, fire start/end, and one clock read per planned wire round — to
the plan-relative flight recorder (:mod:`~..obs.ledger`), which
registered the plan's full round/flow structure once at freeze time;
``tpu-doctor`` expands the records back into synthetic spans with the
interpreted path's exact flow ids. The ``obs_trace_sample`` cvar runs
1-in-N observed fires through the fully interpreted path for
ground-truth deep traces (the frozen plan survives), and inline
sentinel checking (level 2) rides the planned path over ctl frames —
neither tracing nor contract checking de-optimizes the hot path.

Scope guards: plans engage only for the fixed-signature collective
families (``_PLANNABLE``), and only when the call signature is
hashable metadata (:func:`signature_of` returns None for ragged
v-variants and pair ops, which stay interpreted).

pvars: ``coll_compiled_cache_hits`` (1 = fired a frozen plan, 0 = a
capturing run froze one; sum/count = steady-state hit ratio, printed
by ``obs --selftest``) — identical with obs on and off, the satellite
contract tpu_top's compiled-fire ratio column reads. Orchestration
time is witnessed by the driver's ``coll_orchestration_seconds``
timer, which both legs feed.
"""

from __future__ import annotations

import threading
import time as _time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from .. import obs as _obs
from ..mca import pvar
from ..mca import var as mca_var
from ..obs import ledger as _ledger
from ..obs import spans as _spans
from ..obs import watchdog as _watchdog
from ..runtime import progress as _progress
from ..utils.errors import ErrorCode, MPIError

#: plan-cache outcome per plannable collective fire: 1 = a frozen plan
#: fired (compiled program / planned wire rounds), 0 = a capturing run
#: built one. sum/count = the steady-state hit ratio.
_compiled_hits = pvar.aggregate(
    "coll_compiled_cache_hits",
    "compiled-schedule plan-cache outcome per fire (1=fired frozen "
    "plan, 0=capturing run froze one); sum/count = hit ratio",
)
_wire_rounds_frozen = pvar.counter(
    "coll_wire_rounds_frozen",
    "schedule rounds captured into frozen wire plans (peer lists, "
    "frame headers, fragment offsets precomposed at plan time)",
)


def register_vars() -> None:
    mca_var.register(
        "coll_compiled", "bool", True,
        "Fire frozen schedule plans (one compiled XLA program / "
        "precomposed wire rounds per plan signature) for persistent, "
        "blocking, and i-family collectives in steady state; false "
        "restores the fully interpreted per-call dispatch",
    )
    mca_var.register(
        "coll_plan_native", "bool", True,
        "Fire eligible frozen wire plans through the native C plan "
        "executor (one ctypes slice loop walks every round: striped "
        "sends, pooled reassembly, FT fault-word polling). Requires "
        "the native .so and a nativewire card on every round peer; "
        "anything else — and false — replays through the interpreted "
        "PlannedXchg path, bitwise-identical",
    )
    mca_var.register(
        "obs_trace_sample", "int", 0,
        "With obs on, run every Nth compiled-plan fire through the "
        "fully interpreted path for a ground-truth deep trace (full "
        "span/flow record); 0 = never — compiled fires are always "
        "flight-recorded in the obs ledger. Set identically on every "
        "rank (fire counters advance in lockstep)",
    )


register_vars()  # idempotent; the cvar must exist before first dispatch

#: collective families with fixed call signatures whose schedules are
#: deterministic functions of (comm, shapes, op, root) — the plannable
#: set. Ragged v-variants ship data-dependent structure; barrier has
#: no payload to plan; submit()'s arbitrary serialized callables may
#: carry side effects a re-fire would skip.
_PLANNABLE = frozenset({
    "allreduce", "bcast", "allgather", "reduce", "gather", "scatter",
    "reduce_scatter_block", "reduce_scatter", "alltoall", "scan",
    "exscan",
})

# lazy heavyweight imports (driver pulls jax): resolved once at first
# device dispatch so the wire-plan/metadata half of this module stays
# importable device-free (obs --selftest, the fleet-sim tests)
_driver = None
_jnp = None

#: (gen, enabled, overlap, trace_sample) snapshot of the
#: coll_compiled / wire_overlap_exchange / obs_trace_sample cvars —
#: re-resolved only when the registry write generation moves
_conf = (-1, True, True, 0)

_lock = threading.Lock()
#: (cid, signature) -> device-plan entry {"gen", "prog"|"bad"}
_device_plans: Dict[Tuple[int, Tuple], Dict[str, Any]] = {}
#: (cid, signature) -> SpanningPlanState
_span_states: Dict[Tuple[int, Tuple], "SpanningPlanState"] = {}


def _lazy_driver():
    global _driver, _jnp
    if _driver is None:
        import jax.numpy as jnp

        from . import driver

        _driver, _jnp = driver, jnp
    return _driver


def _refresh_conf() -> Tuple[int, bool, bool, int]:
    global _conf
    gen = mca_var.VARS.generation
    if _conf[0] != gen:
        _conf = (gen, bool(mca_var.get("coll_compiled", True)),
                 bool(mca_var.get("wire_overlap_exchange", True)),
                 int(mca_var.get("obs_trace_sample", 0) or 0))
    return _conf


def _enabled() -> bool:
    return _refresh_conf()[1]


def _overlap_on() -> bool:
    # the planned replay path IS the striped/overlapped send path;
    # an operator's wire_overlap_exchange=False opt-out (serialize
    # sends, e.g. around a flaky fabric) must keep spanning fires
    # fully interpreted, where _XchgAdapter honors the flag
    return _refresh_conf()[2]


def _trace_sample() -> int:
    return _refresh_conf()[3]


#: live planned replays, keyed by plan-state identity: the watchdog's
#: "frozen_plans" contributor names the plan (id, signature, round
#: index) a rank is stuck inside, instead of just raw wire waits.
#: Mutated only under an ``_obs.enabled`` gate (postmortems only fire
#: with obs on), so the unobserved hot path never touches it.
_active_replays: Dict[int, Tuple["SpanningPlanState",
                                 "PlannedXchg"]] = {}


def _frozen_plans_snapshot() -> Dict[str, Any]:
    out = []
    for st, px in list(_active_replays.values()):
        plan = px.plan
        out.append({
            "plan": plan.ledger_id, "name": st.name,
            "comm": getattr(st.comm, "name", "?"), "cid": plan.cid,
            "signature": _ledger._sig_summary(st.sig),
            "round": px.i, "rounds_total": len(plan.rounds),
        })
    return {"active_replays": out, **cache_stats()}


_watchdog.add_contributor("frozen_plans", _frozen_plans_snapshot)


def _sig_nbytes(sig: Tuple) -> int:
    """Payload bytes of a plan signature's first array argument (the
    flight recorder's per-fire byte accounting for device plans)."""
    for d in sig[1:]:
        if isinstance(d, tuple) and d and d[0] == "arr":
            n = 1
            for s in d[1]:
                n *= int(s)
            try:
                return n * int(np.dtype(d[2]).itemsize)
            except TypeError:
                return 0
    return 0


def clear_comm(cid: int) -> None:
    """Drop every frozen plan of one communicator (comm free / the
    explicit-cid rebuild path: a reused cid must never fire a dead
    comm's programs)."""
    with _lock:
        for d in (_device_plans, _span_states):
            for key in [k for k in d if k[0] == cid]:
                d.pop(key, None)


def cache_stats() -> Dict[str, int]:
    """Operator-visible plan-cache counters (obs --selftest leg)."""
    st = _compiled_hits.read()
    return {
        "device_plans": len(_device_plans),
        "spanning_plans": len(_span_states),
        "fires": int(st["count"]),
        "hits": int(st["sum"]),
    }


def _reset_for_tests() -> None:
    with _lock:
        _device_plans.clear()
        _span_states.clear()
        _active_replays.clear()


# ---------------------------------------------------------------------------
# plan signatures: hashable metadata of one collective call
# ---------------------------------------------------------------------------

def _arg_desc(a) -> Optional[Tuple]:
    shape = getattr(a, "shape", None)
    if shape is not None and hasattr(a, "dtype"):
        return ("arr", tuple(int(d) for d in shape), str(a.dtype))
    if a is None or isinstance(a, (bool, int, float, str)):
        return ("v", a)
    if hasattr(a, "commutative") and hasattr(a, "name"):
        # an Op: the (frozen, hashable) op itself is the key — two ops
        # sharing a name but different fns must not share a program,
        # and holding the object (not its id) keeps it alive so a
        # recycled address can never alias a dead op's frozen program
        try:
            hash(a)
        except TypeError:
            return None
        return ("op", a)
    if isinstance(a, (list, tuple)):
        if all(isinstance(v, (bool, int, float)) for v in a):
            return ("seq", tuple(a))
        return None  # ragged buffer lists: not plannable
    return None


#: public name: osc/plan reuses the same descriptor rules for RMA
#: epoch signatures — identical Op-OBJECT keying and array metadata,
#: so the two planes can never drift on what is plannable
arg_desc = _arg_desc


def signature_of(name: str, args: Tuple,
                 kw: Optional[Dict]) -> Optional[Tuple]:
    """Hashable plan signature of one collective call, or None when
    the call is not plannable (ragged buffers, pair-op tuples,
    exotic kwargs)."""
    sig: List[Any] = [name]
    for a in args:
        d = _arg_desc(a)
        if d is None:
            return None
        sig.append(d)
    for k in sorted(kw or ()):
        d = _arg_desc(kw[k])
        if d is None:
            return None
        sig.append((k, d))
    return tuple(sig)


# ---------------------------------------------------------------------------
# in-process: one compiled XLA program per plan signature
# ---------------------------------------------------------------------------

def dispatch(comm, name: str, fn: Callable, args: Tuple,
             kw: Optional[Dict] = None,
             sig_box: Optional[list] = None) -> Any:
    """THE in-process collective dispatch: fire the signature's frozen
    compiled program when one exists (steady state — no decision
    logic, no cvar reads), else run the interpreted path under
    capture and freeze the program it dispatched. Falls back to plain
    interpreted execution whenever obs is on (full span record), the
    family is unplannable, or the capture proved the dispatch did
    pre/post-processing the program alone cannot replay.
    ``sig_box``: a persistent request's one-element signature memo —
    the arguments are bound at ``*_init``, so ``start()`` skips even
    the signature build."""
    t0 = _time.perf_counter()
    if name not in _PLANNABLE:
        return fn(comm, *args, **(kw or {}))
    if not _enabled():
        # fully interpreted (coll_compiled=0): still re-base the
        # orchestration timer at THIS entry so the interpreted and
        # compiled legs time the same span
        d = _lazy_driver()
        d.orch_mark(t0)
        try:
            return fn(comm, *args, **(kw or {}))
        finally:
            d.orch_clear()
    if sig_box is not None and sig_box:
        sig = sig_box[0]
    else:
        sig = signature_of(name, args, kw)
        if sig_box is not None:
            sig_box.append(sig)
    if sig is None:
        return fn(comm, *args, **(kw or {}))
    gen = mca_var.VARS.generation
    key = (comm.cid, sig)
    e = _device_plans.get(key)
    if e is not None and e["gen"] == gen:
        prog = e.get("prog")
        if prog is not None:
            # the steady state — observed or not. An observed fire is
            # flight-recorded (one fixed-size ledger record, no span
            # objects); obs_trace_sample=N diverts every Nth observed
            # fire through the interpreted path for a ground-truth
            # deep trace, plan intact.
            obs_on = _obs.enabled
            if obs_on:
                n = _trace_sample()
                if n > 0:
                    f = e["fires"] = e.get("fires", 0) + 1
                    if f % n == 0:
                        d = _lazy_driver()
                        d.orch_mark(t0)
                        try:
                            return fn(comm, *args, **(kw or {}))
                        finally:
                            d.orch_clear()
            d = _lazy_driver()
            # pvar continuity: a frozen-plan fire IS an invocation and
            # a (deeper) plan-cache hit — MPI_T series must not dip
            # when the steady state engages
            d._invoke_count.add()
            d._plan_cache.observe(1.0)
            if comm.cid >= 0:
                # runtime-internal comms (the hier shadow) fire plans
                # too, but only USER-visible collectives count in the
                # hit ratio — the sentinel's negative-cid rule
                _compiled_hits.observe(1)
            # timer closes BEFORE the buffer conversion + launch,
            # exactly where run_sharded closes it on the interpreted
            # leg — the two legs time the identical span
            d._orch.add(_time.perf_counter() - t0)
            with _obs.span(_spans.COLL_LAUNCH):
                out = prog(_jnp.asarray(args[0]))
            if not obs_on:
                return out
            lid = e.get("lid")
            if lid is None:
                lid = e["lid"] = _ledger.register_device_plan(
                    comm.cid, name, _sig_nbytes(sig), sig)
            _ledger.record_fire(_ledger.KIND_DEVICE, lid, comm.cid,
                                t0, _time.perf_counter())
            return out
        if "bad" in e:
            return fn(comm, *args, **(kw or {}))
    # capture attempt: interpreted run with program-dispatch recording
    d = _lazy_driver()
    d.orch_mark(t0)  # the timer covers the decision path too
    cap = d.begin_capture()
    try:
        out = fn(comm, *args, **(kw or {}))
    finally:
        d.end_capture()
        d.orch_clear()
    entry: Dict[str, Any] = {"gen": gen}
    if (len(cap) == 1 and cap[0]["out"] is out
            and cap[0]["x"] is args[0] and not cap[0]["extra"]):
        entry["prog"] = cap[0]["prog"]
        if comm.cid >= 0:
            _compiled_hits.observe(0)
        if _obs.enabled:
            _obs.record("plan_capture_" + name, "plan", t0,
                        _time.perf_counter() - t0, comm_id=comm.cid)
    else:
        entry["bad"] = True
    with _lock:
        _device_plans[key] = entry
    return out


# ---------------------------------------------------------------------------
# spanning: record the round structure, freeze the wire frames
# ---------------------------------------------------------------------------

#: module-level alias so tests can monkeypatch-count conversions:
#: the planned replay path must NOT pay np.asarray for inputs that
#: already are ndarrays (the overwhelmingly common steady state)
_np_asarray = np.asarray


def _as_nd(a):
    return a if isinstance(a, np.ndarray) else _np_asarray(a)


def _round_meta(sends: Dict[int, list]) -> Tuple:
    return tuple(
        (p, tuple((a.shape, str(a.dtype))
                  for a in map(_as_nd, sends[p])))
        for p in sorted(sends) if sends[p]
    )


class RoundRecorder:
    """Exchange-adapter wrapper: delegates every round to the real
    transport and records its structure — (peer, shape, dtype) per
    send, receive counts per peer, and the per-source arrival
    shapes/dtypes (the native executor's reassembly-pool layout;
    per-source order is deterministic: the wire is FIFO per peer).
    Works over the production :class:`~.hier._XchgAdapter` and the
    fleet simulator's ``FleetXchg`` alike (anything honoring the
    exchange contract)."""

    __slots__ = ("inner", "rounds", "recv_metas")

    def __init__(self, inner) -> None:
        self.inner = inner
        self.rounds: List[Tuple[Tuple, Tuple]] = []
        self.recv_metas: List[Tuple] = []

    def exchange(self, sends: Dict[int, list],
                 recvs: Dict[int, int]) -> Dict[int, list]:
        got = self.inner.exchange(sends, recvs)
        self.rounds.append((
            _round_meta(sends),
            tuple(sorted((int(p), int(c)) for p, c in recvs.items()
                         if int(c) > 0)),
        ))
        self.recv_metas.append(tuple(sorted(
            (int(src), tuple((_as_nd(a).shape, str(_as_nd(a).dtype))
                             for a in arrs))
            for src, arrs in got.items() if arrs)))
        return got


class WireRound:
    """One frozen schedule round: verification metadata plus the
    resolved send slots (peer -> per-message FrameTemplates or None
    for shm/legacy sends), channel tag, and striping depth.

    ``recvs_meta`` (per-source arrival shapes/dtypes) sizes the
    native executor's reassembly pool; ``frame_counts`` (frames per
    peer stream, header included) lets the striper skip QoS gating on
    exhausted streams. Both default None: manually-built rounds and
    pre-upgrade plans replay exactly as before."""

    __slots__ = ("sends_meta", "recvs_t", "recvs", "peers",
                 "peer_slots", "tag", "depth", "recvs_meta",
                 "frame_counts")

    def __init__(self, sends_meta: Tuple, recvs_t: Tuple, peer_slots,
                 tag: int, depth: int, recvs_meta: Optional[Tuple] = None,
                 frame_counts: Optional[Tuple] = None) -> None:
        self.sends_meta = sends_meta
        self.recvs_t = recvs_t
        self.recvs = dict(recvs_t)
        self.peers = tuple(p for p, _ in sends_meta)
        self.peer_slots = peer_slots
        self.tag = tag
        self.depth = depth
        self.recvs_meta = recvs_meta
        self.frame_counts = frame_counts


class WirePlan:
    """Frozen wire schedule: every round's structure and precomposed
    frames (the segsize they were built from is baked into each
    :class:`~..btl.components.FrameTemplate`), plus the plan-time
    ``wire_coll_timeout_ms`` snapshot replay waits are bounded by."""

    __slots__ = ("gen", "cid", "rounds", "timeout_ms", "ledger_id")

    def __init__(self, gen: int, cid: int, rounds: List[WireRound],
                 timeout_ms: int) -> None:
        self.gen = gen
        self.cid = cid
        self.rounds = rounds
        self.timeout_ms = timeout_ms
        #: flight-recorder plan id — registered lazily at the first
        #: OBSERVED fire (obs/ledger holds the frozen round/flow
        #: structure; fires then append fixed-size records only)
        self.ledger_id: Optional[int] = None


def freeze_wire_plan(comm, recorded: List[Tuple[Tuple, Tuple]],
                     gen: int,
                     recv_metas: Optional[List[Tuple]] = None,
                     ) -> Optional[WirePlan]:
    """Resolve one recorded round structure into a frozen
    :class:`WirePlan`: wire tuning cvars snapshot once (the satellite
    contract — a mid-job cvar write lands here, at the NEXT plan),
    SGH2 headers and fragment offsets precomposed per send slot.

    ``recv_metas`` (parallel to ``recorded``, the recorder's
    per-source arrival shapes/dtypes) is optional: plans frozen
    without it stay fully replayable, they just never graduate to the
    native executor (which needs arrival metas to size its pool)."""
    router = getattr(comm.runtime, "wire", None)
    if router is None:
        return None
    from ..btl import components as _btl

    tuning = router.refresh_tuning()
    tag = router._coll_tag(comm)
    rounds: List[WireRound] = []
    for i, item in enumerate(recorded):
        sends_meta, recvs_t = item[0], item[1]
        recvs_meta = (recv_metas[i] if recv_metas is not None
                      and i < len(recv_metas) else None)
        peer_slots = []
        frame_counts = []
        for p, arrs in sends_meta:
            tpls = []
            for shape, dtype in arrs:
                tpl = None
                btl = router._btl_for(p)
                # every segsize-framed transport precomposes: dcn's
                # interpreted SGH2 stream and nativewire's
                # scatter-gather stream share the FrameTemplate (the
                # byte-identity authority), each clamped to its OWN
                # max frame size cvar
                if tuning.segsize > 0 and (
                        btl is router._dcn
                        or (router._nw is not None
                            and btl is router._nw)):
                    seg = min(tuning.segsize,
                              max(1, btl.max_send_size))
                    tpl = _btl.plan_frame_template(shape, dtype, seg)
                tpls.append(tpl)
            peer_slots.append((p, tuple(tpls)))
            # frames a stream will emit: header + fragments for a
            # templated message, one frame otherwise — exact for the
            # portable streams and never less than a native stream's
            # steps, so the striper can drop a drained stream without
            # gating it
            frame_counts.append(sum(
                (int(t.nchunks) + 1) if t is not None else 1
                for t in tpls))
        rounds.append(WireRound(sends_meta, recvs_t, tuple(peer_slots),
                                tag, tuning.depth,
                                recvs_meta=recvs_meta,
                                frame_counts=tuple(frame_counts)))
    _wire_rounds_frozen.add(len(rounds))
    return WirePlan(gen, comm.cid, rounds, tuning.coll_timeout_ms)


class PlannedXchg:
    """Exchange adapter replaying a frozen :class:`WirePlan`: each
    round verifies its structure against the plan (cheap tuple
    compare), then sends through the precomposed frame path and reaps
    in arrival order. Divergence is a loud typed error — frames from
    a wrong header would corrupt the peer's reassembly."""

    __slots__ = ("m", "plan", "i", "ts", "seq")

    def __init__(self, module, plan: WirePlan, seq: int = 0) -> None:
        self.m = module
        self.plan = plan
        self.i = 0
        #: the schedule's posting seq: joins this fire's
        #: ``ompi.plan.xchg`` spans to its ``ompi.nbc.wait``
        self.seq = seq
        #: round-end clock reads for the flight recorder (one
        #: perf_counter per planned round); None = unobserved fire,
        #: zero clock reads
        self.ts: Optional[List[float]] = None

    def _mismatch(self, detail: str) -> MPIError:
        return MPIError(
            ErrorCode.ERR_INTERN,
            f"compiled schedule plan diverged mid-run on "
            f"{self.m.comm.name} (round {self.i}): {detail}. The "
            "schedule no longer matches its frozen plan — rebuild the "
            "persistent request (or re-issue the collective) after "
            "changing schedule-selection cvars",
        )

    def exchange(self, sends: Dict[int, list],
                 recvs: Dict[int, int]) -> Dict[int, list]:
        # the whole replay round is Python orchestration (posting,
        # striping, reap polling) — self-report it so the steady-state
        # orchestration split sees the replay loop the native executor
        # exists to eliminate
        t0 = _time.perf_counter()
        try:
            return self._exchange(sends, recvs)
        finally:
            _lazy_driver().orch_add(_time.perf_counter() - t0)

    def _exchange(self, sends: Dict[int, list],
                  recvs: Dict[int, int]) -> Dict[int, list]:
        plan = self.plan
        if self.i >= len(plan.rounds):
            raise self._mismatch("more rounds than the plan recorded")
        rnd = plan.rounds[self.i]
        self.i += 1
        # comparison forms were precomputed at freeze time
        # (rnd.sends_meta / rnd.recvs): no re-sort of the recv list,
        # no np.asarray for inputs that already are ndarrays, and the
        # metadata tuple is built from the once-converted arrays
        sends_f = {p: [_as_nd(a) for a in arrs]
                   for p, arrs in sends.items() if arrs}
        meta = tuple(
            (p, tuple((a.shape, str(a.dtype)) for a in sends_f[p]))
            for p in sorted(sends_f))
        recvs_l = {int(p): int(c)
                   for p, c in recvs.items() if int(c) > 0}
        if meta != rnd.sends_meta or recvs_l != rnd.recvs:
            raise self._mismatch(
                f"sends/recvs {meta}/{recvs_l} != frozen "
                f"{rnd.sends_meta}/{rnd.recvs}")
        m = self.m
        got: Dict[int, list] = {p: [] for p in rnd.recvs}
        with _obs.span(_spans.PLAN_XCHG, cid=plan.cid, seq=self.seq,
                       bytes=sum(a.nbytes for arrs in sends_f.values()
                                 for a in arrs)):
            if sends_f:
                m._send_all_planned(rnd, sends_f)
            if rnd.recvs:
                # record=False: the flight recorder owns this fire's
                # span/flow story (expanded from the plan structure at
                # doctor time) — per-arrival journal spans here would
                # duplicate the synthetic ones and advance the hier
                # flow-k counters the expansion re-derives from zero
                m._reap(dict(rnd.recvs),
                        lambda src, arr: got[src].append(arr),
                        plan.timeout_ms, record=False)
        ts = self.ts
        if ts is not None:
            ts.append(_time.perf_counter())
        return got


class SpanningPlanState:
    """Per-(cid, signature) frozen-wire-plan holder: first fire
    records and freezes, later fires replay; a registry write
    generation bump quietly re-records (cvar writes take effect at
    the next plan, never mid-schedule)."""

    __slots__ = ("comm", "name", "plan", "sig", "fires",
                 "sentinel_tpl", "native")

    def __init__(self, comm, name: str, sig: Optional[Tuple] = None
                 ) -> None:
        self.comm = comm
        self.name = name
        self.plan: Optional[WirePlan] = None
        #: the plan lowered into the C executor (coll/native_exec) —
        #: None when ineligible; lives and dies with ``plan``
        self.native = None
        self.sig = sig
        #: observed-fire counter driving obs_trace_sample (advances in
        #: lockstep across ranks: collectives are, by definition,
        #: fired the same number of times everywhere)
        self.fires = 0
        #: (key, InlineFrameTemplate) cache — sentinel level 2's
        #: precomposed ctl-frame payload for this plan's call shape
        self.sentinel_tpl: Optional[Tuple] = None

    def _drop_native(self) -> None:
        nx, self.native = self.native, None
        if nx is not None:
            try:
                nx.close()
            except Exception:
                pass

    def run(self, fn: Callable, args: Tuple,
            kw: Optional[Dict]) -> Any:
        kw = kw or {}
        m = getattr(self.comm, "_hier_module", None)
        if m is None or not _enabled() or not _overlap_on():
            return fn(*args, **kw)
        gen = mca_var.VARS.generation
        plan = self.plan
        if plan is not None and plan.gen != gen:
            plan = self.plan = None  # cvars moved: re-plan
            self._drop_native()
        old = m._xchg
        if plan is None:
            # recording rides the fully-interpreted transport (spans,
            # flow ids, pvars untouched) — the recorder only watches
            t0 = _time.perf_counter()
            rec = RoundRecorder(old)
            m._xchg = rec
            try:
                out = fn(*args, **kw)
            finally:
                m._xchg = old
            self.plan = freeze_wire_plan(self.comm, rec.rounds, gen)
            if (self.plan is not None
                    and len(self.plan.rounds) == len(rec.recv_metas)):
                # graft the recorder's arrival metas onto the frozen
                # rounds: only the native executor reads them (pool
                # sizing), interpreted replay never looks
                for rnd, rmeta in zip(self.plan.rounds,
                                      rec.recv_metas):
                    try:
                        rnd.recvs_meta = rmeta
                    except (AttributeError, TypeError):
                        break
            if self.plan is not None:
                _compiled_hits.observe(0)
                if _obs.enabled:
                    _obs.record("plan_freeze_" + self.name, "plan",
                                t0, _time.perf_counter() - t0,
                                comm_id=self.comm.cid)
                # lower the fresh plan into the C executor (two
                # wire-free probe runs + descriptor compile + ring
                # bind); None = ineligible, replay stays interpreted
                from . import native_exec as _native
                self.native = _native.try_compile(
                    self, m, fn, args, kw)
            return out
        rec = _obs.enabled
        if rec:
            n = _trace_sample()
            self.fires += 1
            if n > 0 and self.fires % n == 0:
                # ground-truth deep trace: every Nth observed fire
                # runs fully interpreted (complete span/flow record);
                # the frozen plan survives for the next fire
                return fn(*args, **kw)
        seq = _progress.executing_seq()
        nx = self.native
        from . import native_exec as _native
        if nx is not None and nx.gen == plan.gen:
            px = _native.NativeXchg(m, plan, nx, args, seq,
                                    views=self.name in _native.VIEW_OPS)
        else:
            px = PlannedXchg(m, plan, seq)
            _native._python_replays.add()
        t0 = 0.0
        if rec:
            if plan.ledger_id is None:
                plan.ledger_id = _ledger.register_spanning_plan(
                    self.comm.cid, self.name, m.my_pidx, plan.rounds,
                    self.sig)
            px.ts = []
            _active_replays[id(self)] = (self, px)
            t0 = _time.perf_counter()
        m._xchg = px
        try:
            out = fn(*args, **kw)
        except BaseException:
            # ANY replay failure — structure divergence, an FT error
            # mid-round — drops the frozen plan so the next fire
            # re-records instead of replaying the same stale rounds
            # forever (the divergence error's own advice, "re-issue
            # the collective", must actually work)
            self.plan = None
            self._drop_native()
            raise
        finally:
            m._xchg = old
            if isinstance(px, _native.NativeXchg):
                # a native fire runs in segments with the call's wire
                # locks held between them: whatever the schedule body
                # did, they are released here
                px.close()
            if rec:
                _active_replays.pop(id(self), None)
        _compiled_hits.observe(1)
        if rec and _obs.enabled:
            # one fixed-size binary record; round0 is the hier round
            # counter _wrap advanced for this fire (synchronized
            # across ranks under obs), the flow-id base the doctor's
            # expansion shares with the interpreted path
            _ledger.record_fire(_ledger.KIND_SPANNING, plan.ledger_id,
                                self.comm.cid, t0,
                                _time.perf_counter(),
                                round0=m._round, round_ts=px.ts)
        return out


def spanning_state_for(comm, name: str, args: Tuple,
                       kw: Optional[Dict]) -> Optional[SpanningPlanState]:
    """The comm's plan state for this call signature (None = not
    plannable: ragged buffers, non-deterministic families)."""
    if name not in _PLANNABLE:
        return None
    sig = signature_of(name, args, kw)
    if sig is None:
        return None
    key = (comm.cid, sig)
    st = _span_states.get(key)
    if st is None:
        with _lock:
            st = _span_states.setdefault(
                key, SpanningPlanState(comm, name, sig))
    return st


def spanning_wrap(state: Optional[SpanningPlanState],
                  fn: Callable) -> Callable:
    """Wrap one schedule body so its execution (on whichever thread
    the progress engine runs it) records/replays through ``state``."""
    if state is None:
        return fn
    return lambda *a, **k: state.run(fn, a, k)
