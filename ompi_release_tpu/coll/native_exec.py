"""Native plan execution — run frozen wire rounds end-to-end in C.

The reference's steady state walks posted descriptors inside opal
progress without re-entering any interpreter; our PR 13 plans and the
PR 17 native datapath still met in Python: every compiled fire paid
one ``PlannedXchg.exchange`` per round — per-fragment generator
``next()`` calls, per-arrival reap callbacks, fresh reassembly
buffers. This module lowers a whole frozen :class:`~.plan.WirePlan`
into a flat C descriptor table (``native/planexec.cc``) so a fire
becomes ONE ctypes call per ~100 ms slice: sends stripe through the
existing shm-ring writev / vectored-socket legs with the interpreted
path's exact FIFO-per-peer and depth discipline, receives land in a
per-plan preallocated reassembly pool reused across fires, and round
boundaries stamp into a timestamp block the obs ledger record
consumes unchanged.

How rounds >= 1 get their bytes without Python: at descriptor-compile
time the schedule body runs TWICE against a wire-free probe adapter,
each time over fresh random-byte inputs and random-byte synthetic
receives. Every later-round send payload is then located inside the
concatenation of (input regions | receive-pool regions) by unique
16-byte windows — a scatter-gather map of ``(region, offset, length)``
spans. Random bytes make any coincidental match astronomically
unlikely, and the two independently-seeded probes must infer the SAME
map or the plan stays on ``PlannedXchg``. The map is exact byte
provenance: at fire time C composes each send from live region bytes,
so the wire traffic is bitwise-identical to the interpreted path's
(the mixed-fleet contract — a peer without the .so interoperates
frame-for-frame).

Live rounds: a schedule that FOLDS what arrives and sends the fold on
(ring, Rabenseifner, the torus and multiring allreduces) makes bytes
no region holds. A round with such a message — or with one that is
part fold, part region, or too short to prove — is *live*: the fire
runs in segments. The C walk pauses in front of a live round, the
schedule body (in lockstep through :meth:`NativeXchg.exchange`, having
just folded the arrivals it was handed) supplies that round's send
arrays, and the same fire walks on to the next live round or the end.
Round 0, whose sends "come from the arrays the schedule just passed",
is the first case of it. Which rounds are live is what the probe
observed, twice and alike; the folds stay in the schedule body, in its
operand order. One call is still ONE fire: one set of locks held from
the first segment to the last, one ``plan_native_fires`` count.

Selection follows the MCA discipline: the ``coll_plan_native`` cvar
plus a capability check — native symbols present, every round peer on
the nativewire card, every send slot frame-templated, no QoS arbiter
— picks the C executor; anything else falls back to ``PlannedXchg``
unchanged. A fire that finds stashed/early frames or ring-lock
contention falls back for THAT fire only (``plan_native_fallbacks``).

ULFM: the executor polls a per-plan fault word and yields every
``slice_ms``; Python mirrors ``FtState`` into the word and runs
``check_wait`` between slices, so death/revocation surfaces as the
usual typed error within the detection interval.
"""
from __future__ import annotations

import os
import struct
import sys
import threading
import time as _time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from .. import obs as _obs
from ..mca import pvar
from ..mca import var as mca_var
from ..obs import spans as _spans
from ..utils.errors import ErrorCode, MPIError

#: bytes held by the per-plan native reassembly pools (the
#: mpool/rcache analogue: sized from the frozen recv metadata at
#: descriptor-compile time, reused across fires)
_pool_bytes = pvar.counter(
    "plan_pool_bytes",
    "bytes preallocated in native plan-executor reassembly pools "
    "(sized from frozen recv metadata, reused across fires)",
)
_pool_hits = pvar.counter(
    "plan_pool_hits",
    "preallocated pool buffers served to native plan fires (each "
    "hit = one reassembly that allocated nothing)",
)
_native_fires = pvar.counter(
    "plan_native_fires",
    "frozen wire plans fired end-to-end by the C executor (one "
    "ctypes slice loop instead of per-round Python orchestration)",
)
_native_ring_yields = pvar.counter(
    "plan_native_ring_yields",
    "fragments for which the C executor met a full tx ring: it "
    "drained its own arrivals and retried instead of waiting in the "
    "write (0 = the rings never filled; wire_native_ring_stalls "
    "counts the same records, together with the Python leg's)",
)
_native_fallbacks = pvar.counter(
    "plan_native_fallbacks",
    "native-eligible fires that fell back to the interpreted "
    "PlannedXchg replay for one fire (stashed/early frames, "
    "ring-lock contention)",
)

_native_live_rounds = pvar.counter(
    "plan_native_live_rounds",
    "rounds whose sends the schedule body supplied to a running "
    "native fire (the fire paused before them: their bytes are folds "
    "of earlier arrivals, which no region holds)",
)
_python_replays = pvar.counter(
    "plan_python_replays",
    "calls replayed round by round through PlannedXchg because their "
    "frozen plan was never lowered into the C executor (neither a "
    "plan_native_fires nor a plan_native_fallbacks count)",
)

_pool_copy_bytes = pvar.counter(
    "plan_pool_copy_bytes",
    "bytes copied out of a native plan's reassembly slab into fresh "
    "arrays for a schedule that folds its arrivals (0 for the "
    "collectives of VIEW_OPS, whose results are assembled straight "
    "from the slab: hier_assembled_bytes)",
)

#: collectives whose schedules only MOVE what arrives: a native fire
#: hands them read-only views of the slab and ``coll/hier`` assembles
#: the caller's array from those in one pass (``_HierModule._assemble``
#: has read every view before the schedule returns, so before the
#: plan's next fire overwrites the slab). Every other plannable
#: collective folds its arrivals in later jax calls and gets copies.
VIEW_OPS = frozenset({"allgather", "alltoall", "bcast", "gather"})

_BLOB_MAGIC = 0x314345584C504F  # "OPLXEC1" little-endian
_BLOB_VERSION = 2
_WIN = 16        # provenance-window bytes: unique-match granularity
_SEP = 32        # random separator bytes between arena regions
_SLICE_MS = 100  # matches runtime.wire._FT_SLICE_S


class _ProbeFail(Exception):
    """Descriptor compile cannot prove byte provenance — the plan
    stays on the interpreted PlannedXchg replay (never an error)."""


class _Ineligible(Exception):
    """Selection gate said no (cvar off, mixed fleet, missing
    symbols, ...) — same graceful withdrawal as :class:`_ProbeFail`,
    but named so OMPITPU_PLAN_NATIVE_DEBUG reports the gate."""


def available() -> bool:
    """True when the loaded .so carries the planexec symbols."""
    try:
        from ..native import bindings as _b
        return bool(_b.planexec_symbols_available())
    except Exception:
        return False


def _as_np(a):
    return a if isinstance(a, np.ndarray) else np.asarray(a)


def _nbytes_of(shape, dtype_str) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n * int(np.dtype(dtype_str).itemsize)


# ---------------------------------------------------------------------------
# probe: run the schedule body wire-free over random bytes
# ---------------------------------------------------------------------------

class _ProbeXchg:
    """Wire-free exchange adapter for the provenance probe: verifies
    each round's structure against the frozen plan, captures the send
    payload bytes in stream order, and hands back the pre-generated
    random receive arrays (the future pool regions)."""

    __slots__ = ("plan", "pools", "i", "payloads")

    #: nobody reads what the schedule body returns under this adapter:
    #: ``hier._assemble`` builds no result for it
    dry_run = True

    def __init__(self, plan, pools: Dict[Tuple[int, int], list]) -> None:
        self.plan = plan
        self.pools = pools
        self.i = 0
        #: per round: payload bytes per message, in (sorted peer,
        #: message-list) order — the blob's stream order
        self.payloads: List[List[bytes]] = []

    def exchange(self, sends: Dict[int, list],
                 recvs: Dict[int, int]) -> Dict[int, list]:
        plan = self.plan
        if self.i >= len(plan.rounds):
            raise _ProbeFail("probe ran more rounds than the plan")
        rnd = plan.rounds[self.i]
        sends_f = {p: [_as_np(a) for a in arrs]
                   for p, arrs in sends.items() if arrs}
        meta = tuple(
            (p, tuple((a.shape, str(a.dtype)) for a in sends_f[p]))
            for p in sorted(sends_f))
        recvs_t = tuple(sorted((int(p), int(c))
                               for p, c in recvs.items() if int(c) > 0))
        if meta != rnd.sends_meta or recvs_t != rnd.recvs_t:
            raise _ProbeFail("structure diverged under probe inputs")
        pay = []
        for p in sorted(sends_f):
            for a in sends_f[p]:
                pay.append(np.ascontiguousarray(a).tobytes())
        self.payloads.append(pay)
        got = {src: list(self.pools.get((self.i, src), ()))
               for src, _ in rnd.recvs_t}
        self.i += 1
        return got


def _rand_array(rng, shape, dtype_str) -> np.ndarray:
    dt = np.dtype(dtype_str)
    nb = _nbytes_of(shape, dtype_str)
    return np.frombuffer(bytearray(rng.bytes(nb)),
                         dtype=dt).reshape(shape)


def _probe_once(plan, m, fn: Callable, args: Tuple, kw: Dict,
                arg_idx: Tuple[int, ...], seed: int):
    """One wire-free run of the schedule body over random bytes.
    Returns (arg_arrays, pool_list, payloads-per-round)."""
    rng = np.random.default_rng(seed)
    pargs = list(args)
    arg_arrays = []
    for j in arg_idx:
        spec = _as_np(args[j])
        a = _rand_array(rng, spec.shape, str(spec.dtype))
        pargs[j] = a
        arg_arrays.append(a)
    pools: Dict[Tuple[int, int], list] = {}
    pool_list: List[np.ndarray] = []
    for i, rnd in enumerate(plan.rounds):
        for src, metas in rnd.recvs_meta:
            lst = [_rand_array(rng, shape, dt) for shape, dt in metas]
            pools[(i, src)] = lst
            pool_list.extend(lst)
    probe = _ProbeXchg(plan, pools)
    old = m._xchg
    m._xchg = probe
    try:
        # random bytes reinterpreted as floats are free to be NaN/inf
        # — only the structure and the raw payload bytes matter here
        with np.errstate(all="ignore"):
            fn(*pargs, **(kw or {}))
    finally:
        m._xchg = old
    if probe.i != len(plan.rounds):
        raise _ProbeFail("probe ran fewer rounds than the plan")
    return arg_arrays, pool_list, probe.payloads


def _build_arena(rng, arg_arrays, pool_list):
    """Concatenate every provenance source region with random
    separators. Returns (arena bytes, sorted region bounds) where a
    bound is (start, end, kind, idx): kind 0 = input region idx
    (positional — args occupy the first input slots), 1 = pool idx."""
    parts: List[bytes] = []
    bounds: List[Tuple[int, int, int, int]] = []
    pos = 0

    def _add(kind: int, idx: int, raw: bytes) -> None:
        nonlocal pos
        sep = rng.bytes(_SEP)
        parts.append(sep)
        pos += _SEP
        parts.append(raw)
        bounds.append((pos, pos + len(raw), kind, idx))
        pos += len(raw)

    for j, a in enumerate(arg_arrays):
        _add(0, j, a.tobytes())
    for k, a in enumerate(pool_list):
        _add(1, k, a.tobytes())
    parts.append(rng.bytes(_SEP))
    return b"".join(parts), bounds


def _region_at(bounds, off: int):
    """The region containing arena offset ``off`` (binary search), or
    None when it falls into a separator gap."""
    lo, hi = 0, len(bounds)
    while lo < hi:
        mid = (lo + hi) // 2
        if bounds[mid][0] <= off:
            lo = mid + 1
        else:
            hi = mid
    if lo == 0:
        return None
    b = bounds[lo - 1]
    return b if off < b[1] else None


def _match_payload(pay: bytes, arena: bytes, a_arr: np.ndarray,
                   bounds) -> Tuple[Tuple[int, int, int, int], ...]:
    """Greedy scatter-gather decomposition of one send payload over
    the arena: 16-byte windows anchor each span, vectorized compare
    extends it, region bounds clamp it. A window appearing in several
    regions (a round-0 send that aliases an argument, say) resolves
    DETERMINISTICALLY — longest matched span, then lowest arena
    offset — so both probe runs pick the same source; the cross-probe
    map-equality check in :func:`_infer_maps` is what proves the pick
    is structural, not a byte coincidence. Anything unprovable is a
    :class:`_ProbeFail` — fallback, never a guess."""
    n = len(pay)
    if n < _WIN:
        raise _ProbeFail("payload too small for provenance windows")
    p_arr = np.frombuffer(pay, dtype=np.uint8)
    segs: List[Tuple[int, int, int, int]] = []
    pos = 0
    while pos < n:
        if n - pos < _WIN:
            raise _ProbeFail("unmatchable payload tail")
        w = pay[pos:pos + _WIN]
        best = None  # (mlen, -off) maximized
        off = arena.find(w)
        if off < 0:
            raise _ProbeFail("payload bytes not found in any region")
        while off >= 0:
            reg = _region_at(bounds, off)
            if reg is not None and off + _WIN <= reg[1]:
                lim = min(n - pos, reg[1] - off)
                d = np.flatnonzero(
                    a_arr[off:off + lim] != p_arr[pos:pos + lim])
                mlen = int(d[0]) if d.size else lim
                if mlen >= _WIN and (best is None or mlen > best[0]):
                    best = (mlen, off, reg)
            off = arena.find(w, off + 1)
        if best is None:
            raise _ProbeFail("window matches no whole region span")
        mlen, off, reg = best
        start, _end, kind, idx = reg
        prev = segs[-1] if segs else None
        if (prev is not None and prev[0] == kind and prev[1] == idx
                and prev[2] + prev[3] == off - start):
            segs[-1] = (kind, idx, prev[2], prev[3] + mlen)
        else:
            segs.append((kind, idx, off - start, mlen))
        pos += mlen
    return tuple(segs)


def _infer_maps(plan, m, fn, args, kw, arg_idx):
    """Byte-provenance maps for every round >= 1 send message and the
    set of LIVE rounds, both proven identical across two
    independently-seeded probes. Returns ``(maps, live)``: ``maps[r]``
    holds one seg tuple per message of a mapped round and is None for
    round 0 and for a live round.

    A round is live when one of its messages cannot be covered whole
    by region bytes (:func:`_match_payload` refuses it: a fold, a
    payload that is part fold and part region, one too short to
    prove): the schedule body supplies such a round's sends to the
    running fire. So is a round that comes after a live one and reads
    a caller's array (kind 0): the body has run on between two
    segments and may have written to what it holds (Rabenseifner
    accumulates in place); only the slab is the executor's own."""
    results = []
    for seed in (0x5EED01 ^ (plan.cid & 0xFFFF),
                 0x5EED02 ^ (plan.cid & 0xFFFF)):
        arg_arrays, pool_list, payloads = _probe_once(
            plan, m, fn, args, kw, arg_idx, seed)
        # round-0 payload count has to match the stream order BEFORE
        # the arena is laid out: those payloads are input regions
        n0 = sum(len(a) for _, a in plan.rounds[0].sends_meta)
        if len(payloads[0]) != n0:
            raise _ProbeFail("round-0 message count diverged")
        rng = np.random.default_rng(seed ^ 0xA5A5A5)
        # provenance sources = args, then the round-0 send payloads
        # (same order as the C input-region table: a later round may
        # resend a locally-folded partial no argument ever held),
        # then every pool buffer
        inputs = list(arg_arrays) + [
            np.frombuffer(p, dtype=np.uint8) for p in payloads[0]]
        arena, bounds = _build_arena(rng, inputs, pool_list)
        a_arr = np.frombuffer(arena, dtype=np.uint8)
        maps: List[Optional[Tuple]] = [None]  # round 0 is identity
        live = set()
        for r in range(1, len(plan.rounds)):
            try:
                segs = tuple(_match_payload(p, arena, a_arr, bounds)
                             for p in payloads[r])
            except _ProbeFail:
                segs = None
            if segs is not None and live and any(
                    sg[0] == 0 for msg in segs for sg in msg):
                segs = None
            if segs is None:
                live.add(r)
            maps.append(segs)
        results.append((tuple(maps), frozenset(live)))
    if results[0] != results[1]:
        raise _ProbeFail("independent probes inferred different maps "
                         "or live rounds")
    return results[0]


# ---------------------------------------------------------------------------
# descriptor compile: plan + maps -> flat C blob
# ---------------------------------------------------------------------------

def _align8(n: int) -> int:
    return (n + 7) & ~7


def build_blob(tag: int, input_lens, pool_sizes, peer_pidx,
               rounds) -> bytes:
    """Serialize the flat descriptor table ``planexec_create``
    consumes (all fields little-endian int64; byte fields carry an
    int64 length prefix). ``rounds`` entries are dicts with ``depth``,
    ``live`` (optional; 1 = the sends are supplied at the pause before
    the round, each message's segs then ((2, its index in the round,
    0, nbytes),)), ``streams`` = [(peer_idx, [msg...])] where a send
    msg is (pre, mid, nbytes, nchunks, chunk, segs) and segs are
    (kind, idx, off, len); ``rsrcs`` = [(peer_idx, [recv msg...])]
    where a recv msg is (pool_idx, nbytes, nchunks, chunk, pre, mid).
    Exposed module-level so ``obs --selftest`` compiles a descriptor
    table device-free."""
    out = bytearray()

    def w(v: int) -> None:
        out.extend(struct.pack("<q", int(v)))

    def wb(b: bytes) -> None:
        w(len(b))
        out.extend(b)

    w(_BLOB_MAGIC)
    w(_BLOB_VERSION)
    w(tag)
    w(len(input_lens))
    for n in input_lens:
        w(n)
    off = 0
    offs = []
    for n in pool_sizes:
        offs.append(off)
        off = _align8(off + n)
    w(len(pool_sizes))
    for o, n in zip(offs, pool_sizes):
        w(o)
        w(n)
    w(off)  # pool_total
    w(len(peer_pidx))
    for p in peer_pidx:
        w(p)
    w(len(rounds))
    for rd in rounds:
        w(rd["depth"])
        w(rd.get("live", 0))
        w(len(rd["streams"]))
        for peer_idx, msgs in rd["streams"]:
            w(peer_idx)
            w(len(msgs))
            for pre, mid, nbytes, nchunks, chunk, segs in msgs:
                wb(pre)
                wb(mid)
                w(nbytes)
                w(nchunks)
                w(chunk)
                w(len(segs))
                for kind, idx, so, sl in segs:
                    w(kind)
                    w(idx)
                    w(so)
                    w(sl)
        w(len(rd["rsrcs"]))
        for peer_idx, msgs in rd["rsrcs"]:
            w(peer_idx)
            w(len(msgs))
            for pool_idx, nbytes, nchunks, chunk, pre, mid in msgs:
                w(pool_idx)
                w(nbytes)
                w(nchunks)
                w(chunk)
                wb(pre)
                wb(mid)
    return bytes(out)


class NativePlan:
    """One compiled-and-bound native executor: the C descriptor table
    handle, the fire-time layout (input specs, per-round pool
    placements), the ring/lock bindings, and precomputed pvar totals
    so the MPI_T series never dip when the C path engages."""

    __slots__ = (
        "gen", "px", "cid", "tag", "peers", "arg_idx", "arg_specs",
        "r0_specs", "pool_rounds", "timeout_ms", "ftword", "router",
        "rx_entries", "fire_locks", "send_msgs", "send_bytes",
        "recv_msgs", "recv_bytes", "send_frames", "recv_frames",
        "xfer_total", "pool_count", "pool_total", "live",
    )

    def close(self) -> None:
        # the C side is destroyed with the executor's last reference:
        # here, unless a slab view handed to a schedule still holds it
        self.px = None


def _sentinel_level() -> int:
    try:
        return int(mca_var.get("obs_sentinel", 0) or 0)
    except Exception:
        return 0


def try_compile(state, m, fn: Callable, args: Tuple,
                kw: Optional[Dict]):
    """Lower ``state.plan`` into a bound :class:`NativePlan`, or None
    when anything — cvar off, missing symbols, a non-native peer, an
    unprovable byte map — says the interpreted replay should keep the
    plan. Never raises: ineligibility is a selection outcome."""
    t0 = _time.perf_counter()
    try:
        return _compile(state, m, fn, args, kw or {}, t0)
    except Exception as e:
        if os.environ.get("OMPITPU_PLAN_NATIVE_DEBUG"):
            import traceback
            print(f"[native_exec] withdrew: {e!r}", file=sys.stderr)
            traceback.print_exc()
        return None


def _compile(state, m, fn, args, kw, t0):
    plan = state.plan
    if plan is None or not plan.rounds:
        raise _Ineligible("no frozen plan")
    if not bool(mca_var.get("coll_plan_native", True)):
        raise _Ineligible("coll_plan_native=0")
    if _sentinel_level() >= 2:
        # inline sentinel checking rides ctl frames interleaved with
        # the planned rounds — the C reap would stash them mid-fire
        raise _Ineligible("inline sentinel level >= 2")
    if not available():
        raise _Ineligible("planexec symbols absent")
    router = getattr(m, "router", None)
    nw = getattr(router, "_nw", None)
    if router is None or nw is None:
        raise _Ineligible("no nativewire btl")
    tuning = router.tuning()
    if tuning.arbiter is not None:
        # QoS arbiter owns pacing: stay interpreted
        raise _Ineligible("qos arbiter active")
    comm = state.comm

    # argument regions: every positional array arg is an input region
    arg_idx = []
    arg_specs = []
    for j, a in enumerate(args):
        if hasattr(a, "shape") and hasattr(a, "dtype"):
            spec = _as_np(a)
            nb = int(spec.nbytes)
            if nb <= 0:
                raise _Ineligible("zero-byte array arg")
            arg_idx.append(j)
            arg_specs.append((tuple(spec.shape), str(spec.dtype), nb))
    for v in (kw or {}).values():
        if hasattr(v, "shape") and hasattr(v, "dtype"):
            raise _Ineligible("keyword array args not lowered")
    arg_idx = tuple(arg_idx)

    # capability + structure gate over every round
    send_peers = set()
    recv_srcs = set()
    for rnd in plan.rounds:
        metas = getattr(rnd, "recvs_meta", None)
        if metas is None:
            raise _Ineligible("plan has no arrival metas")
        by_src = dict(metas)
        for src, cnt in rnd.recvs_t:
            lst = by_src.get(src)
            if lst is None or len(lst) != cnt:
                raise _Ineligible("arrival metas disagree with recvs")
            recv_srcs.add(src)
            for shape, dt in lst:
                if _nbytes_of(shape, dt) <= 0:
                    raise _Ineligible("zero-byte receive")
        for (p, arrs), (_p2, tpls) in zip(rnd.sends_meta,
                                          rnd.peer_slots):
            send_peers.add(p)
            if len(arrs) != len(tpls) or any(t is None for t in tpls):
                raise _Ineligible("untemplated send slot")
    peers = tuple(sorted(send_peers | recv_srcs))
    if not peers:
        raise _Ineligible("no wire peers")
    for p in peers:
        if router._btl_for(p) is not nw:
            raise _Ineligible(f"peer {p} not on nativewire")
    # byte-provenance probe (two seeds; identical maps and live
    # rounds required)
    maps, live = _infer_maps(plan, m, fn, args, kw, arg_idx)

    seg = min(tuning.segsize, max(1, nw.max_send_size))
    from ..btl.components import plan_frame_template

    # input regions: args first, then round-0 send arrays in stream
    # order (later rounds may resend round-0 bytes that no arg holds)
    input_lens = [nb for _s, _d, nb in arg_specs]
    n_args = len(arg_specs)
    r0_specs = []
    for p, arrs in plan.rounds[0].sends_meta:
        for shape, dt in arrs:
            nb = _nbytes_of(shape, dt)
            r0_specs.append((p, tuple(shape), dt, nb))
            input_lens.append(nb)

    # pool layout: one buffer per (round, sorted src, message), at
    # the same 8-aligned cumulative offsets build_blob will emit
    pool_sizes: List[int] = []
    pool_round: List[int] = []
    pool_off = 0
    pool_rounds = []  # per round: [(src, [(idx, off, shape, dt, nb)])]
    for i, rnd in enumerate(plan.rounds):
        per_src = []
        for src, metas in sorted(dict(rnd.recvs_meta).items()):
            lst = []
            for shape, dt in metas:
                nb = _nbytes_of(shape, dt)
                lst.append((len(pool_sizes), pool_off, tuple(shape),
                            np.dtype(dt), nb))
                pool_sizes.append(nb)
                pool_round.append(i)
                pool_off = _align8(pool_off + nb)
            per_src.append((src, lst))
        pool_rounds.append(per_src)

    peer_index = {p: i for i, p in enumerate(peers)}
    send_msgs = send_bytes = send_frames = 0
    recv_msgs = recv_bytes = recv_frames = 0
    rounds_desc = []
    for i, rnd in enumerate(plan.rounds):
        streams = []
        flat = 0  # message index within the round, stream order
        r0_base = n_args
        for (p, arrs), (_p2, tpls) in zip(rnd.sends_meta,
                                          rnd.peer_slots):
            msgs = []
            for k, ((shape, dt), tpl) in enumerate(zip(arrs, tpls)):
                nb = _nbytes_of(shape, dt)
                if i == 0:
                    segs = ((0, r0_base + flat, 0, nb),)
                elif i in live:
                    segs = ((2, flat, 0, nb),)
                else:
                    segs = maps[i][flat]
                    tot = 0
                    for kind, idx, _so, sl in segs:
                        tot += sl
                        if kind == 1 and pool_round[idx] >= i:
                            # provenance from a not-yet-filled pool
                            # buffer can only be coincidence
                            raise _ProbeFail("acausal provenance")
                    if tot != nb:
                        raise _ProbeFail("map does not cover payload")
                msgs.append((tpl.pre, tpl.mid, nb, int(tpl.nchunks),
                             int(tpl.chunk), segs))
                send_msgs += 1
                send_bytes += nb
                send_frames += int(tpl.nchunks) + 1
                flat += 1
            streams.append((peer_index[p], msgs))
        rsrcs = []
        for src, lst in pool_rounds[i]:
            msgs = []
            for pool_idx, _off, shape, dt, nb in lst:
                tpl = plan_frame_template(shape, dt, seg)
                msgs.append((pool_idx, nb, int(tpl.nchunks),
                             int(tpl.chunk), tpl.pre, tpl.mid))
                recv_msgs += 1
                recv_bytes += nb
                recv_frames += int(tpl.nchunks) + 1
            rsrcs.append((peer_index[src], msgs))
        rounds_desc.append({"depth": int(rnd.depth),
                            "live": int(i in live),
                            "streams": streams, "rsrcs": rsrcs})

    blob = build_blob(plan.rounds[0].tag, input_lens, pool_sizes,
                      peers, rounds_desc)
    from ..native import bindings as _b
    px = _b.PlanExec(blob)

    # bind the live endpoint + ring handles once (rings exist after
    # the recording fire; a missing tx ring means the socket leg)
    handles = nw.plan_endpoints(plan.rounds[0].tag,
                                sorted(send_peers),
                                sorted(recv_srcs))
    tx_h, rx_h, rx_entries, fire_locks = [], [], {}, []
    for p in peers:
        tx, rx = handles[p]
        tx_h.append(tx[0]._h if tx is not None else None)
        rx_h.append(rx[0]._h if rx is not None else None)
        if tx is not None:
            fire_locks.append((p, 0, tx[1]))
        if rx is not None:
            fire_locks.append((p, 1, rx[1]))
            rx_entries[p] = rx
    import ctypes
    word = (ctypes.c_int64 * 1)(0)
    px.bind(router.ep._h, router._nid(m.my_pidx),
            [router._nid(p) for p in peers], tx_h, rx_h)
    px.set_ftword(word)

    npl = NativePlan()
    npl.gen = plan.gen
    npl.px = px
    npl.cid = comm.cid
    npl.tag = plan.rounds[0].tag
    npl.peers = peers
    npl.arg_idx = arg_idx
    npl.arg_specs = tuple(arg_specs)
    npl.r0_specs = tuple(r0_specs)
    npl.pool_rounds = pool_rounds
    npl.timeout_ms = plan.timeout_ms
    npl.ftword = word
    npl.router = router
    npl.rx_entries = rx_entries
    npl.fire_locks = sorted(fire_locks, key=lambda e: (e[0], e[1]))
    npl.send_msgs = send_msgs
    npl.send_bytes = send_bytes
    npl.recv_msgs = recv_msgs
    npl.recv_bytes = recv_bytes
    npl.send_frames = send_frames
    npl.recv_frames = recv_frames
    npl.xfer_total = max(1, send_msgs)
    npl.pool_count = len(pool_sizes)
    npl.pool_total = px.pool_total
    npl.live = live
    _pool_bytes.add(npl.pool_total)
    if _obs.enabled:
        _obs.record("plan_native_compile", "plan", t0,
                    _time.perf_counter() - t0, comm_id=comm.cid)
    return npl


# ---------------------------------------------------------------------------
# fire: the per-replay exchange adapter
# ---------------------------------------------------------------------------

class NativeXchg:
    """Exchange adapter that fires the WHOLE plan C-side, in one
    segment per live round: round-0 sends come verbatim from the
    arrays the schedule just passed and the walk runs on — composing
    mapped rounds from the proven byte-provenance maps, reassembling
    receives into the plan pool — until it stands before a live round
    or at the end; the exchange of a live round supplies that round's
    sends and lets it run on again. Every other exchange only verifies
    structure. Each hands back what arrived in its round: read-only
    views of the pool where ``views`` says the schedule reads them
    once and keeps none (:data:`VIEW_OPS`), else copies.

    One call is one fire: the channel, ring and rx-entry locks are
    taken before the first segment and released after the last (or by
    :meth:`close`, which ``SpanningPlanState.run`` calls whatever the
    schedule body did), so between two segments nobody pops this
    channel's frames. A safety veto before the first segment (stashed
    frames, lock contention) delegates the entire call to a fresh
    :class:`~.plan.PlannedXchg` — same plan, same bytes; once bytes
    have moved a failure is an error."""

    __slots__ = ("m", "plan", "np", "i", "ts", "args", "seq", "views",
                 "_delegate", "_pool", "_c_wait", "_px", "_held")

    def __init__(self, module, plan, npl: NativePlan,
                 args: Tuple, seq: int = 0, views: bool = False) -> None:
        self.m = module
        self.plan = plan
        self.np = npl
        self.i = 0
        self.views = views
        #: the schedule's posting seq: joins this fire's spans to its
        #: ``ompi.nbc.wait``
        self.seq = seq
        self.ts: Optional[List[float]] = None
        self.args = args
        self._delegate = None
        self._pool = None
        #: seconds spent blocked in the C slice loop during the last
        #: exchange — wire-transport time, subtracted from the
        #: orchestration self-report (the ctypes entry/exit and pool
        #: reads are Python orchestration; the descriptor walk isn't)
        self._c_wait = 0.0
        #: the executor of the OPEN fire (None before the first
        #: segment and after the last) and the locks held for it
        self._px = None
        self._held: List[threading.Lock] = []

    def _mismatch(self, detail: str) -> MPIError:
        return MPIError(
            ErrorCode.ERR_INTERN,
            f"compiled schedule plan diverged mid-run on "
            f"{self.m.comm.name} (round {self.i}): {detail}. The "
            "schedule no longer matches its frozen plan — rebuild "
            "the persistent request (or re-issue the collective) "
            "after changing schedule-selection cvars",
        )

    def exchange(self, sends: Dict[int, list],
                 recvs: Dict[int, int]) -> Dict[int, list]:
        if self._delegate is not None:
            return self._delegate.exchange(sends, recvs)
        t0 = _time.perf_counter()
        self._c_wait = 0.0
        try:
            return self._exchange(sends, recvs)
        finally:
            if self._delegate is None:
                # a fire that fell back mid-call accounted itself
                # through the delegate's PlannedXchg.exchange
                from . import driver as _driver
                _driver.orch_add(
                    _time.perf_counter() - t0 - self._c_wait)

    def _exchange(self, sends: Dict[int, list],
                  recvs: Dict[int, int]) -> Dict[int, list]:
        plan = self.plan
        if self.i >= len(plan.rounds):
            raise self._mismatch("more rounds than the plan recorded")
        rnd = plan.rounds[self.i]
        sends_f = {p: [_as_np(a) for a in arrs]
                   for p, arrs in sends.items() if arrs}
        meta = tuple(
            (p, tuple((a.shape, str(a.dtype)) for a in sends_f[p]))
            for p in sorted(sends_f))
        rl = {int(p): int(c) for p, c in recvs.items() if int(c) > 0}
        if meta != rnd.sends_meta or rl != rnd.recvs:
            raise self._mismatch(
                f"sends/recvs {meta}/{rl} != frozen "
                f"{rnd.sends_meta}/{rnd.recvs}")
        if self.i == 0:
            if not self._begin(sends_f):
                _native_fallbacks.add()
                from .plan import PlannedXchg
                dg = PlannedXchg(self.m, plan, self.seq)
                dg.ts = self.ts
                self._delegate = dg
                return dg.exchange(sends, recvs)
            self._segment()
        elif self.i in self.np.live:
            self._supply(sends_f)
            self._segment()
        got = self._arrivals(self.i)
        self.i += 1
        return got

    # -- fire-time plumbing ------------------------------------------------
    def _contig(self, a: np.ndarray) -> np.ndarray:
        if a.flags.c_contiguous:
            return a
        from ..btl.nativewire import _fallback_copies
        _fallback_copies.add()
        return np.ascontiguousarray(a)

    def _stream(self, sends_f) -> List[np.ndarray]:
        """One round's send arrays in the blob's stream order."""
        return [self._contig(a) for p in sorted(sends_f)
                for a in sends_f[p]]

    def _inputs(self, sends_f) -> Optional[List[np.ndarray]]:
        npl = self.np
        out = []
        for j, (shape, dt, _nb) in zip(npl.arg_idx, npl.arg_specs):
            a = self._contig(_as_np(self.args[j]))
            if tuple(a.shape) != shape or str(a.dtype) != dt:
                return None
            out.append(a)
        flat = self._stream(sends_f)
        if len(flat) != len(npl.r0_specs):
            return None
        return out + flat

    def _clean_channel(self) -> bool:
        """True when no stashed/early frame could race the C reap."""
        npl = self.np
        router = npl.router
        cid = npl.cid
        with router._coll_early_lock:
            for (c, _src), q in router._coll_early.items():
                if c == cid and q:
                    return False
        from ..btl.components import _ep_stash
        stash, lock = _ep_stash(router.ep)
        with lock:
            for p in npl.peers:
                if stash.get((router._nid(p), npl.tag)):
                    return False
        return True

    def _check_ft(self) -> None:
        from ..runtime.wire import _ft
        npl = self.np
        _ft().check_wait(npl.cid, npl.peers, "native plan fire",
                         epoch0=getattr(self.m.comm, "_ft_epoch0", 0))

    def _begin(self, sends_f) -> bool:
        """Take the call's locks and arm the fire. False = a veto,
        nothing held, no byte moved: the call replays in Python."""
        npl = self.np
        router = npl.router
        inputs = self._inputs(sends_f)
        if inputs is None:
            return False
        chan = router._chan_lock("collrx", npl.cid)
        if not chan.acquire(blocking=False):
            return False
        held = self._held = [chan]
        armed = False
        try:
            for _p, _kind, lk in npl.fire_locks:
                if not lk.acquire(blocking=False):
                    return False
                held.append(lk)
            if not self._clean_channel():
                return False
            for _src, (_ring, _lk, rstash) in npl.rx_entries.items():
                if rstash.get(npl.tag):
                    return False
            self._check_ft()
            from ..btl import components as _btlc
            base = next(_btlc._xfer_ids)
            for _ in range(npl.xfer_total - 1):
                next(_btlc._xfer_ids)
            npl.ftword[0] = 0
            px = npl.px
            if px.fire_begin(inputs, base, npl.timeout_ms) != 0:
                return False
            self._px = px
            self._pool = px.pool_view()
            armed = True
            return True
        finally:
            if not armed:
                self._release()

    def _supply(self, sends_f) -> None:
        """The sends of the live round the walk stands before."""
        px = self._px
        self._check_ft()
        if px is None or px.fire_supply(self._stream(sends_f)) != 0:
            raise self._mismatch(
                "the native walk does not stand before a live round "
                "of these sends")
        _native_live_rounds.add()

    def _segment(self) -> None:
        """Walk from where the fire stands to the next live round or
        to the end; the end harvests, drains and releases (a failure
        leaves that to :meth:`close`). Every veto withdrew before a
        byte moved, so the span is the C walk alone (one per segment)
        and closes before a delegate's ``ompi.plan.xchg`` could open."""
        npl, px = self.np, self._px
        with _obs.span(_spans.PLAN_NATIVE_FIRE,
                       journal=("plan_native_fire", "plan"),
                       cid=npl.cid, seq=self.seq):
            done = self._run(px, npl)
            if done:
                self._harvest(px, npl)
        if done:
            self.close()

    def _release(self) -> None:
        held, self._held = self._held, []
        for lk in reversed(held):
            lk.release()

    def close(self) -> None:
        """End the open fire, finished or not: hand the frames the
        reap set aside back to the Python stashes (the rx entry locks
        are still held: the restash needs them), let go of the
        caller's arrays, release the call's locks. Idempotent, and
        ``SpanningPlanState.run`` calls it in its ``finally``: a
        segment that fails, or a schedule body that raises between
        two, leaks nothing."""
        px, self._px = self._px, None
        if px is None:
            return
        try:
            self._drain_stash(px)
            px.fire_end()
        finally:
            self._release()

    def _run(self, px, npl: NativePlan) -> bool:
        """One segment in C. True = the plan's last round is done,
        False = paused before a live round."""
        from ..obs import watchdog as _watchdog
        tok = None
        if _watchdog.enabled:
            tok = _watchdog.arm(
                "native_plan_fire", comm_id=npl.cid,
                info=lambda n=npl: {"peers": list(n.peers),
                                    "rounds": len(n.pool_rounds)})
        t_w = _time.perf_counter()
        try:
            while True:
                rc = px.fire_step(_SLICE_MS)
                if rc == px.RC_DONE:
                    return True
                if rc == px.RC_PAUSE:
                    return False
                if rc in (px.RC_AGAIN, px.RC_FTSTOP):
                    # the detection interval: mirror FtState into the
                    # fault word, surface death/revocation typed
                    try:
                        self._check_ft()
                    except MPIError:
                        npl.ftword[0] = 1
                        raise
                    continue
                self._raise_rc(px, npl, rc)
        finally:
            self._c_wait = _time.perf_counter() - t_w
            if tok is not None:
                _watchdog.disarm(tok)

    def _raise_rc(self, px, npl: NativePlan, rc: int) -> None:
        if rc == px.RC_PEERDEAD:
            pidx = px.err_peer()  # the C side stores the pidx
            raise MPIError(
                ErrorCode.ERR_PROC_FAILED,
                f"native plan fire on {self.m.comm.name} depends on "
                f"process {pidx}, which the wire reports dead "
                f"(round {px.err_round()})",
            )
        if rc == px.RC_TIMEOUT:
            raise MPIError(
                ErrorCode.ERR_PENDING,
                f"native plan fire on {self.m.comm.name} timed out "
                f"after {npl.timeout_ms} ms (round {px.err_round()})",
            )
        if rc == px.RC_DIVERGED:
            raise self._mismatch(
                "an inbound header did not match the frozen frame "
                "template (peer re-planned or cvars differ across "
                "ranks)")
        if rc == px.RC_TRUNCATED:
            raise MPIError(
                ErrorCode.ERR_TRUNCATE,
                "native plan fire: reassembled payload failed its "
                f"CRC (round {px.err_round()})",
            )
        raise MPIError(ErrorCode.ERR_INTERN,
                       f"native plan executor returned rc {rc}")

    def _drain_stash(self, px) -> None:
        """Re-inject frames the C reap popped but does not own into
        the shared Python stashes (kind 0 = endpoint frame, kind 1 =
        ring record) — the portable consumers find them exactly where
        the interpreted path would have stashed them."""
        npl = self.np
        try:
            entries = px.drain_stash()
        except Exception:
            return
        if not entries:
            return
        from ..btl.components import _ep_stash
        from ..btl.nativewire import _fallback_copies
        router = npl.router
        for kind, pidx, tag, raw in entries:
            if kind == 1 and pidx in npl.rx_entries:
                _ring, _lk, rstash = npl.rx_entries[pidx]
                # caller already holds the rx entry lock
                rstash.setdefault(tag, []).append(raw)
                _fallback_copies.add()  # the one restash copy
            else:
                stash, lock = _ep_stash(router.ep)
                with lock:
                    stash.setdefault((router._nid(pidx), tag),
                                     []).append(raw)

    def _harvest(self, px, npl: NativePlan) -> None:
        if self.ts is not None:
            self.ts[:] = px.round_ts()
        # pvar continuity: the C fire IS these sends/recvs — MPI_T
        # series must not dip when the native executor engages.
        # Frame counts mirror the interpreted path exactly: chunk
        # pvars count fragments (not headers), _native_frames counts
        # send fragments.
        from . import hier as _hier
        _hier._inter_msgs_sent.add(npl.send_msgs)
        _hier._inter_bytes.add(npl.send_bytes)
        _hier._inter_msgs_recvd.add(npl.recv_msgs)
        from ..btl import nativewire as _nw
        _nw._native_bytes.add(npl.send_bytes + npl.recv_bytes)
        _nw._native_frames.add(npl.send_frames - npl.send_msgs)
        _nw._zero_copy_strict.add(npl.send_bytes + npl.recv_bytes)
        btl = npl.router._nw
        if btl is not None:
            btl.staged_chunks_pvar.add(
                (npl.send_frames - npl.send_msgs)
                + (npl.recv_frames - npl.recv_msgs))
            btl.staged_bytes_pvar.add(npl.send_bytes + npl.recv_bytes)
        _pool_hits.add(npl.pool_count)
        _native_ring_yields.add(px.ring_yields())
        _native_fires.add()

    def _arrivals(self, r: int) -> Dict[int, list]:
        """Round ``r``'s arrivals, per source in message order, with
        the frozen plan's shapes and dtypes: ``ompi.plan.arrivals``,
        whose ``bytes`` are what it copied out of the slab (0 where the
        schedule takes views)."""
        npl = self.np
        pool = self._pool
        got: Dict[int, list] = {}
        copied = 0
        with _obs.span(_spans.PLAN_ARRIVALS, cid=npl.cid,
                       seq=self.seq) as sp:
            for src, lst in npl.pool_rounds[r]:
                arrs = []
                for _pool_idx, off, shape, dt, nb in lst:
                    a = pool[off:off + nb].view(dt).reshape(shape)
                    if not self.views:
                        a = a.copy()
                        copied += nb
                    arrs.append(a)
                got[src] = arrs
            sp.set_metadata(bytes=copied)
        if copied:
            _pool_copy_bytes.add(copied)
        return got
