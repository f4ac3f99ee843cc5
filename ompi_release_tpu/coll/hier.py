"""coll/hier — collectives for communicators that SPAN controller
processes (the unified COMM_WORLD of ``tpurun -n P``).

Two-level compose, the ``coll/ml`` shape (``ompi/mca/coll/ml`` with
bcol/sbgp subgrouping) re-cast for the TPU runtime:

  intra  this process's members: ONE compiled XLA collective over the
         local submesh (a shadow communicator reuses the whole normal
         coll stack — xla/tuned selection, persistent programs);
  inter  the process-combine step over the wire router — shm segment
         handoffs on one host, chunked DCN staging across hosts
         (``runtime/wire.py``), never a fake device_put.

Driver-mode contract on a spanning communicator: buffers carry one
leading-axis slice per LOCAL member (this process's members of the
comm, in comm-rank order) — the per-process shard of the single-
controller convention. Results keep that local leading axis;
"identical on every rank" results are replicated across it.

Reduction order: local partials use the selected local algorithm's
order; the inter step combines partials in process-index order — the
same fixed-order tree discipline the parity harness pins for the
in-process algorithms.

The inter step is SCHEDULED (:mod:`coll.hier_schedules`): recursive
doubling for small allreduce, ring/Rabenseifner reduce-scatter +
allgather for large allreduce (~2n inter bytes per process instead of
(P-1)*n), binomial trees for bcast/reduce/gather/scatter, Bruck for
small allgather/alltoall with pairwise exchange above the cutoff, and
a ``linear`` all-pairs exchange kept as the baseline (and for the
ragged v-variants, whose sizes are not globally derivable). Selection
follows the tuned precedence — ``hier_inter_algorithm`` forcing >
``hier_<coll>`` dynamic rules (PR-2 machinery; min_comm_size matches
the PROCESS count) > fixed decision constants — and every schedule
combines in a fixed, process-index-derived order identical across
ranks and runs, falling back to exact-order schedules for
non-commutative ops. A host-aware LEADER TIER (``hier_leader_tier``,
the coll/ml subgrouping shape) activates when the job spans hosts:
co-hosted processes combine/fan out over shm handoffs first and one
leader per host crosses DCN. The pvars ``hier_inter_bytes`` /
``hier_inter_msgs_sent`` / ``hier_inter_msgs_recvd`` count exactly
what crossed a process boundary so both the two-level byte reduction
and the O(P^2) -> O(log P) message-count claim are auditable.

Exchange overlap (``wire_overlap_exchange``, default on): every round
posts ALL its sends first — striped across peers in pipelined fragment
bursts by ``WireRouter.coll_send_all`` — then reaps receives in
ARRIVAL order (``coll_recv_any``), so one slow peer no longer blocks
the reap of peers whose data already landed, the failure mode of the
old fixed-process-order ``self._recv(p)`` loops. Per-peer FIFO order
still holds (the OOB guarantees it), so multi-message rounds keep
their member ordering.
"""

from __future__ import annotations

import functools
import math
import time as _time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs as _obs
from ..mca import component as mca_component
from ..mca import pvar
from ..mca import var as mca_var
from ..obs import spans as _spans
from ..obs import watchdog as _watchdog
from ..ops.op import Op
from ..utils import output
from ..runtime import progress as _progress
from ..utils.errors import ErrorCode, MPIError
from . import hier_schedules as _hs
from . import topo_schedules as _topo

_log = output.stream("coll")

_inter_bytes = pvar.counter(
    "hier_inter_bytes",
    "bytes crossing a controller-process boundary in hier collectives "
    "(SENT side)",
)
_inter_msgs_sent = pvar.counter(
    "hier_inter_msgs_sent",
    "inter-process messages SENT by hier collectives",
)
_inter_msgs_recvd = pvar.counter(
    "hier_inter_msgs_recvd",
    "inter-process messages RECEIVED by hier collectives",
)
_leader_combines = pvar.counter(
    "hier_leader_combines",
    "host-leader-tier combines performed by spanning collectives",
)

_assembled = pvar.counter(
    "hier_assembled_results",
    "results of bcast/allgather/gather/alltoall on a spanning comm "
    "built in one pass from the rank's own buffer and the arrivals "
    "(_HierModule._assemble)",
)
_assembled_bytes = pvar.counter(
    "hier_assembled_bytes", "bytes of those results")

_rsb_halved = pvar.counter(
    "hier_reduce_scatter_halved",
    "reduce_scatter_block calls on a spanning comm that ran only the "
    "recursive-halving half of Rabenseifner and placed only the "
    "rank's own blocks (_HierModule._reduce_scatter_halved)",
)

#: current spanning-collective round per comm cid, maintained only
#: while obs is enabled: {"op", "round", "awaiting_procs",
#: "awaiting_ranks"}. THE answer to "the job is stuck — who is waiting
#: in what?": the flight recorder dumps this table verbatim.
_round_state: Dict[int, Dict] = {}


def _hier_rounds_snapshot() -> Dict[str, Dict]:
    return {str(cid): dict(st) for cid, st in list(_round_state.items())}


_watchdog.add_contributor("hier_rounds", _hier_rounds_snapshot)


def _d2h(x) -> np.ndarray:
    """``np.asarray(x)``; when ``x`` is a device buffer the fetch is an
    ``ompi.hier.d2h`` span. A value already on the host passes through
    unmarked, so every conversion in this module can come here."""
    if not isinstance(x, jax.Array):
        return np.asarray(x)
    with _obs.span(_spans.HIER_D2H, bytes=_spans.nbytes(x)):
        return np.asarray(x)


def _h2d(v):
    """``jnp.asarray(v)``; when ``v`` is a host value the placement is
    an ``ompi.hier.h2d`` span (until ``jnp.asarray`` returns: the
    transfer itself may still be in flight). A device array passes
    through unmarked."""
    if isinstance(v, jax.Array):
        return jnp.asarray(v)
    with _obs.span(_spans.HIER_H2D, bytes=_spans.nbytes(v)):
        return jnp.asarray(v)


def _aligned_empty(shape: Tuple[int, ...], dtype) -> np.ndarray:
    """``np.empty`` on a 64-byte boundary: the CPU backend keeps such
    a buffer as the array's own instead of copying it."""
    dtype = np.dtype(dtype)
    nbytes = math.prod(shape) * dtype.itemsize
    raw = np.empty(nbytes + 64, np.uint8)
    start = -raw.ctypes.data % 64
    return raw[start:start + nbytes].view(dtype).reshape(shape)


@functools.lru_cache(maxsize=512)
def _assemble_program(pieces: Tuple, local_n: int,
                      shape: Tuple[int, ...]) -> Callable:
    """The join of :meth:`_HierModule._assemble` as ONE device program
    over the blocks (jit keeps one executable per block shapes and
    dtype under it). Everything is cut and joined flat: a slice of a
    buffer with leading axes of 1 compiles, for the chip, to several
    megabytes of code in as many seconds."""
    width = math.prod(shape) // local_n

    def program(*blocks):
        flat = [b.reshape(-1) for b in blocks]

        def row(member):
            cuts = [flat[i][start:start + count]
                    for to, i, start, count in pieces if to == member]
            return jnp.concatenate(cuts) if cuts \
                else jnp.zeros((width,), flat[0].dtype)

        if pieces[0][0] is None:
            out = jnp.broadcast_to(row(None)[None], (local_n, width))
        else:
            out = jnp.stack([row(member) for member in range(local_n)])
        return out.reshape(shape)

    return jax.jit(program)


class _XchgAdapter:
    """The round transport :mod:`coll.hier_schedules` drives: one call
    posts ALL of a schedule round's sends (striped/pipelined by
    ``coll_send_all`` under ``wire_overlap_exchange``), then reaps the
    round's receives in arrival order. Every byte flows through the
    module's instrumented ``_send/_send_all/_recv/_reap`` touchpoints,
    so pvar accounting, ``(cid, round, pair, k)`` flow ids, and the
    watchdog wait registry (``awaiting_info`` names exactly the
    tree/ring neighbors still pending) are identical to the linear
    path's — the PR-4 observability contract survives every schedule."""

    __slots__ = ("m",)

    def __init__(self, module: "_HierModule") -> None:
        self.m = module

    def exchange(self, sends: Dict[int, list],
                 recvs: Dict[int, int]) -> Dict[int, list]:
        m = self.m
        sends = {p: [np.asarray(a) for a in arrs]
                 for p, arrs in sends.items() if arrs}
        recvs = {p: int(c) for p, c in recvs.items() if c > 0}
        got: Dict[int, list] = {p: [] for p in recvs}
        with _obs.span(_spans.PLAN_XCHG, cid=m.comm.cid,
                       seq=_progress.executing_seq(),
                       bytes=sum(a.nbytes for arrs in sends.values()
                                 for a in arrs)):
            if m._overlap():
                if sends:
                    m._send_all(sends)
                if recvs:
                    m._reap(dict(recvs),
                            lambda src, arr: got[src].append(arr))
            else:
                for p in sorted(sends):
                    for a in sends[p]:
                        m._send(p, a)
                for p in sorted(recvs):
                    for _ in range(recvs[p]):
                        got[p].append(m._recv(p))
        return got


class _HierModule:
    """Two-level collectives over (process, local-member) subgroups."""

    def __init__(self, comm) -> None:
        from ..comm.communicator import Communicator
        from ..comm.group import Group

        self.comm = comm
        rt = comm.runtime
        from ..runtime.wire import proc_topology

        t = proc_topology(comm)  # the one shared layout derivation
        self.router = t.router
        self.my_pidx = t.my_pidx
        self.owner = t.owner
        self.procs = t.procs
        self.members_of = t.members_of
        self.local_ranks = t.local_ranks
        self.local_n = t.local_n
        # shadow communicator over the LOCAL members: the intra level,
        # with the full normal coll stack (the bcol analogue).
        # internal=True: shadow creation happens only on processes with
        # local members, so it must not consume a global cid — that
        # counter has to stay SPMD-synchronized for wire addressing
        self.shadow = Communicator(
            rt, Group([comm.group.world_rank(i) for i in self.local_ranks]),
            name=f"{comm.name}.local", internal=True,
        )
        # the shadow lives exactly as long as its owner: freeing the
        # spanning comm frees it (no registry leak per create/free)
        comm._on_free = tuple(getattr(comm, "_on_free", ())) + (
            self.shadow.free,
        )
        # trace context (maintained only while obs is on): a
        # process-synchronized round counter plus per-(src, dst) message
        # indices within the round. Both sides of every inter-process
        # message derive the SAME flow id from (cid, round, pair, k) —
        # collective call order is identical on every process (MPI's
        # own rule) and per-peer FIFO keeps k aligned, so journals join
        # into flow arrows with no wire-format change. Requires obs
        # enabled on every rank (same MCA env under tpurun).
        self._round = 0
        self._flow_k: Dict[tuple, int] = {}
        # host-aware leader tier (the coll/ml sbgp shape): group the
        # participating processes by the SAME modex-card host identity
        # the router's transport choice consults (_btl_for), so the
        # leader fan-in/fan-out stages ride shm exactly when the
        # transports do. Leader = lowest process index on the host.
        cards = self.router.cards
        self.host_of: Dict[int, str] = {
            p: str(cards[p].get("host") or f"proc-{p}")
            for p in self.procs
        }
        self.host_groups: Dict[str, List[int]] = {}
        for p in self.procs:
            self.host_groups.setdefault(self.host_of[p], []).append(p)
        self.leader_of: Dict[int, int] = {
            p: min(self.host_groups[self.host_of[p]]) for p in self.procs
        }
        self.leaders: List[int] = sorted(
            min(g) for g in self.host_groups.values())
        # uniform (d0, d1) host grid, if one exists: what the fixed
        # decision's torus pick and the topo schedules key off
        self.torus_dims = _topo.grid_dims(self.procs, self.host_of)
        # publish the topology fingerprint the tuning database selects
        # rule files by — (hosts, procs-per-host, link classes, P).
        # force=False: the WIDEST comm (the world) owns the global
        # selection; a narrower subcomm must not displace it
        from ..tuning import db as _tuning_db

        _tuning_db.set_active(
            _tuning_db.fingerprint_for(self.host_of, len(self.procs)),
            force=False)
        # where a result is joined (_assemble): a host rank's "device"
        # is the CPU backend, whose arrays live in host memory
        devs = getattr(comm.submesh, "devices", None)
        self._host_join = (devs.flat[0].platform if devs is not None
                           else jax.default_backend()) == "cpu"
        #: comm rank r's row of a gathered result: (its owner's block
        #: as _assemble numbers them — 0 this process's, then the
        #: peers' — and its row in that block)
        self._rows = tuple(
            (0 if self.owner[r] == self.my_pidx
             else 1 + self.peers.index(self.owner[r]),
             self.members_of[self.owner[r]].index(r))
            for r in range(comm.size))
        #: every process owns an equal run of consecutive comm ranks,
        #: the processes in rank order: chunk j of a flat buffer split
        #: into P is then exactly process j's ranks' blocks, in order
        k = comm.size // len(self.procs)
        self._ranks_chunked = all(
            self.members_of[p] == list(range(j * k, (j + 1) * k))
            for j, p in enumerate(self.procs))
        self._xchg = _XchgAdapter(self)
        # handle for coll/plan's frozen-schedule record/replay: the
        # plan layer swaps _xchg for the duration of ONE schedule run
        # (ops on a comm are engine-serialized, so the swap is
        # race-free) — it needs the module, which only closures hold
        comm._hier_module = self

    # -- plumbing ----------------------------------------------------------
    @property
    def peers(self) -> List[int]:
        return [p for p in self.procs if p != self.my_pidx]

    @staticmethod
    def _overlap() -> bool:
        return bool(mca_var.get("wire_overlap_exchange", True))

    # -- trace context / round bookkeeping ---------------------------------
    def _flow(self, src_p: int, dst_p: int) -> int:
        """Flow id of the NEXT message src_p -> dst_p this round (call
        only under an ``_obs.enabled`` gate: the k counters must
        advance in lockstep on both sides)."""
        key = (src_p, dst_p)
        k = self._flow_k.get(key, 0)
        self._flow_k[key] = k + 1
        return _obs.flow_id("hier", self.comm.cid, self._round,
                            src_p, dst_p, k)

    def _round_begin(self, name: str) -> float:
        self._round += 1
        self._flow_k = {}
        _round_state[self.comm.cid] = {
            "op": name, "round": self._round, "comm": self.comm.name,
            "awaiting_procs": [], "awaiting_ranks": [],
        }
        return _time.perf_counter()

    def _round_end(self, name: str, t0: float) -> None:
        _round_state.pop(self.comm.cid, None)
        if _obs.enabled:
            _obs.record(name, "coll", t0, _time.perf_counter() - t0,
                        comm_id=self.comm.cid)

    def _awaiting_info(self, pending: Dict[int, int]) -> Callable:
        """Watchdog info resolver: who has NOT arrived, as processes
        AND world ranks — resolved at dump time so it reflects
        arrivals since arming, and mirrored into the round-state table
        the flight recorder dumps."""

        def resolve() -> Dict[str, list]:
            procs = sorted(p for p, c in pending.items() if c > 0)
            ranks = sorted(
                self.comm.group.world_rank(i)
                for p in procs for i in self.members_of.get(p, ())
            )
            st = _round_state.get(self.comm.cid)
            if st is not None:
                st["awaiting_procs"] = procs
                st["awaiting_ranks"] = ranks
            return {"awaiting_procs": procs, "awaiting_ranks": ranks}

        return resolve

    def _stalled_op(self) -> str:
        st = _round_state.get(self.comm.cid)
        return st["op"] if st else "hier"

    # -- transport touchpoints ---------------------------------------------
    def _send(self, peer: int, arr) -> None:
        arr = np.asarray(arr)
        rec = _obs.enabled  # capture once: flag may flip mid-send
        t0 = _time.perf_counter() if rec else 0.0
        self.router.coll_send(self.comm, peer, arr)
        _inter_msgs_sent.add()
        _inter_bytes.add(int(arr.nbytes))
        if rec and _obs.enabled:
            _obs.record("hier_send", "hier", t0,
                        _time.perf_counter() - t0,
                        nbytes=int(arr.nbytes), peer=peer,
                        comm_id=self.comm.cid,
                        flow=self._flow(self.my_pidx, peer),
                        flow_side="s")

    def _recv(self, peer: int):
        rec = _obs.enabled
        t0 = _time.perf_counter() if rec else 0.0
        tok = None
        if _watchdog.enabled:
            tok = _watchdog.arm(self._stalled_op(),
                                comm_id=self.comm.cid, peer=peer,
                                info=self._awaiting_info({peer: 1}))
        try:
            # the native wire hands arrivals over as device arrays
            out = _d2h(self.router.coll_recv(self.comm, peer))
        finally:
            if tok is not None:
                _watchdog.disarm(tok)
        _inter_msgs_recvd.add()
        if rec and _obs.enabled:
            _obs.record("hier_recv", "hier", t0,
                        _time.perf_counter() - t0,
                        nbytes=int(out.nbytes), peer=peer,
                        comm_id=self.comm.cid,
                        flow=self._flow(peer, self.my_pidx),
                        flow_side="t")
        return out

    def _send_all(self, sends: Dict[int, list]) -> None:
        """Post one round's sends to every peer, striped across
        destinations in pipelined fragment bursts (same pvar
        accounting as per-peer :meth:`_send`)."""
        rec = _obs.enabled
        t0 = _time.perf_counter() if rec else 0.0
        self.router.coll_send_all(self.comm, sends)
        dt = (_time.perf_counter() - t0) if rec else 0.0
        if rec and _obs.enabled:
            # the burst's duration lives on ONE aggregate span; the
            # per-message producer spans below are INSTANTS at the
            # burst start — coll_send_all stripes internally, so no
            # per-message completion time exists, and stamping every
            # message with the burst-end time would put flow-arrow
            # origins AFTER receivers consumed the early fragments
            # (negative latencies in the merged trace). The post time
            # is the causally safe bound.
            _obs.record("hier_send_all", "hier", t0, dt,
                        nbytes=sum(int(a.nbytes) for arrs in
                                   sends.values() for a in arrs),
                        comm_id=self.comm.cid)
        for p, arrs in sends.items():
            for a in arrs:
                _inter_msgs_sent.add()
                _inter_bytes.add(int(a.nbytes))
                if rec and _obs.enabled:
                    # one producer span per message: k advances in list
                    # order, the same order coll_send_all puts each
                    # peer's messages on its FIFO
                    _obs.record("hier_send", "hier", t0, 0.0,
                                nbytes=int(a.nbytes), peer=p,
                                comm_id=self.comm.cid,
                                flow=self._flow(self.my_pidx, p),
                                flow_side="s")

    def _send_all_planned(self, rnd, sends: Dict[int, list]) -> None:
        """Steady-state planned round send (coll/plan frozen
        schedules): channel tag, striping depth, and per-message frame
        headers were precomposed at plan time, so this path is ONE
        ULFM check + memoryview slicing behind precomposed header
        bytes. Inter-process pvar accounting matches :meth:`_send_all`
        exactly; per-message spans are NOT journaled here — observed
        replays append one fixed-size record per fire to the obs
        ledger, and tpu-doctor expands it against the frozen plan
        structure into the same flow-id spans the interpreted path
        emits."""
        self.router.coll_send_planned(self.comm, rnd, sends)
        for arrs in sends.values():
            for a in arrs:
                _inter_msgs_sent.add()
                _inter_bytes.add(int(a.nbytes))

    def _reap(self, pending: Dict[int, int],
              on_arrival: Callable[[int, np.ndarray], None],
              timeout_ms: Optional[int] = None,
              record: bool = True) -> None:
        """Reap ``pending[p]`` messages per peer in ARRIVAL order —
        a slow peer never blocks the reap of one whose data already
        landed (the posted-sends overlap the module docstring pins).
        ``timeout_ms``: explicit wait bound (frozen-plan replays pass
        their plan-time snapshot); None = the live cvar.
        ``record=False`` (frozen-plan replays): skip per-arrival span
        emission and the flow-k advance — the obs ledger's expansion
        re-derives both from the frozen plan structure, and journal
        spans here would double them."""
        left = sum(pending.values())
        tok = None
        if _watchdog.enabled:
            tok = _watchdog.arm(self._stalled_op(),
                                comm_id=self.comm.cid,
                                info=self._awaiting_info(pending))
        try:
            while left:
                rec = record and _obs.enabled
                t0 = _time.perf_counter() if rec else 0.0
                src, arr = self.router.coll_recv_any(self.comm, pending,
                                                     timeout_ms)
                if tok is not None:
                    # progress resets the stall clock (and re-arms a
                    # wait that already dumped): a slow but ARRIVING
                    # round is not a stall, and false dumps would burn
                    # the MAX_STALL_DUMPS budget the real hang needs
                    tok.t0 = _time.perf_counter()
                    tok.dumped = False
                _inter_msgs_recvd.add()
                pending[src] -= 1
                left -= 1
                # the native wire hands arrivals over as device arrays
                # (btl/nativewire.recv_staged ends in a device_put):
                # this is their way back, per message, inside the
                # exchange's span
                arr = _d2h(arr)
                if rec and _obs.enabled:
                    _obs.record("hier_recv", "hier", t0,
                                _time.perf_counter() - t0,
                                nbytes=int(arr.nbytes), peer=src,
                                comm_id=self.comm.cid,
                                flow=self._flow(src, self.my_pidx),
                                flow_side="t")
                on_arrival(src, arr)
        finally:
            if tok is not None:
                _watchdog.disarm(tok)

    def _exchange(self, arrs_for: Dict[int, list]) -> Dict[int, list]:
        """Linear inter-process exchange: send every peer its arrays,
        then receive the same count back from each peer (all sends
        land before any recv parks — deadlock-free for the linear
        pattern). One thin shim over the exchange adapter — the SINGLE
        round-advancing code path, shared with every schedule — which
        owns the overlap/sequential split (``wire_overlap_exchange``)
        and all pvar/flow/watchdog accounting."""
        sends = {p: [np.asarray(a) for a in arrs_for.get(p, [])]
                 for p in self.peers}
        got = self._xchg.exchange(
            sends, {p: len(sends[p]) for p in self.peers})
        return {p: got.get(p, []) for p in self.peers}

    def _check_local_axis(self, x, what: str) -> None:
        if not hasattr(x, "shape") or x.ndim == 0 \
                or x.shape[0] != self.local_n:
            raise MPIError(
                ErrorCode.ERR_COUNT,
                f"{what} on spanning {self.comm.name}: buffers carry "
                f"one slice per LOCAL member ({self.local_n}), got "
                f"shape {getattr(x, 'shape', None)}",
            )
        # same refusal as the compiled driver edge: hier's local
        # partials and jnp conversions would otherwise silently narrow
        # 64-bit buffers with x64 off — and behavior would even differ
        # by process layout (a 1-member process skips the shadow comm)
        from .driver import _check_no_narrowing

        _check_no_narrowing(x)

    def _local_partial(self, x, op: Op):
        """Reduce this process's member slices to one partial."""
        if op.is_pair_op:
            vals, idxs = x
            self._check_local_axis(vals, "pair allreduce")
            if self.local_n == 1:
                return (jnp.asarray(vals[0]), jnp.asarray(idxs[0]))
            out_v, out_i = self.shadow.allreduce((vals, idxs), op)
            return (out_v[0], out_i[0])
        self._check_local_axis(x, "reduce")
        if self.local_n == 1:
            return jnp.asarray(x[0])
        return self.shadow.allreduce(x, op)[0]

    # -- partial packing / combine dispatch --------------------------------
    def _note_alg(self, alg: str) -> None:
        """Record the selected schedule in the round-state table the
        flight recorder dumps (postmortems name op, round AND alg)."""
        if not _obs.enabled:
            return
        st = _round_state.get(self.comm.cid)
        if st is not None:
            st["alg"] = alg

    @staticmethod
    def _pack_pair(pv: np.ndarray, pi: np.ndarray) -> np.ndarray:
        """One contiguous wire payload for a MINLOC/MAXLOC (value,
        index) partial: both sides know the shapes/dtypes from their
        own partial, so the split point ships no metadata — one
        message per peer per step instead of two (half the
        ``hier_inter_msgs_sent`` and per-message framing)."""
        pv = np.ascontiguousarray(pv)
        pi = np.ascontiguousarray(pi)
        return np.concatenate([pv.reshape(-1).view(np.uint8),
                               pi.reshape(-1).view(np.uint8)])

    @staticmethod
    def _unpack_pair(buf: np.ndarray, like_v: np.ndarray,
                     like_i: np.ndarray):
        buf = np.ascontiguousarray(np.asarray(buf)).view(np.uint8)
        nv = int(like_v.nbytes)
        v = buf[:nv].view(like_v.dtype).reshape(like_v.shape)
        i = buf[nv:].view(like_i.dtype).reshape(like_i.shape)
        return v, i

    def _pack_partial(self, partial, op: Op) -> np.ndarray:
        if op.is_pair_op:
            return self._pack_pair(_d2h(partial[0]), _d2h(partial[1]))
        return _d2h(partial)

    def _unpack_partial(self, buf, like, op: Op):
        # `like` is read for shape/dtype/nbytes only — attributes jax
        # arrays expose directly; never np.asarray it here (that would
        # force a device fetch of the unchanged partial per peer)
        if op.is_pair_op:
            v, i = self._unpack_pair(buf, like[0], like[1])
            return (_h2d(v), _h2d(i))
        return _h2d(np.asarray(buf).reshape(like.shape))

    @staticmethod
    def _fold(parts: list, op: Op):
        """``parts`` folded left to right: ``ompi.hier.fold`` (``bytes``
        of the result; on device arrays until the last op is launched)."""
        first = parts[0]
        with _obs.span(_spans.HIER_FOLD, bytes=sum(map(
                _spans.nbytes, first if op.is_pair_op else (first,)))):
            acc = first
            for nxt in parts[1:]:
                acc = op(acc, nxt)
        return acc

    def _fold_flats(self, procs: List[int], flats: Dict[int, object],
                    partial, op: Op):
        """Fold per-process packed partials in PROCESS-INDEX order —
        the one combine sequence every exact-order schedule shares.
        ``flats`` maps pidx -> packed payload for every peer; this
        process contributes ``partial`` directly (never re-unpacked)."""
        me = self.my_pidx
        parts = [partial if p == me
                 else self._unpack_partial(flats[p], partial, op)
                 for p in procs]
        if not op.is_pair_op:
            parts = [jnp.asarray(t) for t in parts]
        return self._fold(parts, op)

    def _leader_tier_active(self, op: Optional[Op] = None) -> bool:
        """Leader tier applies when the comm spans >1 host AND some
        host holds >1 process (else grouping is the flat set); the
        per-host fold regroups the combine order, so reductions keep
        it for commutative ops only."""
        if len(self.leaders) <= 1 or len(self.leaders) == len(self.procs):
            return False
        if op is not None and not op.commutative:
            return False
        return bool(mca_var.get("hier_leader_tier", True))

    def _pick_allreduce(self, procs: List[int], nbytes: int,
                        op: Op) -> str:
        """The inter allreduce pick for ``procs`` — one call site so
        the leader-tier stand-aside and the combine itself can never
        disagree. The topo hint describes THIS process set (the
        leader set is one-per-host, so its grid is never uniform)."""
        dims = self.torus_dims if procs is self.procs \
            else _topo.grid_dims(procs, self.host_of)
        return _hs.pick(
            "allreduce", len(procs), nbytes,
            commutative=op.commutative,
            has_identity=op.identity is not None,
            pair_op=op.is_pair_op, topo=dims,
        )

    def _combine_partials(self, partial, op: Op, host: bool = False):
        """Inter-process combine of per-process partials; identical on
        every process (fixed, process-index-derived order per
        schedule). ``host``: an order-waiving schedule's result, which
        is computed on the host, is returned there, not placed."""
        if len(self.procs) == 1:
            if op.is_pair_op:
                return (jnp.asarray(partial[0]), jnp.asarray(partial[1]))
            return jnp.asarray(partial)
        if self._leader_tier_active(op):
            # a topology-aware pick over the FULL process set is
            # host-aware itself: the leader tier stands aside instead
            # of regrouping the torus/multiring schedule away. The
            # pack+pick feed straight into _combine_flat when it runs
            # — never computed twice on this hot path.
            packed = self._pack_partial(partial, op)
            alg = self._pick_allreduce(self.procs, int(packed.nbytes),
                                       op)
            if alg not in _topo.TOPO_ALGS:
                return self._combine_leader(partial, op)
            return self._combine_flat(self.procs, partial, op,
                                      packed=packed, alg=alg, host=host)
        return self._combine_flat(self.procs, partial, op, host=host)

    def _combine_flat(self, procs: List[int], partial, op: Op,
                      packed=None, alg: Optional[str] = None,
                      host: bool = False):
        """Run the selected allreduce schedule over ``procs`` (the
        whole process set, or the leader set under the leader tier).
        ``packed``/``alg`` let a caller that already packed and picked
        (the leader-tier stand-aside) hand both through; ``host`` as
        in :meth:`_combine_partials`."""
        P = len(procs)
        if P == 1:
            if op.is_pair_op:
                return (jnp.asarray(partial[0]), jnp.asarray(partial[1]))
            return jnp.asarray(partial)
        if packed is None:
            packed = self._pack_partial(partial, op)
        if alg is None:
            alg = self._pick_allreduce(procs, int(packed.nbytes), op)
        self._note_alg(alg)
        me = self.my_pidx
        if alg in _hs.ORDER_WAIVING:
            arr = _d2h(partial)
            npop = lambda a, b: _d2h(op(a, b))  # noqa: E731
            ident = op.identity_for(arr.dtype)
            if alg == "multiring":
                out = _topo.allreduce_multiring(
                    self._xchg, procs, me, arr, npop, ident,
                    int(mca_var.get("hier_multiring_k", 4)))
            elif alg == "torus2d":
                out = _topo.allreduce_torus2d(
                    self._xchg, procs, me, arr, npop, ident,
                    self.host_of)
            else:
                fn = (_hs.allreduce_ring if alg == "ring"
                      else _hs.allreduce_rabenseifner)
                out = fn(self._xchg, procs, me, arr, npop, ident)
            out = np.asarray(out).reshape(arr.shape)
            return out if host else _h2d(out)
        if alg == "recursive_doubling":
            flats = _hs.allgather_bruck(
                self._xchg, procs, me, packed,
                [int(packed.size)] * P)
            return self._fold_flats(
                procs, dict(zip(procs, flats)), partial, op)
        # linear: the all-pairs exchange baseline (one packed message
        # per peer; pair ops no longer ship two)
        got = _hs.linear_exchange(self._xchg, procs, me, packed)
        return self._fold_flats(procs, got, partial, op)

    def _combine_leader(self, partial, op: Op):
        """Host-aware two-stage combine: co-hosted processes fold at
        their host leader (shm), leaders run the selected schedule
        across hosts (DCN), results fan back out. Fold order is fixed:
        host members in process-index order, then hosts in leader-
        index order — identical on every rank and run."""
        me = self.my_pidx
        lead = self.leader_of[me]
        if lead != me:
            _hs.round_exchange(
                self._xchg, {lead: [self._pack_partial(partial, op)]}, {})
            got = _hs.round_exchange(self._xchg, {}, {lead: 1})[lead][0]
            return self._unpack_partial(got, partial, op)
        _leader_combines.add()
        members = self.host_groups[self.host_of[me]]  # sorted (pidx)
        parts = {me: partial}
        others = [p for p in members if p != me]
        if others:
            got = _hs.round_exchange(self._xchg, {},
                                     {p: 1 for p in others})
            for p in others:
                parts[p] = self._unpack_partial(got[p][0], partial, op)
        acc = self._fold([parts[p] for p in members], op)
        total = self._combine_flat(self.leaders, acc, op)
        if others:
            tp = self._pack_partial(total, op)
            _hs.round_exchange(self._xchg, {p: [tp] for p in others}, {})
        return total

    def _bcast_local_axis(self, value):
        value = _h2d(value)
        return jnp.broadcast_to(
            value[None], (self.local_n,) + value.shape
        )

    def _assemble(self, x, fetched: np.ndarray, arrivals: Sequence,
                  pieces: Tuple, shape: Tuple[int, ...]):
        """The caller's array of a data-movement collective, built in
        ONE pass from blocks: block 0 is the rank's own buffer — ``x``
        as the caller passed it where the join runs on the device,
        ``fetched`` (its host copy, which costs a host rank nothing)
        where it runs on the host — and the others are the
        ``arrivals``, which a native fire hands over as read-only views
        of the executor's slab (``native_exec.VIEW_OPS``), a Python
        replay as arrays of its own: both are read here and never kept.
        ``pieces`` are ``(member, block, start, count)``: ``count``
        elements of the flattened block from ``start`` on, which come
        next in local member ``member``'s row of the result — or in
        every member's (``None``, then in all pieces). A member that
        gets no piece gets zeros.

        Nothing returned may alias the slab, whose plan's next fire
        overwrites it. On a chip the arrivals are placed on the device
        (``ompi.hier.h2d``: until the call returns, the transfer may
        still read them) and joined there with the own buffer by one
        cached program; the wait below ends only after the transfers
        have. On a host rank the pieces are written once into an
        aligned buffer of this call's own, which the CPU backend then
        keeps without another copy."""
        if getattr(self._xchg, "dry_run", False):
            # the native-plan probe reads the schedule's sends alone
            from .base import NO_RESULT

            return NO_RESULT
        blocks = [fetched if self._host_join else x, *arrivals]
        dtype = blocks[0].dtype
        nbytes = math.prod(shape) * dtype.itemsize
        with _obs.span(_spans.HIER_ASSEMBLE, bytes=nbytes):
            if self._host_join:
                out = _aligned_empty(shape, dtype)
                rows = out.reshape(self.local_n, -1)
                flat = [b.reshape(-1) for b in blocks]
                at = [0] * self.local_n
                for member, i, start, count in pieces:
                    row = member or 0
                    rows[row, at[row]:at[row] + count] = \
                        flat[i][start:start + count]
                    at[row] += count
                if pieces[0][0] is None:
                    rows[1:] = rows[0]
                else:
                    for row, filled in enumerate(at):
                        rows[row, filled:] = 0
                out = _h2d(out)
            else:
                placed = [_h2d(b) for b in blocks]
                out = _assemble_program(pieces, self.local_n,
                                        shape)(*placed)
                if not all(isinstance(b, jax.Array) for b in blocks):
                    out.block_until_ready()
        _assembled.add()
        _assembled_bytes.add(nbytes)
        return out

    def _assemble_rows(self, x, block: np.ndarray,
                       blocks: Dict[int, np.ndarray],
                       member: Optional[int] = None):
        """Rank-order concatenation of every comm rank's row (0-d rows
        stack into a vector, as all_gather + reshape does) for every
        local member, or for ``member`` alone. ``blocks`` holds every
        peer's block; this process's is ``x``, fetched as ``block``."""
        row = block.shape[1:]
        count = math.prod(row)
        shape = (self.local_n, self.comm.size * row[0]) + row[1:] \
            if row else (self.local_n, self.comm.size)
        return self._assemble(
            x, block, [blocks[p] for p in self.peers],
            tuple((member, i, pos * count, count)
                  for i, pos in self._rows), shape)

    # -- operation table ---------------------------------------------------
    def _wrap(self, name: str, fn: Callable) -> Callable:
        """Round instrumentation around one table entry: when obs is
        off this is ONE attribute check and a tail call; when on, it
        advances the synchronized round counter, publishes the round
        state the flight recorder dumps, and journals the whole op as
        a coll-layer span (what the doctor's skew report rounds on)."""

        def run(comm, *args, **kw):
            if not _obs.enabled:
                return fn(comm, *args, **kw)
            t0 = self._round_begin(name)
            try:
                return fn(comm, *args, **kw)
            finally:
                self._round_end(name, t0)

        return run

    def fns(self) -> Dict[str, Callable]:
        return {name: self._wrap(name, fn)
                for name, fn in self._table().items()}

    def _table(self) -> Dict[str, Callable]:
        return {
            "allreduce": self.allreduce,
            "reduce": self.reduce,
            "bcast": self.bcast,
            "allgather": self.allgather,
            "gather": self.gather,
            "scatter": self.scatter,
            "reduce_scatter_block": self.reduce_scatter_block,
            "alltoall": self.alltoall,
            "scan": self.scan,
            "exscan": self.exscan,
            "barrier": self.barrier,
            "alltoallv": self.alltoallv,
            "allgatherv": self.allgatherv,
            "gatherv": self.gatherv,
            "scatterv": self.scatterv,
            "reduce_scatter": self.reduce_scatter,
        }

    # -- reductions --------------------------------------------------------
    def allreduce(self, comm, x, op: Op):
        total = self._combine_partials(self._local_partial(x, op), op)
        if op.is_pair_op:
            tv, ti = total
            return (self._bcast_local_axis(tv),
                    self._bcast_local_axis(ti))
        return self._bcast_local_axis(total)

    def reduce(self, comm, x, op: Op, root: int):
        """Gather per-process partials to the root's owner — binomial
        tree (one packed send per non-root, ceil(log2 P) receives at
        the root) or direct linear sends — then ONE fold there in
        process-index order: bitwise-identical to the historic
        combine-everywhere path (same fold order) at a fraction of the
        messages, and exact for non-commutative ops. The result is
        masked to the root's slice (zeros elsewhere, the xla rooted-
        reduce convention)."""
        partial = self._local_partial(x, op)
        owner = self.owner[root]
        me = self.my_pidx
        P = len(self.procs)
        packed = self._pack_partial(partial, op)
        alg = _hs.pick("reduce", P, int(packed.nbytes)) if P > 1 \
            else "linear"
        self._note_alg(alg)
        flats = None
        if P == 1:
            total = partial
        elif alg == "binomial":
            flats = _hs.gather_binomial(
                self._xchg, self.procs, me, owner, packed,
                [int(packed.size)] * P)
        elif me != owner:
            _hs.round_exchange(self._xchg, {owner: [packed]}, {})
        else:
            got = _hs.round_exchange(
                self._xchg, {}, {p: 1 for p in self.procs if p != me})
            flats = [packed if p == me else got[p][0]
                     for p in self.procs]
        if flats is not None:
            total = self._fold_flats(
                self.procs, dict(zip(self.procs, flats)), partial, op)
        elif P > 1 and me != owner:
            total = None  # recv buffer undefined off-root (zeros)

        def place(t):
            t = _d2h(t)
            out = np.zeros((self.local_n,) + t.shape, t.dtype)
            if total is not None and root in self.local_ranks:
                out[self.local_ranks.index(root)] = t
            return _h2d(out)

        if op.is_pair_op:
            like = partial if total is None else total
            return (place(like[0]), place(like[1]))
        return place(partial if total is None else total)

    def reduce_scatter_block(self, comm, x, op: Op):
        n = comm.size

        def chunked(total: np.ndarray) -> np.ndarray:
            if total.shape[0] % n:
                raise MPIError(
                    ErrorCode.ERR_COUNT,
                    f"reduce_scatter_block buffer length "
                    f"{total.shape[0]} not divisible by comm size {n}",
                )
            chunks = total.reshape((n, -1) + total.shape[1:])
            out = np.stack([chunks[r] for r in self.local_ranks])
            return out.reshape((self.local_n, -1) + total.shape[1:])

        partial = self._local_partial(x, op)
        if self._halving_fits(partial, op):
            return self._reduce_scatter_halved(partial, op)
        total = self._combine_partials(partial, op, host=True)
        if op.is_pair_op:
            tv, ti = total
            return (_h2d(chunked(_d2h(tv))), _h2d(chunked(_d2h(ti))))
        return _h2d(chunked(_d2h(total)))

    def _halving_fits(self, partial, op: Op) -> bool:
        """Whether reduce_scatter_block may stop after Rabenseifner's
        recursive-halving half: the allreduce pick is ``rabenseifner``
        over the flat process set (a power of two of them, no leader
        tier), and each process's chunk of the flat partial is its
        own ranks' blocks in order, whole (no padding). Every input
        is the same on every process, so all of them take this path
        or none does."""
        P = len(self.procs)
        return (P > 1 and not P & (P - 1) and self._ranks_chunked
                and not op.is_pair_op and partial.ndim > 0
                and partial.shape[0] > 0
                and partial.shape[0] % self.comm.size == 0
                and not self._leader_tier_active(op)
                and self._pick_allreduce(
                    self.procs, int(partial.nbytes), op) == "rabenseifner")

    def _reduce_scatter_halved(self, partial, op: Op):
        """reduce_scatter_block as the halving rounds alone: after
        them chunk ``mi`` of the flat partial is this process's
        ranks' blocks of the sum, folded exactly as the allreduce
        folds them, and only that chunk is placed."""
        arr = _d2h(partial)
        self._note_alg("rabenseifner")
        flat, _, per, mi = _hs.reduce_scatter_halving(
            self._xchg, self.procs, self.my_pidx, arr,
            lambda a, b: _d2h(op(a, b)), op.identity_for(arr.dtype))
        if not getattr(self._xchg, "dry_run", False):  # not a probe
            _rsb_halved.add()
        block = flat[mi * per:(mi + 1) * per]
        return _h2d(block.reshape((self.local_n, -1) + arr.shape[1:]))

    # -- data movement -----------------------------------------------------
    def bcast(self, comm, x, root: int):
        owner = self.owner[root]
        me = self.my_pidx
        if owner == me:
            self._check_local_axis(x, "bcast")
            idx = self.local_ranks.index(root)
            val = _d2h(x[idx])
        else:
            val = None
        # every rank passes an x of the same per-slice shape (the
        # driver-mode SPMD convention), so the decision byte count is
        # derivable symmetrically off-root too
        xa = _d2h(x)
        slice_bytes = int(xa.nbytes // xa.shape[0]) if xa.ndim else 0
        alg = _hs.pick("bcast", len(self.procs), slice_bytes,
                       topo=self.torus_dims)
        self._note_alg(alg)
        if alg == "torus2d" and len(self.procs) > 1:
            # host-aware by construction: the torus bcast subsumes the
            # leader tier's fan-out (one DCN copy per host)
            val = _topo.bcast_torus2d(self._xchg, self.procs, me,
                                      owner, val, self.host_of)
        elif alg == "binomial" and len(self.procs) > 1:
            if self._leader_tier_active():
                val = self._bcast_leader(owner, val)
            else:
                val = _hs.bcast_binomial(self._xchg, self.procs, me,
                                         owner, val)
        elif owner == me:
            self._xchg.exchange({p: [val] for p in self.peers}, {})
        else:
            val = self._xchg.exchange({}, {owner: 1})[owner][0]
        if owner == me:
            # the root's slice is where the result needs it: in x
            count = int(xa.size // self.local_n)
            return self._assemble(x, xa, (),
                                  ((None, 0, idx * count, count),),
                                  xa.shape)
        val = np.asarray(val)
        return self._assemble(val, val, (),
                              ((None, 0, 0, int(val.size)),),
                              (self.local_n,) + val.shape)

    def _bcast_leader(self, owner: int, val):
        """Leader-tier bcast: binomial over {owner + other hosts'
        leaders} crosses DCN, then each of those fans out to its
        co-hosted processes over shm (the owner serves its own host —
        including that host's nominal leader)."""
        me = self.my_pidx
        host = self.host_of
        bset = sorted({owner} | {l for l in self.leaders
                                 if host[l] != host[owner]})
        if me in bset:
            val = _hs.bcast_binomial(self._xchg, bset, me, owner, val)
            fan = [p for p in self.host_groups[host[me]] if p != me]
            if fan:
                _hs.round_exchange(
                    self._xchg, {p: [np.asarray(val)] for p in fan}, {})
            return val
        src = owner if host[me] == host[owner] else self.leader_of[me]
        return np.asarray(
            _hs.round_exchange(self._xchg, {}, {src: 1})[src][0])

    def _gather_blocks(self,
                       block: np.ndarray) -> Dict[int, np.ndarray]:
        """Every process's block via the selected allgather schedule
        (one (local_n, chunk...) block each); returns {process: block},
        this process's own entry being ``block`` itself."""
        me = self.my_pidx
        P = len(self.procs)
        chunk_shape = block.shape[1:]
        chunk_elems = int(np.prod(chunk_shape, dtype=np.int64)) \
            if chunk_shape else 1
        total_bytes = int(self.comm.size * chunk_elems * block.itemsize)
        alg = _hs.pick("allgather", P, total_bytes,
                       topo=self.torus_dims) if P > 1 else "linear"
        self._note_alg(alg)
        blocks: Dict[int, np.ndarray] = {}
        if P == 1 or alg == "linear":
            got = self._exchange({p: [block] for p in self.peers})
            for p in self.procs:
                blocks[p] = block if p == me else np.asarray(got[p][0])
        elif alg == "torus2d":
            parts = _topo.allgather_torus2d(self._xchg, self.procs,
                                            me, block, self.host_of)
            for i, p in enumerate(self.procs):
                blocks[p] = np.asarray(parts[i])
        elif alg == "bruck":
            counts = [len(self.members_of[p]) * chunk_elems
                      for p in self.procs]
            flats = _hs.allgather_bruck(
                self._xchg, self.procs, me,
                np.ascontiguousarray(block).reshape(-1), counts)
            for i, p in enumerate(self.procs):
                blocks[p] = np.asarray(flats[i]).reshape(
                    (len(self.members_of[p]),) + chunk_shape)
        else:  # ring: neighbor-only passes, shapes ride the wire
            parts = _hs.allgather_ring(self._xchg, self.procs, me, block)
            for i, p in enumerate(self.procs):
                blocks[p] = np.asarray(parts[i])
        return blocks

    def allgather(self, comm, x):
        self._check_local_axis(x, "allgather")
        block = _d2h(x)  # (local_n, chunk...): what the peers are sent
        blocks = self._gather_blocks(block)
        return self._assemble_rows(x, block, blocks)

    def gather(self, comm, x, root: int):
        self._check_local_axis(x, "gather")
        owner = self.owner[root]
        me = self.my_pidx
        P = len(self.procs)
        block = _d2h(x)
        full_shape = (comm.size * block.shape[1],) + block.shape[2:] \
            if block.ndim > 1 else (comm.size,)
        chunk_shape = block.shape[1:]
        chunk_elems = int(np.prod(chunk_shape, dtype=np.int64)) \
            if chunk_shape else 1
        slice_bytes = int(chunk_elems * block.itemsize)
        alg = _hs.pick("gather", P, slice_bytes) if P > 1 else "linear"
        self._note_alg(alg)
        if alg == "binomial" and P > 1:
            counts = [len(self.members_of[p]) * chunk_elems
                      for p in self.procs]
            flats = _hs.gather_binomial(
                self._xchg, self.procs, me, owner,
                np.ascontiguousarray(block).reshape(-1), counts)
            if flats is None:
                return jnp.zeros((self.local_n,) + full_shape,
                                 block.dtype)
            blocks = {p: np.asarray(flats[i]).reshape(
                (len(self.members_of[p]),) + chunk_shape)
                for i, p in enumerate(self.procs)}
        else:
            if owner != me:
                self._xchg.exchange({owner: [block]}, {})
                return jnp.zeros((self.local_n,) + full_shape,
                                 block.dtype)
            got = self._xchg.exchange({}, {p: 1 for p in self.peers})
            blocks = {p: np.asarray(got[p][0]) for p in self.peers}
        return self._assemble_rows(x, block, blocks,
                                   member=self.local_ranks.index(root))

    def scatter(self, comm, x, root: int):
        n = comm.size
        owner = self.owner[root]
        me = self.my_pidx
        P = len(self.procs)
        # MPI reads the buffer on the root only, so non-roots cannot
        # know the message size — the schedule decision must still be
        # identical everywhere, so it is taken at bytes=0 (forcing and
        # zero-threshold rules apply; size-split rules cannot)
        alg = _hs.pick("scatter", P, 0) if P > 1 else "linear"
        self._note_alg(alg)
        chunks = None
        if owner == me:
            self._check_local_axis(x, "scatter")
            full = _d2h(x[self.local_ranks.index(root)])
            if full.shape[0] % n:
                raise MPIError(
                    ErrorCode.ERR_COUNT,
                    f"scatter buffer length {full.shape[0]} not "
                    f"divisible by comm size {n}",
                )
            chunks = full.reshape((n, -1) + full.shape[1:])
        if alg == "binomial" and P > 1:
            weights = [len(self.members_of[p]) for p in self.procs]
            per_pos = meta = None
            if owner == me:
                per_pos = [np.ascontiguousarray(
                    chunks[self.members_of[p]]).reshape(-1)
                    for p in self.procs]
                meta = np.asarray(chunks.shape[1:], np.int64)
            flat, meta = _hs.scatter_binomial(self._xchg, self.procs,
                                              me, owner, per_pos,
                                              weights, meta)
            if owner == me:
                mine = chunks[self.members_of[me]]
            else:
                # the forwarded meta header carries the per-rank chunk
                # shape MPI lets only the root read
                shape = (self.local_n,) + tuple(int(s) for s in meta)
                mine = np.asarray(flat).reshape(shape)
        elif owner == me:
            self._xchg.exchange({p: [chunks[self.members_of[p]]]
                                 for p in self.peers}, {})
            mine = chunks[self.members_of[me]]
        else:
            # (local_n, chunk...)
            mine = self._xchg.exchange({}, {owner: 1})[owner][0]
        return _h2d(mine)

    def alltoall(self, comm, x):
        self._check_local_axis(x, "alltoall")
        n = comm.size
        block = _d2h(x)
        if block.shape[1] % n:
            raise MPIError(
                ErrorCode.ERR_COUNT,
                f"alltoall buffer length {block.shape[1]} not divisible "
                f"by comm size {n}",
            )
        c = block.shape[1] // n
        # chunks[a, j]: local member a's chunk destined to comm rank j
        chunks = block.reshape((self.local_n, n, c) + block.shape[2:])
        P = len(self.procs)
        me = self.my_pidx
        trail = int(np.prod(block.shape[2:], dtype=np.int64)) \
            if block.ndim > 2 else 1
        # decision unit = one rank-pair chunk's bytes (block_dsize,
        # coll_tuned_decision_fixed.c:122) — identical on every process
        alg = _hs.pick("alltoall", P, int(c * trail * block.itemsize)) \
            if P > 1 else "linear"
        self._note_alg(alg)
        recv_block: Dict[int, np.ndarray] = {}
        if P == 1:
            pass
        elif alg == "bruck":
            mlen = [len(self.members_of[p]) for p in self.procs]
            cf = c * trail
            pair_counts = [[mlen[o] * mlen[j] * cf for j in range(P)]
                           for o in range(P)]
            mine = [np.ascontiguousarray(
                chunks[:, self.members_of[p]]).reshape(-1)
                for p in self.procs]
            res = _hs.alltoall_bruck(self._xchg, self.procs, me, mine,
                                     pair_counts)
            for i, p in enumerate(self.procs):
                if p == me:
                    continue
                recv_block[p] = np.asarray(res[i]).reshape(
                    (mlen[i], self.local_n, c) + block.shape[2:])
        elif alg == "pairwise":
            payload_for = {p: np.ascontiguousarray(
                chunks[:, self.members_of[p]]) for p in self.peers}
            got = _hs.alltoall_pairwise(self._xchg, self.procs, me,
                                        payload_for)
            recv_block = {p: np.asarray(a) for p, a in got.items()}
        else:  # linear: every peer's aggregate posted at once
            got = self._exchange({p: [chunks[:, self.members_of[p]]]
                                  for p in self.peers})
            recv_block = {p: np.asarray(got[p][0]) for p in self.peers}
        # out[b, i] for my member b and comm rank i: from a local
        # member a it is in[a, mine[b]], from peer p's member a it is
        # recv_block[p][a, b]; each is cf elements of its flat block
        mine = self.local_ranks
        cf = c * trail
        pieces = tuple(
            (b, 0, (mine.index(i) * n + mine[b]) * cf, cf)
            if self.owner[i] == me else
            (b, 1 + self.peers.index(self.owner[i]),
             (self.members_of[self.owner[i]].index(i) * self.local_n
              + b) * cf, cf)
            for b in range(self.local_n) for i in range(n))
        return self._assemble(x, block,
                              [recv_block[p] for p in self.peers],
                              pieces, block.shape)

    # -- v-variant collectives (ragged; lists indexed by LOCAL member) -----
    # Spanning-comm analogue of coll/vcoll.py's driver-mode convention:
    # rank-dependent inputs/outputs are Python lists with one entry per
    # LOCAL member in comm-rank order; identical-everywhere results are
    # returned once. Counts arguments are GLOBAL (the full matrix /
    # per-rank vector on every process), matching MPI's requirement
    # that every caller supplies the complete picture.

    def _ragged_local(self, bufs, what: str) -> List[np.ndarray]:
        if len(bufs) != self.local_n:
            raise MPIError(
                ErrorCode.ERR_COUNT,
                f"{what} on spanning {self.comm.name}: pass one buffer "
                f"per LOCAL member ({self.local_n}), got {len(bufs)}",
            )
        out = [_d2h(b).reshape(-1) for b in bufs]
        dtypes = {a.dtype for a in out}
        if len(dtypes) != 1:
            raise MPIError(
                ErrorCode.ERR_TYPE,
                f"{what} buffers must share one dtype, got "
                f"{sorted(map(str, dtypes))}",
            )
        from .driver import _check_no_narrowing

        if out:
            _check_no_narrowing(out[0])
        return out

    def alltoallv(self, comm, sendbufs, sendcounts):
        """Pairwise exchange, process-aggregated
        (``coll_tuned_alltoallv.c:148`` sends rank-pairwise over the
        PML; here every process sends ONE aggregated message per peer
        process — its members' chunks for that peer's members — since
        both sides derive the sub-layout from the shared count
        matrix). ``sendcounts`` is the full (n, n) matrix; returns
        ``recv[b]`` = source-order concatenation for local member b."""
        n = comm.size
        c = np.asarray(sendcounts, dtype=np.int64)
        if c.shape != (n, n) or (c < 0).any():
            raise MPIError(
                ErrorCode.ERR_COUNT,
                f"alltoallv needs a non-negative ({n},{n}) count "
                f"matrix, got {getattr(c, 'shape', None)}",
            )
        bufs = self._ragged_local(sendbufs, "alltoallv")
        dtype = bufs[0].dtype
        offs = np.concatenate(
            [np.zeros((n, 1), np.int64), np.cumsum(c, axis=1)], axis=1
        )
        for pos, i in enumerate(self.local_ranks):
            if bufs[pos].shape[0] != int(c[i].sum()):
                raise MPIError(
                    ErrorCode.ERR_COUNT,
                    f"alltoallv rank {i}: buffer has "
                    f"{bufs[pos].shape[0]} elements, counts sum to "
                    f"{int(c[i].sum())}",
                )

        def chunk(pos: int, i: int, j: int) -> np.ndarray:
            return bufs[pos][offs[i, j]:offs[i, j] + int(c[i, j])]

        sends = {}
        for p in self.peers:
            parts = [chunk(pos, i, j)
                     for pos, i in enumerate(self.local_ranks)
                     for j in self.members_of[p]]
            sends[p] = [np.concatenate(parts) if parts
                        else np.zeros((0,), dtype)]
        got = self._exchange(sends)
        from_peer: Dict[tuple, np.ndarray] = {}
        for p in self.peers:
            msg = np.asarray(got[p][0])
            off = 0
            for i in self.members_of[p]:
                for j in self.local_ranks:
                    k = int(c[i, j])
                    from_peer[(i, j)] = msg[off:off + k]
                    off += k
            if off != msg.shape[0]:
                raise MPIError(
                    ErrorCode.ERR_TRUNCATE,
                    f"alltoallv message from process {p} has "
                    f"{msg.shape[0]} elements, count matrix implies "
                    f"{off} — mismatched sendcounts across processes?",
                )
        recv = []
        for pos, j in enumerate(self.local_ranks):
            parts = [
                chunk(self.local_ranks.index(i), i, j)
                if self.owner[i] == self.my_pidx else from_peer[(i, j)]
                for i in range(n)
            ]
            recv.append(_h2d(np.concatenate(parts) if parts
                             else np.zeros((0,), dtype)))
        return recv

    def _gather_rows(self, bufs: List[np.ndarray]) -> Dict[int, np.ndarray]:
        """Every rank's ragged buffer: send each LOCAL member's buffer
        as its own message (shapes ride the wire, so no count
        pre-exchange), receive each peer's members' in comm-rank
        order (per-peer FIFO keeps member order under arrival-order
        reaping)."""
        rows: Dict[int, np.ndarray] = {
            r: bufs[pos] for pos, r in enumerate(self.local_ranks)
        }
        got = self._xchg.exchange(
            {p: list(bufs) for p in self.peers},
            {p: len(self.members_of[p]) for p in self.peers})
        for p in self.peers:
            # per-peer FIFO keeps member order under arrival reaping
            for r, arr in zip(self.members_of[p], got[p]):
                rows[r] = np.asarray(arr)
        return rows

    def allgatherv(self, comm, sendbufs):
        """Rank-order concatenation of ragged buffers; identical on
        every rank, returned once (the vcoll convention)."""
        bufs = self._ragged_local(sendbufs, "allgatherv")
        rows = self._gather_rows(bufs)
        return _h2d(
            np.concatenate([rows[r] for r in range(comm.size)])
        )

    def gatherv(self, comm, sendbufs, root: int):
        """Linear gather to the root's owner process
        (``coll_base_gatherv`` linear variant): non-owner processes
        send their members' buffers and return None (MPI leaves the
        recv buffer undefined off-root); the owner returns the
        rank-order concatenation."""
        n = comm.size
        if not 0 <= root < n:
            raise MPIError(ErrorCode.ERR_ROOT, f"bad root {root}")
        bufs = self._ragged_local(sendbufs, "gatherv")
        owner = self.owner[root]
        if owner != self.my_pidx:
            self._xchg.exchange({owner: list(bufs)}, {})
            from .base import NO_RESULT

            return NO_RESULT  # recv buffer undefined off-root
        rows: Dict[int, np.ndarray] = {
            r: bufs[pos] for pos, r in enumerate(self.local_ranks)
        }
        got = self._xchg.exchange(
            {}, {p: len(self.members_of[p]) for p in self.peers})
        for p in self.peers:
            for r, arr in zip(self.members_of[p], got[p]):
                rows[r] = np.asarray(arr)
        return _h2d(np.concatenate([rows[r] for r in range(n)]))

    def scatterv(self, comm, sendbuf, counts, root: int):
        """Root's owner splits ``sendbuf`` by ``counts`` and ships each
        remote rank's chunk to its owner; returns one array per LOCAL
        member. ``sendbuf`` is read only on the owner process."""
        n = comm.size
        if not 0 <= root < n:
            raise MPIError(ErrorCode.ERR_ROOT, f"bad root {root}")
        counts = [int(k) for k in counts]
        if len(counts) != n or any(k < 0 for k in counts):
            raise MPIError(
                ErrorCode.ERR_COUNT,
                f"scatterv needs {n} non-negative counts, got {counts}",
            )
        owner = self.owner[root]
        if owner != self.my_pidx:
            got = self._xchg.exchange({}, {owner: self.local_n})
            return [_h2d(a) for a in got[owner]]
        buf = _d2h(sendbuf).reshape(-1)
        from .driver import _check_no_narrowing

        _check_no_narrowing(buf)
        if buf.shape[0] != sum(counts):
            raise MPIError(
                ErrorCode.ERR_COUNT,
                f"scatterv root buffer has {buf.shape[0]} elements, "
                f"counts sum to {sum(counts)}",
            )
        offs = np.concatenate([[0], np.cumsum(counts)])
        chunks = [buf[offs[j]:offs[j] + counts[j]] for j in range(n)]
        self._xchg.exchange({p: [chunks[j] for j in self.members_of[p]]
                             for p in self.peers}, {})
        return [_h2d(chunks[j]) for j in self.local_ranks]

    def reduce_scatter(self, comm, x, recvcounts, op: Op):
        """General MPI_Reduce_scatter: combine (local partial, then
        process-index-order inter combine — the allreduce discipline),
        each rank keeps its ``recvcounts[i]``-length segment. ``x`` is
        (local_n, total); returns one array per LOCAL member."""
        n = comm.size
        recvcounts = [int(k) for k in recvcounts]
        if len(recvcounts) != n or any(k < 0 for k in recvcounts):
            raise MPIError(
                ErrorCode.ERR_COUNT,
                f"reduce_scatter needs {n} non-negative counts",
            )
        total = sum(recvcounts)
        if op.is_pair_op:
            vals, idxs = x
            self._check_local_axis(vals, "reduce_scatter")
            vals = _d2h(vals)
            if vals.reshape(self.local_n, -1).shape[1] != total:
                raise MPIError(
                    ErrorCode.ERR_COUNT,
                    f"reduce_scatter needs values shaped "
                    f"({self.local_n}, {total}), got {vals.shape}",
                )
            tv, ti = self._combine_partials(
                self._local_partial((vals, idxs), op), op
            )
            tv, ti = _d2h(tv).reshape(-1), _d2h(ti).reshape(-1)
            offs = np.concatenate([[0], np.cumsum(recvcounts)])
            return [
                (_h2d(tv[offs[r]:offs[r] + recvcounts[r]]),
                 _h2d(ti[offs[r]:offs[r] + recvcounts[r]]))
                for r in self.local_ranks
            ]
        x = _d2h(x)
        from .driver import _check_no_narrowing

        _check_no_narrowing(x)  # BEFORE the jnp conversion below
        if x.shape[0] != self.local_n \
                or x.reshape(self.local_n, -1).shape[1] != total:
            raise MPIError(
                ErrorCode.ERR_COUNT,
                f"reduce_scatter needs x shaped ({self.local_n}, "
                f"{total}), got {x.shape}",
            )
        x = x.reshape(self.local_n, total)
        red = _d2h(self._combine_partials(
            self._local_partial(_h2d(x), op), op
        ))
        offs = np.concatenate([[0], np.cumsum(recvcounts)])
        return [_h2d(red[offs[r]:offs[r] + recvcounts[r]])
                for r in self.local_ranks]

    # -- prefix scans ------------------------------------------------------
    def _full_rows(self, x) -> Dict[int, np.ndarray]:
        """Every rank's slice, via the selected allgather schedule."""
        blocks = self._gather_blocks(_d2h(x))
        return {r: blocks[p][pos] for p in self.procs
                for pos, r in enumerate(self.members_of[p])}

    def _scan_impl(self, comm, x, op: Op, exclusive: bool):
        if op.is_pair_op:
            # MINLOC/MAXLOC scans: fold the gathered (value, index)
            # rows with the pair combiner in rank order; the rank-0
            # exscan slice is zeros (MPI leaves it undefined)
            vals, idxs = x
            self._check_local_axis(vals, "scan")
            vrows = self._full_rows(vals)
            irows = self._full_rows(idxs)
            outv, outi = [], []
            for r in self.local_ranks:
                end = r if exclusive else r + 1
                if end == 0:
                    outv.append(np.zeros_like(vrows[0]))
                    outi.append(np.zeros_like(irows[0]))
                    continue
                acc = (_h2d(vrows[0]), _h2d(irows[0]))
                for j in range(1, end):
                    acc = op(acc, (_h2d(vrows[j]), _h2d(irows[j])))
                outv.append(_d2h(acc[0]))
                outi.append(_d2h(acc[1]))
            return (_h2d(np.stack(outv)), _h2d(np.stack(outi)))
        self._check_local_axis(x, "scan")
        rows = self._full_rows(x)
        out = []
        for r in self.local_ranks:
            if exclusive:
                if r == 0:
                    out.append(np.zeros_like(rows[0]))
                    continue
                acc = _h2d(rows[0])
                for j in range(1, r):
                    acc = op(acc, _h2d(rows[j]))
            else:
                acc = _h2d(rows[0])
                for j in range(1, r + 1):
                    acc = op(acc, _h2d(rows[j]))
            out.append(_d2h(acc))
        return _h2d(np.stack(out))

    def scan(self, comm, x, op: Op):
        return self._scan_impl(comm, x, op, exclusive=False)

    def exscan(self, comm, x, op: Op):
        return self._scan_impl(comm, x, op, exclusive=True)

    # -- synchronization ---------------------------------------------------
    def barrier(self, comm):
        if self.local_n > 1:
            self.shadow.barrier()
        self.router.proc_barrier(self.comm, self.procs)


class HierCollComponent(mca_component.Component):
    """Claims exactly the communicators no in-process component can
    serve: those spanning controller processes."""

    NAME = "hier"
    PRIORITY = 150

    def query(self, ctx=None):
        if ctx is None:
            return (self.priority, self)
        if not getattr(ctx, "spans_processes", False):
            return None
        if getattr(ctx.runtime, "wire", None) is None:
            return None  # no router: nothing can serve this comm
        return (self.priority, _HierModule(ctx))
