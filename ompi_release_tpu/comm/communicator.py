"""Communicators — the ``ompi/communicator`` analogue, mesh-native.

A communicator binds a :class:`Group` to a sub-mesh of the world device
mesh, carries a CID, attributes, an error handler, and — the load-
bearing part, exactly as in the reference — a per-communicator table of
collective implementations installed by priority query over the coll
framework (``ompi/mca/coll/base/coll_base_comm_select.c:66-88``).

Driver-mode data convention (single-controller SPMD): operations whose
MPI result is rank-dependent take/return arrays with a leading ``size``
axis (slice i = rank i's buffer, matching the reference's oversubscribed
-mpirun test style, SURVEY §4); operations whose result is identical on
every rank return it once. The in-jit SPMD API (``coll.allreduce`` under
``shard_map``) is the performance path; this host API is the semantic
(MPI-compatible) path and compiles one persistent program per
(op, shape, dtype, algorithm).

CID allocation: the reference runs an iterated MAX-allreduce agreement
(``ompi/communicator/comm_cid.c:190,264-318``); under a static mesh
with a single controller the agreement outcome is a deterministic
monotone counter, so that is what we use.
"""

from __future__ import annotations

import itertools
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import obs as _obs
from ..mca import pvar
from ..obs import sentinel as _sentinel
from ..obs import spans as _spans
from ..utils import output
from ..utils.errors import Errhandler, ErrorCode, MPIError, ERRORS_ARE_FATAL
from .group import Group, UNDEFINED

_log = output.stream("comm")
_cid_counter = itertools.count(0)
#: internal (runtime-private) communicators — e.g. the hier module's
#: process-local shadow — draw NEGATIVE cids from a separate counter:
#: their creation is conditional on local membership, so letting them
#: consume the global counter would desynchronize cid allocation
#: across controller processes (cids must agree SPMD-wide because the
#: wire router addresses communicators by cid)
_internal_cid_counter = itertools.count(-1, -1)
_cid_lock = threading.Lock()
_comm_registry: Dict[int, "Communicator"] = {}

_comm_count = pvar.counter("comm_active_count", "live communicators")

#: serializes lazy FusionBuffer creation (comm.fusion_buffer): the
#: buffer itself is thread-safe, so first use may race — an orphaned
#: second instance would silently escape free()'s drain
_fusion_create_lock = threading.Lock()


def _next_cid(internal: bool = False) -> int:
    with _cid_lock:
        return next(_internal_cid_counter if internal else _cid_counter)


def clear_comm_registry() -> None:
    """Finalize-time teardown: mark every live communicator freed (so
    stale handles raise instead of silently working) and keep the
    comm_active_count pvar honest."""
    for c in list(_comm_registry.values()):
        c._freed = True
        _comm_count.add(-1)
    _comm_registry.clear()


class Keyval:
    """MPI_Comm_create_keyval analogue."""

    _counter = itertools.count(0)

    def __init__(self, copy_fn: Optional[Callable] = None,
                 delete_fn: Optional[Callable] = None,
                 extra_state: Any = None) -> None:
        self.id = next(Keyval._counter)
        self.copy_fn = copy_fn
        self.delete_fn = delete_fn
        self.extra_state = extra_state


class Communicator:
    is_inter = False  # Intercommunicator overrides (MPI_Comm_test_inter)

    def __init__(self, runtime, group: Group, *, name: str = "",
                 parent: Optional["Communicator"] = None,
                 topo: Optional[Any] = None,
                 internal: bool = False,
                 cid: Optional[int] = None) -> None:
        from ..runtime.mesh import build_submesh  # local: avoid cycle

        self.runtime = runtime
        self.group = group
        if cid is not None:
            # explicit cid: the ULFM shrink/rebuild path derives the
            # cid from the HNP-agreed job epoch so survivors and a
            # respawned replacement (whose local counter restarted
            # from zero) mint the SAME cid without agreement traffic.
            # A REVOKED/freed occupant (the epoch-wrapped slot of this
            # lineage's own poisoned ancestor) is evicted — it can
            # never be used again by ULFM rule; a LIVE occupant is a
            # real collision and stays a loud error.
            occupant = _comm_registry.get(cid)
            if occupant is not None and (occupant._revoked
                                         or occupant._freed):
                if not occupant._freed:
                    # real teardown, not flag-poking: the evicted
                    # comm's _on_free hooks (hier shadow, fusion
                    # buffer) must run or they leak registry entries
                    # for the process lifetime
                    try:
                        occupant.free()
                    except MPIError:
                        pass  # a poisoned drain must not block rebuild
                _comm_registry.pop(cid, None)
                occupant = None
            if occupant is not None:
                raise MPIError(
                    ErrorCode.ERR_COMM,
                    f"explicit cid {cid} already registered "
                    f"({_comm_registry[cid].name}) — free it before "
                    "rebuilding at the same epoch",
                )
            # any stale revocation record for this slot belongs to an
            # ANCESTOR's epoch (evicted above, or revoked-then-freed
            # by the app long ago), not to the comm being built — a
            # leftover entry would make every wire wait on the fresh
            # cid raise ERR_REVOKED immediately
            from ..ft import ulfm as _ulfm_slot

            _ulfm_slot.state().clear_revoked(cid)
            # the evicted ancestor's sentinel chain goes with it: a
            # leftover posting seq would false-mismatch the rebuilt
            # comm against a restarted-from-zero replacement
            _sentinel.clear_chain(cid)
            self.cid = cid
        else:
            self.cid = _next_cid(internal)
        self._revoked = False  # ULFM revocation flag (see revoke())
        # ULFM lineage anchor: shrink/rebuild children inherit the
        # ORIGINAL comm's identity, so across ANY number of
        # recoveries every participant — a survivor holding
        # rebuild#N, a fresh replacement holding only its world —
        # keys the recovery agreement and the epoch-derived cid on
        # the same value. The lineage is also the constant ft_cid
        # parent slot, which is what makes an epoch-wrapped slot
        # collision land on this lineage's own revoked ancestor.
        if cid is not None and parent is not None:
            self._ft_lineage = getattr(parent, "_ft_lineage",
                                       parent.cid)
        else:
            self._ft_lineage = self.cid
        # the job epoch this comm was born at: ULFM failures are
        # permanent per communicator, so bounded waits compare each
        # peer's failure episode against THIS epoch — a replacement
        # incarnation is visible only to comms built after its rejoin
        from ..ft import ulfm as _ulfm_mod

        self._ft_epoch0 = _ulfm_mod.state().epoch
        # multi-tenant QoS class (service plane): children inherit the
        # parent's stamp so a tenant's whole comm tree rides its lane
        # class; None defers to the process-wide wire_qos_class cvar
        self._qos_class: Optional[str] = getattr(parent, "_qos_class",
                                                 None)
        self.name = name or f"comm{self.cid}"
        self.errhandler: Errhandler = (
            parent.errhandler if parent else ERRORS_ARE_FATAL
        )
        from .info import Info

        parent_info = getattr(parent, "info", None)
        self.info: Info = (parent_info.dup() if isinstance(parent_info, Info)
                           else Info())  # MPI_Comm_set/get_info object
        self.topo = topo  # topology module (cart/graph), if any
        self._attrs: Dict[int, Any] = {}
        self._freed = False

        # Local membership: under a unified multi-controller world this
        # process owns only a span of world ranks; the submesh (and
        # every compiled collective) covers the LOCAL members, while
        # cross-process traffic rides the wire (hier coll + wire pml).
        # Single-controller: every member is local and nothing changes.
        if getattr(runtime, "unified", False):
            off = runtime.local_rank_offset
            cnt = runtime.local_size
            self.local_comm_ranks = [
                i for i, wr in enumerate(group.world_ranks)
                if off <= wr < off + cnt
            ]
            self.spans_processes = len(self.local_comm_ranks) < group.size
            local_positions = [
                group.world_rank(i) - off for i in self.local_comm_ranks
            ]
        else:
            self.local_comm_ranks = list(range(group.size))
            self.spans_processes = False
            local_positions = list(group.world_ranks)

        # sub-mesh over this group's LOCAL devices, 1-D "rank" axis:
        # collectives ride ICI in world-mesh order regardless of group
        # order (a comm with no local members carries no submesh and
        # installs no engines — its operations are never invoked here)
        if local_positions:
            self.submesh = build_submesh(runtime.mesh, local_positions)
        else:
            self.submesh = None

        # per-comm collective table (c_coll analogue), installed at
        # creation time exactly like coll_base_comm_select
        from ..coll import base as coll_base

        if self.submesh is not None:
            self.c_coll = coll_base.comm_select(self)
        else:
            self.c_coll = {}

        _comm_registry[self.cid] = self
        _comm_count.add()
        _log.verbose(2, f"created {self.name} cid={self.cid} size={self.size}")

    # -- queries -----------------------------------------------------------
    @property
    def size(self) -> int:
        return self.group.size

    def rank_of(self, world_rank: int) -> int:
        return self.group.rank_of(world_rank)

    @property
    def is_self(self) -> bool:
        return self.size == 1

    def _check_alive(self) -> None:
        if self._freed:
            raise MPIError(ErrorCode.ERR_COMM, f"{self.name} already freed")

    def _check_usable(self) -> None:
        """Alive AND not revoked: every communication entry point runs
        this (ULFM: all ops except agree/shrink/get_failed fail with
        ERR_REVOKED on a revoked communicator). One bool check — the
        flag is set by revoke() locally and by the FT watcher when a
        peer's poison frame arrives."""
        self._check_alive()
        if self._revoked:
            raise MPIError(
                ErrorCode.ERR_REVOKED,
                f"{self.name} (cid {self.cid}) has been revoked — "
                "shrink() or rebuild it to continue",
            )

    # -- construction ------------------------------------------------------
    def dup(self, name: str = "") -> "Communicator":
        self._check_alive()
        c = Communicator(
            self.runtime, self.group,
            name=name or f"dup({self.name})", parent=self, topo=self.topo,
        )
        # MPI_Comm_dup runs attribute copy callbacks
        for kv_id, value in list(self._attrs.items()):
            kv = _keyval_table.get(kv_id)
            if kv and kv.copy_fn:
                keep, new_val = kv.copy_fn(self, kv, value, kv.extra_state)
                if keep:
                    c._attrs[kv_id] = new_val
            elif kv:
                c._attrs[kv_id] = value
        return c

    def create(self, group: Group, name: str = "") -> Optional["Communicator"]:
        """MPI_Comm_create: new comm over a subgroup (None if empty)."""
        self._check_alive()
        if group.size == 0:
            return None
        for r in group.world_ranks:
            if self.group.rank_of(r) == UNDEFINED:
                raise MPIError(
                    ErrorCode.ERR_GROUP,
                    f"rank {r} not in parent {self.name}",
                )
        return Communicator(self.runtime, group, name=name, parent=self)

    def split(self, colors: Sequence[int], keys: Optional[Sequence[int]] = None
              ) -> List[Optional["Communicator"]]:
        """MPI_Comm_split, driver mode: per-rank colors/keys vectors.

        Returns one entry per local rank: the communicator that rank
        landed in (ranks sharing a color share the object), or None for
        color=UNDEFINED. Single-controller makes the exchange the
        reference does (allgather of color/key) a local sort.
        """
        self._check_alive()
        if len(colors) != self.size:
            raise MPIError(
                ErrorCode.ERR_ARG,
                f"need {self.size} colors, got {len(colors)}",
            )
        keys = list(keys) if keys is not None else [0] * self.size
        buckets: Dict[int, List[Tuple[int, int]]] = {}
        for local, (color, key) in enumerate(zip(colors, keys)):
            if color == UNDEFINED:
                continue
            if color < 0:
                raise MPIError(ErrorCode.ERR_ARG, f"negative color {color}")
            buckets.setdefault(color, []).append((key, local))
        result: List[Optional[Communicator]] = [None] * self.size
        for color in sorted(buckets):
            members = sorted(buckets[color])  # by (key, local-rank), MPI rule
            g = Group([self.group.world_rank(l) for _, l in members])
            sub = Communicator(
                self.runtime, g,
                name=f"split({self.name},{color})", parent=self,
            )
            for _, local in members:
                result[local] = sub
        return result

    def split_type_shared(self) -> List["Communicator"]:
        """MPI_Comm_split_type(COMM_TYPE_SHARED): group by host process."""
        eps = {e.rank: e for e in self.runtime.endpoints}
        colors = [
            eps[self.group.world_rank(i)].process_index
            for i in range(self.size)
        ]
        return self.split(colors)  # type: ignore[return-value]

    def free(self) -> None:
        self._check_alive()
        fb = getattr(self, "_fusion_buffer", None)
        if fb is not None:
            # pending fused tensors drain before the comm dies —
            # freeing with queued submissions is a late flush, not a
            # lost handle
            fb.flush()
            self._fusion_buffer = None
        if self.spans_processes:
            # outstanding i-collectives must drain FIRST — before the
            # _on_free hooks free the hier shadow comm and the cid
            # leaves the registry, both of which a mid-flight spanning
            # collective still uses (MPI_Comm_free after pending
            # nonblocking ops is erroneous; draining turns it into a
            # late completion, not a crash)
            from ..coll import nbc as _nbc

            _nbc.drain_comm(self)
        for kv_id, value in list(self._attrs.items()):
            kv = _keyval_table.get(kv_id)
            if kv and kv.delete_fn:
                kv.delete_fn(self, kv, value, kv.extra_state)
        self._attrs.clear()
        # runtime-private dependents (e.g. the hier module's shadow
        # comm) registered teardown hooks: free them with their owner
        # or they leak registry entries for the owner's lifetime
        for cb in getattr(self, "_on_free", ()):
            try:
                cb()
            except MPIError:
                pass  # already freed
        _comm_registry.pop(self.cid, None)
        _sentinel.clear_chain(self.cid)
        from ..coll import plan as _coll_plan

        # frozen schedule plans die with their comm: a reused cid must
        # never fire a dead comm's compiled programs or wire rounds
        _coll_plan.clear_comm(self.cid)
        self._freed = True
        _comm_count.add(-1)

    # -- attributes (MPI keyvals) ------------------------------------------
    def set_attr(self, keyval: Keyval, value: Any) -> None:
        self._check_alive()
        self._attrs[keyval.id] = value

    def get_attr(self, keyval: Keyval) -> Tuple[bool, Any]:
        v = self._attrs.get(keyval.id, _MISSING)
        if v is _MISSING:
            return False, None
        return True, v

    def delete_attr(self, keyval: Keyval) -> None:
        v = self._attrs.pop(keyval.id, _MISSING)
        if v is not _MISSING and keyval.delete_fn:
            keyval.delete_fn(self, keyval, v, keyval.extra_state)

    # -- QoS (multi-tenant service plane) ----------------------------------
    @property
    def qos_class(self) -> Optional[str]:
        return self._qos_class

    def set_qos_class(self, cls: Optional[str]) -> None:
        """Stamp this communicator's QoS class (``wire_qos_classes``
        lane class + fair-share weight): a tenant job stamps its
        comms at admission, overriding the process-wide
        ``wire_qos_class`` cvar for exactly this comm tree (children
        created afterwards inherit). None reverts to the cvar."""
        self._check_alive()
        self._qos_class = str(cls) if cls else None

    # -- errors ------------------------------------------------------------
    def set_errhandler(self, handler: Errhandler) -> None:
        self.errhandler = handler

    def call_errhandler(self, err: MPIError) -> None:
        self.errhandler.invoke(self, err)

    def abort(self, errorcode: int = 1):
        """MPI_Abort analogue."""
        raise SystemExit(
            f"MPI_Abort on {self.name} with errorcode {errorcode}"
        )

    # -- ULFM fault tolerance (MPIX_Comm_revoke/shrink/agree) --------------
    def _member_procs(self) -> List[int]:
        """Process indices owning this comm's ranks (spanning comms;
        [my process] otherwise)."""
        if not self.spans_processes:
            return [int(self.runtime.bootstrap.get("process_index", 0))]
        from ..runtime.wire import proc_topology

        return proc_topology(self).procs

    @property
    def revoked(self) -> bool:
        return self._revoked

    def revoke(self) -> None:
        """``MPIX_Comm_revoke``: epoch-stamped poison. Marks the comm
        revoked locally (every pending bounded wait on its wire
        channels raises ERR_REVOKED within one slice, and queued
        progress-engine schedules complete in error without running),
        then pushes TAG_FT_REVOKE frames to every live peer process so
        THEIR pending ops are interrupted too. Idempotent; never
        raises on a dead peer — a corpse needs no poison."""
        self._check_alive()
        from ..ft import ulfm as _ulfm

        st = _ulfm.state()
        self._revoked = True
        first = st.apply_revoke(self.cid, st.epoch)
        agent = getattr(self.runtime, "agent", None)
        if not first or agent is None or not self.spans_processes:
            return
        from ..runtime.wire import proc_topology

        topo = proc_topology(self)
        for p in topo.peers:
            if p in st.failed:
                continue
            try:
                agent.ft_revoke_notify(p, self.cid, st.epoch)
            except MPIError:
                pass  # peer died between the check and the send
        _log.verbose(1, f"{self.name} revoked (epoch {st.epoch})")

    def get_failed(self) -> List[int]:
        """``MPIX_Comm_get_failed``: this comm's ranks owned by
        processes the job epoch marks failed."""
        self._check_alive()
        from ..ft import ulfm as _ulfm

        if not self.spans_processes:
            return []
        from ..runtime.wire import proc_topology

        topo = proc_topology(self)
        dead = set(_ulfm.state().dead_for(set(topo.owner),
                                          self._ft_epoch0))
        return [i for i in range(self.size) if topo.owner[i] in dead]

    def agree(self, flag: bool = True, *, aseq: Optional[int] = None,
              timeout_ms: int = 60_000) -> bool:
        """``MPIX_Comm_agree``: fault-tolerant AND of ``flag`` across
        the comm's LIVE member processes, arbitrated by the HNP
        coordinator (failed contributors are excused as the epoch
        marks them). Works on a revoked communicator — it is the one
        collective ULFM guarantees through failures."""
        self._check_alive()
        agent = getattr(self.runtime, "agent", None)
        if agent is None or not self.spans_processes:
            return bool(flag)
        if aseq is None:
            aseq = self._agree_counter = getattr(
                self, "_agree_counter", 0) + 1
        doc = agent.ft_agree(self.cid, aseq, 1 if flag else 0,
                             self._member_procs(), timeout_ms=timeout_ms)
        return bool(doc.get("flag", 0))

    def shrink(self, name: str = "", *,
               timeout_ms: int = 60_000) -> "Communicator":
        """``MPIX_Comm_shrink``: agree on the surviving group through
        the coordinator (every survivor receives ONE consistent
        epoch/failed snapshot), build a new communicator over it with
        a fresh epoch-derived cid — fresh wire channels, rebuilt
        hier/leader topology via the normal per-comm coll selection —
        and barrier the survivors on it to prove the wiring. Valid on
        a revoked (or failure-poisoned) communicator; the parent is
        left revoked."""
        self._check_alive()
        from ..ft import ulfm as _ulfm

        agent = getattr(self.runtime, "agent", None)
        if agent is None or not self.spans_processes:
            # no failure domain beyond this process: ULFM shrink of a
            # fault-free comm is a plain dup
            return self.dup(name or f"shrink({self.name})")
        from ..runtime.wire import proc_topology

        topo = proc_topology(self)
        aseq = self._agree_counter = getattr(
            self, "_agree_counter", 0) + 1
        doc = agent.ft_agree(self._ft_lineage, aseq, 1, topo.procs,
                             timeout_ms=timeout_ms)
        epoch = int(doc.get("epoch", 0))
        # dead FOR THIS COMM, from the agreement's ONE shared
        # snapshot: the transient failed set PLUS every process whose
        # failure episode began at/after this comm's birth epoch —
        # under the restart policy a corpse moves failed->restarted
        # within milliseconds of promotion, and a shrink that
        # re-included it would park the survivor barrier on a process
        # that never builds this cid
        failed = set(int(p) for p in doc.get("failed", ()))
        failed |= {p for p, e in _ulfm.failed_at_of(doc).items()
                   if e >= self._ft_epoch0}
        survivors = Group([
            self.group.world_rank(i) for i in range(self.size)
            if topo.owner[i] not in failed
        ])
        if survivors.size == 0:
            raise MPIError(ErrorCode.ERR_GROUP,
                           f"shrink({self.name}): no survivors")
        new = Communicator(
            self.runtime, survivors,
            name=name or f"shrink({self.name})", parent=self,
            cid=_ulfm.ft_cid(epoch, self._ft_lineage),
        )
        if new.spans_processes:
            wire = self.runtime.wire
            wire.proc_barrier(new, proc_topology(new).procs,
                              timeout_ms=timeout_ms)
        _log.verbose(
            1, f"shrink({self.name}) -> {new.name} cid={new.cid} "
               f"size={new.size} (epoch {epoch}, "
               f"failed procs {sorted(failed)})")
        return new

    # -- point-to-point (dispatched through the selected PML engine) -------
    @property
    def pml(self):
        """Per-comm PML engine, installed on first use
        (mca_pml_base_select analogue)."""
        eng = getattr(self, "_pml", None)
        if eng is None:
            self._check_alive()
            if self.submesh is None:
                raise MPIError(
                    ErrorCode.ERR_COMM,
                    f"{self.name} has no members on this controller "
                    "process — its operations can only be invoked on "
                    "the processes that own its ranks",
                )
            from ..p2p import pml as pml_mod

            eng = pml_mod.comm_select(self)
            self._pml = eng
        return eng

    def isend(self, data, dest: int, tag: int = 0, *, rank: int, **kw):
        """Nonblocking send issued by ``rank`` (driver mode: the acting
        rank is explicit because one controller plays every rank)."""
        self._check_usable()
        return self.pml.isend(data, dest, tag, src=rank, **kw)

    def send(self, data, dest: int, tag: int = 0, *, rank: int, **kw):
        self._check_usable()
        return self.pml.send(data, dest, tag, src=rank, **kw)

    def irecv(self, source: int = -1, tag: int = -1, *, rank: int):
        self._check_usable()
        return self.pml.irecv(source, tag, dst=rank)

    def recv(self, source: int = -1, tag: int = -1, *, rank: int):
        self._check_usable()
        return self.pml.recv(source, tag, dst=rank)

    def iprobe(self, source: int = -1, tag: int = -1, *, rank: int):
        self._check_usable()
        return self.pml.iprobe(source, tag, dst=rank)

    def sendrecv(self, sendbufs, dests, sendtag: int = 0,
                 sources=None, recvtag: int = -1):
        """MPI_Sendrecv, driver mode: EVERY rank's exchange in one call
        (like split's per-rank vectors) — all sends post first, then
        all recvs complete, which is what makes it deadlock-free. A
        per-rank blocking sendrecv cannot work under a single
        controller: rank 0's recv would block before rank 1 ever ran.

        sendbufs/dests (and optional sources): sequences of length
        ``size``. Returns (values, statuses) lists.
        """
        self._check_usable()
        if self.spans_processes:
            raise MPIError(
                ErrorCode.ERR_NOT_AVAILABLE,
                "driver-mode sendrecv acts as every rank at once; on a "
                "communicator spanning controller processes use "
                "per-rank isend/recv (each process acts only as its "
                "local ranks)",
            )
        n = self.size
        if (len(sendbufs) != n or len(dests) != n
                or (sources is not None and len(sources) != n)):
            raise MPIError(
                ErrorCode.ERR_ARG,
                f"sendrecv needs {n} sendbufs/dests/sources "
                "(one per rank)",
            )
        sreqs = [
            self.pml.isend(sendbufs[r], dests[r], sendtag, src=r)
            for r in range(n)
        ]
        values, statuses = [], []
        for r in range(n):
            src = sources[r] if sources is not None else -1
            v, st = self.pml.recv(src, recvtag, dst=r)
            values.append(v)
            statuses.append(st)
        for sr in sreqs:
            sr.wait()
        return values, statuses

    # -- collectives (dispatch through the installed c_coll table) ---------
    def _coll(self, op_name: str) -> Callable:
        self._check_usable()
        fn = self.c_coll.get(op_name)
        if fn is None:
            raise MPIError(
                ErrorCode.ERR_INTERN,
                f"no {op_name} implementation installed on {self.name}",
            )
        if not self.spans_processes:
            # steady-state compiled dispatch (coll/plan): a signature
            # seen before fires its frozen compiled program — the
            # interpreted decision path runs once per (signature,
            # cvar generation), not once per call
            from ..coll import plan as _plan

            if _sentinel.enabled:
                # contract sentinel: in-process collectives fold into
                # the comm's signature chain too (chain determinism,
                # the post-hoc journal record); spanning comms note
                # inside nbc.run_blocking where the args are bound
                def run(comm_, *a, **k):
                    _sentinel.note(self, op_name, a, k)
                    return _plan.dispatch(comm_, op_name, fn, a, k)
            else:
                def run(comm_, *a, **k):
                    return _plan.dispatch(comm_, op_name, fn, a, k)
        else:
            # fast ULFM fail: a collective involves every member, so a
            # known-failed member process fails the op NOW with the
            # typed error instead of posting a schedule doomed to park
            from ..ft import ulfm as _ulfm

            _ulfm.state().check_wait(
                self.cid, self._member_procs(),
                f"collective {op_name} on {self.name} with member process",
                epoch0=self._ft_epoch0)
            # spanning comms: EVERY collective — blocking or not — goes
            # through the async progress engine as "post schedule +
            # wait", so blocking and nonblocking calls execute in
            # posting order on every process (their wire exchanges
            # share one per-cid channel, and two concurrently-running
            # collectives would interleave frames on it) and there is
            # ONE round-advancing code path (coll/nbc + runtime/progress)
            from ..coll import nbc as _nbc

            def run(comm_, *a, **k):
                return _nbc.run_blocking(self, op_name, fn,
                                         (comm_,) + a, k)
        if self.cid < 0:
            # runtime-internal comms (the hier shadow) write no call
            # span: only USER-visible collectives do, the rule
            # coll_compiled_cache_hits already keeps
            return run

        def call(comm_, *a, **k):
            with _obs.span(_spans.COLL_CALL, op=op_name,
                           cid=self.cid,
                           bytes=_spans.nbytes(a[0]) if a else 0):
                return run(comm_, *a, **k)

        return call

    def _run_serialized(self, fn, *args, **kw):
        """Run ``fn`` in the comm's collective posting order, blocking
        (the two-phase collective-IO path): fire + wait through the
        progress engine on spanning comms, a direct call otherwise."""
        if not self.spans_processes:
            return fn(*args, **kw)
        from ..coll import nbc as _nbc

        return _nbc.run_blocking(
            self, getattr(fn, "__name__", "serialized"), fn, args, kw)

    def _submit_serialized(self, fn, *args, **kw):
        """Nonblocking run of ``fn`` in the comm's collective posting
        order (the nonblocking collective-IO path): returns a Request
        backed by a schedule posted to the progress engine."""
        from ..coll import nbc as _nbc

        return _nbc.submit(self, getattr(fn, "__name__", "serialized"),
                           fn, args, kw)

    def _async(self, value):
        """Wrap already-dispatched future arrays as a Request (XLA
        async dispatch is the round schedule; see coll/nbc)."""
        from ..coll import nbc as _nbc

        return _nbc.async_request(value)

    def allreduce(self, x, op=None, **kw):
        from .. import ops as ops_mod

        return self._coll("allreduce")(self, x, op or ops_mod.SUM, **kw)

    def reduce(self, x, op=None, root: int = 0, **kw):
        from .. import ops as ops_mod

        return self._coll("reduce")(self, x, op or ops_mod.SUM, root, **kw)

    def bcast(self, x, root: int = 0, **kw):
        return self._coll("bcast")(self, x, root, **kw)

    def allgather(self, x, **kw):
        return self._coll("allgather")(self, x, **kw)

    def gather(self, x, root: int = 0, **kw):
        return self._coll("gather")(self, x, root, **kw)

    def scatter(self, x, root: int = 0, **kw):
        return self._coll("scatter")(self, x, root, **kw)

    def reduce_scatter_block(self, x, op=None, **kw):
        from .. import ops as ops_mod

        return self._coll("reduce_scatter_block")(
            self, x, op or ops_mod.SUM, **kw
        )

    def alltoall(self, x, **kw):
        return self._coll("alltoall")(self, x, **kw)

    def scan(self, x, op=None, **kw):
        from .. import ops as ops_mod

        return self._coll("scan")(self, x, op or ops_mod.SUM, **kw)

    def exscan(self, x, op=None, **kw):
        from .. import ops as ops_mod

        return self._coll("exscan")(self, x, op or ops_mod.SUM, **kw)

    def barrier(self) -> None:
        self._coll("barrier")(self)

    # -- small-message fusion (coll/fusion.py) -----------------------------
    def fusion_buffer(self):
        """This communicator's small-message fusion buffer (Horovod
        fusion-buffer / BTL-coalescing analogue): collectives below
        ``coll_fusion_threshold`` pack into one fused device
        collective per (op, dtype). Created lazily, one per comm;
        FusionBuffer is documented thread-safe, so first use may be
        concurrent — creation must not orphan a racing instance."""
        fb = getattr(self, "_fusion_buffer", None)
        if fb is None:
            from ..coll.fusion import FusionBuffer

            with _fusion_create_lock:
                fb = getattr(self, "_fusion_buffer", None)
                if fb is None:
                    fb = FusionBuffer(self)
                    self._fusion_buffer = fb
        return fb

    def fused_allreduce(self, x, op=None):
        """Allreduce through the fusion buffer: small tensors coalesce
        with concurrent submissions (flush with
        ``comm.fusion_buffer().flush()`` or the handle's ``result()``);
        large ones dispatch immediately. Returns a
        :class:`~..coll.fusion.FusedHandle`."""
        return self.fusion_buffer().allreduce(x, op)

    # -- v-variant collectives (per-rank counts; ragged driver edge) -------
    def alltoallv(self, sendbufs, sendcounts, *, recv_capacity=None):
        """MPI_Alltoallv: ``sendbufs[i]`` holds rank i's chunks for
        ranks 0..n-1 back to back, ``sendcounts[i][j]`` elements for
        rank j. Returns ``recv[i]`` = chunks from each source, in
        source order, on rank i's device.

        Device form, with ``recv_capacity``: ``sendbufs`` is one
        rank-sharded ``(size, m)`` array, ``sendcounts`` a rank-sharded
        ``(size, size)`` int32 array; returns ``(recv, counts)``, a
        rank-sharded ``(size, recv_capacity)`` receive buffer and the
        counts each rank received, and no count or element is read on
        the host (``coll/vcoll.alltoallv_device``)."""
        if recv_capacity is None:
            return self._coll("alltoallv")(self, sendbufs, sendcounts)
        if self.spans_processes:
            raise MPIError(
                ErrorCode.ERR_NOT_AVAILABLE,
                f"device-form alltoallv on {self.name}, which spans "
                "controller processes: only the list form crosses "
                "processes",
            )
        return self._coll("alltoallv")(
            self, sendbufs, sendcounts, recv_capacity=recv_capacity)

    def allgatherv(self, sendbufs):
        """MPI_Allgatherv: ragged per-rank buffers, concatenated in
        rank order (identical on all ranks — returned once)."""
        return self._coll("allgatherv")(self, sendbufs)

    def gatherv(self, sendbufs, root: int = 0):
        return self._coll("gatherv")(self, sendbufs, root)

    def scatterv(self, sendbuf, counts, root: int = 0):
        """MPI_Scatterv: root's buffer split into counts[i] elements
        per rank; returns one array per rank."""
        return self._coll("scatterv")(self, sendbuf, counts, root)

    def reduce_scatter(self, x, recvcounts, op=None):
        """General MPI_Reduce_scatter with per-rank recv counts."""
        from .. import ops as ops_mod

        return self._coll("reduce_scatter")(
            self, x, recvcounts, op or ops_mod.SUM
        )

    # -- nonblocking collectives (libnbc analogue; coll/nbc.py) ------------
    # In-process comms: XLA dispatch is already asynchronous — the
    # compiled program IS the libnbc round schedule, and the Request
    # wraps its future arrays. Spanning comms: the whole schedule posts
    # to the async progress engine (runtime/progress.py) — dispatch
    # returns before any wire traffic; execution happens in posting
    # order, at wait() (polling mode) or off the caller on the
    # dedicated progress thread (``progress_thread`` cvar).
    def _icoll(self, name: str, *args, **kw):
        from ..coll import nbc as _nbc

        return _nbc.icoll(self, name, args, kw)

    def iallreduce(self, x, op=None, **kw):
        from .. import ops as ops_mod

        return self._icoll("allreduce", x, op or ops_mod.SUM, **kw)

    def ireduce(self, x, op=None, root: int = 0, **kw):
        from .. import ops as ops_mod

        return self._icoll("reduce", x, op or ops_mod.SUM, root, **kw)

    def ibcast(self, x, root: int = 0, **kw):
        return self._icoll("bcast", x, root, **kw)

    def iallgather(self, x, **kw):
        return self._icoll("allgather", x, **kw)

    def igather(self, x, root: int = 0, **kw):
        return self._icoll("gather", x, root, **kw)

    def iscatter(self, x, root: int = 0, **kw):
        return self._icoll("scatter", x, root, **kw)

    def ireduce_scatter_block(self, x, op=None, **kw):
        from .. import ops as ops_mod

        return self._icoll("reduce_scatter_block", x,
                           op or ops_mod.SUM, **kw)

    def ireduce_scatter(self, x, recvcounts, op=None):
        from .. import ops as ops_mod

        return self._icoll("reduce_scatter", x, recvcounts,
                           op or ops_mod.SUM)

    def ialltoall(self, x, **kw):
        return self._icoll("alltoall", x, **kw)

    def iscan(self, x, op=None, **kw):
        from .. import ops as ops_mod

        return self._icoll("scan", x, op or ops_mod.SUM, **kw)

    def iexscan(self, x, op=None, **kw):
        from .. import ops as ops_mod

        return self._icoll("exscan", x, op or ops_mod.SUM, **kw)

    def ialltoallv(self, sendbufs, sendcounts):
        return self._icoll("alltoallv", sendbufs, sendcounts)

    def iallgatherv(self, sendbufs):
        return self._icoll("allgatherv", sendbufs)

    # -- persistent collectives (MPI-4 *_init; coll/nbc.persistent) --------
    # The plan — resolved dispatch entry, op object, bound buffers —
    # is built ONCE here; Request.start() fires it against the
    # buffers' CURRENT contents each time without blocking (compiled
    # programs / fusion plans are cached, so starts after the first
    # fire cached plans).
    def allreduce_init(self, x, op=None, **kw):
        from .. import ops as ops_mod
        from ..coll import nbc as _nbc

        return _nbc.persistent(self, "allreduce",
                               (x, op or ops_mod.SUM), kw)

    def bcast_init(self, x, root: int = 0, **kw):
        from ..coll import nbc as _nbc

        return _nbc.persistent(self, "bcast", (x, root), kw)

    def allgather_init(self, x, **kw):
        from ..coll import nbc as _nbc

        return _nbc.persistent(self, "allgather", (x,), kw)

    def reduce_scatter_init(self, x, recvcounts, op=None):
        from .. import ops as ops_mod
        from ..coll import nbc as _nbc

        return _nbc.persistent(self, "reduce_scatter",
                               (x, recvcounts, op or ops_mod.SUM))

    def alltoall_init(self, x, **kw):
        from ..coll import nbc as _nbc

        return _nbc.persistent(self, "alltoall", (x,), kw)

    def barrier_init(self):
        from ..coll import nbc as _nbc

        return _nbc.persistent(self, "barrier", ())

    def ibarrier(self):
        """Nonblocking barrier that really is nonblocking: the
        compiled barrier program is dispatched asynchronously and the
        returned request's readiness is the dispatch's readiness (the
        reference's libnbc round schedule, ``nbc.c``, becomes the
        compiled program; XLA async dispatch is the progress engine).
        Spanning comms post the barrier schedule to the progress
        engine — an ibarrier posted between two iallreduces keeps its
        posting-order slot across every process. Providers without an
        async dispatch path run the blocking barrier on a completion
        thread instead — either way ibarrier returns before the
        barrier completes."""
        self._check_usable()
        from ..coll import nbc as _nbc

        if self.spans_processes:
            return _nbc.icoll(self, "barrier", ())
        fn = self.c_coll.get("ibarrier")
        if fn is not None:
            if _sentinel.enabled:
                # the native async-dispatch branch bypasses both the
                # _coll wrapper and nbc.icoll — without this note it
                # would be the one unhashed collective entry
                _sentinel.note(self, "barrier")
            return _nbc.async_request(fn(self))

        import threading

        from ..request.request import Request

        done = threading.Event()
        errs: list = []

        def run() -> None:
            try:
                self.barrier()
            except Exception as exc:  # surfaced at wait()
                errs.append(exc)
            finally:
                done.set()

        threading.Thread(target=run, daemon=True).start()

        def block() -> None:
            done.wait()
            if errs:
                raise errs[0]

        # a failed barrier must surface through test() as well as
        # wait(): the progress hook (polled by test) raises the stored
        # error — the MPI_ERRORS_ARE_FATAL convention this layer uses
        # — instead of reporting completion or pending forever
        def progress(req) -> None:
            if done.is_set() and errs:
                raise errs[0]

        return Request(
            progress_fn=progress,
            ready_fn=lambda: done.is_set() and not errs,
            block_fn=block,
        )

    def __repr__(self) -> str:
        return (
            f"Communicator({self.name}, cid={self.cid}, size={self.size})"
        )


_MISSING = object()
_keyval_table: Dict[int, Keyval] = {}


def create_keyval(copy_fn=None, delete_fn=None, extra_state=None) -> Keyval:
    kv = Keyval(copy_fn, delete_fn, extra_state)
    _keyval_table[kv.id] = kv
    return kv


def free_keyval(kv: Keyval) -> None:
    _keyval_table.pop(kv.id, None)
