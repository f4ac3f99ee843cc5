"""btl/nativewire — the zero-copy native datapath (``btl/sm`` +
``btl/tcp`` writev roles, played by ``native/``).

One component, two transports, selected per peer from the modex
business cards exactly like :meth:`WireRouter._btl_for`:

* **co-hosted peers** ride a shared-memory SPSC byte ring
  (``native/btl_shm.cc``), a message's payload in ONE native call a
  side: the sender's ``write_msg`` writes every SGC2 fragment record
  of the message straight from the source buffer into the mapped
  ring, the receiver's ``read_msg`` copies every fragment directly
  into the reassembly buffer and checksums it inside that copy — no
  Python-side copy and no Python call per fragment; a call hands back
  early only for what Python has to handle (a ring that stayed full,
  another tag's record at the head, an empty slice, a dead peer).
* **cross-host peers** ride vectored socket IO over the existing OOB
  mesh (``native/btl_tcp.cc``): ``wire_sendv`` writev's the frame
  header plus scatter-gather parts in one syscall (byte-identical on
  the wire to ``ep.send(dst, tag, b"".join(parts))``), and
  ``wire_recv_frag`` lands queued SGC2 payloads straight into the
  reassembly buffer.

The SGH2 framing is BYTE-IDENTICAL to the portable staged path
(:class:`~.components.FrameTemplate` is the single framing authority;
``b"".join`` of each scatter-gather list reproduces the staged frame
bit for bit), and header frames ALWAYS ride the portable OOB send —
so sentinel SIG1 piggybacks, any-source header peeks, QoS lane
striping and tpu-doctor flow ids are untouched. Only fragment
payloads leave Python.

Graceful degradation is structural: the component withdraws from MCA
selection (``query`` -> None) when ``libompitpu_native.so`` lacks the
``wire_*``/``shmring_*`` symbols, when ``btl_nativewire_enable``/
``OMPITPU_NATIVEWIRE=0`` turns it off, or — per peer — when the
peer's modex card does not advertise the capability. Every fallback
lands on the portable staged-frames path, which this module can also
SPEAK (legacy SGH1, portable SGH2) because it subclasses
:class:`~.components.DcnBtl`.
"""

from __future__ import annotations

import atexit
import ctypes
import os
import threading
import time as _time
import uuid
import weakref
from typing import Dict, Optional, Tuple

import numpy as np

from .. import obs as _obs
from ..obs import spans as _spans
from ..obs import watchdog as _watchdog
from ..mca import component as mca_component
from ..mca import pvar as _pvar
from ..mca import var as mca_var
from ..utils.errors import ErrorCode, MPIError
from . import base
from . import components as _c
from .components import (
    _CHUNK2_MAGIC, _HDR2_MAGIC, _check_user_tag, _frags_inflight,
    _template_for, _unpack_array_header, _zero_copy_strict, DcnBtl,
    stashed_recv,
)

#: modex business-card key: ``"token:slots:ring_bytes"`` — the
#: receiver-side ring geometry plus a per-process token namespacing
#: its /dev/shm ring names (a restarted replacement process gets a
#: fresh token, so stale rings can never be re-attached)
CARD_KEY = "nativewire"

_RING_SLOTS_DEFAULT = 4
_RING_BYTES_DEFAULT = 8 * 1024 * 1024
_EVENT_SLOTS_DEFAULT = 1 << 16   # 2 MiB of 32-byte records
_SEND_TIMEOUT_MS = 30_000
#: first look (and every look while the peer's frames keep arriving)
#: at our own inbound rings when a send finds the peer's ring full
_FULL_RING_LOOK_MS = 5
#: exit-time grace for tx rings holding bytes no consumer mapped yet —
#: covers a receiver still inside interpreter/jax startup, not a hang
_DRAIN_TIMEOUT_MS = 10_000

#: native-datapath ledger: bytes/frames that crossed through the
#: native wire, and the honesty witness for the zero-copy claim —
#: every host-side materialization the fast path was FORCED into
#: (dlpack refused, non-contiguous source, ring cross-tag restash)
#: counts, so ``wire_native_copies_per_mib`` near 0 is evidence, not
#: advertising.
_native_bytes = _pvar.counter(
    "wire_native_bytes",
    "payload bytes moved by the nativewire datapath (shm-ring writev "
    "+ vectored socket writev + native fragment reassembly)",
)
_native_frames = _pvar.counter(
    "wire_native_frames",
    "SGC2 fragment frames moved by the nativewire datapath",
)
_native_msg_calls = _pvar.counter(
    "wire_native_msg_calls",
    "native calls made for message payloads on the shm-ring leg "
    "(ring.write_msg at the sender, ring.read_msg at the receiver)",
)
_native_msgs = _pvar.counter(
    "wire_native_msgs",
    "messages whose payload crossed the shm-ring leg (sent plus "
    "received); wire_native_msg_calls / wire_native_msgs is 1.0 when "
    "every message crossed whole, above it where a call handed back "
    "mid-message: a ring that stayed full, another tag's record at "
    "the head, a slice with no more fragments",
)
_fallback_copies = _pvar.counter(
    "wire_native_fallback_copies",
    "host-side byte materializations the native path was forced "
    "into: dlpack handoff refused (device array, exotic dtype), "
    "non-contiguous source compaction, ring cross-tag restash",
)
_copies_per_mib = _pvar.PVARS.register(
    "wire_native_copies_per_mib", _pvar.PvarClass.LEVEL,
    "forced host copies per MiB of native wire traffic (the zero-copy "
    "witness: ~0 when the byte path truly bypasses Python)",
    getter=lambda: (_fallback_copies.read()
                    / max(1.0, _native_bytes.read() / float(1 << 20))),
)

# ---------------------------------------------------------------------------
# C-side telemetry fold: the counters block lives IN the ring headers
# (native/btl_shm.cc RingHdr slack) and the tcp endpoint struct
# (native/oob_endpoint.h) — relaxed single-writer u64s the transports
# bump on every writev/read_frag, always on. Python never touches them
# on the byte path; these getter pvars fold the live blocks on READ
# (tpu_info snapshot, sampler tick), so the fleet metrics plane sees
# native stalls without re-adding a Python emit site to the datapath.
# ---------------------------------------------------------------------------

_tele_lock = threading.Lock()
#: live producer-side rings: their w_* counters are THIS process's
#: work (the peer's r_* half belongs to the peer's own fold)
_live_tx: set = set()
#: live consumer-side rings: the r_* half is ours
_live_rx: set = set()
#: endpoints that carried native frames (tcp-leg wire_stats source);
#: weak — an endpoint's lifetime belongs to the OOB layer
_seen_eps: "weakref.WeakSet" = weakref.WeakSet()
#: [stalls, stall_ns] folded out of rings at retire time, so closing
#: a ring never makes the process counters go backwards
_retired = [0, 0]
#: monotonic floor for the fold (a GC'd endpoint drops its share;
#: counters still must never decrease between two reads)
_mono = [0, 0]


def _track_ring(ring, tx: bool) -> None:
    if ring is None:
        return
    with _tele_lock:
        (_live_tx if tx else _live_rx).add(ring)


def _retire_ring(ring, tx: bool) -> None:
    """Fold a closing ring's final stall totals into the retired base
    and drop it from the live set (stats() reads shared memory — it
    must run BEFORE close unmaps)."""
    if ring is None:
        return
    try:
        st = ring.stats()
    except Exception:
        st = None
    with _tele_lock:
        (_live_tx if tx else _live_rx).discard(ring)
        if st is not None:
            pre = "w_" if tx else "r_"
            _retired[0] += int(st.get(pre + "stalls", 0))
            _retired[1] += int(st.get(pre + "stall_ns", 0))


def _track_ep(ep) -> None:
    try:
        _seen_eps.add(ep)
    except TypeError:
        pass  # non-weakrefable test double: no tcp stats to fold


def _stall_fold() -> Tuple[int, int]:
    """(stalls, stall_ns) this process has spent blocked on the native
    datapath: full-ring waits on tx rings, empty-ring waits on rx
    rings, queue-cv waits on tcp endpoints, plus the retired base."""
    with _tele_lock:
        rings = ([(r, "w_") for r in _live_tx]
                 + [(r, "r_") for r in _live_rx])
        eps = list(_seen_eps)
        stalls, ns = _retired[0], _retired[1]
    for ring, pre in rings:
        try:
            st = ring.stats()
        except Exception:
            continue
        stalls += int(st.get(pre + "stalls", 0))
        ns += int(st.get(pre + "stall_ns", 0))
    for ep in eps:
        try:
            ws = ep.wire_stats()
        except Exception:
            continue
        stalls += int(ws.get("rx_stalls", 0))
        ns += int(ws.get("rx_stall_ns", 0))
    with _tele_lock:
        _mono[0] = stalls = max(_mono[0], stalls)
        _mono[1] = ns = max(_mono[1], ns)
    return stalls, ns


def _hwm_fold() -> float:
    """Worst occupancy high-water fraction across live rings (0.0 with
    no rings): how close the busiest ring ever came to backpressure."""
    with _tele_lock:
        rings = list(_live_tx) + list(_live_rx)
    frac = 0.0
    for ring in rings:
        try:
            cap = float(ring.capacity)
            if cap > 0:
                frac = max(frac, float(ring.stats().get("hwm", 0)) / cap)
        except Exception:
            continue
    return min(1.0, frac)


_ring_stalls_pvar = _pvar.PVARS.register(
    "wire_native_ring_stalls", _pvar.PvarClass.COUNTER,
    "native-datapath stalls: records that found their tx ring full "
    "(one per record, however many wait slices it took), reads that "
    "found their rx ring or tcp frame queue empty — folded on read "
    "from the C-side counter blocks, zero Python on the byte path",
    getter=lambda: _stall_fold()[0],
)
_stall_seconds_pvar = _pvar.PVARS.register(
    "wire_native_stall_seconds", _pvar.PvarClass.TIMER,
    "cumulative seconds the native datapath spent blocked waiting on "
    "a peer (the time complement of wire_native_ring_stalls; a full "
    "tx ring counts from first sighting to the write, the sender's "
    "draining of its own inbound rings in between included)",
    getter=lambda: _stall_fold()[1] / 1e9,
)
_hwm_frac_pvar = _pvar.PVARS.register(
    "wire_native_ring_hwm_frac", _pvar.PvarClass.LEVEL,
    "worst shm-ring occupancy high-water mark as a fraction of ring "
    "capacity (1.0 = some ring completely filled; sustained highs "
    "mean the consumer is the bottleneck or rings are undersized)",
    getter=_hwm_fold,
)


def register_nativewire_vars() -> None:
    """The component's own cvars (its standard ``btl_nativewire_*``
    size/ranking vars come from :func:`base.register_module_vars`)."""
    mca_var.register(
        "btl_nativewire_enable", "bool", True,
        "Use the native zero-copy datapath (shm rings + vectored "
        "socket IO) for staged wire transfers when the native library "
        "provides it; off = the portable staged-frames path "
        "(OMPITPU_NATIVEWIRE=0 is the env spelling)",
    )
    mca_var.register(
        "btl_nativewire_ring_bytes", "size", _RING_BYTES_DEFAULT,
        "Capacity of each receive-side shared-memory ring (one ring "
        "per co-hosted sender per slot); fragments larger than a ring "
        "fall back to the vectored-socket loopback automatically",
    )
    mca_var.register(
        "btl_nativewire_ring_slots", "int", _RING_SLOTS_DEFAULT,
        "Shared-memory rings per co-hosted sender: wire channels hash "
        "across slots so independent lanes do not share one FIFO",
    )
    mca_var.register(
        "btl_nativewire_events", "bool", False,
        "Mmap one per-process native event ring (ompitpu-nativeev-v1) "
        "and have the C transports append a 32-byte record per SGC2 "
        "fragment (t_ns, tag, xfer, bytes, wait_ns; drop-oldest wrap); "
        "tpu-doctor expands dumps into wire-layer spans with paired "
        "flow ids. Off (default) = zero event-path work — the ring "
        "counter blocks stay on either way",
    )
    mca_var.register(
        "btl_nativewire_event_slots", "int", _EVENT_SLOTS_DEFAULT,
        "Record capacity of the native event ring (32 bytes each; a "
        "full ring overwrites its oldest records)",
    )


register_nativewire_vars()  # idempotent; read at modex + module bind


def nativewire_ready() -> bool:
    """Local capability: native symbols present AND not disabled.
    Never raises — a probe failure is just 'not available'."""
    if os.environ.get("OMPITPU_NATIVEWIRE", "1").strip().lower() in (
            "0", "false", "no", "off"):
        return False
    if not mca_var.get("btl_nativewire_enable", True):
        return False
    try:
        from ..native import wire_symbols_available

        return bool(wire_symbols_available())
    except Exception:
        return False


_token_lock = threading.Lock()
_token: Optional[str] = None


def _local_token() -> str:
    global _token
    with _token_lock:
        if _token is None:
            _token = f"{os.getpid():x}-{uuid.uuid4().hex[:8]}"
        return _token


_ev_lock = threading.Lock()
_ev_ring = None
_ev_tried = False


def _event_ring():
    """Lazily create + install the per-process native event ring when
    ``btl_nativewire_events`` turns it on (once per process, however
    many modules bind). The shm name is unlinked immediately after
    create — only this process's own mapping matters (records decode
    in-process at dump time), so nothing can leak in /dev/shm. The
    ring is handed to ``obs.nativeev`` so finalize dumps and watchdog
    postmortems read it without importing this module."""
    global _ev_ring, _ev_tried
    with _ev_lock:
        if _ev_tried:
            return _ev_ring
        _ev_tried = True
        if not mca_var.get("btl_nativewire_events", False):
            return None
        try:
            from ..native import NativeEventRing

            slots = int(mca_var.get("btl_nativewire_event_slots",
                                    _EVENT_SLOTS_DEFAULT)
                        or _EVENT_SLOTS_DEFAULT)
            name = f"/onwev-{_local_token()}"
            ring = NativeEventRing.create(name, max(64, slots))
            if ring is None:
                # leftover name from a crashed earlier run (the token
                # makes a LIVE collision impossible): clear and retry
                NativeEventRing.unlink(name)
                ring = NativeEventRing.create(name, max(64, slots))
            if ring is None:
                return None  # symbols absent or shm refused: stay off
            NativeEventRing.unlink(name)
            ring.install()
            from ..obs import nativeev as _nativeev

            _nativeev.set_ring(ring)
            _ev_ring = ring
            atexit.register(_uninstall_event_ring)
        except Exception:
            _ev_ring = None
        return _ev_ring


def _uninstall_event_ring() -> None:
    """Exit hook: detach the C emit sink before interpreter teardown
    starts unmapping things under it. The mapping itself stays (the
    obs finalize dump may still be reading records)."""
    with _ev_lock:
        ring = _ev_ring
    if ring is not None:
        try:
            ring.uninstall()
        except Exception:
            pass


def _reset_event_state_for_tests() -> None:
    global _ev_ring, _ev_tried
    from ..obs import nativeev as _nativeev

    with _ev_lock:
        if _ev_ring is not None:
            try:
                if _nativeev.get_ring() is _ev_ring:
                    _nativeev.set_ring(None)
                _ev_ring.uninstall()
                _ev_ring.close()
            except Exception:
                pass
        _ev_ring = None
        _ev_tried = False


def modex_entry() -> Dict[str, str]:
    """This process's business-card advertisement (empty when the
    capability is absent — peers key their per-peer fallback on the
    key's presence, the add_procs reachability discipline)."""
    if not nativewire_ready():
        return {}
    slots = int(mca_var.get("btl_nativewire_ring_slots",
                            _RING_SLOTS_DEFAULT) or _RING_SLOTS_DEFAULT)
    ring = int(mca_var.get("btl_nativewire_ring_bytes",
                           _RING_BYTES_DEFAULT) or _RING_BYTES_DEFAULT)
    return {CARD_KEY: f"{_local_token()}:{max(1, slots)}:{ring}"}


def _parse_card(entry) -> Optional[Tuple[str, int, int]]:
    try:
        token, slots, ring = str(entry).split(":")
        return token, max(1, int(slots)), max(1 << 16, int(ring))
    except Exception:
        return None  # malformed advertisement = not capable


def module_for(cards, my_pidx: int) -> Optional["NativeWireBtl"]:
    """The wire router's transport instance: None when the native
    datapath cannot run here (portable paths take over wholesale)."""
    if not nativewire_ready():
        return None
    mod = NativeWireBtl()
    mod.bind(cards, int(my_pidx))
    return mod


def _ring_name(token: str, src_pidx: int, slot: int) -> str:
    return f"/onw-{token}-{src_pidx}-{slot}"


def _ring_wait_info(ring, peer_pidx: int, direction: str) -> dict:
    """Watchdog payload for a blocked ring wait, resolved at DUMP
    time: which ring (the /onw name carries the owner token), which
    peer pid sits on the other end, which direction stalled, and the
    ring's live occupancy — everything a postmortem reader needs to
    tell 'consumer wedged' from 'producer never wrote'."""
    info = {"ring": getattr(ring, "name", "?"),
            "dir": direction, "peer_pidx": int(peer_pidx)}
    try:
        info["peer_pid"] = int(ring.consumer_pid() if direction == "send"
                               else ring.producer_pid())
        pending, cap = int(ring.pending()), int(ring.capacity)
        info["pending"] = pending
        info["capacity"] = cap
        info["occupancy"] = round(pending / max(1, cap), 4)
    except Exception:
        pass  # ring unmapped under us: name + direction still help
    return info


def _slot_of(tag: int, slots: int) -> int:
    # wire p2p tags differ per lane only above bit 17 — fold the high
    # bits down so independent lanes hash to different rings instead
    # of re-coupling head-of-line behind one FIFO
    t = int(tag)
    return ((t >> 17) ^ (t >> 7) ^ t) % max(1, int(slots))


def _host_array(data) -> Tuple[np.ndarray, bool]:
    """Contiguous host ndarray over ``data``'s bytes + a did-we-copy
    verdict. dlpack first: a CPU-backed device array hands its buffer
    over without materializing; only when the producer refuses (real
    device memory, exotic dtype) does the portable ``np.asarray``
    staging copy run — and it is COUNTED."""
    copied = False
    if isinstance(data, np.ndarray):
        arr = data
    else:
        try:
            arr = np.from_dlpack(data)
        except Exception:
            arr = np.asarray(data)
            copied = True
    out = np.ascontiguousarray(arr)
    if out is not arr and not np.may_share_memory(out, arr):
        copied = True
    return out, copied


def _native_crc32(buf, prior: int = 0) -> int:
    """``zlib.crc32``'s value by the library's routine
    (``native/crc32.h``), which the ring's one-call read also chains."""
    from ..native import crc32

    return crc32(buf, prior)


def _retry_send(fn, what: str):
    """The wire router's first-contact backoff, minus its FT lookups
    (this module has no router handle): a confirmed process failure
    is never retried — ULFM owns that verdict."""
    last = None
    for attempt in range(5):
        try:
            return fn()
        except MPIError as e:
            if e.code == ErrorCode.ERR_PROC_FAILED:
                raise
            last = e
            _time.sleep(0.05 * (attempt + 1))
    raise MPIError(ErrorCode.ERR_UNREACH,
                   f"{what} failed after retries: {last}")


class NativeWireBtl(DcnBtl):
    """The native datapath module. Subclassing :class:`DcnBtl` is the
    point: every ``send_staged``/``recv_staged`` call site in the wire
    router works unchanged, and the portable framings (legacy SGH1,
    interpreted SGH2) remain speakable for per-peer fallback."""

    NAME = "nativewire"
    EAGER_LIMIT = 64 * 1024
    MAX_SEND_SIZE = 4 * 1024 * 1024
    LATENCY = 20                    # beats dcn: no per-frame Python join
    BANDWIDTH = 50_000
    EXCLUSIVITY = 0
    #: wire transport only — never a device-segment mover, so BML move
    #: lists (device routing) are untouched by this component
    SUPPORTS_MOVE = False

    def __init__(self) -> None:
        super().__init__()
        self.cards = []
        self.my_pidx = -1
        #: per-peer parse cache: pidx -> (raw card entry, parsed) —
        #: validated against the LIVE card string on every lookup,
        #: because respawn recovery refreshes the modex cards in place
        #: and a replacement process advertises a FRESH ring token
        self._caps: Dict[int, tuple] = {}
        #: (peer_pidx, peer_token, slot) -> (ring-or-None, lock)
        self._tx: Dict[Tuple[int, str, int], tuple] = {}
        #: (src_pidx, src_token, slot) -> (ring, lock, cross-tag
        #: stash) — the src token in the key makes a respawned
        #: sender's rings fresh attaches, never stale mappings
        self._rx: Dict[Tuple[int, str, int], tuple] = {}
        self._ring_guard = threading.Lock()
        atexit.register(self._shutdown_rings)

    def bind(self, cards, my_pidx: int) -> None:
        self.cards = cards
        self.my_pidx = int(my_pidx)
        self._caps = {}
        _event_ring()  # cvar-gated; no-op (and cheap) when off

    def _cap(self, pidx: int) -> Optional[Tuple[str, int, int]]:
        """LIVE capability of ``pidx`` from the shared cards list."""
        try:
            card = self.cards[pidx]
        except Exception:
            return None
        entry = card.get(CARD_KEY) if isinstance(card, dict) else None
        if entry is None:
            return None
        cached = self._caps.get(pidx)
        if cached is not None and cached[0] == entry:
            return cached[1]
        parsed = _parse_card(entry)
        self._caps[pidx] = (entry, parsed)
        return parsed

    # -- per-peer eligibility (the add_procs verdict) ---------------------
    def peer_capable(self, peer_pidx: int) -> bool:
        """Both-ended capability: the peer advertised the native
        datapath AND this process advertised it (ring mode needs the
        receiver's geometry from OUR card on the peer's side)."""
        return (peer_pidx != self.my_pidx
                and self._cap(peer_pidx) is not None
                and self._cap(self.my_pidx) is not None)

    def _same_host(self, peer_pidx: int) -> bool:
        try:
            mine = self.cards[self.my_pidx].get("host")
            return bool(mine) and mine == self.cards[peer_pidx].get("host")
        except Exception:
            return False

    # -- ring lifecycle ----------------------------------------------------
    def _tx_ring(self, peer_pidx: int, slot: int):
        """Producer-side ring for (me -> peer, slot), created lazily
        with the RECEIVER's advertised geometry. A create failure is a
        permanent per-ring fallback to the vectored socket path (the
        entry caches None), never an error."""
        token, _slots, ring_bytes = self._cap(peer_pidx)
        key = (peer_pidx, token, slot)
        with self._ring_guard:
            ent = self._tx.get(key)
            if ent is None:
                from ..native import ShmRing

                name = _ring_name(token, self.my_pidx, slot)
                ring = ShmRing.create(name, ring_bytes, os.getpid())
                if ring is None:
                    # leftover name from a crashed earlier run: the
                    # token makes collisions with a LIVE ring impossible
                    ShmRing.unlink(name)
                    ring = ShmRing.create(name, ring_bytes, os.getpid())
                _track_ring(ring, tx=True)
                ent = self._tx[key] = (ring, threading.Lock())
            return ent

    def _rx_ring(self, src_pidx: int, slot: int, deadline: float,
                 wait: bool = True):
        """Consumer-side attach for (src -> me, slot), retried until
        the producer's lazy create lands (``wait=False``: one try —
        one failed ``shm_open`` — and None if the ring is not there
        yet); the name is unlinked right after attach (the mapping
        lives on) so /dev/shm stays clean. A producer that died before
        creating surfaces as the typed ERR_PROC_FAILED — pid liveness
        is authoritative on one host."""
        src_cap = self._cap(src_pidx)
        key = (src_pidx, src_cap[0] if src_cap else "", slot)
        with self._ring_guard:
            ent = self._rx.get(key)
        if ent is not None:
            return ent
        name = _ring_name(self._cap(self.my_pidx)[0], src_pidx, slot)
        ent = self._rx_attach(key, name)
        if ent is not None or not wait:
            return ent
        peer_pid = 0
        try:
            peer_pid = int(self.cards[src_pidx].get("pid", 0) or 0)
        except Exception:
            pass
        tok = None
        if _watchdog.enabled:
            tok = _watchdog.arm(
                "nw_ring_attach", peer=src_pidx,
                info=lambda n=name, p=peer_pid, s=src_pidx: {
                    "ring": n, "dir": "attach", "peer_pidx": int(s),
                    "peer_pid": int(p)})
        try:
            while True:
                ent = self._rx_attach(key, name)
                if ent is not None:
                    return ent
                if peer_pid:
                    try:
                        os.kill(peer_pid, 0)
                    except ProcessLookupError:
                        raise MPIError(
                            ErrorCode.ERR_PROC_FAILED,
                            f"shm ring from process {src_pidx} never "
                            f"appeared and its producer (pid "
                            f"{peer_pid}) is gone — peer died "
                            "mid-transfer",
                        )
                    except PermissionError:
                        pass  # alive under another uid
                if _time.monotonic() >= deadline:
                    raise MPIError(
                        ErrorCode.ERR_PENDING,
                        f"timed out waiting for process {src_pidx}'s "
                        f"shm ring {name}",
                    )
                _time.sleep(0.0005)
        finally:
            if tok is not None:
                _watchdog.disarm(tok)

    def _rx_attach(self, key, name: str):
        """One attach attempt; the rx entry, or None while the producer
        has not created the ring."""
        from ..native import ShmRing

        ring = ShmRing.attach(name, os.getpid())
        if ring is None:
            return None
        ShmRing.unlink(name)
        with self._ring_guard:
            ent = self._rx.get(key)
            if ent is None:
                _track_ring(ring, tx=False)
                ent = self._rx[key] = (ring, threading.Lock(), {})
            else:
                ring.close()  # benign double-attach race
        return ent

    def plan_endpoints(self, tag: int, send_peers, recv_srcs):
        """Per-peer native handles for a frozen-plan executor
        (coll/native_exec): ``{pidx: (tx, rx)}`` where tx is the
        producer-side ``(ring, lock)`` toward the peer (None =
        cross-host or ring creation failed → the executor uses the
        vectored-socket leg, exactly like the interpreted path) and
        rx is the consumer-side ``(ring, lock, cross-tag stash)``
        entry for frames FROM the peer (None = cross-host). The
        executor holds both locks for the whole fire — the rings are
        SPSC, so concurrent Python senders/receivers must stay out
        precisely as long as C owns the cursors."""
        out = {}
        for p in sorted(set(send_peers) | set(recv_srcs)):
            tx = rx = None
            if self._same_host(p):
                if p in send_peers:
                    ent = self._tx_ring(
                        p, _slot_of(tag, self._cap(p)[1]))
                    if ent[0] is not None:
                        tx = ent
                if p in recv_srcs:
                    slot = _slot_of(tag, self._cap(self.my_pidx)[1])
                    rx = self._rx_ring(p, slot,
                                       _time.monotonic() + 5.0)
            out[p] = (tx, rx)
        return out

    def _shutdown_rings(self) -> None:
        from ..native import ShmRing

        with self._ring_guard:
            tx, rx = self._tx, self._rx
            self._tx, self._rx = {}, {}
        # A ring still holding bytes that NO consumer has mapped yet is
        # in-flight data the socket path would have parked in kernel
        # buffers: unlinking now would lose a completed send to a
        # receiver that merely hasn't reached its recv.  Give such
        # rings a bounded grace window to be attached (the attach
        # stamps consumer_pid into the shared header and the mapping
        # outlives our unlink); drained or consumed rings close with
        # zero wait.
        deadline = _time.monotonic() + _DRAIN_TIMEOUT_MS / 1000
        for (ring, _lk) in tx.values():
            if ring is not None:
                try:
                    while (ring.pending() > 0 and ring.consumer_pid() == 0
                           and _time.monotonic() < deadline):
                        _time.sleep(0.001)
                except Exception:
                    pass
                _retire_ring(ring, tx=True)
                ShmRing.unlink(ring.name)  # no-op if consumer unlinked
                ring.close()
        for ent in rx.values():
            _retire_ring(ent[0], tx=False)
            ent[0].close()

    # -- send side ---------------------------------------------------------
    def _stash_inbound(self) -> bool:
        """Move every frame our co-hosted peers have queued for us off
        their rings into the rings' cross-tag stashes — where the
        receive loop looks first, so order holds. True when anything
        moved. A ring one of our own receivers is reading right now is
        left to it (try-lock: the caller holds a tx lock). Rings not
        created yet are probed again on every look, never remembered
        as absent: a peer that starts sending to us while we are
        parked is exactly the one whose ring must be drained."""
        moved = False
        rings = [(p, slot) for p in range(len(self.cards))
                 if self.peer_capable(p) and self._same_host(p)
                 for slot in range(self._cap(self.my_pidx)[1])]
        for src_pidx, slot in rings:
            ent = self._rx_ring(src_pidx, slot, 0.0, wait=False)
            if ent is None or not ent[1].acquire(blocking=False):
                continue
            ring, rlk, rstash = ent
            try:
                queued = ring.pending()
                if queued <= 0:
                    continue
                tmp = bytearray(1 << 16)  # one scratch for the whole ring
                # one span per ring drained, around its frame loop
                with _obs.span(_spans.WIRE_STASH, bytes=queued):
                    while ring.pending() > 0:
                        popped = self._pop_other_locked(ring, tmp)
                        if popped is None:
                            break
                        _rlen, rtag, raw, tmp = popped
                        rstash.setdefault(rtag, []).append(raw)
                        _fallback_copies.add()  # the one restash copy
                        moved = True
            finally:
                rlk.release()
        return moved

    def _ring_put_msg(self, ring, lk, oob_ep, peer_pidx: int, tag: int,
                      xfer: int, u8, tpl, first: int) -> int:
        """Fragments ``first`` .. of one message into the peer's ring;
        returns how many went in (at least one): all that are left
        unless a native call handed back on a full ring after some."""
        deadline = _time.monotonic() + _SEND_TIMEOUT_MS / 1000
        slice_ms = _FULL_RING_LOOK_MS
        tok = None
        if _watchdog.enabled:
            # a full-ring wait blocks INSIDE ring.write_msg, in slices;
            # the ring keeps the stall open across them, so however
            # many it takes a blocked fragment is ONE w_stalls count
            # and its stall time runs to the write (native/btl_shm.cc).
            # The zero-arg info resolves at dump time, so the
            # postmortem names the ring, its consumer, and the LIVE
            # occupancy at the moment the watchdog fired
            tok = _watchdog.arm(
                "nw_ring_put", peer=peer_pidx,
                info=lambda r=ring, p=peer_pidx: _ring_wait_info(
                    r, p, "send"))
        try:
            with lk:
                while True:
                    left = max(1, int((deadline - _time.monotonic())
                                      * 1000))
                    n, rc = ring.write_msg(
                        tag, xfer, u8, tpl.chunk, first, tpl.nchunks,
                        min(left, slice_ms))
                    _native_msg_calls.add()
                    if rc == -3:
                        raise MPIError(
                            ErrorCode.ERR_PROC_FAILED,
                            f"shm ring to process {peer_pidx} reports "
                            "its consumer dead — peer died "
                            "mid-transfer",
                        )
                    if rc == -2:
                        # frame can NEVER fit this ring: the vectored
                        # socket loopback carries it, still zero-copy
                        i = first + n
                        off = tpl.offsets[i]
                        oob_ep.sendv(peer_pidx + 1, tag, [
                            _CHUNK2_MAGIC + int(xfer).to_bytes(8, "big"),
                            tpl.idx_tails[i],
                            memoryview(u8)[off:off + tpl.chunk]])
                        return n + 1
                    if rc == 0:
                        return n
                    if not n and _time.monotonic() >= deadline:
                        raise MPIError(
                            ErrorCode.ERR_PENDING,
                            f"shm ring to process {peer_pidx} stayed "
                            f"full for {_SEND_TIMEOUT_MS} ms "
                            "(consumer stalled)",
                        )
                    # ring full. Every process of a schedule round
                    # posts its sends before it reaps, so past one
                    # ring of bytes the consumer may itself be parked
                    # on a full ring — ours, or (around a cycle of
                    # peers) someone's who waits on us. Take what is
                    # queued for us off our inbound rings; while
                    # frames keep coming look again soon, otherwise
                    # wait in long slices. (native/planexec.cc guards
                    # against the same deadlock but differs on
                    # purpose: it never waits inside the ring write,
                    # drains until a sweep finds nothing and naps
                    # 50 us, where this leg waits in slices of
                    # _FULL_RING_LOOK_MS and copies what it drains
                    # into a stash.)
                    slice_ms = (_FULL_RING_LOOK_MS
                                if self._stash_inbound() else 2000)
                    if n:
                        return n  # the caller resumes from first + n
        finally:
            if tok is not None:
                _watchdog.disarm(tok)

    def frame_stream(self, oob_ep, peer_pidx: int, tag: int, data,
                     tpl=None):
        """Side-effecting generator — the native twin of the router's
        planned/staged frame streams, so QoS striping and the
        in-flight window discipline apply to native transfers. The
        header frame rides the portable OOB send (sentinels,
        any-source peeks and flow ids depend on seeing it there) and
        is one ``next()``. To a co-hosted peer the payload then goes
        into the ring in one native call (``ring.write_msg``: every
        fragment record, written in C) and one ``next()`` — more only
        when a call hands back mid-message on a full ring, one
        ``next()`` per call that moved something; to a cross-host peer
        each fragment is a vectored socket send and a ``next()`` of
        its own. So a stream never yields more often than it has
        frames. Returns the number of fragments sent."""
        _check_user_tag(tag)
        nid = peer_pidx + 1
        seg = self.pipeline_segsize()
        if not self.peer_capable(peer_pidx) or seg <= 0:
            # portable framing end-to-end (legacy SGH1 when seg==0)
            sent = _retry_send(
                lambda: DcnBtl.send_staged(self, oob_ep, nid, tag, data),
                f"staged transfer to process {peer_pidx}")
            yield
            return sent
        rec = _obs.enabled  # capture once: flag may flip mid-send
        t0 = _time.perf_counter() if rec else 0.0
        _track_ep(oob_ep)  # tcp-leg counters fold from its C struct
        arr, copied = _host_array(data)
        if copied:
            _fallback_copies.add()
        if tpl is not None and not tpl.matches(arr):
            raise MPIError(
                ErrorCode.ERR_INTERN,
                f"planned staged transfer: buffer {arr.shape}/"
                f"{arr.dtype} does not match the frozen frame template "
                f"{tpl.shape}/{tpl.dtype} — schedule diverged from its "
                "plan (rebuild the persistent request)",
            )
        if tpl is None:
            tpl = _template_for(arr.shape, arr.dtype, seg)
        u8 = arr.reshape(-1).view(np.uint8) if arr.size \
            else np.empty(0, np.uint8)
        xfer = next(_c._xfer_ids)
        frames = tpl.sg_lists(memoryview(u8), xfer, _native_crc32(u8))
        header = b"".join(next(frames))
        ring = lk = None
        if self._same_host(peer_pidx):
            # ring exists BEFORE the header leaves: a receiver that
            # holds the header can always attach without waiting
            ring, lk = self._tx_ring(
                peer_pidx, _slot_of(tag, self._cap(peer_pidx)[1]))
        _retry_send(lambda: oob_ep.send(nid, tag, header),
                    f"native header to process {peer_pidx}")
        yield

        def moved(n: int, plen: int) -> None:
            _zero_copy_strict.add(plen)
            _native_bytes.add(plen)
            _native_frames.add(n)
            self.staged_chunks_pvar.add(n)

        if ring is not None:
            done = 0
            while done < tpl.nchunks:
                n = self._ring_put_msg(ring, lk, oob_ep, peer_pidx, tag,
                                       xfer, u8, tpl, done)
                moved(n, min(tpl.nbytes, (done + n) * tpl.chunk)
                      - done * tpl.chunk)
                done += n
                yield
            _native_msgs.add()
        else:
            for parts in frames:
                _retry_send(
                    lambda p=parts: oob_ep.sendv(nid, tag, p),
                    f"native fragment to process {peer_pidx}")
                moved(1, len(parts[-1]))
                yield
        self.staged_bytes_pvar.add(tpl.nbytes)
        if rec and _obs.enabled:
            _obs.record("btl_nw_send", "btl", t0,
                        _time.perf_counter() - t0,
                        nbytes=int(tpl.nbytes), peer=peer_pidx)
        return tpl.nchunks

    def send_staged(self, oob_ep, peer_nid: int, tag: int, data) -> int:
        frames = self.frame_stream(oob_ep, peer_nid - 1, tag, data)
        while True:
            try:
                next(frames)
            except StopIteration as end:
                return int(end.value or 0)

    # -- receive side ------------------------------------------------------
    @staticmethod
    def _pop_stashed(oob_ep, src_nid: int, tag: int):
        from .components import _ep_stash

        stash, lock = _ep_stash(oob_ep)
        with lock:
            q = stash.get((src_nid, tag))
            if q:
                return q.pop(0)
        return None

    def recv_staged(self, oob_ep, tag: int, *, src=None,
                    dst_device=None, timeout_ms: int = 30_000,
                    first=None, put=None):
        """Native reassembly: the header is popped/parsed exactly like
        the portable path (shared stash, shared resync discipline);
        SGH2 fragments from a capable co-hosted sender then come out
        of the shm ring — all of them in one ``ring.read_msg`` call,
        which checksums them inside its copy, unless something Python
        must handle turns up — from a capable cross-host sender out
        of the native frame queue; both memcpy'd straight into the
        preallocated buffer. Everything else (legacy SGH1, a sender
        that never advertised the capability) resumes the portable
        reassembly with the already-popped header."""
        import jax

        from ..native import DssBuffer

        _check_user_tag(tag)
        rec = _obs.enabled  # capture once: flag may flip mid-recv
        t_obs = _time.perf_counter() if rec else 0.0
        deadline = _time.monotonic() + timeout_ms / 1000
        while True:
            if first is not None:
                src_got, hraw = first
                first = None
            else:
                src_got, hraw = stashed_recv(oob_ep, src, tag, deadline)
            try:
                hdr = DssBuffer(hraw)
                magic = hdr.unpack_string()
                if magic != _HDR2_MAGIC:
                    if magic == _c._HDR_MAGIC:
                        break  # legacy framing: portable reassembly
                    continue  # orphan chunk: resync to the next header
                (xfer,) = hdr.unpack_int64()
                dtype, shape = _unpack_array_header(hdr)
                nchunks, chunk = hdr.unpack_int64(2)
                (crc,) = hdr.unpack_int64()
            except MPIError:
                continue  # a chunk frame: skip to the next header
            break
        src = src_got
        src_pidx = src - 1
        left_ms = max(1, int((deadline - _time.monotonic()) * 1000))
        if magic != _HDR2_MAGIC or not self.peer_capable(src_pidx):
            return DcnBtl.recv_staged(
                self, oob_ep, tag, src=src, dst_device=dst_device,
                timeout_ms=left_ms, first=(src, hraw), put=put)
        _track_ep(oob_ep)  # tcp-leg counters fold from its C struct
        nbytes = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
        if nbytes < 0 or any(d < 0 for d in shape):
            raise MPIError(ErrorCode.ERR_TRUNCATE,
                           f"staged transfer {xfer}: malformed "
                           f"shape {shape}")
        # every byte is overwritten, or the transfer fails its fragment
        # count or its CRC: no zero-fill
        buf = np.empty(nbytes, np.uint8)
        bmv = memoryview(buf)
        # the running CRC-32 of the fragments placed in order so far and
        # the next index of that order (-1 once one came out of it):
        # ring.read_msg chains it inside its copy, place() below for
        # frames that came through a stash
        crc_run = (ctypes.c_int64 * 2)(0, 0)
        want = _CHUNK2_MAGIC + int(xfer).to_bytes(8, "big")
        _frags_inflight.set(int(nchunks))
        nchunks, chunk = int(nchunks), int(chunk)
        ring_ent = None
        if self._same_host(src_pidx):
            slot = _slot_of(tag, self._cap(self.my_pidx)[1])
            ring_ent = self._rx_ring(src_pidx, slot, deadline)

        def place(praw) -> bool:
            """One already-materialized frame (stash/cross-tag restash
            path): the portable placement + stale-drop discipline."""
            if not praw.startswith(want):
                return False  # stale frame from an abandoned transfer
            idx = int.from_bytes(praw[12:20], "big")
            payload = memoryview(praw)[20:]
            off = idx * chunk
            if idx >= nchunks or off + len(payload) > nbytes:
                raise MPIError(
                    ErrorCode.ERR_TRUNCATE,
                    f"staged transfer {xfer}: fragment {idx} overruns "
                    f"the {nbytes}-byte buffer",
                )
            bmv[off:off + len(payload)] = payload
            if crc_run[1] == idx:
                crc_run[0] = _native_crc32(payload, crc_run[0])
                crc_run[1] = idx + 1
            else:
                crc_run[1] = -1
            return True

        got = 0
        tok = None
        if ring_ent is not None and _watchdog.enabled:
            # the empty-ring wait blocks inside ring.read_msg (C
            # slices of <=200ms): name the ring, its producer, and the
            # live occupancy in any stall postmortem
            tok = _watchdog.arm(
                "nw_ring_recv", peer=src_pidx,
                info=lambda r=ring_ent[0], p=src_pidx: _ring_wait_info(
                    r, p, "recv"))
        try:
            while got < nchunks:
                praw = self._pop_stashed(oob_ep, src, tag)
                if praw is not None:
                    if place(praw):
                        got += 1
                        self.staged_chunks_pvar.add()
                    continue
                left_ms = int((deadline - _time.monotonic()) * 1000)
                if left_ms <= 0:
                    raise MPIError(
                        ErrorCode.ERR_PENDING,
                        f"native staged transfer {xfer} from process "
                        f"{src_pidx}: timed out with {got}/{nchunks} "
                        "fragments",
                    )
                step = min(left_ms, 200)
                if ring_ent is not None:
                    ring, rlk, rstash = ring_ent
                    restash = None
                    # both stashes are looked at before the call and
                    # again only when it hands back
                    with rlk:
                        q = rstash.get(tag)
                        praw = q.pop(0) if q else None
                        if praw is None:
                            n, rc = ring.read_msg(
                                tag, xfer, nchunks, chunk, buf,
                                nchunks - got, step, crc_run)
                            _native_msg_calls.add()
                            if rc == -5:
                                restash = self._pop_other_locked(ring)
                    if praw is not None:
                        if place(praw):
                            got += 1
                            self.staged_chunks_pvar.add()
                        continue
                    got += n
                    self.staged_chunks_pvar.add(n)
                    if restash is not None:
                        _rlen, rtag, raw2, _scratch = restash
                        with rlk:
                            rstash.setdefault(rtag, []).append(raw2)
                        _fallback_copies.add()  # the one restash copy
                        continue
                    if rc == -1 and not n:
                        # nothing came in a whole slice. Its sender may
                        # be parked on a full ring to a process that is
                        # itself parked in a read like this one — with
                        # four processes around a cycle (0 writes to 3,
                        # which reads from 2, which writes to 1, which
                        # reads from 0): a reader has to take what is
                        # queued for it on its OTHER inbound rings, as
                        # a sender on a full ring does (_ring_put_msg)
                        self._stash_inbound()
                        continue
                    if rc in (0, -1, -5):
                        continue  # all in / slice over / head raced
                    if rc == -3:
                        raise MPIError(
                            ErrorCode.ERR_PROC_FAILED,
                            f"shm ring from process {src_pidx} reports "
                            f"its producer dead with {got}/{nchunks} "
                            "fragments landed — peer died mid-transfer",
                        )
                    raise MPIError(
                        ErrorCode.ERR_TRUNCATE,
                        f"staged transfer {xfer}: malformed ring "
                        f"record (rc {rc})",
                    )
                else:
                    rc = oob_ep.recv_frag(src, tag, xfer, nchunks,
                                          chunk, buf, step)
                    if rc >= 0:
                        crc_run[1] = -1  # landed unchecksummed
                        got += 1
                        self.staged_chunks_pvar.add()
                        continue
                    if rc == -1:
                        continue  # slice timeout: re-check deadline
                    if rc == -4:
                        # the queue head for (src, tag) is not ours:
                        # pop it through the shared stash machinery and
                        # apply the portable stale-drop filter
                        try:
                            _, raw2 = stashed_recv(
                                oob_ep, src, tag,
                                _time.monotonic() + 0.05)
                        except MPIError:
                            continue
                        if place(raw2):
                            got += 1
                            self.staged_chunks_pvar.add()
                        continue
                    raise MPIError(
                        ErrorCode.ERR_TRUNCATE,
                        f"staged transfer {xfer}: fragment overruns "
                        f"the {nbytes}-byte buffer (native rc {rc})",
                    )
        finally:
            if tok is not None:
                _watchdog.disarm(tok)
        if ring_ent is not None:
            _native_msgs.add()
        if crc_run[1] != nchunks:  # not all in order: one pass now
            crc_run[0] = _native_crc32(buf)
        if crc_run[0] != int(crc):
            raise MPIError(
                ErrorCode.ERR_TRUNCATE,
                f"staged transfer {xfer} failed its payload CRC — "
                "wire corruption or interleaved frames",
            )
        _zero_copy_strict.add(nbytes)
        _native_bytes.add(nbytes)
        arr = buf.view(dtype).reshape(shape)
        self.staged_bytes_pvar.add(arr.nbytes)
        if rec and _obs.enabled:
            _obs.record("btl_nw_recv", "btl", t_obs,
                        _time.perf_counter() - t_obs,
                        nbytes=int(arr.nbytes), peer=src_pidx)
        if dst_device is None:
            dst_device = jax.local_devices()[0]
        return (put or jax.device_put)(arr, dst_device)

    @staticmethod
    def _pop_other_locked(ring, tmp=None):
        """Pop the ring head (a record some other receive wants) while
        the caller holds the ring lock; returns (len, tag, bytes,
        scratch) or None when the head raced away / cannot be
        materialized. ``tmp``: a scratch bytearray to read through
        (grown x8 when the record does not fit, and handed back so a
        caller popping many records allocates once)."""
        if tmp is None:
            tmp = bytearray(1 << 16)
        while True:
            rc, rtag = ring.read_into(tmp, 10)
            if rc == -2:
                if len(tmp) >= ring.capacity:
                    return None
                tmp = bytearray(min(len(tmp) * 8, ring.capacity))
                continue
            if rc < 0:  # -1 raced-empty / -3 dead: main loop handles
                return None
            return rc, rtag, bytes(memoryview(tmp)[:rc]), tmp


class NativeWireComponent(mca_component.Component):
    """MCA shell: withdraws (``query`` -> None) whenever the local
    capability is absent, so BML selection and the fallback contract
    are decided by the standard component machinery."""

    NAME = "nativewire"
    PRIORITY = 45  # between shm (50) and dcn (40): preferred wire path

    def register_vars(self) -> None:
        base.register_module_vars(NativeWireBtl)
        register_nativewire_vars()

    def query(self, ctx=None):
        if not nativewire_ready():
            return None
        return (self.priority, NativeWireBtl())


base.BTL_FRAMEWORK.register(NativeWireComponent())


def _native_rings() -> dict:
    """Watchdog-postmortem contributor: every live native ring's
    identity, endpoints, occupancy, and counter block — whatever rank
    is stalled, the postmortem shows which ring sat full/empty and
    which pid was supposed to drain/fill it."""
    with _tele_lock:
        tx = list(_live_tx)
        rx = list(_live_rx)
        retired = (_retired[0], _retired[1])

    def row(ring) -> dict:
        try:
            return {"name": ring.name, "capacity": int(ring.capacity),
                    "pending": int(ring.pending()),
                    "producer_pid": int(ring.producer_pid()),
                    "consumer_pid": int(ring.consumer_pid()),
                    "stats": ring.stats()}
        except Exception:
            return {"name": getattr(ring, "name", "?")}

    return {"tx": [row(r) for r in tx], "rx": [row(r) for r in rx],
            "retired_stalls": retired[0],
            "retired_stall_ns": retired[1]}


_watchdog.add_contributor("native_rings", _native_rings)
