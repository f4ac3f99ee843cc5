"""btl/nativewire — the zero-copy native datapath.

Three layers of proof:

- **byte identity**: the native frame stream's scatter-gather lists
  join byte-identical to the portable staged frames across a
  segsize x lane matrix (same ``FrameTemplate`` authority, same xfer
  counter), and the two framings INTEROPERATE on real sockets in both
  directions (portable sender -> native receiver and back), CRC
  enforced end to end.
- **selection / graceful degradation**: the MCA component withdraws
  when the capability is absent (env kill-switch, cvar, missing
  symbols); per-peer eligibility is both-ended and card-driven, so a
  peer that never advertised falls back to the portable path.
- **real jobs**: 3-process loopback worlds run collective families
  bitwise-parity over the shm-ring mode AND the forced cross-host
  vectored-socket mode; a mixed fleet (one rank opted out) proves the
  per-peer fallback; a SIGKILLed sender mid-transfer surfaces as the
  typed ERR_PROC_FAILED through the shm ring's dead-producer check.
"""

import itertools
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from ompi_release_tpu.btl import components as btl_comps
from ompi_release_tpu.btl import nativewire as nw
from ompi_release_tpu.mca import var as mca_var
from ompi_release_tpu.runtime.state import JobState
from ompi_release_tpu.tools.tpurun import Job
from ompi_release_tpu.utils.errors import ErrorCode, MPIError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

try:
    from ompi_release_tpu.native import wire_symbols_available
    _NATIVE = bool(wire_symbols_available())
except Exception:
    _NATIVE = False

needs_native = pytest.mark.skipif(
    not _NATIVE, reason="native wire symbols unavailable (no "
    "toolchain); the portable staged path is covered elsewhere")

#: the wire p2p tag base + lane stride (QoS lanes differ above bit 17)
USER_TAG = 1 << 20
LANE_STRIDE = 1 << 17


def _cards(hosts, capable=None, pids=None):
    """Modex cards for a fake fleet: ``hosts[i]`` is pidx i's host,
    ``capable`` the set of pidxs advertising the native datapath."""
    capable = set(range(len(hosts))) if capable is None else capable
    out = []
    for i, h in enumerate(hosts):
        card = {"host": h,
                "pid": (pids or {}).get(i, os.getpid()),
                "node_id": i + 1}
        if i in capable:
            card[nw.CARD_KEY] = f"tok{i}:4:{8 << 20}"
        out.append(card)
    return out


class _CaptureEp:
    """OOB stand-in recording every frame as its joined wire bytes —
    sendv MUST be byte-equivalent to send(b"".join(parts))."""

    def __init__(self):
        self.frames = []

    def send(self, nid, tag, data):
        self.frames.append(bytes(data))

    def sendv(self, nid, tag, parts):
        self.frames.append(b"".join(bytes(p) for p in parts))


@pytest.fixture
def seg(request):
    mca_var.set_value("wire_pipeline_segsize", str(request.param))
    try:
        yield int(request.param)
    finally:
        mca_var.VARS.unset("wire_pipeline_segsize")


class TestByteIdentity:
    """The native stream is the SAME framing, not a compatible one."""

    @pytest.mark.parametrize("seg", [256, 1000, 64 * 1024],
                             indirect=True)
    @pytest.mark.parametrize("lane", [0, 1, 3])
    @pytest.mark.parametrize(
        "dtype,n", [(np.float32, 7321), (np.int16, 4096),
                    (np.uint8, 1)])
    def test_matrix_native_frames_equal_staged_frames(
            self, seg, lane, dtype, n):
        """segsize x lane x dtype: b''.join of every native
        scatter-gather list == the portable staged frame, including
        the ragged tail fragment and the header."""
        cards = _cards(["hostA", "hostB"])  # distinct: sendv path
        mod = nw.NativeWireBtl()
        mod.bind(cards, 0)
        x = (np.arange(n) % 251).astype(dtype)
        tag = USER_TAG + lane * LANE_STRIDE + 5
        saved = btl_comps._xfer_ids
        try:
            btl_comps._xfer_ids = itertools.count(9000)
            ep = _CaptureEp()
            for _ in mod.frame_stream(ep, 1, tag, x):
                pass
            btl_comps._xfer_ids = itertools.count(9000)
            ref = list(btl_comps.DcnBtl().staged_frames(x, segsize=seg))
        finally:
            btl_comps._xfer_ids = saved
        assert len(ep.frames) == len(ref)
        assert ep.frames == ref

    @pytest.mark.parametrize("seg", [256], indirect=True)
    def test_planned_template_same_identity(self, seg):
        """The frozen-template (compiled-plan) leg of the native
        stream matches planned_frames bit for bit."""
        cards = _cards(["hostA", "hostB"])
        mod = nw.NativeWireBtl()
        mod.bind(cards, 0)
        x = np.arange(600, dtype=np.float64)
        tpl = btl_comps.plan_frame_template(x.shape, x.dtype, seg)
        saved = btl_comps._xfer_ids
        try:
            btl_comps._xfer_ids = itertools.count(77)
            ep = _CaptureEp()
            for _ in mod.frame_stream(ep, 1, USER_TAG + 9, x, tpl=tpl):
                pass
            btl_comps._xfer_ids = itertools.count(77)
            ref = list(btl_comps.DcnBtl().planned_frames(x, tpl))
        finally:
            btl_comps._xfer_ids = saved
        assert ep.frames == ref

    @pytest.mark.parametrize("seg", [256], indirect=True)
    def test_template_mismatch_is_loud(self, seg):
        cards = _cards(["hostA", "hostB"])
        mod = nw.NativeWireBtl()
        mod.bind(cards, 0)
        tpl = btl_comps.plan_frame_template((8,), np.float32, seg)
        with pytest.raises(MPIError) as ei:
            for _ in mod.frame_stream(_CaptureEp(), 1, USER_TAG + 1,
                                      np.zeros(9, np.float32), tpl=tpl):
                pass
        assert ei.value.code == ErrorCode.ERR_INTERN


@needs_native
class TestSocketInterop:
    """Both framings on REAL sockets, mixed directions: the native
    receiver reassembles a portable sender's frames and vice versa —
    the wire contract that makes per-peer fallback safe mid-fleet."""

    def _pair(self):
        from ompi_release_tpu.native import OobEndpoint

        a, b = OobEndpoint(1), OobEndpoint(2)
        b.connect(1, "127.0.0.1", a.port)
        return a, b

    @pytest.mark.parametrize("seg", [1 << 16], indirect=True)
    def test_native_sender_portable_receiver(self, seg):
        a, b = self._pair()
        try:
            cards = _cards(["hostA", "hostB"])
            mod = nw.NativeWireBtl()
            mod.bind(cards, 1)  # sender is pidx 1 -> sendv to nid 1
            x = np.arange(300_000, dtype=np.float32)
            mod.send_staged(b, 1, USER_TAG + 3, x)
            got = btl_comps.DcnBtl().recv_staged(a, USER_TAG + 3)
            np.testing.assert_array_equal(np.asarray(got), x)
        finally:
            a.close()
            b.close()

    @pytest.mark.parametrize("seg", [1 << 16], indirect=True)
    def test_portable_sender_native_receiver(self, seg):
        a, b = self._pair()
        try:
            cards = _cards(["hostA", "hostB"])
            mod = nw.NativeWireBtl()
            mod.bind(cards, 0)  # receiver is pidx 0; sender pidx 1
            before = nw._native_bytes.read()
            x = np.arange(123_457, dtype=np.int32)
            btl_comps.DcnBtl().send_staged(b, 1, USER_TAG + 4, x)
            got = mod.recv_staged(a, USER_TAG + 4)
            np.testing.assert_array_equal(np.asarray(got), x)
            assert nw._native_bytes.read() - before == x.nbytes
        finally:
            a.close()
            b.close()

    @pytest.mark.parametrize("seg", [1 << 14], indirect=True)
    @pytest.mark.parametrize("leg", ["socket", "ring"])
    def test_crc_catches_corruption(self, seg, leg):
        """A corrupted fragment payload fails the transfer CRC with
        the typed ERR_TRUNCATE — never silently wrong data. On the
        socket leg a portable sender's last frame is altered; on the
        ring leg the native sender's ``write_msg`` has put the whole
        payload into the ring and one byte of the ring's data area is
        flipped before the receiver's ``read_msg`` takes it out (the
        checksum chained inside that copy is what catches it)."""
        a, b = self._pair()
        try:
            hosts = ["hostA", "hostB"] if leg == "socket" \
                else ["hostX", "hostX"]
            cards = _cards(hosts)
            mod = nw.NativeWireBtl()
            mod.bind(cards, 0)
            x = np.arange(20_000, dtype=np.int32)
            if leg == "socket":
                frames = list(btl_comps.DcnBtl().staged_frames(
                    x, segsize=seg))
                bad = bytearray(frames[-1])
                bad[-1] ^= 0xFF
                frames[-1] = bytes(bad)
                for fr in frames:
                    b.send(1, USER_TAG + 6, fr)
            else:
                tx = nw.NativeWireBtl()
                tx.bind(cards, 1)
                tx.send_staged(b, 1, USER_TAG + 6, x)
                ring, _lk = tx._tx_ring(
                    0, nw._slot_of(USER_TAG + 6, tx._cap(0)[1]))
                # 128-byte ring header, then the first record: 8 bytes
                # of length and tag, the 20-byte SGC2 prefix, payload
                with open("/dev/shm" + ring.name, "r+b") as f:
                    f.seek(128 + 8 + 20 + 1000)
                    byte = f.read(1)
                    f.seek(-1, os.SEEK_CUR)
                    f.write(bytes([byte[0] ^ 0xFF]))
            with pytest.raises(MPIError) as ei:
                mod.recv_staged(a, USER_TAG + 6, timeout_ms=10_000)
            assert ei.value.code == ErrorCode.ERR_TRUNCATE
            assert "failed its payload CRC" in str(ei.value)
        finally:
            a.close()
            b.close()
            for m in (locals().get("tx"), locals().get("mod")):
                if isinstance(m, nw.NativeWireBtl):
                    m._shutdown_rings()

    @pytest.mark.parametrize("seg", [1 << 15], indirect=True)
    def test_shm_ring_loopback_same_process(self, seg):
        """Co-hosted mode in one process: fragments cross a real
        /dev/shm ring while the header rides the OOB; the zero-copy
        witness stays ~0 for a clean transfer."""
        a, b = self._pair()
        try:
            cards = _cards(["hostX", "hostX"])  # SAME host: ring mode
            tx = nw.NativeWireBtl()
            tx.bind(cards, 1)
            rx = nw.NativeWireBtl()
            rx.bind(cards, 0)
            x = np.arange(500_000, dtype=np.float32)
            b_fb = nw._fallback_copies.read()
            b_nb = nw._native_bytes.read()
            err = []

            def _send():
                try:
                    tx.send_staged(b, 1, USER_TAG + 8, x)
                except Exception as e:  # surfaced by the main thread
                    err.append(e)

            th = threading.Thread(target=_send, daemon=True)
            th.start()
            got = rx.recv_staged(a, USER_TAG + 8, timeout_ms=60_000)
            th.join(timeout=60)
            assert not err, err
            np.testing.assert_array_equal(np.asarray(got), x)
            assert nw._native_bytes.read() - b_nb == 2 * x.nbytes
            # clean same-tag transfer: no forced host copies at all
            assert nw._fallback_copies.read() == b_fb
        finally:
            a.close()
            b.close()
            # unlink any ring this test left mapped
            for mod in (locals().get("tx"), locals().get("rx")):
                if isinstance(mod, nw.NativeWireBtl):
                    mod._shutdown_rings()

    @pytest.mark.parametrize("seg", [1 << 14], indirect=True)
    def test_a_parked_reader_takes_its_other_rings(self, seg):
        """Four processes can park around a cycle — 0 writes to 3, which
        reads from 2, which writes to 1, which reads from 0 — when two
        of them have posted all their sends and reap while the others
        still send past one ring of bytes (an all-pairs allgather's
        first, interpreted call; ISSUE 31, step 0). A sender on a full
        ring drains its inbound rings; so must a reader whose ring stays
        empty for a slice: here process 0 holds a header from 1 and waits
        on the ring from 1, which sends nothing for a while, and the
        ring from 2 is full of another transfer's frames. They have to be in the
        stash — the ring empty, its writer free to go on — before
        process 1 sends another byte."""
        a, b = self._pair()
        try:
            cards = _cards(["hostX"] * 3)
            rx = nw.NativeWireBtl()
            rx.bind(cards, 0)
            tx1 = nw.NativeWireBtl()
            tx1.bind(cards, 1)
            tx2 = nw.NativeWireBtl()
            tx2.bind(cards, 2)
            tag = USER_TAG + 8
            ring2, _lk = tx2._tx_ring(0, nw._slot_of(tag, tx2._cap(0)[1]))
            rec, queued = bytes(256 * 1024), 0
            while ring2.writev(tag + 1, [rec], 5) == 0:
                queued += 1  # until the ring from 2 is full
            assert queued and ring2.stats()["w_stalls"] == 1
            x = np.arange(100_000, dtype=np.float32)  # 25 fragments
            frames = tx1.frame_stream(b, 0, tag, x)
            got = []
            th = threading.Thread(
                target=lambda: got.append(
                    rx.recv_staged(a, tag, timeout_ms=30_000)),
                daemon=True)
            th.start()
            # the header alone: since PR 35 the next step of the stream
            # would put the whole payload into the ring in one call
            next(frames)
            deadline = time.monotonic() + 5
            while (ring2.stats()["r_frames"] < queued
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            drained = ring2.stats()["r_frames"]
            room = ring2.writev(tag + 1, [rec], 5)
            for _ in frames:  # the reader ends before anything closes
                pass
            th.join(timeout=30)
            # every frame of the other transfer left the ring while the
            # reader's own ring stayed empty: its writer could go on
            assert drained == queued and room == 0
            np.testing.assert_array_equal(np.asarray(got[0]), x)
        finally:
            a.close()
            b.close()
            for mod in (locals().get("rx"), locals().get("tx1"),
                        locals().get("tx2")):
                if isinstance(mod, nw.NativeWireBtl):
                    mod._shutdown_rings()

    def _ring_fleet(self, ring_bytes=8 << 20):
        cards = _cards(["hostX", "hostX"])
        for i, card in enumerate(cards):
            card[nw.CARD_KEY] = f"tok{i}-{os.getpid()}:4:{ring_bytes}"
        tx = nw.NativeWireBtl()
        tx.bind(cards, 1)
        rx = nw.NativeWireBtl()
        rx.bind(cards, 0)
        return tx, rx

    @staticmethod
    def _msg_counters():
        return nw._native_msg_calls.read(), nw._native_msgs.read()

    @pytest.mark.parametrize("seg", [1 << 15], indirect=True)
    def test_a_message_that_fits_is_one_native_call_a_side(self, seg):
        """The whole payload is in the ring before the receiver looks:
        one ``write_msg``, one ``read_msg``, whatever the fragment
        count — ``wire_native_msg_calls`` : ``wire_native_msgs`` reads
        1 : 1 — and the stream yields once for the header and once for
        the payload."""
        a, b = self._pair()
        try:
            tx, rx = self._ring_fleet()
            x = np.arange(500_000, dtype=np.float32)  # 62 fragments
            calls0, msgs0 = self._msg_counters()
            frames0 = nw._native_frames.read()
            fb0 = nw._fallback_copies.read()
            steps = sum(1 for _ in tx.frame_stream(b, 0, USER_TAG + 8, x))
            assert steps == 2
            assert self._msg_counters() == (calls0 + 1, msgs0 + 1)
            got = rx.recv_staged(a, USER_TAG + 8, timeout_ms=30_000)
            np.testing.assert_array_equal(np.asarray(got), x)
            assert self._msg_counters() == (calls0 + 2, msgs0 + 2)
            assert nw._native_frames.read() - frames0 == 62
            assert nw._fallback_copies.read() == fb0
        finally:
            a.close()
            b.close()
            for mod in (locals().get("tx"), locals().get("rx")):
                if isinstance(mod, nw.NativeWireBtl):
                    mod._shutdown_rings()

    @pytest.mark.parametrize("seg", [1 << 14], indirect=True)
    def test_a_ring_smaller_than_the_message_resumes(self, seg):
        """400 KB through a 64 KiB ring: ``write_msg`` hands back at
        the full ring, the sender does what it did per fragment (its
        own inbound rings, a slice) and calls again from that
        fragment; the reader's ``read_msg`` returns what has landed
        when a slice ends. More calls than messages; the payload and
        its checksum whole; the stream never yields more often than
        the message has frames."""
        a, b = self._pair()
        try:
            tx, rx = self._ring_fleet(ring_bytes=1 << 16)
            x = np.arange(100_000, dtype=np.float32)  # 25 fragments
            calls0, msgs0 = self._msg_counters()
            err, steps = [], []

            def _send():
                try:
                    steps.append(sum(
                        1 for _ in tx.frame_stream(b, 0, USER_TAG + 8, x)))
                except Exception as e:  # surfaced by the main thread
                    err.append(e)

            th = threading.Thread(target=_send, daemon=True)
            th.start()
            time.sleep(0.1)  # the sender is parked on the full ring
            got = rx.recv_staged(a, USER_TAG + 8, timeout_ms=60_000)
            th.join(timeout=60)
            assert not err, err
            np.testing.assert_array_equal(np.asarray(got), x)
            calls, msgs = self._msg_counters()
            assert msgs - msgs0 == 2
            assert calls - calls0 > msgs - msgs0
            assert 2 < steps[0] <= 1 + 25
        finally:
            a.close()
            b.close()
            for mod in (locals().get("tx"), locals().get("rx")):
                if isinstance(mod, nw.NativeWireBtl):
                    mod._shutdown_rings()

    @pytest.mark.parametrize("seg", [1 << 14], indirect=True)
    def test_foreign_tag_at_the_ring_head_is_restashed(self, seg):
        """Another lane's record sits in the ring among this
        message's fragments (two tags that hash to one slot):
        ``read_msg`` hands back in front of it (-5), Python moves it to
        the ring's cross-tag stash — the one counted copy — and the
        call resumes; the other lane finds its record there."""
        a, b = self._pair()
        try:
            tx, rx = self._ring_fleet()
            tag = USER_TAG + 8
            slot = nw._slot_of(tag, 4)
            other = next(t for t in range(tag + 1, tag + 64)
                         if nw._slot_of(t, 4) == slot)
            ring, _lk = tx._tx_ring(0, slot)
            x = np.arange(50_000, dtype=np.float32)  # 13 fragments
            stream = tx.frame_stream(b, 0, tag, x)
            next(stream)  # the header
            # the other lane's record goes in first, then the payload
            assert ring.writev(other, [b"another lane's frame"], 100) == 0
            for _ in stream:
                pass
            calls0, msgs0 = self._msg_counters()
            fb0 = nw._fallback_copies.read()
            got = rx.recv_staged(a, tag, timeout_ms=30_000)
            np.testing.assert_array_equal(np.asarray(got), x)
            calls, msgs = self._msg_counters()
            assert (calls - calls0, msgs - msgs0) == (2, 1)
            assert nw._fallback_copies.read() - fb0 == 1
            ent = rx._rx_ring(1, slot, time.monotonic() + 1)
            assert ent[2][other] == [b"another lane's frame"]
        finally:
            a.close()
            b.close()
            for mod in (locals().get("tx"), locals().get("rx")):
                if isinstance(mod, nw.NativeWireBtl):
                    mod._shutdown_rings()

    @pytest.mark.parametrize("seg", [1 << 14], indirect=True)
    def test_fragments_partly_from_the_stash(self, seg):
        """The first fragments of a message were taken off the ring
        into the cross-tag stash (a sender of ours on a full ring
        drained its inbound rings meanwhile), the rest are still in
        the ring: the receiver places the stashed ones, lets
        ``read_msg`` land the others, and the checksum — chained over
        both, they are in order — holds. A flipped byte in a stashed
        fragment fails it."""
        a, b = self._pair()
        try:
            tx, rx = self._ring_fleet(ring_bytes=1 << 16)
            tag = USER_TAG + 8
            x = np.arange(40_000, dtype=np.float32)  # 10 fragments
            for flip in (False, True):
                stream = tx.frame_stream(b, 0, tag, x)
                next(stream)  # the header
                next(stream)  # what fits a 64 KiB ring: three fragments
                fb0 = nw._fallback_copies.read()
                assert rx._stash_inbound()
                stashed = nw._fallback_copies.read() - fb0
                assert 0 < stashed < 10
                if flip:
                    slot = nw._slot_of(tag, 4)
                    q = rx._rx_ring(1, slot, time.monotonic() + 1)[2][tag]
                    bad = bytearray(q[1])
                    bad[-1] ^= 0xFF
                    q[1] = bytes(bad)
                th = threading.Thread(
                    target=lambda: [None for _ in stream], daemon=True)
                th.start()
                if flip:
                    with pytest.raises(MPIError) as ei:
                        rx.recv_staged(a, tag, timeout_ms=30_000)
                    assert ei.value.code == ErrorCode.ERR_TRUNCATE
                else:
                    got = rx.recv_staged(a, tag, timeout_ms=30_000)
                    np.testing.assert_array_equal(np.asarray(got), x)
                th.join(timeout=30)
                assert not th.is_alive()
        finally:
            a.close()
            b.close()
            for mod in (locals().get("tx"), locals().get("rx")):
                if isinstance(mod, nw.NativeWireBtl):
                    mod._shutdown_rings()

    def test_shutdown_waits_for_unattached_consumer(self):
        """A completed send whose receiver hasn't attached yet must
        survive producer exit — the socket path parks such bytes in
        kernel buffers, so the ring path may not lose them either.
        ``_shutdown_rings`` holds the unlink until a consumer maps the
        ring, then finishes promptly (the mapping outlives the name)."""
        from ompi_release_tpu.native import ShmRing

        cards = _cards(["hostX", "hostX"])
        tx = nw.NativeWireBtl()
        tx.bind(cards, 1)
        ring, _lk = tx._tx_ring(0, 3)
        payload = np.arange(4096, dtype=np.int32).tobytes()
        assert ring.writev(77, [payload], 2000) == 0
        name = nw._ring_name(tx._cap(0)[0], 1, 3)
        th = threading.Thread(target=tx._shutdown_rings, daemon=True)
        th.start()
        time.sleep(0.25)
        assert th.is_alive(), \
            "shutdown unlinked a ring still holding undelivered bytes"
        late = ShmRing.attach(name, os.getpid())
        assert late is not None, "ring name vanished before attach"
        try:
            th.join(timeout=10)
            assert not th.is_alive(), "shutdown ignored the attach"
            buf = bytearray(len(payload))
            rc, tag = late.read_into(buf, 2000)
            assert rc >= 0 and tag == 77
            assert bytes(buf) == payload
        finally:
            late.close()
            ShmRing.unlink(name)

    def test_full_ring_stall_is_one_event_across_slices(self):
        """Senders wait on a full ring in short slices (between them
        they drain their own inbound rings) and retry the same record.
        The ring keeps the stall open across the slices: one blocked
        record is ONE ``w_stalls`` count, and ``w_stall_ns`` runs from
        the first full-ring sighting to the write, the time between
        slices included."""
        from ompi_release_tpu.native import ShmRing

        name = f"/onw-stalltest-{os.getpid()}"
        ShmRing.unlink(name)
        tx = ShmRing.create(name, 64 * 1024, os.getpid())
        rx = ShmRing.attach(name, os.getpid())
        try:
            rec = bytes(40 * 1024)
            assert tx.writev(5, [rec], 100) == 0  # fits: no stall
            assert tx.stats()["w_stalls"] == 0
            t0 = time.monotonic()
            for _ in range(6):  # full: 6 slices of one stall
                assert tx.writev(5, [rec], 5) == -1
                time.sleep(0.01)  # the caller's work between slices
            st = tx.stats()
            assert st["w_stalls"] == 1, st
            buf = bytearray(len(rec))
            assert rx.read_into(buf, 100)[0] == len(rec)
            assert tx.writev(5, [rec], 100) == 0  # the retry lands
            total_ns = (time.monotonic() - t0) * 1e9
            st = tx.stats()
            assert st["w_stalls"] == 1, st
            assert 0.06e9 <= st["w_stall_ns"] <= total_ns, (st, total_ns)
            # the stall is closed: the next blocked record is a new one
            assert tx.writev(5, [rec], 5) == -1
            assert tx.stats()["w_stalls"] == 2
        finally:
            rx.close()
            tx.close()
            ShmRing.unlink(name)


class TestSelectionAndFallback:
    """Graceful degradation is structural: MCA withdrawal + per-peer
    card checks, never a runtime surprise."""

    def test_component_registered_at_package_import(self):
        """Importing the btl package alone registers the component —
        a user listing the framework pre-init sees nativewire in the
        help banner even when query() would withdraw it."""
        out = subprocess.check_output(
            [sys.executable, "-c", textwrap.dedent(f"""
                import sys; sys.path.insert(0, {REPO!r})
                from ompi_release_tpu.btl import BTL_FRAMEWORK
                print([c.NAME for c in BTL_FRAMEWORK.components()])
            """)],
        )
        assert b"nativewire" in out

    def test_env_killswitch_withdraws_component(self, monkeypatch):
        monkeypatch.setenv("OMPITPU_NATIVEWIRE", "0")
        assert not nw.nativewire_ready()
        assert nw.modex_entry() == {}
        assert nw.NativeWireComponent().query() is None
        assert nw.module_for(_cards(["h", "h"]), 0) is None

    def test_cvar_killswitch_withdraws_component(self):
        mca_var.set_value("btl_nativewire_enable", "false")
        try:
            assert not nw.nativewire_ready()
            assert nw.NativeWireComponent().query() is None
        finally:
            mca_var.VARS.unset("btl_nativewire_enable")

    @needs_native
    def test_component_selected_when_ready(self):
        got = nw.NativeWireComponent().query()
        assert got is not None
        prio, mod = got
        assert prio == 45 and mod.NAME == "nativewire"
        # ranked between the shm handoff (50) and dcn (40)
        from ompi_release_tpu.btl import base as btl_base

        names = [c.NAME for c in btl_base.BTL_FRAMEWORK.components()]
        assert "nativewire" in names

    @needs_native
    def test_modex_card_roundtrip(self):
        entry = nw.modex_entry()
        assert set(entry) == {nw.CARD_KEY}
        token, slots, ring = nw._parse_card(entry[nw.CARD_KEY])
        assert token == nw._local_token()
        assert slots >= 1 and ring >= 1 << 16

    def test_parse_card_malformed_means_not_capable(self):
        assert nw._parse_card("garbage") is None
        assert nw._parse_card(None) is None
        assert nw._parse_card("") is None
        # floors: zero slots / tiny ring are clamped, not trusted
        token, slots, ring = nw._parse_card("t:0:1")
        assert slots == 1 and ring == 1 << 16

    def test_peer_capable_is_both_ended_and_live(self):
        cards = _cards(["h", "h", "h"], capable={0, 1})
        mod = nw.NativeWireBtl()
        mod.bind(cards, 0)
        assert mod.peer_capable(1)
        assert not mod.peer_capable(2)   # peer never advertised
        assert not mod.peer_capable(0)   # self is never a wire peer
        # respawn: the card is refreshed IN PLACE with a new token —
        # the verdict and parsed geometry must follow the live entry
        cards[1][nw.CARD_KEY] = f"fresh:2:{1 << 20}"
        assert mod.peer_capable(1)
        assert mod._cap(1)[0] == "fresh"
        del cards[1][nw.CARD_KEY]
        assert not mod.peer_capable(1)

    def test_peer_capable_needs_own_card(self):
        cards = _cards(["h", "h"], capable={1})
        mod = nw.NativeWireBtl()
        mod.bind(cards, 0)  # we never advertised: ring geometry absent
        assert not mod.peer_capable(1)

    def test_slot_hash_spreads_lanes(self):
        """QoS lanes (tag stride 1<<17) land on distinct rings instead
        of re-coupling head-of-line behind one FIFO."""
        slots = {nw._slot_of(USER_TAG + lane * LANE_STRIDE + 5, 4)
                 for lane in range(4)}
        assert len(slots) > 1
        for t in (USER_TAG, USER_TAG + 123456):
            assert nw._slot_of(t, 4) == nw._slot_of(t, 4)
            assert 0 <= nw._slot_of(t, 4) < 4
        assert nw._slot_of(USER_TAG, 1) == 0

    def test_host_array_copy_accounting(self):
        arr = np.arange(32, dtype=np.float32)
        out, copied = nw._host_array(arr)
        assert out is arr and not copied
        out, copied = nw._host_array(arr[::2])  # non-contiguous
        assert copied and out.flags["C_CONTIGUOUS"]
        out, copied = nw._host_array([1, 2, 3])  # no buffer protocol
        assert copied

    @pytest.mark.parametrize("seg", [1024], indirect=True)
    def test_incapable_peer_rides_portable_framing(self, seg):
        """frame_stream to a peer WITHOUT the card must produce the
        portable staged frames (single-yield, DcnBtl.send_staged)."""
        cards = _cards(["hostA", "hostB"], capable={0})
        mod = nw.NativeWireBtl()
        mod.bind(cards, 0)
        x = np.arange(2048, dtype=np.int16)
        saved = btl_comps._xfer_ids
        try:
            btl_comps._xfer_ids = itertools.count(31)
            ep = _CaptureEp()
            for _ in mod.frame_stream(ep, 1, USER_TAG + 2, x):
                pass
            btl_comps._xfer_ids = itertools.count(31)
            ref = list(btl_comps.DcnBtl().staged_frames(
                x, segsize=seg))
        finally:
            btl_comps._xfer_ids = saved
        assert ep.frames == ref


# ---------------------------------------------------------------------------
# real multi-process jobs
# ---------------------------------------------------------------------------

APP_PRELUDE = textwrap.dedent("""
    import os, sys, time
    sys.path.insert(0, %r)
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2"
    ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import ompi_release_tpu as mpi
    from ompi_release_tpu.runtime.runtime import Runtime
""" % REPO)


def _run_job(tmp_path, capfd, body, *, n=3, timeout=180, job_kw=None,
             mca=None):
    app = tmp_path / "nw_app.py"
    app.write_text(APP_PRELUDE + textwrap.dedent(body))
    kw = {"heartbeat_s": 0.5, "miss_limit": 8, **(job_kw or {})}
    job = Job(n, [sys.executable, str(app)], list(mca or ()), **kw)
    rc = job.run(timeout_s=timeout)
    out = capfd.readouterr()
    return rc, out.out + out.err, job


PARITY_BODY = """
    world = mpi.init()
    rt = Runtime.current()
    me = rt.bootstrap["process_index"]
    n = world.size
    off = rt.local_rank_offset
    nw = rt.wire._nw
    assert nw is not None, "native datapath did not come up"
    for p in range(3):
        if p != me:
            assert rt.wire._btl_for(p).NAME == "nativewire", \\
                rt.wire._btl_for(p).NAME
    from ompi_release_tpu.mca import pvar
    nb = pvar.PVARS.lookup("wire_native_bytes")

    # allreduce: bitwise int32 parity against the numpy total
    x = np.stack([np.arange(64, dtype=np.int32) * (off + i + 1)
                  for i in range(2)])
    got = np.asarray(world.allreduce(x))
    want = sum(np.arange(64, dtype=np.int32) * (r + 1)
               for r in range(n))
    for i in range(2):
        np.testing.assert_array_equal(got[i], want)

    # bcast from a cross-process root
    bx = (np.stack([np.arange(1024, dtype=np.int32) * 3] * 2)
          if off == 0 else np.zeros((2, 1024), np.int32))
    bgot = np.asarray(world.bcast(bx, root=0))
    np.testing.assert_array_equal(
        bgot[0], np.arange(1024, dtype=np.int32) * 3)

    # reduce_scatter + allgather round-trip
    rs = np.stack([np.arange(n * 8, dtype=np.int32) + 10 * (off + i)
                   for i in range(2)])
    rgot = np.asarray(world.reduce_scatter_block(rs))
    want_full = sum(np.arange(n * 8, dtype=np.int32) + 10 * r
                    for r in range(n))
    for i in range(2):
        r = off + i
        np.testing.assert_array_equal(
            rgot[i], want_full[r * 8:(r + 1) * 8])

    ag = np.asarray(world.allgather(
        np.stack([np.full(4, off + i, np.int32) for i in range(2)])))
    np.testing.assert_array_equal(
        ag[0].reshape(n, 4)[:, 0], np.arange(n, dtype=np.int32))

    # p2p across the process boundary
    if me == 0:
        world.send(np.arange(50_000, dtype=np.float32), n - 1,
                   tag=21, rank=0)
    elif me == 2:
        val, st = world.recv(source=0, tag=21, rank=n - 1)
        np.testing.assert_array_equal(
            np.asarray(val), np.arange(50_000, dtype=np.float32))
    world.barrier()
    assert float(nb.read()) > 0, "no bytes rode the native datapath"
    print(f"NW_PARITY_OK {me} native_bytes={float(nb.read()):.0f}",
          flush=True)
    mpi.finalize()
"""


@needs_native
class TestNativeJobs:
    def test_shm_ring_collectives_parity_3proc(self, tmp_path, capfd):
        """3 co-hosted processes: every cross-process byte rides the
        shm-ring mode; collective families parity-check bitwise and
        the native byte counter proves the path was really taken."""
        rc, out, job = _run_job(tmp_path, capfd, PARITY_BODY)
        assert rc == 0, out
        for me in range(3):
            assert f"NW_PARITY_OK {me}" in out, out
        assert job.job_state.visited(JobState.TERMINATED)

    def test_opposing_full_rings_do_not_deadlock(self, tmp_path, capfd):
        """Every process of a schedule round posts its sends before it
        reaps, so a collective past one ring of bytes parks all the
        senders on full rings at once — pairwise and, with 3
        processes, around a cycle. A sender that finds the ring full
        takes its own arrivals off its inbound rings, so the round
        completes (the ring is shrunk so 2 MiB is 8 rings of bytes)."""
        body = """
    world = mpi.init()
    rt = Runtime.current()
    off, n = rt.local_rank_offset, world.size
    x = np.stack([np.full(1 << 19, off + i + 1, np.int32)
                  for i in range(2)])
    got = np.asarray(world.allreduce(x))
    assert (got == n * (n + 1) // 2).all(), got[:, :4]
    ag = np.asarray(world.allgather(x)).reshape(2, n, -1)
    np.testing.assert_array_equal(ag[0, :, 0], np.arange(1, n + 1))
    print(f"NW_FULL_OK {rt.bootstrap['process_index']}", flush=True)
    mpi.finalize()
"""
        rc, out, _ = _run_job(
            tmp_path, capfd, body, timeout=120,
            mca=[("btl_nativewire_ring_bytes", 256 * 1024),
                 ("wire_pipeline_segsize", 64 * 1024)])
        assert rc == 0, out
        for me in range(3):
            assert f"NW_FULL_OK {me}" in out, out

    def test_tcp_vectored_collectives_parity_3proc(self, tmp_path,
                                                   capfd):
        """Same families, forced cross-host (distinct OMPITPU_HOST_ID
        per worker): fragments ride the vectored-socket path."""
        body = """
    import os
    os.environ["OMPITPU_HOST_ID"] = (
        "nwhost-" + os.environ["OMPITPU_NODE_ID"])
""" + PARITY_BODY
        rc, out, job = _run_job(tmp_path, capfd, body)
        assert rc == 0, out
        for me in range(3):
            assert f"NW_PARITY_OK {me}" in out, out

    def test_mixed_fleet_per_peer_fallback(self, tmp_path, capfd):
        """One rank opts out (OMPITPU_NATIVEWIRE=0): capable pairs
        keep the native path, pairs touching the opted-out rank fall
        back per peer, and the whole world still parity-checks."""
        rc, out, _job = _run_job(tmp_path, capfd, """
    import os
    if os.environ["OMPITPU_NODE_ID"] == "3":
        os.environ["OMPITPU_NATIVEWIRE"] = "0"
    world = mpi.init()
    rt = Runtime.current()
    me = rt.bootstrap["process_index"]
    n = world.size
    off = rt.local_rank_offset
    if me == 2:
        assert rt.wire._nw is None
        for p in (0, 1):
            assert rt.wire._btl_for(p).NAME in ("shm", "dcn")
    else:
        nw = rt.wire._nw
        assert nw is not None
        other = 1 - me
        assert nw.peer_capable(other)
        assert not nw.peer_capable(2), "opted-out peer looked capable"
        assert rt.wire._btl_for(other).NAME == "nativewire"
        assert rt.wire._btl_for(2).NAME in ("shm", "dcn")
    x = np.stack([np.arange(32, dtype=np.int32) * (off + i + 1)
                  for i in range(2)])
    got = np.asarray(world.allreduce(x))
    want = sum(np.arange(32, dtype=np.int32) * (r + 1)
               for r in range(n))
    np.testing.assert_array_equal(got[0], want)
    if me == 0:
        world.send(np.arange(9999, dtype=np.int32), n - 1, tag=23,
                   rank=0)
    elif me == 2:
        val, st = world.recv(source=0, tag=23, rank=n - 1)
        np.testing.assert_array_equal(
            np.asarray(val), np.arange(9999, dtype=np.int32))
    world.barrier()
    print(f"NW_MIXED_OK {me}", flush=True)
    mpi.finalize()
""")
        assert rc == 0, out
        for me in range(3):
            assert f"NW_MIXED_OK {me}" in out, out

    def test_sigkill_mid_transfer_raises_proc_failed(self, tmp_path,
                                                     capfd):
        """A sender SIGKILLed mid-transfer (header sent, ring partly
        drained) surfaces as the typed ERR_PROC_FAILED through the shm
        ring's dead-producer check — fast, never the generic 30s
        ERR_PENDING timeout."""
        rc, out, _job = _run_job(tmp_path, capfd, """
    import signal, threading
    world = mpi.init()
    rt = Runtime.current()
    me = rt.bootstrap["process_index"]
    if me == 1:
        big = np.zeros(48 << 20, np.uint8)  # 48 MiB >> the 8 MiB ring

        def _s():
            world.send(big, 0, tag=25, rank=rt.local_rank_offset)

        threading.Thread(target=_s, daemon=True).start()
        time.sleep(1.0)  # header out, ring full, writev blocked
        os.kill(os.getpid(), signal.SIGKILL)
    time.sleep(2.0)  # let the sender die mid-stream first
    t0 = time.monotonic()
    try:
        world.recv(source=rt.local_size, tag=25, rank=0)
        raise AssertionError("recv from killed sender returned")
    except mpi.MPIError as e:
        dt = time.monotonic() - t0
        assert e.code == mpi.ErrorCode.ERR_PROC_FAILED, e
        assert dt < 20, f"typed error took {dt:.1f}s"
    print(f"NW_KILL_OK {me}", flush=True)
    mpi.finalize()
""", n=2, timeout=120, job_kw={"on_failure": "continue"})
        assert rc == 0, out
        assert "NW_KILL_OK 0" in out, out

    def test_sigstop_consumer_postmortem_names_ring(self, tmp_path,
                                                    capfd):
        """A consumer SIGSTOPped mid-drain leaves the sender blocked
        in the armed ``nw_ring_put`` wait; the stall watchdog's
        postmortem names the blocked ring token (the ``/onw-`` shm
        name), the frozen peer's pid, the direction, and the live
        occupancy — and the ``native_rings`` contributor carries every
        ring's counter block. A third rank SIGCONTs the consumer so
        the job still finishes clean (the typed-error contract for a
        DEAD peer is the previous test; a stopped peer is a stall, not
        a failure)."""
        import json

        pm_dir = tmp_path / "pm"
        pidf = tmp_path / "consumer.pid"
        rc, out, _job = _run_job(tmp_path, capfd, """
    import signal
    world = mpi.init()
    rt = Runtime.current()
    me = rt.bootstrap["process_index"]
    off = rt.local_rank_offset
    pidf = %(pidf)r
    big = np.zeros(48 << 20, np.uint8)  # 48 MiB >> the 8 MiB ring
    warm = np.ones(2 << 20, np.uint8)   # rides the native rings
    world.barrier()
    if me == 1:
        # warm transfer first: the consumer ATTACHES the rx ring
        # (stamping its pid into the shared header) before freezing
        world.send(warm, 0, tag=25, rank=off)
        world.recv(source=0, tag=27, rank=off)  # consumer frozen now
        world.send(big, 0, tag=25, rank=off)  # jams in nw_ring_put
        v, _st = world.recv(source=0, tag=26, rank=off)
        assert int(np.asarray(v)[0]) == 7
    elif me == 0:
        world.recv(source=rt.local_size, tag=25, rank=0)  # attach
        world.send(np.full(4, 9, np.int32), rt.local_size, tag=27,
                   rank=0)
        with open(pidf + ".tmp", "w") as f:
            f.write(str(os.getpid()))
        os.replace(pidf + ".tmp", pidf)  # rank 2's SIGCONT cue
        os.kill(os.getpid(), signal.SIGSTOP)  # freeze mid-transfer
        got, _st = world.recv(source=rt.local_size, tag=25, rank=0)
        assert np.asarray(got).nbytes == big.nbytes
        world.send(np.full(4, 7, np.int32), rt.local_size, tag=26,
                   rank=0)
    else:
        while not os.path.exists(pidf):
            time.sleep(0.05)
        time.sleep(5.0)  # stall timeout 1.5s: postmortem is on disk
        os.kill(int(open(pidf).read()), signal.SIGCONT)
    world.barrier()
    print(f"NW_STALL_OK {me}", flush=True)
    mpi.finalize()
""" % {"pidf": str(pidf)}, n=3, timeout=180,
            mca=[("obs_enable", "1"), ("obs_stall_timeout", "1.5"),
                 ("obs_postmortem_dir", str(pm_dir))],
            job_kw={"miss_limit": 40})
        assert rc == 0, out
        for me in range(3):
            assert f"NW_STALL_OK {me}" in out, out
        consumer_pid = int(pidf.read_text())
        pms = sorted(pm_dir.glob("postmortem-*.json"))
        assert pms, f"no postmortems in {pm_dir}: {out}"
        stalls = []
        for p in pms:
            with open(p) as f:
                doc = json.load(f)
            for w in doc.get("stalled") or []:
                if w.get("op") == "nw_ring_put":
                    stalls.append((doc["rank"], w,
                                   doc.get("native_rings")))
        assert stalls, f"no nw_ring_put stall in {pms}"
        # the ring table of THAT dump: under load other ranks dump too
        # (a barrier that waits out the 48 MiB), and theirs sent nothing
        (rank, w, rings_doc), = stalls[:1]
        assert int(rank["pidx"]) == 1, stalls
        info = w["info"]
        assert info["ring"].startswith("/onw-"), info
        assert info["dir"] == "send", info
        assert int(info["peer_pid"]) == consumer_pid, (
            info, consumer_pid)
        assert info["occupancy"] > 0.5, info  # ring jammed full
        assert info["pending"] > 0 and info["capacity"] > 0, info
        # the fleet-wide ring table rode along in the same dump
        assert isinstance(rings_doc, dict), pms
        assert rings_doc["tx"], rings_doc
        tx0 = rings_doc["tx"][0]
        assert tx0["name"].startswith("/onw-"), tx0
        assert tx0["stats"]["w_stalls"] >= 1, tx0
