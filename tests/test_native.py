"""Native control plane tests: DSS, routed OOB, multi-process
coordinator (the oob_stress / orte system-test analogue, SURVEY §4.3 —
real processes over localhost)."""

import ctypes
import json
import os
import subprocess
import sys
import textwrap
import zlib

import numpy as np
import pytest

from ompi_release_tpu.native import DssBuffer, OobEndpoint, ShmRing, crc32
from ompi_release_tpu.native import bindings as nb
from ompi_release_tpu.runtime.coordinator import HnpCoordinator
from ompi_release_tpu.utils.errors import ErrorCode, MPIError


class TestDss:
    def test_roundtrip_all_types(self):
        b = DssBuffer()
        b.pack_int64([1, -2, 3]).pack_string("héllo").pack_double(
            [3.25, -0.5]
        ).pack_bytes(b"\x00\xff\x80")
        r = DssBuffer(b.tobytes())
        assert r.peek() == ("int64", 3)
        assert r.unpack_int64() == [1, -2, 3]
        assert r.unpack_string() == "héllo"
        assert r.unpack_double() == [3.25, -0.5]
        assert r.unpack_bytes() == b"\x00\xff\x80"
        assert r.peek() is None  # exhausted

    def test_type_mismatch_raises_and_preserves_cursor(self):
        b = DssBuffer()
        b.pack_int64(7).pack_string("x")
        r = DssBuffer(b.tobytes())
        with pytest.raises(MPIError):
            r.unpack_string()
        assert r.unpack_int64() == [7]  # cursor unharmed by the miss

    def test_truncated_buffer_raises(self):
        b = DssBuffer()
        b.pack_int64([1, 2, 3, 4])
        r = DssBuffer(b.tobytes()[:10])  # cut mid-payload
        with pytest.raises(MPIError):
            r.unpack_int64()

    def test_rewind(self):
        b = DssBuffer()
        b.pack_string("again")
        raw = DssBuffer(b.tobytes())
        assert raw.unpack_string() == "again"
        raw.rewind()
        assert raw.unpack_string() == "again"

    def test_unpack_scratch_is_the_items_size_not_the_bound(self):
        """A ctypes scratch array is zero-filled: sized by the default
        bound it was 8 MiB per ``unpack_int64()`` — two of them in
        every staged header a receiver parses. The scratch is what the
        next item holds; the bound still refuses a longer item and
        leaves the cursor where it was."""
        b = DssBuffer()
        b.pack_int64([5, 6, 7]).pack_string("abc").pack_bytes(b"xy")
        b.pack_double([1.5])
        r = DssBuffer(b.tobytes())
        assert r._room(1_048_576) == 3
        with pytest.raises(MPIError) as ei:
            r.unpack_int64(max_count=2)  # the item is longer: refused
        assert ei.value.code == ErrorCode.ERR_TYPE
        assert r.unpack_int64(max_count=3) == [5, 6, 7]
        assert r._room(1 << 20, extra=1) == 4
        with pytest.raises(MPIError):
            r.unpack_string(max_len=3)  # no room for the NUL
        assert r.unpack_string(max_len=4) == "abc"
        assert r.unpack_bytes() == b"xy"
        assert r.unpack_double() == [1.5]
        assert r._room(8) == 1  # exhausted: one element, then the error
        with pytest.raises(MPIError) as ei:
            r.unpack_int64()
        assert ei.value.code == ErrorCode.ERR_TRUNCATE


_CRC_SIZES = [0, 1, 63, 64, 65, (1 << 20) - 1, 1 << 20, (1 << 20) + 1]


class TestSharedCrc:
    """``native/crc32.h`` is the one CRC of the library: the plan
    executor's, the ring's one-call read and — through
    ``native.crc32`` — the nativewire sender's. Its value is
    ``zlib.crc32``'s (the header frame of a mixed fleet depends on
    it), on the CPU's carry-less-multiply path and on the tables, from
    any start and chained over any cut."""

    @staticmethod
    def _crc(path, buf, lo, hi, prior=0):
        if path == "cpu":  # what the wire calls
            return crc32(memoryview(buf)[lo:hi], prior)
        ptr = ctypes.cast(buf.ctypes.data + lo,
                          ctypes.POINTER(ctypes.c_uint8))
        return int(nb.load_library().planexec_crc32(prior, ptr, hi - lo, 1))

    @pytest.mark.parametrize("path", ["cpu", "tables"])
    @pytest.mark.parametrize("n", _CRC_SIZES)
    def test_equals_zlib_from_any_start(self, n, path):
        buf = np.random.default_rng(n).integers(0, 256, n + 16,
                                                dtype=np.uint8)
        raw = buf.tobytes()
        for off in (0, 1, 3, 7, 13):
            assert self._crc(path, buf, off, off + n) == \
                zlib.crc32(raw[off:off + n]), (n, off)

    @pytest.mark.parametrize("path", ["cpu", "tables"])
    @pytest.mark.parametrize(
        "n,chunk", [(n, c) for n in _CRC_SIZES
                    for c in (1, 61, 4096, 1 << 18) if n // c <= 5000])
    def test_chained_over_fragment_cuts(self, n, chunk, path):
        buf = np.random.default_rng(n + chunk).integers(
            0, 256, n, dtype=np.uint8)
        got = 0
        for lo in range(0, n, chunk):
            got = self._crc(path, buf, lo, min(n, lo + chunk), got)
        assert got == zlib.crc32(buf.tobytes())


@pytest.fixture
def ring_pair():
    """A producer and a consumer handle on one small ring."""
    made = []

    def make(capacity):
        name = f"/onw-msgtest-{os.getpid()}-{len(made)}"
        ShmRing.unlink(name)
        tx = ShmRing.create(name, capacity, os.getpid())
        rx = ShmRing.attach(name, os.getpid())
        made.append((name, tx, rx))
        return tx, rx

    yield make
    for name, tx, rx in made:
        rx.close()
        tx.close()
        ShmRing.unlink(name)


class TestRingMessageCalls:
    """``shmring_write_msg`` / ``shmring_read_msg``: a message's
    fragment records in one call a side, handing back only for what
    the caller has to deal with."""

    TAG, XFER = 41, 9001

    @staticmethod
    def _payload(n):
        return np.random.default_rng(n).integers(0, 256, n, dtype=np.uint8)

    def test_roomy_ring_one_call_a_side(self, ring_pair):
        tx, rx = ring_pair(1 << 20)
        x = self._payload(300_001)
        chunk, nchunks = 1 << 16, 5
        assert tx.write_msg(self.TAG, self.XFER, x, chunk, 0, nchunks,
                            100) == (nchunks, 0)
        assert tx.stats()["w_frames"] == nchunks
        out = np.empty(x.nbytes, np.uint8)
        crc = (ctypes.c_int64 * 2)(0, 0)
        assert rx.read_msg(self.TAG, self.XFER, nchunks, chunk, out,
                           nchunks, 100, crc) == (nchunks, 0)
        np.testing.assert_array_equal(out, x)
        # checksummed inside the copy, every fragment in order
        assert (crc[0], crc[1]) == (zlib.crc32(x.tobytes()), nchunks)
        assert rx.pending() == 0

    def test_records_are_the_templates_fragments(self, ring_pair):
        """What write_msg puts in the ring is what writev puts there
        from ``FrameTemplate.sg_lists``: the same records, byte for
        byte (a portable reader, the plan executor and ``read_frag``
        all read them)."""
        from ompi_release_tpu.btl import components as btl_comps

        tx, rx = ring_pair(1 << 20)
        x = self._payload(10_000)
        tpl = btl_comps.plan_frame_template(x.shape, x.dtype, 4096)
        frames = list(tpl.sg_lists(memoryview(x), self.XFER, 0))[1:]
        assert tx.write_msg(self.TAG, self.XFER, x, tpl.chunk, 0,
                            tpl.nchunks, 100) == (tpl.nchunks, 0)
        tmp = bytearray(1 << 16)
        for parts in frames:
            n, tag = rx.read_into(tmp, 100)
            assert tag == self.TAG
            assert bytes(tmp[:n]) == b"".join(bytes(p) for p in parts)

    def test_small_ring_hands_back_and_resumes(self, ring_pair):
        """A ring smaller than the message: the writer hands back at
        the first record that does not fit, after its slice, with the
        count that went in; the reader hands back on an empty ring;
        both resume where they stopped and the checksum chains across
        the calls."""
        tx, rx = ring_pair(64 * 1024)
        x = self._payload(250_000)
        chunk = 16 * 1024
        nchunks = -(-x.nbytes // chunk)
        out = np.empty(x.nbytes, np.uint8)
        crc = (ctypes.c_int64 * 2)(0, 0)
        sent = got = wcalls = rcalls = 0
        while got < nchunks:
            if sent < nchunks:
                n, rc = tx.write_msg(self.TAG, self.XFER, x, chunk, sent,
                                     nchunks, 5)
                wcalls += 1
                sent += n
                assert rc == (0 if sent == nchunks else -1)
                assert 0 < n < nchunks  # some, never all: 64 KiB ring
            n, rc = rx.read_msg(self.TAG, self.XFER, nchunks, chunk, out,
                                nchunks - got, 5, crc)
            rcalls += 1
            got += n
            assert rc == (0 if got == nchunks else -1)
        assert wcalls > 1 and rcalls > 1
        np.testing.assert_array_equal(out, x)
        assert (crc[0], crc[1]) == (zlib.crc32(x.tobytes()), nchunks)
        # a blocked record is one stall, however the calls were cut
        assert tx.stats()["w_stalls"] == wcalls - 1

    def test_foreign_tag_at_the_head_is_left_for_the_caller(self,
                                                            ring_pair):
        tx, rx = ring_pair(1 << 20)
        x = self._payload(40_000)
        chunk, nchunks = 8192, 5
        assert tx.write_msg(self.TAG, self.XFER, x, chunk, 0, 2, 100) \
            == (2, 0)
        assert tx.writev(self.TAG + 1, [b"someone else's"], 100) == 0
        assert tx.write_msg(self.TAG, self.XFER, x, chunk, 2, nchunks,
                            100) == (3, 0)
        out = np.empty(x.nbytes, np.uint8)
        crc = (ctypes.c_int64 * 2)(0, 0)
        assert rx.read_msg(self.TAG, self.XFER, nchunks, chunk, out,
                           nchunks, 100, crc) == (2, -5)
        tmp = bytearray(64)
        n, tag = rx.read_into(tmp, 100)  # the caller restashes it
        assert (bytes(tmp[:n]), tag) == (b"someone else's", self.TAG + 1)
        assert rx.read_msg(self.TAG, self.XFER, nchunks, chunk, out, 3,
                           100, crc) == (3, 0)
        np.testing.assert_array_equal(out, x)
        assert (crc[0], crc[1]) == (zlib.crc32(x.tobytes()), nchunks)

    def test_out_of_order_and_stale_fragments(self, ring_pair):
        """Fragments out of index order land where they belong and
        break the running checksum (the caller then checks the whole
        buffer); a fragment of another transfer on the same tag is
        dropped inside the call, as ``read_frag`` drops it."""
        tx, rx = ring_pair(1 << 20)
        x = self._payload(20_000)
        chunk, nchunks = 8192, 3
        pre = b"SGC2" + self.XFER.to_bytes(8, "big")
        stale = b"SGC2" + (self.XFER - 1).to_bytes(8, "big")
        mv = memoryview(x)
        assert tx.writev(self.TAG, [stale, (0).to_bytes(8, "big"),
                                    b"old"], 100) == 0
        for idx in (1, 0, 2):
            assert tx.writev(
                self.TAG, [pre, idx.to_bytes(8, "big"),
                           mv[idx * chunk:(idx + 1) * chunk]], 100) == 0
        out = np.empty(x.nbytes, np.uint8)
        crc = (ctypes.c_int64 * 2)(0, 0)
        assert rx.read_msg(self.TAG, self.XFER, nchunks, chunk, out,
                           nchunks, 100, crc) == (nchunks, 0)
        np.testing.assert_array_equal(out, x)
        assert crc[1] == -1
        assert rx.stats()["r_frames"] == nchunks + 1

    def test_empty_message_is_one_empty_fragment(self, ring_pair):
        tx, rx = ring_pair(1 << 16)
        x = np.empty(0, np.uint8)
        assert tx.write_msg(self.TAG, self.XFER, x, 4096, 0, 1, 100) \
            == (1, 0)
        crc = (ctypes.c_int64 * 2)(0, 0)
        assert rx.read_msg(self.TAG, self.XFER, 1, 4096, x, 1, 100,
                           crc) == (1, 0)
        assert (crc[0], crc[1]) == (0, 1)

    def test_codes_the_caller_must_handle(self, ring_pair):
        tx, rx = ring_pair(1 << 16)
        x = self._payload(1 << 18)
        # a fragment record that can never fit this ring: route it
        assert tx.write_msg(self.TAG, self.XFER, x, 1 << 17, 0, 2, 5) \
            == (0, -2)
        # an overrun (consumed, as read_frag does) is malformed
        assert tx.write_msg(self.TAG, self.XFER, x, 4096, 0, 2, 5) \
            == (2, 0)
        out = np.empty(4096, np.uint8)  # room for one fragment only
        crc = (ctypes.c_int64 * 2)(0, 0)
        assert rx.read_msg(self.TAG, self.XFER, 2, 4096, out, 2, 5,
                           crc) == (1, -2)
        assert rx.pending() == 0
        # an empty slice
        assert rx.read_msg(self.TAG, self.XFER, 2, 4096, out, 1, 5,
                           crc) == (0, -1)


class TestOob:
    def test_direct_send_recv(self):
        a, b = OobEndpoint(0), OobEndpoint(1)
        try:
            b.connect(0, "127.0.0.1", a.port)
            b.send(0, 7, b"hi root")
            src, tag, p = a.recv(tag=7, timeout_ms=5000)
            assert (src, tag, p) == (1, 7, b"hi root")
            a.send(1, 8, b"hi leaf")  # reverse over same connection
            assert b.recv(tag=8, timeout_ms=5000)[2] == b"hi leaf"
        finally:
            a.close()
            b.close()

    def test_tree_routing_three_hop(self):
        """A - B - C chain: frames relay through B both directions."""
        a, mid, c = OobEndpoint(0), OobEndpoint(1), OobEndpoint(2)
        try:
            a.connect(1, "127.0.0.1", mid.port)
            c.connect(1, "127.0.0.1", mid.port)
            a.add_route(2, 1)
            c.set_default_route(1)
            a.send(2, 42, b"down")
            assert c.recv(tag=42, timeout_ms=5000)[2] == b"down"
            c.send(0, 43, b"up")
            assert a.recv(tag=43, timeout_ms=5000)[2] == b"up"
        finally:
            for e in (a, mid, c):
                e.close()

    def test_large_payload_and_tag_selectivity(self):
        a, b = OobEndpoint(0), OobEndpoint(1)
        try:
            b.connect(0, "127.0.0.1", a.port)
            big = bytes(range(256)) * 8192  # 2 MiB
            b.send(0, 2, b"second")
            b.send(0, 1, big)
            src, tag, p = a.recv(tag=1, timeout_ms=5000)
            assert p == big  # picked by tag, not arrival order
            assert a.recv(tag=2, timeout_ms=5000)[2] == b"second"
        finally:
            a.close()
            b.close()

    def test_auth_refuses_unauthenticated_frames(self):
        """A WELL-FORMED announce + data frame from a connection that
        never answered the challenge must be refused — the server
        queues nothing and counts the rejection (opal/mca/sec
        analogue; VERDICT r4 missing #4)."""
        import socket
        import struct

        srv = OobEndpoint(0, secret=b"job-secret")
        try:
            # raw TCP injector: speaks the frame format but has no key
            s = socket.create_connection(("127.0.0.1", srv.port),
                                         timeout=5)
            try:
                # server sends its challenge first; read & ignore it
                hdr = s.recv(24)
                assert len(hdr) == 24
                magic, _, _, tag, _, ln = struct.unpack("<IiiiiI", hdr)
                assert magic == 0x4F4D5054 and tag == -998
                s.recv(ln)
                # well-formed announce (tag -999), then a data frame
                s.sendall(struct.pack("<IiiiiI", 0x4F4D5054, 7, 0,
                                      -999, 32, 0))
                s.sendall(struct.pack("<IiiiiI", 0x4F4D5054, 7, 0,
                                      5, 32, 4) + b"evil")
                with pytest.raises(MPIError):
                    srv.recv(tag=5, timeout_ms=500)
                assert srv.auth_rejected() >= 1
            finally:
                s.close()
        finally:
            srv.close()

    def test_auth_wrong_secret_refused_right_secret_works(self):
        srv = OobEndpoint(0, secret=b"right")
        try:
            bad = OobEndpoint(1, secret=b"wrong")
            try:
                # the TCP connect itself succeeds; the first use shows
                # the server dropped the link after the bad response
                try:
                    bad.connect(0, "127.0.0.1", srv.port)
                    bad.send(0, 5, b"x")
                except MPIError:
                    pass
                with pytest.raises(MPIError):
                    srv.recv(tag=5, timeout_ms=500)
            finally:
                bad.close()
            good = OobEndpoint(2, secret=b"right")
            try:
                good.connect(0, "127.0.0.1", srv.port)
                good.send(0, 6, b"authed")
                src, tag, p = srv.recv(tag=6, timeout_ms=5000)
                assert (src, tag, p) == (2, 6, b"authed")
                srv.send(2, 7, b"back")
                assert good.recv(tag=7, timeout_ms=5000)[2] == b"back"
            finally:
                good.close()
        finally:
            srv.close()

    def test_recv_timeout(self):
        a = OobEndpoint(0)
        try:
            with pytest.raises(MPIError):
                a.recv(tag=9, timeout_ms=100)
        finally:
            a.close()


WORKER_SCRIPT = textwrap.dedent("""
    import sys, json
    sys.path.insert(0, "/root/repo")
    from ompi_release_tpu.runtime.coordinator import WorkerAgent

    rank, port = int(sys.argv[1]), int(sys.argv[2])
    n = 4
    agent = WorkerAgent(rank, "127.0.0.1", port)
    cards = agent.run_modex({"host": f"worker{rank}", "devices": rank})
    assert cards[rank]["devices"] == rank, cards
    # tree links (cards[0] is the HNP's card; workers are 1..n-1)
    agent.setup_tree(n, cards[1:])
    agent.barrier()   # gates xcast on every tree edge being live
    payload = agent.recv_xcast()   # relays to tree children
    agent.barrier()
    print(json.dumps({"rank": rank, "n_cards": len(cards),
                      "xcast": payload.decode()}))
    agent.wait_fin()
""")


class TestCoordinator:
    def test_multiprocess_modex_barrier_xcast(self, tmp_path):
        """4 real processes: modex allgather, two barriers, one xcast —
        the wire-up sequence of SURVEY §3.2 over localhost."""
        n = 4
        script = tmp_path / "worker.py"
        script.write_text(WORKER_SCRIPT)
        hnp = HnpCoordinator(n)
        procs = [
            subprocess.Popen(
                [sys.executable, str(script), str(r), str(hnp.port)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            )
            for r in range(1, n)
        ]
        try:
            cards = hnp.run_modex({"host": "hnp", "devices": 0})
            assert [c["devices"] for c in cards] == [0, 1, 2, 3]
            hnp.barrier()
            hnp.xcast(b"job-config-v1")
            hnp.barrier()
        finally:
            hnp.shutdown()
        for p in procs:
            out, err = p.communicate(timeout=30)
            assert p.returncode == 0, err
            rec = json.loads(out.strip().splitlines()[-1])
            assert rec["n_cards"] == n and rec["xcast"] == "job-config-v1"


PUBSUB_SCRIPT = textwrap.dedent("""
    import sys, json, time
    sys.path.insert(0, "/root/repo")
    from ompi_release_tpu.runtime.coordinator import WorkerAgent

    rank, port = int(sys.argv[1]), int(sys.argv[2])
    agent = WorkerAgent(rank, "127.0.0.1", port)
    agent.run_modex({"role": rank})
    if rank == 1:
        # the LOOKUP is issued first (the HNP parks it until the
        # publish arrives — pubsub_orte's blocking lookup)
        found = agent.lookup_name("ocean-svc", timeout_ms=15000)
        print(json.dumps({"rank": rank, "found": found}))
    else:
        time.sleep(0.5)  # let worker 1's lookup land first
        agent.publish_name("ocean-svc", "tpu-port:42")
        found = agent.lookup_name("ocean-svc")
        try:
            agent.publish_name("ocean-svc", "tpu-port:43")
            dup_rejected = False
        except Exception:
            dup_rejected = True
        agent.unpublish_name("ocean-svc")
        print(json.dumps({"rank": rank, "found": found,
                          "dup_rejected": dup_rejected}))
    agent.close()
""")


class TestNameServer:
    def test_publish_lookup_over_oob(self, tmp_path):
        """HNP-hosted name service (pubsub_orte/orte-server role):
        a parked lookup is answered by a later publish from another
        process; duplicate publish is rejected; unpublish works."""
        n = 3
        script = tmp_path / "pubsub_worker.py"
        script.write_text(PUBSUB_SCRIPT)
        hnp = HnpCoordinator(n)
        hnp.start_name_server()
        procs = [
            subprocess.Popen(
                [sys.executable, str(script), str(r), str(hnp.port)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            )
            for r in range(1, n)
        ]
        try:
            hnp.run_modex({"role": "hnp"})
            recs = {}
            for p in procs:
                out, err = p.communicate(timeout=30)
                assert p.returncode == 0, err
                rec = json.loads(out.strip().splitlines()[-1])
                recs[rec["rank"]] = rec
        finally:
            hnp.shutdown()
        assert recs[1]["found"] == "tpu-port:42"
        assert recs[2]["found"] == "tpu-port:42"
        assert recs[2]["dup_rejected"] is True


def test_closed_endpoint_raises_not_segfaults():
    """Every OobEndpoint entry point on a closed endpoint raises a
    clean MPIError instead of handing NULL to the C layer."""
    ep = OobEndpoint(0)
    port = ep.port
    ep.close()
    ep.close()  # idempotent
    with pytest.raises(MPIError):
        _ = ep.port
    with pytest.raises(MPIError):
        ep.send(1, 5, b"x")
    with pytest.raises(MPIError):
        ep.recv(tag=5, timeout_ms=50)
    with pytest.raises(MPIError):
        ep.connect(1, "127.0.0.1", port)


def test_processes_that_start_together_build_once(tmp_path):
    """A fresh checkout (no ``native/build``), four processes reaching
    for the library at once, as xdist workers and ``tpurun`` ranks do:
    the compiler runs once and every process loads a whole library —
    before, each ran ``make`` into the same directory and one could
    load the .so another compiler was still writing (35 tests of
    ``test_native_exec.py`` skipped as "planexec symbols not in the
    loaded .so" in one whole run of two)."""
    import os
    import shutil
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    shutil.copytree(os.path.join(repo, "ompi_release_tpu"),
                    tmp_path / "ompi_release_tpu",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(os.path.join(repo, "native"), tmp_path / "native",
                    ignore=shutil.ignore_patterns("build"))
    log = tmp_path / "compiles.log"
    cxx = tmp_path / "cxx"
    cxx.write_text(f'#!/bin/sh\necho run >> {log}\nexec g++ "$@"\n')
    cxx.chmod(0o755)
    probe = ("import sys; sys.path.insert(0, %r)\n"
             "from ompi_release_tpu.native import bindings as b\n"
             "assert b.__file__.startswith(%r), b.__file__\n"
             "print('WHOLE', b.planexec_symbols_available()"
             " and b.wire_symbols_available())\n"
             % (str(tmp_path), str(tmp_path)))
    env = dict(os.environ, CXX=str(cxx))
    procs = [subprocess.Popen([sys.executable, "-c", probe], env=env,
                              stdout=subprocess.PIPE, text=True)
             for _ in range(4)]
    outs = [p.communicate(timeout=300)[0] for p in procs]
    assert [p.returncode for p in procs] == [0] * 4, outs
    assert all("WHOLE True" in o for o in outs), outs
    assert log.read_text().count("run") == 1
