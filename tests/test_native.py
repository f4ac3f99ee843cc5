"""Native control plane tests: DSS, routed OOB, multi-process
coordinator (the oob_stress / orte system-test analogue, SURVEY §4.3 —
real processes over localhost)."""

import json
import subprocess
import sys
import textwrap

import pytest

from ompi_release_tpu.native import DssBuffer, OobEndpoint
from ompi_release_tpu.runtime.coordinator import HnpCoordinator
from ompi_release_tpu.utils.errors import MPIError


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
