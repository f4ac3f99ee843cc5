"""tpu_info CLI + tracing interposition tests."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import ompi_release_tpu as mpi
from ompi_release_tpu import ops
from ompi_release_tpu.tools import tpu_info, trace
from ompi_release_tpu.utils.errors import MPIError


@pytest.fixture(scope="module")
def world():
    yield mpi.init()


class TestTpuInfo:
    def test_gather_structure(self, world):
        info = tpu_info.gather()
        names = [f["name"] for f in info["frameworks"]]
        assert "coll" in names and "pml" in names and "op" in names
        coll = next(f for f in info["frameworks"] if f["name"] == "coll")
        comp_names = [c["name"] for c in coll["components"]]
        assert "tuned" in comp_names and "xla" in comp_names
        assert any(v["name"] == "pml_eager_limit"
                   for v in info["variables"])
        assert len(info["devices"]) >= 1

    def test_render_text(self, world):
        info = tpu_info.gather()
        text = tpu_info.render_text(info, show_vars=True)
        assert "Frameworks:" in text and "pml_eager_limit" in text

    def test_cli_json_subprocess(self):
        out = subprocess.run(
            [sys.executable, "-m", "ompi_release_tpu.tools.tpu_info",
             "--json", "--param", "coll"],
            capture_output=True, text=True, timeout=120, cwd="/root/repo",
            env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
                 "HOME": "/root"},
        )
        assert out.returncode == 0, out.stderr
        info = json.loads(out.stdout)
        assert all("coll" in v["name"] for v in info["variables"])


class TestTracing:
    def test_interposition_records_events(self, world, tmp_path):
        sink = str(tmp_path / "trace.jsonl")
        tc = trace.wrap(world, sink)
        x = np.ones((world.size, 100), np.float32)
        tc.allreduce(x, ops.SUM)
        tc.bcast(x, root=0)
        tc.barrier()
        tc.send(np.int32(1), dest=1, tag=600, rank=0)
        tc.recv(source=0, tag=600, rank=1)
        s = tc.summary()
        assert s["allreduce"]["calls"] == 1
        assert s["allreduce"]["bytes"] == x.nbytes
        assert s["barrier"]["calls"] == 1 and s["recv"]["calls"] == 1
        tc.close()
        lines = [json.loads(l) for l in open(sink)]
        assert len(lines) == 5
        assert lines[0]["op"] == "allreduce" and lines[0]["dt"] >= 0

    def test_passthrough_untraced(self, world):
        tc = trace.wrap(world)
        assert tc.size == world.size  # attribute passthrough
        sub = tc.dup("traced_dup")  # untraced method passthrough
        sub.free()


class TestTpuServer:
    """Standalone orte-server analogue: name exchange between
    INDEPENDENT jobs (no shared HNP)."""

    def test_cross_job_publish_lookup(self):
        from ompi_release_tpu.tools.tpu_server import (
            NameClient, NameServer,
        )

        srv = NameServer()
        a = NameClient("127.0.0.1", srv.port)  # "job A"
        b = NameClient("127.0.0.1", srv.port)  # "job B"
        try:
            assert a.client_id != b.client_id
            a.publish("cross-job-svc", "tpu-port:99")
            assert b.lookup("cross-job-svc") == "tpu-port:99"
            # parked lookup answered by a later publish
            import threading

            got = {}
            t = threading.Thread(
                target=lambda: got.update(
                    v=b.lookup("late-svc", timeout_ms=15000))
            )
            t.start()
            import time
            time.sleep(0.3)
            a.publish("late-svc", "tpu-port:7")
            t.join(timeout=15)
            assert got["v"] == "tpu-port:7"
            a.unpublish("cross-job-svc")
            with pytest.raises(MPIError):
                b.lookup("cross-job-svc", timeout_ms=300)
        finally:
            a.close()
            b.close()
            srv.shutdown()

    def test_concurrent_rpcs_do_not_serialize(self):
        """A publish issued from another thread of the SAME client
        endpoint while a lookup is parked server-side completes
        immediately and unparks that lookup — the reply demultiplexer
        means concurrent RPCs never wait out each other's timeouts."""
        import threading
        import time as _time

        from ompi_release_tpu.tools.tpu_server import (
            NameClient, NameServer,
        )

        srv = NameServer()
        client = NameClient("127.0.0.1", srv.port)
        try:
            got = {}

            def looker():
                t0 = _time.monotonic()
                got["value"] = client.lookup("late-svc",
                                             timeout_ms=20_000)
                got["elapsed"] = _time.monotonic() - t0

            t = threading.Thread(target=looker, daemon=True)
            t.start()
            _time.sleep(0.3)  # lookup is parked server-side now
            t0 = _time.monotonic()
            client.publish("late-svc", "9191")  # same endpoint!
            publish_took = _time.monotonic() - t0
            t.join(timeout=10)
            assert not t.is_alive()
            assert got["value"] == "9191"
            # the publish must not have waited behind the parked
            # lookup's 20s budget, and the lookup unparked promptly
            assert publish_took < 5, publish_took
            assert got["elapsed"] < 10, got["elapsed"]
        finally:
            client.close()
            srv.shutdown()

    def test_cli_prints_uri(self):
        import subprocess
        import sys

        p = subprocess.Popen(
            [sys.executable, "-m", "ompi_release_tpu.tools.tpu_server"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        try:
            line = p.stdout.readline()
            assert line.startswith("tpu-server URI: ")
            host_port = line.split(": ", 1)[1].strip()
            host, port = host_port.rsplit(":", 1)
            assert int(port) > 0
        finally:
            p.terminate()
            p.wait(timeout=10)


class TestExamples:
    """The reference's examples/ are its acceptance programs
    (SURVEY §4 item 4); ours must run the same way."""

    @pytest.mark.parametrize("name", [
        "ring_tpu.py", "connectivity_tpu.py", "allreduce_tpu.py",
        "hello_oshmem_tpu.py", "ring_oshmem_tpu.py",
        "oshmem_reduction_tpu.py", "unified_world_tpu.py",
    ])
    def test_example_runs_driver_mode(self, name):
        import os
        import subprocess

        from conftest import subprocess_env

        # a bare subprocess would take the machine's accelerator and
        # run single-device instead of on the 8-device mesh
        env = subprocess_env(
            XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                       + " --xla_force_host_platform_device_count=8"))
        r = subprocess.run(
            [sys.executable, f"examples/{name}"], cwd="/root/repo",
            env=env, capture_output=True, text=True, timeout=300,
        )
        assert r.returncode == 0, r.stderr
        assert "OK" in r.stdout or "complete" in r.stdout

    def test_unified_world_example_under_tpurun(self):
        """The cross-process acceptance example: 2 processes x 4
        virtual devices, collectives + p2p + RMA across the boundary
        through the public API."""
        import os
        import subprocess

        from conftest import subprocess_env

        env = subprocess_env(
            XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                       + " --xla_force_host_platform_device_count=4"))
        r = subprocess.run(
            [sys.executable, "-m", "ompi_release_tpu.tools.tpurun",
             "-n", "2", sys.executable,
             "examples/unified_world_tpu.py"],
            cwd="/root/repo", env=env, capture_output=True, text=True,
            timeout=300,
        )
        assert r.returncode == 0, r.stderr + r.stdout
        assert "unified world OK (ranks 0..3 of 8)" in r.stdout
        assert "unified world OK (ranks 4..7 of 8)" in r.stdout

    def test_hello_under_tpurun(self):
        import subprocess

        from conftest import subprocess_env

        # explicit host ranks: this launch test is about tpurun, not
        # about chips (a chip belongs to one process at a time)
        env = subprocess_env()
        r = subprocess.run(
            [sys.executable, "-m", "ompi_release_tpu.tools.tpurun",
             "-n", "3", sys.executable, "examples/hello_tpu.py"],
            cwd="/root/repo", env=env, capture_output=True, text=True,
            timeout=300,
        )
        assert r.returncode == 0, r.stderr + r.stdout
        for rank in range(3):
            assert f"I am process {rank} of 3" in r.stdout


class TestTpuClean:
    """tpu-clean (orte-clean analogue): stale sessions + orphaned shm
    segments of dead jobs are removed; live ones are never touched."""

    def test_clean_reaps_only_dead_owners(self, tmp_path, monkeypatch):
        import io
        import json
        from multiprocessing import shared_memory

        from ompi_release_tpu.tools import tpu_clean, tpurun

        sess = tmp_path / "sessions"
        sess.mkdir()
        monkeypatch.setattr(tpurun, "SESSION_DIR", str(sess))
        # dead-pid file, live file, malformed-but-valid-JSON debris
        # ({"pid": null} and a JSON list both count), non-JSON debris
        (sess / "111.json").write_text(json.dumps({"pid": 2 ** 22 + 17}))
        (sess / "live.json").write_text(json.dumps({"pid": os.getpid()}))
        (sess / "junk.json").write_text("{not json")
        (sess / "nullpid.json").write_text('{"pid": null}')
        (sess / "list.json").write_text("[1, 2]")

        # a per-test prefix isolates the scan from any real ompitpu-*
        # debris on this machine (and keeps the real clean() pass from
        # touching segments the test did not create)
        prefix = f"omtst{os.getpid()}-"
        dead_seg = shared_memory.SharedMemory(
            create=True, size=64, name=f"{prefix}{2 ** 22 + 19}-dead")
        live_seg = shared_memory.SharedMemory(
            create=True, size=64, name=f"{prefix}{os.getpid()}-live")
        fresh_dead = shared_memory.SharedMemory(
            create=True, size=64, name=f"{prefix}{2 ** 22 + 23}-fresh")
        try:
            kw = dict(min_age_s=0.0, shm_prefix=prefix)
            # dry run removes nothing
            buf = io.StringIO()
            ns, ng = tpu_clean.clean(dry_run=True, verbose=True,
                                     out=buf, **kw)
            assert ns == 4 and ng == 2, buf.getvalue()
            assert (sess / "111.json").exists()
            # the min-age gate protects in-flight ownership handoffs
            # (sender exited, receiver about to map)
            _, ng_aged = tpu_clean.clean(
                dry_run=True, min_age_s=3600.0, shm_prefix=prefix,
                out=buf)
            assert ng_aged == 0
            ns, ng = tpu_clean.clean(verbose=True, out=buf, **kw)
            assert ns == 4 and ng == 2, buf.getvalue()
            for gone in ("111.json", "junk.json", "nullpid.json",
                         "list.json"):
                assert not (sess / gone).exists(), gone
            assert (sess / "live.json").exists()
            # dead-creator segments are gone, the live one intact
            for seg in (dead_seg, fresh_dead):
                with pytest.raises(FileNotFoundError):
                    shared_memory.SharedMemory(name=seg.name)
            shared_memory.SharedMemory(name=live_seg.name).close()
        finally:
            for seg in (live_seg, dead_seg, fresh_dead):
                seg.close()
                try:
                    seg.unlink()
                except FileNotFoundError:
                    pass

    def test_cli_reports_counts(self, tmp_path, monkeypatch):
        import subprocess

        r = subprocess.run(
            [sys.executable, "-m", "ompi_release_tpu.tools.tpu_clean",
             "--dry-run"],
            cwd="/root/repo", capture_output=True, text=True, timeout=60,
        )
        assert r.returncode == 0, r.stderr
        assert "tpu-clean: would remove" in r.stdout
