"""Pipelined, zero-copy wire transport (runtime/wire.py + the DCN
staged path) — fragmentation/reassembly parity, channel concurrency,
overlapped spanning-comm exchanges, and the satellite fixes riding the
same PR.

Parity discipline: fragmented transfers must be BITWISE identical to
monolithic ones for every dtype/shape in the suite, and
``wire_pipeline_segsize=0`` must restore the exact legacy single-pass
framing (SGH1 header + ordered join), pinned here by sniffing the
actual wire frames.
"""

import os
import sys
import textwrap
import threading

import numpy as np
import pytest

from ompi_release_tpu.btl.components import (
    DcnBtl, _CHUNK2_MAGIC, _HDR2_MAGIC, _HDR_MAGIC,
)
from ompi_release_tpu.mca import pvar as pvar_mod
from ompi_release_tpu.mca import var as mca_var
from ompi_release_tpu.native import DssBuffer, OobEndpoint
from ompi_release_tpu.runtime.state import JobState
from ompi_release_tpu.tools.tpurun import Job

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Segsize:
    """Context manager pinning wire_pipeline_segsize (restores on exit)."""

    def __init__(self, seg):
        self.seg = seg

    def __enter__(self):
        mca_var.set_value("wire_pipeline_segsize", self.seg)

    def __exit__(self, *exc):
        mca_var.VARS.unset("wire_pipeline_segsize")


class TestStagedPipelineParity:
    """In-process OOB endpoint pairs: the fragment protocol itself."""

    def _pair(self):
        a, b = OobEndpoint(0), OobEndpoint(1)
        b.connect(0, "127.0.0.1", a.port)
        return a, b

    def test_fragmented_equals_monolithic_bitwise(self):
        """Odd sizes, segsize±1 boundaries, single-chunk fast path,
        several dtypes: every framing reassembles bitwise."""
        a, b = self._pair()
        m = DcnBtl()
        rng = np.random.RandomState(0)
        try:
            for seg in (0, 1024, 4096):
                with _Segsize(seg):
                    for n in (0, 1, 37, 255, 256, 257, 1023, 1024,
                              1025, 50_000):
                        for dt in (np.float32, np.int32, np.uint8):
                            x = (rng.randn(n) * 100).astype(dt)
                            m.send_staged(b, 0, 151, x)
                            got = np.asarray(m.recv_staged(a, 151))
                            assert got.dtype == x.dtype
                            assert got.shape == x.shape
                            np.testing.assert_array_equal(got, x)
                    # 2-D shape survives the flat byte stream
                    x = rng.randn(13, 7).astype(np.float32)
                    m.send_staged(b, 0, 151, x)
                    np.testing.assert_array_equal(
                        np.asarray(m.recv_staged(a, 151)), x)
            # byte-exact segsize boundaries: seg-1, seg, seg+1 payloads
            with _Segsize(1024):
                for nb in (1023, 1024, 1025, 2048, 2049):
                    x = rng.randint(0, 255, nb).astype(np.uint8)
                    m.send_staged(b, 0, 151, x)
                    np.testing.assert_array_equal(
                        np.asarray(m.recv_staged(a, 151)), x)
        finally:
            a.close()
            b.close()

    def test_segsize_zero_restores_legacy_framing(self):
        """seg=0 puts the LEGACY header magic on the wire; seg>0 the
        pipelined one — the acceptance criterion is the actual frame
        format, not just the result."""
        a, b = self._pair()
        m = DcnBtl()
        try:
            with _Segsize(0):
                m.send_staged(b, 0, 153, np.arange(64, dtype=np.float32))
            _, _, hraw = a.recv(tag=153, timeout_ms=10_000)
            assert DssBuffer(hraw).unpack_string() == _HDR_MAGIC
            a.recv(tag=153, timeout_ms=10_000)  # drain the chunk
            with _Segsize(64):
                m.send_staged(b, 0, 153, np.arange(64, dtype=np.float32))
            _, _, hraw = a.recv(tag=153, timeout_ms=10_000)
            assert DssBuffer(hraw).unpack_string() == _HDR2_MAGIC
            # drain the 4 fragments (64 f32 = 256 B at 64 B/frag)
            for _ in range(4):
                _, _, raw = a.recv(tag=153, timeout_ms=10_000)
                assert raw.startswith(_CHUNK2_MAGIC)
        finally:
            a.close()
            b.close()

    def test_interleaved_tags_one_peer(self):
        """Two fragmented transfers on DIFFERENT tags from one sender,
        frames interleaved on the wire: each tag reassembles its own
        payload intact (the per-(peer, tag-class) channel discipline)."""
        a, b = self._pair()
        m = DcnBtl()
        rng = np.random.RandomState(1)
        try:
            with _Segsize(512):
                x1 = rng.randn(2000).astype(np.float32)
                x2 = (rng.randn(1500) * 9).astype(np.int32)
                f1 = m.staged_frames(x1, segsize=512)
                f2 = m.staged_frames(x2, segsize=512)
                alive = [iter(f1), iter(f2)]
                tags = [201, 202]
                while alive:
                    keep = []
                    for it, tag in zip(alive, tags):
                        try:
                            b.send(0, tag, next(it))
                            keep.append((it, tag))
                        except StopIteration:
                            pass
                    alive = [it for it, _ in keep]
                    tags = [t for _, t in keep]
                got2 = np.asarray(m.recv_staged(a, 202))
                got1 = np.asarray(m.recv_staged(a, 201))
                np.testing.assert_array_equal(got1, x1)
                np.testing.assert_array_equal(got2, x2)
        finally:
            a.close()
            b.close()

    def test_interleaved_senders_one_tag_pipelined(self):
        """Two senders' fragment streams on ONE tag: the stash matches
        frames to each transfer's source (the legacy discipline, now
        under the pipelined framing)."""
        root, s1, s2 = OobEndpoint(0), OobEndpoint(1), OobEndpoint(2)
        try:
            s1.connect(0, "127.0.0.1", root.port)
            s2.connect(0, "127.0.0.1", root.port)
            m = DcnBtl()
            with _Segsize(4096):
                x1 = np.full(30_000, 1.5, np.float32)
                x2 = np.full(40_000, 2.5, np.float32)
                t1 = threading.Thread(
                    target=lambda: m.send_staged(s1, 0, 109, x1))
                t2 = threading.Thread(
                    target=lambda: m.send_staged(s2, 0, 109, x2))
                t1.start()
                t2.start()
                a = np.asarray(m.recv_staged(root, 109))
                c = np.asarray(m.recv_staged(root, 109))
                t1.join()
                t2.join()
                got = {arr.shape[0]: arr for arr in (a, c)}
                np.testing.assert_array_equal(got[30_000], x1)
                np.testing.assert_array_equal(got[40_000], x2)
        finally:
            for e in (root, s1, s2):
                e.close()

    def test_zero_copy_and_inflight_pvars_account(self):
        a, b = self._pair()
        m = DcnBtl()
        try:
            fi = pvar_mod.PVARS.lookup("wire_frags_inflight")

            def zc():
                got = pvar_mod.PVARS.read_all()
                return float(got["wire_bytes_zero_copy_strict"]
                             + got["wire_bytes_sliced"])

            assert fi is not None
            before = zc()
            with _Segsize(1024):
                x = np.ones(4096, np.uint8)
                m.send_staged(b, 0, 155, x)
                np.testing.assert_array_equal(
                    np.asarray(m.recv_staged(a, 155)), x)
            # sender slices + receiver view: 2 x 4096 bytes accounted
            assert zc() - before >= 2 * 4096
            assert float(fi.read()) >= 4  # 4 fragments announced
        finally:
            a.close()
            b.close()


# ---------------------------------------------------------------------------
# multi-process CPU-mesh jobs (the tpurun harness test_unified_world uses)
# ---------------------------------------------------------------------------

APP_PRELUDE = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, %r)
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4"
    ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import ompi_release_tpu as mpi
    from ompi_release_tpu.runtime.runtime import Runtime
""" % REPO)


def _write_app(tmp_path, body, name="app.py"):
    p = tmp_path / name
    p.write_text(APP_PRELUDE + textwrap.dedent(body))
    return str(p)


def _run(tmp_path, capfd, body, n=2, timeout=180, mca=()):
    app = _write_app(tmp_path, body)
    job = Job(n, [sys.executable, app], list(mca), heartbeat_s=0.5,
              miss_limit=8)
    rc = job.run(timeout_s=timeout)
    out = capfd.readouterr()
    assert rc == 0, out.out + out.err
    assert job.job_state.visited(JobState.TERMINATED)
    return out.out


class TestWireJobs:
    def test_pipelined_dcn_parity_and_concurrent_tags(self, tmp_path,
                                                      capfd):
        """Forced-DCN (distinct shm identities) with a small pipeline
        segsize: collectives and large p2p stay bitwise across the
        fragment protocol, two concurrent large sends on DISTINCT tags
        both arrive intact through their own lanes, and the zero-copy
        pvar proves the fragment path actually carried the bytes."""
        out = _run(tmp_path, capfd, """
            import threading
            os.environ["OMPITPU_HOST_ID"] = (
                "fakehost-" + os.environ["OMPITPU_NODE_ID"])
            from ompi_release_tpu.mca import pvar, var as mca_var
            world = mpi.init()
            rt = Runtime.current()
            off = rt.local_rank_offset
            n = world.size
            assert int(mca_var.get("wire_pipeline_segsize")) == 65536

            # collectives across the fragmented wire: bitwise parity
            x = np.stack([np.arange(65536, dtype=np.int32) * (off + i + 1)
                          for i in range(4)])  # 256 KiB/slice > segsize
            got = np.asarray(world.allreduce(x))
            want = sum(np.arange(65536, dtype=np.int32) * (r + 1)
                       for r in range(n))
            np.testing.assert_array_equal(got[0], want)
            full = [np.arange(10_000 + r, dtype=np.int32) + r
                    for r in range(n)]
            ag = np.asarray(world.allgatherv(full[off:off + 4]))
            np.testing.assert_array_equal(ag, np.concatenate(full))

            # two concurrent large p2p sends, distinct tags -> distinct
            # lanes: both payloads intact, delivery order preserved
            big1 = np.arange(1 << 19, dtype=np.float32)        # 2 MiB
            big2 = np.arange(1 << 19, dtype=np.float32) * -2.0
            if off == 0:
                ts = [threading.Thread(
                          target=lambda: world.send(big1, 5, tag=1,
                                                    rank=1)),
                      threading.Thread(
                          target=lambda: world.send(big2, 6, tag=2,
                                                    rank=2))]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
            else:
                v2, st2 = world.recv(source=2, tag=2, rank=6)
                v1, st1 = world.recv(source=1, tag=1, rank=5)
                np.testing.assert_array_equal(np.asarray(v1), big1)
                np.testing.assert_array_equal(np.asarray(v2), big2)
            world.barrier()
            got = pvar.PVARS.read_all()
            zc = (got.get("wire_bytes_zero_copy_strict", 0)
                  + got.get("wire_bytes_sliced", 0))
            assert zc > 0, "fragment path never carried a byte"
            print(f"WIREPIPE-OK {off}")
            mpi.finalize()
        """, mca=[("wire_pipeline_segsize", "65536")])
        assert "WIREPIPE-OK 0" in out and "WIREPIPE-OK 4" in out

    def test_exchange_reaps_in_arrival_order(self, tmp_path, capfd):
        """Posted-sends overlap: process 0 expects one message each
        from a SLOW peer (p1, sleeps before sending) and a fast peer
        (p2). Arrival-order reaping must complete the fast peer's
        transfer first — the fixed-process-order loop would park on
        p1 the whole time."""
        app = tmp_path / "app3.py"
        app.write_text(textwrap.dedent("""
            import os, sys, time
            sys.path.insert(0, %r)
            os.environ["XLA_FLAGS"] = (
                "--xla_force_host_platform_device_count=2")
            os.environ["JAX_PLATFORMS"] = "cpu"
            import jax
            jax.config.update("jax_platforms", "cpu")
            import numpy as np
            import ompi_release_tpu as mpi
            from ompi_release_tpu.runtime.runtime import Runtime

            world = mpi.init()      # 3 procs x 2 devices
            rt = Runtime.current()
            me = rt.bootstrap["process_index"]
            router = rt.wire
            payload = np.full(1000, me, np.int32)
            if me == 0:
                pending = {1: 1, 2: 1}
                srcs = []
                got = {}
                while sum(pending.values()):
                    src, arr = router.coll_recv_any(world, pending)
                    pending[src] -= 1
                    srcs.append(src)
                    got[src] = np.asarray(arr)
                assert srcs[0] == 2, f"reaped {srcs} (slow peer first)"
                for s in (1, 2):
                    np.testing.assert_array_equal(
                        got[s], np.full(1000, s, np.int32))
                print("ARRIVAL-ORDER-OK")
            elif me == 1:
                time.sleep(0.8)
                router.coll_send(world, 0, payload)
            else:
                router.coll_send(world, 0, payload)
            world.barrier()
            mpi.finalize()
        """ % REPO))
        job = Job(3, [sys.executable, str(app)], [], heartbeat_s=0.5,
                  miss_limit=8)
        rc = job.run(timeout_s=180)
        out = capfd.readouterr()
        assert rc == 0, out.out + out.err
        assert "ARRIVAL-ORDER-OK" in out.out

    def test_wire_win_two_thread_lock_contention(self, tmp_path, capfd):
        """ADVICE r5 medium regression, as a LEGAL two-window
        MPI_THREAD_MULTIPLE program: p0's T2 waits for a deferred
        remote grant on window B (held by p1), and p1 only releases it
        after p0's T1 lands a put through window A. The old
        process-wide ``outbound`` lock made T1's request wait behind
        T2's deferred-grant wait — a cross-process circular wait that
        burned the full 120 s timeout. Token-demultiplexed replies
        must finish the whole dance in seconds."""
        out = _run(tmp_path, capfd, """
            import threading, time
            from ompi_release_tpu.osc.window import win_allocate
            world = mpi.init()
            rt = Runtime.current()
            off = rt.local_rank_offset

            win_a = win_allocate(world, (1,), np.int32)
            win_b = win_allocate(world, (1,), np.int32)
            t0 = time.monotonic()
            if off == 4:  # process 1: home of ranks 4..7
                win_b.lock(5)      # hold B's lock BEFORE p0 contends
                world.barrier()
                # release B only after p0 T1's window-A put lands —
                # with the old outbound lock that put could never be
                # sent while T2 awaited the grant: deadlock till 120s
                deadline = time.monotonic() + 90
                while time.monotonic() < deadline:
                    if int(np.asarray(win_a.read())[0, 0]) == 42:
                        break
                    time.sleep(0.01)
                else:
                    raise SystemExit("FAIL: window-A put never landed")
                win_b.unlock(5)
            else:          # process 0: two threads, two windows
                world.barrier()
                errs = []

                def t2_fn():
                    try:
                        win_b.lock(5)     # deferred behind p1's hold
                        win_b.unlock(5)
                    except Exception as e:
                        errs.append(e)

                def t1_fn():
                    try:
                        time.sleep(0.3)   # let T2 get its wait going
                        win_a.lock(4)
                        win_a.put(np.int32([42]), 4)
                        win_a.unlock(4)
                    except Exception as e:
                        errs.append(e)

                ts = [threading.Thread(target=t2_fn),
                      threading.Thread(target=t1_fn)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                assert not errs, errs
            elapsed = time.monotonic() - t0
            world.barrier()
            assert elapsed < 60, f"lock contention took {elapsed:.1f}s"
            win_b.free()
            win_a.free()
            print(f"WINLOCK-OK {off}")
            mpi.finalize()
        """, timeout=170)
        assert "WINLOCK-OK 0" in out and "WINLOCK-OK 4" in out

    def test_legacy_single_frame_path_opt_out(self, tmp_path, capfd):
        """wire_pipeline_segsize=0 + one lane + sequential exchange =
        the exact pre-pipeline wire; everything still passes parity."""
        out = _run(tmp_path, capfd, """
            world = mpi.init()
            rt = Runtime.current()
            off = rt.local_rank_offset
            n = world.size
            x = np.stack([np.arange(4096, dtype=np.int32) * (off + i + 1)
                          for i in range(4)])
            got = np.asarray(world.allreduce(x))
            want = sum(np.arange(4096, dtype=np.int32) * (r + 1)
                       for r in range(n))
            np.testing.assert_array_equal(got[0], want)
            if off == 0:
                world.send(np.arange(1 << 18, dtype=np.float32), 5,
                           tag=7, rank=1)
            else:
                v, st = world.recv(source=1, tag=7, rank=5)
                np.testing.assert_array_equal(
                    np.asarray(v), np.arange(1 << 18, dtype=np.float32))
            world.barrier()
            print(f"LEGACY-OK {off}")
            mpi.finalize()
        """, mca=[("wire_pipeline_segsize", "0"),
                  ("wire_p2p_lanes", "1"),
                  ("wire_overlap_exchange", "false")])
        assert "LEGACY-OK 0" in out and "LEGACY-OK 4" in out


# ---------------------------------------------------------------------------
# satellite fixes riding this PR
# ---------------------------------------------------------------------------

class TestSatellites:
    def test_window_free_runs_keyval_delete_callbacks(self):
        """MPI_Win_free must run user-keyval delete callbacks for
        still-attached attributes, mirroring Communicator.free()."""
        import jax.numpy as jnp

        import ompi_release_tpu as mpi
        from ompi_release_tpu.comm.communicator import (create_keyval,
                                                        free_keyval)
        from ompi_release_tpu.osc.window import win_allocate

        comm = mpi.init()
        deleted = []
        kv = create_keyval(
            delete_fn=lambda obj, k, v, extra: deleted.append((v, extra)),
            extra_state="xs",
        )
        try:
            win = win_allocate(comm, (2,), jnp.float32)
            win.set_attr(kv, "payload")
            win.free()
            assert deleted == [("payload", "xs")]
        finally:
            free_keyval(kv)

    def test_stdin_secret_empty_is_launch_error(self):
        import io

        from ompi_release_tpu.runtime.ess import read_stdin_secret
        from ompi_release_tpu.utils.errors import MPIError

        assert read_stdin_secret(io.StringIO("tok3n\n")) == "tok3n"
        with pytest.raises(MPIError) as ei:
            read_stdin_secret(io.StringIO(""))
        assert "secret" in str(ei.value)

    def test_tpu_tune_measure_restores_forced_algorithm(self):
        """measure() must restore the operator's forced
        coll_tuned_<op>_algorithm, not clobber it with 'auto'."""
        import ompi_release_tpu as mpi
        from ompi_release_tpu.tools import tpu_tune

        comm = mpi.init()
        var = "coll_tuned_allreduce_algorithm"
        mca_var.set_value(var, "ring")
        try:
            tpu_tune.measure(comm, ["allreduce"], [256], repeats=1,
                             algs=["recursive_doubling"])
            assert mca_var.get(var) == "ring"
            # the segsize sweep must restore it too
            x = np.ones((comm.size, 1024), np.float32)
            tpu_tune.sweep_segsizes(comm, "allreduce", "ring", x,
                                    [512], repeats=1)
            assert mca_var.get(var) == "ring"
        finally:
            mca_var.VARS.unset(var)

    def test_wire_segsize_sweep_measures_and_restores(self):
        from ompi_release_tpu.tools.tpu_tune import (emit_wire_rules,
                                                     sweep_wire_segsizes)

        prev = mca_var.get("wire_pipeline_segsize", 1 << 20)
        out = sweep_wire_segsizes([65536], size_bytes=1 << 20, repeats=1)
        assert set(out) == {0, 65536}
        assert all(v > 0 for v in out.values())
        assert mca_var.get("wire_pipeline_segsize", 1 << 20) == prev
        text = emit_wire_rules(out, 1 << 20)
        assert "wire_pipeline_segsize" in text and text.startswith("\n#")
