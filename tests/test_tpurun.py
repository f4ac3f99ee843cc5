"""tpurun launcher tests — the orterun/orted system-test analogue.

Real multi-process jobs over localhost: wire-up through the OOB
coordinator during MPI init, stdio forwarding, exit-code aggregation,
and failure detection (abnormal exit + heartbeat loss) driving the job
state machine into the error states (``plm_types.h:113-151``).
"""

import os
import subprocess
import sys
import textwrap

import pytest

from ompi_release_tpu.runtime.state import JobState, ProcState
from ompi_release_tpu.tools.tpurun import Job

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

APP_PRELUDE = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, %r)
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import ompi_release_tpu as mpi
    from ompi_release_tpu.runtime.runtime import Runtime
""" % REPO)


def _write_app(tmp_path, body, name="app.py"):
    p = tmp_path / name
    p.write_text(APP_PRELUDE + textwrap.dedent(body))
    return str(p)


class TestEndToEnd:
    def test_four_process_job(self, tmp_path, capfd):
        """tpurun -n 4: every worker inits through the coordinator,
        sees the right identity, and exits 0."""
        app = _write_app(tmp_path, """
            world = mpi.init()
            rt = Runtime.current()
            pi = rt.bootstrap["process_index"]
            pc = rt.bootstrap["process_count"]
            peers = rt.bootstrap["peer_cards"]
            assert pc == 4 and 0 <= pi < 4
            assert len(peers) == 4
            assert peers[pi]["pid"] == os.getpid()
            print(f"hello from {pi}/{pc}")
            mpi.finalize()
        """)
        job = Job(4, [sys.executable, app], [], heartbeat_s=0.3)
        rc = job.run(timeout_s=120)
        out = capfd.readouterr().out
        assert rc == 0, out
        for r in range(4):
            assert f"[rank {r}] hello from {r}/4" in out
        assert job.job_state.visited(JobState.RUNNING)
        assert job.job_state.visited(JobState.TERMINATED)
        assert all(s == ProcState.TERMINATED
                   for s in job.proc_state.values())

    def test_xcast_reaches_all_workers(self, tmp_path, capfd):
        """An HNP tree xcast after wire-up reaches every worker via
        binomial relay (grpcomm xcast, not a star loop)."""
        app = _write_app(tmp_path, """
            world = mpi.init()
            rt = Runtime.current()
            payload = rt.agent.recv_xcast(timeout_ms=30000)
            print("got:" + payload.decode())
            mpi.finalize()
        """)
        job = Job(5, [sys.executable, app], [], heartbeat_s=0.3)

        # inject the xcast once the job reports RUNNING
        import threading

        def cast_when_running():
            import time

            for _ in range(600):
                if job.job_state.visited(JobState.RUNNING):
                    job.hnp.xcast(b"tree-payload")
                    return
                time.sleep(0.05)

        t = threading.Thread(target=cast_when_running, daemon=True)
        t.start()
        rc = job.run(timeout_s=120)
        out = capfd.readouterr().out
        assert rc == 0, out
        assert out.count("got:tree-payload") == 5

    def test_mca_vars_propagate(self, tmp_path, capfd):
        app = _write_app(tmp_path, """
            world = mpi.init()
            _ = world.pml   # registers the pml vars (env applies then)
            from ompi_release_tpu.mca import var as mca_var
            print("val=" + str(mca_var.get("pml_eager_limit", 0)))
            mpi.finalize()
        """)
        job = Job(2, [sys.executable, app],
                  [("pml_eager_limit", "12345")], heartbeat_s=0.3)
        rc = job.run(timeout_s=120)
        out = capfd.readouterr().out
        assert rc == 0, out
        assert out.count("val=12345") == 2


class TestPubsub:
    def test_publish_lookup_inside_job(self, tmp_path, capfd):
        """MPI_Publish_name/Lookup_name inside a live tpurun job: the
        launcher's HNP serves the name table (orte-server role), so
        one worker's publish is visible to the others' lookups —
        including a lookup issued BEFORE the publish (parked)."""
        app = _write_app(tmp_path, """
            world = mpi.init()
            rt = Runtime.current()
            pi = rt.bootstrap["process_index"]
            if pi == 0:
                import time
                time.sleep(0.4)  # let the others' lookups park first
                rt.agent.publish_name("job-svc", "tpu-port:7")
                port = rt.agent.lookup_name("job-svc")
            else:
                port = rt.agent.lookup_name("job-svc", timeout_ms=20000)
            print("found:" + port)
            mpi.finalize()
        """)
        job = Job(3, [sys.executable, app], [], heartbeat_s=0.3)
        rc = job.run(timeout_s=120)
        out = capfd.readouterr().out
        assert rc == 0, out
        assert out.count("found:tpu-port:7") == 3


class TestPubsubPublicApi:
    def test_comm_publish_lookup_bridges_to_hnp(self, tmp_path, capfd):
        """The PUBLIC comm.publish_name/lookup_name API must reach the
        JOB-global name table under tpurun (not each process's local
        dict, which no other worker can see)."""
        app = _write_app(tmp_path, """
            from ompi_release_tpu.comm import publish_name, lookup_name
            world = mpi.init()
            rt = Runtime.current()
            pi = rt.bootstrap["process_index"]
            if pi == 0:
                publish_name("pub-api-svc", "tpu-port:5")
            port = lookup_name("pub-api-svc", timeout_s=20)
            print("found:" + port)
            mpi.finalize()
        """)
        job = Job(2, [sys.executable, app], [], heartbeat_s=0.3)
        rc = job.run(timeout_s=120)
        out = capfd.readouterr().out
        assert rc == 0, out
        assert out.count("found:tpu-port:5") == 2


class TestFailureDetection:
    def test_tpu_ps_snapshots_live_job(self, tmp_path, capfd):
        """tpu-ps against a LIVE job: session-dir discovery finds the
        contact file, the HNP's TAG_PS responder returns per-rank
        pid/state/rss/vmsize piggybacked from heartbeats, and the
        rendered table carries them (orte-ps + sensor_resusage)."""
        import threading
        import time as _time

        from ompi_release_tpu.tools import tpu_ps

        app = _write_app(tmp_path, """
            import time
            world = mpi.init()
            time.sleep(2.5)   # stay alive across several beats
            mpi.finalize()
        """)
        job = Job(2, [sys.executable, app], [], heartbeat_s=0.3)
        results = {}

        def probe_when_running():
            for _ in range(600):
                if job.job_state.visited(JobState.RUNNING):
                    break
                _time.sleep(0.05)
            _time.sleep(1.0)  # let a resusage-bearing beat land
            jobs = tpu_ps.discover_jobs()
            results["discovered"] = [
                j for j in jobs if j["pid"] == os.getpid()
            ]
            client = tpu_ps.PsClient("127.0.0.1", job.hnp.port)
            try:
                results["snap"] = client.query()
            finally:
                client.close()

        t = threading.Thread(target=probe_when_running, daemon=True)
        t.start()
        rc = job.run(timeout_s=120)
        t.join(timeout=10)
        assert rc == 0
        # discovery: this launcher's contact file was found and live
        assert results.get("discovered"), results
        assert results["discovered"][0]["n"] == 2
        snap = results.get("snap")
        assert snap and snap["num_workers"] == 2, snap
        for nid in ("1", "2"):
            w = snap["workers"][nid]
            assert w["pid"] > 0          # piggybacked sample arrived
            assert w["rss"] > 0 and w["vmsize"] > 0
            assert w["beat_age_s"] is not None
            assert snap["proc_states"][nid] == "RUNNING"
        # rendering includes rank rows with byte-formatted columns
        text = tpu_ps.render_job(results["discovered"][0], snap)
        assert "rank" in text and "RUNNING" in text
        # contact file removed after the job ends
        assert not [j for j in tpu_ps.discover_jobs()
                    if j["pid"] == os.getpid()]

    def test_resilient_restart_resumes_from_checkpoint(self, tmp_path,
                                                       capfd):
        """rmaps/resilient + errmgr recovery: a worker KILLED mid-job
        is respawned on a surviving slot (same rank identity, fresh
        wire-up through the rejoin service) and resumes from its last
        committed checkpoint; the job completes rc=0."""
        ckdir = tmp_path / "ck"
        ckdir.mkdir()
        app = _write_app(tmp_path, """
            import os, signal
            from ompi_release_tpu.ft import Checkpointer
            world = mpi.init()
            rt = Runtime.current()
            pi = rt.bootstrap["process_index"]
            ck = Checkpointer(%r + f"/rank{pi}", comm=world,
                              private_dir=True)
            state = {"step": jax.numpy.zeros((), jax.numpy.int32)}
            latest = ck.latest_step()
            restored = latest is not None
            start = 0
            if restored:
                state = ck.restore(state, step=latest)
                start = int(state["step"])
                print(f"RESUMED {pi} from {start}")
            for step in range(start, 10):
                state["step"] = jax.numpy.asarray(step + 1)
                if step == 4 and not restored:
                    ck.save(step + 1, state)
                    ck.wait()
                    if pi == 1:
                        os.kill(os.getpid(), signal.SIGKILL)
            print(f"DONE {pi} step=10")
            mpi.finalize()
        """ % str(ckdir))
        job = Job(2, [sys.executable, app], [], heartbeat_s=0.3,
                  on_failure="restart", max_restarts=2)
        rc = job.run(timeout_s=120)
        out = capfd.readouterr().out
        assert rc == 0, out
        assert "RESUMED 1 from 5" in out
        assert "DONE 0 step=10" in out and "DONE 1 step=10" in out
        assert job._restarts.get(2) == 1  # exactly one respawn, rank 1
        assert not job.job_state.visited(JobState.ABORTED)
        assert job.job_state.visited(JobState.TERMINATED)

    def test_restart_budget_exhaustion_aborts(self, tmp_path, capfd):
        """A rank that keeps dying exhausts max_restarts and the job
        aborts (the resilient policy never loops forever)."""
        app = _write_app(tmp_path, """
            import os, signal
            world = mpi.init()
            rt = Runtime.current()
            if rt.bootstrap["process_index"] == 0:
                os.kill(os.getpid(), signal.SIGKILL)
            import time
            time.sleep(30)
        """)
        job = Job(2, [sys.executable, app], [], heartbeat_s=0.3,
                  on_failure="restart", max_restarts=1)
        rc = job.run(timeout_s=60)
        assert rc != 0
        assert job._restarts.get(1) == 1
        assert job.job_state.visited(JobState.ABORTED)

    def test_abnormal_exit_aborts_job(self, tmp_path, capfd):
        """One worker exits 3 mid-job: the job reaches ABORTED, the
        others are torn down, exit code propagates."""
        app = _write_app(tmp_path, """
            import time
            world = mpi.init()
            pi = Runtime.current().bootstrap["process_index"]
            if pi == 1:
                time.sleep(0.5)
                os._exit(3)
            time.sleep(600)   # would hang forever without teardown
        """)
        job = Job(3, [sys.executable, app], [], heartbeat_s=0.3)
        rc = job.run(timeout_s=120)
        assert rc == 3
        assert job.job_state.visited(JobState.ABORTED)
        assert job.proc_state[2] == ProcState.ABORTED  # node 2 = rank 1

    def test_heartbeat_loss_detected(self, tmp_path, capfd):
        """A worker that stops beating (but stays alive) is detected by
        the HNP monitor: HEARTBEAT_FAILED -> job ABORTED -> teardown
        (sensor_heartbeat.c:61,78 + errmgr policy)."""
        app = _write_app(tmp_path, """
            import time
            world = mpi.init()
            rt = Runtime.current()
            pi = rt.bootstrap["process_index"]
            if pi == 0:
                rt.agent.stop_heartbeats()   # go silent, stay alive
            time.sleep(600)
        """)
        job = Job(2, [sys.executable, app], [],
                  heartbeat_s=0.3, miss_limit=3)
        rc = job.run(timeout_s=120)
        assert rc != 0
        assert job.job_state.visited(JobState.ABORTED)
        assert job.proc_state[1] == ProcState.HEARTBEAT_FAILED

    def test_worker_crash_before_wireup(self, tmp_path, capfd):
        """A worker dying before the modex completes fails the start
        (FAILED_TO_START or ABORTED, never a hang)."""
        app = _write_app(tmp_path, """
            pi = int(os.environ["OMPITPU_NODE_ID"])
            if pi == 2:
                os._exit(7)
            world = mpi.init()
            import time; time.sleep(600)
        """)
        job = Job(2, [sys.executable, app], [], heartbeat_s=0.3)
        rc = job.run(timeout_s=120)
        assert rc == 7
        assert (job.job_state.visited(JobState.ABORTED)
                or job.job_state.visited(JobState.FAILED_TO_START))


class TestCli:
    def test_module_cli(self, tmp_path):
        """python -m ompi_release_tpu.tools.tpurun -n 2 ... end to end."""
        app = _write_app(tmp_path, """
            world = mpi.init()
            print("cli-ok", Runtime.current().bootstrap["process_index"])
            mpi.finalize()
        """)
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        r = subprocess.run(
            [sys.executable, "-m", "ompi_release_tpu.tools.tpurun",
             "-n", "2", "--timeout", "120", sys.executable, app],
            capture_output=True, text=True, env=env, timeout=180,
        )
        assert r.returncode == 0, r.stderr
        assert "[rank 0] cli-ok 0" in r.stdout
        assert "[rank 1] cli-ok 1" in r.stdout


class TestMultiHost:
    """Multi-host launch (VERDICT r2 #4): rmaps-lite rank->host
    mapping, rsh launch path, real addresses in modex cards."""

    def test_hostfile_and_map_policies(self, tmp_path):
        from ompi_release_tpu.tools.tpurun import (
            HostSpec, map_ranks, parse_host_list, parse_hostfile,
        )

        hf = tmp_path / "hosts"
        hf.write_text("# allocation\nnodeA slots=2\nnodeB slots=3\n")
        hosts = parse_hostfile(str(hf))
        assert [(h.name, h.slots) for h in hosts] == [
            ("nodeA", 2), ("nodeB", 3)]
        assert [(h.name, h.slots) for h in parse_host_list("x:2,y")] == [
            ("x", 2), ("y", 1)]
        # by-slot: fill nodeA before nodeB (rmaps_rr byslot)
        names = [h.name for h in map_ranks(hosts, 4, "slot")]
        assert names == ["nodeA", "nodeA", "nodeB", "nodeB"]
        # by-node: round robin one per host per pass
        names = [h.name for h in map_ranks(hosts, 4, "node")]
        assert names == ["nodeA", "nodeB", "nodeA", "nodeB"]
        # third pass only nodeB has a slot left
        names = [h.name for h in map_ranks(hosts, 5, "node")]
        assert names == ["nodeA", "nodeB", "nodeA", "nodeB", "nodeB"]
        import pytest as _pytest

        from ompi_release_tpu.utils.errors import MPIError

        with _pytest.raises(MPIError):
            map_ranks(hosts, 6, "slot")  # oversubscription rejected

    def test_ppr_and_seq_mappers(self, tmp_path):
        """rmaps/ppr and rmaps/seq analogues: exact N per node in
        allocation order; one rank per allocation LINE."""
        import pytest as _pytest

        from ompi_release_tpu.tools.tpurun import map_ranks, parse_hostfile
        from ompi_release_tpu.utils.errors import MPIError

        hf = tmp_path / "hosts"
        hf.write_text("nodeA slots=4\nnodeB slots=4\nnodeC slots=4\n")
        hosts = parse_hostfile(str(hf))
        names = [h.name for h in map_ranks(hosts, 5, "ppr:2:node")]
        assert names == ["nodeA", "nodeA", "nodeB", "nodeB", "nodeC"]
        with _pytest.raises(MPIError, match="places only"):
            map_ranks(hosts, 7, "ppr:2:node")  # 2*3 hosts < 7
        with _pytest.raises(MPIError, match="exceeds"):
            map_ranks(hosts, 4, "ppr:5:node")  # > slots, no oversub
        with _pytest.raises(MPIError, match="ppr"):
            map_ranks(hosts, 2, "ppr:2:socket")  # only :node exists

        # seq: file ORDER, duplicates allowed, slots ignored
        sf = tmp_path / "seqhosts"
        sf.write_text("nodeB\nnodeA\nnodeB\n")
        seq_hosts = parse_hostfile(str(sf))
        names = [h.name for h in map_ranks(seq_hosts, 3, "seq")]
        assert names == ["nodeB", "nodeA", "nodeB"]
        with _pytest.raises(MPIError, match="allocation lines"):
            map_ranks(seq_hosts, 4, "seq")

    def test_rankfile_mapping(self, tmp_path):
        """rmaps/rank_file analogue: explicit placement wins over the
        policy mapper, with full-coverage and allocation checks."""
        import pytest as _pytest

        from ompi_release_tpu.tools.tpurun import (
            HostSpec, Job, parse_rankfile,
        )
        from ompi_release_tpu.utils.errors import MPIError

        alloc = [HostSpec("nodeA", 2), HostSpec("nodeB", 2)]
        rf = tmp_path / "ranks"
        rf.write_text(
            "# explicit placement\n"
            "rank 0=nodeB slot=0\n"
            "rank 2=nodeA\n"
            "rank 1=nodeB slot=1\n"
        )
        names = [h.name for h in parse_rankfile(str(rf), 3, alloc)]
        assert names == ["nodeB", "nodeB", "nodeA"]

        # Job honors the rankfile over --map-by
        job = Job(3, ["true"], [], hosts=alloc, map_by="slot",
                  rankfile=str(rf))
        assert [h.name for h in job.rank_hosts] == \
            ["nodeB", "nodeB", "nodeA"]

        rf.write_text("rank 0=nodeA\n")  # rank 1 unmapped
        with _pytest.raises(MPIError, match="unmapped"):
            parse_rankfile(str(rf), 2, alloc)
        rf.write_text("rank 0=nodeA\nrank 0=nodeB\nrank 1=nodeA\n")
        with _pytest.raises(MPIError, match="twice"):
            parse_rankfile(str(rf), 2, alloc)
        rf.write_text("rank 0=nodeZ\nrank 1=nodeA\n")
        with _pytest.raises(MPIError, match="not in"):
            parse_rankfile(str(rf), 2, alloc)
        rf.write_text("rank 0=nodeA\nrank 1=nodeA\nrank 2=nodeA\n")
        with _pytest.raises(MPIError, match="exceed"):
            parse_rankfile(str(rf), 3, alloc)  # 3 ranks, 2 slots
        rf.write_text("rank 0=nodeA slot=7\nrank 1=nodeB\n")
        with _pytest.raises(MPIError, match="slot 7"):
            parse_rankfile(str(rf), 2, alloc)
        rf.write_text("banana\n")
        with _pytest.raises(MPIError, match="unparseable"):
            parse_rankfile(str(rf), 1, alloc)
        # no allocation: named hosts form their own — and the Job's
        # allocation (self.hosts) must be rebuilt from them so the
        # remapper/migrator host-load bookkeeping (keyed by identity
        # over self.hosts) covers every placed rank
        rf.write_text("rank 0=alpha\nrank 1=alpha\n")
        names = [h.name for h in parse_rankfile(str(rf), 2, None)]
        assert names == ["alpha", "alpha"]
        job2 = Job(2, ["true"], [], rankfile=str(rf))
        assert [(h.name, h.slots) for h in job2.hosts] == [("alpha", 2)]
        assert all(h is job2.hosts[0] for h in job2.rank_hosts)

    def test_fake_ssh_two_host_job(self, tmp_path, capfd):
        """End-to-end 2-'host' job through the rsh launch path: a fake
        ssh agent records each target host then execs locally (the
        standard clusterless PLM test), the OMPITPU_* contract rides
        the remote command line, and every rank wires up + exits 0."""
        log = tmp_path / "ssh_targets.log"
        agent = tmp_path / "fakessh"
        # faithful ssh fake: join the args into ONE string and give it
        # to a shell, exactly like real ssh hands the remote command
        # line to the login shell (this is what makes the launcher's
        # shlex quoting load-bearing rather than untested)
        agent.write_text(
            "#!/bin/sh\n"
            f'echo "$1" >> {log}\n'
            "shift\n"
            'exec sh -c "$*"\n'
        )
        agent.chmod(0o755)
        app = _write_app(tmp_path, """
            world = mpi.init()
            rt = Runtime.current()
            pi = rt.bootstrap["process_index"]
            print(f"host={os.environ['OMPITPU_HOST']} rank={pi}")
            print("mca=" + os.environ["OMPITPU_MCA_quoting_probe"])
            mpi.finalize()
        """)
        from ompi_release_tpu.tools.tpurun import HostSpec

        # the mca value carries spaces and shell metachars: it must
        # survive the ssh join + remote-shell re-parse intact
        job = Job(
            4, [sys.executable, app],
            [("quoting_probe", "two words; $(rm -rf /) `x`")],
            heartbeat_s=0.3,
            hosts=[HostSpec("nodeA", 2), HostSpec("nodeB", 2)],
            launch_agent=str(agent),
        )
        rc = job.run(timeout_s=120)
        out = capfd.readouterr().out
        assert rc == 0, out
        targets = sorted(log.read_text().split())
        assert targets == ["nodeA", "nodeA", "nodeB", "nodeB"]
        assert "host=nodeA rank=0" in out
        assert "host=nodeB rank=2" in out
        assert out.count("mca=two words; $(rm -rf /) `x`") == 4
        assert job.job_state.visited(JobState.TERMINATED)

    def test_nonloopback_wireup_and_card_addresses(self):
        """Distinct listen interface: the HNP binds 0.0.0.0, the
        worker dials the machine's real (non-loopback) address, and
        its modex card advertises that address — not 127.0.0.1."""
        from ompi_release_tpu.runtime.coordinator import (
            HnpCoordinator, WorkerAgent, local_addr_toward,
        )

        ip = local_addr_toward("192.0.2.1")  # TEST-NET: no packet sent
        if ip.startswith("127."):
            pytest.skip("no non-loopback interface available")
        import threading

        hnp = HnpCoordinator(2, bind_addr="0.0.0.0")
        agent = None
        try:
            t = threading.Thread(target=lambda: hnp.run_modex(None))
            t.start()
            agent = WorkerAgent(1, ip, hnp.port)
            worker_cards = agent.run_modex({"pid": os.getpid()})
            t.join(timeout=10)
            assert worker_cards[0]["oob_host"] == ip
            assert not worker_cards[0]["oob_host"].startswith("127.")
        finally:
            if agent is not None:
                agent.close()
            hnp.shutdown()


class TestMigration:
    """tpu-migrate (orte-migrate analogue): proactively evacuate a
    host of a live job through the HNP's TAG_MIGRATE responder."""

    def test_migrate_off_host_resumes_elsewhere(self, tmp_path, capfd):
        """A 2-'host' fake-ssh job is asked to evacuate nodeB: the
        rank there is terminated, remapped to nodeA (which stays
        excluded for later respawns), respawned, and resumes from its
        last committed checkpoint; the job completes rc=0 and the
        failure-restart budget is untouched.

        The request is made while rank 1 stands between the world
        barrier of its fifth save and that save's commit (it says so
        through a file and lingers there): the kill waits the commit
        out, so the new incarnation resumes at step 5, the step rank 0
        counts, and not one barrier behind it (M5's race: that job
        hung at its last save). Rank 0 stays out of the next barrier
        until the new incarnation is up: a barrier token sent to an
        incarnation that is dying is lost with it (M5, open)."""
        import threading
        import time as _time

        from ompi_release_tpu.tools.tpu_migrate import request_migration
        from ompi_release_tpu.tools.tpurun import HostSpec

        log = tmp_path / "ssh_targets.log"
        agent = tmp_path / "fakessh"
        agent.write_text(
            "#!/bin/sh\n"
            f'echo "$1" >> {log}\n'
            "shift\n"
            'exec sh -c "$*"\n'
        )
        agent.chmod(0o755)
        ckdir = tmp_path / "ck"
        ckdir.mkdir()
        in_window = tmp_path / "rank1_between_barrier_and_commit"
        resumed = tmp_path / "rank1_resumed"
        app = _write_app(tmp_path, """
            import time
            from ompi_release_tpu.ft import Checkpointer
            world = mpi.init()
            rt = Runtime.current()
            pi = rt.bootstrap["process_index"]
            ck = Checkpointer(%r + f"/rank{pi}", comm=world,
                              private_dir=True)
            state = {"step": jax.numpy.zeros((), jax.numpy.int32)}
            latest = ck.latest_step()
            start = 0
            if latest is not None:
                state = ck.restore(state, step=latest)
                start = int(state["step"])
                print(f"RESUMED {pi} from {start}", flush=True)
                open(%r, "w").close()
            elif pi == 1:
                quiesce = ck.quiesce

                def quiesce_then_linger():
                    quiesce()  # the world barrier of this save
                    if int(state["step"]) == 5:
                        open(%r, "w").close()
                        time.sleep(0.3)

                ck.quiesce = quiesce_then_linger
            for step in range(start, 16):
                state["step"] = jax.numpy.asarray(step + 1)
                ck.save(step + 1, state)
                ck.wait()
                time.sleep(0.25)
                if pi == 0 and step + 1 == 5:
                    for _ in range(6000):
                        if os.path.exists(%r):
                            break
                        time.sleep(0.01)
            print(f"DONE {pi}", flush=True)
            mpi.finalize()
        """ % (str(ckdir), str(resumed), str(in_window), str(resumed)))
        job = Job(2, [sys.executable, app], [], heartbeat_s=0.3,
                  hosts=[HostSpec("nodeA", 2), HostSpec("nodeB", 2)],
                  map_by="node", launch_agent=str(agent),
                  on_failure="restart", max_restarts=2)
        results = {}

        def migrate_when_running():
            for _ in range(6000):
                if in_window.exists():
                    break
                _time.sleep(0.01)
            results["reply"] = request_migration(
                "127.0.0.1", job.hnp.port, "nodeB")

        t = threading.Thread(target=migrate_when_running, daemon=True)
        t.start()
        rc = job.run(timeout_s=120)
        t.join(timeout=10)
        out = capfd.readouterr().out
        assert rc == 0, out
        reply = results.get("reply")
        assert reply and reply.get("ok"), (reply, out)
        assert reply["ranks"] == [1]
        # rank 1 now lives on nodeA; nodeB stays excluded
        assert job.rank_hosts[1].name == "nodeA"
        assert "nodeB" in job._excluded_hosts
        # the moved app resumed from a committed step and finished —
        # and the OLD incarnation actually died (TAG_DIE through the
        # control plane: killing only the local fake-ssh client would
        # orphan it to run to completion, printing DONE 1 twice)
        assert "RESUMED 1 from 5" in out, out
        assert "DONE 0" in out and "DONE 1" in out
        assert out.count("DONE 1") == 1, out
        assert out.count("RESUMED 1") == 1, out
        # an operator move is not a failure: budget untouched
        assert not job._restarts.get(2)
        assert not job.job_state.visited(JobState.ABORTED)
        assert job.job_state.visited(JobState.TERMINATED)
        # the respawn actually went through the launch agent to nodeA
        targets = log.read_text().split()
        assert targets.count("nodeA") == 2 and targets.count("nodeB") == 1

    def test_migrate_refused_without_capacity(self, tmp_path, capfd):
        """Evacuating the only host with free slots is refused whole —
        no rank is killed on a request that cannot complete."""
        import threading
        import time as _time

        from ompi_release_tpu.tools.tpu_migrate import request_migration

        app = _write_app(tmp_path, """
            import time
            world = mpi.init()
            time.sleep(3.0)
            mpi.finalize()
        """)
        # default single-host allocation: localhost with exactly n slots
        job = Job(2, [sys.executable, app], [], heartbeat_s=0.3,
                  on_failure="restart")
        results = {}

        def probe():
            for _ in range(600):
                if job.job_state.visited(JobState.RUNNING):
                    break
                _time.sleep(0.05)
            results["reply"] = request_migration(
                "127.0.0.1", job.hnp.port, "localhost")
            results["bogus"] = request_migration(
                "127.0.0.1", job.hnp.port, "no-such-host")

        t = threading.Thread(target=probe, daemon=True)
        t.start()
        rc = job.run(timeout_s=60)
        t.join(timeout=10)
        assert rc == 0
        reply = results.get("reply")
        assert reply and not reply.get("ok")
        assert "cannot evacuate" in reply["error"]
        assert "localhost" not in job._excluded_hosts  # rolled back
        bogus = results.get("bogus")
        assert bogus and not bogus.get("ok")
        assert "no ranks mapped" in bogus["error"]


class TestCommSpawn:
    def test_spawn_exchange_and_wait(self, tmp_path, capfd):
        """MPI_Comm_spawn analogue: parent launches 2 children, sends
        each a tagged frame over the job OOB, receives replies, and
        joins a clean exit."""
        from ompi_release_tpu.comm import comm_spawn
        from ompi_release_tpu.utils.errors import MPIError

        app = _write_app(tmp_path, """
            world = mpi.init()
            rt = Runtime.current()
            pi = rt.bootstrap["process_index"]
            src, tag, payload = rt.agent.ep.recv(tag=101,
                                                 timeout_ms=30000)
            rt.agent.ep.send(0, 102,
                             payload + f"+child{pi}".encode())
            mpi.finalize()
        """)
        job = comm_spawn([sys.executable, app], 2, timeout_s=120)
        assert job.remote_size == 2
        # wait for wire-up before messaging (children recv after init)
        from ompi_release_tpu.runtime.state import JobState as JS
        import time
        for _ in range(600):
            if job.job.job_state.visited(JS.RUNNING):
                break
            time.sleep(0.05)
        job.send(0, 101, b"hello")
        job.send(1, 101, b"hello")
        replies = {}
        for _ in range(2):
            rank, payload = job.recv(102, timeout_ms=30000)
            replies[rank] = payload
        assert replies == {0: b"hello+child0", 1: b"hello+child1"}
        assert job.wait(timeout_s=60) == 0
        with pytest.raises(MPIError):
            job.send(5, 101, b"x")
        with pytest.raises(MPIError):
            job.send(0, 3, b"x")  # control-plane tags protected

    def test_messaging_after_job_end_errors_cleanly(self, tmp_path,
                                                    capfd):
        """Late send/recv on a finished spawn must raise ERR_SPAWN —
        this used to SEGFAULT (NULL native handle after shutdown)."""
        from ompi_release_tpu.comm import comm_spawn
        from ompi_release_tpu.utils.errors import MPIError

        app = _write_app(tmp_path, """
            world = mpi.init()
            mpi.finalize()
        """)
        job = comm_spawn([sys.executable, app], 1, timeout_s=120)
        assert job.wait(timeout_s=60) == 0
        with pytest.raises(MPIError):
            job.send(0, 101, b"late")
        with pytest.raises(MPIError):
            job.recv(102, timeout_ms=100)
