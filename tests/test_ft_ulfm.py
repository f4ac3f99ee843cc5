"""ULFM-style elastic fault tolerance (PR 9).

Layers under test:

- ``utils/errors``: the new ``ERR_PROC_FAILED`` / ``ERR_REVOKED``
  classes.
- ``ft/ulfm.py``: the process-local failure picture — epoch
  monotonicity, per-incarnation failure permanence (``dead_for``),
  revocation, the deterministic epoch-derived cid.
- ``runtime/coordinator.py``: the heartbeat monitor's promotion path
  (miss-limit, recovered-in-time beats, errmgr callback ordering),
  ``promote_failed`` idempotence, TAG_PROC_FAILED notices, the TAG_FT
  state/agreement responder.
- ``runtime/progress.py``: ``fail_queued`` (revoke interrupts queued
  schedules without running them).
- ``ft/sensor.py``: seeded/deterministic/armed-kill FtTester modes.
- ``comm/dpm.py``: FT-aware rendezvous (dead-port fast fail, stale
  epoch fence, mid-wait revocation).
- ``tools/tpurun.py``: ``--ft-inject`` / ``--ft-continue`` plumbing.
- end-to-end: two REAL 3-process recovery jobs — a SIGKILLed rank
  mid-allreduce recovered by revoke+shrink (degraded world, exact
  loss) and by respawn+rebuild (full-size world, exact loss).
"""

import os
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from ompi_release_tpu.ft import ulfm
from ompi_release_tpu.ft.sensor import FtTester, InjectedFault
from ompi_release_tpu.mca import pvar, var as mca_var
from ompi_release_tpu.runtime import coordinator as coord
from ompi_release_tpu.runtime import progress as progress_mod
from ompi_release_tpu.runtime.state import JobState
from ompi_release_tpu.tools.tpurun import Job
from ompi_release_tpu.utils.errors import ErrorCode, MPIError


@pytest.fixture
def ft_state():
    """A clean process-local failure picture per test."""
    st = ulfm.state()
    st.reset()
    yield st
    st.reset()


# ---------------------------------------------------------------------------
# error classes + state machine
# ---------------------------------------------------------------------------

class TestFtState:
    def test_error_classes_distinct(self):
        assert ErrorCode.ERR_PROC_FAILED != ErrorCode.ERR_REVOKED
        assert ErrorCode.ERR_PROC_FAILED not in (
            ErrorCode.ERR_PENDING, ErrorCode.ERR_UNREACH)

    def test_notice_updates_and_counts_once(self, ft_state):
        base = float(pvar.PVARS.lookup("ft_failures_detected").read())
        ft_state.apply_notice({"epoch": 1, "failed": [2]})
        ft_state.apply_notice({"epoch": 2, "failed": [2]})  # same pidx
        assert ft_state.epoch == 2
        assert ft_state.failed == {2}
        got = float(pvar.PVARS.lookup("ft_failures_detected").read())
        assert got == base + 1  # one failure, counted once

    def test_stale_epoch_ignored(self, ft_state):
        ft_state.apply_notice({"epoch": 5, "failed": [1]})
        ft_state.apply_notice({"epoch": 3, "failed": []})
        assert ft_state.epoch == 5 and ft_state.failed == {1}

    def test_check_wait_raises_proc_failed(self, ft_state):
        ft_state.apply_notice({"epoch": 1, "failed": [2]})
        with pytest.raises(MPIError) as ei:
            ft_state.check_wait(0, [1, 2], "reap")
        assert ei.value.code == ErrorCode.ERR_PROC_FAILED
        ft_state.check_wait(0, [0, 1], "reap")  # survivors: no raise

    def test_failure_permanence_per_comm_epoch(self, ft_state):
        """ULFM permanence: a pidx that failed at epoch 1 stays dead
        for comms born at epoch 0 even after its replacement rejoins
        (failed set empties), while a comm born at the recovery epoch
        sees the new incarnation as alive."""
        ft_state.apply_notice({"epoch": 1, "failed": [2]})
        ft_state.apply_notice({"epoch": 2, "failed": [],
                               "restarted": [2]})
        ft_state.apply_notice({"epoch": 3, "failed": [],
                               "restarted": [2], "rejoined": [2]})
        assert ft_state.dead_for([0, 1, 2], epoch0=0) == [2]
        with pytest.raises(MPIError) as ei:
            ft_state.check_wait(0, [2], "reap", epoch0=0)
        assert ei.value.code == ErrorCode.ERR_PROC_FAILED
        # a comm built at the recovery epoch talks to the replacement
        assert ft_state.dead_for([0, 1, 2], epoch0=3) == []
        ft_state.check_wait(900, [2], "reap", epoch0=3)
        # a SECOND death kills it for the rebuild comm too
        ft_state.apply_notice({"epoch": 4, "failed": [2]})
        assert ft_state.dead_for([2], epoch0=3) == [2]

    def test_revoke_marks_and_raises(self, ft_state):
        base = float(pvar.PVARS.lookup("ft_revokes").read())
        assert ft_state.apply_revoke(7, 1) is True
        assert ft_state.apply_revoke(7, 1) is False  # idempotent
        assert ft_state.is_revoked(7)
        with pytest.raises(MPIError) as ei:
            ft_state.check_wait(7, [0], "reap")
        assert ei.value.code == ErrorCode.ERR_REVOKED
        assert float(pvar.PVARS.lookup("ft_revokes").read()) == base + 1

    def test_ft_cid_deterministic_and_bounded(self):
        a = ulfm.ft_cid(3, 0)
        assert a == ulfm.ft_cid(3, 0)
        assert a != ulfm.ft_cid(4, 0) and a != ulfm.ft_cid(3, 1)
        assert ulfm.FT_CID_BASE <= a < (1 << 20)

    def test_ft_cid_distinct_per_parent_at_one_epoch(self):
        """The shrink-every-comm recovery pattern: distinct parent
        cids at ONE epoch must mint distinct rebuild cids (the old
        mod-64 parent slot collided cid 2 with cid 66)."""
        minted = {ulfm.ft_cid(5, c) for c in range(200)}
        assert len(minted) == 200
        assert ulfm.ft_cid(5, 2) != ulfm.ft_cid(5, 66)

    def test_rebuild_evicts_revoked_slot_occupant(self):
        """An epoch-wrapped ft cid landing on this lineage's OLD
        revoked comm evicts it instead of failing the recovery; a
        LIVE occupant stays a loud error."""
        import ompi_release_tpu as mpi
        from ompi_release_tpu.comm.communicator import (
            Communicator, _comm_registry,
        )
        from ompi_release_tpu.comm.group import Group

        world = mpi.init()
        slot = ulfm.ft_cid(1, 0)
        old = Communicator(world.runtime, Group([0, 1]), name="old",
                           cid=slot)
        with pytest.raises(MPIError):  # live occupant: loud error
            Communicator(world.runtime, Group([0, 1]), cid=slot)
        old._revoked = True  # poisoned ancestor: evictable
        ulfm.state().apply_revoke(slot, 1)  # its wire-level poison
        new = Communicator(world.runtime, Group([0, 1]), name="new",
                           cid=slot)
        assert _comm_registry[slot] is new and old._freed
        # the ancestor's revocation record must not poison the fresh
        # comm minted at the wrapped slot
        assert not ulfm.state().is_revoked(slot)
        new.free()
        # ...including when the ancestor was revoked-then-FREED long
        # ago (no registry occupant left at the slot): the stale
        # record is cleared unconditionally on the explicit-cid path
        ulfm.state().apply_revoke(slot, 2)
        again = Communicator(world.runtime, Group([0, 1]),
                             name="again", cid=slot)
        assert not ulfm.state().is_revoked(slot)
        again.free()

    def test_lineage_anchor_survives_rebuild_chain(self):
        """Recovery agreements/cids key on the LINEAGE: a rebuild's
        rebuild still anchors to the original comm, matching what a
        fresh replacement (holding only its world) derives."""
        import ompi_release_tpu as mpi
        from ompi_release_tpu.comm.communicator import Communicator
        from ompi_release_tpu.comm.group import Group

        world = mpi.init()
        base = Communicator(world.runtime, Group([0, 1]), name="base")
        r1 = Communicator(world.runtime, Group([0, 1]), parent=base,
                          cid=ulfm.ft_cid(1, base._ft_lineage))
        r2 = Communicator(world.runtime, Group([0, 1]), parent=r1,
                          cid=ulfm.ft_cid(2, r1._ft_lineage))
        assert base._ft_lineage == base.cid
        assert r1._ft_lineage == base.cid
        assert r2._ft_lineage == base.cid
        # survivors on r1 and a fresh process on base mint the SAME
        # recovery cid for the next epoch
        assert ulfm.ft_cid(3, r1._ft_lineage) == \
            ulfm.ft_cid(3, base._ft_lineage)
        for c in (r2, r1, base):
            c.free()

    def test_watchdog_contributor_snapshot(self, ft_state):
        from ompi_release_tpu.obs import watchdog
        ft_state.apply_notice({"epoch": 2, "failed": [1]})
        snap = dict(watchdog._contributors)["ft_state"]()
        assert snap["failed"] == [1] and snap["epoch"] == 2

    def test_postmortem_awaiting_names_known_failed(self, ft_state):
        """The watchdog info split: a known-failed peer is NAMED as
        failed in postmortems, not listed as merely 'awaiting'."""
        from ompi_release_tpu.runtime.wire import _ft_split_awaiting

        ft_state.apply_notice({"epoch": 1, "failed": [2]})
        info = _ft_split_awaiting([1, 2, 3])
        assert info == {"awaiting_procs": [1, 3],
                        "known_failed_procs": [2]}


# ---------------------------------------------------------------------------
# progress engine: revoke interrupts queued schedules
# ---------------------------------------------------------------------------

class TestFailQueued:
    def test_queued_ops_complete_in_error_without_running(self):
        eng = progress_mod.ProgressEngine()
        ran = []
        blocker = progress_mod.ScheduledOp(
            ("comm", 42), "blocker", lambda: ran.append("b"))
        victim = progress_mod.ScheduledOp(
            ("comm", 42), "victim", lambda: ran.append("v"))
        eng.post(blocker)
        eng.post(victim)
        n = eng.fail_queued(
            ("comm", 42),
            lambda: MPIError(ErrorCode.ERR_REVOKED, "revoked"))
        assert n == 2 and not ran
        assert victim.done.is_set() and victim.error.code == \
            ErrorCode.ERR_REVOKED
        with pytest.raises(MPIError):
            eng.wait(victim)
        assert eng.inflight_count() == 0

    def test_running_op_untouched(self):
        eng = progress_mod.ProgressEngine()
        started = threading.Event()
        release = threading.Event()

        def slow():
            started.set()
            release.wait(5)
            return "done"

        op = progress_mod.ScheduledOp(("comm", 43), "slow", slow)
        eng.post(op)
        t = threading.Thread(target=eng.wait, args=(op,), daemon=True)
        t.start()
        assert started.wait(5)
        assert eng.fail_queued(("comm", 43), lambda: MPIError(
            ErrorCode.ERR_REVOKED, "r")) == 0
        release.set()
        t.join(5)
        assert op.error is None and op.result == "done"


# ---------------------------------------------------------------------------
# sensor: seeded / every-N / armed-kill injection
# ---------------------------------------------------------------------------

class TestFtTester:
    def test_seed_reproducible(self):
        a = FtTester(fail_prob=0.5, seed=1234)
        b = FtTester(fail_prob=0.5, seed=1234)

        def trace(t):
            out = []
            for _ in range(50):
                try:
                    t.maybe_fail()
                    out.append(0)
                except InjectedFault:
                    out.append(1)
            return out

        ta, tb = trace(a), trace(b)
        assert ta == tb and sum(ta) > 0
        assert trace(FtTester(fail_prob=0.5, seed=99)) != ta

    def test_seed_cvar_feeds_default(self, monkeypatch):
        monkeypatch.setenv("OMPITPU_MCA_sensor_ft_seed", "777")
        mca_var.VARS.refresh_from_env()
        try:
            a, b = FtTester(fail_prob=0.5), FtTester(fail_prob=0.5)
            ra = [a._rng.random() for _ in range(8)]
            rb = [b._rng.random() for _ in range(8)]
            assert ra == rb  # both seeded from the cvar
        finally:
            monkeypatch.delenv("OMPITPU_MCA_sensor_ft_seed")
            mca_var.VARS.refresh_from_env()

    def test_every_n_deterministic(self):
        t = FtTester(fail_prob=0.0, every_n=3)
        fired = []
        for s in range(10):
            try:
                t.step()
            except InjectedFault:
                fired.append(s)
        assert fired == [3, 6, 9]

    def test_kill_armed_at_step(self, monkeypatch):
        killed = []
        monkeypatch.setattr(os, "kill",
                            lambda pid, sig: killed.append((pid, sig)))
        t = FtTester(fail_prob=0.0, kill_step=2)
        t.step(); t.step()
        assert not killed
        t.step()  # step index 2: fires
        assert killed and killed[0][0] == os.getpid()

    def test_from_cvars_rank_scoping(self, monkeypatch):
        monkeypatch.setenv("OMPITPU_MCA_sensor_ft_kill_step", "5")
        monkeypatch.setenv("OMPITPU_MCA_sensor_ft_kill_rank", "1")
        mca_var.VARS.refresh_from_env()
        try:
            assert FtTester.from_cvars(process_index=1).kill_step == 5
            assert FtTester.from_cvars(process_index=0).kill_step == -1
        finally:
            monkeypatch.delenv("OMPITPU_MCA_sensor_ft_kill_step")
            monkeypatch.delenv("OMPITPU_MCA_sensor_ft_kill_rank")
            mca_var.VARS.refresh_from_env()


# ---------------------------------------------------------------------------
# heartbeat monitor + ULFM coordinator plane (satellite: direct tests)
# ---------------------------------------------------------------------------

class TestHeartbeatMonitor:
    def _pair(self, n_workers=1):
        hnp = coord.HnpCoordinator(n_workers + 1)
        agents = []
        threads = []

        def mk(nid):
            a = coord.WorkerAgent(nid, "127.0.0.1", hnp.port)
            a.run_modex({"node_id": nid})
            agents.append(a)

        for nid in range(1, n_workers + 1):
            t = threading.Thread(target=mk, args=(nid,))
            t.start()
            threads.append(t)
        hnp.run_modex(None)
        for t in threads:
            t.join(10)
        agents.sort(key=lambda a: a.node_id)
        return hnp, agents

    def test_miss_limit_promotes_once(self, ft_state):
        """A worker silent for miss_limit intervals is reported
        exactly once, the job epoch bumps, and a live peer's watcher
        receives the TAG_PROC_FAILED notice."""
        hnp, (w1, w2) = self._pair(2)
        try:
            fired = []
            notices = []
            w1.start_ft_watcher(lambda d: notices.append(d))
            hnp.start_heartbeat_monitor(fired.append,
                                        interval_s=0.1, miss_limit=2)
            deadline = time.monotonic() + 5
            while not fired and time.monotonic() < deadline:
                w1.heartbeat()  # only w1 beats; w2 goes silent
                time.sleep(0.05)
            # keep w1 alive through the would-be-duplicate window
            end = time.monotonic() + 0.5
            while time.monotonic() < end:
                w1.heartbeat()
                time.sleep(0.05)
            assert fired == [2]
            doc = hnp._ft_doc()
            assert doc["epoch"] >= 1 and doc["failed"] == [1]
            assert any(n.get("failed") == [1] for n in notices)
        finally:
            hnp.shutdown()
            for a in (w1, w2):
                a.close()

    def test_recovered_in_time_beat_does_not_fire(self):
        """Beats arriving inside the miss window must never promote —
        today's gap in coverage: start_heartbeat_monitor had no direct
        tests at all."""
        hnp, (w,) = self._pair(1)
        try:
            fired = []
            hnp.start_heartbeat_monitor(fired.append,
                                        interval_s=0.1, miss_limit=3)
            end = time.monotonic() + 1.2  # 4x the miss window
            while time.monotonic() < end:
                w.heartbeat()
                time.sleep(0.1)  # inside interval*miss_limit = 0.3s
            assert fired == []
            assert hnp._ft_doc()["failed"] == []
        finally:
            hnp.shutdown()
            w.close()

    def test_started_beats_do_not_need_the_gil(self):
        """start_heartbeats beats from the endpoint's native thread:
        while this thread holds the GIL inside ONE C call for several
        beat intervals (what the native-plan probe's bulk byte
        searches did to a 64 MiB collective's first call), beats keep
        arriving at the HNP — a Python beat thread sent none and the
        job was torn down as HEARTBEAT_FAILED. Each beat carries the
        resusage sample tpu-ps shows."""
        import json
        import os

        from ompi_release_tpu.runtime.coordinator import TAG_HEARTBEAT

        hnp, (w,) = self._pair(1)
        try:
            per = 1.0  # the fastest of five: a sample that was
            for _ in range(5):  # descheduled would shorten the hold
                t0 = time.perf_counter()
                sum(range(10 ** 6))
                per = min(per, time.perf_counter() - t0)
            per = max(per, 1e-4)
            w.start_heartbeats(0.05)
            t0 = time.perf_counter()
            sum(range(int(10 ** 6 * 0.6 / per)))  # ~0.6 s, GIL held
            held = time.perf_counter() - t0
            beats = []
            try:  # what queued up during the hold, nothing later
                while True:
                    beats.append(hnp.ep.recv(tag=TAG_HEARTBEAT,
                                             timeout_ms=1))
            except MPIError:
                pass
            assert held >= 0.2, held
            assert len(beats) >= held / 0.05 / 3, (len(beats), held)
            src, _, raw = beats[-1]
            ru = json.loads(raw)
            assert src == 1 and ru["pid"] == os.getpid()
            assert ru["rss"] > 0 and ru["vmsize"] >= ru["rss"]
            w.stop_heartbeats()
            time.sleep(0.15)  # one in flight at most, then silence
            n = hnp.ep.pending()
            time.sleep(0.2)
            assert hnp.ep.pending() == n
        finally:
            hnp.shutdown()
            w.close()

    def test_failure_callback_orders_with_errmgr_handle(self):
        """The promotion sequence an errmgr policy observes: epoch
        bump BEFORE the on_failure callback, so a policy that consults
        the ft doc inside its handler already sees the failure; and
        ErrMgr.handle dispatches the typed error to its registrants."""
        from ompi_release_tpu.ft.errmgr import ErrMgr

        hnp, (w1, w2) = self._pair(2)
        try:
            order = []
            mgr = ErrMgr()
            mgr.register(MPIError,
                         lambda e: order.append(("handler", e.code)))

            def on_failure(nid):
                # the epoch must already record the failure HERE
                order.append(("cb", nid,
                              tuple(hnp._ft_doc()["failed"])))
                claimed = mgr.handle(MPIError(
                    ErrorCode.ERR_PROC_FAILED, f"worker {nid}"))
                order.append(("handled", claimed))

            hnp.start_heartbeat_monitor(on_failure,
                                        interval_s=0.1, miss_limit=2)
            deadline = time.monotonic() + 5
            while len(order) < 3 and time.monotonic() < deadline:
                w1.heartbeat()
                time.sleep(0.05)
            assert order[0] == ("cb", 2, (1,))
            assert order[1] == ("handler", ErrorCode.ERR_PROC_FAILED)
            assert order[2] == ("handled", True)
        finally:
            hnp.shutdown()
            for a in (w1, w2):
                a.close()

    def test_restart_grace_excuses_cold_startup_silence(self):
        """A respawned worker's first beat is gated on full process
        startup (cold jax import can exceed the whole heartbeat
        window); note_restarted must grant a startup grace so the
        monitor does not re-promote the replacement before it could
        possibly beat — observed as a real flake of the respawn
        acceptance job on cold runs."""
        hnp, (w,) = self._pair(1)
        try:
            fired = []
            hnp.start_heartbeat_monitor(fired.append,
                                        interval_s=0.05, miss_limit=2)
            time.sleep(0.3)  # w never beats: promoted normally
            assert fired == [1]
            hnp.note_restarted(1)
            time.sleep(0.5)  # 5x the window, still inside the grace
            assert fired == [1], "replacement re-promoted during boot"
            w.heartbeat()  # first beat ends the grace
            time.sleep(0.2)
            with hnp._hb_lock:
                assert 1 not in hnp._hb_restart_grace
            time.sleep(0.4)  # silent AFTER the first beat: normal rules
            assert fired == [1, 1]
        finally:
            hnp.shutdown()
            w.close()

    def test_promote_failed_idempotent_and_skips_finished(self):
        hnp, (w,) = self._pair(1)
        try:
            hnp.start_ft_responder()
            assert hnp.promote_failed(1) is True
            assert hnp.promote_failed(1) is False  # already failed
            assert w.ft_query()["failed"] == [0]
            hnp.note_restarted(1)
            doc = w.ft_query()
            assert doc["failed"] == [] and doc["restarted"] == [0]
            # a cleanly-finished worker is never promoted
            hnp.note_finished(1)
            assert hnp.promote_failed(1) is False
        finally:
            hnp.shutdown()
            w.close()

    def test_ft_agreement_excuses_failed_and_ands_flags(self):
        """MPIX_Comm_agree at the HNP: parked until every LIVE
        participant contributed, failed participants excused, reply =
        AND of flags + one consistent snapshot."""
        hnp, (w1, w2) = self._pair(2)
        try:
            hnp.start_ft_responder()
            hnp.promote_failed(2)  # pidx 1 is dead
            out = {}

            def contribute(agent, flag):
                out[agent.node_id] = agent.ft_agree(
                    5, 1, flag, [0, 1], timeout_ms=10_000)

            t = threading.Thread(target=contribute, args=(w1, 0))
            t.start()
            t.join(10)
            assert not t.is_alive(), "agreement never completed"
            doc = out[1]
            assert doc["flag"] == 0 and doc["failed"] == [1]
        finally:
            hnp.shutdown()
            for a in (w1, w2):
                a.close()


# ---------------------------------------------------------------------------
# errmgr: respawn-readiness predicate + dead-for-comm mapping
# ---------------------------------------------------------------------------

class TestRespawnReadiness:
    def test_stale_cumulative_rejoined_not_ready(self):
        """Second-recovery regression: restarted/rejoined are
        cumulative, so a NEW failure whose respawn was just granted
        (failed empty, old survivor still in rejoined) must NOT look
        ready — only once the new replacement's rejoin lands."""
        from ompi_release_tpu.ft.errmgr import respawn_ready

        assert not respawn_ready(None)
        assert not respawn_ready({"epoch": 0})
        # recovery #1 complete
        assert respawn_ready({"epoch": 3, "failed": [],
                              "restarted": [2], "rejoined": [2]})
        # failure #2 detected
        assert not respawn_ready({"epoch": 4, "failed": [1],
                                  "restarted": [2], "rejoined": [2]})
        # respawn of pidx 1 granted but NOT yet rejoined
        assert not respawn_ready({"epoch": 5, "failed": [],
                                  "restarted": [1, 2],
                                  "rejoined": [2]})
        # replacement wired: ready
        assert respawn_ready({"epoch": 6, "failed": [],
                              "restarted": [1, 2],
                              "rejoined": [1, 2]})

    def test_finish_checked_respects_comm_epoch(self, ft_state):
        """A rejoined replacement's flaky transfer on a POST-recovery
        comm must stay a flake (original error), not be escalated to
        ERR_PROC_FAILED by its old failure episode."""
        import ompi_release_tpu as mpi
        from ompi_release_tpu.runtime.wire import WireRouter

        mpi.init()
        ft_state.apply_notice({"epoch": 1, "failed": [2]})
        ft_state.apply_notice({"epoch": 3, "failed": [],
                               "restarted": [2], "rejoined": [2]})
        rt = type("R", (), {})()

        def boom(self, *a, **k):
            raise MPIError(ErrorCode.ERR_TRUNCATE, "flaky tail")

        router = WireRouter.__new__(WireRouter)
        router._finish_transfer = boom.__get__(router)
        # pre-failure comm: escalated to the typed process failure
        with pytest.raises(MPIError) as ei:
            router._finish_checked(2, 0, b"", 0.0, epoch0=0)
        assert ei.value.code == ErrorCode.ERR_PROC_FAILED
        # post-recovery comm: the flake surfaces as itself
        with pytest.raises(MPIError) as ei:
            router._finish_checked(2, 0, b"", 0.0, epoch0=3)
        assert ei.value.code == ErrorCode.ERR_TRUNCATE


# ---------------------------------------------------------------------------
# dpm: FT-aware rendezvous + lookup (satellite)
# ---------------------------------------------------------------------------

class TestDpmFt:
    @pytest.fixture
    def world(self):
        import ompi_release_tpu as mpi

        return mpi.init()

    def test_connect_to_revoked_acceptor_fast_fails(self, world,
                                                    ft_state):
        """A connect against a parked acceptor whose comm is revoked
        returns the typed error IMMEDIATELY (no timeout burn)."""
        from ompi_release_tpu.comm.dpm import (
            close_port, comm_accept, comm_connect, open_port,
        )
        from ompi_release_tpu.comm.group import Group
        from ompi_release_tpu.comm.communicator import Communicator

        a = Communicator(world.runtime, Group([0, 1]), name="dpm-a")
        b = Communicator(world.runtime, Group([2, 3]), name="dpm-b")
        port = open_port()
        errs = {}

        def accept():
            try:
                comm_accept(a, port, timeout_s=15)
            except MPIError as e:
                errs["accept"] = e

        t = threading.Thread(target=accept, daemon=True)
        t.start()
        time.sleep(0.3)  # acceptor parked
        a._revoked = True  # poison the parked side
        t0 = time.monotonic()
        with pytest.raises(MPIError) as ei:
            comm_connect(b, port, timeout_s=15)
        assert time.monotonic() - t0 < 5  # not the full timeout
        assert ei.value.code == ErrorCode.ERR_REVOKED
        t.join(5)
        close_port(port)
        a._revoked = False
        a.free()
        b.free()

    def test_accept_rejects_stale_epoch_joiner(self, world, ft_state):
        from ompi_release_tpu.comm.dpm import (
            close_port, comm_connect, open_port,
        )
        from ompi_release_tpu.comm.group import Group
        from ompi_release_tpu.comm.communicator import Communicator

        ft_state.apply_notice({"epoch": 4, "failed": []})
        port = open_port()  # opened at epoch 4
        b = Communicator(world.runtime, Group([2, 3]), name="dpm-c")
        with pytest.raises(MPIError) as ei:
            comm_connect(b, port, timeout_s=5, epoch=2)  # stale view
        assert ei.value.code == ErrorCode.ERR_REVOKED
        assert "stale" in str(ei.value)
        close_port(port)
        b.free()

    def test_lookup_of_closed_port_raises_proc_failed(self, world,
                                                      ft_state):
        from ompi_release_tpu.comm.dpm import (
            close_port, lookup_name, open_port, publish_name,
            unpublish_name,
        )

        port = open_port()
        publish_name("dead-svc", port)
        close_port(port)  # publisher died without unpublishing
        t0 = time.monotonic()
        with pytest.raises(MPIError) as ei:
            lookup_name("dead-svc", timeout_s=10)
        assert ei.value.code == ErrorCode.ERR_PROC_FAILED
        assert time.monotonic() - t0 < 5
        unpublish_name("dead-svc")


# ---------------------------------------------------------------------------
# tpurun plumbing + bench gate directions (satellites)
# ---------------------------------------------------------------------------

class TestTpurunFtFlags:
    def test_ft_inject_arms_only_chosen_child_first_incarnation(self):
        job = Job(3, ["true"], [], ft_inject=(1, 7))
        job.hnp = type("H", (), {"port": 1})()
        job.hnp_host = "127.0.0.1"
        envs = {n: job._ompitpu_env(n) for n in (1, 2, 3)}
        key = "OMPITPU_MCA_sensor_ft_kill_step"
        assert envs[2][key] == "7"
        assert key not in envs[1] and key not in envs[3]
        # a respawned incarnation is NOT re-armed (one failure)
        job._restarts[2] = 1
        assert key not in job._ompitpu_env(2)

    def test_ft_inject_validation(self):
        with pytest.raises(MPIError):
            Job(2, ["true"], [], ft_inject=(5, 0))
        with pytest.raises(MPIError):
            Job(2, ["true"], [], ft_inject=(0, -1))

    def test_cli_parses_ft_flags(self, capsys):
        from ompi_release_tpu.tools import tpurun as tpurun_mod

        with pytest.raises(SystemExit):
            tpurun_mod.main(["--help"])
        out = capsys.readouterr().out
        assert "--ft-inject" in out and "--ft-continue" in out
        with pytest.raises(SystemExit):
            tpurun_mod.main(["-n", "2", "--ft-inject", "bogus", "true"])
        with pytest.raises(SystemExit):
            tpurun_mod.main(["-n", "2", "--enable-recovery",
                             "--ft-continue", "true"])

    def test_continue_policy_accepted(self):
        job = Job(2, ["true"], [], on_failure="continue")
        assert job.on_failure == "continue"
        with pytest.raises(MPIError):
            Job(2, ["true"], [], on_failure="bogus")

    def test_continue_policy_aborts_on_bringup_failure(self):
        """A child that dies during LAUNCH must abort the job loudly
        — the degraded-world policy only applies once RUNNING, or
        survivors would park in wire-up masking the real startup
        error."""
        import subprocess as _sp

        from ompi_release_tpu.runtime.state import ProcState

        job = Job(2, ["true"], [], on_failure="continue")
        job.hnp = coord.HnpCoordinator(3)
        job.job_state.activate(JobState.INIT)
        job.job_state.activate(JobState.LAUNCH_DAEMONS)  # NOT running
        try:
            job._on_worker_failure(1, ProcState.ABORTED)
            assert job._failed.is_set()  # aborted, not "continued"
            assert not job._ft_failed_ranks
        finally:
            job.hnp.shutdown()

    def test_sendrecv_refuses_revoked_comm(self):
        import ompi_release_tpu as mpi

        world = mpi.init()
        c = world.dup("sr-revoked")
        c._revoked = True
        with pytest.raises(MPIError) as ei:
            c.sendrecv([np.zeros(2)] * c.size, list(range(c.size)))
        assert ei.value.code == ErrorCode.ERR_REVOKED
        c._revoked = False
        c.free()

    def test_failed_at_of_parses_wire_map(self):
        assert ulfm.failed_at_of(None) == {}
        assert ulfm.failed_at_of({"failed_at": {"2": 5, "bad": "x",
                                                "1": "3"}}) \
            == {2: 5, 1: 3}


# ---------------------------------------------------------------------------
# ElasticStep in-process: injected-fault rollback (no job needed)
# ---------------------------------------------------------------------------

class TestElasticStepLocal:
    def test_injected_fault_rolls_back_to_committed_step(self,
                                                         tmp_path):
        import ompi_release_tpu as mpi
        from ompi_release_tpu.ft.checkpoint import Checkpointer
        from ompi_release_tpu.parallel.elastic import ElasticStep

        world = mpi.init()
        ck = Checkpointer(str(tmp_path / "ck"))
        calls = []

        def step_fn(step, state, comm):
            calls.append(step)
            return np.asarray(state) + np.float32(step + 1)

        es = ElasticStep(world, step_fn, ck, policy="shrink",
                         checkpoint_every=1,
                         tester=FtTester(fail_prob=0.0, every_n=4))
        state, stats = es.run(np.zeros((), np.float32), 6)
        # every-4 fires at tester-steps 4 and (after rollback resumes
        # counting) 8; each rolls back to the last committed step
        assert stats["injected_rollbacks"] >= 1
        assert float(np.asarray(state)) == float(sum(range(1, 7)))
        assert stats["steps_lost"] == 0  # checkpoint_every=1

    def test_unseeded_probabilistic_injection_refused_spanning(self):
        """Unseeded random injection on a spanning comm would
        desynchronize the collective schedule (one rank rolls back,
        peers post the step) — refused loudly at construction; a
        SEEDED tester (same step sequence fleet-wide) is accepted."""
        import ompi_release_tpu as mpi
        from ompi_release_tpu.ft.checkpoint import Checkpointer
        from ompi_release_tpu.parallel.elastic import ElasticStep

        world = mpi.init()
        fake = type("C", (), {"spans_processes": True,
                              "runtime": world.runtime})()
        with pytest.raises(MPIError) as ei:
            ElasticStep(fake, lambda s, st, c: st,
                        Checkpointer("/tmp/_es_refuse"),
                        tester=FtTester(fail_prob=0.1))
        assert "sensor_ft_seed" in str(ei.value)
        # a programmatically SEEDED tester is accepted as-is (no cvar
        # involved): the tester's own seed is what makes it replayable
        ElasticStep(fake, lambda s, st, c: st,
                    Checkpointer("/tmp/_es_refuse"),
                    tester=FtTester(fail_prob=0.1, seed=42))

    def test_unconfirmed_suspect_error_reraises(self, tmp_path,
                                                ft_state):
        import ompi_release_tpu as mpi
        from ompi_release_tpu.ft.checkpoint import Checkpointer
        from ompi_release_tpu.parallel.elastic import ElasticStep

        world = mpi.init()
        ck = Checkpointer(str(tmp_path / "ck2"))

        def step_fn(step, state, comm):
            raise MPIError(ErrorCode.ERR_TRUNCATE, "flaky transfer")

        es = ElasticStep(world, step_fn, ck, confirm_timeout_s=0.3)
        with pytest.raises(MPIError) as ei:
            es.run(np.zeros((), np.float32), 2)
        assert ei.value.code == ErrorCode.ERR_TRUNCATE  # not swallowed


# ---------------------------------------------------------------------------
# end-to-end recovery jobs (the acceptance criterion)
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
    from ompi_release_tpu.mca import pvar
    from ompi_release_tpu.ft.checkpoint import Checkpointer
    from ompi_release_tpu.ft.sensor import FtTester
    from ompi_release_tpu.parallel.elastic import ElasticStep
    from ompi_release_tpu.runtime.runtime import Runtime

    def _pv(name):
        p = pvar.PVARS.lookup(name)
        return float(p.read()) if p is not None else 0.0

    world = mpi.init()
    rt = Runtime.current()
    me = rt.bootstrap["process_index"]
    STEPS = 8

    def step_fn(step, state, comm):
        wrs = [comm.group.world_rank(i) for i in comm.local_comm_ranks]
        contrib = np.stack(
            [np.full(4, (step + 1) * (wr + 1), np.float32)
             for wr in wrs])
        got = np.asarray(comm.allreduce(contrib))
        return np.asarray(state) + got[:1]
""" % REPO)


def _run_ft_job(tmp_path, capfd, body, *, n=3, timeout=240,
                job_kw=None):
    app = tmp_path / "ft_app.py"
    app.write_text(APP_PRELUDE + textwrap.dedent(body))
    os.environ["OMPITPU_FT_TEST_DIR"] = str(tmp_path / "ftdir")
    try:
        job = Job(n, [sys.executable, str(app)], [],
                  heartbeat_s=0.3, miss_limit=3, **(job_kw or {}))
        rc = job.run(timeout_s=timeout)
    finally:
        os.environ.pop("OMPITPU_FT_TEST_DIR", None)
    out = capfd.readouterr()
    return rc, out.out + out.err, job


class TestRecoveryJobs:
    def test_kill_mid_allreduce_shrink_recovers_exact_loss(
            self, tmp_path, capfd):
        """THE acceptance criterion, shrink leg: a 3-process job has
        rank 2 SIGKILLed at step 3 (survivors are inside that step's
        allreduce); survivors detect via the heartbeat/waitpid epoch
        bump (ERR_PROC_FAILED from the bounded reap, NOT a watchdog
        timeout), revoke() the world, shrink() to a working 4-rank
        communicator, restore the last committed checkpoint, and
        finish with the exact degraded loss — with the ft_* pvars
        witnessing exactly one failure and one recovery."""
        rc, out, job = _run_ft_job(tmp_path, capfd, """
            ckpt = Checkpointer(os.path.join(
                os.environ["OMPITPU_FT_TEST_DIR"], f"rank{me}"))
            es = ElasticStep(world, step_fn, ckpt, policy="shrink",
                             checkpoint_every=1,
                             tester=FtTester.from_cvars(me))
            state, stats = es.run(np.zeros((1, 4), np.float32), STEPS)

            # exact replay math: steps 0-2 on the 6-rank world
            # (sum(wr+1) = 21), steps 3-7 on the 4 survivors (10)
            exp = (sum((s + 1) * 21 for s in range(0, 3))
                   + sum((s + 1) * 10 for s in range(3, 8)))
            got = np.asarray(state)
            assert np.array_equal(
                got, np.full((1, 4), float(exp), np.float32)), \\
                (got, exp)
            assert stats["recoveries"] == 1, stats
            fail = stats["failures"][0][1]
            assert ("ERR_PROC_FAILED" in fail
                    or "ERR_REVOKED" in fail), fail
            assert es.comm.size == 4
            assert not es.comm.spans_processes or \\
                len(es.comm.local_comm_ranks) == 2
            assert _pv("ft_failures_detected") == 1.0
            assert _pv("ft_recoveries") == 1.0
            assert _pv("ft_revokes") >= 1.0
            assert _pv("ft_recovery_seconds") > 0.0
            # the old world is poisoned: new collectives refuse fast
            try:
                world.allreduce(np.zeros((2, 2), np.float32))
                raise AssertionError("revoked world still worked")
            except mpi.MPIError as e:
                assert e.code in (
                    mpi.ErrorCode.ERR_REVOKED,
                    mpi.ErrorCode.ERR_PROC_FAILED), e
            print(f"FT_SHRINK_OK rank{me} final={float(got[0][0])}",
                  flush=True)
            mpi.finalize()
        """, job_kw={"on_failure": "continue", "ft_inject": (2, 3)})
        assert rc == 0, out
        assert out.count("FT_SHRINK_OK") == 2, out  # both survivors
        assert "FT_SHRINK_OK rank2" not in out
        assert job.job_state.visited(JobState.TERMINATED)
        assert job._ft_failed_ranks == {3}  # node id of pidx 2

    def test_p2p_recv_on_dead_peer_raises_typed_error(self, tmp_path,
                                                      capfd):
        """A blocking p2p recv whose sender process dies raises
        ERR_PROC_FAILED within the detection interval — not a generic
        ERR_PENDING after the full 30s pml_wire_timeout."""
        rc, out, _job = _run_ft_job(tmp_path, capfd, """
            if me == 1:
                time.sleep(1.0)
                import signal
                os.kill(os.getpid(), signal.SIGKILL)
            t0 = time.monotonic()
            try:
                world.recv(source=2, rank=0)  # rank 2 lives on pidx 1
                raise AssertionError("recv from dead peer returned")
            except mpi.MPIError as e:
                dt = time.monotonic() - t0
                assert e.code == mpi.ErrorCode.ERR_PROC_FAILED, e
                assert dt < 15, f"typed error took {dt:.1f}s"
            print(f"FT_P2P_OK rank{me}", flush=True)
            mpi.finalize()
        """, n=2, timeout=120, job_kw={"on_failure": "continue"})
        assert rc == 0, out
        assert "FT_P2P_OK rank0" in out

    def test_exit_zero_without_fin_is_promoted(self, tmp_path, capfd):
        """A worker that exits 0 WITHOUT sending FIN (os._exit mid-
        run) is lifeline-lost, not cleanly finished: it must still be
        promoted through the job epoch so survivors' waits raise the
        typed error — note_finished may only fire on a confirmed
        FIN."""
        rc, out, _job = _run_ft_job(tmp_path, capfd, """
            if me == 1:
                time.sleep(1.0)
                os._exit(0)  # exit 0, no FIN, no finalize
            t0 = time.monotonic()
            try:
                step_fn(0, np.zeros((1, 4), np.float32), world)
                raise AssertionError("collective with dead peer ran")
            except mpi.MPIError as e:
                dt = time.monotonic() - t0
                assert e.code in (mpi.ErrorCode.ERR_PROC_FAILED,
                                  mpi.ErrorCode.ERR_REVOKED), e
                assert dt < 20, f"typed error took {dt:.1f}s"
            print(f"FT_NOFIN_OK rank{me}", flush=True)
            mpi.finalize()
        """, n=2, timeout=120, job_kw={"on_failure": "continue"})
        assert rc == 0, out
        assert "FT_NOFIN_OK rank0" in out

    def test_kill_then_respawn_rebuilds_full_world_exact_loss(
            self, tmp_path, capfd):
        """The acceptance criterion's second leg: same kill, but under
        tpurun --enable-recovery the launcher respawns the rank; the
        replacement re-wires through the rejoin service at the new
        epoch, survivors re-dial it, and errmgr.recover('respawn')
        rebuilds a FULL-SIZE communicator (epoch-derived cid minted
        identically by survivors and the restarted process) whose
        allreduce is bitwise-correct; everyone resumes from the agreed
        checkpoint and reaches the no-failure loss."""
        rc, out, job = _run_ft_job(tmp_path, capfd, """
            ckpt = Checkpointer(os.path.join(
                os.environ["OMPITPU_FT_TEST_DIR"], f"rank{me}"))
            es = ElasticStep(world, step_fn, ckpt, policy="respawn",
                             checkpoint_every=1, recover_timeout_s=120,
                             tester=FtTester.from_cvars(me))
            state, stats = es.run(np.zeros((1, 4), np.float32), STEPS)

            # full-size recovery: every step sums over all 6 ranks
            exp = sum((s + 1) * 21 for s in range(STEPS))
            got = np.asarray(state)
            assert np.array_equal(
                got, np.full((1, 4), float(exp), np.float32)), \\
                (got, exp)
            assert es.comm.size == 6
            assert es.comm.name.startswith("rebuild")
            assert _pv("ft_recoveries") == 1.0
            print(f"FT_RESPAWN_OK rank{me} final={float(got[0][0])}",
                  flush=True)
            mpi.finalize()
        """, timeout=300,
            job_kw={"on_failure": "restart", "max_restarts": 2,
                    "ft_inject": (2, 3)})
        assert rc == 0, out
        # all three FINAL incarnations finish, replacement included
        for r in range(3):
            assert f"FT_RESPAWN_OK rank{r}" in out, out
        assert job._restarts.get(3) == 1  # exactly one respawn
        assert job.job_state.visited(JobState.TERMINATED)

    def test_two_sequential_failures_both_respawned(self, tmp_path,
                                                    capfd):
        """Multi-recovery: a SECOND rank dies after the first
        recovery completed. The lineage anchor is what makes this
        work — the second rebuild's agreement/cid pair a survivor
        holding rebuild#1 with a fresh replacement holding only its
        world — and the exact full-size loss proves both rollbacks
        replayed correctly."""
        rc, out, job = _run_ft_job(tmp_path, capfd, """
            ckpt = Checkpointer(os.path.join(
                os.environ["OMPITPU_FT_TEST_DIR"], f"rank{me}"))
            tester = FtTester.from_cvars(me)
            if me == 1 and not os.environ.get("OMPITPU_INCARNATION"):
                # the SECOND failure: rank 1's first incarnation dies
                # a few steps after recovery #1 completes
                tester.kill_step = 6
            es = ElasticStep(world, step_fn, ckpt, policy="respawn",
                             checkpoint_every=1, recover_timeout_s=120,
                             tester=tester)
            state, stats = es.run(np.zeros((1, 4), np.float32), STEPS)
            exp = sum((s + 1) * 21 for s in range(STEPS))
            got = np.asarray(state)
            assert np.array_equal(
                got, np.full((1, 4), float(exp), np.float32)), \\
                (got, exp)
            assert es.comm.size == 6
            print(f"FT_TWOFAIL_OK rank{me}", flush=True)
            mpi.finalize()
        """, timeout=300,
            job_kw={"on_failure": "restart", "max_restarts": 2,
                    "ft_inject": (2, 3)})
        assert rc == 0, out
        for r in range(3):
            assert f"FT_TWOFAIL_OK rank{r}" in out, out
        assert job._restarts.get(3) == 1  # rank 2's respawn
        assert job._restarts.get(2) == 1  # rank 1's respawn
