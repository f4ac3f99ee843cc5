"""Simulated-fleet scale harness: the real schedule/FT/sentinel stack
at P=256-4096 over the virtual wire.

Five layers:

1. Fabric units: link classes over host topologies, slow-NIC
   stragglers, deterministic loss retransmit penalties, partition
   windows (healing and black-hole).
2. Metrology/virtual-clock units at small P: inter-host byte
   accounting, straggler makespan impact, clock monotonicity.
3. SCALING CURVES at P in {256, 1024, 4096} (P >= 1024 @slow): the
   unmodified ``hier_schedules`` round code must show bcast root
   sends = ceil(log2 P), recursive-doubling rounds = ceil(log2 P),
   and Rabenseifner inter-process send bytes/rank = exactly
   2n(P-1)/P (every simulated rank is one process, so bytes_sent IS
   the hier_inter_bytes quantity; inter_bytes_sent is the
   host-crossing subset) — the O(log P)/O(n) claims, asserted at
   the scale they were made for.
4. ULFM + sentinel at scale: a 256-rank multi-failure chaos episode
   whose typed-error cascade, epoch agreement, ft_cid rebuild, and
   verified rerun all drive the real ``ft/ulfm.py`` state machines;
   a 256-rank sentinel desync whose journals feed the real
   ``tpu-doctor contracts`` / ``report`` forensics.
5. Determinism: the seeded P=64 chaos smoke scenario (tier-1) replays
   with bit-identical event logs — chaos as reproducible evidence.
"""

import json

import numpy as np
import pytest

from ompi_release_tpu.coll import hier_schedules as hs
from ompi_release_tpu.ft.ulfm import FT_CID_BASE
from ompi_release_tpu.obs import doctor as doctor_mod
from ompi_release_tpu.testing import fleet_sim as fs
from ompi_release_tpu.testing import scenarios as sc
from ompi_release_tpu.utils.errors import ErrorCode

slow = pytest.mark.slow

#: the acceptance grid: P=256 in tier-1, the fleet sizes @slow
SCALING_P = [256,
             pytest.param(1024, marks=slow),
             pytest.param(4096, marks=slow)]


# ---------------------------------------------------------------------------
# fabric units
# ---------------------------------------------------------------------------


class TestFabric:
    def test_host_grouping_and_link_classes(self):
        f = fs.Fabric(16, hosts_per=4)
        assert f.host(0) == f.host(3) == "h0"
        assert f.host(4) == "h1"
        assert not f.crosses_host(0, 3)
        assert f.crosses_host(3, 4)
        assert sorted(f.hosts()) == ["h0", "h1", "h2", "h3"]
        lat_i, bps_i, _ = f.link(0, 1)
        lat_x, bps_x, _ = f.link(0, 5)
        assert lat_x > lat_i and bps_x < bps_i

    def test_delivery_latency_plus_bandwidth(self):
        f = fs.Fabric(4, hosts_per=4)   # all intra
        lat, bps, _ = f.link(0, 1)
        arr, retx = f.delivery(0, 1, 1 << 20, 2.0, 0)
        assert retx == 0
        assert arr == pytest.approx(2.0 + lat + (1 << 20) / bps)

    def test_slow_nic_straggler_shapes_both_directions(self):
        f = fs.Fabric(4, hosts_per=4)
        base = f.delivery(0, 1, 4096, 0.0, 0)[0]
        f.slow_nic(1, 4.0)
        assert f.delivery(0, 1, 4096, 0.0, 0)[0] > base
        assert f.delivery(1, 2, 4096, 0.0, 0)[0] > base
        assert f.delivery(2, 3, 4096, 0.0, 0)[0] == base

    def test_loss_penalty_is_deterministic(self):
        mk = lambda: fs.Fabric(  # noqa: E731
            4, hosts_per=4, seed=9,
            intra=fs.LinkSpec(1e-6, 100.0, loss=0.5))
        a, b = mk(), mk()
        outs_a = [a.delivery(0, 1, 64, 0.0, k) for k in range(64)]
        outs_b = [b.delivery(0, 1, 64, 0.0, k) for k in range(64)]
        assert outs_a == outs_b
        retxs = [r for (_, r) in outs_a]
        assert any(r > 0 for r in retxs), "50% loss never retransmitted?"
        # every retransmit costs the rto on top of the lossless time
        clean = fs.Fabric(4, hosts_per=4).delivery(0, 1, 64, 0.0, 0)[0]
        for (arr, r) in outs_a:
            assert arr == pytest.approx(clean + r * a.rto_s)

    def test_partition_heals_and_blackholes(self):
        f = fs.Fabric(4, hosts_per=2)
        f.partition([0, 1], [2, 3], t0=1.0, t1=2.0)
        lat, bps, _ = f.link(0, 2)
        # inside the window: held in the switch until the heal
        arr, _ = f.delivery(0, 2, 64, 1.5, 0)
        assert arr >= 2.0 + lat
        # after the heal / not crossing: undisturbed delivery math
        assert f.delivery(0, 2, 64, 2.5, 0)[0] \
            == pytest.approx(2.5 + lat + 64 / bps)
        assert f.delivery(0, 1, 64, 1.5, 0)[0] < 2.0
        f.partition([0], [3], t0=0.0, t1=None)  # severed forever
        assert f.delivery(0, 3, 64, 0.5, 0)[0] is None


# ---------------------------------------------------------------------------
# virtual clock + metrology at small P
# ---------------------------------------------------------------------------


class TestMetrology:
    def test_ring_allgather_rounds_and_clock(self):
        P = 8
        fleet = fs.FleetSim(P, hosts_per=4)
        procs = fleet.procs
        blocks = {p: np.full(4, p, np.int32) for p in procs}
        rep = fleet.run(
            lambda x, p: hs.allgather_ring(x, procs, p, blocks[p]),
            label="allgather")
        assert rep.ok() == procs
        assert rep.min_rounds() == rep.max_rounds() == P - 1
        assert rep.makespan > 0.0
        for i, got in enumerate(rep.value(3)):
            np.testing.assert_array_equal(got, blocks[i])

    def test_inter_host_bytes_counted_only_across_hosts(self):
        # ring over hosts of 2: rank p sends everything to (p+1)%4,
        # so odd ranks cross hosts (1->2, 3->0), even ranks stay shm
        fleet = fs.FleetSim(4, hosts_per=2)
        procs = fleet.procs
        rep = fleet.run(
            lambda x, p: hs.allgather_ring(
                x, procs, p, np.full(8, p, np.int64)),
            label="allgather")
        for p in (0, 2):
            assert rep.inter_bytes_sent[p] == 0, rep.inter_bytes_sent
        for p in (1, 3):
            assert rep.inter_bytes_sent[p] == rep.bytes_sent[p] > 0

    def test_straggler_stretches_makespan(self):
        def makespan(straggle):
            fleet = fs.FleetSim(16, hosts_per=4)
            if straggle:
                fleet.fabric.slow_nic(5, 8.0)
            procs = fleet.procs
            rep = fleet.run(
                lambda x, p: hs.allgather_ring(
                    x, procs, p, np.full(1024, p, np.int64)),
                label="allgather")
            return rep.makespan

        assert makespan(True) > makespan(False)

    def test_lossy_link_costs_retransmit_time(self):
        def run(loss):
            fleet = fs.FleetSim(
                8, fabric=fs.Fabric(
                    8, hosts_per=8, seed=5,
                    intra=fs.LinkSpec(1e-6, 100.0, loss=loss)))
            procs = fleet.procs
            rep = fleet.run(
                lambda x, p: hs.allgather_ring(
                    x, procs, p, np.full(16, p, np.int32)),
                label="allgather")
            return rep

        clean, lossy = run(0.0), run(0.4)
        assert sum(lossy.loss_retx.values()) > 0
        assert sum(clean.loss_retx.values()) == 0
        assert lossy.makespan > clean.makespan


# ---------------------------------------------------------------------------
# the scaling curves (the acceptance grid)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("P", SCALING_P)
class TestScalingCurves:
    def test_bcast_root_sends_ceil_log2(self, P):
        fleet = fs.FleetSim(P, hosts_per=8, real_timeout_s=240)
        procs = fleet.procs
        val = np.arange(16, dtype=np.int32)
        rep = fleet.run(
            lambda x, p: hs.bcast_binomial(
                x, procs, p, 0, val if p == 0 else None),
            label="bcast", timeout_s=400)
        assert len(rep.ok()) == P
        # THE O(log P) fan-out claim, at the scale it was made for:
        # the root sends exactly ceil(log2 P) messages, not P-1
        assert rep.msgs_sent[0] == fs.log2_rounds(P)
        assert rep.rounds[0] == 1
        for p in (1, P // 2, P - 1):
            np.testing.assert_array_equal(np.asarray(rep.value(p)), val)
        # the binomial tree is O(log P) deep in virtual time too: far
        # below P serialized inter-latency hops
        lat = fleet.fabric.inter.latency_s
        assert rep.makespan < 4 * fs.log2_rounds(P) * 10 * lat

    def test_recursive_doubling_rounds_ceil_log2(self, P):
        fleet = fs.FleetSim(P, hosts_per=8, real_timeout_s=240)
        procs = fleet.procs
        data = {p: np.full(2, p + 1, np.int64) for p in procs}
        rep = fleet.run(
            lambda x, p: np.sum(
                np.stack(hs.allgather_bruck(x, procs, p, data[p],
                                            [2] * P)), axis=0),
            label="allreduce_rd", timeout_s=400)
        assert len(rep.ok()) == P
        # the doubling-distance partial exchange behind the
        # recursive_doubling allreduce: ceil(log2 P) rounds on EVERY
        # rank, regardless of P
        assert rep.min_rounds() == rep.max_rounds() \
            == fs.log2_rounds(P)
        want = np.full(2, P * (P + 1) // 2, np.int64)
        np.testing.assert_array_equal(np.asarray(rep.value(P // 3)),
                                      want)

    def test_rabenseifner_inter_bytes_2n(self, P):
        fleet = fs.FleetSim(P, hosts_per=8, real_timeout_s=240)
        procs = fleet.procs
        n = 2 * P
        data = {p: np.arange(n, dtype=np.float32) * ((p % 7) + 1)
                for p in procs}
        rep = fleet.run(
            lambda x, p: hs.allreduce_rabenseifner(
                x, procs, p, data[p], np.add, 0.0),
            label="allreduce_rab", timeout_s=400)
        assert len(rep.ok()) == P
        nbytes = n * 4
        want_bytes = fs.rabenseifner_bytes_per_rank(n, 4, P)
        # EXACT: (P-1) chunks out in the halving reduce-scatter plus
        # (P-1) back in the doubling allgather = 2n(P-1)/P per rank.
        # bytes_sent IS the inter-process (hier_inter_bytes) quantity
        # here: one simulated rank = one process...
        assert set(rep.bytes_sent.values()) == {want_bytes}
        assert want_bytes <= 2 * nbytes
        # ...which is O(n), not the linear path's O(P n): at fleet
        # scale the gap is what makes the schedule usable at all
        assert want_bytes * 64 < (P - 1) * nbytes
        # and 2*ceil(log2 P) rounds per rank
        assert rep.min_rounds() == rep.max_rounds() \
            == 2 * fs.log2_rounds(P)
        want = sum(np.arange(n, dtype=np.float32) * ((p % 7) + 1)
                   for p in procs)
        np.testing.assert_allclose(np.asarray(rep.value(5)), want,
                                   rtol=1e-4)


# ---------------------------------------------------------------------------
# ULFM at scale: cascades, typed errors, recovery
# ---------------------------------------------------------------------------


class TestUlfmAtScale:
    def test_death_cascades_into_typed_errors(self):
        """One staged death mid-ring: the direct waiter raises
        ERR_PROC_FAILED through the REAL check_wait, the revoke storm
        propagates, and downstream waiters raise ERR_REVOKED —
        exactly the PR 9 semantics, process-free."""
        P = 16
        fleet = fs.FleetSim(P, hosts_per=8)
        procs = fleet.procs
        fleet.kill(6, at_round=3)
        rep = fleet.run(
            lambda x, p: hs.allgather_ring(
                x, procs, p, np.full(4, p, np.int32)),
            label="allgather")
        assert rep.killed() == [6]
        assert not fleet.ranks[6].alive
        errs = {p: rep.outcomes[p][1] for p in rep.errored()}
        assert errs, "no rank detected the death"
        codes = {e.code for e in errs.values()}
        assert ErrorCode.ERR_PROC_FAILED in codes
        # the failure wave travels the ring one hop per round: rank
        # 7 (the direct waiter) fails at round 3, rank 7+d at round
        # 3+d — so exactly the ranks within P-1-3 hops error, and
        # the two furthest-downstream ranks (4, 5) legally finish
        # all 15 rounds first. Downstream detectors saw the revoke
        # storm, not the raw death.
        assert rep.ok() == [4, 5]
        assert len(rep.errored()) == P - 3
        assert ErrorCode.ERR_REVOKED in codes
        # every detector's OWN FtState carries the failure picture
        for p in rep.errored():
            st = fleet.ranks[p].ft
            assert 6 in st.failed_at
            assert st.is_revoked(1) or st.dead_for([6])

    def test_same_cid_rerun_after_error_is_refused(self):
        """An errored rank's exit markers (and undrained payloads)
        still sit on the failed cid's queues, so replaying survivors
        on the SAME cid would fail spuriously — run() enforces the
        production ULFM rule: rebuild on a fresh cid."""
        fleet = fs.FleetSim(8, hosts_per=8)
        procs = fleet.procs
        fleet.kill(3, at_round=2)
        rep = fleet.run(
            lambda x, p: hs.allgather_ring(
                x, procs, p, np.full(4, p, np.int32)),
            label="allgather")
        survivors = [p for p in procs if fleet.ranks[p].alive]
        assert rep.errored()
        with pytest.raises(ValueError, match="fresh cid"):
            fleet.run(lambda x, p: None, ranks=survivors, cid=1)
        # the rebuild shape works: fresh cid, clean run
        rep2 = fleet.run(
            lambda x, p: hs.allgather_ring(
                x, survivors, p, np.full(4, p, np.int32)),
            ranks=survivors, cid=2)
        assert rep2.ok() == survivors

    def test_blackhole_partition_raises_unreachable(self):
        fleet = fs.FleetSim(8, hosts_per=4)
        fleet.fabric.partition(range(4), range(4, 8), t0=0.0, t1=None)
        procs = fleet.procs
        rep = fleet.run(
            lambda x, p: hs.allgather_ring(
                x, procs, p, np.full(4, p, np.int32)),
            label="allgather")
        errs = [rep.outcomes[p][1] for p in rep.errored()]
        assert errs
        assert any(e.code == ErrorCode.ERR_UNREACH for e in errs)

    def test_multi_failure_episode_256(self):
        """The satellite scenario: a 256-rank, 3-death cascade with a
        healing partition and a straggler, recovered through the real
        epoch agreement + ft_cid rebuild, rerun verified."""
        res = sc.cascading_failure(P=256, seed=7, deaths=3)
        assert len(res.victims) == 3
        assert len(res.survivors) == 256 - 3
        assert res.agreed_epoch == 3
        # every survivor derived the SAME rebuilt cid from its own
        # state (asserted inside the scenario) in the wire FT band
        assert FT_CID_BASE <= res.new_cid < (1 << 20)
        assert res.phase1.killed() == res.victims
        # phase 2 completed on every survivor (verified numerically
        # inside the scenario)
        assert res.phase2.ok() == res.survivors

    def test_forensics_incident_timeline_names_culprits_256(
            self, tmp_path):
        """Dump the 256-rank episode's per-rank journals and make the
        REAL tpu-doctor report name the story: which ranks died, that
        the comm was revoked, that recovery landed on the rebuilt
        cid — forensics past 8 ranks for the first time."""
        res = sc.cascading_failure(P=256, seed=7, deaths=3)
        d = tmp_path / "dumps"
        assert res.fleet.write_journals(str(d)) == 256
        dumps = doctor_mod.load_dir(str(d))
        text, data = doctor_mod.skew_report(dumps)
        incidents = data["incidents"]
        failed = sorted({e["failed_pidx"] for e in incidents
                         if e["op"] == "ft_failure"})
        assert failed == res.victims
        revoked_cids = {e["cid"] for e in incidents
                        if e["op"] == "ft_revoke"}
        assert 1 in revoked_cids
        recs = [e for e in incidents if e["op"] == "ft_recovery"]
        assert recs and recs[0]["new_cid"] == res.new_cid
        assert "incident timeline" in text
        for v in res.victims:
            assert f"process {v} FAILED" in text


# ---------------------------------------------------------------------------
# sentinel at scale: 256-rank desync through the real doctor
# ---------------------------------------------------------------------------


class TestSentinelAtScale:
    def test_contracts_names_the_divergent_rank_of_256(self, tmp_path):
        fleet = sc.sentinel_desync(P=256, divergent_rank=137,
                                   divergent_seq=2)
        d = tmp_path / "dumps"
        assert fleet.write_journals(str(d)) == 256
        dumps = doctor_mod.load_dir(str(d))
        text, data = doctor_mod.contract_report(dumps,
                                                directory=str(d))
        assert data["divergences"] == 1
        div = data["comms"]["1"]["divergence"]
        assert div["kind"] == "signature_mismatch"
        assert div["seq"] == 2 and div["divergent"] == 137
        assert div["expected"]["canon"] \
            == "allreduce|sum|float32|1024|-1"
        assert div["actual"]["canon"] == "bcast|-|float32|1024|0"
        assert "proc 137 posted bcast" in text
        assert "restore.py:88" in text and "trainer.py:203" in text

    def test_doctor_cli_exit_code_on_the_sim_dump(self, tmp_path,
                                                  capsys):
        from ompi_release_tpu.tools import tpu_doctor

        fleet = sc.sentinel_desync(P=64, divergent_rank=33,
                                   divergent_seq=1)
        d = tmp_path / "dumps"
        fleet.write_journals(str(d))
        rc = tpu_doctor.main(["contracts", str(d)])
        out = capsys.readouterr().out
        assert rc == 3
        assert "proc 33" in out and "DESYNC at seq 1" in out

    def test_healthy_fleet_chains_agree(self):
        """No divergence injected: 256 production CallSig chains fold
        to ONE value — the cross-rank determinism the sentinel's
        whole design rests on, at 256 ranks."""
        fleet = sc.sentinel_desync(P=256, divergent_rank=-1,
                                   divergent_seq=2)  # never fires
        chains = {fleet.chain_of(p, 1) for p in fleet.procs}
        assert len(chains) == 1 and 0 not in chains


# ---------------------------------------------------------------------------
# determinism: seeded chaos replays bit-identically (tier-1 smoke)
# ---------------------------------------------------------------------------


class TestDeterminism:
    def test_p64_smoke_chaos_replays_identically(self):
        """THE tier-1 smoke scenario: P=64, cascading deaths, healing
        partition, slow-NIC straggler — full episode (chaos -> typed
        errors -> agreement -> ft_cid rebuild -> verified rerun)
        twice, with bit-identical event logs."""
        r1 = sc.cascading_failure(P=64, seed=3)
        r2 = sc.cascading_failure(P=64, seed=3)
        assert r1.event_log_json == r2.event_log_json
        ev = json.loads(r1.event_log_json)
        kinds = {e["kind"] for e in ev}
        assert {"died", "error", "revoke", "learned_failure", "done",
                "recovered"} <= kinds
        assert r1.victims == r2.victims
        assert r1.new_cid == r2.new_cid
        # the chaos actually happened: both typed error classes
        codes = {r1.phase1.outcomes[p][1].code
                 for p in r1.phase1.errored()}
        assert codes & {ErrorCode.ERR_PROC_FAILED,
                        ErrorCode.ERR_REVOKED}

    def test_different_seed_different_story(self):
        r1 = sc.cascading_failure(P=64, seed=3)
        r2 = sc.cascading_failure(P=64, seed=4)
        assert r1.event_log_json != r2.event_log_json

    @slow
    def test_p256_chaos_replays_identically(self):
        r1 = sc.cascading_failure(P=256, seed=11, deaths=4)
        r2 = sc.cascading_failure(P=256, seed=11, deaths=4)
        assert r1.event_log_json == r2.event_log_json


# ---------------------------------------------------------------------------
# the scaling observables at one small P, against their closed forms
# ---------------------------------------------------------------------------


class TestScalingObservables:
    def test_p64_counts_match_closed_forms(self):
        """One fleet of 64 (8 per host) runs the three schedules the
        O(log P) claims rest on; the counts are the simulator's own
        (tier: sim), never a time."""
        P = 64
        fleet = fs.FleetSim(P, hosts_per=8, seed=1)
        procs = fleet.procs
        val = np.arange(16, dtype=np.int32)
        rep = fleet.run(
            lambda x, p: hs.bcast_binomial(
                x, procs, p, 0, val if p == 0 else None),
            label="bcast")
        assert rep.msgs_sent[0] == 6 == fs.log2_rounds(P)
        assert rep.makespan > 0
        data = {p: np.full(8, p + 1, np.int64) for p in procs}
        rep = fleet.run(
            lambda x, p: hs.allgather_bruck(x, procs, p, data[p],
                                            [8] * P),
            label="allgather")
        assert rep.max_rounds() == 6
        n_el = 2 * P
        fdata = {p: np.arange(n_el, dtype=np.float32) * ((p % 7) + 1)
                 for p in procs}
        rep = fleet.run(
            lambda x, p: hs.allreduce_rabenseifner(
                x, procs, p, fdata[p], np.add, 0.0),
            label="allreduce")
        assert rep.max_bytes_sent() \
            == fs.rabenseifner_bytes_per_rank(128, 4, 64)
        assert rep.max_rounds() == 2 * 6

    def test_bcast_makespan_shrinks_vs_flat_wire(self):
        """The fabric model is doing real work: the same binomial
        bcast over an 8-per-host topology beats an all-DCN wire."""
        def makespan(hosts_per):
            fleet = fs.FleetSim(64, hosts_per=hosts_per)
            procs = fleet.procs
            val = np.arange(16, dtype=np.int32)
            rep = fleet.run(
                lambda x, p: hs.bcast_binomial(
                    x, procs, p, 0, val if p == 0 else None),
                label="bcast")
            return rep.makespan

        assert makespan(8) < makespan(1)


# ---------------------------------------------------------------------------
# multi-tenant: N tenants x small fleets over one shared fabric
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mt_result():
    """ONE P=256 multi_tenant scenario run (solo + contended-QoS with
    a staged bulk-rank kill + contended-FIFO legs) shared by the
    fairness and FT-isolation assertions — the kill is staged in the
    bulk tenant only, so the latency tenant's virtual clocks are
    identical to a kill-free run (contention is the deterministic
    bandwidth-share model, not the bulk schedule's fate)."""
    return sc.multi_tenant(P=256, seed=1, kill_bulk=True)


class TestMultiTenant:
    def test_tenant_cid_banding_units(self):
        from ompi_release_tpu.ft import ulfm

        lo0, hi0 = ulfm.tenant_band(0)
        lo1, hi1 = ulfm.tenant_band(1)
        assert hi0 == lo1 and hi0 - lo0 == ulfm.TENANT_CID_SLOT
        assert ulfm.tenant_band(ulfm.MAX_TENANTS - 1)[1] == FT_CID_BASE
        # app cids and tenant-scoped rebuild cids stay in-band
        c = ulfm.tenant_cid(3, 7)
        assert ulfm.tenant_of_cid(c) == 3
        r = ulfm.ft_cid(5, c, tenant=3)
        assert ulfm.tenant_of_cid(r) == 3
        assert r != c
        # distinct tenants recovering at one epoch never collide
        assert ulfm.ft_cid(5, c, tenant=3) != ulfm.ft_cid(5, c, tenant=4)
        # legacy (tenant-less) rebuilds stay in the FT band
        assert ulfm.ft_cid(5, c) >= FT_CID_BASE
        assert ulfm.tenant_of_cid(ulfm.ft_cid(5, c)) == -1
        assert ulfm.tenant_of_cid(17) == -1
        with pytest.raises(Exception):
            ulfm.tenant_band(ulfm.MAX_TENANTS)

    def test_bandwidth_share_scales_only_bandwidth(self):
        fab = fs.Fabric(4, hosts_per=2)
        lat0, bps0, _ = fab.link(0, 2)
        fab.bandwidth_share(0, 0.25)
        lat1, bps1, _ = fab.link(0, 2)
        assert lat1 == lat0                    # latency untouched
        assert bps1 == pytest.approx(bps0 * 0.25)
        # receiver-side share does not apply (sender egress model)
        assert fab.link(2, 1)[1] == pytest.approx(bps0)

    def test_fairness_bound_at_p256(self, mt_result):
        """Bulk tenant saturating the wire leaves the latency
        tenant's virtual-clock makespan within the weighted-fair
        bound of its solo run — while the FIFO (no-QoS) model of the
        same contention blows far past it."""
        r = mt_result
        assert len(r.lat_ranks) == 32 and len(r.bulk_ranks) == 224
        bound = r.solo_makespan / r.share_lat * 1.10
        assert r.qos_makespan <= bound
        assert r.p99(r.qos_durations) <= \
            r.p99(r.solo_durations) / r.share_lat * 1.10
        # the QoS win over head-of-line FIFO is large and measurable
        assert r.fifo_makespan > 2.0 * r.qos_makespan

    def test_isolation_ratios_at_p64(self):
        """Solo, contended-under-QoS and contended-FIFO p99 of the
        latency tenant at a small P (virtual clocks, tier: sim): the
        isolation ratio stays inside the inverse fair share, the FIFO
        wire costs more than twice that."""
        r = sc.multi_tenant(P=64, seed=1, kill_bulk=False)
        solo_p99 = r.p99(r.solo_durations)
        qos_p99 = r.p99(r.qos_durations)
        fifo_p99 = r.p99(r.fifo_durations)
        iso = qos_p99 / solo_p99
        assert 1.0 <= iso <= (1.0 / r.share_lat) * 1.10
        assert fifo_p99 / solo_p99 > 2.0 * iso
        assert qos_p99 >= solo_p99
        assert r.p99(r.bulk_durations) > 0

    def test_ft_isolation_at_p256(self, mt_result):
        """SIGKILLing a bulk-tenant rank mid-allreduce revokes ONLY
        the bulk tenant's band cids: every latency rank finishes ok,
        every bulk survivor raises a typed ULFM error, and no
        latency-rank FtState ever saw a revocation."""
        from ompi_release_tpu.ft import ulfm

        r = mt_result
        assert r.killed_rank in r.bulk_ranks
        assert all(k == "ok" for k, _ in r.outcomes_lat.values())
        kinds = {}
        for p, (k, v) in r.outcomes_bulk.items():
            kinds.setdefault(k, []).append(p)
            if k == "error":
                assert v.code in (ErrorCode.ERR_PROC_FAILED,
                                  ErrorCode.ERR_REVOKED)
        assert kinds["killed"] == [r.killed_rank]
        assert len(kinds["error"]) == len(r.bulk_ranks) - 1
        # revocations confined to the bulk tenant's band
        for p in r.bulk_ranks:
            for c in r.qos_fleet.ranks[p].ft.revoked:
                assert ulfm.tenant_of_cid(c) == 1
        for p in r.lat_ranks:
            assert not r.qos_fleet.ranks[p].ft.revoked

    def test_band_revoke_poisons_future_cids_and_clear_band_heals(self):
        from ompi_release_tpu.ft import ulfm
        from ompi_release_tpu.utils.errors import MPIError

        st = ulfm.FtState()
        lo, hi = ulfm.tenant_band(2)
        st.revoke_band(lo, hi)
        assert st.is_revoked(ulfm.tenant_cid(2, 9))  # never minted
        with pytest.raises(MPIError) as ei:
            st.check_wait(ulfm.tenant_cid(2, 9), (), "wait")
        assert ei.value.code == ErrorCode.ERR_REVOKED
        assert "tenant 2" in str(ei.value)
        # the neighbor band is untouched
        st.check_wait(ulfm.tenant_cid(3, 9), (), "wait")
        assert [lo, hi] in st.snapshot()["revoked_bands"]
        st.clear_band(lo, hi)
        st.check_wait(ulfm.tenant_cid(2, 9), (), "wait")

    def test_per_rank_cid_scopes_exit_markers_small(self):
        """Two tenants in ONE run at small P: a death in tenant B's
        cid never wakes tenant A's queues (the cid(p) callable run
        shape, fast version of the P=256 episode)."""
        from ompi_release_tpu.ft import ulfm

        fleet = fs.FleetSim(8, hosts_per=4, seed=0)
        a_ranks, b_ranks = [0, 2, 4, 6], [1, 3, 5, 7]
        a_cid, b_cid = ulfm.tenant_cid(0, 0), ulfm.tenant_cid(1, 0)
        fleet.kill(3, at_round=1)
        data = {p: np.full(4, p + 1, np.int64) for p in range(8)}

        def fn(x, p):
            grp = a_ranks if p in a_ranks else b_ranks
            return hs.allgather_bruck(x, grp, p, data[p], [4] * 4)

        rep = fleet.run(fn, cid=lambda p: a_cid if p in a_ranks
                        else b_cid, label="mt")
        assert all(rep.outcomes[p][0] == "ok" for p in a_ranks)
        assert rep.outcomes[3][0] == "killed"
        assert all(rep.outcomes[p][0] == "error" for p in b_ranks
                   if p != 3)
