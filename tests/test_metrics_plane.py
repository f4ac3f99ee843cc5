"""The continuous fleet metrics plane.

Three layers under test:

- unit: sampler delta snapshots (scalar + histogram deltas, zero-delta
  suppression, per-communicator scoping from journal spans, ring
  bounds), histogram percentile math, OpenMetrics-with-timestamps
  exposition, and the series dump/merge clock correction;
- in-process fleet: a live HnpCoordinator TAG_SERIES responder
  aggregating three WorkerAgents' pushes, queried through tpu_top's
  FleetClient and rendered as per-rank rows;
- job: a 3-process tpurun run with the sampler armed — per-rank
  series dumps at finalize, clock-corrected merge, tpu_top rows, the
  HNP-side aggregation, and the skew report's sampled-rate annotation
  (the acceptance criteria).
"""

import json
import os
import sys
import time

import numpy as np
import pytest

from ompi_release_tpu.mca import pvar as pvar_mod
from ompi_release_tpu.mca import var as mca_var
from ompi_release_tpu.obs import doctor as doctor_mod
from ompi_release_tpu.obs import export as export_mod
from ompi_release_tpu.obs import sampler as sampler_mod
from ompi_release_tpu.tools.tpurun import Job

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def obs_sampling():
    """obs enabled + sampler state reset; fully restored afterwards."""
    import ompi_release_tpu.obs as obs

    obs.enable()
    sampler_mod._reset_for_tests()
    try:
        yield obs
    finally:
        sampler_mod._reset_for_tests()
        obs.disable()


# ---------------------------------------------------------------------------
# unit: sampler deltas
# ---------------------------------------------------------------------------

class TestSamplerDeltas:
    def test_counter_delta_not_cumulative_value(self, obs_sampling):
        c = pvar_mod.counter("mp_test_ctr", "t")
        c.add(100)
        s = sampler_mod.SAMPLER
        s.sample_once()  # baseline: first sight records the current read
        c.add(7)
        s.sample_once()
        pts = [p for p in sampler_mod.snapshot()
               if p["name"] == "mp_test_ctr"]
        # second tick's point is the DELTA, not the cumulative 107
        assert pts[-1]["v"] == 7.0, pts

    def test_zero_delta_suppressed(self, obs_sampling):
        c = pvar_mod.counter("mp_quiet_ctr", "t")
        c.add(1)
        s = sampler_mod.SAMPLER
        s.sample_once()
        n_before = len([p for p in sampler_mod.snapshot()
                        if p["name"] == "mp_quiet_ctr"])
        s.sample_once()  # nothing bumped: no new point for this series
        n_after = len([p for p in sampler_mod.snapshot()
                       if p["name"] == "mp_quiet_ctr"])
        assert n_after == n_before == 1

    def test_histogram_delta_buckets(self, obs_sampling):
        h = pvar_mod.histogram("mp_test_hist", "t")
        h.observe(3.0)
        s = sampler_mod.SAMPLER
        s.sample_once()
        h.observe(3.5)   # same (2,4] bucket
        h.observe(100.0)
        s.sample_once()
        pts = [p for p in sampler_mod.snapshot()
               if p["name"] == "mp_test_hist"]
        d = pts[-1]["v"]
        assert d["count"] == 2.0
        assert d["buckets"][4.0] == 1.0    # only the NEW observation
        assert d["buckets"][128.0] == 1.0

    def test_per_communicator_scoping(self, obs_sampling):
        obs = obs_sampling
        s = sampler_mod.SAMPLER
        s.sample_once()
        t = time.perf_counter()
        obs.journal.record("allreduce", "coll", t, 1e-3, nbytes=4096,
                           comm_id=3)
        obs.journal.record("allreduce", "coll", t, 2e-3, nbytes=4096,
                           comm_id=3)
        obs.journal.record("bcast", "coll", t, 1e-3, nbytes=128,
                           comm_id=9)
        obs.journal.record("wire_send", "wire", t, 1e-3, nbytes=999,
                           comm_id=3)  # non-coll layer: not a series
        s.sample_once()
        by_cid = {}
        for p in sampler_mod.snapshot():
            if p["name"] in ("coll_ops", "coll_bytes", "coll_seconds"):
                by_cid.setdefault(p["cid"], {})[p["name"]] = p["v"]
        assert by_cid[3]["coll_ops"] == 2.0
        assert by_cid[3]["coll_bytes"] == 8192.0
        assert by_cid[9]["coll_ops"] == 1.0
        assert by_cid[3]["coll_seconds"] == pytest.approx(3e-3)

    def test_ring_bound_and_counters(self, obs_sampling):
        ring = sampler_mod.SeriesRing(size=4)
        for i in range(10):
            ring.append(float(i), -1, "x", float(i))
        snap = ring.snapshot()
        assert len(snap) == 4
        assert [p["v"] for p in snap] == [6.0, 7.0, 8.0, 9.0]
        assert ring.total_recorded == 10
        pts, cursor = ring.drain_since(8)
        assert [p["v"] for p in pts] == [8.0, 9.0] and cursor == 10

    def test_disabled_sampler_records_nothing(self):
        import ompi_release_tpu.obs as obs

        sampler_mod._reset_for_tests()
        assert not obs.enabled
        assert sampler_mod.SAMPLER.sample_once() == 0
        assert sampler_mod.snapshot() == []
        # and maybe_start without the interval cvar set arms nothing
        obs.enable()
        try:
            assert not sampler_mod.maybe_start()
            assert not sampler_mod.SAMPLER.running()
        finally:
            obs.disable()

    def test_idle_ticks_are_fully_quiet(self, obs_sampling):
        """The self-observation feedback loop stays closed: after the
        baseline tick, a process where NOTHING happened records zero
        points (the sampler's own pvars and the journal bookkeeping
        its tick span moves are excluded from the scan), so an idle
        fleet pushes nothing."""
        s = sampler_mod.SAMPLER
        s.sample_once()  # baseline (first sight of every pvar)
        s.sample_once()  # may see deltas from the baseline tick itself
        assert s.sample_once() == 0

    def test_overhead_pvar_accounts_ticks(self, obs_sampling):
        ov0 = float(pvar_mod.PVARS.lookup(
            "obs_sample_overhead_seconds").read())
        sampler_mod.SAMPLER.sample_once()
        assert float(pvar_mod.PVARS.lookup(
            "obs_sample_overhead_seconds").read()) > ov0


# ---------------------------------------------------------------------------
# unit: percentile math
# ---------------------------------------------------------------------------

class TestPercentile:
    def test_empty(self):
        assert sampler_mod.percentile({}, 0.5) is None
        assert sampler_mod.percentile({4.0: 0}, 0.5) is None

    def test_single_bucket_midpoint(self):
        # all mass in (4, 8]: the geometric-midpoint estimate is 6
        assert sampler_mod.percentile({8.0: 5}, 0.5) == 6.0
        assert sampler_mod.percentile({8.0: 5}, 0.99) == 6.0

    def test_quantile_picks_the_right_bucket(self):
        # 90 obs in (0.5, 1], 10 in (512, 1024]
        b = {1.0: 90, 1024.0: 10}
        assert sampler_mod.percentile(b, 0.5) == 0.75
        assert sampler_mod.percentile(b, 0.99) == 768.0

    def test_zero_bucket_and_string_keys(self):
        assert sampler_mod.percentile({"0.0": 3}, 0.5) == 0.0
        assert sampler_mod.percentile({"8.0": 1, "0.0": 0}, 0.5) == 6.0


# ---------------------------------------------------------------------------
# unit: OpenMetrics-with-timestamps + series dump/merge clock math
# ---------------------------------------------------------------------------

def _pt(i, t, cid, name, v):
    return {"i": i, "t": t, "cid": cid, "name": name, "v": v}


class TestSeriesExport:
    def test_openmetrics_has_timestamps_and_eof(self):
        pts = [_pt(0, 10.5, -1, "coll_invocations", 3.0),
               _pt(1, 10.5, 2, "coll_ops", 5.0)]
        om = export_mod.openmetrics_series(pts, pidx=1,
                                           clock_offset_s=2.0)
        assert om.endswith("# EOF\n")
        assert ('ompitpu_coll_invocations_delta{pidx="1",cid="-1"} '
                "3 12.500000") in om
        assert 'cid="2"' in om

    def test_openmetrics_histogram_expansion(self):
        pts = [_pt(0, 1.0, -1, "coll_allreduce_latency",
                   {"count": 4.0, "sum": 2.0, "min": 0.1, "max": 1.0,
                    "buckets": {1.0: 4}})]
        om = export_mod.openmetrics_series(pts)
        assert "_delta_count" in om and "_delta_sum" in om
        assert "_delta_p50" in om and "_delta_p99" in om

    def test_openmetrics_families_contiguous_and_typed_once(self):
        # interleaved input points; the exposition must regroup them
        # (spec: one TYPE line per family, family samples contiguous)
        pts = [_pt(0, 1.0, -1, "aa", 1.0), _pt(1, 1.0, -1, "bb", 2.0),
               _pt(2, 2.0, -1, "aa", 3.0)]
        lines = export_mod.openmetrics_series(pts).splitlines()
        types = [ln for ln in lines if ln.startswith("# TYPE")]
        assert len(types) == len(set(types)) == 2
        ia = lines.index("# TYPE ompitpu_aa_delta gauge")
        assert lines[ia + 1].startswith("ompitpu_aa_delta{")
        assert lines[ia + 2].startswith("ompitpu_aa_delta{")

    def test_openmetrics_per_point_pidx_for_merged_fleet(self):
        pts = [dict(_pt(0, 1.0, -1, "x", 1.0), pidx=2)]
        om = export_mod.openmetrics_series(pts)
        assert 'pidx="2"' in om

    def test_dump_load_merge_clock_correction(self, tmp_path):
        d0 = {"meta": {"pidx": 0, "clock_offset_s": 0.0},
              "points": [_pt(0, 100.0, -1, "x", 1.0)]}
        d1 = {"meta": {"pidx": 1, "clock_offset_s": 5.0},
              "points": [_pt(0, 96.0, -1, "x", 2.0)]}
        for d in (d0, d1):
            export_mod.dump_series_jsonl(
                str(tmp_path / f"series-p{d['meta']['pidx']}.jsonl"), d)
        docs = doctor_mod.load_series_dir(str(tmp_path))
        assert [int(d["meta"]["pidx"]) for d in docs] == [0, 1]
        merged = doctor_mod.merge_series(docs)
        # p1's 96.0 + offset 5.0 = 101.0 sorts AFTER p0's 100.0
        assert [p["pidx"] for p in merged] == [0, 1]
        assert merged[1]["ts"] == pytest.approx(101.0)

    def test_series_rates_skips_single_tick_procs(self):
        merged = [{"ts": 5.0, "t": 5.0, "pidx": 0, "cid": 0,
                   "name": "coll_ops", "v": 10.0}]
        # one tick = no measurable window: no rate, not a 10000/s lie
        assert doctor_mod.series_rates(merged) == {}

    def test_series_rates_fold(self):
        merged = []
        for k in range(5):
            t = 10.0 + k
            merged.append({"ts": t, "t": t, "pidx": 0, "cid": 0,
                           "name": "coll_ops", "v": 8.0})
            merged.append({"ts": t, "t": t, "pidx": 0, "cid": 0,
                           "name": "coll_bytes", "v": 4e6})
        rates = doctor_mod.series_rates(merged)
        assert rates[0]["coll_ops_per_s"] == pytest.approx(10.0)
        assert rates[0]["coll_mb_per_s"] == pytest.approx(5.0)

    def test_skew_report_annotated_with_rates(self):
        def jdump(pidx, spans):
            return {"meta": {"pidx": pidx, "rank_offset": pidx * 2,
                             "local_size": 2, "clock_offset_s": 0.0},
                    "spans": spans}

        def span(op, t):
            return {"seq": 0, "op": op, "layer": "coll", "t": t,
                    "dt": 0.1, "bytes": 0, "peer": -1, "comm": 0}

        dumps = [
            jdump(0, [span("allreduce", 1.0)]),
            jdump(1, [span("allreduce", 1.4)]),
        ]
        series = [{"meta": {"pidx": 0, "clock_offset_s": 0.0},
                   "points": [_pt(0, 1.0, 0, "coll_ops", 3.0),
                              _pt(1, 2.0, 0, "coll_ops", 3.0)]}]
        text, data = doctor_mod.skew_report(dumps, series=series)
        assert "sampled rates" in text
        assert "coll/s" in text
        assert "0" in data["sampled_rates"]


# ---------------------------------------------------------------------------
# in-process fleet: HNP TAG_SERIES aggregation + FleetClient + rows
# ---------------------------------------------------------------------------

class TestFleetAggregation:
    def test_hnp_aggregates_and_fleet_client_queries(self):
        from ompi_release_tpu.obs.doctor import fleet_to_series_docs
        from ompi_release_tpu.runtime.coordinator import (
            HnpCoordinator, WorkerAgent)
        from ompi_release_tpu.tools.tpu_top import (FleetClient,
                                                    render_fleet)

        hnp = HnpCoordinator(4)
        agents, fc = [], None
        try:
            hnp.start_series_responder()
            for nid in (1, 2, 3):
                ag = WorkerAgent(nid, "127.0.0.1", hnp.port)
                agents.append(ag)
                ag.push_series(
                    [_pt(0, 1.0 + nid, 0, "coll_ops", float(nid))],
                    offset_s=0.25 * nid)
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                if len(hnp.fleet_series()["procs"]) == 3:
                    break
                time.sleep(0.02)
            fleet = hnp.fleet_series()
            assert set(fleet["procs"]) == {"0", "1", "2"}
            assert fleet["procs"]["1"]["clock_offset_s"] == 0.5
            assert fleet["procs"]["2"]["points"][0]["v"] == 3.0
            # the dashboard's live query path
            fc = FleetClient("127.0.0.1", hnp.port)
            queried = fc.query()
            assert set(queried["procs"]) == {"0", "1", "2"}
            table = render_fleet(fleet_to_series_docs(queried))
            rows = [ln for ln in table.splitlines()[1:] if ln.strip()]
            assert len(rows) == 3, table
        finally:
            if fc is not None:
                fc.close()
            for ag in agents:
                ag.ep.close()
            hnp.shutdown()

    def test_responder_survives_malformed_push(self):
        from ompi_release_tpu.runtime.coordinator import (
            HnpCoordinator, TAG_SERIES, WorkerAgent)

        hnp = HnpCoordinator(2)
        ag = None
        try:
            hnp.start_series_responder()
            ag = WorkerAgent(1, "127.0.0.1", hnp.port)
            # garbled push: non-numeric pidx must cost only this frame
            ag.ep.send(0, TAG_SERIES, json.dumps(
                {"pidx": "x", "points": [], "clock_offset_s": "y"}
            ).encode())
            ag.push_series([_pt(0, 1.0, -1, "x", 1.0)])
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                if hnp.fleet_series()["procs"]:
                    break
                time.sleep(0.02)
            assert "0" in hnp.fleet_series()["procs"], (
                "responder died on the malformed push")
        finally:
            if ag is not None:
                ag.ep.close()
            hnp.shutdown()

    def test_push_store_is_bounded(self):
        from ompi_release_tpu.runtime import coordinator as coord

        hnp = coord.HnpCoordinator(2)
        try:
            hnp.start_series_responder()
            big = [_pt(i, float(i), -1, "x", 1.0)
                   for i in range(coord.SERIES_KEEP + 100)]
            hnp._ingest_series(1, {"pidx": 0, "points": big})
            ent = hnp.fleet_series()["procs"]["0"]
            assert len(ent["points"]) == coord.SERIES_KEEP
            assert ent["points"][-1]["i"] == coord.SERIES_KEEP + 99
        finally:
            hnp.shutdown()


# ---------------------------------------------------------------------------
# tpu_top: row math + reconnect behaviour
# ---------------------------------------------------------------------------

class TestTpuTop:
    def test_summarize_points_rates_and_percentiles(self):
        from ompi_release_tpu.tools.tpu_top import summarize_points

        pts = []
        for k in range(6):  # one tick per second, 5 s window
            t = 100.0 + k
            pts.append(_pt(3 * k, t, 0, "coll_ops", 10.0))
            pts.append(_pt(3 * k + 1, t, 0, "coll_bytes", 2e6))
            pts.append(_pt(3 * k + 2, t, -1, "coll_allreduce_latency",
                           {"count": 10.0, "sum": 0.1,
                            "buckets": {0.015625: 10.0}}))
        s = summarize_points(pts, window_s=100.0)
        assert s["ops_s"] == pytest.approx(12.0)   # 60 ops over 5 s
        assert s["mb_s"] == pytest.approx(2.4)
        assert s["p50_ms"] == pytest.approx(11.71875)  # bucket midpoint
        assert s["cids"] == [0]

    def test_summarize_single_tick_has_no_rate(self):
        from ompi_release_tpu.tools.tpu_top import summarize_points

        pts = [_pt(0, 5.0, 0, "coll_ops", 10.0),
               _pt(1, 5.0, 0, "coll_bytes", 1e6)]
        s = summarize_points(pts)
        assert s["ops_s"] is None and s["mb_s"] is None

    def test_summarize_flags_stalls(self):
        from ompi_release_tpu.tools.tpu_top import (render_fleet,
                                                    summarize_points)

        pts = [_pt(0, 1.0, -1, "obs_stalls_detected", 2.0),
               _pt(1, 2.0, 0, "coll_ops", 1.0)]
        s = summarize_points(pts)
        assert s["stalls"] == 2
        table = render_fleet([{"meta": {"pidx": 4}, "points": pts}])
        assert "STALL×2" in table and " 4 " in table

    def test_render_fleet_marks_stale_procs(self):
        from ompi_release_tpu.tools.tpu_top import render_fleet

        docs = [{"meta": {"pidx": 0, "push_age_s": 120.0},
                 "points": [_pt(0, 1.0, 0, "coll_ops", 1.0)]}]
        table = render_fleet(docs, stale_after_s=6.0)
        assert "STALE" in table

    def test_metrics_loop_survives_dead_server(self, capsys):
        from ompi_release_tpu.tools.tpu_server import NameServer
        from ompi_release_tpu.tools.tpu_top import _metrics_loop

        srv = NameServer()
        port = srv.port
        srv.shutdown()  # nothing listens here anymore
        rc = _metrics_loop(f"127.0.0.1:{port}", delay=0.05,
                           iterations=1)
        out = capsys.readouterr().out
        assert "STALE" in out
        assert rc == 1  # never saw data — but no exception, no exit 2

    def test_metrics_loop_renders_live_server(self, capsys):
        from ompi_release_tpu.tools.tpu_server import NameServer
        from ompi_release_tpu.tools.tpu_top import _metrics_loop

        srv = NameServer()
        try:
            rc = _metrics_loop(f"127.0.0.1:{srv.port}", delay=0.05,
                               iterations=2)
        finally:
            srv.shutdown()
        out = capsys.readouterr().out
        assert rc == 0 and "ompitpu_" in out

    def test_compiled_fire_ratio_column(self):
        """comp% folds from the coll_compiled_cache_hits AGGREGATE
        deltas: sum = frozen-plan replays, count = fires through the
        plan layer."""
        from ompi_release_tpu.tools.tpu_top import (render_fleet,
                                                    summarize_points)

        pts = [_pt(0, 1.0, -1, "coll_compiled_cache_hits",
                   {"sum": 9.0, "count": 10.0}),
               _pt(1, 2.0, 0, "coll_ops", 10.0),
               _pt(2, 2.0, -1, "ledger_records", 9.0)]
        s = summarize_points(pts)
        assert s["compiled_frac"] == pytest.approx(0.9)
        assert s["ledger_records"] == 9
        assert s["dark"] is False
        table = render_fleet([{"meta": {"pidx": 0}, "points": pts}])
        assert "comp%" in table and " 90.0" in table
        assert "DARK" not in table
        # no plan traffic in the window: the column renders '-'
        s2 = summarize_points([_pt(0, 1.0, 0, "coll_ops", 1.0)])
        assert s2["compiled_frac"] is None

    def test_dark_rank_flagged(self):
        """A rank replaying frozen plans whose window shows NEITHER
        journal-derived coll_ops points NOR flight-recorder records is
        DARK: obs is on (the sampler only runs under obs) but the
        compiled hot path left no trace — the exact de-optimization
        regression the flight recorder exists to prevent."""
        from ompi_release_tpu.tools.tpu_top import (render_fleet,
                                                    summarize_points)

        pts = [_pt(0, 1.0, -1, "coll_compiled_cache_hits",
                   {"sum": 5.0, "count": 5.0}),
               _pt(1, 2.0, -1, "obs_sample_overhead_pad", 1.0)]
        s = summarize_points(pts)
        assert s["dark"] is True
        table = render_fleet([{"meta": {"pidx": 2}, "points": pts}])
        assert "DARK" in table
        # one ledger record in the window clears the flag
        lit = pts + [_pt(2, 2.0, -1, "ledger_records", 5.0)]
        assert summarize_points(lit)["dark"] is False

    def test_native_vs_staged_byte_split(self):
        """nwMB/s and nat% fold from the wire_native_bytes deltas
        against btl_dcn_staged_bytes (the whole staged-path volume,
        native included): staged_mb_s is the remainder that rode the
        portable copy path."""
        from ompi_release_tpu.tools.tpu_top import (render_fleet,
                                                    summarize_points)

        pts = [_pt(0, 1.0, -1, "wire_native_bytes", 3e6),
               _pt(1, 2.0, -1, "wire_native_bytes", 3e6),
               _pt(2, 2.0, -1, "btl_dcn_staged_bytes", 8e6),
               _pt(3, 2.0, -1, "wire_native_frames", 4.0),
               _pt(4, 2.0, -1, "wire_native_ring_stalls", 0.0),
               _pt(5, 2.0, -1, "wire_native_ring_hwm_frac", 0.25)]
        s = summarize_points(pts)
        assert s["native_mb_s"] == pytest.approx(6.0)  # 6e6 B / 1 s
        assert s["staged_mb_s"] == pytest.approx(2.0)
        assert s["native_frac"] == pytest.approx(0.75)
        assert s["dark_native"] is False
        table = render_fleet([{"meta": {"pidx": 0}, "points": pts}])
        assert "nwMB/s" in table and "nat%" in table
        assert "DARK-NATIVE" not in table
        # no wire traffic at all: the split renders as absent
        s2 = summarize_points([_pt(0, 1.0, 0, "coll_ops", 1.0)])
        assert s2["native_frac"] is None
        assert s2["dark_native"] is False

    def test_dark_native_rank_flagged(self):
        """Native frames moved but NONE of the three C-counter series
        (stalls / stall seconds / hwm) produced a window point: the
        stale-.so signature — fragments ride a library without the
        telemetry block. DARK-NATIVE, like DARK, is a heuristic flag
        on the fleet row."""
        from ompi_release_tpu.tools.tpu_top import (render_fleet,
                                                    summarize_points)

        pts = [_pt(0, 1.0, -1, "wire_native_frames", 2.0),
               _pt(1, 2.0, -1, "wire_native_bytes", 4e6)]
        s = summarize_points(pts)
        assert s["dark_native"] is True
        table = render_fleet([{"meta": {"pidx": 1}, "points": pts}])
        assert "DARK-NATIVE" in table
        # any one native telemetry point in the window clears it
        lit = pts + [_pt(2, 2.0, -1, "wire_native_ring_stalls", 1.0)]
        assert summarize_points(lit)["dark_native"] is False

    def test_server_series_rpc(self, obs_sampling):
        from ompi_release_tpu.tools.tpu_server import (NameClient,
                                                       NameServer)

        sampler_mod.SAMPLER.sample_once()
        srv = NameServer()
        client = None
        try:
            client = NameClient("127.0.0.1", srv.port)
            doc = client.series()
            assert "meta" in doc and isinstance(doc["points"], list)
            assert doc["points"], "series RPC returned an empty ring"
        finally:
            if client is not None:
                client.close()
            srv.shutdown()


# ---------------------------------------------------------------------------
# the real thing: 3-process job with the sampler armed
# ---------------------------------------------------------------------------

_SERIES_APP = r'''
import os, sys, time
sys.path.insert(0, %(repo)r)
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import ompi_release_tpu as mpi
from ompi_release_tpu.runtime.runtime import Runtime
from ompi_release_tpu import obs
from ompi_release_tpu.obs import sampler as sampler_mod

world = mpi.init()          # 3 procs x 2 devices
rt = Runtime.current()
me = rt.bootstrap["process_index"]
assert obs.enabled and sampler_mod.SAMPLER.running(), (
    obs.enabled, sampler_mod.SAMPLER.running())

x = np.stack([np.arange(128, dtype=np.float32) * (me + i + 1)
              for i in range(2)])
for _ in range(6):
    world.allreduce(x)
    time.sleep(0.12)        # span several sampler ticks
world.barrier()
print(f"SERIES-APP-OK {me}")
mpi.finalize()              # final tick + push + series dump happen here
'''


def test_3proc_job_fleet_series(tmp_path, capfd):
    """Acceptance: a 3-proc loopback job with obs_sample_interval set
    produces per-rank series dumps that merge clock-corrected, renders
    per-rank tpu_top rows, aggregates at the HNP, and annotates the
    doctor report with sampled rates."""
    dump_dir = tmp_path / "dumps"
    app = tmp_path / "series_app.py"
    app.write_text(_SERIES_APP % {"repo": REPO})
    job = Job(3, [sys.executable, str(app)],
              [("obs_enable", "1"),
               ("obs_sample_interval", "0.1"),
               ("obs_dump_dir", str(dump_dir))],
              heartbeat_s=0.5, miss_limit=10)
    rc = job.run(timeout_s=180)
    out = capfd.readouterr()
    assert rc == 0, out.out + out.err
    for me in (0, 1, 2):
        assert f"SERIES-APP-OK {me}" in out.out

    # -- per-rank series dumps, merged with clock correction ----------
    docs = doctor_mod.load_series_dir(str(dump_dir))
    assert len(docs) == 3, sorted(os.listdir(dump_dir))
    for d in docs:
        assert d["points"], f"rank {d['meta']['pidx']} series is empty"
        assert d["meta"]["clock_offset_s"] is not None, d["meta"]
    merged = doctor_mod.merge_series(docs)
    assert {p["pidx"] for p in merged} == {0, 1, 2}
    assert all("ts" in p for p in merged)
    # every rank saw collective activity in its per-cid series
    for pidx in (0, 1, 2):
        ops = sum(p["v"] for p in merged
                  if p["pidx"] == pidx and p["name"] == "coll_ops")
        assert ops >= 6, f"rank {pidx} coll_ops={ops}"

    # -- tpu_top renders per-rank rows from the dumps -----------------
    from ompi_release_tpu.tools.tpu_top import fleet_from_dir

    table = fleet_from_dir(str(dump_dir))
    rows = [ln for ln in table.splitlines()[1:] if ln.strip()]
    assert len(rows) == 3, table
    assert any("allgather" not in r and r.split()[2] != "0.0"
               for r in rows), f"no nonzero coll/s column:\n{table}"

    # -- HNP aggregated the pushed per-rank series --------------------
    fleet = job.hnp.fleet_series()
    assert set(fleet["procs"]) == {"0", "1", "2"}, fleet["procs"].keys()
    for pidx, ent in fleet["procs"].items():
        assert ent["points"], f"HNP holds no points for proc {pidx}"

    # -- report annotation consumes the merged series -----------------
    jdumps = doctor_mod.load_dir(str(dump_dir))
    text, data = doctor_mod.skew_report(jdumps, series=docs)
    assert "sampled rates" in text
    assert set(data["sampled_rates"]) == {"0", "1", "2"}

    # -- OpenMetrics exposition of the merged fleet -------------------
    for d in docs:
        om = export_mod.openmetrics_series(
            d["points"], pidx=int(d["meta"]["pidx"]),
            clock_offset_s=float(d["meta"]["clock_offset_s"]))
        assert om.endswith("# EOF\n")
        assert f'pidx="{int(d["meta"]["pidx"])}"' in om
