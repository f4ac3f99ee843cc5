"""The continuous fleet metrics plane.

Four layers under test:

- unit: sampler delta snapshots (scalar + histogram deltas, zero-delta
  suppression, per-communicator scoping from journal spans, ring
  bounds), histogram percentile math, OpenMetrics-with-timestamps
  exposition, and the series dump/merge clock correction;
- in-process fleet: a live HnpCoordinator TAG_SERIES responder
  aggregating three WorkerAgents' pushes, queried through tpu_top's
  FleetClient and rendered as per-rank rows;
- gate: tpu_bench_gate's noise-bound fit catching an injected 2x
  latency regression (and a halved bandwidth) in synthetic BENCH
  history while passing the repo's REAL history;
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
# the bench gate
# ---------------------------------------------------------------------------

def _round_file(path, lines):
    tail = "\n".join(json.dumps(ln) for ln in lines) + "\n"
    path.write_text(json.dumps({"n": 1, "rc": 0, "tail": tail}))
    return str(path)


def _bw(v):
    return {"metric": "allreduce_256MiB", "value": v, "unit": "GB/s",
            "vs_baseline": 1.0, "tier_label": "tpu"}


def _lat(v):
    return {"metric": "ring_4hop_latency", "value": v, "unit": "us/hop",
            "vs_baseline": None, "tier_label": "tpu"}


class TestBenchGate:
    def _history(self, tmp_path, n=4):
        vals = [680.0, 686.0, 678.0, 683.0]
        lats = [0.0085, 0.0088, 0.0082, 0.0086]
        return [_round_file(tmp_path / f"BENCH_r{k:02d}.json",
                            [_bw(vals[k]), _lat(lats[k])])
                for k in range(n)]

    def test_catches_2x_latency_regression(self, tmp_path):
        from ompi_release_tpu.tools import tpu_bench_gate as gate

        hist = self._history(tmp_path)
        cand = _round_file(tmp_path / "cand.json",
                           [_bw(681.0), _lat(0.017)])  # 2x latency
        rc = gate.main(hist + ["--candidate", cand])
        assert rc == 1
        verdict = gate.evaluate(
            [gate.parse_round_file(p) for p in hist],
            gate.parse_round_file(cand))
        regs = {r["metric"] for r in verdict["regressions"]}
        assert regs == {"ring_4hop_latency"}

    def test_catches_halved_bandwidth(self, tmp_path):
        from ompi_release_tpu.tools import tpu_bench_gate as gate

        hist = self._history(tmp_path)
        cand = _round_file(tmp_path / "cand.json",
                           [_bw(340.0), _lat(0.0085)])
        verdict = gate.evaluate(
            [gate.parse_round_file(p) for p in hist],
            gate.parse_round_file(cand))
        assert [r["metric"] for r in verdict["regressions"]] \
            == ["allreduce_256MiB"]

    def test_passes_within_noise(self, tmp_path):
        from ompi_release_tpu.tools import tpu_bench_gate as gate

        hist = self._history(tmp_path)
        cand = _round_file(tmp_path / "cand.json",
                           [_bw(655.0), _lat(0.0095)])  # ~4%/10% off
        rc = gate.main(hist + ["--candidate", cand])
        assert rc == 0

    def test_skips_unclean_and_tier_mismatched_lines(self, tmp_path):
        from ompi_release_tpu.tools import tpu_bench_gate as gate

        hist = [gate.parse_round_file(p)
                for p in self._history(tmp_path)]
        cand = [
            dict(_bw(100.0), unstable=True),          # flagged: skip
            dict(_bw(100.0), partial_rounds=2),       # salvage: skip
            {"metric": "allreduce_256MiB", "value": None, "unit":
             "GB/s", "vs_baseline": None},            # null: skip
            # cpu-tier line must NOT be judged against tpu history
            dict(_bw(3.0), tier_label="loopback-cpu"),
        ]
        verdict = gate.evaluate(hist, cand)
        assert verdict["regressions"] == []
        assert verdict["checked"] == 0

    def test_min_rounds_required(self, tmp_path):
        from ompi_release_tpu.tools import tpu_bench_gate as gate

        hist = [gate.parse_round_file(p)
                for p in self._history(tmp_path, n=2)]
        verdict = gate.evaluate(hist, [_bw(10.0)])
        assert verdict["checked"] == 0 and not verdict["regressions"]

    def test_zero_on_a_steady_history(self, tmp_path):
        """With no --candidate the newest round file is the candidate:
        a steady trajectory passes its own gate, and the same
        trajectory ending in a halved-bandwidth round does not."""
        from ompi_release_tpu.tools import tpu_bench_gate as gate

        hist = self._history(tmp_path)
        assert gate.main(hist) == 0
        bad = _round_file(tmp_path / "BENCH_r04.json",
                          [_bw(340.0), _lat(0.0085)])
        assert gate.main(hist + [bad]) == 1

    def test_legacy_backend_label_maps_to_cpu_tier(self):
        from ompi_release_tpu.tools.tpu_bench_gate import line_tier

        assert line_tier({"backend": "cpu"}) == "loopback-cpu"
        assert line_tier({}) == "tpu"
        assert line_tier({"tier_label": "loopback-cpu"}) \
            == "loopback-cpu"

    def test_sim_metrics_are_lower_better_in_their_own_tier(self,
                                                            tmp_path):
        """The fleet_scaling suite's sim_* lines: the sim_ prefix is
        registered lower-better (more schedule rounds / more bytes
        per rank / longer simulated makespan = regression), and the
        "sim" tier label keeps the deterministic simulator numbers
        out of the wall-clock tiers' noise fits."""
        from ompi_release_tpu.tools import tpu_bench_gate as gate

        def sim(metric, v, unit):
            return {"metric": metric, "value": v, "unit": unit,
                    "vs_baseline": None, "tier_label": "sim"}

        assert gate._direction("rounds", "sim_rd_rounds_p256") == -1
        assert gate._direction("bytes",
                               "sim_rab_bytes_per_rank_p256") == -1
        assert gate._direction("sim_ms",
                               "sim_allreduce_makespan_p256") == -1
        hist = [_round_file(
            tmp_path / f"BENCH_r{k:02d}.json",
            [sim("sim_rd_rounds_p256", 8, "rounds"),
             sim("sim_rab_bytes_per_rank_p256", 4080, "bytes")])
            for k in range(4)]
        # a schedule regression (log-round schedule degrading toward
        # linear: 8 -> 16 rounds) trips the gate...
        cand = _round_file(
            tmp_path / "cand.json",
            [sim("sim_rd_rounds_p256", 16, "rounds"),
             sim("sim_rab_bytes_per_rank_p256", 4080, "bytes")])
        rc = gate.main(hist + ["--candidate", str(cand)])
        assert rc == 1
        verdict = gate.evaluate(
            [gate.parse_round_file(p) for p in hist],
            gate.parse_round_file(cand))
        assert [r["metric"] for r in verdict["regressions"]] \
            == ["sim_rd_rounds_p256"]
        assert verdict["regressions"][0]["tier"] == "sim"
        # ...the identical deterministic replay does not...
        ok = _round_file(
            tmp_path / "ok.json",
            [sim("sim_rd_rounds_p256", 8, "rounds"),
             sim("sim_rab_bytes_per_rank_p256", 4080, "bytes")])
        assert gate.main(hist + ["--candidate", str(ok)]) == 0
        # ...and a same-named line in ANOTHER tier is never judged
        # against the sim history
        other = gate.evaluate(
            [gate.parse_round_file(p) for p in hist],
            [{"metric": "sim_rd_rounds_p256", "value": 99,
              "unit": "rounds", "vs_baseline": None,
              "tier_label": "loopback-cpu"}])
        assert other["checked"] == 0 and not other["regressions"]

    def test_steady_state_metric_directions(self, tmp_path):
        """The steady_state suite's lines: steady_* (per-op wall /
        Python-orchestration seconds) are registered lower-better,
        compiled_* (interpreted-vs-compiled orchestration speedups)
        higher-better — a slower orchestration OR a shrunk speedup is
        a regression, never an improvement."""
        from ompi_release_tpu.tools import tpu_bench_gate as gate

        assert gate._direction(
            "s", "steady_orch_allreduce_256KiB_compiled") == -1
        assert gate._direction(
            None, "steady_orch_allreduce_256KiB_interpreted") == -1
        assert gate._direction(
            "x_orchestration",
            "compiled_allreduce_256KiB_orch_speedup") == 1
        assert gate._direction(
            None, "compiled_spanning_allreduce_orch_speedup") == 1

        def ln(metric, v, unit):
            return {"metric": metric, "value": v, "unit": unit,
                    "vs_baseline": None, "tier_label": "loopback-cpu"}

        hist = [_round_file(
            tmp_path / f"BENCH_r{k:02d}.json",
            [ln("steady_orch_allreduce_256KiB_compiled",
                6.6e-5 + k * 1e-6, "s"),
             ln("compiled_allreduce_256KiB_orch_speedup",
                2.4 + 0.02 * k, "x_orchestration")])
            for k in range(4)]
        # orchestration doubling or the speedup collapsing trips it
        bad = _round_file(
            tmp_path / "cand.json",
            [ln("steady_orch_allreduce_256KiB_compiled", 2.0e-4, "s"),
             ln("compiled_allreduce_256KiB_orch_speedup", 1.0,
                "x_orchestration")])
        from ompi_release_tpu.tools import tpu_bench_gate as gate2

        verdict = gate2.evaluate(
            [gate2.parse_round_file(p) for p in hist],
            gate2.parse_round_file(bad))
        regressed = {r["metric"] for r in verdict["regressions"]}
        assert regressed == {
            "steady_orch_allreduce_256KiB_compiled",
            "compiled_allreduce_256KiB_orch_speedup"}
        # ...an in-band round passes
        ok = _round_file(
            tmp_path / "ok.json",
            [ln("steady_orch_allreduce_256KiB_compiled", 6.7e-5, "s"),
             ln("compiled_allreduce_256KiB_orch_speedup", 2.42,
                "x_orchestration")])
        assert gate2.main(hist + ["--candidate", str(ok)]) == 0

    def test_native_rounds_metric_directions(self, tmp_path):
        """The native_rounds suite's lines (frozen plans lowered into
        the C plan executor): steady_native_orch_* seconds are
        lower-better, compiled_native_* speedups (native over the
        interpreted PlannedXchg replay — the executor's acceptance
        factor) higher-better, and a drift in either direction trips
        the gate against the fitted history."""
        from ompi_release_tpu.tools import tpu_bench_gate as gate

        assert gate._direction(
            "s", "steady_native_orch_allreduce_256KiB") == -1
        assert gate._direction(
            None, "steady_native_orch_bcast_4KiB") == -1
        assert gate._direction(
            "x_orchestration",
            "compiled_native_allreduce_256KiB_orch_speedup") == 1
        assert gate._direction(
            None, "compiled_native_allgather_64KiB_orch_speedup") == 1

        def ln(metric, v, unit):
            return {"metric": metric, "value": v, "unit": unit,
                    "vs_baseline": None, "tier_label": "loopback-cpu"}

        hist = [_round_file(
            tmp_path / f"BENCH_r{k:02d}.json",
            [ln("steady_native_orch_allreduce_256KiB",
                3.1e-5 + k * 1e-6, "s"),
             ln("compiled_native_allreduce_256KiB_orch_speedup",
                2.6 + 0.02 * k, "x_orchestration")])
            for k in range(4)]
        bad = _round_file(
            tmp_path / "cand.json",
            [ln("steady_native_orch_allreduce_256KiB", 1.5e-4, "s"),
             ln("compiled_native_allreduce_256KiB_orch_speedup", 0.9,
                "x_orchestration")])
        verdict = gate.evaluate(
            [gate.parse_round_file(p) for p in hist],
            gate.parse_round_file(bad))
        regressed = {r["metric"] for r in verdict["regressions"]}
        assert regressed == {
            "steady_native_orch_allreduce_256KiB",
            "compiled_native_allreduce_256KiB_orch_speedup"}
        ok = _round_file(
            tmp_path / "ok.json",
            [ln("steady_native_orch_allreduce_256KiB", 3.2e-5, "s"),
             ln("compiled_native_allreduce_256KiB_orch_speedup",
                2.63, "x_orchestration")])
        assert gate.main(hist + ["--candidate", str(ok)]) == 0

    def test_rma_steady_metric_directions(self, tmp_path):
        """The rma_steady suite's lines (frozen RMA access plans,
        osc/plan): steady_rma_* / steady_shmem_* seconds are
        lower-better, the compiled_* orchestration and bulk-path
        speedups higher-better — slower epochs or a collapsed speedup
        regress, never improve."""
        from ompi_release_tpu.tools import tpu_bench_gate as gate

        assert gate._direction(
            "s", "steady_rma_fence_4KiB_planned") == -1
        assert gate._direction(
            None, "steady_rma_fence_4KiB_interpreted") == -1
        assert gate._direction(
            "x_orchestration",
            "compiled_rma_fence_4KiB_orch_speedup") == 1
        assert gate._direction(
            "s", "steady_shmem_put_4KiB_bulk") == -1
        assert gate._direction(
            "x_wall", "compiled_shmem_put_4KiB_bulk_speedup") == 1

        def ln(metric, v, unit):
            return {"metric": metric, "value": v, "unit": unit,
                    "vs_baseline": None, "tier_label": "loopback-cpu"}

        hist = [_round_file(
            tmp_path / f"BENCH_r{k:02d}.json",
            [ln("steady_rma_fence_4KiB_planned",
                7.0e-5 + k * 1e-6, "s"),
             ln("compiled_shmem_put_4KiB_bulk_speedup",
                1.8 + 0.02 * k, "x_wall")])
            for k in range(4)]
        # a doubled planned close or a collapsed bulk win trips it
        bad = _round_file(
            tmp_path / "cand.json",
            [ln("steady_rma_fence_4KiB_planned", 2.0e-4, "s"),
             ln("compiled_shmem_put_4KiB_bulk_speedup", 0.9,
                "x_wall")])
        verdict = gate.evaluate(
            [gate.parse_round_file(p) for p in hist],
            gate.parse_round_file(bad))
        regressed = {r["metric"] for r in verdict["regressions"]}
        assert regressed == {
            "steady_rma_fence_4KiB_planned",
            "compiled_shmem_put_4KiB_bulk_speedup"}
        # ...an in-band round passes
        ok = _round_file(
            tmp_path / "ok.json",
            [ln("steady_rma_fence_4KiB_planned", 7.1e-5, "s"),
             ln("compiled_shmem_put_4KiB_bulk_speedup", 1.83,
                "x_wall")])
        assert gate.main(hist + ["--candidate", str(ok)]) == 0

    def test_flight_recorder_metric_directions(self, tmp_path):
        """The flight-recorder lines: steady_obs_* (obs-ON compiled
        orchestration seconds and the obs-ON/obs-OFF overhead ratio —
        the "tracing never de-optimizes the hot path" budget) and
        ledger_* (bytes per fire record) are all lower-better, so the
        gate trips when enabling obs gets more expensive or the
        fixed-size record grows."""
        from ompi_release_tpu.tools import tpu_bench_gate as gate

        assert gate._direction(
            "s", "steady_obs_orch_spanning_allreduce_256KiB_compiled"
        ) == -1
        assert gate._direction(
            "ratio", "steady_obs_overhead_spanning_allreduce_256KiB"
        ) == -1
        assert gate._direction(
            "bytes", "ledger_record_bytes_spanning_allreduce_256KiB"
        ) == -1

        def ln(metric, v, unit):
            return {"metric": metric, "value": v, "unit": unit,
                    "vs_baseline": None, "tier_label": "loopback-cpu"}

        hist = [_round_file(
            tmp_path / f"BENCH_r{k:02d}.json",
            [ln("steady_obs_overhead_spanning_allreduce_256KiB",
                1.05 + 0.01 * k, "ratio"),
             ln("ledger_record_bytes_spanning_allreduce_256KiB",
                55, "bytes")]) for k in range(4)]
        # the obs-ON leg blowing past its 1.15x budget (tracing
        # de-optimized the hot path again) or a fattened record trips
        bad = _round_file(
            tmp_path / "cand.json",
            [ln("steady_obs_overhead_spanning_allreduce_256KiB",
                4.0, "ratio"),
             ln("ledger_record_bytes_spanning_allreduce_256KiB",
                2048, "bytes")])
        verdict = gate.evaluate(
            [gate.parse_round_file(p) for p in hist],
            gate.parse_round_file(bad))
        regressed = {r["metric"] for r in verdict["regressions"]}
        assert regressed == {
            "steady_obs_overhead_spanning_allreduce_256KiB",
            "ledger_record_bytes_spanning_allreduce_256KiB"}
        ok = _round_file(
            tmp_path / "ok.json",
            [ln("steady_obs_overhead_spanning_allreduce_256KiB",
                1.06, "ratio"),
             ln("ledger_record_bytes_spanning_allreduce_256KiB",
                55, "bytes")])
        assert gate.main(hist + ["--candidate", str(ok)]) == 0

    def test_native_wire_metric_directions(self, tmp_path):
        """The native_wire suite's lines: wire_native_p2p_* bandwidths
        (GB/s) are higher-better, while the wire_native_copies_per_mib
        witness (byte-path materializations per MiB shipped — 0.0 is
        the zero-copy acceptance target) is lower-better: a collapsed
        bandwidth OR arrays sneaking back onto the copy path must both
        trip the gate."""
        from ompi_release_tpu.tools import tpu_bench_gate as gate

        assert gate._direction("GB/s", "wire_native_p2p_256MiB") == 1
        assert gate._direction("GB/s", "wire_native_p2p_shm_256MiB") == 1
        assert gate._direction(
            "copies/MiB", "wire_native_copies_per_mib") == -1
        # ...and the prefix rule covers a unit-less round file too
        assert gate._direction(None, "wire_native_copies_per_mib") == -1

        def ln(metric, v, unit):
            return {"metric": metric, "value": v, "unit": unit,
                    "vs_baseline": None, "tier_label": "loopback-cpu"}

        hist = [_round_file(
            tmp_path / f"BENCH_r{k:02d}.json",
            [ln("wire_native_p2p_256MiB", 2.0 + 0.05 * k, "GB/s"),
             ln("wire_native_copies_per_mib", 0.0, "copies/MiB")])
            for k in range(4)]
        # bandwidth collapsing or copies reappearing trips the gate
        bad = _round_file(
            tmp_path / "cand.json",
            [ln("wire_native_p2p_256MiB", 0.4, "GB/s"),
             ln("wire_native_copies_per_mib", 3.0, "copies/MiB")])
        verdict = gate.evaluate(
            [gate.parse_round_file(p) for p in hist],
            gate.parse_round_file(bad))
        regressed = {r["metric"] for r in verdict["regressions"]}
        assert regressed == {"wire_native_p2p_256MiB",
                             "wire_native_copies_per_mib"}
        ok = _round_file(
            tmp_path / "ok.json",
            [ln("wire_native_p2p_256MiB", 2.1, "GB/s"),
             ln("wire_native_copies_per_mib", 0.0, "copies/MiB")])
        assert gate.main(hist + ["--candidate", str(ok)]) == 0

    def test_native_obs_metric_directions(self, tmp_path):
        """The native_obs suite's lines: the C counter-block series
        (stall count / cumulative stall seconds / ring occupancy HWM)
        are LOWER-better — growth is backpressure, not throughput —
        and native_obs_overhead_ratio (event-ring-on p2p wall over the
        counters-only baseline, acceptance budget 1.05) is lower-better
        via its metric prefix: its unit is 'ratio', NOT an 'x_*' unit,
        which would flip it higher-better in the unit table."""
        from ompi_release_tpu.tools import tpu_bench_gate as gate

        assert gate._direction(
            "stalls", "wire_native_stall_count") == -1
        assert gate._direction(
            "s", "wire_native_stall_seconds") == -1
        assert gate._direction(
            "frac", "wire_native_ring_hwm_frac") == -1
        assert gate._direction(
            "ratio", "native_obs_overhead_ratio") == -1
        assert gate._direction("s", "native_obs_counters_wall_s") == -1
        # the x_* unit family stays higher-better (speedups): the
        # overhead ratio must never be filed under it
        assert gate._direction("x_vs_staged", "anything") == 1

        def ln(metric, v, unit):
            return {"metric": metric, "value": v, "unit": unit,
                    "vs_baseline": None, "tier_label": "loopback-cpu"}

        hist = [_round_file(
            tmp_path / f"BENCH_r{k:02d}.json",
            [ln("native_obs_overhead_ratio", 1.01 + 0.002 * k,
                "ratio"),
             ln("wire_native_stall_seconds", 0.02, "s")])
            for k in range(4)]
        # observability cost ballooning or stalls growing both trip
        bad = _round_file(
            tmp_path / "cand.json",
            [ln("native_obs_overhead_ratio", 1.8, "ratio"),
             ln("wire_native_stall_seconds", 4.0, "s")])
        verdict = gate.evaluate(
            [gate.parse_round_file(p) for p in hist],
            gate.parse_round_file(bad))
        regressed = {r["metric"] for r in verdict["regressions"]}
        assert regressed == {"native_obs_overhead_ratio",
                             "wire_native_stall_seconds"}
        ok = _round_file(
            tmp_path / "ok.json",
            [ln("native_obs_overhead_ratio", 1.012, "ratio"),
             ln("wire_native_stall_seconds", 0.019, "s")])
        assert gate.main(hist + ["--candidate", str(ok)]) == 0

    def test_topo_metric_directions(self, tmp_path):
        """The fleet_scaling suite's topo_* lines (topology-aware
        schedule speedups over the flat ring: inter-host byte ratio,
        virtual-makespan ratio) are registered higher-better in the
        sim tier — a shrunk ratio means the torus/multiring advantage
        regressed, and it must trip the gate."""
        from ompi_release_tpu.tools import tpu_bench_gate as gate

        assert gate._direction(
            "x_inter_bytes", "topo_torus_inter_bytes_x_p1024") == 1
        assert gate._direction(
            "x_makespan", "topo_torus_makespan_x_p256") == 1
        assert gate._direction(
            None, "topo_multiring_makespan_x_p256") == 1
        # ...while the sim_torus_* observables stay lower-better
        assert gate._direction(
            "bytes", "sim_torus_inter_bytes_per_rank_p1024") == -1
        assert gate._direction("rounds", "sim_torus_rounds_p256") == -1

        def ln(metric, v, unit):
            return {"metric": metric, "value": v, "unit": unit,
                    "vs_baseline": None, "tier_label": "sim"}

        hist = [_round_file(
            tmp_path / f"BENCH_r{k:02d}.json",
            [ln("topo_torus_inter_bytes_x_p1024", 8.0, "x_inter_bytes")])
            for k in range(4)]
        bad = _round_file(
            tmp_path / "cand.json",
            [ln("topo_torus_inter_bytes_x_p1024", 1.0,
                "x_inter_bytes")])
        assert gate.main(hist + ["--candidate", str(bad)]) == 1
        ok = _round_file(
            tmp_path / "ok.json",
            [ln("topo_torus_inter_bytes_x_p1024", 8.0,
                "x_inter_bytes")])
        assert gate.main(hist + ["--candidate", str(ok)]) == 0

    def test_tenant_metric_directions(self, tmp_path):
        """The multi_tenant suite's tenant_* lines (service plane):
        latency-tenant p99s and the tenant_latency_isolation
        degradation ratio are registered lower-better in the sim tier
        — a GROWN isolation ratio means the weighted-fair wire lets a
        bulk tenant degrade a latency tenant further, and it must
        trip the gate at the sim tier's tight floor."""
        from ompi_release_tpu.tools import tpu_bench_gate as gate

        assert gate._direction(
            "p99_ratio", "tenant_latency_isolation_p256") == -1
        assert gate._direction(
            "sim_ms", "tenant_lat_contended_p99_p256") == -1
        assert gate._direction(
            None, "tenant_fifo_hol_ratio_p256") == -1

        def ln(metric, v, unit):
            return {"metric": metric, "value": v, "unit": unit,
                    "vs_baseline": None, "tier_label": "sim"}

        hist = [_round_file(
            tmp_path / f"BENCH_r{k:02d}.json",
            [ln("tenant_latency_isolation_p256", 1.22, "p99_ratio"),
             ln("tenant_lat_contended_p99_p256", 0.81, "sim_ms")])
            for k in range(4)]
        # fairness eroding (1.22 -> 1.9, still under the FIFO blowup)
        # IS a regression at the 2% sim floor...
        bad = _round_file(
            tmp_path / "cand.json",
            [ln("tenant_latency_isolation_p256", 1.9, "p99_ratio"),
             ln("tenant_lat_contended_p99_p256", 0.81, "sim_ms")])
        verdict = gate.evaluate(
            [gate.parse_round_file(p) for p in hist],
            gate.parse_round_file(bad))
        assert [r["metric"] for r in verdict["regressions"]] \
            == ["tenant_latency_isolation_p256"]
        assert verdict["regressions"][0]["tier"] == "sim"
        # ...the deterministic replay passes
        ok = _round_file(
            tmp_path / "ok.json",
            [ln("tenant_latency_isolation_p256", 1.22, "p99_ratio"),
             ln("tenant_lat_contended_p99_p256", 0.81, "sim_ms")])
        assert gate.main(hist + ["--candidate", str(ok)]) == 0

    def test_multi_tenant_bench_lines_are_gateable(self):
        """The bench suite itself (small P for speed): emits the
        solo/contended/FIFO p99 legs per QoS class + the isolation
        ratio, sim-tiered, with the in-band fairness bound holding."""
        import bench

        lines = bench._multi_tenant_micro_suite(sizes=(64,))
        by_metric = {l["metric"]: l for l in lines}
        iso = by_metric["tenant_latency_isolation_p64"]
        assert iso["tier_label"] == "sim"
        assert 1.0 <= iso["value"] <= iso["bound"] * 1.10
        assert by_metric["tenant_fifo_hol_ratio_p64"]["value"] \
            > 2.0 * iso["value"]
        solo = by_metric["tenant_lat_solo_p99_p64"]
        cont = by_metric["tenant_lat_contended_p99_p64"]
        assert solo["qos"] == "latency" and cont["value"] \
            >= solo["value"]
        assert by_metric["tenant_bulk_contended_p99_p64"]["qos"] \
            == "bulk"
        from ompi_release_tpu.tools import tpu_bench_gate as gate

        for l in lines:
            assert gate._direction(l["unit"], l["metric"]) == -1

    def test_sim_tier_band_is_tight_not_wall_clock_wobble(self,
                                                          tmp_path):
        """Sim lines are deterministic replays: the ±25% wall-clock
        noise floor must NOT apply, or a 8 -> 10 round schedule
        regression (+25%) would pass silently. The sim tier's floor
        is 2%."""
        from ompi_release_tpu.tools import tpu_bench_gate as gate

        def sim(v, tier="sim"):
            return {"metric": "sim_rd_rounds_p256", "value": v,
                    "unit": "rounds", "vs_baseline": None,
                    "tier_label": tier}

        hist = [[sim(8)] for _ in range(4)]      # bit-identical
        verdict = gate.evaluate(hist, [sim(10)])  # +25%: a real
        assert len(verdict["regressions"]) == 1   # regression, trips
        assert gate.evaluate(hist, [sim(8)])["regressions"] == []
        # the wall-clock tiers keep the wobble floor: +25% on a quiet
        # tpu-tier history stays inside the band
        thist = [[{"metric": "steps_used", "value": 8.0, "unit":
                   "steps", "vs_baseline": None, "tier_label": "tpu"}]
                 for _ in range(4)]
        tcand = [{"metric": "steps_used", "value": 9.9, "unit":
                  "steps", "vs_baseline": None, "tier_label": "tpu"}]
        assert gate.evaluate(thist, tcand)["regressions"] == []


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
