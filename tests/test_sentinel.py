"""Collective contract sentinel: cross-rank call-signature hashing,
desync forensics, and the typed ERR_COLL_MISMATCH.

Three layers under test:

- unit: chain determinism (two identical call streams fold to one
  chain value), the journal-event encode/parse round-trip, the call
  site fingerprint, the doctor's contract alignment on SYNTHETIC
  dumps (every divergence kind: mismatch, posting-order swap, missing
  participant, epoch skew, and the no-divergence case), the watchdog
  contributor, the incident-timeline rendering, the tpu_top DESYNC
  flag, and the bench-gate direction of ``sentinel_`` metrics;
- in-process: entry-point coverage — blocking, i-family, persistent
  ``start()`` — through a real (loopback-device) communicator;
- job: REAL 3-process tpurun desync injections. Inline mode
  (``obs_sentinel=2``): one rank posts a mismatched dtype and every
  process raises the typed ``ERR_COLL_MISMATCH`` within that round,
  naming the divergent process and both call sites — instead of
  hanging. Post-hoc mode (``obs_sentinel=1``): one rank swaps the
  posting order of two collectives, the job deadlocks, the watchdog
  postmortems capture the signature stream, and ``tpu-doctor
  contracts`` names the first divergent (cid, seq) and both call
  sites from the dumps alone.
"""

import json
import os
import sys

import numpy as np
import pytest

from ompi_release_tpu import obs
from ompi_release_tpu.mca import pvar as mca_pvar
from ompi_release_tpu.mca import var as mca_var
from ompi_release_tpu.obs import doctor as doctor_mod
from ompi_release_tpu.obs import sentinel
from ompi_release_tpu.runtime.state import JobState
from ompi_release_tpu.tools.tpurun import Job
from ompi_release_tpu.utils.errors import ErrorCode

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def armed_sentinel():
    """obs + sentinel post-hoc mode on, fully restored afterwards."""
    was_obs = obs.enabled
    sentinel._reset_for_tests()
    mca_var.set_value("obs_sentinel", 1)
    obs.enable()
    sentinel.refresh(True)
    yield
    mca_var.VARS.unset("obs_sentinel")
    sentinel._reset_for_tests()
    if not was_obs:
        obs.disable()
    else:
        sentinel.refresh(True)


# ---------------------------------------------------------------------------
# unit: chains, encoding, call sites
# ---------------------------------------------------------------------------


class TestChain:
    def test_disabled_is_inert(self):
        sentinel._reset_for_tests()
        assert not sentinel.enabled and sentinel.mode() == 0
        assert sentinel.record_sig(1, "allreduce") is None
        assert sentinel.chain_of(1) == 0

    def test_determinism_and_divergence(self, armed_sentinel):
        stream = (("allreduce", "sum", "float32", 64, -1),
                  ("barrier", "-", "-", 0, -1),
                  ("bcast", "-", "int32", 8, 2))
        for cid in (11, 12):
            for fam, op_n, dt, cnt, root in stream:
                sentinel.record_sig(cid, fam, op_n, dt, cnt, root,
                                    site="x.py:1")
        assert sentinel.chain_of(11) == sentinel.chain_of(12) != 0
        # call SITES differ -> chains still agree (sites are
        # forensics, not contract)
        sentinel.record_sig(11, "allreduce", "sum", "float32", 64, -1,
                            site="a.py:10")
        sentinel.record_sig(12, "allreduce", "sum", "float32", 64, -1,
                            site="b.py:99")
        assert sentinel.chain_of(11) == sentinel.chain_of(12)
        # any contract FIELD difference splits the chain
        sentinel.record_sig(11, "allreduce", "sum", "float32", 64, -1)
        sentinel.record_sig(12, "allreduce", "max", "float32", 64, -1)
        assert sentinel.chain_of(11) != sentinel.chain_of(12)

    def test_journal_event_roundtrip(self, armed_sentinel):
        sig = sentinel.record_sig(7, "reduce", "min", "float64", 32, 1,
                                  epoch=3, site="train.py:88")
        assert sig is not None and len(sig.digest()) == 16
        span = [s for s in obs.journal.snapshot()
                if s.layer == "sentinel"][-1]
        assert span.comm_id == 7 and span.peer == sig.seq
        parsed = sentinel.parse_op(span.op)
        assert parsed == {"canon": "reduce|min|float64|32|1",
                          "family": "reduce", "epoch": 3,
                          "site": "train.py:88"}
        assert sentinel.parse_op("allreduce") is None
        assert sentinel.parse_op("a|b|c|d|e|f|g") is None  # no e<int>

    def test_call_site_is_this_file(self, armed_sentinel):
        sig = sentinel.record_sig(8, "allreduce")
        assert sig.site.startswith("test_sentinel.py:"), sig.site

    def test_ring_is_bounded(self, armed_sentinel):
        mca_var.set_value("obs_sentinel_ring", 4)
        try:
            for i in range(10):
                sentinel.record_sig(9, "allreduce", "sum", "f32", i, -1)
            snap = sentinel.chains_snapshot()["comms"]["9"]
            assert snap["next_seq"] == 10
            assert len(snap["last"]) == 4
            assert snap["last"][-1]["seq"] == 9
        finally:
            mca_var.VARS.unset("obs_sentinel_ring")

    def test_watchdog_contributor_registered(self, armed_sentinel):
        from ompi_release_tpu.obs import watchdog

        sentinel.record_sig(13, "allreduce", "sum", "float32", 4, -1)
        doc = watchdog._contributors["sentinel"]()
        assert doc["mode"] == 1
        assert doc["comms"]["13"]["next_seq"] == 1

    def test_describe_strips_local_rank_axis(self, armed_sentinel):
        class FakeComm:
            cid = 21
            spans_processes = False

        c = FakeComm()
        sig = sentinel.note(c, "allreduce",
                            (np.ones((2, 128), np.float32),), {})
        # per-rank count, not the stacked driver-mode buffer
        assert sig.canon == "allreduce|-|float32|128|-1", sig.canon

    def test_note_skips_internal_comms(self, armed_sentinel):
        class Internal:
            cid = -3
            spans_processes = False

        assert sentinel.note(Internal(), "allreduce") is None


# ---------------------------------------------------------------------------
# unit: doctor contract alignment on synthetic dumps
# ---------------------------------------------------------------------------


def _sig_span(cid, seq, canon, site, epoch=0):
    return {"seq": seq, "op": sentinel.encode_op(canon, epoch, site),
            "layer": "sentinel", "t": float(seq), "dt": 0.0,
            "bytes": 0, "peer": seq, "comm": cid}


def _dump(pidx, spans):
    return {"meta": {"pidx": pidx, "rank_offset": pidx, "local_size": 1,
                     "clock_offset_s": 0.0},
            "spans": spans}


AR = "allreduce|sum|float32|64|-1"
BC = "bcast|-|float32|64|0"
BAR = "barrier|-|-|0|-1"


class TestContractReport:
    def test_aligned_streams_report_clean(self):
        dumps = [_dump(p, [_sig_span(0, s, c, f"dp.py:{10 + s}")
                           for s, c in enumerate((AR, BAR, AR))])
                 for p in range(3)]
        text, data = doctor_mod.contract_report(dumps)
        assert data["divergences"] == 0
        assert "no divergence" in text and "DESYNC" not in text

    def test_signature_mismatch_names_rank_seq_and_sites(self):
        dumps = [
            _dump(0, [_sig_span(0, 0, AR, "dp.py:203"),
                      _sig_span(0, 1, AR, "dp.py:203")]),
            _dump(1, [_sig_span(0, 0, AR, "dp.py:203"),
                      _sig_span(0, 1, AR, "dp.py:203")]),
            _dump(2, [_sig_span(0, 0, AR, "dp.py:203"),
                      _sig_span(0, 1, BC, "train.py:88")]),
        ]
        text, data = doctor_mod.contract_report(dumps)
        div = data["comms"]["0"]["divergence"]
        assert div["kind"] == "signature_mismatch"
        assert div["seq"] == 1 and div["divergent"] == 2
        assert "DESYNC at seq 1" in text
        assert "proc 2 posted " + BC in text
        assert "train.py:88" in text and "dp.py:203" in text

    def test_mismatch_attributes_culprit_by_majority(self):
        # proc 0 ITSELF is the desynced rank: the majority canon is
        # the expected one, so the report must blame proc 0, not the
        # agreeing procs that happen to sort after it
        dumps = [
            _dump(0, [_sig_span(0, 0, BC, "train.py:88")]),
            _dump(1, [_sig_span(0, 0, AR, "dp.py:203")]),
            _dump(2, [_sig_span(0, 0, AR, "dp.py:203")]),
        ]
        text, data = doctor_mod.contract_report(dumps)
        div = data["comms"]["0"]["divergence"]
        assert div["divergent"] == 0 and div["agreeing"] == [1, 2]
        assert div["expected"]["canon"] == AR
        assert div["actual"]["canon"] == BC
        assert "proc 0 posted " + BC in text

    def test_chain_cleared_on_comm_free_and_cid_reuse(
            self, armed_sentinel):
        sentinel.record_sig(33, "allreduce", "sum", "float32", 8, -1)
        assert sentinel.chain_of(33) != 0
        sentinel.clear_chain(33)
        assert sentinel.chain_of(33) == 0
        assert "33" not in sentinel.chains_snapshot()["comms"]
        # and through the real comm lifecycle: free() closes the
        # comm's contract story
        import ompi_release_tpu as mpi

        world = mpi.init()
        sub = world.dup(name="sentinel_free_probe")
        x = np.ones((world.size, 4), np.float32)
        sub.allreduce(x)
        assert sentinel.chain_of(sub.cid) != 0
        sub.free()
        assert sentinel.chain_of(sub.cid) == 0

    def test_posting_order_swap_classified(self):
        dumps = [
            _dump(0, [_sig_span(0, 0, AR, "a.py:1"),
                      _sig_span(0, 1, BAR, "a.py:2"),
                      _sig_span(0, 2, AR, "a.py:3")]),
            _dump(1, [_sig_span(0, 0, AR, "a.py:1"),
                      _sig_span(0, 2, BAR, "b.py:9"),
                      _sig_span(0, 1, AR, "b.py:8")]),
        ]
        text, data = doctor_mod.contract_report(dumps)
        div = data["comms"]["0"]["divergence"]
        assert div["kind"] == "posting_order_swap" and div["seq"] == 1
        assert "posting-order swap" in text

    def test_missing_participant_names_last_posted(self):
        dumps = [
            _dump(0, [_sig_span(0, s, AR, "a.py:1") for s in range(4)]),
            _dump(1, [_sig_span(0, s, AR, "a.py:1") for s in range(4)]),
            _dump(2, [_sig_span(0, s, AR, "a.py:1") for s in range(2)]),
        ]
        text, data = doctor_mod.contract_report(dumps)
        div = data["comms"]["0"]["divergence"]
        assert div["kind"] == "missing_participant"
        assert div["seq"] == 2 and div["missing"] == [2]
        assert "never posted" in text

    def test_ring_wrap_is_not_a_divergence(self):
        # proc 1's journal wrapped: its window starts later — the
        # overlap agrees, so no desync may be reported
        dumps = [
            _dump(0, [_sig_span(0, s, AR, "a.py:1") for s in range(6)]),
            _dump(1, [_sig_span(0, s, AR, "a.py:1")
                      for s in range(3, 6)]),
        ]
        _, data = doctor_mod.contract_report(dumps)
        assert data["divergences"] == 0

    def test_epoch_skew_detected(self):
        dumps = [
            _dump(0, [_sig_span(0, 0, AR, "a.py:1", epoch=2)]),
            _dump(1, [_sig_span(0, 0, AR, "a.py:1", epoch=1)]),
        ]
        text, data = doctor_mod.contract_report(dumps)
        div = data["comms"]["0"]["divergence"]
        assert div["kind"] == "epoch_skew" and div["divergent"] == 1
        assert "epoch skew" in text

    def test_transient_epoch_skew_is_not_a_divergence(self):
        # FT notices propagate asynchronously: a one-round epoch lag
        # that converges at the next common seq is legal, not a desync
        dumps = [
            _dump(0, [_sig_span(0, 0, AR, "a.py:1", epoch=1),
                      _sig_span(0, 1, AR, "a.py:1", epoch=1)]),
            _dump(1, [_sig_span(0, 0, AR, "a.py:1", epoch=0),
                      _sig_span(0, 1, AR, "a.py:1", epoch=1)]),
        ]
        _, data = doctor_mod.contract_report(dumps)
        assert data["divergences"] == 0

    def test_epoch_skew_expected_comes_from_fresh_proc(self):
        # the stale proc may be the lowest-indexed one: expected must
        # still carry the FRESH side's record, never the culprit's own
        dumps = [
            _dump(0, [_sig_span(0, 0, AR, "a.py:1", epoch=1)]),
            _dump(1, [_sig_span(0, 0, AR, "b.py:2", epoch=2)]),
        ]
        _, data = doctor_mod.contract_report(dumps)
        div = data["comms"]["0"]["divergence"]
        assert div["kind"] == "epoch_skew" and div["divergent"] == 0
        assert div["expected"]["epoch"] == 2
        assert div["expected"]["site"] == "b.py:2"
        assert div["actual"]["site"] == "a.py:1"

    def test_finalize_meta_ring_feeds_alignment(self):
        # journal wrapped past every sentinel span before finalize:
        # the rings in meta["sentinel"] must still carry the desync
        def meta_dump(pidx, canon, site):
            d = _dump(pidx, [])
            d["meta"]["sentinel"] = {"mode": 1, "comms": {"0": {
                "next_seq": 1, "chain": "ab",
                "last": [{"seq": 0, "canon": canon, "epoch": 0,
                          "site": site, "sig": 1}]}}}
            return d

        dumps = [meta_dump(0, AR, "dp.py:203"),
                 meta_dump(1, BC, "train.py:88")]
        text, data = doctor_mod.contract_report(dumps)
        assert data["divergences"] == 1
        assert "train.py:88" in text and "dp.py:203" in text

    def test_postmortem_ring_feeds_alignment(self, tmp_path):
        # no journals at all: only postmortems with the sentinel
        # contributor ring — alignment still names the desync
        for p, canon, site in ((0, AR, "dp.py:203"),
                               (1, BC, "train.py:88")):
            pm = {"reason": "stall", "time_unix": 1.0,
                  "rank": {"pidx": p, "pid": 100 + p,
                           "rank_offset": p, "local_size": 1},
                  "clock": {"offset_s": 0.0},
                  "journal_tail": [],
                  "sentinel": {"mode": 1, "comms": {"0": {
                      "next_seq": 1, "chain": "ab",
                      "last": [{"seq": 0, "canon": canon, "epoch": 0,
                                "site": site, "sig": 1}]}}}}
            (tmp_path / f"postmortem-p{p}-stall-1.json").write_text(
                json.dumps(pm))
        dumps = doctor_mod.load_dir(str(tmp_path))
        text, data = doctor_mod.contract_report(
            dumps, directory=str(tmp_path))
        assert data["divergences"] == 1
        assert "train.py:88" in text and "dp.py:203" in text


# ---------------------------------------------------------------------------
# unit: incident timeline + tpu_top flag + gate direction
# ---------------------------------------------------------------------------


def test_incident_timeline_renders_ft_events():
    spans0 = [
        {"seq": 0, "op": "ft_failure", "layer": "ft", "t": 10.0,
         "dt": 0.0, "bytes": 0, "peer": 2, "comm": 1},
        {"seq": 1, "op": "ft_revoke", "layer": "ft", "t": 10.1,
         "dt": 0.0, "bytes": 0, "peer": 1, "comm": 5},
        {"seq": 2, "op": "ft_recovery", "layer": "ft", "t": 10.2,
         "dt": 0.85, "bytes": 0, "peer": 3, "comm": 524288},
        {"seq": 3, "op": "allreduce", "layer": "coll", "t": 11.0,
         "dt": 0.01, "bytes": 64, "peer": -1, "comm": 0},
    ]
    spans1 = [{"seq": 0, "op": "allreduce", "layer": "coll", "t": 11.0,
               "dt": 0.02, "bytes": 64, "peer": -1, "comm": 0}]
    dumps = [_dump(0, spans0), _dump(1, spans1)]
    evs = doctor_mod.incident_timeline(dumps)
    assert [e["op"] for e in evs] == ["ft_failure", "ft_revoke",
                                     "ft_recovery"]
    assert evs[0]["failed_pidx"] == 2 and evs[0]["epoch"] == 1
    assert evs[2]["duration_s"] == pytest.approx(0.85)
    # the report folds the timeline in as its incident section
    text, data = doctor_mod.skew_report(dumps)
    assert "incident timeline" in text
    assert "learned process 2 FAILED" in text
    assert "revoked cid 5" in text
    assert "recovered in 0.850s" in text
    assert len(data["incidents"]) == 3


def test_skew_report_without_incidents_has_no_section():
    dumps = [_dump(p, [{"seq": 0, "op": "allreduce", "layer": "coll",
                        "t": 1.0 + p, "dt": 0.01, "bytes": 4,
                        "peer": -1, "comm": 0}]) for p in range(2)]
    text, data = doctor_mod.skew_report(dumps)
    assert "incident timeline" not in text
    assert data["incidents"] == []


def test_tpu_top_desync_flag():
    from ompi_release_tpu.tools.tpu_top import render_fleet, \
        summarize_points

    pts = [{"i": 0, "t": 1.0, "cid": -1, "name": "sentinel_mismatches",
            "v": 2.0},
           {"i": 1, "t": 2.0, "cid": 0, "name": "coll_ops", "v": 5.0}]
    s = summarize_points(pts)
    assert s["desyncs"] == 2
    table = render_fleet([{"meta": {"pidx": 0, "rank_offset": 0,
                                    "local_size": 1}, "points": pts}])
    assert "DESYNC×2" in table
    # and absent when the sentinel saw nothing
    assert "DESYNC" not in render_fleet(
        [{"meta": {"pidx": 0}, "points": pts[1:]}])


def test_inline_frame_template_renders_byte_identical_payload():
    """The planned path's precomposed ctl frame: for any (seq, epoch)
    the template's render must be byte-for-byte what the interpreted
    inline check serializes — digest + json.dumps(descriptor()) — or
    planned and interpreted ranks would flag each other as desynced."""
    canon = sentinel.make_canon("allreduce", "sum", "float32", 512, -1)
    site = "app.py:42"
    tpl = sentinel.InlineFrameTemplate(canon, site)
    for seq, epoch, chain_prev in ((0, 0, 0), (7, 2, 12345),
                                   (2**31, 9, 2**60)):
        sig = sentinel.CallSig(3, seq, "allreduce", canon, epoch,
                               site, chain_prev)
        want = sig.digest() + json.dumps(sig.descriptor()).encode()
        assert tpl.render(sig) == want
    # a template is keyed by (canon, site): rendering a different
    # call stream through it would ship the wrong canon — the cache
    # in coll/nbc keys on exactly this pair
    assert tpl.key == (canon, site)


def test_err_coll_mismatch_is_a_distinct_class():
    assert ErrorCode.ERR_COLL_MISMATCH.value == 77
    assert ErrorCode.ERR_COLL_MISMATCH != ErrorCode.ERR_PROC_FAILED


# ---------------------------------------------------------------------------
# in-process: entry-point coverage through a real communicator
# ---------------------------------------------------------------------------


def test_entry_points_cover_blocking_ifamily_persistent(armed_sentinel):
    import ompi_release_tpu as mpi

    world = mpi.init()
    h0 = float(mca_pvar.PVARS.lookup("sentinel_ops_hashed").read())
    x = np.ones((world.size, 8), np.float32)
    world.allreduce(x)                      # blocking
    world.iallreduce(x).wait()              # i-family
    world.ibarrier().wait()                 # native async-dispatch
    req = world.allreduce_init(x)           # persistent: 2 starts
    req.start(); req.wait()
    req.start(); req.wait()
    hashed = float(
        mca_pvar.PVARS.lookup("sentinel_ops_hashed").read()) - h0
    assert hashed == 5.0, hashed
    sigs = [s for s in obs.journal.snapshot() if s.layer == "sentinel"
            and s.comm_id == world.cid]
    assert len(sigs) >= 5
    seqs = [s.peer for s in sigs[-5:]]
    assert seqs == sorted(seqs), seqs  # strict posting order
    parsed = sentinel.parse_op(sigs[-1].op)
    assert parsed["canon"] == "allreduce|sum|float32|8|-1"
    assert parsed["site"].startswith("test_sentinel.py:")


# ---------------------------------------------------------------------------
# job: REAL 3-process desync injections
# ---------------------------------------------------------------------------

_INLINE_APP = r'''
import os, sys
sys.path.insert(0, %(repo)r)
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import ompi_release_tpu as mpi
from ompi_release_tpu.runtime.runtime import Runtime
from ompi_release_tpu import obs
from ompi_release_tpu.obs import sentinel
from ompi_release_tpu.utils.errors import ErrorCode, MPIError

world = mpi.init()
rt = Runtime.current()
me = rt.bootstrap["process_index"]
assert obs.enabled and sentinel.enabled and sentinel.mode() == 2

x32 = np.ones((1, 64), np.float32)
x64 = np.ones((1, 64), np.float64)

# a matching round first: the contract holds, nothing raises
got = np.asarray(world.allreduce(x32))
np.testing.assert_allclose(got[0], 3.0)

try:
    if me == 1:
        world.allreduce(x64)   # the desync: float64 where others f32
    else:
        world.allreduce(x32)
    print(f"SENTINEL-NO-RAISE {me}", flush=True)
except MPIError as e:
    assert e.code == ErrorCode.ERR_COLL_MISMATCH, e
    print(f"SENTINEL-MISMATCH-OK {me} :: {e}", flush=True)

# the typed error fired BEFORE any payload traffic: the comm is still
# coherent, and the next round's signatures line up again
got = np.asarray(world.allreduce(x32))
np.testing.assert_allclose(got[0], 3.0)
world.barrier()
print(f"SENTINEL-APP-DONE {me}", flush=True)
mpi.finalize()
'''


def test_inline_mismatch_raises_typed_error_in_round(tmp_path, capfd):
    """obs_sentinel=2: rank 1 posts a float64 allreduce where ranks
    0/2 posted float32 — EVERY process raises ERR_COLL_MISMATCH
    within that round (no hang, no watchdog needed), the message
    names the divergent process and both call sites, and the comm
    stays usable for the next (matching) round."""
    app = tmp_path / "mismatch_app.py"
    app.write_text(_INLINE_APP % {"repo": REPO})
    job = Job(3, [sys.executable, str(app)],
              [("obs_enable", "1"), ("obs_sentinel", "2")],
              heartbeat_s=0.5, miss_limit=10)
    rc = job.run(timeout_s=180)
    out = capfd.readouterr()
    assert rc == 0, out.out + out.err
    assert job.job_state.visited(JobState.TERMINATED)
    for me in (0, 1, 2):
        assert f"SENTINEL-MISMATCH-OK {me}" in out.out, out.out
        assert f"SENTINEL-APP-DONE {me}" in out.out
    assert "SENTINEL-NO-RAISE" not in out.out
    # the typed error names the contract fields and both call sites
    mis = [ln for ln in out.out.splitlines()
           if "SENTINEL-MISMATCH-OK 0" in ln]
    assert mis and "ERR_COLL_MISMATCH" in mis[0]
    assert "process 1" in mis[0]
    assert "float64" in mis[0] and "float32" in mis[0]
    assert mis[0].count("mismatch_app.py:") == 2, mis[0]


_SWAP_APP = r'''
import os, sys, threading, time
sys.path.insert(0, %(repo)r)
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import ompi_release_tpu as mpi
from ompi_release_tpu.runtime.runtime import Runtime
from ompi_release_tpu import obs
from ompi_release_tpu.obs import sentinel
from ompi_release_tpu.obs import watchdog as wd

world = mpi.init()
rt = Runtime.current()
me = rt.bootstrap["process_index"]
assert obs.enabled and wd.enabled and sentinel.mode() == 1

def bail():
    # long enough for every rank's stall watchdog to dump, then die:
    # the desynced job can never finish on its own
    time.sleep(%(bail_s)s)
    print(f"SWAP-APP-BAIL {me}", flush=True)
    os._exit(9)

threading.Thread(target=bail, daemon=True).start()

x = np.ones((1, 32), np.float32)
world.allreduce(x)            # a healthy aligned round first

if me == 2:
    r1 = world.iallreduce(x)  # the swap: allreduce posted BEFORE the
    r2 = world.ibarrier()     # barrier ranks 0/1 posted first
else:
    r1 = world.ibarrier()
    r2 = world.iallreduce(x)
r1.wait()                     # deadlock: barrier (ctl) vs allreduce
r2.wait()                     # (coll channel) can never pair up
print(f"SWAP-APP-UNEXPECTED-FINISH {me}", flush=True)
mpi.finalize()
'''


def test_posting_order_swap_postmortem_contracts(tmp_path, capfd):
    """obs_sentinel=1 on a hung mismatched run: rank 2 swaps the
    posting order of an ibarrier/iallreduce pair, the job deadlocks,
    the watchdog postmortems capture each rank's signature stream,
    and ``tpu-doctor contracts`` over the postmortem dir alone names
    the first divergent (cid, seq), classifies the swap, and shows
    both call sites."""
    pm_dir = tmp_path / "pm"
    app = tmp_path / "swap_app.py"
    app.write_text(_SWAP_APP % {"repo": REPO, "bail_s": 8.0})
    job = Job(3, [sys.executable, str(app)],
              [("obs_enable", "1"), ("obs_sentinel", "1"),
               ("obs_stall_timeout", "1.5"),
               ("obs_postmortem_dir", str(pm_dir))],
              heartbeat_s=0.5, miss_limit=20)
    rc = job.run(timeout_s=180)
    out = capfd.readouterr()
    assert rc != 0, "a desynced job must not exit clean"
    assert "SWAP-APP-UNEXPECTED-FINISH" not in out.out
    pms = sorted(pm_dir.glob("postmortem-*.json"))
    assert pms, f"no postmortems in {pm_dir}: {out.out}"

    dumps = doctor_mod.load_dir(str(pm_dir))
    text, data = doctor_mod.contract_report(dumps,
                                            directory=str(pm_dir))
    assert data["divergences"] >= 1, text
    div = next(c["divergence"] for c in data["comms"].values()
               if c["divergence"])
    assert div["kind"] == "posting_order_swap", (div, text)
    assert div["divergent"] == 2
    assert "DESYNC at seq" in text
    assert "posting-order swap" in text
    # both call sites, straight out of the postmortem dumps
    assert text.count("swap_app.py:") == 2, text
    exp, act = div["expected"], div["actual"]
    assert exp["canon"].startswith("barrier|")
    assert act["canon"].startswith("allreduce|")
    assert exp["site"] != act["site"]

    # the CLI subcommand exits 3 on divergence
    from ompi_release_tpu.tools.tpu_doctor import main as doctor_main

    assert doctor_main(["contracts", str(pm_dir)]) == 3
    cli_out = capfd.readouterr().out
    assert "posting-order swap" in cli_out
