"""Idle gaps by what the host was doing: an idle moment goes to the
innermost ``ompi.*`` span that covers it, named after the benchmark's
call span around it; else to the call span; else to "between calls". On
hand-made intervals and on the trace recorded on the chip (which holds
no library spans: every gap is a call's, as before)."""

import os

import pytest

from perfbench import manifest, trace

RECORDED = os.path.join(manifest.HERE, "testdata", "osu_ici4.large.xplane.pb")
MS = 1_000_000  # ns
CALL = trace.CALL + "allgather:8"


def test_the_innermost_library_span_that_covers_an_idle_moment_gets_it():
    spans = [(0, 100 * MS, trace.SLICE),
             (10 * MS, 90 * MS, CALL),
             (12 * MS, 88 * MS, "ompi.coll.call"),
             (20 * MS, 80 * MS, "ompi.nbc.wait"),
             (20 * MS, 30 * MS, "ompi.hier.d2h"),      # opens with its parent
             (40 * MS, 70 * MS, "ompi.plan.native_fire"),
             (50 * MS, 55 * MS, "ompi.wire.stash"),    # another thread, inside
             (45 * MS, 46 * MS, "PjRtCApiClient::BufferFromHostBuffer")]
    pieces = trace.owners(spans)
    assert pieces == sorted(pieces)
    assert all(a[1] <= b[0] for a, b in zip(pieces, pieces[1:]))
    assert [name for _, _, name in pieces] == [
        CALL, CALL + ":ompi.coll.call", CALL + ":ompi.hier.d2h",
        CALL + ":ompi.nbc.wait", CALL + ":ompi.plan.native_fire",
        CALL + ":ompi.wire.stash", CALL + ":ompi.plan.native_fire",
        CALL + ":ompi.nbc.wait", CALL + ":ompi.coll.call", CALL]
    # the device runs 22..26 ms and 60..62 ms: the rest of the slice is idle
    devices = {0: [(22 * MS, 26 * MS, "%copy.1 = f32[2]{0} copy(x)"),
                   (60 * MS, 62 * MS, "%copy.1 = f32[2]{0} copy(x)")]}
    red = trace.reduce(devices, spans)
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert gaps == {
        "between calls": pytest.approx(0.020),                    # 0..10, 90..100
        CALL: pytest.approx(0.004),                               # 10..12, 88..90
        CALL + ":ompi.coll.call": pytest.approx(0.016),           # 12..20, 80..88
        CALL + ":ompi.hier.d2h": pytest.approx(0.006),            # 20..22, 26..30
        CALL + ":ompi.nbc.wait": pytest.approx(0.020),            # 30..40, 70..80
        CALL + ":ompi.plan.native_fire": pytest.approx(0.023),    # 40..50, 55..60, 62..70
        CALL + ":ompi.wire.stash": pytest.approx(0.005)}          # 50..55
    assert sum(gaps.values()) + red["busy_s"] == pytest.approx(red["window_s"])
    assert red["calls_in_slice"] == 1


def test_a_library_span_outside_every_call_goes_by_its_own_name():
    spans = [(0, 10 * MS, "ompi.coll.call"), (20 * MS, 30 * MS, CALL)]
    assert trace.owners(spans) == [(0, 10 * MS, "ompi.coll.call"),
                                   (20 * MS, 30 * MS, CALL)]


def test_spans_that_touch_make_one_piece_and_an_empty_span_makes_none():
    spans = [(0, 10 * MS, CALL), (2 * MS, 5 * MS, "ompi.hier.h2d"),
             (5 * MS, 8 * MS, "ompi.hier.h2d"), (9 * MS, 9 * MS, "ompi.hier.d2h")]
    assert trace.owners(spans) == [
        (0, 2 * MS, CALL), (2 * MS, 8 * MS, CALL + ":ompi.hier.h2d"),
        (8 * MS, 10 * MS, CALL)]


def test_the_trace_recorded_on_the_chip_has_no_library_spans():
    """PR 24's trace predates the library's spans: the pieces are the
    benchmark's forty call spans and every gap is a call's."""
    devices, spans, _ = trace.load(RECORDED)
    calls = sorted(iv for iv in spans if iv[2].startswith(trace.CALL))
    assert trace.owners(spans) == calls and len(calls) == 40
    gaps = dict(trace.reduce(devices, spans)["breakdown"]["idle_gaps"])
    assert gaps and all(k.startswith(trace.CALL) and ":ompi." not in k
                        or k == "between calls" for k in gaps)
