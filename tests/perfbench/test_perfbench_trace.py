"""The trace reduction: union not sum, clipping to the slice, the busiest
single device, 0 < busy_s <= window_s, idle gaps by the benchmark's own
spans; on hand-made intervals and on a trace recorded on the chip."""

import os

import pytest

from perfbench import manifest, trace, worker

RECORDED = os.path.join(manifest.HERE, "testdata", "osu_ici4.large.xplane.pb")
MS = 1_000_000  # ns


def test_busy_is_a_union_clipped_to_the_slice_on_the_busiest_device():
    spans = [(10 * MS, 110 * MS, trace.SLICE),
             (10 * MS, 50 * MS, trace.CALL + "allreduce:8"),
             (60 * MS, 100 * MS, trace.CALL + "bcast:8")]
    devices = {
        # overlapping operations count once; the one before the slice is
        # cut at its start, the one after it is left out
        0: [(0, 20 * MS, "%early = f32[2]{0} copy(x)"),
            (15 * MS, 30 * MS, "%fusion.1 = f32[2]{0} fusion(x)"),
            (70 * MS, 80 * MS, "%fusion.1 = f32[2]{0} fusion(x)"),
            (120 * MS, 130 * MS, "%late = f32[2]{0} copy(x)")],
        1: [(20 * MS, 25 * MS, "%fusion.1 = f32[2]{0} fusion(x)")],
    }
    red = trace.reduce(devices, spans)
    assert red["window_s"] == pytest.approx(0.100)
    assert red["busy_s"] == pytest.approx(0.030)  # 10..30 and 70..80: not 45
    assert red["busiest_device"] == "0" and red["devices"] == 2
    assert red["calls_in_slice"] == 2
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["%fusion.1 f32[2]"] == pytest.approx(0.025)
    assert ops["%early f32[2]"] == pytest.approx(0.010)  # clipped at 10 ms
    assert "%late f32[2]" not in ops
    gaps = dict(red["breakdown"]["idle_gaps"])
    # idle 30..70 and 80..110, shared out over the calls it overlaps
    assert gaps[trace.CALL + "allreduce:8"] == pytest.approx(0.020)  # 30..50
    assert gaps[trace.CALL + "bcast:8"] == pytest.approx(0.030)  # 60..70, 80..100
    assert gaps["between calls"] == pytest.approx(0.020)  # 50..60, 100..110
    assert sum(gaps.values()) + red["busy_s"] == pytest.approx(red["window_s"])


def test_without_the_slice_span_the_host_clock_gives_the_window():
    devices = {0: [(5 * MS, 10 * MS, "a"), (20 * MS, 25 * MS, "a")]}
    red = trace.reduce(devices, [], window_s=0.050)
    assert red["window_s"] == pytest.approx(0.050)
    assert red["busy_s"] == pytest.approx(0.010)
    assert dict(red["breakdown"]["idle_gaps"]) == {
        "between calls": pytest.approx(0.040)}


@pytest.mark.parametrize("devices", [{}, {0: []},
                                     {0: [(200 * MS, 210 * MS, "outside")]}])
def test_no_device_operations_in_the_slice_is_an_error_never_zero(devices):
    spans = [(10 * MS, 110 * MS, trace.SLICE)]
    with pytest.raises(trace.NoDevicePlane):
        trace.reduce(devices, spans)


def test_span_seconds_by_pattern():
    spans = [(0, 2 * MS, "coll.stage"), (5 * MS, 6 * MS, "coll.stage"),
             (0, 9 * MS, "other")]
    assert trace.span_seconds(spans, r"^coll\.") == pytest.approx(0.003)


def test_the_trace_recorded_on_the_chip():
    """Four rounds of osu_ici4.large on four v5e chips (PR 24): four
    device planes, the benchmark's spans on the host plane."""
    devices, spans, seen = trace.load(RECORDED)
    assert sorted(devices) == [0, 1, 2, 3]
    assert any(p == "/device:TPU:0" and ln == trace.OPS_LINE
               for p, ln, _ in seen)
    assert sum(name == trace.SLICE for _, _, name in spans) == 1
    red = trace.reduce(devices, spans)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["calls_in_slice"] == 40  # four rounds of five operations, two sizes
    per_device = {d: sum(e - s for s, e in trace.union(
        iv, *[(s, e) for s, e, n in spans if n == trace.SLICE][0])) / 1e9
        for d, iv in devices.items()}
    assert red["busy_s"] == pytest.approx(max(per_device.values()))
    assert red["busy_s"] < sum(per_device.values())  # never a sum over devices
    assert len(red["breakdown"]["device_ops"]) <= 10
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) + red["busy_s"] <= red["window_s"] + 1e-9
    assert all(k.startswith(trace.CALL) or k == "between calls" for k in gaps)


def _trace_on_the_cpu(tmp_path, work):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.SLICE):
        work()
    jax.profiler.stop_trace()


def test_a_trace_with_no_device_plane_ends_the_run(tmp_path):
    """Only host work was traced: the worker exits with what the trace
    did hold and prints no result."""
    _trace_on_the_cpu(tmp_path, lambda: sum(range(1000)))
    with pytest.raises(SystemExit) as e:
        worker.read_trace(str(tmp_path), 0.001)
    assert e.value.code not in (0, None) and "/host:CPU" in str(e.value.code)
    with pytest.raises(trace.NoDevicePlane):
        trace.newest_xplane(str(tmp_path / "nothing_here"))


def test_host_operations_stand_in_for_a_device_only_in_a_rehearsal(tmp_path):
    """A trace whose host plane holds XLA:CPU operations (``hlo_op``) and
    which has no ``/device:TPU`` plane: a rehearsal reads them, labelled;
    any other run (a chip run whose trace lost its device planes) ends
    non-zero and never prints host time under a device metric's name."""
    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256), jnp.float32)
    step(x).block_until_ready()  # compiled before the trace
    _trace_on_the_cpu(
        tmp_path, lambda: [step(x).block_until_ready() for _ in range(5)])
    path = trace.newest_xplane(str(tmp_path))
    devices, _, seen = trace.load(path, rehearsal=True)
    assert devices and not any(trace.DEVICE_PLANE.match(p) for p, _, _ in seen)
    red, _ = worker.read_trace(str(tmp_path), 0.001, rehearsal=True)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert trace.load(path)[0] == {}  # rehearse off: the same events are host spans
    with pytest.raises(SystemExit) as e:
        worker.read_trace(str(tmp_path), 0.001)
    assert e.value.code not in (0, None)
    assert "no device operations" in str(e.value.code)
