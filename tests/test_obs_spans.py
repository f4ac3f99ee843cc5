"""Library spans on the profiler's clock (``obs.span``, ``obs/spans.py``).

Every test opens a REAL ``jax.profiler`` session through the operator's
tool (``tools.trace.profiler_trace``) and reads the XPlane back with
``ProfileData``: what a benchmark's ``--trace 1`` run and an operator's
trace see is what is asserted here.

- in-process: a steady-state collective writes exactly one
  ``ompi.coll.call`` with one ``ompi.coll.launch`` inside it on the
  same thread, ``ompi.coll.compile`` on the first call only, and no
  call span for runtime-internal comms;
- a two-process spanning job: ``d2h``/exchange/``h2d`` inside their
  ``call``, the exchange's ``(cid, seq)`` equal to its
  ``ompi.nbc.wait``'s, on the interpreted, the planned and the native
  path; ``native_fire`` and ``xchg`` never nested; what a folding
  allreduce of 64 KiB does between its exchanges — the arrivals handed
  out, the partial padded, the folds — each under its own span inside
  the wait;
- with no session nothing is written and results are bit-identical;
- with ``obs.enabled`` the journal holds the ``(op, layer)`` names it
  held before the spans existed, and a journaled span agrees with its
  ``TraceAnnotation`` to 1 ms through the clock anchor.
"""

import glob
import json
import os
import sys
import textwrap
import time
import warnings

import numpy as np
import pytest

import ompi_release_tpu as mpi
from ompi_release_tpu import obs
from ompi_release_tpu.obs import export as obs_export
from ompi_release_tpu.obs import spans
from ompi_release_tpu.tools import trace as tools_trace
from ompi_release_tpu.tools.tpurun import Job

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read_xplane(logdir):
    """(``profile_start_time`` in wall ns, the library's events as dicts
    ``name, line, t0, t1, stats`` with times in ns from the profile's
    start). ``line`` numbers the host thread the event was written on."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    start, events = None, []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if plane.name == "Task Environment":
                start = dict(plane.stats).get("profile_start_time")
            if plane.name != "/host:CPU":
                continue
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith("ompi."):
                        events.append({
                            "name": e.name, "line": i,
                            "t0": e.start_ns,
                            "t1": e.start_ns + e.duration_ns,
                            "stats": dict(e.stats)})
    return start, sorted(events, key=lambda e: e["t0"])


def inside(child, parent):
    return (child["line"] == parent["line"]
            and parent["t0"] <= child["t0"] and child["t1"] <= parent["t1"])


def named(events, name):
    return [e for e in events if e["name"] == name]


@pytest.fixture(scope="module")
def world():
    yield mpi.init()


@pytest.fixture(scope="module")
def comm4(world):
    """A 4-rank communicator on 4 of the virtual devices."""
    n = world.size
    sub = world.split([0 if r < 4 else 1 for r in range(n)])[0]
    assert sub.size == 4 and sub.cid >= 0
    yield sub


def _sharded(comm, x):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.device_put(x, NamedSharding(comm.submesh, P("rank")))


CALLS = {
    "allreduce": lambda c, x: c.allreduce(x),
    "bcast": lambda c, x: c.bcast(x, root=1),
    "allgather": lambda c, x: c.allgather(x),
    "reduce_scatter_block": lambda c, x: c.reduce_scatter_block(x),
    "alltoall": lambda c, x: c.alltoall(x),
}


# ---------------------------------------------------------------------------
# in-process: call > launch, compile on the first call only
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op", sorted(CALLS))
def test_in_process_call_writes_call_and_launch(comm4, tmp_path, op):
    import jax

    # a width no other test uses: this op's program is not cached yet
    width = 4 * (3 + sorted(CALLS).index(op))
    x = _sharded(comm4, np.arange(4 * width, dtype=np.float32)
                 .reshape(4, width))
    with tools_trace.profiler_trace(str(tmp_path)):
        outs = [jax.block_until_ready(CALLS[op](comm4, x))
                for _ in range(4)]
    _, events = read_xplane(str(tmp_path))
    calls = named(events, spans.COLL_CALL)
    assert len(calls) == 4
    for c in calls:
        assert c["stats"] == {"op": op, "cid": comm4.cid,
                              "bytes": 4 * width * 4}
    # the first call compiled, under its own name and never as a launch
    compiles = named(events, spans.COLL_COMPILE)
    assert len(compiles) == 1 and inside(compiles[0], calls[0])
    assert compiles[0]["stats"] == {"op": op}
    # every later (steady-state) call: exactly two spans, one in the other
    launches = named(events, spans.COLL_LAUNCH)
    assert len(launches) == 3
    for c, ln in zip(calls[1:], launches):
        assert inside(ln, c) and ln["stats"] == {}
        assert [e for e in events if e is not c and inside(e, c)] == [ln]
    assert {e["name"] for e in events} == {
        spans.COLL_CALL, spans.COLL_COMPILE, spans.COLL_LAUNCH}
    # no session: nothing is written, and the result is the same bits
    untraced = np.asarray(CALLS[op](comm4, x)).tobytes()
    assert all(np.asarray(o).tobytes() == untraced for o in outs)


def test_no_session_writes_nothing(comm4, tmp_path):
    x = _sharded(comm4, np.ones((4, 40), np.float32))
    comm4.allreduce(x)
    comm4.allreduce(x)  # spans opened here have no session to land in
    with tools_trace.profiler_trace(str(tmp_path)):
        time.sleep(0.01)
    _, events = read_xplane(str(tmp_path))
    assert events == []


def test_internal_comm_writes_no_call_span(world, tmp_path):
    """The hier shadow and other runtime-internal comms (negative cid)
    launch programs but are not user-visible collectives."""
    import jax
    from ompi_release_tpu.comm.communicator import Communicator
    from ompi_release_tpu.comm.group import Group

    inner = Communicator(world.runtime, Group(list(range(4))),
                         name="spans.internal", internal=True)
    try:
        assert inner.cid < 0
        x = _sharded(inner, np.ones((4, 44), np.float32))
        with tools_trace.profiler_trace(str(tmp_path)):
            for _ in range(2):
                jax.block_until_ready(inner.allreduce(x))
        _, events = read_xplane(str(tmp_path))
        assert [e["name"] for e in events] == [spans.COLL_COMPILE,
                                               spans.COLL_LAUNCH]
    finally:
        inner.free()


# ---------------------------------------------------------------------------
# the journal: same names as before, and one timeline through the anchor
# ---------------------------------------------------------------------------

@pytest.fixture()
def obs_on():
    obs.journal.clear()
    obs.enable()
    try:
        yield obs
    finally:
        obs.disable()
        obs.journal.clear()


def test_journal_names_unchanged_in_process(comm4, obs_on):
    """What an observed in-process allreduce journaled before the spans
    existed (read on the parent commit), first call and replays."""
    x = _sharded(comm4, np.ones((4, 48), np.float32))
    for _ in range(3):
        comm4.allreduce(x)
    got = {(s.op, s.layer) for s in obs.journal.snapshot()}
    assert got == {("allreduce", "coll"), ("plan_capture_allreduce", "plan")}


def test_journaled_span_and_annotation_agree_through_the_anchor(
        obs_on, tmp_path):
    """A converted site (``journal=``) writes the journal's old name and
    the annotation for ONE interval; the anchor ``obs.enable()`` stored
    lays the journal (perf_counter) over the XPlane (wall clock)."""
    with tools_trace.profiler_trace(str(tmp_path / "t")):
        with obs.span(spans.PLAN_NATIVE_FIRE,
                      journal=("plan_native_fire", "plan"), cid=3, seq=9):
            time.sleep(0.02)
    start, events = read_xplane(str(tmp_path / "t"))
    (ev,) = named(events, spans.PLAN_NATIVE_FIRE)
    assert ev["stats"] == {"cid": 3, "seq": 9}
    (sp,) = [s for s in obs.journal.snapshot()
             if (s.op, s.layer) == ("plan_native_fire", "plan")]
    assert sp.comm_id == 3
    # the XPlane's epoch IS the wall clock: profile_start_time + offset
    assert abs((start + ev["t0"]) - obs.wall_ns(sp.t_start)) < 1e6
    assert abs((ev["t1"] - ev["t0"]) - sp.dt * 1e9) < 1e6
    # every dump carries the anchor
    anchor = obs.clock_anchor()
    assert set(anchor) == {"perf_counter_s", "time_ns"}
    with open(obs_export.dump_chrome_trace(str(tmp_path / "c.json"))) as f:
        assert json.load(f)["otherData"]["clock_anchor"] == anchor
    with open(obs_export.dump_jsonl(str(tmp_path / "j.jsonl"))) as f:
        lines = [json.loads(ln) for ln in f]
    assert lines[-1]["wall_ns"] == obs.wall_ns(sp.t_start)
    assert obs_export.rank_dump(clock_sync=False)["meta"][
        "clock_anchor"] == anchor


def test_span_without_journal_name_or_with_obs_off_journals_nothing(
        tmp_path):
    obs.journal.clear()
    with obs.span(spans.PLAN_NATIVE_FIRE,
                  journal=("plan_native_fire", "plan"), cid=1, seq=1):
        pass
    obs.enable()
    try:
        with obs.span(spans.HIER_D2H, bytes=8):
            pass
        with pytest.raises(ValueError):  # a failed interval is not journaled
            with obs.span(spans.PLAN_NATIVE_FIRE,
                          journal=("plan_native_fire", "plan")):
                raise ValueError("withdrawn")
    finally:
        obs.disable()
    assert len(obs.journal) == 0


# ---------------------------------------------------------------------------
# the operator's tool and its documents
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", spans.NAMES)
def test_span_names_are_documented(name):
    assert name.startswith("ompi.") and spans.NAMES.count(name) == 1
    assert name in tools_trace.profiler_trace.__doc__
    assert name in spans.__doc__
    with open(os.path.join(REPO, "README.md")) as f:
        assert f"`{name}`" in f.read()


def test_profiler_trace_uses_the_benchmarks_options(tmp_path, monkeypatch):
    import jax

    seen = {}
    real = jax.profiler.start_trace

    def start(logdir, **kw):
        seen.update(kw, logdir=logdir)
        return real(logdir, **kw)

    monkeypatch.setattr(jax.profiler, "start_trace", start)
    with tools_trace.profiler_trace(str(tmp_path)):
        with obs.span(spans.WIRE_STASH, bytes=5):
            pass
    assert seen["logdir"] == str(tmp_path)
    assert seen["profiler_options"].python_tracer_level == 0
    _, events = read_xplane(str(tmp_path))
    assert [(e["name"], e["stats"]) for e in events] == [
        (spans.WIRE_STASH, {"bytes": 5})]


# ---------------------------------------------------------------------------
# a two-process spanning job, rank 0 traced
# ---------------------------------------------------------------------------

APP = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, %r)
    sys.path.insert(0, %r)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np
    import ompi_release_tpu as mpi
    from ompi_release_tpu import obs
    from ompi_release_tpu.mca import var as mca_var
    from ompi_release_tpu.runtime.runtime import Runtime
    from ompi_release_tpu.tools import trace as tools_trace
    import test_obs_spans as T

    out_dir = sys.argv[1]
    world = mpi.init()
    rt = Runtime.current()
    me = rt.bootstrap["process_index"]
    off = rt.local_rank_offset
    x = jax.device_put(np.stack([np.arange(512, dtype=np.float32)
                                 * (off + i + 1) for i in range(2)]))

    def one_round():
        return [np.asarray(o).tobytes() for o in (
            world.allreduce(x), world.bcast(x, root=0),
            world.allgather(x))]

    def traced(tag, rounds):
        # rank 0 holds the profiler; rank 1 makes the same calls
        if me != 0:
            return [one_round() for _ in range(rounds)], None
        d = os.path.join(out_dir, tag)
        with tools_trace.profiler_trace(d):
            outs = [one_round() for _ in range(rounds)]
        return outs, T.read_xplane(d)

    doc = {"cid": world.cid}
    # 1. the first round records its schedules through the interpreted
    #    adapter; the second replays them through the C executor
    outs, got = traced("first", 2)
    doc["first"] = got
    untraced = one_round()
    doc["bit_identical"] = all(o == untraced for o in outs)
    # 2. the C executor off: a cvar write re-plans (one interpreted
    #    round), then the planned replay runs in Python
    mca_var.set_value("coll_plan_native", False)
    one_round()
    _, doc["planned"] = traced("planned", 1)
    # 3. observed, on both ranks: the journal's names, and the native
    #    fire's journal span against its annotation
    mca_var.set_value("coll_plan_native", True)
    one_round()
    one_round()
    obs.journal.clear()
    obs.enable()
    _, doc["observed"] = traced("observed", 1)
    obs.disable()
    doc["journal"] = [[s.op, s.layer, obs.wall_ns(s.t_start), s.dt]
                      for s in obs.journal.snapshot()]
    # 4. a partial of 64 KiB: the folding schedule (Rabenseifner at two
    #    processes), recorded, replayed, then traced; and a bcast from
    #    the other process, whose arrival is read once and never kept
    from ompi_release_tpu.mca import pvar
    big = jax.device_put(np.stack([np.arange(16384, dtype=np.float32)
                                   %% 7 + off + i for i in range(2)]))
    copied = pvar.PVARS.lookup("plan_pool_copy_bytes")

    def folding_round():
        return [np.asarray(o).tobytes() for o in (
            world.allreduce(big), world.bcast(big, root=2))]

    folding_round()
    untraced = folding_round()
    c0 = copied.read()
    if me == 0:
        with tools_trace.profiler_trace(os.path.join(out_dir, "fold")):
            outs = folding_round()
        doc["folding"] = T.read_xplane(os.path.join(out_dir, "fold"))
    else:
        outs = folding_round()
    doc["folding_copied"] = copied.read() - c0
    doc["folding_bit_identical"] = outs == untraced
    if me == 0:
        with open(os.path.join(out_dir, "rank0.json"), "w") as f:
            json.dump(doc, f)
    world.barrier()
    mpi.finalize()
""") % (REPO, os.path.join(REPO, "tests"))

EXCHANGES = (spans.PLAN_XCHG, spans.PLAN_NATIVE_FIRE)
OPS = ("allreduce", "bcast", "allgather")


@pytest.fixture(scope="module")
def spanning(tmp_path_factory):
    """One job for all the spanning assertions: rank 0's three traces."""
    tmp = tmp_path_factory.mktemp("spanning")
    app = tmp / "app.py"
    app.write_text(APP)
    job = Job(2, [sys.executable, str(app), str(tmp)], [],
              heartbeat_s=0.5, miss_limit=8)
    assert job.run(timeout_s=240) == 0
    with open(tmp / "rank0.json") as f:
        return json.load(f)


def _calls_with_children(events):
    calls = named(events, spans.COLL_CALL)
    return [(c, [e for e in events if e is not c and inside(e, c)])
            for c in calls]


def check_spanning_calls(doc, events, want_exchange):
    """Every call of the traced rounds: one ``ompi.nbc.wait`` inside it,
    exchanges of the wanted kind inside the wait with its ``(cid,
    seq)``, a fetch before the first exchange and after the last either
    a placement (allreduce) or the one-pass assembly of the result
    (bcast, allgather: its placement inside it), nothing nested that
    must not nest."""
    pairs = _calls_with_children(events)
    assert [c["stats"]["op"] for c, _ in pairs] == \
        list(OPS) * (len(pairs) // 3)
    for call, kids in pairs:
        assert call["stats"]["cid"] == doc["cid"]
        assert call["stats"]["bytes"] == 2 * 512 * 4
        (wait,) = named(kids, spans.NBC_WAIT)
        xs = [e for e in kids if e["name"] in EXCHANGES]
        assert xs and {e["name"] for e in xs} <= set(want_exchange)
        for e in xs:
            assert inside(e, wait)
            assert (e["stats"]["cid"], e["stats"]["seq"]) == (
                wait["stats"]["cid"], wait["stats"]["seq"])
            assert not any(inside(o, e) for o in xs if o is not e)
        d2h, h2d = named(kids, spans.HIER_D2H), named(kids, spans.HIER_H2D)
        built = named(kids, spans.HIER_ASSEMBLE)
        assert d2h and d2h[0]["t1"] <= xs[0]["t0"]
        if call["stats"]["op"] == "allreduce":
            assert h2d and xs[-1]["t1"] <= h2d[-1]["t0"] and not built
        else:
            (done,) = built
            assert xs[-1]["t1"] <= done["t0"] and inside(done, wait)
            # the whole result: every local member's copy of it
            assert done["stats"]["bytes"] == 2 * 512 * 4 * (
                4 if call["stats"]["op"] == "allgather" else 1)
            # a host rank hands jax the buffer it has just filled
            (placed,) = h2d
            assert inside(placed, done)
            assert placed["stats"]["bytes"] == done["stats"]["bytes"]
        assert all(e["stats"]["bytes"] > 0 for e in d2h + h2d)
        moves = d2h + h2d
        assert not any(inside(a, b) for a in moves for b in moves
                       if a is not b)
        # the shadow comm's in-process reduction launches, but writes
        # no call span of its own
        assert not named(kids, spans.COLL_CALL)
    seqs = [c[1][0]["stats"]["seq"] for c in pairs]
    assert seqs == sorted(set(seqs))  # one posted schedule per call


def test_spanning_results_bit_identical_traced_or_not(spanning):
    assert spanning["bit_identical"] is True


def test_spanning_first_round_interpreted_then_native(spanning):
    _, events = spanning["first"]
    pairs = _calls_with_children(events)
    assert len(pairs) == 6
    # the recording round rides the interpreted adapter ...
    first = [e for c, kids in pairs[:3] for e in [c] + kids]
    check_spanning_calls(spanning, first, {spans.PLAN_XCHG})
    # ... the replay is one native fire per call
    second = [e for c, kids in pairs[3:] for e in [c] + kids]
    check_spanning_calls(spanning, second, {spans.PLAN_NATIVE_FIRE})
    for _, kids in pairs[3:]:
        assert len(named(kids, spans.PLAN_NATIVE_FIRE)) == 1
        assert not named(kids, spans.PLAN_XCHG)
    # only the job's very first round compiles (the shadow comm's programs)
    for e in named(events, spans.COLL_COMPILE):
        assert any(inside(e, c) for c, _ in pairs[:3])


def test_spanning_planned_replay_in_python(spanning):
    _, events = spanning["planned"]
    check_spanning_calls(spanning, events, {spans.PLAN_XCHG})
    assert not named(events, spans.PLAN_NATIVE_FIRE)
    assert not named(events, spans.COLL_COMPILE)
    # the wire hands an arrival over as a device array: its way back
    # to the host is a fetch of its own, inside the exchange that reaped it
    xs = named(events, spans.PLAN_XCHG)
    nested = [d for d in named(events, spans.HIER_D2H)
              if any(inside(d, x) for x in xs)]
    assert nested and all(d["stats"]["bytes"] > 0 for d in nested)


def test_spanning_observed_journal_names_and_one_timeline(spanning):
    start, events = spanning["observed"]
    check_spanning_calls(spanning, events, {spans.PLAN_NATIVE_FIRE})
    journal = spanning["journal"]
    # the names an observed replay round journaled on the parent commit
    assert {(op, layer) for op, layer, _, _ in journal} == {
        ("allreduce", "coll"), ("bcast", "coll"), ("allgather", "coll"),
        ("nbc_allreduce", "nbc"), ("nbc_bcast", "nbc"),
        ("nbc_allgather", "nbc"), ("nbc_post", "nbc"),
        ("hier_sched_round", "hier"), ("plan_native_fire", "plan")}
    fires = [j for j in journal if j[0] == "plan_native_fire"]
    marks = named(events, spans.PLAN_NATIVE_FIRE)
    assert len(fires) == len(marks) == 3
    for (_, _, wall, dt), ev in zip(fires, marks):
        assert abs((start + ev["t0"]) - wall) < 1e6
        assert abs((ev["t1"] - ev["t0"]) - dt * 1e9) < 1e6


def test_a_folding_allreduce_names_what_its_wait_holds(spanning):
    """ISSUE 38: between the segments of its native fire a Rabenseifner
    allreduce of 64 KiB takes its arrivals out of the executor's slab
    (copies: ``plan_pool_copy_bytes``), pads (here: copies the read-only
    fetch of) its partial and folds once; a bcast's arrival is a view of
    the slab and nothing is folded."""
    _, events = spanning["folding"]
    (reduce, kids), (bcast, moved) = _calls_with_children(events)
    assert (reduce["stats"]["op"], bcast["stats"]["op"]) == (
        "allreduce", "bcast")
    (wait,) = named(kids, spans.NBC_WAIT)
    arrivals = named(kids, spans.PLAN_ARRIVALS)
    (pad,), (fold,) = named(kids, spans.HIER_PAD), named(kids,
                                                         spans.HIER_FOLD)
    fires = named(kids, spans.PLAN_NATIVE_FIRE)
    assert len(fires) == 2 and len(arrivals) == 2  # one live round
    inner = arrivals + [pad, fold]
    assert all(inside(e, wait) for e in inner)
    assert sum(e["t1"] - e["t0"] for e in inner + fires) <= (
        wait["t1"] - wait["t0"])
    assert not any(inside(a, b) for a in inner + fires
                   for b in inner + fires if a is not b)
    for e in arrivals:
        assert (e["stats"]["cid"], e["stats"]["seq"]) == (
            wait["stats"]["cid"], wait["stats"]["seq"])
    # each round brings half of the 64 KiB partial; both are copied out
    assert [e["stats"]["bytes"] for e in arrivals] == [32768, 32768]
    assert spanning["folding_copied"] == 65536
    assert pad["stats"] == {"bytes": 65536}  # a jax-backed fetch: read-only
    assert fold["stats"] == {"bytes": 32768}
    assert pad["t1"] <= fires[0]["t0"] and fires[0]["t1"] <= fold["t0"]
    assert fold["t1"] <= fires[1]["t0"]
    # the bcast: one arrival, handed out as a view, no fold, no pad
    (view,) = named(moved, spans.PLAN_ARRIVALS)
    assert view["stats"]["bytes"] == 0
    assert inside(view, named(moved, spans.NBC_WAIT)[0])
    assert not named(moved, spans.HIER_FOLD) + named(moved, spans.HIER_PAD)
    assert spanning["folding_bit_identical"] is True
