"""The OpenSHMEM spans and counters under ``tpurun -n 2`` on the CPU.

Both ranks hold a profiler session while PE 0, through the public API
alone, posts eight puts at their offsets and four AMOs on one word of PE 1
and calls ``quiet``, reads two blocks back with blocking gets and makes one
fetching AMO of each kind. PE 0's trace has to hold each span of
``obs/spans.py``'s ``ompi.shmem.*`` with its stats: the drain inside the
quiet, the window's one ``ompi.osc.sync`` inside the drain, and exactly ONE
sync inside each blocking call (it flushes its target once). ``shmem_ops``,
``shmem_blocking_ops`` and ``shmem_quiets`` tick by what was issued and the
wire by one batch per quiet and one per blocking call. With no session open
the same calls write nothing and deliver the same bits; with ``obs`` enabled
the drain still journals as ``shmem_bulk_flush``. The AMOs' operands are
host scalars and stay on the host (ISSUE 37): ``osc_host_payloads`` counts
them and ``ompi.osc.d2h`` reports the puts' device blocks alone. Each batch's
reply brings the home's turn back (ISSUE 38): the ``osc_home_*_seconds``
timers tick once per blocking call and once per allocation a quiet drained.
"""

import json
import os
import sys
import textwrap

import pytest

from ompi_release_tpu.obs import spans
from ompi_release_tpu.tools.tpurun import Job

import test_obs_spans as T

REPO = T.REPO
PIECE, PUTS, ADDS = 256, 8, 4  # elements: int32, so 4 bytes each

APP = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, %r)
    sys.path.insert(0, %r)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np
    import ompi_release_tpu as mpi
    import ompi_release_tpu.osc.wire_win  # its counters
    from ompi_release_tpu import obs
    from ompi_release_tpu.mca import pvar
    from ompi_release_tpu.oshmem import shmem
    from ompi_release_tpu.tools import trace as tools_trace
    import test_obs_spans as T

    out_dir, PIECE, PUTS, ADDS = sys.argv[1], *map(int, sys.argv[2:5])
    world = mpi.init()
    ctx = shmem.shmem_init(world)
    me = ctx.my_pe
    sym = ctx.malloc((2 * PIECE * PUTS,), np.int32)
    idle = ctx.malloc((4,), np.int32)  # never written: quiet skips it
    pieces = [jax.device_put(np.arange(PIECE, dtype=np.int32) + 100 * i)
              for i in range(PUTS)]
    COUNTERS = ("shmem_ops", "shmem_blocking_ops", "shmem_quiets",
                "shmem_bulk_ops", "shmem_bulk_flushes", "osc_wire_requests",
                "osc_host_payloads")

    TIMERS = tuple("osc_home_%%s_seconds" %% k
                   for k in ("turn", "recv", "program", "out", "back"))

    def counters():
        return {k: pvar.PVARS.lookup(k).read() for k in COUNTERS + TIMERS}

    def calls():
        # PE 0 alone issues; PE 1's service thread applies
        got = []
        if me == 0:
            for j, p in enumerate(pieces):
                ctx.put(sym, p, 1, offset=2 * j * PIECE)
            for j in range(ADDS):
                ctx.atomic_add(sym, j + 1, 1, index=1)
            ctx.quiet()
            got = [ctx.get(sym, 1, offset=0, nelems=PIECE),
                   ctx.get(sym, 1, offset=2 * PIECE, nelems=PIECE),
                   ctx.atomic_fetch_add(sym, 5, 1, index=1),
                   ctx.atomic_swap(sym, 9, 1, index=1),
                   ctx.atomic_compare_swap(sym, 9, 1, 1, index=1)]
            got = [np.asarray(v).tobytes().hex() for v in got]
        ctx.barrier_all()
        if me == 1:
            got = [np.asarray(sym.local(1)).tobytes().hex()]
        return got

    doc = {"rank": me}
    calls()  # compiles, plans and first contacts, outside every count
    ctx.barrier_all()
    before = counters()
    with tools_trace.profiler_trace(os.path.join(out_dir, "t%%d" %% me)):
        doc["traced"] = calls()
    doc["events"] = T.read_xplane(os.path.join(out_dir, "t%%d" %% me))[1]
    after = counters()
    doc["delta"] = {k: after[k] - before[k] for k in COUNTERS}
    doc["timers"] = [after[k] - before[k] for k in TIMERS]
    ctx.barrier_all()
    doc["untraced"] = calls()
    # no session: the sites above wrote nothing
    with tools_trace.profiler_trace(os.path.join(out_dir, "e%%d" %% me)):
        pass
    doc["events_after"] = T.read_xplane(os.path.join(out_dir, "e%%d" %% me))[1]
    # the journal record of the bulk flush is the drain span's journal pair
    obs.enable()
    calls()
    doc["journal"] = [[s.op, s.layer, s.nbytes]
                      for s in obs.journal.snapshot()
                      if s.op == "shmem_bulk_flush"]
    obs.disable()
    with open(os.path.join(out_dir, "rank%%d.json" %% me), "w") as f:
        json.dump(doc, f)
    shmem.shmem_finalize()
    world.barrier()
    mpi.finalize()
""") % (REPO, os.path.join(REPO, "tests"))


@pytest.fixture(scope="module")
def ranks(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("shmem_spans")
    app = tmp / "app.py"
    app.write_text(APP)
    job = Job(2, [sys.executable, str(app), str(tmp), str(PIECE), str(PUTS),
                  str(ADDS)], [], heartbeat_s=0.5, miss_limit=8)
    assert job.run(timeout_s=240) == 0
    docs = []
    for r in (0, 1):
        with open(tmp / f"rank{r}.json") as f:
            docs.append(json.load(f))
    return docs


def test_a_quiet_holds_the_drain_and_the_drain_the_windows_sync(ranks):
    events = ranks[0]["events"]
    quiets = T.named(events, spans.SHMEM_QUIET)
    drains = T.named(events, spans.SHMEM_DRAIN)
    # calls() ends in barrier_all, a quiet with nothing left to complete
    assert [q["stats"] for q in quiets] == [
        {"allocs": 1, "ops": PUTS + ADDS}, {"allocs": 0, "ops": 0}]
    (drain,) = drains
    assert T.inside(drain, quiets[0])
    # the AMOs' operands are Python ints in the queue: no bytes there
    assert drain["stats"]["ops"] == PUTS + ADDS
    assert drain["stats"]["bytes"] == 4 * PIECE * PUTS
    syncs = [s for s in T.named(events, spans.OSC_SYNC)
             if T.inside(s, drain)]
    assert len(syncs) == 1 and syncs[0]["stats"]["ops"] == PUTS + ADDS
    assert not [s for s in T.named(events, spans.OSC_SYNC)
                if T.inside(s, quiets[1])]


def test_a_blocking_call_is_one_span_with_one_sync_inside(ranks):
    events = ranks[0]["events"]
    gets, amos = (T.named(events, spans.SHMEM_GET),
                  T.named(events, spans.SHMEM_AMO))
    assert [g["stats"] for g in gets] == [{"bytes": 4 * PIECE}] * 2
    assert [a["stats"] for a in amos] == [
        {"kind": "fetch_add"}, {"kind": "swap"}, {"kind": "cswap"}]
    syncs = T.named(events, spans.OSC_SYNC)
    for call in gets + amos:
        mine = [s for s in syncs if T.inside(s, call)]
        assert len(mine) == 1 and mine[0]["stats"]["ops"] == 1
    assert len(syncs) == 1 + len(gets + amos)
    assert {e["name"] for e in events if ".shmem." in e["name"]} == {
        spans.SHMEM_QUIET, spans.SHMEM_DRAIN, spans.SHMEM_GET,
        spans.SHMEM_AMO}
    # the home wrote none of them: it issued nothing
    assert not [e for e in ranks[1]["events"]
                if ".shmem." in e["name"] and e["name"] != spans.SHMEM_QUIET]


def test_the_fetch_counts_what_a_device_held(ranks):
    """``ompi.osc.d2h``'s ``bytes``: the puts' device blocks, not the
    AMOs' host operands beside them in the quiet's batch; a blocking
    call's batch holds host operands alone, or nothing."""
    events = ranks[0]["events"]
    syncs, fetches = (T.named(events, spans.OSC_SYNC),
                      T.named(events, spans.OSC_D2H))
    assert len(fetches) == len(syncs) == 1 + 5
    assert all(T.inside(f, s) for f, s in zip(fetches, syncs))
    assert [f["stats"]["bytes"] for f in fetches] == [
        4 * PIECE * PUTS, 0, 0, 0, 0, 0]
    # the sync still counts every payload it took off the queue
    assert [s["stats"]["bytes"] for s in syncs] == [
        4 * PIECE * PUTS + 4 * ADDS, 0, 0, 4, 4, 8]


def test_counters_tick_by_what_was_issued(ranks):
    assert ranks[0]["delta"] == {
        "shmem_ops": PUTS + ADDS + 5, "shmem_blocking_ops": 5,
        "shmem_quiets": 2, "shmem_bulk_ops": PUTS + ADDS,
        "shmem_bulk_flushes": 1,
        "osc_wire_requests": 1 + 5,  # one batch a quiet, one a blocking call
        # the AMOs' operands and the one compare value; no put's block
        "osc_host_payloads": ADDS + 3 + 1}
    assert ranks[1]["delta"] == {
        "shmem_ops": 0, "shmem_blocking_ops": 0, "shmem_quiets": 1,
        "shmem_bulk_ops": 0, "shmem_bulk_flushes": 0,
        "osc_wire_requests": 0, "osc_host_payloads": 0}


def test_no_session_writes_nothing_and_delivers_the_same_bits(ranks):
    for doc in ranks:
        assert doc["events_after"] == []
        assert doc["traced"] == doc["untraced"]
        assert len(doc["traced"]) == (5 if doc["rank"] == 0 else 1)


def test_the_drain_still_journals_as_the_bulk_flush(ranks):
    assert ranks[0]["journal"] == [
        ["shmem_bulk_flush", "osc", 4 * PIECE * PUTS]]
    assert ranks[1]["journal"] == []


def test_the_homes_turn_ticks_once_a_blocking_call_and_a_drain(ranks):
    events = ranks[0]["events"]
    waits = T.named(events, spans.OSC_REPLY_WAIT)
    quiets = T.named(events, spans.SHMEM_QUIET)
    blocking = T.named(events, spans.SHMEM_GET) + T.named(events,
                                                          spans.SHMEM_AMO)
    # one routed reply, with its turn, per fetching AMO and blocking get;
    # one for the allocation the first quiet drained (the idle allocation
    # and the second quiet have nothing to ask a home)
    for call in blocking + quiets[:1]:
        (mine,) = [w for w in waits if T.inside(w, call)]
        assert mine["stats"]["turn_us"] >= mine["stats"]["program_us"] > 0
    assert not [w for w in waits if T.inside(w, quiets[1])]
    assert len(waits) == ranks[0]["delta"]["osc_wire_requests"]
    turn, recv, program, out, back = ranks[0]["timers"]
    assert turn * 1e6 == pytest.approx(
        sum(w["stats"]["turn_us"] for w in waits), abs=0.01)
    assert 0 < recv + program <= turn and out >= 0 and back >= 0
    assert ranks[1]["timers"] == [0, 0, 0, 0, 0]
