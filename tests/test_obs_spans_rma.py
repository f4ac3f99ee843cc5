"""The one-sided spans and counters under ``tpurun -n 2`` on the CPU.

Both ranks hold a profiler session while rank 0, inside one shared lock
epoch on rank 1, puts eight blocks at their displacements and flushes,
then gets them back and flushes: through the public API alone. Rank 0's
trace has to hold each origin-side span of ``obs/spans.py`` with its
stats, nested as documented (``d2h`` inside ``pack`` inside ``sync``;
``reply_wait`` inside ``request``; a reply's read values come off the wire
under ``unpack`` and are placed under ``h2d``); rank 1's, the home's, holds
``ompi.osc.apply`` with the epoch program inside it, on the service thread.
``osc_wire_bytes`` and ``osc_wire_ops`` move by what the wire audit expects.
With no session open the same calls write nothing and deliver the same bits.

The home's turn (ISSUE 38): every batch reply carries the home's stamps,
the origin sums them into the five ``osc_home_*_seconds`` timers, once per
batch, and writes them as stats of its open ``ompi.osc.reply_wait``, whose
``token`` the home's ``ompi.osc.apply`` carries too. A lock grant, an
abandon and an error reply carry zeros and tick nothing; between processes
that do not share a host ``out`` and ``back`` stand still; the reply of a
request that timed out is still drained, frames and all.
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
PIECE, WINDOW = 1024, 8  # elements: float32, so 4 bytes each
BYTES = 4 * PIECE * WINDOW
KIND_BATCH = 1

APP = textwrap.dedent("""
    import json, os, sys, time
    sys.path.insert(0, %r)
    sys.path.insert(0, %r)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np
    import ompi_release_tpu as mpi
    import ompi_release_tpu.osc.wire_win  # its counters
    from ompi_release_tpu.mca import pvar
    from ompi_release_tpu.osc.window import LOCK_SHARED, win_allocate
    from ompi_release_tpu.tools import trace as tools_trace
    import test_obs_spans as T

    out_dir, PIECE, WINDOW = sys.argv[1], *map(int, sys.argv[2:4])
    world = mpi.init()
    me = world.local_comm_ranks[0]
    win = win_allocate(world, (2 * PIECE * WINDOW,), jnp.float32)
    pieces = [jax.device_put(np.arange(PIECE, dtype=np.float32) + i)
              for i in range(WINDOW)]
    COUNTERS = ("osc_wire_bytes", "osc_wire_ops", "osc_rma_ops",
                "osc_wire_requests", "osc_host_payloads")
    TIMERS = tuple("osc_home_%%s_seconds" %% k
                   for k in ("turn", "recv", "program", "out", "back"))

    def timers():
        return [pvar.PVARS.lookup(k).read() for k in TIMERS]

    def counters():
        return {k: pvar.PVARS.lookup(k).read() for k in COUNTERS}

    def calls():
        # rank 0 alone issues; rank 1's service thread applies
        got = []
        if me == 0:
            for j, p in enumerate(pieces):
                win.put(p, 1, disp=2 * j * PIECE)
            win.flush(1)
            reqs = [win.get(1, disp=2 * j * PIECE, count=PIECE)
                    for j in range(WINDOW)]
            win.flush(1)
            got = [np.asarray(r.value).tobytes().hex() for r in reqs]
        world.barrier()
        if me == 1:
            got = [np.asarray(win.read()).tobytes().hex()]
        return got

    doc = {"rank": me}
    if me == 0:
        win.lock(1, LOCK_SHARED)
    calls()  # compiles, plans and first contacts, outside every count
    world.barrier()
    before, t_before = counters(), timers()
    with tools_trace.profiler_trace(os.path.join(out_dir, "t%%d" %% me)):
        doc["traced"] = calls()
    doc["events"] = T.read_xplane(os.path.join(out_dir, "t%%d" %% me))[1]
    after = counters()
    doc["delta"] = {k: after[k] - before[k] for k in COUNTERS}
    doc["timers"] = [a - b for a, b in zip(timers(), t_before)]
    world.barrier()
    doc["untraced"] = calls()
    # no session: the sites above wrote nothing
    with tools_trace.profiler_trace(os.path.join(out_dir, "e%%d" %% me)):
        pass
    doc["events_after"] = T.read_xplane(os.path.join(out_dir, "e%%d" %% me))[1]
    if me == 0:
        win.unlock(1)

    # -- replies that carry no turn, a peer on another clock, a stale reply
    from ompi_release_tpu.osc import wire_win
    from ompi_release_tpu.osc.window import LOCK_EXCLUSIVE, _PendingOp
    from ompi_release_tpu.request.request import Request
    from ompi_release_tpu.utils.errors import MPIError
    svc, requests = win.service, pvar.PVARS.lookup("osc_wire_requests")

    def ticked(fn):
        # (what fn returned, each timer's change, requests made)
        t0, r0 = timers(), requests.read()
        got = fn()
        return got, [a - b for a, b in zip(timers(), t0)], requests.read() - r0

    def refused():
        try:
            svc.request(win, 1, wire_win.KIND_BATCH, -1, 0,
                        payload=wire_win.Batch('{"ops": 1}', (), []))
        except MPIError as e:
            return e.code.name

    def on_another_host():
        keep = wire_win._same_host
        wire_win._same_host = lambda router, pidx: False
        try:
            win.put(pieces[0], 1, disp=0)
            win.flush(1)
        finally:
            wire_win._same_host = keep

    def late():
        # the home cannot take the window's lock for a second: the
        # request gives up first, and its reply, read frame and all,
        # arrives with nobody waiting
        seg = svc.tuning().segment
        op = _PendingOp("get", 1, request=Request(), disp=0, count=PIECE)
        try:
            svc.request(win, 1, wire_win.KIND_BATCH, -1, 0,
                        payload=wire_win._pack_batch([op], seg),
                        timeout_ms=300)
        except MPIError as e:
            return e.code.name

    def get_again():
        req = win.get(1, disp=0, count=PIECE)
        win.flush(1)
        return np.asarray(req.value).tobytes().hex()

    if me == 0:
        # an abandon with no interest to forget: the home just answers
        doc["abandon"] = ticked(lambda: svc.request(
            win, 1, wire_win.KIND_ABANDON, 1, 0))[1:]
        doc["grant"] = ticked(lambda: win.lock(1, LOCK_EXCLUSIVE))[1:]
        doc["refused"] = ticked(refused)
        doc["other_host"] = ticked(on_another_host)[1:]
    world.barrier()
    if me == 1:
        with win._op_lock:
            world.barrier()
            time.sleep(1.0)
    else:
        world.barrier()
        doc["late"] = ticked(late)
    world.barrier()
    if me == 0:
        with tools_trace.profiler_trace(os.path.join(out_dir, "s0")):
            doc["after_late"] = ticked(get_again)
        doc["events_late"] = T.read_xplane(os.path.join(out_dir, "s0"))[1]
        doc["want"] = np.asarray(pieces[0]).tobytes().hex()
        win.unlock(1)
    win.free()
    with open(os.path.join(out_dir, "rank%%d.json" %% me), "w") as f:
        json.dump(doc, f)
    world.barrier()
    mpi.finalize()
""") % (REPO, os.path.join(REPO, "tests"))


@pytest.fixture(scope="module")
def ranks(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("rma_spans")
    app = tmp / "app.py"
    app.write_text(APP)
    job = Job(2, [sys.executable, str(app), str(tmp), str(PIECE),
                  str(WINDOW)], [], heartbeat_s=0.5, miss_limit=8)
    assert job.run(timeout_s=240) == 0
    docs = []
    for r in (0, 1):
        with open(tmp / f"rank{r}.json") as f:
            docs.append(json.load(f))
    return docs


def osc(events):
    return [e for e in events if e["name"].startswith("ompi.osc.")]


def test_a_flush_is_one_sync_with_its_pack_and_its_fetch_inside(ranks):
    events = osc(ranks[0]["events"])
    syncs = T.named(events, spans.OSC_SYNC)
    # the puts' flush carries their payload, the gets' flush none
    assert [(s["stats"]["ops"], s["stats"]["bytes"]) for s in syncs] == [
        (WINDOW, BYTES), (WINDOW, 0)]
    assert all(s["stats"]["win"] == syncs[0]["stats"]["win"]
               and s["stats"]["cid"] >= 0 for s in syncs)
    packs, fetches = (T.named(events, spans.OSC_PACK),
                      T.named(events, spans.OSC_D2H))
    assert len(packs) == len(fetches) == 2
    for sync, pack, fetch in zip(syncs, packs, fetches):
        assert T.inside(pack, sync) and T.inside(fetch, pack)
    assert [f["stats"]["bytes"] for f in fetches] == [BYTES, 0]
    # a frame is its request records and its payloads
    assert BYTES < packs[0]["stats"]["bytes"] <= BYTES + 2048
    assert 0 < packs[1]["stats"]["bytes"] <= 2048


def test_a_request_holds_its_reply_wait_and_a_reply_its_unpack(ranks):
    events = osc(ranks[0]["events"])
    syncs = T.named(events, spans.OSC_SYNC)
    reqs, waits = (T.named(events, spans.OSC_REQUEST),
                   T.named(events, spans.OSC_REPLY_WAIT))
    packs = T.named(events, spans.OSC_PACK)
    assert len(reqs) == len(waits) == 2
    for sync, pack, req, wait in zip(syncs, packs, reqs, waits):
        assert T.inside(req, sync) and T.inside(wait, req)
        assert pack["t1"] <= req["t0"]  # composed, then sent
        assert req["stats"] == {"kind": KIND_BATCH, "peer": 1,
                                "bytes": pack["stats"]["bytes"]}
        assert set(wait["stats"]) == {"kind", "peer", "token", "out_us",
                                      "turn_us", "recv_us", "program_us",
                                      "back_us"}
        assert (wait["stats"]["kind"], wait["stats"]["peer"]) == (
            KIND_BATCH, 1)
    # only the gets' reply carries read values: off the wire inside the
    # wait, onto the device after it, inside the flush
    (unpack,), (h2d,) = (T.named(events, spans.OSC_UNPACK),
                         T.named(events, spans.OSC_H2D))
    assert unpack["stats"] == {"bytes": BYTES} == h2d["stats"]
    assert T.inside(unpack, waits[1])
    assert T.inside(h2d, syncs[1]) and reqs[1]["t1"] <= h2d["t0"]
    assert {e["name"] for e in events} == {
        spans.OSC_SYNC, spans.OSC_PACK, spans.OSC_D2H, spans.OSC_REQUEST,
        spans.OSC_REPLY_WAIT, spans.OSC_UNPACK, spans.OSC_H2D}


def test_the_home_applies_each_batch_with_its_program_inside(ranks):
    events = osc(ranks[1]["events"])
    applies, progs = (T.named(events, spans.OSC_APPLY),
                      T.named(events, spans.OSC_PROGRAM))
    assert len(applies) == len(progs) == 2
    for apply, prog in zip(applies, progs):
        assert T.inside(prog, apply)  # on the service thread
        assert prog["stats"] == {"ops": WINDOW}
        assert apply["stats"]["origin"] == 0
        assert apply["stats"]["ops"] == WINDOW
    # spans of one request share an identifier: (origin, token)
    waits = T.named(osc(ranks[0]["events"]), spans.OSC_REPLY_WAIT)
    assert [a["stats"]["token"] for a in applies] == [
        w["stats"]["token"] for w in waits]
    assert waits[0]["stats"]["token"] != waits[1]["stats"]["token"]
    assert BYTES < applies[0]["stats"]["bytes"] <= BYTES + 2048
    assert 0 < applies[1]["stats"]["bytes"] <= 2048
    assert {e["name"] for e in events} == {spans.OSC_APPLY,
                                           spans.OSC_PROGRAM}


def test_counters_tick_by_what_the_wire_audit_expects(ranks):
    delta = ranks[0]["delta"]
    # the puts' frame out, the gets' records out and their values back
    assert 2 * BYTES < delta["osc_wire_bytes"] <= 2 * BYTES + 4096
    assert delta["osc_wire_ops"] == delta["osc_rma_ops"] == 2 * WINDOW
    assert delta["osc_wire_requests"] == 2  # one batch a flush
    # the home issued nothing and shipped nothing
    assert ranks[1]["delta"] == {"osc_wire_bytes": 0, "osc_wire_ops": 0,
                                 "osc_rma_ops": 0, "osc_wire_requests": 0,
                                 "osc_host_payloads": 0}
    # every block was a device array: queued as passed, fetched for the
    # frame (``ompi.osc.d2h`` reports all of it, above)
    assert delta["osc_host_payloads"] == 0


def test_no_session_writes_nothing_and_delivers_the_same_bits(ranks):
    for doc in ranks:
        assert doc["events_after"] == []
        assert doc["traced"] == doc["untraced"]
        assert len(doc["traced"]) == (WINDOW if doc["rank"] == 0 else 1)


TURN, RECV, PROGRAM, OUT, BACK = range(5)  # the app's TIMERS, in order


def test_the_homes_turn_ticks_once_a_batch_into_timers_and_stats(ranks):
    turn, recv, program, out, back = ranks[0]["timers"]
    waits = T.named(osc(ranks[0]["events"]), spans.OSC_REPLY_WAIT)
    # one reply routed per request made: the timers are the sums of
    # what each wait wrote into its own stats
    assert len(waits) == ranks[0]["delta"]["osc_wire_requests"] == 2
    for value, stat in ((turn, "turn_us"), (recv, "recv_us"),
                        (program, "program_us"), (out, "out_us"),
                        (back, "back_us")):
        assert value * 1e6 == pytest.approx(
            sum(w["stats"][stat] for w in waits), abs=0.01)
    assert 0 < recv and 0 < program and recv + program <= turn
    assert out >= 0 and back >= 0
    # a get has no payload frame: the wait opens as the envelope is
    # sent, so the three pieces lie inside the request and fill most of
    # the wait (bounds a loaded machine keeps)
    get_wait = waits[1]["stats"]
    pieces = get_wait["out_us"] + get_wait["turn_us"] + get_wait["back_us"]
    req = T.named(osc(ranks[0]["events"]), spans.OSC_REQUEST)[1]
    assert pieces * 1e3 <= req["t1"] - req["t0"]
    assert pieces * 1e3 >= (waits[1]["t1"] - waits[1]["t0"]) / 2
    # the home made no request: nothing was routed to it
    assert ranks[1]["timers"] == [0, 0, 0, 0, 0]


def test_a_reply_without_a_turn_ticks_nothing(ranks):
    doc = ranks[0]
    # a lock grant and an abandon: one request each, zeros on the reply
    assert doc["grant"] == [[0, 0, 0, 0, 0], 1]
    assert doc["abandon"] == [[0, 0, 0, 0, 0], 1]
    # an error reply is loud at the origin, as before, and counts nothing
    assert doc["refused"] == ["ERR_RMA_SYNC", [0, 0, 0, 0, 0], 1]


def test_across_hosts_only_the_turn_ticks(ranks):
    delta, made = ranks[0]["other_host"]
    assert made == 1
    assert delta[TURN] > 0 and delta[RECV] > 0 and delta[PROGRAM] > 0
    assert delta[OUT] == 0 and delta[BACK] == 0


def test_a_stale_reply_is_still_drained(ranks):
    doc = ranks[0]
    # the request that gave up was routed nothing ...
    assert doc["late"] == ["ERR_PENDING", [0, 0, 0, 0, 0], 1]
    # ... and the next one finds its own values behind the late reply's
    got, delta, made = doc["after_late"]
    assert got == doc["want"] and made == 1 and delta[TURN] > 0
    events = osc(doc["events_late"])
    (wait,) = T.named(events, spans.OSC_REPLY_WAIT)
    unpacks = T.named(events, spans.OSC_UNPACK)
    assert [u["stats"]["bytes"] for u in unpacks] == [4 * PIECE] * 2
    assert all(T.inside(u, wait) for u in unpacks)
    assert delta[TURN] * 1e6 == pytest.approx(wait["stats"]["turn_us"],
                                              abs=0.01)
