"""The point-to-point spans and counters under ``tpurun -n 2`` on the CPU.

Rank 0 holds a profiler session around one ping-pong and one window (eight
``isend`` against eight ``irecv``, then an acknowledgement back), through
the public API alone. Its trace has to hold each of the six p2p spans
(``obs/spans.py``) with the ``bytes`` that were sent, nested as documented,
and on both ranks ``pml_wire_sends``/``pml_wire_recvs``/``pml_wire_bytes``
move by exactly the messages and bytes of the calls. With no session open
the same calls write nothing and deliver the same bits.
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
PING, PIECE, WINDOW = 4096, 1024, 8  # elements: float32, so 4 bytes each

APP = textwrap.dedent("""
    import json, os, sys, time
    sys.path.insert(0, %r)
    sys.path.insert(0, %r)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np
    import ompi_release_tpu as mpi
    from ompi_release_tpu.mca import pvar
    from ompi_release_tpu.request import wait_all
    from ompi_release_tpu.tools import trace as tools_trace
    import test_obs_spans as T

    out_dir, PING, PIECE, WINDOW = sys.argv[1], *map(int, sys.argv[2:5])
    world = mpi.init()
    me = world.local_comm_ranks[0]
    peer = 1 - me
    ping = jax.device_put(np.arange(PING, dtype=np.float32) + 100 * me)
    pieces = [jax.device_put(np.arange(PIECE, dtype=np.float32) + i)
              for i in range(WINDOW)]
    COUNTERS = ("pml_wire_sends", "pml_wire_recvs", "pml_wire_bytes",
                "pml_eager_sends", "pml_rndv_sends")

    def counters():
        return {k: pvar.PVARS.lookup(k).read() for k in COUNTERS}

    def calls():
        # a ping-pong: rank 1 answers late, so rank 0's recv has to block
        if me == 0:
            world.send(ping, peer, 1, rank=me)
            pong, _ = world.recv(peer, 1, rank=me)
        else:
            pong, _ = world.recv(peer, 1, rank=me)
            time.sleep(0.2)
            world.send(ping, peer, 1, rank=me)
        # a window one way, then the acknowledgement
        if me == 0:
            wait_all([world.isend(p, peer, 100, rank=me) for p in pieces])
            time.sleep(0.05)
            got, _ = world.recv(peer, 100, rank=me)
            got = [got]
        else:
            reqs = [world.irecv(peer, 100, rank=me) for _ in pieces]
            wait_all(reqs)
            time.sleep(0.2)
            world.send(ping[:1], peer, 100, rank=me)
            got = [r.value for r in reqs]
        return [np.asarray(v).tobytes().hex() for v in [pong] + got]

    doc = {"rank": me}
    calls()  # compiles and first contacts, outside every count
    world.barrier()
    before = counters()
    if me == 0:
        with tools_trace.profiler_trace(os.path.join(out_dir, "t")):
            doc["traced"] = calls()
        doc["events"] = T.read_xplane(os.path.join(out_dir, "t"))[1]
    else:
        doc["traced"] = calls()
    after = counters()
    doc["delta"] = {k: after[k] - before[k] for k in COUNTERS}
    world.barrier()
    doc["untraced"] = calls()
    if me == 0:  # no session: the sites above wrote nothing
        with tools_trace.profiler_trace(os.path.join(out_dir, "empty")):
            pass
        doc["events_after"] = T.read_xplane(os.path.join(out_dir, "empty"))[1]
    with open(os.path.join(out_dir, "rank%%d.json" %% me), "w") as f:
        json.dump(doc, f)
    world.barrier()
    mpi.finalize()
""") % (REPO, os.path.join(REPO, "tests"))


@pytest.fixture(scope="module")
def ranks(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("p2p_spans")
    app = tmp / "app.py"
    app.write_text(APP)
    job = Job(2, [sys.executable, str(app), str(tmp), str(PING), str(PIECE),
                  str(WINDOW)], [], heartbeat_s=0.5, miss_limit=8)
    assert job.run(timeout_s=240) == 0
    docs = []
    for r in (0, 1):
        with open(tmp / f"rank{r}.json") as f:
            docs.append(json.load(f))
    return docs


def test_each_send_is_one_span_with_its_fetch_and_its_wire_leg(ranks):
    events = ranks[0]["events"]
    sends = T.named(events, spans.PML_SEND)
    sizes = [4 * PING] + [4 * PIECE] * WINDOW
    assert [s["stats"] for s in sends] == (
        [{"bytes": 4 * PING, "peer": 1, "tag": 1}]
        + [{"bytes": 4 * PIECE, "peer": 1, "tag": 100}] * WINDOW)
    d2h, legs = T.named(events, spans.PML_D2H), T.named(events, spans.WIRE_P2P_SEND)
    assert len(d2h) == len(legs) == len(sends)
    for send, fetch, leg, size in zip(sends, d2h, legs, sizes):
        assert T.inside(fetch, send) and T.inside(leg, send)
        assert fetch["t1"] <= leg["t0"]  # fetched, then written to the wire
        assert fetch["stats"] == {"bytes": size}
        assert leg["stats"]["bytes"] == size
    seqs = [leg["stats"]["seq"] for leg in legs]
    assert seqs == sorted(set(seqs))


def test_each_arrival_is_one_pump_with_its_placement(ranks):
    events = ranks[0]["events"]
    pumps, h2d = T.named(events, spans.WIRE_P2P_PUMP), T.named(events, spans.PML_H2D)
    # rank 0 received the pong and the one-element acknowledgement
    assert [p["stats"]["bytes"] for p in pumps] == [4 * PING, 4]
    assert [h["stats"] for h in h2d] == [{"bytes": 4 * PING}, {"bytes": 4}]
    assert all(T.inside(h, p) for h, p in zip(h2d, pumps))
    assert all(p["stats"]["seq"] > 0 for p in pumps)  # the SENDER's seq
    waits = T.named(events, spans.PML_RECV_WAIT)
    assert [w["stats"] for w in waits] == [{"source": 1, "tag": 1},
                                           {"source": 1, "tag": 100}]
    for wait, pump in zip(waits, pumps):  # the blocked receive pumped its own
        assert T.inside(pump, wait)
        assert wait["t1"] - wait["t0"] >= 0.15e9  # the peer answered late
    assert {e["name"] for e in events} == {
        spans.PML_SEND, spans.PML_D2H, spans.WIRE_P2P_SEND,
        spans.PML_RECV_WAIT, spans.WIRE_P2P_PUMP, spans.PML_H2D}


def test_counters_move_by_exactly_the_messages_and_bytes(ranks):
    window = 4 * PIECE * WINDOW
    assert ranks[0]["delta"] == {
        "pml_wire_sends": 1 + WINDOW, "pml_wire_bytes": 4 * PING + window,
        "pml_wire_recvs": 2, "pml_eager_sends": 0, "pml_rndv_sends": 0}
    assert ranks[1]["delta"] == {
        "pml_wire_sends": 2, "pml_wire_bytes": 4 * PING + 4,
        "pml_wire_recvs": 1 + WINDOW, "pml_eager_sends": 0,
        "pml_rndv_sends": 0}


def test_no_session_writes_nothing_and_delivers_the_same_bits(ranks):
    assert ranks[0]["events_after"] == []
    for doc in ranks:
        assert doc["traced"] == doc["untraced"]
        assert len(doc["traced"]) == (2 if doc["rank"] == 0 else 1 + WINDOW)
