"""A native fire in segments (ISSUE 33): real ``tpurun`` jobs.

A spanning allreduce or reduce_scatter_block of ``hier_small_message``
bytes or more runs Rabenseifner (2 and 4 processes; reduce_scatter_block
only its recursive-halving half) or the ring (3): schedules that fold
what arrives and send the fold on. Their plans have LIVE rounds (all
but reduce_scatter_block's single round at two processes) — the C walk pauses before each, the schedule body
supplies that round's sends, the same fire walks on — so they leave
``PlannedXchg``. What is held here: results bit for bit those of the
interpreted and the replayed path, one ``plan_native_fires`` per call,
the expected ``plan_native_live_rounds``, no ``plan_python_replays``;
a mixed fleet; rings far smaller than a message; calls back to back
with peers a call ahead; a peer killed between two segments; a fold
that raises.

The device-free half (which rounds are live, the executor's pause and
supply) is in ``tests/test_native_exec.py``.
"""

import pytest

from test_native_exec import _run_job, needs_native

pytestmark = needs_native

#: elements of one rank's buffer: 64 KiB and 1 MiB of float32 and a
#: little, so that 2, 3 and 4 processes of two ranks each divide them
SIZES = {"64k": 16392, "1m": 262152}

#: a process's live rounds per allreduce: Rabenseifner's rounds after
#: the first (2 log2 P - 1), the ring's two folds-sent-on at three
LIVE = {2: 1, 3: 2, 4: 3}

#: the same for reduce_scatter_block, which stops after Rabenseifner's
#: recursive-halving rounds (log2 P - 1 of them live); the ring at
#: three runs whole
LIVE_RSB = {2: 0, 3: 2, 4: 1}

SETUP = """
    from ompi_release_tpu.coll import native_exec as nx
    mca_var.set_value("hier_inter_algorithm", "auto")
    PER, N_LIVE, OPNAME, COLL = %(per)d, %(live)d, %(op)r, %(coll)r
    op = {"sum": mpi.ops.SUM, "max": mpi.ops.MAX}[OPNAME]

    def rows(salt):  # small whole numbers: every sum is exact in f32
        return [((np.arange(PER) * (r + 3) + salt) %% 509 - 254.0)
                .astype(np.float32) for r in range(n)]

    def data(salt):
        return np.stack(rows(salt)[off:off + 2])

    def want(salt):
        total = (np.sum if OPNAME == "sum" else np.max)(rows(salt), axis=0)
        if COLL == "allreduce":
            return np.stack([total] * 2)
        return np.stack([c for c in total.reshape(n, -1)[off:off + 2]])

    def call(v):
        # no process a call ahead of another: the counts are then exact
        # (an early frame popped by a reap that is still running is
        # restashed, and the next fire rightly falls back)
        out = getattr(world, COLL)(v, op)
        world.barrier()
        return np.asarray(out)

    COUNTERS = ("plan_native_fires", "plan_native_fallbacks",
                "plan_native_live_rounds", "plan_python_replays")

    def counts():
        return [_pv(c) for c in COUNTERS]
"""


def _setup(n, size, coll, op):
    live = (LIVE if coll == "allreduce" else LIVE_RSB)[n]
    return SETUP % {"per": SIZES[size], "live": live, "op": op,
                    "coll": coll}


@pytest.mark.parametrize("op", ["sum", "max"])
@pytest.mark.parametrize("coll", ["allreduce", "reduce_scatter_block"])
@pytest.mark.parametrize("size", ["64k", "1m"])
@pytest.mark.parametrize("n", [2, 3, 4])
def test_reductions_fire_in_segments_bitwise(tmp_path, capfd, n, size,
                                             coll, op):
    """The recorded (interpreted) call, three native fires with other
    data between them, and — ``coll_plan_native`` off — a recorded and
    a replayed call return the same bits, which are numpy's."""
    rc, out, _ = _run_job(tmp_path, capfd, _setup(n, size, coll, op) + """
    xa, xb = data(0), data(7)
    first = call(xa)  # interpreted: records and lowers the plan
    np.testing.assert_array_equal(first, want(0))
    c0 = counts()
    for salt, x in ((0, xa), (7, xb), (0, xa)):
        got = call(x)
        np.testing.assert_array_equal(got, want(salt))
        if salt == 0:
            np.testing.assert_array_equal(got, first)  # BITWISE
    fires, fallbacks, live, replays = (a - b for a, b in
                                       zip(counts(), c0))
    assert (fires, fallbacks, replays) == (3, 0, 0), \\
        (fires, fallbacks, replays)
    assert live == 3 * N_LIVE, live
    assert _pv("wire_native_fallback_copies") == 0
    # the same job with the executor off: recorded anew, then replayed
    mca_var.set_value("coll_plan_native", 0)
    c0 = counts()
    for _ in range(2):
        np.testing.assert_array_equal(call(xa), first)
    assert [a - b for a, b in zip(counts(), c0)] == [0, 0, 0, 1]
    print(f"LIVE-OK {me} live={live}", flush=True)
    mpi.finalize()
    """, n=n, timeout=120)
    assert rc == 0, out
    for me in range(n):
        assert f"LIVE-OK {me} " in out


@pytest.mark.parametrize("coll", ["allreduce", "reduce_scatter_block"])
@pytest.mark.parametrize("n", [2, 3, 4])
def test_mixed_fleet_one_rank_interpreted(tmp_path, capfd, n, coll):
    """The wire is byte for byte the interpreted path's: the last rank
    replays in Python (as a rank without the .so would), the others
    fire in segments, and every rank returns the same bits."""
    rc, out, _ = _run_job(tmp_path, capfd, _setup(n, "64k", coll, "sum") + """
    if me == n - 1:
        mca_var.set_value("coll_plan_native", 0)
    xa, xb = data(0), data(7)
    first = call(xa)
    np.testing.assert_array_equal(first, want(0))
    c0 = counts()
    for salt, x in ((0, xa), (7, xb), (0, xa)):
        np.testing.assert_array_equal(call(x), want(salt))
    fires, fallbacks, live, replays = (a - b for a, b in
                                       zip(counts(), c0))
    if me == n - 1:
        assert (fires, live, replays) == (0, 0, 3), (fires, live, replays)
    else:
        assert (fires, fallbacks, live, replays) == (3, 0, 3 * N_LIVE, 0), \\
            (fires, fallbacks, live, replays)
    print(f"MIXED-LIVE-OK {me}", flush=True)
    mpi.finalize()
    """, n=n, timeout=120)
    assert rc == 0, out
    for me in range(n):
        assert f"MIXED-LIVE-OK {me}" in out


@pytest.mark.parametrize("n", [2, 4])
def test_segments_through_a_small_ring(tmp_path, capfd, n):
    """64 KiB rings, 16 KiB fragments, 1 MiB a rank: every segment is
    many rings of bytes with opposing senders, so fragments meet full
    rings and the executor turns to its own arrivals — in each segment
    anew, across the pauses. No hang, the bits of the interpreted call,
    one stall per blocked record at most."""
    rc, out, _ = _run_job(tmp_path, capfd, _setup(n, "1m", "allreduce",
                                                  "sum") + """
    from ompi_release_tpu.btl import nativewire as nw
    xa, xb = data(0), data(7)
    first = call(xa)
    np.testing.assert_array_equal(first, want(0))
    c0 = counts()
    for salt, x in ((0, xa), (7, xb), (0, xa), (7, xb)):
        np.testing.assert_array_equal(call(x), want(salt))
    fires, fallbacks, live, replays = (a - b for a, b in
                                       zip(counts(), c0))
    assert (fires, fallbacks, live, replays) == (4, 0, 4 * N_LIVE, 0), \\
        (fires, fallbacks, live, replays)
    yields = _pv("plan_native_ring_yields")
    assert yields > 0, yields
    stalls = 0
    for ring in list(nw._live_tx):
        st = ring.stats()
        assert st["w_stalls"] <= st["w_frames"], st
        stalls += st["w_stalls"]
    assert yields <= stalls, (yields, stalls)
    print(f"SMALL-RING-OK {me} yields={yields}", flush=True)
    mpi.finalize()
    """, n=n, timeout=180, mca=[("btl_nativewire_ring_bytes", "65536"),
                                ("wire_pipeline_segsize", "16384")])
    assert rc == 0, out
    for me in range(n):
        assert f"SMALL-RING-OK {me} " in out


@pytest.mark.parametrize("n", [2, 4])
def test_back_to_back_calls_with_peers_a_call_ahead(tmp_path, capfd, n):
    """No barrier between calls, reductions and moves in turn, 64 KiB
    rings: a rank leaves a call while its peers still stand between two
    segments of it and sends the next call's frames on — they wait in
    the ring (the fire's locks are held across its pauses) until that
    call's fire reaps them. Every result exact; every replayed call
    either fired or, vetoed before its first segment, fell back whole."""
    rc, out, _ = _run_job(tmp_path, capfd, _setup(n, "1m", "allreduce",
                                                  "sum") + """
    tot = {s: np.sum(rows(s), axis=0) for s in (0, 7)}
    c0 = counts()
    ITERS = 12
    for it in range(ITERS):
        s = (0, 7)[it % 2]
        x = data(s)
        a = np.asarray(world.allreduce(x))
        b = np.asarray(world.reduce_scatter_block(x))
        c = np.asarray(world.bcast(x, root=1))
        g = np.asarray(world.allgather(x))
        np.testing.assert_array_equal(a[0], tot[s])
        np.testing.assert_array_equal(b[0], tot[s].reshape(n, -1)[off])
        np.testing.assert_array_equal(c[0], rows(s)[1])
        np.testing.assert_array_equal(g[0], np.concatenate(rows(s)))
    fires, fallbacks, live, replays = (a - b for a, b in
                                       zip(counts(), c0))
    assert replays == 0 and fires + fallbacks == 4 * (ITERS - 1), \
        (fires, fallbacks, replays)
    assert live <= 2 * (ITERS - 1) * N_LIVE, live
    world.barrier()
    print(f"AHEAD-OK {me} fires={fires} fallbacks={fallbacks}", flush=True)
    mpi.finalize()
    """, n=n, timeout=180, mca=[("btl_nativewire_ring_bytes", "65536"),
                                ("wire_pipeline_segsize", "16384")])
    assert rc == 0, out
    for me in range(n):
        assert f"AHEAD-OK {me} " in out


#: a user op that is SUM until told otherwise, then misbehaves in the
#: schedule's fold alone (numpy operands; the local partial is traced)
USER_OP = """
    import signal
    MODE = {"v": "sum"}

    def combine(a, b):
        if isinstance(a, np.ndarray) and MODE["v"] != "sum":
            if MODE["v"] == "raise":
                raise RuntimeError("planted: the fold failed")
            os.kill(os.getpid(), signal.SIGKILL)
        return a + b

    op = mpi.ops.user_op("planted_sum", combine, True, lambda d: 0)
"""


def test_a_fold_that_raises_leaves_no_lock_held(tmp_path, capfd):
    """Every rank's fold raises after the first segment: the call
    fails on every rank with the fold's own error, the channel, ring
    and rx-entry locks of the fire are free again
    (``SpanningPlanState.run``'s ``finally``), and the communicator's
    next collectives complete — recorded anew, then in segments."""
    rc, out, _ = _run_job(tmp_path, capfd, _setup(2, "64k", "allreduce",
                                                  "sum") + USER_OP + """
    x = data(0)
    first = call(x)
    np.testing.assert_array_equal(call(x), first)  # a native fire
    c0 = counts()
    MODE["v"] = "raise"
    try:
        call(x)
        raise AssertionError("the planted fold did not raise")
    except RuntimeError as e:
        assert "planted" in str(e), e
    MODE["v"] = "sum"
    router = world._hier_module.router
    assert not router._chan_lock("collrx", world.cid).locked()
    btl = router._nw
    locks = [ent[1] for ent in list(btl._tx.values())
             + list(btl._rx.values()) if ent[0] is not None]
    assert locks and not any(lk.locked() for lk in locks)
    world.barrier()
    for _ in range(3):  # records, lowers, fires in segments again
        np.testing.assert_array_equal(call(x), first)
    fires, fallbacks, live, replays = (a - b for a, b in
                                       zip(counts(), c0))
    # the failed call moved round 0 and counted no fire
    assert (fires, fallbacks, live, replays) == (2, 0, 2, 0), \\
        (fires, fallbacks, live, replays)
    print(f"RAISE-OK {me}", flush=True)
    mpi.finalize()
    """, n=2, timeout=120)
    assert rc == 0, out
    for me in range(2):
        assert f"RAISE-OK {me}" in out


def test_sigkill_between_two_segments_is_typed_and_fast(tmp_path, capfd):
    """Rank 1 dies in its fold, after the first segment and before it
    supplies the second: the survivors stand in (or before) a segment
    that waits for its frames, and leave it with the typed
    ERR_PROC_FAILED (or the revoke that follows) inside the detection
    interval, as ``test_sigkill_mid_plan_fire_is_typed_and_fast``."""
    rc, out, _ = _run_job(tmp_path, capfd, _setup(3, "64k", "allreduce",
                                                  "sum") + USER_OP + """
    x = data(0)
    for it in range(3):  # freeze + native fires in segments
        call(x)
    assert _pv("plan_native_live_rounds") >= 2 * N_LIVE
    if me == 1:
        MODE["v"] = "die"
    t0 = time.monotonic()
    try:
        for it in range(50):
            world.allreduce(x, op)
        raise AssertionError("collective with dead peer ran")
    except mpi.MPIError as e:
        dt = time.monotonic() - t0
        assert e.code in (mpi.ErrorCode.ERR_PROC_FAILED,
                          mpi.ErrorCode.ERR_REVOKED), e
        assert dt < 20, f"typed error took {dt:.1f}s"
        if e.code == mpi.ErrorCode.ERR_PROC_FAILED:
            assert "1" in str(e)  # names the dead process
    # the survivor's locks went with the failed fire
    router = world._hier_module.router
    assert not router._chan_lock("collrx", world.cid).locked()
    print(f"FT-LIVE-OK {me}", flush=True)
    mpi.finalize()
    """, n=3, timeout=120, job_kw={"on_failure": "continue"})
    assert rc == 0, out
    assert "FT-LIVE-OK 0" in out
    assert "FT-LIVE-OK 2" in out
