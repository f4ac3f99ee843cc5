"""Native plan executor: ``coll/native_exec.py`` + ``native/planexec.cc``.

Four layers:

1. DEVICE-FREE units — the descriptor blob round-trips through the C
   parser (``build_blob`` -> ``PlanExec``), the byte-provenance
   matcher (``_match_payload``) decomposes payloads over an arena and
   refuses every ambiguous case loudly, and ``try_compile`` withdraws
   gracefully (returns None, touches nothing) when the cvar is off or
   the .so lacks the symbols.
2. SATELLITE units — ``PlannedXchg.exchange``'s per-fire fast path
   never calls ``np.asarray`` for inputs that already are ndarrays
   (monkeypatch-counted), and the striper's frame-count discipline
   gates bursts at their real cost while dropping drained streams
   without buying window for them.
3. REAL 3-process jobs — the executor engages on a recursive-doubling
   allreduce (``plan_native_fires`` advances, zero fallback copies,
   bitwise-stable results), and a mixed fleet (one rank opted out via
   the ``coll_plan_native`` cvar) interoperates frame-for-frame: the
   wire bytes are the contract, so results stay bitwise identical.
4. FAULT TOLERANCE — a SIGKILL mid-plan-fire surfaces as the typed
   ERR_PROC_FAILED naming the dead process within the detection
   interval (the C slice loop re-checks the FT epoch between 100 ms
   slices; it never turns into an untyped 30 s timeout).
"""

import os
import sys
import textwrap

import numpy as np
import pytest

from ompi_release_tpu.coll import native_exec as nx
from ompi_release_tpu.coll import plan as cplan
from ompi_release_tpu.mca import var as mca_var
from ompi_release_tpu.runtime.state import JobState
from ompi_release_tpu.runtime.wire import WireRouter
from ompi_release_tpu.tools.tpurun import Job
from ompi_release_tpu.utils.errors import ErrorCode, MPIError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

needs_native = pytest.mark.skipif(
    not nx.available(), reason="planexec symbols not in the loaded .so")


# ---------------------------------------------------------------------------
# 1. descriptor blob round-trip (device-free)
# ---------------------------------------------------------------------------

class TestBlob:
    @needs_native
    def test_round_trip_through_c_parser(self):
        """A hand-built two-round descriptor table parses: counts and
        the 8-aligned pool layout come back through the handle."""
        from ompi_release_tpu.native.bindings import PlanExec

        rounds = [
            {"depth": 2,
             "streams": [(0, [(b"PRE0", b"MID0", 64, 0, 64,
                               ((0, 0, 0, 64),))])],
             "rsrcs": [(1, [(0, 24, 0, 24, b"PRE1", b"MID1")])]},
            {"depth": 2,
             "streams": [(1, [(b"PRE2", b"MID2", 24, 0, 24,
                               ((1, 0, 0, 24),))])],
             "rsrcs": [(0, [(1, 64, 0, 64, b"PRE3", b"MID3")])]},
        ]
        blob = nx.build_blob(7, [64], [24, 64], [3, 5], rounds)
        px = PlanExec(blob)
        try:
            assert px.round_count == 2
            assert px.input_count == 1
            assert px.pool_count == 2
            # 24 is already 8-aligned, so the layout is 0 / 24 / 88
            assert px.pool_total == 88
        finally:
            px.close()

    @needs_native
    def test_garbage_blob_is_rejected(self):
        from ompi_release_tpu.native.bindings import PlanExec

        with pytest.raises(Exception):
            PlanExec(b"not a descriptor table at all")

    def test_align8(self):
        assert [nx._align8(v) for v in (0, 1, 7, 8, 9)] == \
            [0, 8, 8, 8, 16]


# ---------------------------------------------------------------------------
# 1a. the executor's CRC and its full-ring turn (device-free, one process)
# ---------------------------------------------------------------------------

_CRC_LENGTHS = [0, 1, 7, 8, 9, 63, 64, 65, 4095, 4096, 4097,
                (1 << 20) + 3]


@needs_native
class TestExecutorCrc:
    """The header CRC is the wire contract of a mixed fleet: a C
    sender's header is checked by ``zlib.crc32`` in a Python receiver
    and the reverse. Both of the executor's implementations (the
    CPU's carry-less multiply where it has one, the slicing tables)
    must be that function, from any start offset, chained or not."""

    @pytest.mark.parametrize("tables_only", [0, 1],
                             ids=["cpu", "tables"])
    @pytest.mark.parametrize("n", _CRC_LENGTHS)
    def test_equals_zlib(self, n, tables_only):
        import ctypes
        import zlib

        from ompi_release_tpu.native import bindings as nb

        lib = nb.load_library()
        rng = np.random.default_rng(n + 1)
        buf = rng.integers(0, 256, n + 16, dtype=np.uint8)
        raw = buf.tobytes()

        def crc(lo, hi, prior=0):  # of buf[lo:hi], in place
            ptr = ctypes.cast(buf.ctypes.data + lo,
                              ctypes.POINTER(ctypes.c_uint8))
            return int(lib.planexec_crc32(prior, ptr, hi - lo,
                                          tables_only))

        for off in (0, 1, 3, 8, 13):  # unaligned starts
            assert crc(off, off + n) == zlib.crc32(raw[off:off + n]), \
                (n, off)
        # chained over 2..5 segments, as scatter-gather payloads are
        for parts in (2, 3, 4, 5):
            cuts = sorted(rng.integers(0, n + 1, parts - 1).tolist())
            got = 0
            for lo, hi in zip([0] + cuts, cuts + [n]):
                got = crc(lo, hi, got)
            assert got == zlib.crc32(raw[:n]), (n, cuts)


class _ExecPair:
    """Two plan executors in one process, wired as two co-hosted
    ranks are: an OOB endpoint each (headers) and one shm ring per
    direction (fragments), each side's plan one round that sends
    ``nbytes`` to the other and/or receives as much."""

    TAG = 77
    PRE, MID = b"SGH2-pre", b"-mid-"

    def __init__(self, tmp_name, ring_bytes, nbytes, chunk,
                 a_sends=True, b_sends=True, blob_of=None):
        from ompi_release_tpu.native.bindings import (
            OobEndpoint, PlanExec, ShmRing)

        self._unlink = ShmRing.unlink
        self.nbytes, self.chunk = nbytes, chunk
        self.nchunks = -(-nbytes // chunk)
        self.eps = [OobEndpoint(1, secret=b""), OobEndpoint(2, secret=b"")]
        self.eps[0].connect(2, "127.0.0.1", self.eps[1].port)
        self.eps[1].connect(1, "127.0.0.1", self.eps[0].port)
        pid = os.getpid()
        self.names = [f"/ompitpu-t-{pid}-{tmp_name}-{d}" for d in "ab"]
        # ring 0 carries a -> b, ring 1 carries b -> a
        self.tx = [ShmRing.create(nm, ring_bytes, pid)
                   for nm in self.names]
        self.rx = [ShmRing.attach(nm, pid) for nm in self.names]
        assert all(self.tx) and all(self.rx)
        self.px = []
        for me, sends, recvs in ((0, a_sends, b_sends),
                                 (1, b_sends, a_sends)):
            send_msg = (self.PRE, self.MID, nbytes, self.nchunks, chunk,
                        ((0, 0, 0, nbytes),))
            recv_msg = (0, nbytes, self.nchunks, chunk, self.PRE,
                        self.MID)
            rounds = [{"depth": 2,
                       "streams": [(0, [send_msg] if sends else [])],
                       "rsrcs": [(0, [recv_msg] if recvs else [])]}]
            px = PlanExec(blob_of(me) if blob_of else nx.build_blob(
                self.TAG, [nbytes], [nbytes] if recvs else [],
                [1 - me], rounds))
            px.bind(self.eps[me]._h, me + 1, [2 - me],
                    [self.tx[me]._h], [self.rx[1 - me]._h])
            self.px.append(px)

    def header(self, xfer, crc):
        def rec(v):  # DSS int64 single-value record
            return b"\x01\x01\x00\x00\x00" + int(v).to_bytes(8, "little")
        return self.PRE + rec(xfer) + self.MID + rec(crc)

    def fire(self, me, data, xfer_base, out, timeout_ms=20_000):
        px = self.px[me]
        rc = px.fire_begin([data], xfer_base, timeout_ms)
        if rc == 0:
            rc = px.RC_AGAIN
            while rc == px.RC_AGAIN:
                rc = px.fire_step(100)
        out[me] = rc

    def hand_send(self, xfer, crc, wire):
        """Rank a's message as a foreign sender would put it on the
        wire: header on the endpoint, SGC2 fragments on the ring."""
        self.eps[0].send(2, self.TAG, self.header(xfer, crc))
        for ci in range(self.nchunks):
            frag = wire[ci * self.chunk:(ci + 1) * self.chunk]
            assert self.tx[0].writev(
                self.TAG, [b"SGC2" + xfer.to_bytes(8, "big"),
                           ci.to_bytes(8, "big"), frag], 1000) == 0

    def close(self):
        for px in self.px:
            px.close()
        for r in self.tx + self.rx:
            r.close()
        for nm in self.names:
            self._unlink(nm)
        for ep in self.eps:
            ep.close()


@needs_native
class TestExecutorFullRing:
    """Messages of many rings' worth through 64 KiB rings: the send
    phase must turn to its own arrivals when its ring is full, and the
    ring's stall count must stay one per blocked record. Counters
    only, never wall time."""

    RING, CHUNK = 64 << 10, 16 << 10

    @pytest.mark.parametrize("way", ["both", "one"])
    def test_opposing_and_one_way_messages(self, way):
        import threading

        nbytes = 16 * self.RING + 1000  # 16 rings and a ragged tail
        pair = _ExecPair(f"fr-{way}", self.RING, nbytes, self.CHUNK,
                         a_sends=True, b_sends=(way == "both"))
        try:
            rng = np.random.default_rng(5)
            data = [rng.integers(0, 256, nbytes, dtype=np.uint8)
                    for _ in range(2)]
            rcs = [None, None]
            ths = [threading.Thread(target=pair.fire,
                                    args=(me, data[me], 100 * (me + 1),
                                          rcs))
                   for me in range(2)]
            for t in ths:
                t.start()
            for t in ths:
                t.join(60)
            assert rcs == [0, 0], rcs
            senders = [0, 1] if way == "both" else [0]
            for me in senders:
                got = pair.px[1 - me].pool_view()[:nbytes]
                np.testing.assert_array_equal(got, data[me])
                st = pair.tx[me].stats()
                assert st["w_frames"] == pair.nchunks
                # one stall per record that found the ring full,
                # however many zero-wait retries it took
                assert st["w_stalls"] <= st["w_frames"], st
                assert pair.px[me].ring_yields() == st["w_stalls"], st
            if way == "both":
                # 16 rings each way cannot pass without a full ring
                assert sum(px.ring_yields() for px in pair.px) > 0
            else:
                assert pair.px[1].ring_yields() == 0  # sent nothing
        finally:
            pair.close()

    @pytest.mark.parametrize("fault", ["none", "payload_bit",
                                       "header_crc"])
    def test_corruption_is_truncated(self, fault):
        """The check covers every byte on the receiving side: one
        flipped bit in any fragment, or a header that promises another
        CRC, ends the fire with RC_TRUNCATED, never with data. The
        same hand-made frames with nothing altered pass (``none``), so
        the verdict is the CRC's and the CRC is zlib's."""
        import zlib

        nbytes = 5 * self.CHUNK + 17
        pair = _ExecPair(f"tr-{fault}", 1 << 20, nbytes, self.CHUNK,
                         a_sends=True, b_sends=False)
        try:
            data = np.random.default_rng(9).integers(
                0, 256, nbytes, dtype=np.uint8)
            crc = zlib.crc32(data.tobytes())
            wire = data.copy()
            if fault == "payload_bit":
                wire[3 * self.CHUNK + 5] ^= 0x10
            elif fault == "header_crc":
                crc ^= 1
            pair.hand_send(4242, crc, wire)
            rcs = [None, None]
            pair.fire(1, data, 1, rcs, timeout_ms=10_000)
            px = pair.px[1]
            if fault == "none":
                assert rcs[1] == px.RC_DONE, rcs
                np.testing.assert_array_equal(
                    px.pool_view()[:nbytes], data)
            else:
                assert rcs[1] == px.RC_TRUNCATED, rcs
        finally:
            pair.close()


def _live_pair_blob(me, nbytes, chunk, pre, mid, live=1, segs=None):
    """Two rounds with the one peer: round 0 sends input 0 and receives
    into pool 0; round 1 (live) sends what is supplied and receives
    into pool 1."""
    nchunks = -(-nbytes // chunk)

    def rnd(is_live, pool_idx, sg):
        return {"depth": 2, "live": is_live,
                "streams": [(0, [(pre, mid, nbytes, nchunks, chunk, sg)])],
                "rsrcs": [(0, [(pool_idx, nbytes, nchunks, chunk, pre,
                                mid)])]}

    return nx.build_blob(
        _ExecPair.TAG, [nbytes], [nbytes, nbytes], [1 - me],
        [rnd(0, 0, ((0, 0, 0, nbytes),)),
         rnd(live, 1, segs or ((2, 0, 0, nbytes),))])


@needs_native
class TestExecutorLiveRounds:
    """ISSUE 33: a fire runs in segments. The walk pauses in front of a
    live round (``RC_PAUSE``), ``fire_supply`` hands it that round's
    sends, and the same fire — same xfer ids, same slab — walks on."""

    NB, CHUNK = 40_000, 16 << 10

    def _pair(self, name):
        return _ExecPair(
            name, 1 << 20, self.NB, self.CHUNK,
            blob_of=lambda me: _live_pair_blob(
                me, self.NB, self.CHUNK, _ExecPair.PRE, _ExecPair.MID))

    @staticmethod
    def _walk(px):
        rc = px.RC_AGAIN
        while rc == px.RC_AGAIN:
            rc = px.fire_step(100)
        return rc

    def test_pause_supply_and_walk_on(self):
        import threading

        pair = self._pair("live-ok")
        try:
            rng = np.random.default_rng(33)
            data = [rng.integers(0, 256, self.NB, dtype=np.uint8)
                    for _ in range(2)]
            folds, rcs = [None, None], [[], []]

            def rank(me):
                px = pair.px[me]
                assert px.fire_begin([data[me]], 100 * (me + 1),
                                     20_000) == 0
                rcs[me].append(self._walk(px))      # round 0, then pause
                # nothing to supply twice, nothing of another size
                got = np.array(px.pool_view()[:self.NB])
                folds[me] = data[me] + got          # the schedule's fold
                rcs[me].append(px.fire_supply([folds[me][:-1]]))
                rcs[me].append(px.fire_supply([folds[me]]))
                rcs[me].append(px.fire_supply([folds[me]]))
                rcs[me].append(self._walk(px))      # round 1, to the end

            ths = [threading.Thread(target=rank, args=(me,))
                   for me in range(2)]
            for t in ths:
                t.start()
            for t in ths:
                t.join(60)
            px = pair.px[0]
            want = [px.RC_PAUSE, px.RC_BADARG, 0, px.RC_BADARG,
                    px.RC_DONE]
            assert rcs == [want, want], rcs
            off1 = nx._align8(self.NB)
            for me in range(2):
                pool = pair.px[me].pool_view()
                np.testing.assert_array_equal(pool[:self.NB],
                                              data[1 - me])
                np.testing.assert_array_equal(
                    pool[off1:off1 + self.NB], folds[1 - me])
                # both rounds' end stamps, in order
                ts = pair.px[me].round_ts()
                assert 0 < ts[0] <= ts[1]
                pair.px[me].fire_end()
        finally:
            pair.close()

    def test_an_abandoned_fire_leaves_the_executor_usable(self):
        """A schedule that raises between two segments: ``fire_end``
        leaves the fire, and the executor's next fire starts clean."""
        import threading

        pair = self._pair("live-abort")
        try:
            data = [np.full(self.NB, 3 + me, np.uint8) for me in range(2)]
            for attempt in range(2):
                rcs = [None, None]

                def rank(me):
                    px = pair.px[me]
                    assert px.fire_begin([data[me]], 1000 * attempt
                                         + 100 * (me + 1), 20_000) == 0
                    rc = self._walk(px)
                    if attempt == 0:
                        px.fire_end()   # both sides leave at the pause
                        assert px.fire_step(100) == px.RC_BADARG
                        assert px.fire_supply([data[me]]) == px.RC_BADARG
                    else:
                        assert px.fire_supply([data[me]]) == 0
                        rc = self._walk(px)
                    rcs[me] = rc

                ths = [threading.Thread(target=rank, args=(me,))
                       for me in range(2)]
                for t in ths:
                    t.start()
                for t in ths:
                    t.join(60)
                px = pair.px[0]
                assert rcs == ([px.RC_PAUSE] * 2 if attempt == 0
                               else [px.RC_DONE] * 2), rcs
        finally:
            pair.close()

    @pytest.mark.parametrize("case", ["mapped_as_live", "live_as_mapped",
                                      "partial", "wrong_index"])
    def test_parser_holds_a_live_round_to_its_form(self, case):
        """A live round's message is ONE supplied array, whole; a
        mapped round never reads a supplied one."""
        from ompi_release_tpu.native.bindings import PlanExec

        nb = 64
        live, segs = {
            "mapped_as_live": (1, ((0, 0, 0, nb),)),
            "live_as_mapped": (0, ((2, 0, 0, nb),)),
            "partial": (1, ((2, 0, 0, nb // 2), (1, 0, 0, nb // 2))),
            "wrong_index": (1, ((2, 1, 0, nb),)),
        }[case]
        with pytest.raises(MPIError):
            PlanExec(_live_pair_blob(0, nb, nb, b"P", b"M", live, segs))
        PlanExec(_live_pair_blob(0, nb, nb, b"P", b"M")).close()


@needs_native
class TestSlabViews:
    """What a native fire hands a schedule (ISSUE 31): read-only views
    of the executor's slab for the collectives that only move their
    arrivals, copies for those that fold them."""

    NB = 4096

    def _fired(self, name, fill):
        pair = _ExecPair(name, 1 << 20, self.NB, 1024, a_sends=True,
                         b_sends=False)
        data = np.full(self.NB // 4, fill, np.int32)
        rcs = [None, None]
        import threading

        th = threading.Thread(target=pair.fire, args=(0, data, 10, rcs))
        th.start()
        pair.fire(1, data, 20, rcs)
        th.join(30)
        assert rcs == [0, 0], rcs
        return pair, data

    def _xchg(self, px, views):
        npl = nx.NativePlan()
        npl.cid = 0  # the stat of ``ompi.plan.arrivals``
        npl.pool_rounds = [[(0, [(0, 0, (2, self.NB // 8),
                                  np.dtype("int32"), self.NB)])]]
        x = nx.NativeXchg(None, None, npl, (), views=views)
        x._pool = px.pool_view()
        return x

    def test_views_are_the_slab_read_only_and_hold_the_executor(self):
        pair, data = self._fired("sv-view", 7)
        try:
            px = pair.px[1]
            copied = nx._pool_copy_bytes.read()
            (a,) = self._xchg(px, views=True)._arrivals(0)[0]
            assert a.shape == (2, self.NB // 8) and a.dtype == np.int32
            np.testing.assert_array_equal(a.reshape(-1), data)
            assert not a.flags.writeable and not a.flags.owndata
            with pytest.raises(ValueError):
                a[0, 0] = 1
            with pytest.raises(ValueError):
                a.setflags(write=True)
            assert nx._pool_copy_bytes.read() == copied
            base = a
            while isinstance(base, np.ndarray):
                base = base.base
            # a slice keeps the slab alive (the read-only buffer under
            # numpy's base chain holds the executor)
            assert base.readonly and base.obj._owner is px
            # the slab is REUSED: the plan's next fire lands in it
            rcs = [None, None]
            import threading

            fresh = np.full(self.NB // 4, 9, np.int32)
            th = threading.Thread(target=pair.fire,
                                  args=(0, fresh, 30, rcs))
            th.start()
            pair.fire(1, fresh, 40, rcs)
            th.join(30)
            assert rcs == [0, 0], rcs
            np.testing.assert_array_equal(a.reshape(-1), fresh)
        finally:
            pair.close()

    def test_copies_for_schedules_that_fold(self):
        pair, data = self._fired("sv-copy", 3)
        try:
            copied = nx._pool_copy_bytes.read()
            (a,) = self._xchg(pair.px[1], views=False)._arrivals(0)[0]
            assert a.flags.writeable and a.flags.owndata
            np.testing.assert_array_equal(a.reshape(-1), data)
            assert nx._pool_copy_bytes.read() - copied == self.NB
        finally:
            pair.close()

    def test_view_ops_are_the_data_movement_collectives(self):
        assert nx.VIEW_OPS == {"allgather", "alltoall", "bcast", "gather"}
        assert nx.VIEW_OPS <= cplan._PLANNABLE

    def test_closing_a_plan_leaves_the_slab_to_its_views(self):
        """``NativePlan.close`` drops its reference; the C side goes
        with the last one, so a view read after a re-plan is sound."""
        from ompi_release_tpu.native.bindings import PlanExec

        rounds = [{"depth": 2, "streams": [],
                   "rsrcs": [(0, [(0, 64, 0, 64, b"P", b"M")])]}]
        npl = nx.NativePlan()
        npl.px = PlanExec(nx.build_blob(7, [], [64], [3], rounds))
        view = npl.px.pool_view()
        npl.close()
        assert npl.px is None
        assert int(view.sum()) == 0 and view.base.obj._owner._h  # still there


def _hier_counters():
    from ompi_release_tpu.coll import hier

    return (hier._assembled.read(), hier._assembled_bytes.read())


class _JoinModule:
    """``_HierModule``'s ``_assemble`` on a hand-made layout: two
    processes of two members each, this one being process ``me``."""

    def __new__(cls, host, local_n=2, me=0):
        import types

        from ompi_release_tpu.coll import hier

        m = object.__new__(hier._HierModule)
        m.local_n, m._host_join, m._xchg = local_n, host, object()
        m.procs, m.my_pidx = [0, 1], me
        m.comm = types.SimpleNamespace(size=2 * local_n)
        # block 0 is this process's, block 1 the peer's
        m._rows = tuple((int(r // local_n != me), r % local_n)
                        for r in range(2 * local_n))
        return m


def _slab(shape, seed):
    """An int32 'arrival' as a native fire hands it over: a read-only
    view into a 64-byte aligned buffer, which the CPU backend would
    keep instead of copying. Returns (view, the buffer to overwrite)."""
    from ompi_release_tpu.coll import hier

    n = int(np.prod(shape))
    buf = hier._aligned_empty((n,), np.int32)
    buf[:] = np.random.default_rng(seed).integers(0, 1 << 30, n)
    view = buf.reshape(shape)[...]
    view.setflags(write=False)
    return view, buf


class TestAssemble:
    """One pass from the slab to the caller's array (ISSUE 31): the two
    joins give the same array as the numpy the four collectives used to
    run, and nothing of it aliases the slab, which the plan's next fire
    overwrites. Each case fails on a tree that hands the caller the
    arrival itself (``jnp.asarray`` of an aligned view keeps it)."""

    JOINS = pytest.mark.parametrize("host", [True, False],
                                    ids=["host_join", "device_join"])

    @staticmethod
    def _own(block):  # the caller's buffer, as jax holds it
        import jax.numpy as jnp

        return jnp.asarray(block)

    @JOINS
    @pytest.mark.parametrize("member", [None, 1],
                             ids=["allgather", "gather"])
    @pytest.mark.parametrize("row", [(), (3,), (4, 5)],
                             ids=["0d", "1d", "2d"])
    def test_rows(self, host, member, row):
        m = _JoinModule(host, me=1)
        block = np.arange(2 * int(np.prod(row)), dtype=np.int32).reshape(
            (2,) + row) - 100
        theirs, buf = _slab((2,) + row, 1)
        rows = list(theirs) + list(block)  # process 0's rows come first
        full = np.stack(rows) if not row else np.concatenate(rows)
        want = np.zeros((2,) + full.shape, np.int32)
        want[slice(None) if member is None else member] = full
        before = _hier_counters()
        out = m._assemble_rows(self._own(block), block, {0: theirs},
                               member=member)
        buf[:] = -1  # the plan's next fire
        assert out.shape == want.shape and out.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(out), want)
        assert _hier_counters() == (before[0] + 1,
                                      before[1] + want.nbytes)

    @JOINS
    @pytest.mark.parametrize("local_n", [1, 2])
    def test_bcast_off_the_root_and_at_it(self, host, local_n):
        m = _JoinModule(host, local_n=local_n)
        val, buf = _slab((6, 2), 2)
        want = np.broadcast_to(np.array(val)[None], (local_n, 6, 2))
        out = m._assemble(val, val, (), ((None, 0, 0, 12),),
                          (local_n, 6, 2))
        buf[:] = -1
        np.testing.assert_array_equal(np.asarray(out), want)
        # at the root the slice is cut from the caller's own buffer
        x = np.arange(local_n * 12, dtype=np.int32).reshape(local_n, 6, 2)
        out = m._assemble(self._own(x), x, (),
                          ((None, 0, (local_n - 1) * 12, 12),),
                          (local_n, 6, 2))
        np.testing.assert_array_equal(
            np.asarray(out), np.broadcast_to(x[-1][None], x.shape))

    @JOINS
    def test_alltoall_interleaves_by_source_rank(self, host):
        m = _JoinModule(host, me=1)  # my members: comm ranks 2, 3
        n, c = 4, 3
        x = np.arange(2 * n * c * 2, dtype=np.int32).reshape(2, n * c, 2)
        chunks = x.reshape(2, n, c, 2)
        recv, buf = _slab((2, 2, c, 2), 3)  # [a, b]: 0's member a -> my b
        want = np.empty_like(chunks)
        for a, i in enumerate((2, 3)):
            for b, j in enumerate((2, 3)):
                want[b, i] = chunks[a, j]
        for a, i in enumerate((0, 1)):
            for b in range(2):
                want[b, i] = recv[a, b]
        cf = c * 2  # elements of one rank-pair chunk
        pieces = tuple(
            (b, 1, (i * 2 + b) * cf, cf) if i < 2 else
            (b, 0, ((i - 2) * n + (2, 3)[b]) * cf, cf)
            for b in range(2) for i in range(n))
        out = m._assemble(self._own(x), x, [recv], pieces, x.shape)
        buf[:] = -1
        np.testing.assert_array_equal(np.asarray(out),
                                      want.reshape(x.shape))

    def test_the_probe_builds_nothing(self):
        from ompi_release_tpu.coll import base

        m = _JoinModule(True)
        m._xchg = nx._ProbeXchg(None, {})
        before = _hier_counters()
        zeros = np.zeros(4, np.int32)
        assert m._assemble(zeros, zeros, (), ((None, 0, 0, 4),),
                           (2, 4)) is base.NO_RESULT
        assert _hier_counters() == before

    def test_host_join_hands_jax_a_buffer_it_keeps(self):
        """The host rank's result is written once: the CPU backend takes
        the aligned buffer as the array's own (no second copy)."""
        from ompi_release_tpu.coll import hier

        a = hier._aligned_empty((3, 1000), np.float32)
        assert a.ctypes.data % 64 == 0 and a.shape == (3, 1000)
        assert hier._aligned_empty((), np.int32).shape == ()
        import jax.numpy as jnp

        a[:] = 1.5
        assert jnp.asarray(a).unsafe_buffer_pointer() == a.ctypes.data


# ---------------------------------------------------------------------------
# 1b. byte-provenance matcher (device-free)
# ---------------------------------------------------------------------------

def _arena_of(*regions):
    """Build an arena the way the prober does: random separators
    around every region; returns (arena, a_arr, bounds)."""
    rng = np.random.default_rng(0xBEEF)
    arrs = [np.frombuffer(r, dtype=np.uint8) for r in regions]
    arena, bounds = nx._build_arena(
        rng, arrs, [])  # all regions as "inputs"
    return arena, np.frombuffer(arena, dtype=np.uint8), bounds


class TestMatchPayload:
    def test_whole_region_and_slice(self):
        rng = np.random.default_rng(1)
        r0 = rng.bytes(64)
        arena, a_arr, bounds = _arena_of(r0)
        segs = nx._match_payload(r0, arena, a_arr, bounds)
        assert segs == ((0, 0, 0, 64),)
        segs = nx._match_payload(r0[16:48], arena, a_arr, bounds)
        assert segs == ((0, 0, 16, 32),)

    def test_concatenation_across_regions(self):
        """A payload stitched from two source regions decomposes into
        two segs — the scatter-gather form the C executor emits."""
        rng = np.random.default_rng(2)
        r0, r1 = rng.bytes(64), rng.bytes(64)
        arena, a_arr, bounds = _arena_of(r0, r1)
        segs = nx._match_payload(r0[:32] + r1[32:], arena, a_arr,
                                 bounds)
        assert segs == ((0, 0, 0, 32), (0, 1, 32, 32))

    def test_adjacent_spans_merge(self):
        rng = np.random.default_rng(3)
        r0 = rng.bytes(256)
        arena, a_arr, bounds = _arena_of(r0)
        # one contiguous source span must come back as ONE seg even
        # though matching proceeds window by window
        segs = nx._match_payload(r0, arena, a_arr, bounds)
        assert len(segs) == 1

    def test_duplicate_regions_resolve_deterministically(self):
        """Bytes appearing in two regions (a round-0 send aliasing an
        argument) resolve to a FIXED pick — longest span, then lowest
        arena offset — so both probe seeds infer the same map and the
        cross-probe equality proof stays meaningful."""
        rng = np.random.default_rng(4)
        dup = rng.bytes(32)
        arena, a_arr, bounds = _arena_of(dup, dup)
        assert nx._match_payload(dup, arena, a_arr, bounds) == \
            ((0, 0, 0, 32),)
        # the longer candidate wins even when it sits later
        rng = np.random.default_rng(7)
        tail = rng.bytes(32)
        arena, a_arr, bounds = _arena_of(dup, dup + tail)
        assert nx._match_payload(dup + tail, arena, a_arr, bounds) \
            == ((0, 1, 0, 64),)

    def test_foreign_bytes_fail(self):
        rng = np.random.default_rng(5)
        arena, a_arr, bounds = _arena_of(rng.bytes(64))
        with pytest.raises(nx._ProbeFail):
            nx._match_payload(rng.bytes(32), arena, a_arr, bounds)

    def test_tiny_payload_fails(self):
        rng = np.random.default_rng(6)
        r0 = rng.bytes(64)
        arena, a_arr, bounds = _arena_of(r0)
        with pytest.raises(nx._ProbeFail):
            nx._match_payload(r0[:8], arena, a_arr, bounds)


# ---------------------------------------------------------------------------
# 1b'. which rounds are live: found by the probe, not declared (device-free)
# ---------------------------------------------------------------------------

class _LoopXchg:
    """One rank's exchange adapter over in-process queues: P threads
    run one schedule in lockstep, the way P processes do."""

    def __init__(self, queues, me):
        self.q, self.me = queues, me

    def exchange(self, sends, recvs):
        for dst in sorted(sends):
            for a in sends[dst]:
                self.q[(self.me, dst)].put(np.array(a))
        return {src: [self.q[(src, self.me)].get(timeout=30)
                      for _ in range(int(c))]
                for src, c in recvs.items() if int(c) > 0}


class _ProbedModule:
    """What ``_infer_maps`` needs of a hier module: ``_xchg``."""

    def __init__(self, xchg):
        self._xchg = xchg


def _frozen_probe(schedule, P, me, mine_of, cid=5):
    """Record ``schedule(x, procs, rank, mine)`` on P threads, freeze
    rank ``me``'s rounds by hand (no wire: nothing is templated) and
    return ``_infer_maps``'s arguments for it."""
    import queue
    import threading

    procs = list(range(P))
    qs = {(a, b): queue.Queue() for a in procs for b in procs if a != b}
    recs = [cplan.RoundRecorder(_LoopXchg(qs, r)) for r in procs]
    errs = []

    def rank(r):
        try:
            schedule(recs[r], procs, r, mine_of(r))
        except BaseException as e:  # surfaced below, with its rank
            errs.append((r, e))

    ths = [threading.Thread(target=rank, args=(r,)) for r in procs]
    for t in ths:
        t.start()
    for t in ths:
        t.join(60)
    assert not errs, errs
    rec = recs[me]
    rounds = [cplan.WireRound(sm, rt, tuple((p, (None,) * len(a))
                                            for p, a in sm),
                              9, 2, recvs_meta=meta)
              for (sm, rt), meta in zip(rec.rounds, rec.recv_metas)]
    plan = _Plan()
    plan.rounds, plan.cid = rounds, cid
    m = _ProbedModule(None)
    # hier hands a schedule the fetched partial: a read-only array
    fn = lambda mine: schedule(m._xchg, procs, me, np.array(mine))  # noqa: E731
    return plan, m, fn, (mine_of(me),), {}, (0,)


def _mine(n, dtype):
    return lambda r: (np.arange(n) * (r + 3) % 251).astype(dtype)


class TestLiveRounds:
    """ISSUE 33, item 1: ``_infer_maps`` marks a round live where a
    message cannot be covered whole by region bytes, both probes alike;
    no schedule names, no size test."""

    OPS = pytest.mark.parametrize(
        "op,dtype", [(np.add, "int32"), (np.maximum, "float32")],
        ids=["sum", "max"])

    @staticmethod
    def _reduce(fn, op, dtype):
        from ompi_release_tpu.coll import hier_schedules as hs

        ident = 0 if op is np.add else -np.inf
        return lambda x, procs, me, mine: getattr(hs, fn)(
            x, procs, me, mine, op, np.dtype(dtype).type(ident))

    @OPS
    @pytest.mark.parametrize("P,me,want", [(2, 0, {1}), (2, 1, {1}),
                                           (4, 0, {1, 2, 3}),
                                           (4, 3, {1, 2, 3})])
    def test_rabenseifner_sends_folds_after_round_0(self, op, dtype, P,
                                                    me, want):
        """Halving sends what it has just folded; the last doubling
        round at four sends a fold beside a slab region: live as a
        whole."""
        probe = _frozen_probe(
            self._reduce("allreduce_rabenseifner", op, dtype), P, me,
            _mine(4096, dtype))
        maps, live = nx._infer_maps(*probe)
        assert len(probe[0].rounds) == 2 * (P.bit_length() - 1)
        assert live == want
        assert all((maps[r] is None) == (r == 0 or r in live)
                   for r in range(len(maps)))

    @OPS
    @pytest.mark.parametrize("me", [0, 2])
    def test_ring_at_three_forwards_its_last_arrival(self, op, dtype, me):
        """Rounds 1 and 2 send folds; round 3 sends on what round 2
        brought: mapped, from the slab, C's own."""
        probe = _frozen_probe(self._reduce("allreduce_ring", op, dtype),
                              3, me, _mine(3 * 1024, dtype))
        maps, live = nx._infer_maps(*probe)
        assert len(probe[0].rounds) == 4 and live == {1, 2}
        (segs,) = maps[3]  # one message, one span: round 2's arrival
        assert [sg[0] for sg in segs] == [1] and segs[0][2:] == (0, 4096)

    @pytest.mark.parametrize("name", ["bcast", "allgather", "alltoall",
                                      "alltoall_bruck"])
    def test_schedules_that_only_move_have_no_live_round(self, name):
        from ompi_release_tpu.coll import hier_schedules as hs

        P, n = 4, 1024
        sched = {
            "bcast": lambda x, procs, me, mine: hs.bcast_binomial(
                x, procs, me, 0, mine),
            "allgather": lambda x, procs, me, mine: hs.allgather_bruck(
                x, procs, me, mine, [n] * P),
            "alltoall": lambda x, procs, me, mine: hs.alltoall_pairwise(
                x, procs, me, {p: mine[p * n:(p + 1) * n] for p in procs}),
            "alltoall_bruck": lambda x, procs, me, mine: hs.alltoall_bruck(
                x, procs, me, [mine[p * n:(p + 1) * n] for p in procs],
                [[n] * P] * P),
        }[name]
        size = n if name in ("bcast", "allgather") else n * P
        # rank 2 of a binomial bcast receives, then forwards
        probe = _frozen_probe(sched, P, 2, _mine(size, "int32"))
        maps, live = nx._infer_maps(*probe)
        assert live == frozenset() and len(probe[0].rounds) >= 2
        assert all(m is not None for m in maps[1:])

    def test_a_short_payload_is_live_not_a_withdrawal(self):
        """Eight bytes cannot be proven (no 16-byte window): the
        schedule supplies them, which is always right."""
        probe = _frozen_probe(
            self._reduce("allreduce_ring", np.add, "int32"), 3, 1,
            _mine(6, "int32"))
        _maps, live = nx._infer_maps(*probe)
        assert live == {1, 2, 3}

    def test_a_mapped_round_after_a_live_one_reads_only_the_slab(
            self, monkeypatch):
        """Between two segments the schedule body runs on and may write
        to the arrays it holds: a later round mapped onto a caller's
        array (kind 0) is made live instead."""
        probe = _frozen_probe(
            self._reduce("allreduce_ring", np.add, "int32"), 3, 0,
            _mine(3 * 1024, "int32"))
        real = nx._match_payload

        def from_an_input(pay, arena, a_arr, bounds):
            segs = real(pay, arena, a_arr, bounds)
            return tuple((0, 0, sg[2], sg[3]) if sg[0] == 1 else sg
                         for sg in segs)

        monkeypatch.setattr(nx, "_match_payload", from_an_input)
        _maps, live = nx._infer_maps(*probe)
        assert live == {1, 2, 3}

    def test_probes_that_disagree_on_the_live_set_withdraw(
            self, monkeypatch):
        probe = _frozen_probe(
            self._reduce("allreduce_ring", np.add, "int32"), 3, 0,
            _mine(3 * 1024, "int32"))
        real, calls = nx._match_payload, []

        def second_probe_loses_round_3(pay, arena, a_arr, bounds):
            calls.append(1)
            if len(calls) == 6:  # three rounds a probe, one message each
                raise nx._ProbeFail("planted")
            return real(pay, arena, a_arr, bounds)

        monkeypatch.setattr(nx, "_match_payload",
                            second_probe_loses_round_3)
        with pytest.raises(nx._ProbeFail, match="live rounds"):
            nx._infer_maps(*probe)
        assert len(calls) == 6


# ---------------------------------------------------------------------------
# 1c. graceful withdrawal
# ---------------------------------------------------------------------------

class _Plan:
    def __init__(self):
        rnd = cplan.WireRound(((1, (((4,), "int32"),)),), ((1, 1),),
                              ((1, (None,)),), 9, 2)
        self.rounds = [rnd]
        self.gen = 0
        self.cid = 1
        self.timeout_ms = 1000


class _State:
    def __init__(self):
        self.plan = _Plan()


class TestWithdrawal:
    def test_cvar_off_withdraws(self):
        old = mca_var.get("coll_plan_native", True)
        mca_var.set_value("coll_plan_native", 0)
        try:
            # m is never touched once the cvar says no
            assert nx.try_compile(_State(), object(), None, (), {}) \
                is None
        finally:
            mca_var.set_value("coll_plan_native", old)

    def test_missing_symbols_withdraw(self, monkeypatch):
        monkeypatch.setattr(nx, "available", lambda: False)
        assert nx.try_compile(_State(), object(), None, (), {}) is None

    def test_inline_sentinel_withdraws(self):
        # obs_sentinel=2 interleaves ctl frames with the planned
        # rounds — the C reap would stash them mid-fire, so the
        # executor must leave inline-checked comms to PlannedXchg
        # (the gate once read a nonexistent cvar name and engaged
        # anyway, derailing the sentinel's posting seq)
        old = mca_var.get("obs_sentinel", 0)
        mca_var.set_value("obs_sentinel", 2)
        try:
            assert nx.try_compile(_State(), object(), None, (), {}) \
                is None
        finally:
            mca_var.set_value("obs_sentinel", old)

    def test_try_compile_never_raises(self):
        # a state with no plan, then one whose module explodes on
        # attribute access: both are selection outcomes, not errors
        class _NoPlan:
            plan = None

        assert nx.try_compile(_NoPlan(), object(), None, (), {}) is None

        class _Hostile:
            def __getattr__(self, k):
                raise RuntimeError("boom")

        assert nx.try_compile(_State(), _Hostile(), None, (), {}) \
            is None


# ---------------------------------------------------------------------------
# 2a. satellite: PlannedXchg per-fire asarray skip
# ---------------------------------------------------------------------------

class _FakeModule:
    """Minimal stand-in honoring the slice of the hier-module contract
    PlannedXchg uses: planned sends and arrival-order reaping."""

    def __init__(self, arrivals):
        self.arrivals = arrivals
        self.sent = []

        class _C:
            name = "fake_comm"

        self.comm = _C()

    def _send_all_planned(self, rnd, sends):
        self.sent.append((rnd, sends))

    def _reap(self, recvs, cb, timeout_ms, record=True):
        for src, cnt in sorted(recvs.items()):
            for k in range(cnt):
                cb(src, self.arrivals[src][k])


def _one_round_plan(peer=1, src=2, shape=(8,), dtype="int32"):
    rnd = cplan.WireRound(
        ((peer, ((shape, dtype),)),), ((src, 1),),
        ((peer, (None,)),), 11, 2)
    return cplan.WirePlan(0, 1, [rnd], 1000)


class TestAsarraySkip:
    def test_as_nd_is_identity_for_ndarrays(self, monkeypatch):
        calls = []
        real = np.asarray
        monkeypatch.setattr(
            cplan, "_np_asarray",
            lambda a, *k, **kw: calls.append(1) or real(a, *k, **kw))
        a = np.arange(4, dtype=np.int32)
        assert cplan._as_nd(a) is a
        assert not calls
        assert cplan._as_nd([1, 2]).tolist() == [1, 2]
        assert len(calls) == 1

    def test_round_meta_skips_converted_inputs(self, monkeypatch):
        calls = []
        real = np.asarray
        monkeypatch.setattr(
            cplan, "_np_asarray",
            lambda a, *k, **kw: calls.append(1) or real(a, *k, **kw))
        a = np.arange(8, dtype=np.int32)
        meta = cplan._round_meta({1: [a, a]})
        assert meta == ((1, (((8,), "int32"), ((8,), "int32"))),)
        assert not calls

    def test_planned_exchange_zero_asarray_for_ndarrays(
            self, monkeypatch):
        """The per-fire fast path: ndarray sends ride straight into
        the comparison tuple — zero conversions per exchange."""
        arr = np.arange(8, dtype=np.int32)
        m = _FakeModule({2: [np.ones(3, np.int32)]})
        px = cplan.PlannedXchg(m, _one_round_plan())
        calls = []
        real = np.asarray
        monkeypatch.setattr(
            cplan, "_np_asarray",
            lambda a, *k, **kw: calls.append(1) or real(a, *k, **kw))
        got = px.exchange({1: [arr]}, {2: 1})
        assert not calls
        assert got[2][0].tolist() == [1, 1, 1]
        # the planned send saw the SAME array object — no copy
        assert m.sent[0][1][1][0] is arr

    def test_planned_exchange_divergence_is_typed(self):
        m = _FakeModule({2: [np.ones(3, np.int32)]})
        px = cplan.PlannedXchg(m, _one_round_plan())
        with pytest.raises(MPIError) as ei:
            px.exchange({1: [np.zeros((9, 9), np.float64)]}, {2: 1})
        assert ei.value.code == ErrorCode.ERR_INTERN
        assert "diverged" in str(ei.value)


# ---------------------------------------------------------------------------
# 2b. satellite: frame-count-exact stripe gating
# ---------------------------------------------------------------------------

class _Arb:
    def __init__(self):
        self.events = []

    def enter(self, cls):
        self.events.append(("enter", cls))

    def gate(self, cls, cost=1):
        self.events.append(("gate", cls, cost))

    def leave(self, cls):
        self.events.append(("leave", cls))


def _gen(log, label, n):
    for k in range(n):
        log.append((label, k))
        yield


class TestStripeCounts:
    def test_partial_tail_gates_at_real_cost(self):
        """counts=(5, 2), depth=3: stream B's single burst costs 2,
        stream A's tail burst costs 2 — never the full depth."""
        log, arb = [], _Arb()
        WireRouter._stripe([_gen(log, "a", 5), _gen(log, "b", 2)], 3,
                           arbiter=arb, cls="bulk", counts=(5, 2))
        gates = [e for e in arb.events if e[0] == "gate"]
        assert gates == [("gate", "bulk", 3), ("gate", "bulk", 2),
                         ("gate", "bulk", 2)]
        assert len([e for e in log if e[0] == "a"]) == 5
        assert len([e for e in log if e[0] == "b"]) == 2
        assert arb.events[0] == ("enter", "bulk")
        assert arb.events[-1] == ("leave", "bulk")

    def test_drained_stream_is_dropped_without_gating(self):
        """A zero-count stream must not pass the gate NOR be pulled:
        window bought for frames that never exist starves the other
        classes for nothing."""
        log, arb = [], _Arb()
        WireRouter._stripe([_gen(log, "a", 4), _gen(log, "dead", 9)],
                           2, arbiter=arb, cls="lat", counts=(4, 0))
        gates = [e for e in arb.events if e[0] == "gate"]
        assert gates == [("gate", "lat", 2), ("gate", "lat", 2)]
        assert not [e for e in log if e[0] == "dead"]

    def test_legacy_no_counts_gates_full_depth(self):
        """Without counts (interpreted path) behavior is unchanged:
        every live stream's burst is gated at the full depth."""
        log, arb = [], _Arb()
        WireRouter._stripe([_gen(log, "a", 4)], 3,
                           arbiter=arb, cls="c", counts=None)
        gates = [e for e in arb.events if e[0] == "gate"]
        assert gates == [("gate", "c", 3), ("gate", "c", 3)]
        assert len(log) == 4

    def test_no_arbiter_counts_still_bound_pulls(self):
        log = []
        g = _gen(log, "a", 9)
        WireRouter._stripe([g], 4, counts=(6,))
        # exactly the counted frames were pulled, none past the plan
        assert len(log) == 6


# ---------------------------------------------------------------------------
# 3 + 4. real 3-process jobs
# ---------------------------------------------------------------------------

APP_PRELUDE = textwrap.dedent("""
    import os, sys, time
    sys.path.insert(0, %r)
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2"
    ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import ompi_release_tpu as mpi
    from ompi_release_tpu.mca import pvar, var as mca_var
    from ompi_release_tpu.runtime.runtime import Runtime

    def _pv(name):
        p = pvar.PVARS.lookup(name)
        return float(p.read()) if p is not None else 0.0

    world = mpi.init()
    rt = Runtime.current()
    me = rt.bootstrap["process_index"]
    off = rt.local_rank_offset
    n = world.size
    mca_var.set_value("hier_inter_algorithm", "recursive_doubling")
""" % REPO)


def _run_job(tmp_path, capfd, body, n=3, timeout=240, job_kw=None,
             mca=()):
    app = tmp_path / "app.py"
    app.write_text(APP_PRELUDE + textwrap.dedent(body))
    job = Job(n, [sys.executable, str(app)], list(mca),
              heartbeat_s=0.5, miss_limit=8, **(job_kw or {}))
    rc = job.run(timeout_s=timeout)
    out = capfd.readouterr()
    return rc, out.out + out.err, job


class TestNativeJobs:
    def test_native_engages_bitwise_stable(self, tmp_path, capfd):
        """Recursive-doubling allreduce on 3 processes: the plan
        freezes on fire 1, compiles natively, and every later fire
        runs the whole frozen schedule C-side — fires counted, zero
        per-fire fallbacks, zero contiguous-path copies, and the
        results bitwise-identical to the recorded (interpreted)
        fire."""
        rc, out, _ = _run_job(tmp_path, capfd, """
            x = np.stack([np.arange(256, dtype=np.int32)
                          * (off + i + 1) for i in range(2)])
            want = sum(np.arange(256, dtype=np.int32) * (r + 1)
                       for r in range(n))
            first = None
            for it in range(5):
                got = np.asarray(world.allreduce(x))
                np.testing.assert_array_equal(got[0], want)
                if first is None:
                    first = got.copy()
                np.testing.assert_array_equal(got, first)  # BITWISE
            fires = _pv("plan_native_fires")
            assert fires >= 3, fires
            assert _pv("plan_native_fallbacks") == 0
            assert _pv("plan_pool_hits") >= fires
            assert _pv("plan_pool_bytes") > 0
            assert _pv("wire_native_fallback_copies") == 0
            # an allreduce folds its arrivals in later jax calls: its
            # native fires hand it copies, and no result is assembled
            assert _pv("plan_pool_copy_bytes") > 0
            assert _pv("hier_assembled_results") == 0
            world.barrier()
            print(f"NATIVE-OK {me} fires={fires}", flush=True)
            mpi.finalize()
        """)
        assert rc == 0, out
        for me in range(3):
            assert f"NATIVE-OK {me} " in out

    def test_mixed_fleet_bitwise_parity(self, tmp_path, capfd):
        """One rank opts out (cvar off — same wire position as a rank
        whose .so lacks the symbols): its fires stay interpreted,
        the others go native, and because the wire bytes are the
        contract the results are STILL bitwise identical on every
        rank."""
        rc, out, _ = _run_job(tmp_path, capfd, """
            if me == 2:
                mca_var.set_value("coll_plan_native", 0)
            x = np.stack([np.arange(128, dtype=np.int32)
                          * (off + i + 1) for i in range(2)])
            want = sum(np.arange(128, dtype=np.int32) * (r + 1)
                       for r in range(n))
            for it in range(4):
                got = np.asarray(world.allreduce(x))
                np.testing.assert_array_equal(got[0], want)  # BITWISE
            fires = _pv("plan_native_fires")
            if me == 2:
                assert fires == 0, fires
                # replayed in Python: nothing comes out of a slab
                assert _pv("plan_pool_copy_bytes") == 0
            else:
                assert fires >= 2, fires
            assert _pv("hier_assembled_results") == 0
            world.barrier()
            print(f"MIXED-OK {me} fires={fires}", flush=True)
            mpi.finalize()
        """)
        assert rc == 0, out
        for me in range(3):
            assert f"MIXED-OK {me} " in out

    @pytest.mark.parametrize("n", [2, 4])
    @pytest.mark.parametrize("op", ["allgather", "bcast", "alltoall",
                                    "gather"])
    def test_many_rings_through_a_small_ring(self, tmp_path, capfd, op,
                                             n):
        """Two and four processes, 64 KiB rings, a message below one
        ring (4 KiB) and one of many rings (1 MiB per process and fire).
        The native fires return what the interpreted first call
        returned, bit for bit; with opposing senders some fragment must
        have met a full ring and yielded; a tx ring counts at most one
        stall per fragment it carried. Counters, never wall time: the
        processes meet in a barrier after every call, so which fires
        the C executor takes does not depend on who runs ahead.

        The executor's slab is reused (ISSUE 31): a result that was
        KEPT is bit-identical after the plan's next fire has run with
        other data, on the host join and on the device join; a fire
        that is vetoed (``_clean_channel`` false) returns the same array
        through the same consumer; no byte is copied out of the slab
        (``plan_pool_copy_bytes`` 0) and every call's result was
        assembled in one pass (``hier_assembled_results``), the probe's
        two dry runs building none."""
        rc, out, _ = _run_job(tmp_path, capfd, """
            from ompi_release_tpu.btl import nativewire as nw
            from ompi_release_tpu.coll import native_exec as nx
            OP = %r
            mod = world._hier_module
            calls = {"allgather": world.allgather,
                     "alltoall": world.alltoall,
                     "bcast": lambda v: world.bcast(v, root=1),
                     "gather": lambda v: world.gather(v, root=n - 1)}

            def call(v):
                # The counts below are exact only if no process is a
                # call ahead of another: a bcast root returns once its
                # sends are posted, a receiver still reaping the call
                # before pops that early frame and restashes it, and its
                # next fire is then rightly delegated to the Python leg
                # (a counted fallback). The barrier's tokens ride the
                # OOB endpoint, not the rings.
                out = calls[OP](v)
                world.barrier()
                return out

            def data(per, salt):
                return np.stack([(np.arange(per, dtype=np.int32) * 7
                                  + 1000 * (off + i) + salt)
                                 for i in range(2)])

            def want(per, salt):  # the result's row for local member 0/1
                rows = [np.arange(per, dtype=np.int32) * 7 + 1000 * r
                        + salt for r in range(n)]
                if OP == "allgather":
                    return [np.concatenate(rows)] * 2
                if OP == "bcast":
                    return [rows[1]] * 2
                if OP == "gather":
                    return [np.concatenate(rows) if off + i == n - 1
                            else np.zeros(per * n, np.int32)
                            for i in range(2)]
                c = per // n
                return [np.concatenate([r[(off + i) * c:(off + i + 1) * c]
                                        for r in rows]) for i in range(2)]

            # a gather builds a result on the root's process alone
            builds = OP != "gather" or off + 2 == n
            asm0 = _pv("hier_assembled_results")
            made = vetoes = 0
            for per in (1024, (1 << 20) // 8):  # int32: 4 KiB, 512 KiB a rank
                xa, xb = data(per, 0), data(per, 5)
                first = call(xa)  # interpreted: records the plan
                made += 1
                # the probe ran the schedule twice and built nothing
                assert _pv("hier_assembled_results") - asm0 == made * builds
                np.testing.assert_array_equal(np.asarray(first),
                                              np.stack(want(per, 0)))
                fires = _pv("plan_native_fires")
                for host in (True, False):
                    mod._host_join = host
                    kept = call(xa)  # a native fire
                    snap = np.array(kept)
                    other = call(xb)  # the same plan's next fire
                    made += 2
                    np.testing.assert_array_equal(np.asarray(other),
                                                  np.stack(want(per, 5)))
                    np.testing.assert_array_equal(np.asarray(kept), snap)
                    np.testing.assert_array_equal(snap,
                                                  np.asarray(first))
                mod._host_join = True
                assert _pv("plan_native_fires") - fires == 4
                assert _pv("plan_native_fallbacks") == vetoes
                clean = nx.NativeXchg._clean_channel
                nx.NativeXchg._clean_channel = lambda self: False
                try:
                    vetoed = call(xa)
                finally:
                    nx.NativeXchg._clean_channel = clean
                made += 1
                vetoes += 1
                np.testing.assert_array_equal(np.asarray(vetoed),
                                              np.asarray(first))
                assert _pv("plan_native_fallbacks") == vetoes
            assert _pv("plan_pool_copy_bytes") == 0
            assert _pv("hier_assembled_results") - asm0 == made * builds
            yields = _pv("plan_native_ring_yields")
            if OP in ("allgather", "alltoall"):
                assert yields > 0, yields
            frames = stalls = 0
            for ring in list(nw._live_tx):
                st = ring.stats()
                assert st["w_stalls"] <= st["w_frames"], st
                frames += st["w_frames"]
                stalls += st["w_stalls"]
            assert yields <= stalls, (yields, stalls)
            world.barrier()
            print(f"RINGS-OK {me} yields={yields} "
                  f"frames={frames} stalls={stalls}", flush=True)
            mpi.finalize()
        """ % op, n=n, mca=[("btl_nativewire_ring_bytes", "65536"),
                            ("wire_pipeline_segsize", "16384")])
        assert rc == 0, out
        for me in range(n):
            assert f"RINGS-OK {me} " in out

    def test_sigkill_mid_plan_fire_is_typed_and_fast(
            self, tmp_path, capfd):
        """FT contract: rank 1 dies between native fires; the
        survivors' next fire surfaces ERR_PROC_FAILED (or the revoke
        that follows) naming the dead process well inside the
        detection interval — the C slice loop re-checks the FT word
        every ~100 ms, so death never becomes a silent hang."""
        rc, out, _ = _run_job(tmp_path, capfd, """
            x = np.stack([np.arange(64, dtype=np.int32)
                          * (off + i + 1) for i in range(2)])
            for it in range(3):  # freeze + native fires
                world.allreduce(x)
            assert me == 2 or _pv("plan_native_fires") >= 1
            world.barrier()
            if me == 1:
                time.sleep(0.5)
                import signal
                os.kill(os.getpid(), signal.SIGKILL)
            t0 = time.monotonic()
            try:
                for it in range(50):
                    world.allreduce(x)
                raise AssertionError("collective with dead peer ran")
            except mpi.MPIError as e:
                dt = time.monotonic() - t0
                assert e.code in (mpi.ErrorCode.ERR_PROC_FAILED,
                                  mpi.ErrorCode.ERR_REVOKED), e
                assert dt < 20, f"typed error took {dt:.1f}s"
                if e.code == mpi.ErrorCode.ERR_PROC_FAILED:
                    assert "1" in str(e)  # names the dead process
            print(f"FT-NATIVE-OK {me}", flush=True)
            mpi.finalize()
        """, job_kw={"on_failure": "continue"})
        assert rc == 0, out
        assert "FT-NATIVE-OK 0" in out
        assert "FT-NATIVE-OK 2" in out
