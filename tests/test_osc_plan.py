"""Frozen RMA access plans (osc/plan): the one-sided analogue of the
compiled collective plans.

Layers:

1. Parity matrix: put/accumulate/get/get_accumulate across dtypes and
   every sync flavor (fence, passive lock, PSCW) — the planned close
   must be BITWISE identical to the interpreted close, because the
   fused program dispatches through the very same branch lambdas.
2. Steady state: a 10-epoch passive-target loop compiles exactly ONE
   fused program and replays it 9 times (``osc_plan_programs`` /
   ``osc_plan_cache_hits`` witnesses).
3. Lifecycle: a cvar write re-plans at the next close (generation
   witness), replay divergence drops the plan loudly and falls back
   interpreted, ``win.free()`` evicts every plan and template.
4. Wire frames: the frozen ``BatchTemplate`` renders a batch IDENTICAL
   to ``_pack_batch``'s (pinned, round-tripped through
   ``_unpack_batch``), packing is deterministic, and payloads share a
   frame only while they fit one wire segment.
5. Hot-path cvar caching: steady-state closes and request timeouts hit
   the MCA registry ZERO times (the ``OscTuning`` snapshot + the
   generation-cached plan conf), and same-NAMED user ops can neither
   alias a predefined program locally nor ship over the wire.
6. One real 3-process job: the wire window's home-side epochs replay
   frozen plans with parity over the wire.
"""

import os
import sys
import textwrap

import numpy as np
import pytest

import jax.numpy as jnp

import ompi_release_tpu as mpi
from ompi_release_tpu import ops
from ompi_release_tpu.mca import pvar
from ompi_release_tpu.mca import var as mca_var
from ompi_release_tpu.ops.op import Op
from ompi_release_tpu.osc import LOCK_EXCLUSIVE, win_allocate
from ompi_release_tpu.osc import plan as osc_plan
from ompi_release_tpu.osc.wire_win import (
    OscTuning, _frames_of, _pack_batch, _unpack_batch,
)
from ompi_release_tpu.osc.window import _PendingOp
from ompi_release_tpu.runtime.state import JobState
from ompi_release_tpu.tools.tpurun import Job
from ompi_release_tpu.utils.errors import MPIError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _pv(name):
    p = pvar.PVARS.lookup(name)
    assert p is not None, name
    return p.read()


@pytest.fixture(scope="module")
def world():
    yield mpi.init()


@pytest.fixture()
def win(world):
    w = win_allocate(world, (4,), jnp.float32)
    yield w
    if not w._freed:
        w.free()


def _interpreted(fn):
    """Run fn with access plans off (the interpreted twin)."""
    mca_var.set_value("osc_compiled", 0)
    try:
        return fn()
    finally:
        mca_var.VARS.unset("osc_compiled")


# ---------------------------------------------------------------------------
# 1. parity matrix
# ---------------------------------------------------------------------------


class TestPlannedParity:
    """Planned and interpreted closes share ``Window._branch_fn``
    lambdas, so parity is a structural identity being spot-checked —
    any mismatch means the fused unrolling diverged from the scan."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
    @pytest.mark.parametrize("sync", ["fence", "lock", "pscw"])
    def test_epoch_matrix_bitwise(self, world, dtype, sync):
        def epoch(w):
            pay = np.arange(4).astype(w.dtype)
            acc = np.full(4, 3, w.dtype)
            if sync == "fence":
                w.fence()
            elif sync == "lock":
                w.lock(1, LOCK_EXCLUSIVE)
            else:
                w.post(world.group)
                w.start(world.group)
            w.put(pay, target=1)
            w.accumulate(acc, target=1, op=ops.SUM)
            g = w.get(target=1)
            ga = w.get_accumulate(acc, target=1, op=ops.MAX)
            if sync == "fence":
                w.fence_end()
            elif sync == "lock":
                w.unlock(1)
            else:
                w.complete()
                w.wait()
            return (np.asarray(g.value), np.asarray(ga.value),
                    np.asarray(w.read()))

        def run(w):
            outs = [epoch(w) for _ in range(3)]  # capture + replays
            return outs

        wi = win_allocate(world, (4,), dtype)
        wc = win_allocate(world, (4,), dtype)
        try:
            want = _interpreted(lambda: run(wi))
            h0 = _pv("osc_plan_cache_hits")
            got = run(wc)
            h1 = _pv("osc_plan_cache_hits")
            for (gg, gga, gdata), (wg, wga, wdata) in zip(got, want):
                np.testing.assert_array_equal(gg, wg)
                np.testing.assert_array_equal(gga, wga)
                np.testing.assert_array_equal(gdata, wdata)
            # epoch 1 captures (observe 0), 2..3 replay (observe 1)
            assert h1["sum"] - h0["sum"] == 2, (h0, h1)
        finally:
            wi.free()
            wc.free()

    def test_indexed_cas_and_fetch_parity(self, world):
        def run(w):
            w.fence()
            w.put(np.arange(6, dtype=np.float32), target=1)
            w.fence_end()
            w.lock(1, LOCK_EXCLUSIVE)
            old = w.compare_and_swap(
                np.float32(99.0), np.float32(3.0), target=1, index=3)
            fetched = w.fetch_and_op(
                np.float32(10.0), target=1, op=ops.SUM, index=0)
            w.unlock(1)
            return (np.asarray(old.value), np.asarray(fetched.value),
                    np.asarray(w.read()))

        wi = win_allocate(world, (6,), jnp.float32)
        wc = win_allocate(world, (6,), jnp.float32)
        try:
            want = _interpreted(lambda: run(wi))
            got = run(wc)   # capture
            got2 = run(wc)  # replay fires the fused program
            want2 = _interpreted(lambda: run(wi))
            for g, w_ in zip(got + got2, want + want2):
                np.testing.assert_array_equal(g, w_)
        finally:
            wi.free()
            wc.free()


# ---------------------------------------------------------------------------
# 2. steady state: exactly one compile
# ---------------------------------------------------------------------------


class TestSteadyState:
    def test_ten_epochs_one_program(self, world):
        w = win_allocate(world, (4,), jnp.float32)
        try:
            mca_var.set_value("osc_plan_max_ops", 128)  # pin generation
            try:
                pay = np.full(4, 2.0, np.float32)
                h0 = _pv("osc_plan_cache_hits")
                p0 = _pv("osc_plan_programs")
                f0 = _pv("osc_plans_frozen")
                for _ in range(10):
                    w.lock(1, LOCK_EXCLUSIVE)
                    w.put(pay, target=1)
                    w.accumulate(pay, target=1, op=ops.SUM)
                    w.unlock(1)
                h1 = _pv("osc_plan_cache_hits")
                assert h1["count"] - h0["count"] == 10, (h0, h1)
                assert h1["sum"] - h0["sum"] == 9, (h0, h1)
                # exactly ONE plan frozen, ONE fused program compiled
                # (at the first replay), across all ten closes
                assert _pv("osc_plans_frozen") - f0 == 1
                assert _pv("osc_plan_programs") - p0 == 1
                assert len(w._access_plans) == 1
            finally:
                mca_var.VARS.unset("osc_plan_max_ops")
        finally:
            w.free()

    def test_orchestration_timer_feeds_both_paths(self, world, win):
        def one(w):
            w.fence()
            w.put(np.ones(4, np.float32), target=0)
            w.fence_end()

        o0 = _pv("osc_orchestration_seconds")
        _interpreted(lambda: one(win))
        o1 = _pv("osc_orchestration_seconds")
        assert o1 > o0  # interpreted close reported its span
        one(win)  # capture
        one(win)  # replay
        assert _pv("osc_orchestration_seconds") > o1

    def test_oversized_epoch_stays_interpreted(self, world, win):
        mca_var.set_value("osc_plan_max_ops", 2)
        try:
            h0 = _pv("osc_plan_cache_hits")
            win.fence()
            for _ in range(3):
                win.put(np.ones(4, np.float32), target=1)
            win.fence_end()
            h1 = _pv("osc_plan_cache_hits")
            assert h1["count"] == h0["count"]  # not even counted
            assert not win._access_plans
        finally:
            mca_var.VARS.unset("osc_plan_max_ops")


# ---------------------------------------------------------------------------
# 3. lifecycle: generation, divergence, eviction
# ---------------------------------------------------------------------------


class TestPlanLifecycle:
    def _one(self, w):
        w.lock(1, LOCK_EXCLUSIVE)
        w.put(np.full(4, 5.0, np.float32), target=1)
        w.unlock(1)

    def test_cvar_write_replans(self, world, win):
        self._one(win)  # capture + freeze
        self._one(win)  # replay
        (sig, old_plan), = win._access_plans.items()
        # ANY cvar write bumps the registry generation: the frozen
        # plan is stale at the next close
        mca_var.set_value("wire_pipeline_depth", 6)
        try:
            h0 = _pv("osc_plan_cache_hits")
            self._one(win)  # re-capture under the new generation
            h1 = _pv("osc_plan_cache_hits")
            assert h1["count"] - h0["count"] == 1
            assert h1["sum"] - h0["sum"] == 0  # a capture, not a hit
            new_plan = win._access_plans[sig]
            assert new_plan is not old_plan
            assert new_plan.gen > old_plan.gen
            self._one(win)  # and replays resume
            h2 = _pv("osc_plan_cache_hits")
            assert h2["sum"] - h1["sum"] == 1
        finally:
            mca_var.VARS.unset("wire_pipeline_depth")

    def test_divergence_drops_plan_loudly(self, world, win):
        self._one(win)
        self._one(win)  # replay: plan is live with a built program
        (sig, plan), = win._access_plans.items()
        assert plan.prog is not None

        def boom(*a, **k):
            raise RuntimeError("synthetic divergence")

        plan.prog = boom
        self._one(win)  # must fall back interpreted, not raise
        np.testing.assert_array_equal(
            np.asarray(win.read())[1], np.full(4, 5.0))
        # the diverged plan was dropped; the NEXT close re-records a
        # fresh one and replays resume after it
        assert sig not in win._access_plans
        self._one(win)
        fresh = win._access_plans[sig]
        assert fresh is not plan
        h0 = _pv("osc_plan_cache_hits")
        self._one(win)
        assert _pv("osc_plan_cache_hits")["sum"] - h0["sum"] == 1

    def test_window_free_evicts_plans(self, world):
        w = win_allocate(world, (4,), jnp.float32)
        self._one(w)
        assert w._access_plans
        w.free()
        assert not w._access_plans
        assert not w._batch_templates

    def test_unplannable_user_op_without_hash_is_skipped(self, world,
                                                         win):
        # Op is a frozen dataclass (hashable) — unplannability comes
        # from unhashable payload descriptors; simulate with a raw
        # pending op carrying a list payload
        p = _PendingOp("put", 0, data=[1.0, "x"], op=ops.REPLACE)
        assert osc_plan.epoch_signature([p]) is None


# ---------------------------------------------------------------------------
# 4. wire frames: byte-identical templates
# ---------------------------------------------------------------------------


def _wire_todo():
    from ompi_release_tpu.request.request import Request

    return [
        _PendingOp("put", 1, data=jnp.arange(4, dtype=jnp.float32),
                   op=ops.REPLACE),
        _PendingOp("acc", 2, data=jnp.full((4,), 2.0, jnp.float32),
                   op=ops.SUM),
        _PendingOp("get", 1, request=Request()),
        _PendingOp("cas", 0, data=jnp.float32(9.0),
                   compare=jnp.float32(1.0), request=Request(),
                   index=2),
        _PendingOp("put", 3, data=jnp.float32(7.0), op=ops.REPLACE,
                   index=1),
    ]


SEG = 1 << 20  # wire_pipeline_segsize's default


def _same_batch(a, b):
    assert a.meta == b.meta and a.frames == b.frames
    assert len(a.arrays) == len(b.arrays)
    for x, y in zip(a.arrays, b.arrays):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()  # BYTE-identical


class TestFrameTemplates:
    def test_template_bytes_identical_to_pack_batch(self):
        todo = _wire_todo()
        want = _pack_batch(todo, SEG)
        tpl = osc_plan.BatchTemplate(mca_var.VARS.generation, todo, SEG)
        _same_batch(tpl.render(todo), want)

    def test_pack_batch_is_time_deterministic(self):
        # two packs of the same ops are the same request records and
        # the same payload bytes: nothing of the clock is in a frame
        todo = _wire_todo()
        _same_batch(_pack_batch(todo, SEG), _pack_batch(todo, SEG))

    def test_template_round_trips_through_unpack(self):
        todo = _wire_todo() + [
            _PendingOp("put", 2, data=jnp.arange(3, dtype=jnp.float32),
                       op=ops.REPLACE, disp=1, count=3)]
        tpl = osc_plan.BatchTemplate(mca_var.VARS.generation, todo, SEG)
        batch = tpl.render(todo)
        back = _unpack_batch(batch.meta, batch.arrays)
        assert [(p.kind, p.target) for p in back] == \
               [(p.kind, p.target) for p in todo]
        for p, q in zip(back, todo):
            assert (p.op.name if p.op else "") == \
                   (q.op.name if q.op else "")
            assert (p.index, p.disp, p.count) == (q.index, q.disp, q.count)
            assert (p.request is not None) == (q.request is not None)
            if q.data is not None:
                np.testing.assert_array_equal(
                    np.asarray(p.data), np.asarray(q.data))
            if q.compare is not None:
                np.testing.assert_array_equal(
                    np.asarray(p.compare), np.asarray(q.compare))

    def test_payloads_share_a_frame_while_they_fit_one_segment(self):
        # consecutive payloads are joined while together they fit one
        # wire segment; one that does not fit travels alone
        assert _frames_of([4, 4, 4], 16) == [3]
        assert _frames_of([8, 8, 8], 16) == [2, 1]
        assert _frames_of([64, 4, 4, 64], 16) == [1, 2, 1]
        assert _frames_of([16] * 4, 16) == [1, 1, 1, 1]
        assert _frames_of([], 16) == [] and _frames_of([4, 4], 0) == [1, 1]
        batch = _pack_batch(_wire_todo(), SEG)
        assert sum(batch.frames) == len(batch.arrays) == 5
        assert batch.nbytes == len(batch.meta) + sum(
            a.nbytes for a in batch.arrays)


# ---------------------------------------------------------------------------
# 5. hot-path cvar caching + op identity
# ---------------------------------------------------------------------------


class TestHotPathCvars:
    def test_steady_closes_hit_registry_zero_times(self, world, win,
                                                   monkeypatch):
        pay = np.full(4, 1.0, np.float32)

        def one():
            win.lock(1, LOCK_EXCLUSIVE)
            win.put(pay, target=1)
            win.unlock(1)

        for _ in range(3):
            one()  # warm: conf cached, plan frozen + replaying
        calls = []
        real_get = mca_var.get
        monkeypatch.setattr(
            mca_var, "get",
            lambda *a, **k: calls.append(a) or real_get(*a, **k))
        for _ in range(5):
            one()
        assert calls == [], (
            "steady-state RMA closes must not touch the MCA registry; "
            f"saw {calls}")

    def test_osc_tuning_snapshot_honors_wire_timeout(self):
        mca_var.set_value("osc_request_timeout_ms", 5000)
        mca_var.set_value("wire_coll_timeout_ms", 9000)
        try:
            t = OscTuning()
            # the RMA wait bound must not undercut an operator-raised
            # collective bound: max() of the two
            assert t.request_timeout_ms == 9000
            assert t.gen == mca_var.VARS.generation
        finally:
            mca_var.VARS.unset("osc_request_timeout_ms")
            mca_var.VARS.unset("wire_coll_timeout_ms")
        mca_var.set_value("osc_request_timeout_ms", 200_000)
        try:
            # above the wire default (60 s): the RMA bound wins
            assert OscTuning().request_timeout_ms == 200_000
        finally:
            mca_var.VARS.unset("osc_request_timeout_ms")

    def test_same_named_user_op_gets_its_own_plan(self, world):
        """Op keying is by OBJECT, not name: a user op named "sum"
        must neither reuse SUM's frozen program locally nor ship over
        the wire as if it were SUM."""
        clobber = Op("sum", lambda a, b: a * 0 + 99.0,
                     commutative=True)
        w = win_allocate(world, (4,), jnp.float32)
        try:
            def run(op):
                w.fence()
                w.accumulate(np.full(4, 2.0, np.float32), target=1,
                             op=op)
                w.fence_end()
                return np.asarray(w.read())[1]

            run(ops.SUM); run(ops.SUM)  # freeze + replay SUM's plan
            np.testing.assert_array_equal(run(clobber),
                                          np.full(4, 99.0))
            sigs = list(w._access_plans)
            assert len(sigs) == 2, "same-named op aliased SUM's plan"
            # and back: SUM still replays ITS program, not clobber's
            np.testing.assert_array_equal(
                run(ops.SUM), np.full(4, 101.0))
        finally:
            w.free()

    def test_same_named_user_op_refused_on_the_wire(self):
        clobber = Op("sum", lambda a, b: a * 0 + 99.0,
                     commutative=True)
        todo = [_PendingOp("acc", 0,
                           data=jnp.ones((4,), jnp.float32),
                           op=clobber)]
        with pytest.raises(MPIError):
            _pack_batch(todo, SEG)

    def test_cache_stats_shape(self):
        st = osc_plan.cache_stats()
        assert set(st) == {"epoch_plans", "batch_templates",
                           "programs", "fires", "hits"}


# ---------------------------------------------------------------------------
# 6. the real 3-process job
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
    import ompi_release_tpu.osc.plan  # register the plan pvars NOW
    from ompi_release_tpu.mca import pvar, var as mca_var

    def _pv(name):
        p = pvar.PVARS.lookup(name)
        return p.read() if p is not None else None
""" % REPO)


class TestOscPlanJob:
    def test_wire_window_replays_plans_with_parity(self, tmp_path,
                                                   capfd):
        """3-process world: every rank hammers the same lock epoch on
        a spanning window. The home side's repeated batch epochs
        freeze access plans and replay them; results stay bitwise
        equal to the first (interpreted, capturing) epoch and the
        plan pvars witness replays on at least the home rank."""
        app = tmp_path / "app.py"
        app.write_text(APP_PRELUDE + textwrap.dedent("""
            import jax.numpy as jnp
            from ompi_release_tpu import ops
            from ompi_release_tpu.osc import LOCK_EXCLUSIVE
            from ompi_release_tpu.osc.window import win_allocate
            from ompi_release_tpu.runtime.runtime import Runtime

            world = mpi.init()
            rt = Runtime.current()
            off = rt.local_rank_offset
            nloc = len(world.local_comm_ranks)
            # every process hammers a REMOTE rank owned by the next
            # process — each home applies one origin's repeated batch
            tgt = (off + nloc) % world.size
            pay = np.full(4, float(off + 1), np.float32)

            def one():
                w.lock(tgt, LOCK_EXCLUSIVE)
                w.put(pay, target=tgt)
                w.accumulate(pay, target=tgt, op=ops.SUM)
                g = w.get(target=tgt)
                w.unlock(tgt)
                return np.asarray(g.value)

            w = win_allocate(world, (4,), jnp.float32)
            first = one()  # capture on the home side
            np.testing.assert_array_equal(first, pay * 2)
            for _ in range(6):
                np.testing.assert_array_equal(one(), first)  # BITWISE
            world.barrier()
            # my row `off` was written by the PREVIOUS process
            prev = (off - nloc) % world.size
            np.testing.assert_array_equal(
                np.asarray(w.read())[0],
                np.full(4, (prev + 1) * 2.0, np.float32))
            st = _pv("osc_plan_cache_hits")
            # spanning allreduce: one slice per LOCAL member; member 0
            # carries this process's plan-replay count
            buf = np.zeros((nloc, 1), np.float32)
            buf[0, 0] = st["sum"] if st else 0
            fires = float(np.asarray(world.allreduce(buf))[0, 0])
            assert fires >= 6, (fires, st)
            w.free()
            print("OSC-PLAN-JOB-OK", flush=True)
            mpi.finalize()
        """))
        job = Job(3, [sys.executable, str(app)], [],
                  heartbeat_s=0.5, miss_limit=8)
        rc = job.run(timeout_s=240)
        out = capfd.readouterr()
        assert rc == 0, out.out + out.err
        assert job.job_state.visited(JobState.TERMINATED)
        assert out.out.count("OSC-PLAN-JOB-OK") == 3
