"""One-sided/RMA window tests (osc analogue)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import ompi_release_tpu as mpi
from ompi_release_tpu import ops
from ompi_release_tpu.osc import (
    LOCK_EXCLUSIVE, Window, win_allocate, win_create,
)
from ompi_release_tpu.utils.errors import MPIError


@pytest.fixture(scope="module")
def world():
    yield mpi.init()


@pytest.fixture()
def win(world):
    w = win_allocate(world, (4,), jnp.float32)
    yield w
    if w._epoch.name != "NONE":
        pytest.fail("test left an open epoch")
    w.free()


class TestFenceEpochs:
    def test_put_get_fence(self, world, win):
        win.fence()
        win.put(np.full(4, 7.0, np.float32), target=3)
        g = win.get(target=3)
        assert not g.is_complete  # completes at the closing fence
        win.fence()
        np.testing.assert_array_equal(np.asarray(g.value), np.full(4, 7.0))
        np.testing.assert_array_equal(
            np.asarray(win.read())[3], np.full(4, 7.0)
        )
        win.fence_end()

    def test_device_resident_origin(self, world, win):
        """An origin buffer that already lives on one device (committed
        there) must reach a window spread over all of them — through
        the interpreted epoch program and its frozen-plan replay."""
        dev = world.submesh.devices.flat[2]
        for rep in range(3):  # capture, freeze, replay
            val = jax.device_put(np.full(4, 5.0 + rep, np.float32), dev)
            win.fence()
            win.put(val, target=1)
            win.accumulate(val, target=6)
            win.fence_end()
            np.testing.assert_array_equal(
                np.asarray(win.read())[1], np.full(4, 5.0 + rep))
        np.testing.assert_array_equal(
            np.asarray(win.read())[6], np.full(4, 18.0))

    def test_rma_outside_epoch_raises(self, win):
        with pytest.raises(MPIError):
            win.put(np.zeros(4, np.float32), target=0)

    def test_ordering_put_then_get(self, world, win):
        """Same-epoch ordering: get sees the preceding put (MPI
        same-origin ordering for overlapping ops)."""
        win.fence()
        win.put(np.full(4, 1.0, np.float32), target=0)
        g1 = win.get(target=0)
        win.put(np.full(4, 2.0, np.float32), target=0)
        g2 = win.get(target=0)
        win.fence_end()
        np.testing.assert_array_equal(np.asarray(g1.value), np.full(4, 1.0))
        np.testing.assert_array_equal(np.asarray(g2.value), np.full(4, 2.0))

    def test_accumulate_sum_and_max(self, world, win):
        win.fence()
        for t in (1, 1, 2):
            win.accumulate(np.full(4, 2.0, np.float32), target=t, op=ops.SUM)
        win.accumulate(np.full(4, -5.0, np.float32), target=2, op=ops.MAX)
        win.fence_end()
        out = np.asarray(win.read())
        np.testing.assert_array_equal(out[1], np.full(4, 4.0))
        np.testing.assert_array_equal(out[2], np.full(4, 2.0))  # max(2,-5)


class TestPassiveTarget:
    def test_lock_unlock(self, world, win):
        win.lock(2, LOCK_EXCLUSIVE)
        win.put(np.full(4, 9.0, np.float32), target=2)
        win.unlock(2)
        np.testing.assert_array_equal(
            np.asarray(win.read())[2], np.full(4, 9.0)
        )

    def test_lock_required_for_target(self, win):
        win.lock(1)
        with pytest.raises(MPIError):
            win.put(np.zeros(4, np.float32), target=3)  # not locked
        win.unlock(1)

    def test_lock_all_flush(self, world, win):
        win.lock_all()
        win.accumulate(np.ones(4, np.float32), target=0)
        win.flush(0)
        np.testing.assert_array_equal(
            np.asarray(win.read())[0], np.ones(4)
        )
        win.accumulate(np.ones(4, np.float32), target=0)
        win.unlock_all()
        np.testing.assert_array_equal(
            np.asarray(win.read())[0], np.full(4, 2.0)
        )

    def test_fetch_and_op(self, world, win):
        win.lock(5)
        f = win.fetch_and_op(np.full(4, 3.0, np.float32), target=5, op=ops.SUM)
        win.unlock(5)
        np.testing.assert_array_equal(np.asarray(f.value), np.zeros(4))
        np.testing.assert_array_equal(
            np.asarray(win.read())[5], np.full(4, 3.0)
        )

    def test_compare_and_swap(self, world, win):
        win.lock(4)
        win.put(np.full(4, 1.0, np.float32), target=4)
        win.flush(4)
        c = win.compare_and_swap(
            np.full(4, 8.0, np.float32), compare=np.full(4, 1.0, np.float32),
            target=4,
        )
        win.unlock(4)
        np.testing.assert_array_equal(np.asarray(c.value), np.full(4, 1.0))
        np.testing.assert_array_equal(
            np.asarray(win.read())[4], np.full(4, 8.0)
        )


class TestSingleElement:
    """Single-element RMA (MPI target_disp semantics, osc.h:310,324)."""

    def test_indexed_put(self, world, win):
        win.fence()
        win.put(np.float32(5.0), target=2, index=1)
        win.fence_end()
        out = np.asarray(win.read())[2]
        np.testing.assert_array_equal(out, [0.0, 5.0, 0.0, 0.0])

    def test_indexed_cas_swaps_one_element_only(self, world, win):
        win.lock(3)
        win.put(np.full(4, 1.0, np.float32), target=3)
        win.flush(3)
        c = win.compare_and_swap(
            np.float32(9.0), compare=np.float32(1.0), target=3, index=2
        )
        win.unlock(3)
        # returned value is the single pre-op element
        assert np.asarray(c.value).shape == ()
        assert float(c.value) == 1.0
        out = np.asarray(win.read())[3]
        np.testing.assert_array_equal(out, [1.0, 1.0, 9.0, 1.0])

    def test_indexed_cas_mismatch_leaves_element(self, world, win):
        win.lock(1)
        win.put(np.full(4, 2.0, np.float32), target=1)
        win.flush(1)
        c = win.compare_and_swap(
            np.float32(9.0), compare=np.float32(7.0), target=1, index=0
        )
        win.unlock(1)
        assert float(c.value) == 2.0
        np.testing.assert_array_equal(
            np.asarray(win.read())[1], np.full(4, 2.0)
        )

    def test_indexed_fetch_add(self, world, win):
        win.lock(0)
        f = win.fetch_and_op(np.float32(4.0), target=0, op=ops.SUM, index=3)
        win.unlock(0)
        assert float(f.value) == 0.0
        np.testing.assert_array_equal(
            np.asarray(win.read())[0], [0.0, 0.0, 0.0, 4.0]
        )

    def test_mixed_epoch_indexed_and_full(self, world, win):
        """Indexed and whole-slot ops interleave in one epoch in
        submission order."""
        win.fence()
        win.put(np.full(4, 1.0, np.float32), target=0)
        win.accumulate(np.float32(10.0), target=0, op=ops.SUM, index=0)
        g = win.get(target=0)
        win.fence_end()
        np.testing.assert_array_equal(
            np.asarray(g.value), [11.0, 1.0, 1.0, 1.0]
        )


class TestProgramCacheBounded:
    def test_epoch_lengths_share_bucketed_programs(self, world):
        """Varying epoch lengths must NOT compile one program each:
        op counts are padded to powers of two, so lengths 3..8 of the
        same branch set land in at most two buckets (4 and 8)."""
        from ompi_release_tpu.osc import window as win_mod

        w = win_allocate(world, (8,), jnp.float32)
        before = len(win_mod._program_cache)
        for n_ops in (3, 4, 5, 6, 7, 8):
            w.fence()
            for k in range(n_ops):
                w.accumulate(np.float32(1.0), target=k % world.size,
                             op=ops.SUM, index=k % 8)
            w.fence_end()
        added = len(win_mod._program_cache) - before
        assert added <= 2, f"expected <=2 bucketed programs, got {added}"
        w.free()

    def test_scalar_payload_epoch_correct(self, world):
        """Scalar accumulates on a larger window stay scalar on the
        host side and still apply correctly."""
        w = win_allocate(world, (16,), jnp.float32)
        w.fence()
        for _ in range(5):
            w.accumulate(np.float32(2.0), target=1, op=ops.SUM)
        w.fence_end()
        np.testing.assert_array_equal(
            np.asarray(w.read())[1], np.full(16, 10.0)
        )
        w.free()


class TestPSCW:
    def test_post_start_complete(self, world, win):
        win.post(world.group)
        win.start(world.group)
        win.put(np.full(4, 6.0, np.float32), target=1)
        win.complete()
        np.testing.assert_array_equal(
            np.asarray(win.read())[1], np.full(4, 6.0)
        )

    def test_win_test_and_flush_local_and_sync(self, world, win):
        """MPI_Win_test / flush_local(_all) / win_sync surface: test()
        closes a completed exposure; flush_local completes locally
        (epoch-checked); sync is a no-op under MPI_WIN_UNIFIED."""
        from ompi_release_tpu.utils.errors import MPIError

        with pytest.raises(MPIError):
            win.test()  # no exposure posted
        win.post(world.group)
        win.start(world.group)
        win.accumulate(np.float32(1.0), target=2)
        win.complete()
        assert win.test() is True
        with pytest.raises(MPIError):
            win.test()  # exposure already closed

        win.lock(1)
        win.put(np.full(4, 3.25, np.float32), 1)
        win.flush_local(1)
        win.flush_local_all()
        win.unlock(1)
        np.testing.assert_array_equal(
            np.asarray(win.read())[1], np.full(4, 3.25))
        win.sync()  # MPI_WIN_UNIFIED: one storage copy

    def test_win_user_keyvals(self, world, win):
        """User keyvals on windows share the comm keyval machinery
        (win.c's single attribute system)."""
        from ompi_release_tpu.comm.communicator import (create_keyval,
                                                        free_keyval)

        deleted = []
        kv = create_keyval(
            delete_fn=lambda w, k, v, es: deleted.append(v))
        try:
            found, _ = win.get_attr(kv)
            assert not found
            win.set_attr(kv, {"tag": 42})
            found, v = win.get_attr(kv)
            assert found and v == {"tag": 42}
            win.delete_attr(kv)
            assert deleted == [{"tag": 42}]
            assert win.get_attr(kv) == (False, None)
            # predefined string attrs still answer
            found, model = win.get_attr("win_model")
            assert found
        finally:
            free_keyval(kv)

    def test_request_based_rma(self, world, win):
        """MPI_Rput/Raccumulate/Rget: requests completable inside the
        epoch at flush, not only at its close."""
        win.lock(3)
        r1 = win.rput(np.full(4, 2.0, np.float32), 3)
        r2 = win.raccumulate(np.full(4, 0.5, np.float32), 3)
        assert not r1.is_complete and not r2.is_complete
        win.flush(3)
        assert r1.is_complete and r2.is_complete
        r3 = win.rget(3)
        win.flush(3)
        np.testing.assert_array_equal(np.asarray(r3.value),
                                      np.full(4, 2.5))
        win.unlock(3)


class TestCreate:
    def test_win_create_from_existing(self, world):
        base = np.arange(world.size * 2, dtype=np.float32).reshape(
            world.size, 2
        )
        w = win_create(world, base)
        w.fence()
        g = w.get(target=world.size - 1)
        w.fence_end()
        np.testing.assert_array_equal(
            np.asarray(g.value), base[world.size - 1]
        )
        w.free()

    def test_bad_shape_raises(self, world):
        with pytest.raises(MPIError):
            win_create(world, np.zeros((world.size + 1, 3), np.float32))

    def test_free_with_pending_raises(self, world):
        w = win_allocate(world, (2,), jnp.float32)
        w.fence()
        w.put(np.ones(2, np.float32), target=0)
        with pytest.raises(MPIError):
            w.free()
        w.fence_end()
        w.free()


class TestPSCWWait:
    def test_complete_then_wait(self, world, win):
        """Canonical PSCW: origin complete()s, target wait()s."""
        win.post(world.group)
        win.start(world.group)
        win.put(np.full(4, 2.0, np.float32), target=0)
        win.complete()
        win.wait()  # must close the exposure side, not raise
        np.testing.assert_array_equal(
            np.asarray(win.read())[0], np.full(4, 2.0)
        )

    def test_wait_without_post_raises(self, win):
        with pytest.raises(MPIError):
            win.wait()


class TestSharedWindow:
    """MPI_Win_allocate_shared + shared_query (osc/sm role): one
    contiguous allocation, per-rank segments directly loadable."""

    def test_allocate_shared_query(self, world):
        from ompi_release_tpu.osc import win_allocate_shared
        from ompi_release_tpu.utils.errors import MPIError

        w = win_allocate_shared(world, (6,), jnp.float32)
        try:
            # put into rank 3's segment, then load it DIRECTLY via
            # shared_query — the osc/sm promise
            w.lock_all()
            w.put(jnp.arange(6, dtype=jnp.float32), 3)
            w.flush_all()
            size, disp, blk = w.shared_query(3)
            assert size == 24 and disp == 4
            np.testing.assert_array_equal(np.asarray(blk),
                                          np.arange(6, dtype=np.float32))
            # MPI_PROC_NULL convention: -1 answers for the lowest rank
            _, _, blk0 = w.shared_query(-1)
            assert blk0.shape == (6,)
            with pytest.raises(MPIError, match="out of range"):
                w.shared_query(99)
            w.unlock_all()
        finally:
            w.free()

    def test_multi_host_comm_rejected(self, world):
        """The single-host gate reads the comm's OWN members' modex
        host identities — a two-host world is refused."""
        import dataclasses

        from ompi_release_tpu.osc import win_allocate_shared
        from ompi_release_tpu.utils.errors import MPIError

        rt = world.runtime
        old = rt.endpoints
        try:
            rt.endpoints = [
                dataclasses.replace(
                    ep, host="hostB" if ep.rank >= 4 else "hostA")
                for ep in old
            ]
            with pytest.raises(MPIError, match="single-host"):
                win_allocate_shared(world, (2,), jnp.float32)
            # a sub-comm living entirely on one "host" still qualifies
            sub = world.create(world.group.incl([0, 1, 2]),
                               name="one_host")
            try:
                w = win_allocate_shared(sub, (2,), jnp.float32)
                w.free()
            finally:
                sub.free()
        finally:
            rt.endpoints = old

    def test_plain_window_rejects_shared_query(self, world):
        from ompi_release_tpu.osc import win_allocate
        from ompi_release_tpu.utils.errors import MPIError

        w = win_allocate(world, (2,), jnp.float32)
        try:
            with pytest.raises(MPIError, match="allocate_shared"):
                w.shared_query(0)
        finally:
            w.free()


def test_window_predefined_attributes(world):
    """MPI_Win_get_attr: WIN_BASE/SIZE/DISP_UNIT/CREATE_FLAVOR/MODEL
    (ompi/win/win.c predefined attribute set)."""
    from ompi_release_tpu import osc
    from ompi_release_tpu.osc import window as W

    for ctor, flavor in ((osc.win_allocate, W.FLAVOR_ALLOCATE),
                         (W.win_allocate_shared, W.FLAVOR_SHARED)):
        w = ctor(world, (6,), jnp.float32)
        try:
            assert w.get_attr(W.WIN_SIZE) == (True, 24)
            assert w.get_attr(W.WIN_DISP_UNIT) == (True, 4)
            assert w.get_attr(W.WIN_CREATE_FLAVOR) == (True, flavor)
            assert w.get_attr(W.WIN_MODEL) == (True, W.MODEL_UNIFIED)
            found, base = w.get_attr(W.WIN_BASE)
            assert found and base.shape[0] == world.size
            assert w.get_attr("nonsense") == (False, None)
        finally:
            w.free()
    w = W.win_create(world, jnp.zeros((world.size, 2), jnp.float32))
    try:
        assert w.get_attr(W.WIN_CREATE_FLAVOR) == (True, W.FLAVOR_CREATE)
    finally:
        w.free()


class TestDynamicWindow:
    """MPI_Win_create_dynamic + attach/detach (the dynamic flavor):
    regions come and go on a live window; epochs span all of them."""

    def test_attach_rma_detach(self, world):
        from ompi_release_tpu.osc import win_create_dynamic
        from ompi_release_tpu.osc import window as W

        w = win_create_dynamic(world)
        try:
            assert w.get_attr(W.WIN_CREATE_FLAVOR) == \
                (True, W.FLAVOR_DYNAMIC)
            assert w.get_attr(W.WIN_SIZE) == (True, 0)  # MPI_BOTTOM-ish
            r1 = w.attach((4,), jnp.float32)
            r2 = w.attach((2,), jnp.int32)
            w.fence()
            w.put(np.full(4, 3.0, np.float32), 1, region=r1)
            w.accumulate(np.array([5, 7], np.int32), 6, region=r2)
            g = w.get(1, region=r1)
            w.fence_end()
            np.testing.assert_array_equal(np.asarray(g.value),
                                          np.full(4, 3.0))
            np.testing.assert_array_equal(
                np.asarray(w.read(r2))[6], [5, 7])
            w.detach(r1)
            with pytest.raises(MPIError, match="not attached"):
                w.put(np.zeros(4, np.float32), 0, region=r1)
            # r2 still lives across the detach
            w.lock_all()
            f = w.fetch_and_op(np.array([1, 1], np.int32), 6,
                               region=r2, op=ops.SUM)
            w.unlock_all()
            np.testing.assert_array_equal(np.asarray(f.value), [5, 7])
            np.testing.assert_array_equal(
                np.asarray(w.read(r2))[6], [6, 8])
        finally:
            w.free()
        with pytest.raises(MPIError, match="freed"):
            w.attach((2,), jnp.float32)

    def test_detach_with_pending_refused(self, world):
        from ompi_release_tpu.osc import win_create_dynamic

        w = win_create_dynamic(world)
        try:
            r = w.attach((2,), jnp.float32)
            w.fence()
            w.put(np.ones(2, np.float32), 0, region=r)
            with pytest.raises(MPIError, match="unsynchronized"):
                w.detach(r)
            w.fence_end()
            w.detach(r)
        finally:
            w.free()


def test_dynamic_window_attach_mid_epoch(world):
    """MPI_Win_attach is legal mid-epoch: a region attached inside an
    open fence (or lock_all) inherits the epoch and is immediately
    RMA-addressable; the closing fence drains every region."""
    from ompi_release_tpu.osc import win_create_dynamic

    w = win_create_dynamic(world)
    try:
        r1 = w.attach((2,), jnp.float32)
        w.fence()
        w.put(np.ones(2, np.float32), 0, region=r1)
        r2 = w.attach((3,), jnp.float32)  # joins the open epoch
        w.put(np.full(3, 4.0, np.float32), 5, region=r2)
        w.fence_end()
        np.testing.assert_array_equal(np.asarray(w.read(r2))[5],
                                      np.full(3, 4.0))
        w.lock_all()
        r3 = w.attach((2,), jnp.float32)  # joins the lock epoch
        w.put(np.full(2, 9.0, np.float32), 1, region=r3)
        w.flush_all()
        np.testing.assert_array_equal(np.asarray(w.read(r3))[1],
                                      np.full(2, 9.0))
        w.unlock_all()
    finally:
        w.free()


def test_dynamic_window_free_is_atomic(world):
    """free() with ANY unsynchronized region frees NOTHING — the
    window stays fully usable, drains, then frees."""
    from ompi_release_tpu.osc import win_create_dynamic

    w = win_create_dynamic(world)
    r1 = w.attach((2,), jnp.float32)
    r2 = w.attach((2,), jnp.float32)
    w.fence()
    w.put(np.ones(2, np.float32), 0, region=r2)
    with pytest.raises(MPIError, match="unsynchronized"):
        w.free()
    # nothing was freed: both regions still serve the epoch
    w.put(np.ones(2, np.float32), 0, region=r1)
    w.fence_end()
    np.testing.assert_array_equal(np.asarray(w.read(r1))[0],
                                  np.ones(2))
    w.free()
