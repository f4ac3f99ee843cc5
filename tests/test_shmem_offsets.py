"""OpenSHMEM at an address (ISSUE 36), in driver mode on the virtual CPU
devices: ``put``/``get`` at an offset and the AMOs on one word against the
numpy model of the symmetric heap (``shmem_reference.py``) on seeded random
operation lists; a range that leaves the allocation; a fetching AMO behind
a posted put; what a blocking call and a ``quiet`` flush; the counters."""

import numpy as np
import pytest

import jax.numpy as jnp

import ompi_release_tpu as mpi
from ompi_release_tpu.mca import pvar
from ompi_release_tpu.oshmem import ShmemCtx
from ompi_release_tpu.utils.errors import ErrorCode, MPIError

import shmem_reference as ref

ELEMS = 24


@pytest.fixture(scope="module")
def ctx():
    ctx = ShmemCtx(mpi.init())
    yield ctx
    ctx.finalize()


@pytest.fixture
def sym(ctx):
    sym = ctx.malloc((4, ELEMS // 4), jnp.int32)  # offsets are flat
    yield sym
    sym.free()


def heap_of(ctx, sym):
    return [np.asarray(sym.local(pe)).reshape(-1) for pe in range(ctx.n_pes)]


@pytest.mark.parametrize("dtype, seed", [
    (np.int32, 1), (np.int32, 2), (np.int32, 2**31 + 3),
    (np.float32, 4), (np.float32, 5)])
def test_seeded_operation_lists_agree_with_the_model(ctx, dtype, seed):
    ops = ref.random_ops(np.random.default_rng(seed), ctx.n_pes, ELEMS, 120,
                         dtype)
    want, heap = ref.run(ctx.n_pes, ELEMS, dtype, ops)
    sym = ctx.malloc((4, ELEMS // 4), dtype)
    try:
        got = ref.play(ctx, sym, ops)
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype and g.shape == w.shape
            np.testing.assert_array_equal(g, w)
        for mine, theirs in zip(heap_of(ctx, sym), heap):
            np.testing.assert_array_equal(mine, theirs)
    finally:
        sym.free()


def test_whole_slot_calls_keep_their_meaning(ctx, sym):
    row = np.arange(ELEMS, dtype=np.int32).reshape(4, -1)
    ctx.put(sym, row, 3)
    ctx.atomic_add(sym, np.ones((4, ELEMS // 4), np.int32), 3)
    ctx.atomic_inc(sym, 3)  # a scalar 1 over the whole slot
    np.testing.assert_array_equal(np.asarray(ctx.get(sym, 3)), row + 2)
    old = ctx.atomic_fetch_inc(sym, 3)
    np.testing.assert_array_equal(np.asarray(old), row + 2)
    np.testing.assert_array_equal(np.asarray(ctx.atomic_fetch(sym, 3)),
                                  row + 3)


@pytest.mark.parametrize("call", [
    lambda c, s: c.put(s, np.ones(5, np.int32), 1, offset=ELEMS - 4),
    lambda c, s: c.put(s, np.ones(5, np.int32), 1, offset=-1),
    lambda c, s: c.get(s, 1, offset=ELEMS - 4, nelems=5),
    lambda c, s: c.atomic_add(s, 1, 1, index=ELEMS),
    lambda c, s: c.atomic_inc(s, 1, index=-1),
    lambda c, s: c.atomic_fetch_add(s, 1, 1, index=ELEMS),
    lambda c, s: c.atomic_compare_swap(s, 0, 1, 1, index=ELEMS),
    lambda c, s: c.put_elem(s, 1, ELEMS, 1),
], ids=["put_past_end", "put_before_start", "get_past_end", "add", "inc",
        "fetch_add", "cswap", "put_elem"])
def test_a_range_out_of_the_allocation_raises_and_queues_nothing(ctx, sym,
                                                                 call):
    ctx.put(sym, np.int32([7, 8]), 1, offset=2)  # posted before: it stays
    ops = pvar.PVARS.lookup("shmem_ops").read()
    with pytest.raises(MPIError) as e:
        call(ctx, sym)
    assert e.value.code is ErrorCode.ERR_RMA_RANGE
    assert len(ctx._bulk[sym]) == 1 and not sym._win._pending
    assert pvar.PVARS.lookup("shmem_ops").read() == ops
    ctx.quiet()
    want = np.zeros(ELEMS, np.int32)
    want[2:4] = [7, 8]
    np.testing.assert_array_equal(heap_of(ctx, sym)[1], want)


def test_a_fetching_amo_sees_the_put_posted_before_it(ctx, sym):
    ctx.put(sym, np.int32([41]), 5, offset=9)
    ctx.atomic_inc(sym, 5, index=9)
    assert int(ctx.atomic_fetch_add(sym, 10, 5, index=9)) == 42
    assert int(ctx.atomic_swap(sym, -1, 5, index=9)) == 52
    assert int(ctx.atomic_compare_swap(sym, -1, 3, 5, index=9)) == -1
    assert int(ctx.atomic_compare_swap(sym, -1, 4, 5, index=9)) == 3
    got = np.asarray(ctx.get(sym, 5, offset=8, nelems=3))
    np.testing.assert_array_equal(got, [0, 3, 0])


def test_a_posted_scalar_stays_a_host_scalar_until_the_drain(ctx, sym):
    ctx.atomic_inc(sym, 2, index=3)
    ctx.atomic_add(sym, 5, 2, index=3)
    assert [(t[2], type(t[2]), t[4]) for t in ctx._bulk[sym]] == [
        (1, int, 3), (5, int, 3)]
    ctx.quiet()
    assert int(ctx.atomic_fetch(sym, 2, index=3)) == 6


class Flushes:
    """Counts what a window is asked to flush."""

    def __init__(self, monkeypatch, win):
        self.all, self.targets = 0, []
        flush_all, flush = win.flush_all, win.flush

        def count_all():
            self.all += 1
            return flush_all()

        def count_one(target):
            self.targets.append(target)
            return flush(target)

        monkeypatch.setattr(win, "flush_all", count_all)
        monkeypatch.setattr(win, "flush", count_one)


def test_a_blocking_call_flushes_its_target_once(ctx, sym, monkeypatch):
    seen = Flushes(monkeypatch, sym._win)
    ctx.get(sym, 6, offset=0, nelems=2)
    ctx.atomic_fetch_add(sym, 1, 4, index=0)
    ctx.atomic_compare_swap(sym, 1, 2, 4, index=0)
    assert seen.all == 0 and seen.targets == [6, 4, 4]
    # behind posted operations: their drain (one flush_all), then its own
    ctx.put(sym, np.int32([1]), 6, offset=1)
    ctx.get(sym, 6)
    assert seen.all == 1 and seen.targets == [6, 4, 4, 6]


def test_quiet_skips_an_allocation_with_nothing_to_complete(ctx, sym,
                                                            monkeypatch):
    idle = ctx.malloc((2,), jnp.int32)
    try:
        busy, quiet = Flushes(monkeypatch, sym._win), Flushes(
            monkeypatch, idle._win)
        ctx.put(sym, np.int32([1]), 0, offset=0)
        ctx.quiet()
        ctx.quiet()  # nothing queued anywhere: no window is touched
        assert busy.all == 1 and quiet.all == 0
    finally:
        idle.free()


def test_counters_count_every_operation_and_every_quiet(ctx, sym):
    names = ("shmem_ops", "shmem_blocking_ops", "shmem_quiets",
             "shmem_bulk_ops", "shmem_bulk_flushes")

    def read():
        return np.array([pvar.PVARS.lookup(n).read() for n in names])

    before = read()
    ctx.put(sym, np.int32([1, 2]), 1, offset=0)
    ctx.atomic_add(sym, 1, 1, index=0)
    ctx.atomic_inc(sym, 1, index=1)
    ctx.atomic_set(sym, 9, 1, index=2)
    ctx.quiet()
    ctx.get(sym, 1, offset=0, nelems=3)
    ctx.atomic_fetch_inc(sym, 1, index=0)
    ctx.atomic_swap(sym, 1, 1, index=0)
    ctx.fence()
    assert list(read() - before) == [7, 3, 2, 4, 1]


def test_driver_mode_has_no_pe_of_its_own(ctx, sym):
    assert ctx.my_pe is None
    with pytest.raises(MPIError) as e:
        ctx.wait_until(sym, "eq", 0)
    assert e.value.code is ErrorCode.ERR_ARG
    # a PE this controller plays is polled in its local slot: no request
    seen = pvar.PVARS.lookup("osc_rma_ops").read()
    ctx.put(sym, np.int32([5]), 3, offset=0)
    got = ctx.wait_until(sym, "ge", np.int32([5] + [0] * (ELEMS - 1))
                         .reshape(4, -1), pe=3)
    assert int(np.asarray(got).reshape(-1)[0]) == 5
    assert ctx.test(sym, "eq", 0, pe=2) and not ctx.test(sym, "eq", 0, pe=3)
    assert pvar.PVARS.lookup("osc_rma_ops").read() == seen + 1  # the put
