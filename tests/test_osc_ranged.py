"""RMA on a contiguous block at a displacement (``disp=``/``count=``),
held to a plain numpy model of a window (``rma_reference.py``, which
imports nothing of the library) on seeded random epochs that mix kinds,
extents (whole slot, one element, ranges that overlap) and targets:

1. ``Window`` in driver mode on four virtual devices, under fence,
   lock/unlock and flush: the interpreted close and the planned close
   give bitwise the same reads and the same window, and both equal the
   model.
2. The same sequences through ``WireWindow`` between two ``tpurun`` ranks
   on the CPU (one origin at a time, so the order is the program's), as a
   second case of the test that runs them in driver mode.
3. ``ERR_RMA_RANGE`` at the call site, before anything is queued.
4. The wire audit: 64 puts of 16 KiB into a 64 MiB slot ship 64 x 16 KiB
   plus a header of a few KiB, 64 gets bring the same back.
5. No argument and no result of an epoch program but the window itself
   has the slot's size.
"""

import json
import os
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import ompi_release_tpu as mpi
from ompi_release_tpu.mca import pvar
from ompi_release_tpu.mca import var as mca_var
from ompi_release_tpu.ops.op import PREDEFINED_OPS
from ompi_release_tpu.osc import window as osc_window
from ompi_release_tpu.osc.window import win_allocate, win_create
from ompi_release_tpu.tools.tpurun import Job
from ompi_release_tpu.utils.errors import ErrorCode, MPIError

import rma_reference as ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHAPE = (4, 6)  # a slot: 24 elements, flattened in C order
SEEDS = (11, 2147483659)


@pytest.fixture(scope="module")
def world():
    yield mpi.init()


def issue(win, ops):
    """One epoch's operations on a window of the library, in order: one
    entry per operation, its request or None."""
    reqs = []
    for op in ops:
        kind, t, ext = op["kind"], op["target"], ref.extent_of(op)
        if kind == "put":
            reqs.append(win.put(op["data"], t, **ext))
        elif kind == "get":
            reqs.append(win.get(t, **{k: op[k] for k in ("disp", "count")
                                      if k in op}))
        elif kind == "accumulate":
            reqs.append(win.accumulate(op["data"], t,
                                       PREDEFINED_OPS[op["op"]], **ext))
        elif kind == "get_accumulate":
            reqs.append(win.get_accumulate(op["data"], t,
                                           PREDEFINED_OPS[op["op"]], **ext))
        else:
            reqs.append(win.compare_and_swap(op["data"], op["compare"], t,
                                             **ext))
    return reqs


def epoch(win, sync, ops):
    """``ops`` inside one epoch of kind ``sync``; the read values."""
    targets = sorted({op["target"] for op in ops})
    if sync == "fence":
        win.fence()
        reqs = issue(win, ops)
        win.fence_end()
    else:
        win.lock_all()
        reqs = issue(win, ops)
        if sync == "flush":
            for t in targets:
                win.flush(t)
            # a flush completes what was issued: readable before unlock
            assert all(r is None or r.is_complete for r in reqs)
        win.unlock_all()
    return [None if r is None else np.asarray(r.value) for r in reqs]


def same(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert (g is None) == (w is None)
        if w is not None:
            assert g.shape == np.shape(w) and g.dtype == np.float32
            np.testing.assert_array_equal(g, w)


def run_driver(world, sync, seed, compiled, repeats=3):
    """The seeded epochs on a fresh window, each ``repeats`` times (a
    repeated epoch replays its frozen plan when plans are on). Returns
    every epoch's reads and the window after each, with the model's."""
    rng = np.random.default_rng(seed)
    base = rng.integers(0, 6, (world.size,) + SHAPE).astype(np.float32)
    mca_var.set_value("osc_compiled", int(compiled))
    try:
        win, model = win_create(world, base), ref.Model(base)
        out = []
        for _ in range(2):
            ops = ref.random_epoch(rng, range(world.size), SHAPE, 10)
            for _ in range(repeats):
                got, want = epoch(win, sync, ops), ref.apply(model, ops)
                same(got, want)
                state = np.asarray(win.read())
                np.testing.assert_array_equal(state, model.read())
                out.append((got, state))
        win.free()
        return out
    finally:
        mca_var.VARS.unset("osc_compiled")


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("sync", ["fence", "lock", "flush"])
def test_interpreted_and_planned_closes_are_bitwise_equal(world, sync, seed):
    """Both equal the model (inside ``run_driver``) and each other, bit
    for bit; with plans on, the repeats replayed a frozen plan."""
    import ompi_release_tpu.osc.plan  # noqa: F401  (its counters)

    interpreted = run_driver(world, sync, seed, compiled=False)
    hits0 = pvar.PVARS.lookup("osc_plan_cache_hits").read()["sum"]
    planned = run_driver(world, sync, seed, compiled=True)
    assert pvar.PVARS.lookup("osc_plan_cache_hits").read()["sum"] > hits0
    for (reads_i, state_i), (reads_p, state_p) in zip(interpreted, planned):
        assert state_i.tobytes() == state_p.tobytes()
        for a, b in zip(reads_i, reads_p):
            assert (a is None and b is None) or a.tobytes() == b.tobytes()


def test_whole_slot_and_element_calls_keep_their_results(world):
    """``disp`` absent: a slot-shaped read for a whole-slot call, the
    element itself for an ``index=`` call, as before ranged operations
    existed; a ranged read is 1-D whatever the slot's shape."""
    base = np.arange(world.size * 24, dtype=np.float32).reshape(
        (world.size,) + SHAPE)
    win = win_create(world, base)
    win.fence()
    whole = win.get(1)
    elem = win.fetch_and_op(np.float32(1), 1, index=7)
    block = win.rget(1, disp=6, count=6)
    pre = win.rput(np.zeros(3, np.float32), 2, disp=0)
    win.fence_end()
    assert whole.value.shape == SHAPE and elem.value.shape == ()
    np.testing.assert_array_equal(np.asarray(whole.value), base[1])
    assert float(elem.value) == 24 + 7
    want = base[1].reshape(-1)[6:12].copy()
    want[1] += 1  # the fetch_and_op before it in the epoch, element 7
    np.testing.assert_array_equal(np.asarray(block.value), want)
    np.testing.assert_array_equal(np.asarray(pre.value), [48, 49, 50])
    assert isinstance(block.value, jax.Array)
    win.free()


@pytest.mark.parametrize("call, code", [
    (lambda w: w.put(np.zeros(5, np.float32), 1, disp=20),
     ErrorCode.ERR_RMA_RANGE),
    (lambda w: w.get(1, disp=24, count=1), ErrorCode.ERR_RMA_RANGE),
    (lambda w: w.get(1, disp=-1, count=2), ErrorCode.ERR_RMA_RANGE),
    (lambda w: w.get(1, disp=0, count=0), ErrorCode.ERR_RMA_RANGE),
    (lambda w: w.accumulate(np.zeros(25, np.float32), 0, disp=0),
     ErrorCode.ERR_RMA_RANGE),
    (lambda w: w.rget(2, disp=23, count=2), ErrorCode.ERR_RMA_RANGE),
    (lambda w: w.get(1, disp=3), ErrorCode.ERR_ARG),
    (lambda w: w.get(1, count=3), ErrorCode.ERR_ARG),
    (lambda w: w.put(np.float32(1), 1, index=2, disp=2), ErrorCode.ERR_ARG),
])
def test_a_range_that_leaves_the_slot_is_refused_at_the_call_site(
        world, call, code):
    win = win_allocate(world, SHAPE, jnp.float32)
    win.fence()
    issued = pvar.PVARS.lookup("osc_rma_ops").read()
    with pytest.raises(MPIError) as e:
        call(win)
    assert e.value.code is code
    # before anything is queued
    assert not win._pending
    assert pvar.PVARS.lookup("osc_rma_ops").read() == issued
    win.put(np.ones(4, np.float32), 1, disp=20)  # the last four: fine
    win.fence_end()
    assert np.asarray(win.read())[1].reshape(-1)[20:].tolist() == [1.0] * 4
    win.free()


@pytest.mark.parametrize("compiled", [False, True])
def test_nothing_but_the_window_has_the_slots_size(world, monkeypatch,
                                                   compiled):
    """Every argument and result of every epoch program of a ranged
    epoch — payloads, compares, reads — is of the blocks' size: 16
    operations of 8 elements on a slot of 65,536 never stage, take or
    hand back 65,536 elements but in the window itself."""
    slot, seen = 1 << 16, []

    real_jit = jax.jit

    def spy_jit(fn, *a, **kw):
        prog = real_jit(fn, *a, **kw)
        if getattr(fn, "__name__", "") not in (
                "close_epoch", "close_ranged", "fused"):
            return prog

        def call(data, *args):
            out = prog(data, *args)
            seen.append((jax.tree_util.tree_leaves(args),
                         jax.tree_util.tree_leaves(out[1:]), out[0]))
            return out

        return call

    monkeypatch.setattr(jax, "jit", spy_jit)
    osc_window._program_cache.clear()
    mca_var.set_value("osc_compiled", int(compiled))
    try:
        win = win_allocate(world, (slot,), jnp.float32)
        for _ in range(3):
            win.fence()
            for j in range(8):
                win.put(np.full(8, j, np.float32), j % world.size, disp=64 * j)
                win.get_accumulate(np.ones(8, np.float32), (j + 1) % world.size,
                                   disp=8 * j)
            win.fence()  # an epoch of gets alone
            reads = [win.get(2, disp=j, count=8) for j in range(4)]
            win.fence_end()
            assert all(r.value.shape == (8,) for r in reads)
        win.free()
    finally:
        mca_var.VARS.unset("osc_compiled")
        osc_window._program_cache.clear()
    assert seen
    for args, results, window in seen:
        assert all(int(np.size(a)) < slot for a in args)
        assert all(int(np.size(r)) < slot for r in results)
        assert window is None or window.shape == (world.size, slot)
    # gets alone hand no window back
    assert any(window is None for _, _, window in seen)


# ---------------------------------------------------------------------------
# two tpurun ranks on the CPU: the same sequences through WireWindow, and
# the wire audit
# ---------------------------------------------------------------------------

APP = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, %r)
    sys.path.insert(0, os.path.join(%r, "tests"))
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import numpy as np
    import jax.numpy as jnp
    import ompi_release_tpu as mpi
    import ompi_release_tpu.osc.wire_win  # its counters
    from ompi_release_tpu.mca import pvar
    from ompi_release_tpu.osc.window import win_allocate, win_create
    import rma_reference as ref
    import test_osc_ranged as t

    world = mpi.init()
    me = world.local_comm_ranks[0]
    out, sync, seed = sys.argv[1], sys.argv[2], int(sys.argv[3])
    verdict = {"reads": 0}

    def pv(name):
        return pvar.PVARS.lookup(name).read()

    # the driver-mode sequences: one origin at a time, both ranks keep the
    # model; an origin's reads and every rank's own slot are compared
    rng = np.random.default_rng(seed)
    base = rng.integers(0, 6, (2,) + t.SHAPE).astype(np.float32)
    win, model = win_create(world, base[me:me + 1]), ref.Model(base)
    for phase in range(4):
        origin = phase %% 2
        ops = ref.random_epoch(rng, (0, 1), t.SHAPE, 10)
        for _ in range(3):  # the repeats replay frozen plans and frames
            want = ref.apply(model, ops)
            if sync == "fence":
                win.fence()
                reqs = t.issue(win, ops) if me == origin else []
                win.fence_end()
            elif me == origin:
                win.lock_all()
                reqs = t.issue(win, ops)
                if sync == "flush":
                    win.flush(0)
                    win.flush(1)
                    assert all(r is None or r.is_complete for r in reqs)
                win.unlock_all()
            world.barrier()
            if me == origin:
                t.same([None if r is None else np.asarray(r.value)
                        for r in reqs], want)
                verdict["reads"] += sum(r is not None for r in reqs)
            np.testing.assert_array_equal(np.asarray(win.read())[0],
                                          model.read()[me])
    win.free()

    # the wire audit: 64 operations of 16 KiB on a 64 MiB slot
    n, slot = 4096, 16 << 20
    big = win_allocate(world, (slot,), jnp.float32)
    if me == 0:
        x = np.arange(64 * n, dtype=np.float32)
        big.lock(1)
        for rep in range(2):
            b0, o0 = pv("osc_wire_bytes"), pv("osc_wire_ops")
            for j in range(64):
                big.put(x[j * n:(j + 1) * n], 1, disp=(64 - j) * n)
            big.flush(1)
            verdict["put_bytes"] = pv("osc_wire_bytes") - b0
            b0 = pv("osc_wire_bytes")
            reqs = [big.get(1, disp=(64 - j) * n, count=n) for j in range(64)]
            big.flush(1)
            verdict["get_bytes"] = pv("osc_wire_bytes") - b0
            verdict["ops"] = pv("osc_wire_ops") - o0
            got = np.concatenate([np.asarray(r.value) for r in reqs])
            verdict["got_back"] = bool(np.array_equal(got, x))
        big.unlock(1)
    world.barrier()
    if me == 1:
        held = np.asarray(big.read())[0]
        want = np.zeros(slot, np.float32)
        for j in range(64):
            want[(64 - j) * n:(65 - j) * n] = np.arange(j * n, (j + 1) * n)
        verdict["slot"] = bool(np.array_equal(held, want))
    big.free()
    with open(os.path.join(out, "rank%%d.json" %% me), "w") as f:
        json.dump(verdict, f)
    world.barrier()
    mpi.finalize()
""") % (REPO, REPO)


def run_tpurun(tmp_path, sync, seed, compiled):
    app = tmp_path / "app.py"
    app.write_text(APP)
    job = Job(2, [sys.executable, str(app), str(tmp_path), sync, str(seed)],
              [("osc_compiled", str(int(compiled)))],
              heartbeat_s=0.5, miss_limit=20)
    assert job.run(timeout_s=300) == 0
    ranks = []
    for rank in (0, 1):
        with open(tmp_path / f"rank{rank}.json") as f:
            ranks.append(json.load(f))
    return ranks


@pytest.mark.parametrize("mode, sync, compiled", [
    ("driver", "lock", True),
    ("tpurun", "fence", True),
    ("tpurun", "lock", True),
    ("tpurun", "flush", True),
    ("tpurun", "flush", False),
])
def test_driver_mode_and_tpurun_ranks_match_the_model(world, tmp_path, mode,
                                                      sync, compiled):
    """The same seeded sequences in one process (four virtual devices)
    and between two ``tpurun`` ranks: every read and every slot equal
    the numpy model, and on the wire the audit holds."""
    seed = SEEDS[1]
    if mode == "driver":
        assert run_driver(world, sync, seed, compiled)
        return
    rank0, rank1 = run_tpurun(tmp_path, sync, seed, compiled)
    assert rank0["reads"] > 0 and rank1["reads"] > 0
    payload = 64 * 16 * 1024
    # 64 x 16 KiB and a header of a few KiB out; for the gets a header
    # out and the same 64 x 16 KiB coming back
    assert payload < rank0["put_bytes"] <= payload + 8 * 1024
    assert payload < rank0["get_bytes"] <= payload + 8 * 1024
    assert rank0["ops"] == 128 and rank0["got_back"] and rank1["slot"]
