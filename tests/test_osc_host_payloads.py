"""Where an RMA payload waits between the call and the epoch close
(ISSUE 37): a ``jax.Array`` is queued as the object that was passed, a
host value (Python scalar, numpy scalar, ndarray, list) as a host numpy
snapshot of the shape and dtype ``jnp.asarray`` gives it — never a bare
Python scalar, which an epoch signature would key by value. Driver mode,
eight virtual devices; ``tests/test_shmem_two_ranks.py`` and
``tests/test_obs_spans_shmem.py`` hold the same on two ``tpurun`` ranks.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import ompi_release_tpu as mpi
from ompi_release_tpu import ops
from ompi_release_tpu.mca import pvar
from ompi_release_tpu.osc import win_allocate
from ompi_release_tpu.osc.plan import epoch_signature

SLOT = 4
HOST_PAYLOADS = pvar.PVARS.lookup("osc_host_payloads")


@pytest.fixture(scope="module")
def world():
    yield mpi.init()


@pytest.fixture()
def locked(world):
    """int32 and float32 windows inside a passive epoch on every rank."""
    wins = {"int32": win_allocate(world, (SLOT,), jnp.int32),
            "float32": win_allocate(world, (SLOT,), jnp.float32)}
    for w in wins.values():
        w.lock_all()
    yield wins
    for w in wins.values():
        w.unlock_all()
        w.free()


# entry point -> (call(win, x, **where), which field of the queued op is x)
ENTRIES = {
    "put": (lambda w, x, **k: w.put(x, 1, **k), "data"),
    "accumulate": (lambda w, x, **k: w.accumulate(x, 1, **k), "data"),
    "get_accumulate": (lambda w, x, **k: w.get_accumulate(x, 1, **k),
                       "data"),
    "fetch_and_op": (lambda w, x, **k: w.fetch_and_op(x, 1, **k), "data"),
    "rput": (lambda w, x, **k: w.rput(x, 1, **k), "data"),
    "raccumulate": (lambda w, x, **k: w.raccumulate(x, 1, **k), "data"),
    "cas_value": (lambda w, x, **k: w.compare_and_swap(
        x, jnp.zeros(np.shape(x), jnp.int32), 1, **k), "data"),
    "cas_compare": (lambda w, x, **k: w.compare_and_swap(
        jnp.zeros(np.shape(x), jnp.int32), x, 1, **k), "compare"),
}

# payload kind -> (value, is it on the host)
KINDS = {
    "py_int": (lambda: 5, True),
    "py_float": (lambda: 2.5, True),
    "py_bool": (lambda: True, True),
    "np_int64": (lambda: np.int64(7), True),
    "np_float64": (lambda: np.float64(1.25), True),
    "nd_0d": (lambda: np.array(9, np.int64), True),
    "nd_1d": (lambda: np.arange(SLOT, dtype=np.int64), True),
    "nd_1d_f16": (lambda: np.arange(SLOT, dtype=np.float16), True),
    "list": (lambda: [1, 2, 3, 4], True),
    "jax_0d": (lambda: jnp.asarray(3, jnp.int32), False),
    "jax_1d": (lambda: jnp.arange(SLOT, dtype=jnp.int32), False),
}


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("entry", ENTRIES)
def test_a_payload_is_queued_where_the_caller_had_it(locked, entry, kind):
    call, field = ENTRIES[entry]
    make, on_host = KINDS[kind]
    x = make()
    win = locked["int32"]
    where = {"index": 2} if np.ndim(x) == 0 else {}
    before = HOST_PAYLOADS.read()
    call(win, x, **where)
    queued = getattr(win._pending[-1], field)
    want = jnp.asarray(x)
    if on_host:
        assert type(queued) is np.ndarray and queued is not x
    else:
        assert queued is x  # no copy, no fetch, no launch
    assert queued.shape == want.shape and queued.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(queued), np.asarray(want))
    assert HOST_PAYLOADS.read() - before == int(on_host)
    win.flush_all()


def _signature_of(win, queue):
    queue(win)
    sig = epoch_signature(win._pending)
    win.flush_all()
    return sig


@pytest.mark.parametrize("entry", ENTRIES)
def test_a_signature_carries_shape_and_dtype_never_the_value(locked, entry):
    """The ``("v", value)`` trap: a bare Python int in the queue would
    key the signature by its value, and every distinct operand would
    freeze a plan of its own."""
    call, _ = ENTRIES[entry]
    win = locked["int32"]
    plans = len(win._access_plans)
    sigs = [_signature_of(win, lambda w, x=x: call(w, x, index=1))
            for x in (3, 7, np.int64(11), np.array(13),
                      jnp.asarray(3, jnp.int32), 2**31 - 1)]
    assert sigs[0] is not None and len(set(sigs)) == 1
    assert len(win._access_plans) == plans + 1
    blocks = [_signature_of(win, lambda w, x=x: call(w, x))
              for x in (np.arange(SLOT), [4, 3, 2, 1],
                        jnp.arange(SLOT, dtype=jnp.int32))]
    assert len(set(blocks)) == 1 and blocks[0] != sigs[0]


@pytest.mark.parametrize("entry", ENTRIES)
def test_a_buffer_rewritten_before_the_flush_lands_as_it_was_passed(
        locked, entry):
    call, _ = ENTRIES[entry]

    def after(rewrite):
        win = locked["int32"]
        win.put(np.zeros(SLOT, np.int32), 1)
        win.flush_all()
        buf = np.array([1, 0, 3, 0], np.int32)
        req = call(win, buf)
        if rewrite:
            buf[:] = 99
        win.flush_all()
        return (np.asarray(win.read())[1].tobytes(),
                None if req is None else np.asarray(req.value).tobytes())

    assert after(rewrite=True) == after(rewrite=False)


def _play(win, device: bool, script):
    """``script`` three times (capture, freeze, replay): the window's
    bytes and every fetched value's bytes and dtype, per repetition."""
    conv = jnp.asarray if device else (lambda x: x)
    out = []
    for _ in range(3):
        reqs = script(win, conv)
        win.flush_all()
        out.append((np.asarray(win.read()).tobytes(),
                    [(str(r.value.dtype), np.asarray(r.value).tobytes())
                     for r in reqs if r is not None]))
    return out


TOP = 2**31 - 1
SCRIPTS = {
    "sum_wraps_int32": ("int32", lambda w, c: [
        w.put(c(TOP), 3, index=0), w.accumulate(c(5), 3, index=0),
        w.fetch_and_op(c(TOP), 3, ops.SUM, index=0),
        w.accumulate(c(np.full(SLOT, TOP)), 2),
        w.get_accumulate(c([TOP, 1, -TOP, 2]), 2)]),
    "replace": ("int32", lambda w, c: [
        w.accumulate(c(8), 5, ops.REPLACE, index=3),
        w.fetch_and_op(c(-4), 5, ops.REPLACE, index=3),
        w.raccumulate(c(np.int64(6)), 5, ops.REPLACE),
        w.rput(c([4, 3, 2, 1]), 6)]),
    "cas_hit_and_miss": ("int32", lambda w, c: [
        w.put(c(10), 4, index=1),
        w.compare_and_swap(c(20), c(10), 4, index=1),   # hit
        w.compare_and_swap(c(30), c(10), 4, index=1),   # miss
        w.compare_and_swap(c(np.int64(40)), c(np.array(20)), 4, index=1),
        w.compare_and_swap(c([7, 7, 7, 7]), c([0, 40, 0, 1]), 4)]),
    "float_into_int32": ("int32", lambda w, c: [
        w.put(c(2.75), 1, index=0), w.accumulate(c(-1.5), 1, index=0),
        w.fetch_and_op(c(np.float64(3.99)), 1, ops.SUM, index=0),
        w.put(c(np.array([0.5, 1.5, -2.5, 1e3], np.float32)), 7),
        w.compare_and_swap(c(9.9), c(1.2), 7, index=1)]),
    "ranged_and_float32": ("float32", lambda w, c: [
        w.put(c(np.array([1.5, 2.5])), 2, disp=1),
        w.accumulate(c([0.25, 0.25]), 2, disp=2),
        w.get_accumulate(c(3), 2, disp=0),
        w.compare_and_swap(c([8.0, 9.0]), c(np.array([4.5, 0.0])), 2,
                           disp=1)]),
}


@pytest.mark.parametrize("name", SCRIPTS)
def test_results_are_bitwise_those_of_device_payloads(world, name):
    dtype, script = SCRIPTS[name]
    got = {}
    for device in (False, True):
        win = win_allocate(world, (SLOT,), jnp.dtype(dtype))
        win.lock_all()
        before = HOST_PAYLOADS.read()
        got[device] = _play(win, device, script)
        ticked = HOST_PAYLOADS.read() - before
        assert (ticked == 0) if device else (ticked > 0)
        win.unlock_all()
        win.free()
    assert got[False] == got[True]
    assert any(f for _, f in got[False])  # something was fetched


@pytest.mark.parametrize("bad", [2**31, -2**31 - 1, 2**40, [0, 2**31],
                                 None, "seven"],
                         ids=["top", "bottom", "far", "in_a_list", "none",
                              "str"])
@pytest.mark.parametrize("entry", ENTRIES)
def test_what_jnp_asarray_refuses_is_refused_at_the_call(locked, entry, bad):
    call, _ = ENTRIES[entry]
    win = locked["int32"]
    with pytest.raises((OverflowError, TypeError, ValueError)) as theirs:
        jnp.asarray(bad)
    before = HOST_PAYLOADS.read()
    with pytest.raises(theirs.type):
        call(win, bad, index=0)
    assert not win._pending and HOST_PAYLOADS.read() == before
