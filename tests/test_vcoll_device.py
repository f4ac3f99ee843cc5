"""The device form of ``world.alltoallv``: a rank-sharded ``(n, m)``
send buffer and ``(n, n)`` count matrix in, the ``(n, recv_capacity)``
receive buffer and the received counts out, every one on the
communicator's devices and none of it read on the host.

On the CPU mesh the padded body runs (XLA:CPU has no
ragged-all-to-all); the ragged body's operands are fed to a numpy model
of ``lax.ragged_all_to_all`` as its docstring states it, so what the
TPU path would move is held to the same reference.
"""

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

import ompi_release_tpu as mpi
from ompi_release_tpu.coll import vcoll
from ompi_release_tpu.coll.driver import run_sharded
from ompi_release_tpu.mca import pvar
from ompi_release_tpu.utils.errors import ErrorCode, MPIError

M = 700  # elements a rank sends: more than five rows of the exchange


@pytest.fixture(scope="module")
def world():
    yield mpi.init()


def _put(comm, a):
    return jax.device_put(np.asarray(a), NamedSharding(comm.submesh,
                                                       P("rank")))


def _read(name):
    return pvar.PVARS.lookup(name).read()


def _split(total, n, rng, weights=None):
    """``total`` elements cut into ``n`` contiguous counts."""
    w = rng.random(n) if weights is None else np.asarray(weights, float)
    c = np.floor(total * w / w.sum()).astype(np.int32)
    c[np.argmax(w)] += total - c.sum()
    return c


def counts_of(pattern, n, rng):
    c = np.zeros((n, n), np.int32)
    for i in range(n):
        if pattern == "uniform":
            c[i] = M // n
        elif pattern == "skewed":
            # NAS IS's cut of a bell: the middle ranks' buckets are dense
            mid = np.exp(-((np.arange(n) - (n - 1) / 2) / (n / 4)) ** 2)
            c[i] = _split(rng.integers(M // 2, M + 1), n, rng, mid)
        elif pattern == "silent_rank":
            c[i] = 0 if i == 1 else _split(rng.integers(0, M + 1), n, rng)
        elif pattern == "hot_pair":
            c[i] = 3
            if i == 0:
                c[i, n - 1] = M - 3 * (n - 1)
        elif pattern == "random":
            c[i] = _split(rng.integers(0, M + 1), n, rng)
    return c


def reference(x, c, cap):
    """Row j: the chunks for j from each source in source order, cut at
    ``cap``, zero past it; the received counts; what was cut."""
    n = c.shape[0]
    off = np.cumsum(c, axis=1) - c
    recv = np.zeros((n, cap), x.dtype)
    cut = np.zeros(n, np.int64)
    for j in range(n):
        got = np.concatenate([x[i, off[i, j]:off[i, j] + c[i, j]]
                              for i in range(n)])
        cut[j] = max(0, got.size - cap)
        recv[j, :min(cap, got.size)] = got[:cap]
    return recv, c.T, cut


def _on_own_devices(comm, a):
    devices = list(comm.submesh.devices.flat)
    assert set(a.sharding.device_set) == set(devices)
    for shard in a.addressable_shards:
        assert shard.device == devices[shard.index[0].start or 0]


@pytest.mark.parametrize("pattern", ["uniform", "skewed", "silent_rank",
                                     "hot_pair", "random"])
def test_matches_the_reference_on_the_chips(world, pattern):
    n, rng = world.size, np.random.default_rng(len(pattern))
    c = counts_of(pattern, n, rng)
    cap = int(c.sum(0).max()) + 64
    x = rng.integers(-2**31, 2**31, (n, M), dtype=np.int64).astype(np.int32)
    want, want_counts, cut = reference(x, c, cap)
    assert not cut.any()
    host0, dropped0 = _read("vcoll_alltoallv_host_bytes"), vcoll.truncated(
        world)
    calls0 = _read("vcoll_alltoallv_device_calls")
    recv, counts = world.alltoallv(_put(world, x), _put(world, c),
                                   recv_capacity=cap)
    assert isinstance(recv, jax.Array) and recv.shape == (n, cap)
    np.testing.assert_array_equal(np.asarray(recv), want)
    np.testing.assert_array_equal(np.asarray(counts), want_counts)
    _on_own_devices(world, recv)
    _on_own_devices(world, counts)
    assert _read("vcoll_alltoallv_host_bytes") == host0
    assert _read("vcoll_alltoallv_device_calls") == calls0 + 1
    np.testing.assert_array_equal(np.asarray(vcoll.truncated(world)),
                                  np.asarray(dropped0))


def test_past_the_capacity_the_counts_and_the_counter_say_so(world):
    """A total past ``recv_capacity`` is cut where the buffer ends, never
    silently: the counts are what was sent, and the device-side counter
    holds what was cut."""
    n, rng = world.size, np.random.default_rng(3)
    comm = world.dup(name="vcoll_truncate")
    try:
        c = counts_of("random", n, rng)
        c[:, 2] += 100  # rank 2 is sent 100 more from every source
        c[:, 2] = np.minimum(c[:, 2], M - (c.sum(1) - c[:, 2]))
        cap = int(c[:, 2].sum()) - 37
        x = rng.integers(0, 1000, (n, M)).astype(np.int32)
        want, want_counts, cut = reference(x, c, cap)
        assert cut[2] == 37
        recv, counts = comm.alltoallv(_put(comm, x), _put(comm, c),
                                      recv_capacity=cap)
        np.testing.assert_array_equal(np.asarray(recv), want)
        np.testing.assert_array_equal(np.asarray(counts), want_counts)
        assert np.asarray(counts)[2].sum() == cap + 37
        np.testing.assert_array_equal(np.asarray(vcoll.truncated(comm)), cut)
        comm.alltoallv(_put(comm, x), _put(comm, c), recv_capacity=cap)
        np.testing.assert_array_equal(np.asarray(vcoll.truncated(comm)),
                                      2 * cut)
    finally:
        comm.free()


def test_changing_counts_compile_nothing_new(world):
    n, rng = world.size, np.random.default_rng(4)
    x = _put(world, rng.integers(0, 9, (n, M)).astype(np.int32))
    cap = n * M
    world.alltoallv(x, _put(world, counts_of("random", n, rng)),
                    recv_capacity=cap)
    compiled = _read("coll_programs_compiled")
    for pattern in ("uniform", "skewed", "silent_rank", "hot_pair"):
        c = counts_of(pattern, n, rng)
        recv, _ = world.alltoallv(x, _put(world, c), recv_capacity=cap)
        want, _, _ = reference(np.asarray(x), c, cap)
        np.testing.assert_array_equal(np.asarray(recv), want)
    assert _read("coll_programs_compiled") == compiled


def test_a_one_rank_communicator_through_the_self_component(world):
    solo = world.create(world.group.incl([3]), name="solo_device")
    try:
        x = np.arange(M, dtype=np.int32)[None]
        c = np.array([[M - 5]], np.int32)
        recv, counts = solo.alltoallv(_put(solo, x), _put(solo, c),
                                      recv_capacity=M)
        want = np.zeros((1, M), np.int32)
        want[0, :M - 5] = x[0, :M - 5]
        np.testing.assert_array_equal(np.asarray(recv), want)
        np.testing.assert_array_equal(np.asarray(counts), c)
        _on_own_devices(solo, recv)
    finally:
        solo.free()


def ragged_model(operand, output, input_offsets, send_sizes,
                 output_offsets, recv_sizes):
    """``lax.ragged_all_to_all`` as its docstring states it, over all
    ranks at once (leading axis: the rank; one slice per peer)."""
    n = len(operand)
    result = [o.copy() for o in output]
    for i in range(n):
        for j in range(n):
            k = send_sizes[i][j]
            assert k == recv_sizes[j][i]  # what i sends, j expects
            src = operand[i][input_offsets[i][j]:input_offsets[i][j] + k]
            result[j][output_offsets[i][j]:output_offsets[i][j] + k] = src
    return result


@pytest.mark.parametrize("pattern", ["skewed", "silent_rank", "hot_pair",
                                     "truncated"])
def test_the_ragged_operands_give_the_reference(world, pattern):
    """What the TPU body would hand ``lax.ragged_all_to_all``, built by
    the same function inside a program, fed to the numpy model; the
    receive buffer compacted from its rows as the body does."""
    n, rng = world.size, np.random.default_rng(5)
    c = counts_of("random" if pattern == "truncated" else pattern, n, rng)
    cap = int(c.sum(0).max()) + (64 if pattern != "truncated" else -50)
    x = rng.integers(0, 1 << 20, (n, M)).astype(np.int32)

    def operands(sc):
        return tuple(vcoll.exchange_operands(sc, cap))

    ops = run_sharded(world, ("test", "operands", cap), operands,
                      _put(world, c))
    o = vcoll.Operands(*[np.asarray(a) for a in ops])
    r = vcoll.GRANULE
    rows = np.zeros((n, -(-M // r) * r), np.int32)
    rows[:, :M] = x
    rows = rows.reshape(n, -1, r)
    stage = np.zeros((n, vcoll.stage_rows(n, cap), r), np.int32)
    staged = ragged_model(rows, stage, o.input_offsets, o.send_sizes,
                          o.output_offsets, o.recv_sizes)
    want, want_counts, cut = reference(x, c, cap)
    for j in range(n):
        flat = staged[j].reshape(-1)
        got = np.zeros(cap, np.int32)
        for k in range(n):
            got[o.at[j, k]:o.at[j, k] + o.held[j, k]] = flat[
                o.start[j, k]:o.start[j, k] + o.held[j, k]]
        np.testing.assert_array_equal(got, want[j])
    np.testing.assert_array_equal(o.recvcounts, want_counts)
    np.testing.assert_array_equal(o.dropped, cut)
    assert (pattern == "truncated") == bool(cut.any())


def test_the_body_follows_the_backend(world):
    assert not vcoll._ragged_on(world)  # XLA:CPU: the padded body


@pytest.mark.parametrize("bad", ["buffer_rows", "counts_shape",
                                 "no_capacity"])
def test_shapes_are_checked_on_the_host(world, bad):
    n = world.size
    x = _put(world, np.zeros((n, 8), np.int32))
    c = _put(world, np.zeros((n, n), np.int32))
    with pytest.raises(MPIError) as e:
        if bad == "buffer_rows":
            world.alltoallv(x[:n - 1], c, recv_capacity=8)
        elif bad == "counts_shape":
            world.alltoallv(x, c[:, :2], recv_capacity=8)
        else:
            world.alltoallv(x, c, recv_capacity=0)
    assert e.value.code == ErrorCode.ERR_COUNT


@pytest.mark.parametrize("keys_log2", [9, 12])
def test_both_bodies_script_on_nas_is_counts(world, keys_log2):
    """``tests/chip_alltoallv.py`` (both bodies at NAS IS class C on the
    chips) as the CPU runs it: the padded body on IS's bucketed keys and
    NAS's cut, against the same numpy model."""
    import chip_alltoallv

    got = chip_alltoallv.run(world, keys_log2, 2**31 + 5, ["padded"])
    assert got["padded"]["match"]
    assert not any(got["cut"]) and not any(got["truncated"])
    assert np.asarray(got["send_counts"]).sum() == world.size << keys_log2
