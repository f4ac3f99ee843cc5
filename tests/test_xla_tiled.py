"""The ``xla`` component's allgather, alltoall and reduce_scatter_block
run their collective tiled on the rank's block as the caller lays it
out (PR 39): no ``(n, ...)`` view of it before the collective and none
after. Each result is held to numpy, and bit for bit to the reshaped
lowering the component had before, which this file keeps as its
oracle; the StableHLO of each program is held to the tiled form."""

import re
import zlib

import jax
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

import ompi_release_tpu as mpi
from ompi_release_tpu import ops
from ompi_release_tpu.coll import spmd

AXIS = "rank"


@pytest.fixture(scope="module")
def comms():
    world = mpi.init()
    four = world.create(world.group.incl([0, 1, 2, 3]), name="xla_tiled4")
    yield {4: four, 8: world}
    four.free()


def _oracle(comm, body, x):
    """``body`` run as ``coll/driver.run_sharded`` runs a component's."""
    def wrapper(xb):
        return body(xb[0])[None]

    return jax.jit(jax.shard_map(wrapper, mesh=comm.submesh,
                                 in_specs=P(AXIS), out_specs=P(AXIS)))(x)


# the parent's reshaped lowerings, kept as the oracle
def _allgather_reshaped(n):
    def body(xb):
        g = lax.all_gather(xb, AXIS, axis=0)
        return g.reshape((-1,) + g.shape[2:])
    return body


def _alltoall_reshaped(n):
    def body(xb):
        blocks = xb.reshape((n, -1) + xb.shape[1:])
        out = lax.all_to_all(blocks, AXIS, 0, 0, tiled=False)
        return out.reshape(xb.shape)
    return body


def _rsb_reshaped(n):
    def body(xb):
        blocks = xb.reshape((n, xb.shape[0] // n) + xb.shape[1:])
        return lax.psum_scatter(blocks, AXIS, scatter_dimension=0,
                                tiled=False)
    return body


def _expected(op, x):
    n = x.shape[0]
    if op == "allgather":
        return np.broadcast_to(x.reshape((-1,) + x.shape[2:]),
                               (n, n * x.shape[1]) + x.shape[2:])
    chunk = x.shape[1] // n
    blocks = x.reshape((n, n, chunk) + x.shape[2:])  # [rank, block]
    if op == "alltoall":
        return np.swapaxes(blocks, 0, 1).reshape(x.shape)
    return blocks.sum(axis=0)


# a rank's block: 1-D of whole (8, 128) tiles (reduce_scatter_block's
# 128-lane rows), 1-D of any length, and 2-D
LAYOUTS = {"lanes": lambda n: (n * 2048,), "flat": lambda n: (n * 6,),
           "2d": lambda n: (n * 3, 5)}
COLLECTIVE = {"allgather": "all_gather", "alltoall": "all_to_all",
              "reduce_scatter_block": "reduce_scatter"}


def _tensor(shape, dtype):
    return "tensor<" + "x".join(str(d) for d in shape) + "x" + dtype + ">"


@pytest.mark.parametrize("n", [4, 8])
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("op", sorted(COLLECTIVE))
def test_tiled_on_the_block(comms, op, layout, dtype, n):
    comm = comms[n]
    assert comm._coll_providers[op][0] == "xla"
    block = LAYOUTS[layout](n)
    rng = np.random.default_rng(zlib.crc32(f"{op}:{layout}:{n}".encode()))
    x = (rng.standard_normal((n,) + block) if dtype == np.float32
         else rng.integers(-1000, 1000, (n,) + block)).astype(dtype)
    if op == "reduce_scatter_block":
        out = comm.reduce_scatter_block(x, ops.SUM)
        oracle = _rsb_reshaped(n)
    else:
        out = getattr(comm, op)(x)
        oracle = {"allgather": _allgather_reshaped,
                  "alltoall": _alltoall_reshaped}[op](n)
    got = np.asarray(out)
    want = _expected(op, x)
    assert got.shape == want.shape and got.dtype == x.dtype
    if dtype == np.float32 and op == "reduce_scatter_block":
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    else:
        np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, np.asarray(_oracle(comm, oracle, x)))

    # the program: its collective takes the flat block and gives the
    # result, and nothing but the unit rank axis is reshaped around it
    key = ("xla", op) + ((ops.SUM,) if op == "reduce_scatter_block" else ())
    text = comm._coll_programs[key].lower(x).as_text()
    ty = {np.float32: "f32", np.int32: "i32"}[dtype]
    lanes = op == "reduce_scatter_block" and layout == "lanes"
    operand = (block[0] // 128, 128) if lanes else block
    result = {"allgather": (n * block[0],) + block[1:], "alltoall": block,
              "reduce_scatter_block": (operand[0] // n,) + operand[1:]}[op]
    sig = re.findall(rf'"stablehlo\.{COLLECTIVE[op]}"\(.*?'
                     r" : \((tensor<[^>]*>)\) -> (tensor<[^>]*>)", text,
                     re.DOTALL)
    assert sig == [(_tensor(operand, ty), _tensor(result, ty))], text
    # x[0]'s squeeze, and for the 128-lane rows the view there and back
    reshapes = re.findall(r"stablehlo\.reshape %\S+ : \((\S+)\) -> (\S+)",
                          text)
    assert reshapes[0] == (_tensor((1,) + block, ty), _tensor(block, ty))
    assert len(reshapes) == (3 if lanes else 1), reshapes
    assert text.count("stablehlo.broadcast_in_dim") == 1


def test_alltoall_lax_keeps_its_blocks(comms):
    """``spmd.alltoall_lax`` still takes ``(n, chunk...)`` blocks, as
    ``tuned``'s lax/basic_linear alltoall and ``vcoll``'s alltoallv call
    it: ``out[j]`` is what rank j sent this rank."""
    comm = comms[4]
    x = np.arange(4 * 4 * 3 * 2, dtype=np.int32).reshape(4, 4, 3, 2)
    out = np.asarray(_oracle(
        comm, lambda b: spmd.alltoall_lax(b, AXIS, 4), x))
    for r in range(4):
        for j in range(4):
            np.testing.assert_array_equal(out[r, j], x[j, r])
