"""The ``xla`` component's allgather, alltoall and reduce_scatter_block
compiled ahead of time for a v5e 2x2 host at ``osu_ici4``'s sizes (16
and 256 MiB a rank), with no chip: libtpu compiles for a described
topology. The optimized HLO holds no relayout of the rank's block
(PR 39; before it, ``reduce.3`` and ``reshape_squeeze.3`` copied the
reduce_scatter_block's input twice), and reduce_scatter_block is a
reduce-scatter, not a whole all-reduce and a slice.

The topology is described inside a fixture, never at import: only one
process may load libtpu, and this file's worker keeps it (the
on-chip-measurement guide, section 2)."""

import re
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ompi_release_tpu import ops
from ompi_release_tpu.coll import components

SIZES = [16 << 20, 256 << 20]  # bytes a rank, as osu_ici4.large has them


@pytest.fixture(scope="module")
def mesh():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield Mesh(np.asarray(topo.devices), ("rank",))
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _body(monkeypatch, op, n):
    """The component's own body, as ``run_sharded`` would receive it."""
    seen = {}
    monkeypatch.setattr(components, "run_sharded",
                        lambda comm, key, body, x, **kw: seen.update(b=body))
    comm = SimpleNamespace(size=n)
    xla = components._XlaModule(comm)
    if op == "reduce_scatter_block":
        xla.reduce_scatter_block(comm, None, ops.SUM)
    else:
        getattr(xla, op)(comm, None)
    return seen["b"]


def _entry(mesh, body, elems, dtype):
    """(opcode, shape, is_root, line) of each instruction of the entry
    computation, with ``run_sharded``'s wrapper around ``body``."""
    def wrapper(xb):
        return body(xb[0])[None]

    prog = jax.jit(jax.shard_map(wrapper, mesh=mesh, in_specs=P("rank"),
                                 out_specs=P("rank")))
    x = jax.ShapeDtypeStruct((mesh.size, elems), dtype,
                             sharding=NamedSharding(mesh, P("rank")))
    hlo = prog.lower(x).compile().as_text()
    entry = hlo[hlo.index("\nENTRY"):]
    found = []
    for line in entry.splitlines():
        m = re.match(r"\s*(ROOT )?%\S+ = \w+\[([\d,]*)\]\S* ([\w-]+)\(",
                     line)
        if m:
            shape = tuple(int(d) for d in m.group(2).split(",") if d)
            found.append((m.group(3), shape, bool(m.group(1)), line))
    return found


@pytest.mark.parametrize("size", SIZES)
@pytest.mark.parametrize("op,collective,dtype", [
    ("allgather", "all-gather", jnp.float32),
    ("alltoall", "all-to-all", jnp.int32),
])
def test_gather_and_exchange_touch_the_block_once(mesh, monkeypatch, op,
                                                  collective, dtype, size):
    elems = size // 4
    found = _entry(mesh, _body(monkeypatch, op, mesh.size), elems, dtype)
    kinds = [k for k, _, _, _ in found]
    assert kinds.count(collective) == 1, found
    assert set(kinds) <= {"parameter", "bitcast", collective, "copy"}, found
    # the one copy XLA:TPU keeps is its own: a collective's output is
    # not handed out as the program's result (copy insertion's special
    # case, in the parent's lowering too), not a relayout of the block
    copies = [f for f in found if f[0] == "copy"]
    assert len(copies) <= 1 and all(root for _, _, root, _ in copies)


@pytest.mark.parametrize("size", SIZES)
def test_reduce_scatter_block_is_a_reduce_scatter(mesh, monkeypatch, size):
    n, elems = mesh.size, size // 4
    found = _entry(mesh, _body(monkeypatch, "reduce_scatter_block", n),
                   elems, jnp.float32)
    kinds = {k for k, _, _, _ in found}
    assert not kinds & {"all-reduce", "reduce", "reshape", "transpose",
                        "copy"}, found
    assert any("calls=%all-reduce-scatter" in line
               for _, _, _, line in found), found
    # nothing after the collective is larger than the rank's result
    # (plus the collective's own ring offset)
    for kind, shape, _, _ in found:
        if kind not in ("parameter", "bitcast", "fusion"):
            assert np.prod(shape) <= 2 * elems // n, (kind, shape)


def test_device_alltoallv_moves_rows_at_nas_is_class_c(mesh):
    """The device form's TPU body at NAS IS class C (2^25 int32 keys a
    rank, a receive buffer of 1.5 times that): one ragged-all-to-all of
    rows of ``GRANULE`` elements. A flat buffer would have been laid out
    one 128-lane tile per element (24 GiB for the receive buffer, which
    the compiler refuses); in rows, nothing the program holds besides
    its arguments is larger than twice the receive buffer."""
    import functools

    from ompi_release_tpu.coll import vcoll

    n, m = mesh.size, 1 << 25
    cap = 3 * m // 2

    def wrapper(xb, *eb):
        out = vcoll._ragged_body(xb[0], *[e[0] for e in eb], cap=cap)
        return jax.tree.map(lambda a: a[None], out)

    prog = jax.jit(jax.shard_map(wrapper, mesh=mesh, in_specs=P("rank"),
                                 out_specs=P("rank")))
    sh = NamedSharding(mesh, P("rank"))
    args = [jax.ShapeDtypeStruct((n, m), jnp.int32, sharding=sh),
            jax.ShapeDtypeStruct((n, n), jnp.int32, sharding=sh),
            jax.ShapeDtypeStruct((n,), jnp.int32, sharding=sh)]
    compiled = prog.lower(*args).compile()
    hlo = compiled.as_text()
    ragged = [ln for ln in hlo.splitlines()
              if " ragged-all-to-all(" in ln]
    assert len(ragged) == 1
    assert f"s32[{vcoll.stage_rows(n, cap)},1,{vcoll.GRANULE}]" in ragged[0]
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * 4 * cap
