"""Reduction op tests — analogue of the op_base_functions.c kernel table."""

import numpy as np
import pytest

import jax.numpy as jnp

from ompi_release_tpu import ops


@pytest.mark.parametrize("name,expect", [
    ("sum", 10), ("prod", 24), ("max", 4), ("min", 1),
])
def test_arith_ops(name, expect):
    op = ops.PREDEFINED_OPS[name]
    vals = [jnp.array(v, jnp.float32) for v in [1, 2, 3, 4]]
    acc = vals[0]
    for v in vals[1:]:
        acc = op(acc, v)
    assert float(acc) == expect


def test_logical_ops():
    t, f = jnp.array(True), jnp.array(False)
    assert bool(ops.LAND(t, f)) is False
    assert bool(ops.LOR(t, f)) is True
    assert bool(ops.LXOR(t, t)) is False


def test_bitwise_ops():
    a, b = jnp.array(0b1100, jnp.int32), jnp.array(0b1010, jnp.int32)
    assert int(ops.BAND(a, b)) == 0b1000
    assert int(ops.BOR(a, b)) == 0b1110
    assert int(ops.BXOR(a, b)) == 0b0110


def test_identities():
    assert ops.SUM.identity_for(np.float32) == 0
    assert ops.PROD.identity_for(np.int32) == 1
    assert ops.MIN.identity_for(np.int32) == np.iinfo(np.int32).max
    assert float(ops.MAX.identity_for(np.float32)) == -np.inf
    assert int(ops.BAND.identity_for(np.uint8)) == 0xFF


def test_maxloc_minloc_tie_lower_index():
    v = jnp.array([3.0, 5.0]), jnp.array([0, 1])
    w = jnp.array([3.0, 5.0]), jnp.array([2, 0])
    mv, mi = ops.MAXLOC(v, w)
    np.testing.assert_array_equal(np.asarray(mv), [3.0, 5.0])
    np.testing.assert_array_equal(np.asarray(mi), [0, 0])  # ties -> lower idx
    nv, ni = ops.MINLOC(v, w)
    np.testing.assert_array_equal(np.asarray(ni), [0, 0])


def test_replace_noop():
    a, b = jnp.array(1.0), jnp.array(2.0)
    assert float(ops.REPLACE(a, b)) == 2.0
    assert float(ops.NO_OP(a, b)) == 1.0


def test_user_op():
    op = ops.user_op("avg2", lambda a, b: (a + b) / 2, commute=True)
    assert float(op(jnp.array(2.0), jnp.array(4.0))) == 3.0
    assert op.commutative


def test_op_framework_selection():
    # two components registered: pallas (accelerated, 20) > xla (10)
    names = {c.NAME for c in ops.OP_FRAMEWORK.components()}
    assert names == {"xla", "pallas"}
    # highest-priority component claims nothing without shape context;
    # resolution falls through to the xla base table
    assert ops.resolve(ops.SUM) is ops.SUM


class TestPallasOpComponent:
    """The accelerated op component (ompi/mca/op override role):
    claims large contiguous f32/bf16 SUMs, declines everything else."""

    def test_claims_large_f32_sum(self):
        import numpy as np

        got = ops.resolve(ops.SUM, np.float32, 64 * 1024 * 1024)
        assert got.name == "sum[pallas]"
        assert got.commutative and got.identity is not None
        # the accelerated combiner computes the same thing
        a = jnp.arange(600, dtype=jnp.float32)
        b = jnp.ones(600, jnp.float32)
        np.testing.assert_allclose(np.asarray(got(a, b)),
                                   np.asarray(a + b))

    def test_declines_small_wrong_dtype_wrong_op(self):
        import numpy as np

        assert ops.resolve(ops.SUM, np.float32, 1024) is ops.SUM
        assert ops.resolve(ops.SUM, np.int32,
                           64 * 1024 * 1024) is ops.SUM
        assert ops.resolve(ops.MAX, np.float32,
                           64 * 1024 * 1024) is ops.MAX

    def test_threshold_is_tunable(self):
        import numpy as np

        from ompi_release_tpu.mca import var as mca_var

        old = mca_var.get("op_pallas_threshold", 4 * 1024 * 1024)
        try:
            mca_var.VARS.apply_cli([("op_pallas_threshold", "64")])
            got = ops.resolve(ops.SUM, np.float32, 128)
            assert got.name == "sum[pallas]"
        finally:
            mca_var.VARS.apply_cli([("op_pallas_threshold", str(old))])

    def test_exclude_list_disables_component(self):
        import numpy as np

        from ompi_release_tpu.mca import var as mca_var

        try:
            mca_var.VARS.apply_cli([("op", "^pallas")])
            assert ops.resolve(ops.SUM, np.float32,
                               64 * 1024 * 1024) is ops.SUM
        finally:
            mca_var.VARS.apply_cli([("op", "")])

    def test_tuned_allreduce_selects_pallas_kernel(self):
        """A tuned ring allreduce over the claim threshold compiles
        against the pallas combiner (distinct cache key) and stays
        bitwise... no — numerically identical: same adds, same order,
        different kernel."""
        import numpy as np

        import ompi_release_tpu as mpi
        from ompi_release_tpu.mca import var as mca_var

        world = mpi.init()
        x = np.random.RandomState(7).randn(world.size, 4096) \
            .astype(np.float32)
        try:
            mca_var.VARS.apply_cli([
                ("op_pallas_threshold", "1024"),
                ("coll_tuned_allreduce_algorithm", "ring"),
                ("coll", "tuned,basic,self"),  # xla out of the chain
            ])
            comm = world.dup(name="pallas-op-test")
            got = np.asarray(comm.allreduce(x))
            keys = [k for k in comm._coll_programs
                    if "sum[pallas]" in str(k)]
            assert keys, list(comm._coll_programs)
            comm.free()
        finally:
            mca_var.VARS.apply_cli([
                ("op_pallas_threshold", str(4 * 1024 * 1024)),
                ("coll_tuned_allreduce_algorithm", "auto"),
                ("coll", ""),
            ])
        np.testing.assert_allclose(
            got, np.broadcast_to(x.sum(0), got.shape), atol=1e-3)

    def test_tpu_info_lists_both_op_components(self):
        from ompi_release_tpu.tools import tpu_info

        info = tpu_info.gather(include_vars=False)
        opfw = next(f for f in info["frameworks"] if f["name"] == "op")
        names = {c["name"] for c in opfw["components"]}
        assert names == {"xla", "pallas"}


def test_non_commutative_flag():
    assert not ops.REPLACE.commutative
    assert ops.SUM.commutative


class TestPallasOpKernels:
    """Streaming Pallas reduction kernels (interpret mode on CPU)."""

    def test_axpy_matches_reference(self):
        from ompi_release_tpu.ops import pallas_op

        rng = np.random.RandomState(0)
        # non-multiple of the block size: exercises padding
        a = rng.randn(3000).astype(np.float32)
        acc = rng.randn(3000).astype(np.float32)
        out = pallas_op.axpy(jnp.asarray(a), jnp.asarray(acc), 0.5)
        np.testing.assert_allclose(
            np.asarray(out), acc * 0.5 + a, rtol=1e-6
        )

    def test_scale_matches_reference(self):
        from ompi_release_tpu.ops import pallas_op

        rng = np.random.RandomState(1)
        x = rng.randn(17, 33).astype(np.float32)
        out = pallas_op.scale(jnp.asarray(x), 2.0)
        np.testing.assert_allclose(np.asarray(out), x * 2.0, rtol=1e-6)


def test_reduce_local():
    """MPI_Reduce_local: inout = in OP inout, no communication; pair
    ops take (value, index) tuples; big f32 SUMs resolve through the
    accelerated op component like the collectives' local steps."""
    from ompi_release_tpu import ops as ops_mod
    from ompi_release_tpu.ops.op import reduce_local

    rng = np.random.RandomState(7)
    a = rng.randn(1000).astype(np.float32)
    b = rng.randn(1000).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(reduce_local(a, b, ops_mod.SUM)), a + b, rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(reduce_local(a, b, ops_mod.MAX)), np.maximum(a, b))
    # pair op: elementwise argmin across the two operands
    ia = np.zeros(1000, np.int32)
    ib = np.ones(1000, np.int32)
    mv, mi = reduce_local((a, ia), (b, ib), ops_mod.MINLOC)
    np.testing.assert_allclose(np.asarray(mv), np.minimum(a, b),
                               rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(mi), np.where(a <= b, 0, 1))
