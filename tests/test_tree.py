"""Pytree-native planned collectives (``parallel/tree``).

Four layers:

1. The rules interface: regex partition rules -> PartitionSpec pytree
   (first match wins, scalar leaves unpartitioned per the fmengine
   rule, unmatched leaves fail loudly), and the flagship model's
   ``PARTITION_RULES`` reproducing its hand-written spec table.
2. Plan units: per-(dtype) bucketing through the shared fusion
   planner, signature-keyed caching (``tree_plan_cache_hits``), and
   the tuned bucket-size resolution chain (``tree_buckets`` dynamic
   rules > ``tree_bucket_bytes`` > ``dp_bucket_bytes``).
3. The BITWISE PARITY MATRIX: every planned SPMD pass (allreduce /
   reduce_scatter / allgather) against the per-leaf reference path
   (``bucket_bytes=0``) over mixed-dtype trees with scalar leaves,
   the ZeRO shard/unshard round-trip, and the host-driver
   :class:`TreeSync` families against their per-leaf blocking
   collectives (plus the HostPipeline schedule against its blocking
   leg and the compiled ``pp.pipeline`` reference).
4. A real 3-process ``tpurun`` job: the overlapped whole-tree pass
   under the progress thread hides comm (``nbc_hidden_seconds`` and
   ``tree_hidden_seconds`` both > 0) with bitwise parity, and the
   HostPipeline boundary transfers run nonblocking with identical
   results.
"""

import os
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import ompi_release_tpu as mpi
from ompi_release_tpu.mca import pvar
from ompi_release_tpu.mca import var as mca_var
from ompi_release_tpu.parallel import dp, pp, tree, zero
from ompi_release_tpu.runtime.state import JobState
from ompi_release_tpu.tools.tpurun import Job

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def world():
    return mpi.init()


def mesh1d(n, name):
    return Mesh(np.array(jax.devices()[:n]), (name,))


def smap(fn, mesh, in_specs, out_specs):
    return jax.jit(
        jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False)
    )


def _pv(name):
    p = pvar.PVARS.lookup(name)
    v = p.read() if p is not None else 0.0
    return v if isinstance(v, dict) else float(v)


# ---------------------------------------------------------------------------
# rules -> PartitionSpec plan
# ---------------------------------------------------------------------------

class TestPartitionRules:
    RULES = (
        (r"embed", P("tp", None)),
        (r"layers/w.*", P("pp", None, "tp")),
        (r"layers/ln\d", P("pp", None)),
    )

    def test_regex_rules_match_paths_first_wins(self):
        tree_ = {
            "embed": np.zeros((8, 4)),
            "layers": {"w1": np.zeros((2, 4, 4)),
                       "ln1": np.zeros((2, 4))},
        }
        specs = tree.match_partition_rules(self.RULES, tree_)
        assert specs["embed"] == P("tp", None)
        assert specs["layers"]["w1"] == P("pp", None, "tp")
        # "layers/ln1" also matches neither w-rule; the ln rule wins
        assert specs["layers"]["ln1"] == P("pp", None)

    def test_scalar_leaves_unpartitioned(self):
        """The fmengine rule: 0-d and single-element leaves get P()
        regardless of what the rules say."""
        tree_ = {"embed": np.zeros(()), "layers": {"w1": np.zeros((1,))}}
        specs = tree.match_partition_rules(self.RULES, tree_)
        assert specs["embed"] == P()
        assert specs["layers"]["w1"] == P()

    def test_unmatched_leaf_raises(self):
        with pytest.raises(ValueError, match="orphan"):
            tree.match_partition_rules(self.RULES,
                                       {"orphan": np.zeros((3, 3))})

    def test_named_tree_map_paths(self):
        names = []
        tree.named_tree_map(
            lambda name, x: names.append(name),
            {"a": {"b": [np.zeros(2), np.zeros(3)]}, "c": np.zeros(1)})
        assert sorted(names) == ["a/b/0", "a/b/1", "c"]

    def test_model_partition_rules_match_literal_table(self):
        """The flagship model's regex rules reproduce the hand-written
        spec tree for both dense and MoE configs."""
        from ompi_release_tpu.models import transformer as tfm

        for n_experts in (0, 8):
            cfg = tfm.ModelConfig(n_experts=n_experts)
            specs = tfm.param_specs(cfg)
            layers = {"ln1": P("pp", None), "wq": P("pp", None, "tp"),
                      "wk": P("pp", None, "tp"),
                      "wv": P("pp", None, "tp"),
                      "wo": P("pp", "tp", None), "ln2": P("pp", None)}
            if n_experts:
                layers.update(router=P("pp", None, None),
                              we1=P("pp", "ep", None, None),
                              we2=P("pp", "ep", None, None))
            else:
                layers.update(w1=P("pp", None, "tp"),
                              w2=P("pp", "tp", None))
            assert specs == {"embed": P("tp", None), "ln_f": P(),
                             "layers": layers}


# ---------------------------------------------------------------------------
# plan units: bucketing, caching, tuned resolution
# ---------------------------------------------------------------------------

class TestPlan:
    def test_buckets_group_by_dtype_and_capacity(self):
        plan = tree.plan_from_meta(
            [((100,), "float32"),   # 400 B
             ((100,), "float32"),   # 400 B -> same bucket
             ((10,), "int32"),      # dtype break
             ((1000,), "float32"),  # 4000 B >= capacity -> big
             ((2,), "float32")],
            1024)
        assert plan.big == [3]
        assert plan.buckets == [[0, 1], [2], [4]]
        assert plan.n_transfers() == 4

    def test_zero_capacity_is_per_leaf(self):
        plan = tree.plan_from_meta([((4,), "float32")] * 3, 0)
        assert plan.big == [0, 1, 2] and plan.buckets == []

    def test_plan_cache_hits(self):
        sig = [((41,), "float32"), ((13,), "int32")]
        before = _pv("tree_plan_cache_hits")
        p1 = tree.plan_from_meta(sig, 3331)
        assert tree.plan_from_meta(sig, 3331) is p1
        assert tree.plan_from_meta(sig, 3332) is not p1
        after = _pv("tree_plan_cache_hits")
        assert after["count"] - before["count"] == 3
        assert after["sum"] - before["sum"] == 1  # exactly one hit

    def test_resolution_chain(self, tmp_path, world):
        # (world: the coll_tuned_* gating cvars register at init)
        # cvar layer
        mca_var.set_value("tree_bucket_bytes", 12345)
        try:
            assert tree.resolve_bucket_bytes(8, 1 << 20) == 12345
        finally:
            mca_var.VARS.unset("tree_bucket_bytes")
        # dp fallback
        assert tree.resolve_bucket_bytes(8, 1 << 20) == int(
            mca_var.get("dp_bucket_bytes", 4 * 1024 * 1024))
        # dynamic-rule layer outranks both: fused capacity + per_leaf
        rules = tmp_path / "rules.conf"
        rules.write_text(
            "tree_buckets  0  0        fused  65536\n"
            "tree_buckets  0  1048576  per_leaf\n")
        mca_var.set_value("coll_tuned_use_dynamic_rules", True)
        mca_var.set_value("coll_tuned_dynamic_rules_filename",
                          str(rules))
        try:
            assert tree.resolve_bucket_bytes(8, 1024) == 65536
            assert tree.resolve_bucket_bytes(8, 2 << 20) == 0
        finally:
            mca_var.VARS.unset("coll_tuned_use_dynamic_rules")
            mca_var.VARS.unset("coll_tuned_dynamic_rules_filename")


# ---------------------------------------------------------------------------
# SPMD bitwise parity matrix: planned pass vs per-leaf reference
# ---------------------------------------------------------------------------

def _mixed_tree(n, rng):
    """Mixed dtypes, sizes straddling every bucket boundary, plus a
    single-element leaf (lead axis n for the dp-sharded passes)."""
    return {
        "a": rng.randn(n, 3, 4).astype(np.float32),
        "b": rng.randn(n, 7).astype(np.float32),
        "big": rng.randn(n, 2000).astype(np.float32),
        "i": (rng.randn(n, 5) * 100).astype(np.int32),
        "h": rng.randn(n, 11).astype(np.float16),
        "s": rng.randn(n, 1).astype(np.float32),  # scalar-per-rank
    }


class TestSpmdBitwiseParity:
    @pytest.mark.parametrize("mean", [False, True])
    @pytest.mark.parametrize("bucket", [64, 4096, 1 << 20])
    def test_tree_allreduce_bitwise(self, mean, bucket):
        n = 8
        mesh = mesh1d(n, "dp")
        grads = _mixed_tree(n, np.random.RandomState(0))

        def run(bb):
            return smap(
                lambda g: tree.tree_allreduce(g, "dp", mean=mean,
                                              bucket_bytes=bb),
                mesh, (P("dp"),), P("dp"))(grads)

        planned, perleaf = run(bucket), run(0)
        for k in grads:
            np.testing.assert_array_equal(np.asarray(planned[k]),
                                          np.asarray(perleaf[k]))

    @pytest.mark.parametrize("mean", [False, True])
    def test_tree_reduce_scatter_bitwise(self, mean):
        n = 8
        mesh = mesh1d(n, "dp")
        grads = _mixed_tree(n, np.random.RandomState(1))

        def run(bb):
            return smap(
                lambda g: tree.tree_reduce_scatter(g, "dp", mean=mean,
                                                   bucket_bytes=bb),
                mesh, (P("dp"),), P("dp"))(grads)

        planned, perleaf = run(512), run(0)
        for k in grads:
            np.testing.assert_array_equal(np.asarray(planned[k]),
                                          np.asarray(perleaf[k]))

    def test_tree_allgather_bitwise_roundtrip(self):
        """ZeRO shard/unshard round-trip: shard_like -> planned
        unshard returns the EXACT original leaves (pure data
        movement), identical between planned and per-leaf paths."""
        n = 4
        mesh = mesh1d(n, "dp")
        rng = np.random.RandomState(2)
        params = {"w": rng.randn(6, 3).astype(np.float32),  # pad path
                  "v": rng.randn(16).astype(np.float32),
                  "i": (rng.randn(5) * 9).astype(np.int32)}

        def run(bb):
            def body(p):
                shards = zero.shard_like(p, "dp")
                shapes = jax.tree.map(lambda x: x.shape, p)
                return zero.unshard_params(shards, shapes, "dp",
                                           bucket_bytes=bb)
            return smap(body, mesh, (P(),), P())(params)

        planned, perleaf = run(128), run(0)
        for k in params:
            np.testing.assert_array_equal(np.asarray(planned[k]),
                                          np.asarray(perleaf[k]))
            np.testing.assert_array_equal(np.asarray(planned[k]),
                                          params[k])

    def test_zero_grad_shard_roundtrip_bitwise(self):
        """shard_gradients -> unshard over the planned path equals the
        per-leaf path bitwise (the reduce does real float sums, so the
        two paths must fold identically, not just closely)."""
        n = 8
        mesh = mesh1d(n, "dp")
        rng = np.random.RandomState(3)
        grads = {"w": rng.randn(6, 3).astype(np.float32),
                 "v": rng.randn(15).astype(np.float32)}

        def run(bb):
            def body(g):
                sh = zero.shard_gradients(g, "dp", mean=False,
                                          bucket_bytes=bb)
                shapes = jax.tree.map(lambda x: x.shape, g)
                return zero.unshard_params(sh, shapes, "dp",
                                           bucket_bytes=bb)
            return smap(body, mesh, (P(),), P())(grads)

        planned, perleaf = run(64), run(0)
        for k in grads:
            np.testing.assert_array_equal(np.asarray(planned[k]),
                                          np.asarray(perleaf[k]))

    def test_zero_step_still_matches_dense_sgd(self):
        """The refactored zero_step (planned passes underneath) keeps
        the numerical contract of the original per-leaf version."""
        n = 4
        mesh = mesh1d(n, "dp")
        rng = np.random.RandomState(7)
        params = {"w": rng.randn(6, 3).astype(np.float32)}
        grads = rng.randn(n, 6, 3).astype(np.float32)
        lr = 0.1

        def opt_update(gs, state, ps):
            return jax.tree.map(lambda g: -lr * g, gs), state

        def body(p, g):
            new_p, _ = zero.zero_step(p, {"w": g}, None, opt_update,
                                      "dp", bucket_bytes=128)
            return new_p

        out = smap(body, mesh, (P(), P("dp")), P())(params, grads)
        ref = params["w"] - lr * grads.mean(0)
        np.testing.assert_allclose(np.asarray(out["w"]), ref,
                                   rtol=1e-5, atol=1e-6)

    def test_dp_allreduce_gradients_rides_tree_pass(self):
        """dp.allreduce_gradients is now a thin wrapper: same result,
        and the plan-cache aggregate proves the planned path traced
        (SPMD bodies run at trace time, so the plan events — not
        tree_passes, a driver-only counter — are the witness)."""
        n = 8
        mesh = mesh1d(n, "dp")
        rng = np.random.RandomState(5)
        grads = {"a": rng.randn(n, 9).astype(np.float32)}
        before = _pv("tree_plan_cache_hits")["count"]
        out = smap(
            lambda g: dp.allreduce_gradients(g, "dp", mean=True,
                                             bucket_bytes=64),
            mesh, (P("dp"),), P("dp"))(grads)
        ref = np.broadcast_to(grads["a"].mean(0, keepdims=True),
                              grads["a"].shape)
        np.testing.assert_allclose(np.asarray(out["a"]), ref, rtol=1e-5)
        assert _pv("tree_plan_cache_hits")["count"] > before


# ---------------------------------------------------------------------------
# host-driver TreeSync: overlapped families vs blocking per-leaf
# ---------------------------------------------------------------------------

class TestTreeSyncDriver:
    def test_allreduce_bitwise_vs_blocking(self, world):
        n = world.size
        rng = np.random.RandomState(0)
        grads = {"a": rng.randn(n, 40).astype(np.float32),
                 "b": rng.randn(n, 7).astype(np.float32),
                 "i": (rng.randn(n, 5) * 10).astype(np.int32),
                 "big": rng.randn(n, 3000).astype(np.float32)}
        sync = tree.TreeSync(world, mean=False, bucket_bytes=256)
        out = sync.issue(grads).wait()
        for k in grads:
            np.testing.assert_array_equal(
                np.asarray(out[k]), np.asarray(world.allreduce(grads[k])))

    def test_allreduce_mean(self, world):
        n = world.size
        x = {"a": np.ones((n, 4), np.float32) * 3}
        out = tree.TreeSync(world, mean=True).issue(x).wait()
        np.testing.assert_allclose(np.asarray(out["a"]),
                                   np.ones((n, 4)) * 3)

    def test_reduce_scatter_bitwise_vs_blocking(self, world):
        n = world.size
        rng = np.random.RandomState(1)
        grads = {"a": rng.randn(n, 40).astype(np.float32),
                 "b": rng.randn(n, 7).astype(np.float32)}
        sync = tree.TreeSync(world, mean=False, bucket_bytes=512)
        out = sync.issue_reduce_scatter(grads).wait()
        for k, g in grads.items():
            pad = (-g.shape[1]) % n
            gp = np.concatenate(
                [g, np.zeros((n, pad), g.dtype)], axis=1) if pad else g
            ref = np.asarray(world.reduce_scatter_block(gp))
            np.testing.assert_array_equal(np.asarray(out[k]), ref)

    def test_allgather_roundtrip_bitwise(self, world):
        n = world.size
        rng = np.random.RandomState(2)
        grads = {"a": rng.randn(n, 40).astype(np.float32),
                 "b": rng.randn(n, 7).astype(np.float32)}
        sync = tree.TreeSync(world, mean=False, bucket_bytes=512)
        shards = sync.issue_reduce_scatter(grads).wait()
        shapes = {k: (v.shape[1],) for k, v in grads.items()}
        full = sync.issue_allgather(shards, shapes).wait()
        for k in grads:
            c = np.asarray(shards[k]).shape[1]
            ref = np.asarray(world.allgather(np.asarray(shards[k])))
            np.testing.assert_array_equal(
                np.asarray(full[k]), ref[:, :shapes[k][0]])

    def test_scalar_leaf_rejected(self, world):
        with pytest.raises(ValueError, match="leading"):
            tree.TreeSync(world).issue({"s": np.float32(1.0)})

    def test_mismatched_lead_rejected(self, world):
        n = world.size
        with pytest.raises(ValueError, match="leading"):
            tree.TreeSync(world).issue(
                {"a": np.ones((n, 2), np.float32),
                 "b": np.ones((n + 1, 2), np.float32)})

    def test_gradient_sync_is_tree_sync(self, world):
        """dp.GradientSync kept its API as the allreduce
        specialization (mean defaults on)."""
        assert issubclass(dp.GradientSync, tree.TreeSync)
        n = world.size
        g = {"a": np.ones((n, 6), np.float32)}
        out = dp.GradientSync(world, bucket_bytes=64).issue(g).wait()
        np.testing.assert_allclose(np.asarray(out["a"]),
                                   np.ones((n, 6), np.float32))


# ---------------------------------------------------------------------------
# HostPipeline (driver-mode single controller runs stages in sequence)
# ---------------------------------------------------------------------------

class TestHostPipeline:
    def _run_all_stages(self, comm, weights, mbs, nonblocking):
        outs = None
        for s in range(comm.size):
            w = weights[s]
            r = pp.HostPipeline(
                comm, lambda x, w=w: np.tanh(np.asarray(x) @ w),
                stage=s, nonblocking=nonblocking).run(mbs)
            if s == comm.size - 1:
                outs = r
        return outs

    def test_matches_sequential_and_blocking_leg(self, world):
        n = world.size
        rng = np.random.RandomState(8)
        weights = [rng.randn(6, 6).astype(np.float32) * 0.3
                   for _ in range(n)]
        mbs = [rng.randn(2, 6).astype(np.float32) for _ in range(5)]
        nb = self._run_all_stages(world, weights, mbs, True)
        bl = self._run_all_stages(world, weights, mbs, False)
        ref = mbs
        for s in range(n):
            ref = [np.tanh(x @ weights[s]) for x in ref]
        for a, b, r in zip(nb, bl, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            np.testing.assert_allclose(np.asarray(a), r, rtol=1e-6)

    def test_matches_compiled_pipeline(self):
        """The host schedule computes the same function as the
        compiled shard_map ppermute pipeline."""
        n, m = 4, 6
        mesh = mesh1d(n, "pp")
        rng = np.random.RandomState(9)
        ws = rng.randn(n, 6, 6).astype(np.float32) * 0.3
        x = rng.randn(m, 2, 6).astype(np.float32)

        def stage_fn(w, xb):
            return jnp.tanh(xb @ w)

        out = smap(
            lambda w, xb: pp.pipeline(stage_fn, w[0], xb,
                                      axis_name="pp")[None],
            mesh, (P("pp"), P()), P("pp"))(ws, x)
        compiled = np.asarray(out)[n - 1]

        # chain n single-stage host schedules with the same weights
        # (stage s's outputs feed stage s+1's microbatch stream)
        outs = list(x)
        for s in range(n):
            outs = pp.HostPipeline(
                _SoloComm(), lambda xb, w=ws[s]: np.asarray(
                    jnp.tanh(jnp.asarray(xb) @ w)),
                stage=0, nonblocking=True).run(outs)
        host = np.stack(outs)
        np.testing.assert_allclose(host, compiled, rtol=1e-5,
                                   atol=1e-6)

    def test_boundary_pvars_count(self, world):
        before = _pv("pp_boundary_msgs")
        weights = [np.eye(3, dtype=np.float32)] * world.size
        mbs = [np.ones((2, 3), np.float32)] * 4
        self._run_all_stages(world, weights, mbs, True)
        # every non-final stage sends one activation per microbatch
        assert _pv("pp_boundary_msgs") - before == (world.size - 1) * 4


class _SoloComm:
    """1-stage comm stub: HostPipeline degenerates to a map() — lets
    the compiled-pipeline parity test apply stages functionally."""
    size = 1
    local_comm_ranks = [0]


# ---------------------------------------------------------------------------
# real 3-process job: overlap witnessed by the hidden-seconds pvars
# ---------------------------------------------------------------------------

_JOB_APP = textwrap.dedent("""
    import os, sys, time
    sys.path.insert(0, %r)
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=1"
    ).strip()  # 1 device/process: member ranks == stages for the pp leg
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["OMPITPU_HOST_ID"] = (
        "treejob-" + os.environ["OMPITPU_NODE_ID"])
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import ompi_release_tpu as mpi
    from ompi_release_tpu.mca import pvar, var as mca_var
    from ompi_release_tpu.parallel import pp as pp_mod, tree as tree_mod
    from ompi_release_tpu.runtime.runtime import Runtime

    def _pv(name):
        p = pvar.PVARS.lookup(name)
        return float(p.read()) if p is not None else 0.0

    world = mpi.init()
    rt = Runtime.current()
    me = rt.bootstrap["process_index"]
    ln = len(world.local_comm_ranks)
    off = rt.local_rank_offset
    n = world.size

    grads = {"w%%d" %% k: np.stack(
                 [np.arange(12000, dtype=np.float32) * (off + i + k + 1)
                  for i in range(ln)])
             for k in range(6)}
    sync = tree_mod.TreeSync(world, mean=False, bucket_bytes=1 << 20)
    sync.issue(grads).wait()  # warm programs + plan + channels

    mca_var.set_value("progress_thread", True)
    world.barrier()
    h0 = _pv("nbc_hidden_seconds")
    t0 = _pv("tree_hidden_seconds")
    pending = sync.issue(grads)
    t_end = time.perf_counter() + 0.5
    a = np.ones((64, 64), np.float32)
    while time.perf_counter() < t_end:
        a = a @ a * 1e-4  # caller compute while the engine moves bytes
    out = pending.wait()
    hidden_nbc = _pv("nbc_hidden_seconds") - h0
    hidden_tree = _pv("tree_hidden_seconds") - t0
    mca_var.VARS.unset("progress_thread")

    # overlap witness: the engine itself accounted comm time as hidden
    assert hidden_nbc > 0, hidden_nbc
    assert hidden_tree > 0, hidden_tree
    # bitwise parity with the per-leaf blocking path
    for k in sorted(grads):
        ref = np.asarray(world.allreduce(grads[k]))
        np.testing.assert_array_equal(np.asarray(out[k]), ref)

    # HostPipeline across REAL process boundaries: nonblocking
    # boundary transfers, identical results to the blocking leg
    W = np.eye(32, dtype=np.float32) * 0.5
    mbs = [np.ones((8, 32), np.float32) * (k + 1) for k in range(5)]
    outs = {}
    for leg, nb in (("nb", True), ("bl", False)):
        pipe = pp_mod.HostPipeline(world, lambda x: np.asarray(x) @ W,
                                   stage=me, nonblocking=nb)
        world.barrier()
        outs[leg] = pipe.run(mbs)
        world.barrier()
    if me == n - 1:
        assert len(outs["nb"]) == 5
        for a_, b_ in zip(outs["nb"], outs["bl"]):
            np.testing.assert_array_equal(np.asarray(a_),
                                          np.asarray(b_))
        for k, a_ in enumerate(outs["nb"]):
            ref = np.ones((8, 32), np.float32) * (k + 1)
            for _ in range(n):
                ref = ref @ W
            np.testing.assert_array_equal(np.asarray(a_), ref)
    print("TREE-JOB-OK %%d hidden=%%.4f" %% (me, hidden_nbc))
    world.barrier()
    mpi.finalize()
""" % REPO)


class TestTreeJob:
    def test_overlapped_tree_pass_job(self, tmp_path, capfd):
        """3 processes: the planned whole-tree pass overlaps comm
        under the progress thread (both hidden-seconds pvars > 0),
        bitwise parity holds, and HostPipeline boundary transfers run
        nonblocking across real process boundaries."""
        app = tmp_path / "tree_job.py"
        app.write_text(_JOB_APP)
        job = Job(3, [sys.executable, str(app)], [],
                  heartbeat_s=0.5, miss_limit=8)
        rc = job.run(timeout_s=240)
        out = capfd.readouterr()
        assert rc == 0, out.out + out.err
        assert job.job_state.visited(JobState.TERMINATED)
        for pidx in range(3):
            assert f"TREE-JOB-OK {pidx}" in out.out
