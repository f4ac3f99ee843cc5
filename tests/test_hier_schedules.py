"""Scalable spanning collectives: the scheduled inter-process
algorithms of ``coll/hier_schedules.py`` and their integration in
``coll/hier.py``.

Three layers:

1. The LOCKSTEP SIMULATOR (``ompi_release_tpu.testing.lockstep`` —
   first-class since the fleet-sim PR; the fleet-scale harness in
   ``testing/fleet_sim.py`` shares its adapter contract) drives the
   pure schedules with P threads and per-(src, dst) FIFO queues — the
   exact transport contract the real ``_XchgAdapter`` provides — so
   the bitwise-parity matrix runs the whole (P, op, dtype, algorithm)
   cross product in milliseconds, device- and process-free.
2. Selection-unit tests for ``pick`` (forcing > rules > fixed
   constants, the non-commutative downgrades) and the pair-op payload
   packing.
3. Real 3-process ``tpurun`` Job tests per schedule family, a
   leader-tier job over a faked two-host topology, and a
   hang-injection job proving the watchdog postmortem names the
   stalled round, its algorithm, and the awaited ring neighbor.

Parity discipline: every schedule's combine order is fixed and
process-index-derived, so results are bitwise-identical to the linear
path for every order-invariant case (integer dtypes; MIN/MAX/BAND on
any dtype; ``recursive_doubling`` and ``linear`` ALWAYS, including
non-commutative ops — they fold once, in index order). ``ring`` /
``rabenseifner`` re-associate float sums by construction (rotated /
halving chunk folds), so float32 SUM under them is compared to tight
tolerance; everything else in the matrix is bitwise.
"""

import json
import math
import os
import sys
import textwrap

import numpy as np
import pytest

from ompi_release_tpu import ops
import ompi_release_tpu.coll.components  # noqa: F401  (registers the
# coll_tuned_* cvars and the plain rule namespaces the shipped rules
# file also uses)
from ompi_release_tpu.coll import hier_schedules as hs
from ompi_release_tpu.mca import var as mca_var
from ompi_release_tpu.runtime.state import JobState
from ompi_release_tpu.testing.lockstep import simulate
from ompi_release_tpu.tools.tpurun import Job
from ompi_release_tpu.utils.errors import MPIError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _linear_fold(parts, op):
    acc = parts[0]
    for nxt in parts[1:]:
        acc = np.asarray(op(acc, nxt))
    return acc


PROC_SETS = ([3, 9], [0, 1, 5], [2, 4, 6, 8], [1, 2, 3, 5, 7],
             list(range(8)))


class TestAllreduceParityMatrix:
    """Every allreduce schedule vs the linear process-index fold."""

    OPS = [(ops.SUM, "sum"), (ops.PROD, "prod"), (ops.MAX, "max"),
           (ops.MIN, "min"), (ops.BAND, "band")]

    @pytest.mark.parametrize("procs", PROC_SETS,
                             ids=lambda p: f"P{len(p)}")
    @pytest.mark.parametrize("dtype", [np.int32, np.float32])
    def test_matrix(self, procs, dtype):
        rng = np.random.RandomState(len(procs))
        P = len(procs)
        for op, opname in self.OPS:
            if opname == "band" and dtype is np.float32:
                continue
            if opname == "prod":
                data = {p: rng.randint(1, 4, 13).astype(dtype)
                        for p in procs}
            else:
                data = {p: (rng.randint(1, 100, 13)).astype(dtype)
                        for p in procs}
            want = _linear_fold([data[p] for p in procs], op)
            npop = lambda a, b: np.asarray(op(a, b))  # noqa: E731
            ident = op.identity_for(dtype)

            for alg in ("ring", "rabenseifner"):
                fn = (hs.allreduce_ring if alg == "ring"
                      else hs.allreduce_rabenseifner)
                out = simulate(procs, lambda x, p: fn(
                    x, procs, p, data[p], npop, ident))
                for p in procs:
                    got = np.asarray(out[p]).astype(dtype)
                    if dtype is np.float32 and opname in ("sum", "prod"):
                        np.testing.assert_allclose(got, want, rtol=1e-6)
                    else:  # order-invariant: bitwise
                        np.testing.assert_array_equal(
                            got, want, err_msg=f"{alg}/{opname}/P={P}")

            # recursive_doubling folds ONCE in index order: bitwise vs
            # linear for every op — including this non-commutative one
            out = simulate(procs, lambda x, p: _linear_fold(
                hs.allgather_bruck(x, procs, p, data[p], [13] * P), op))
            for p in procs:
                np.testing.assert_array_equal(
                    np.asarray(out[p]).astype(dtype), want,
                    err_msg=f"recursive_doubling/{opname}/P={P}")

    @pytest.mark.parametrize("procs", PROC_SETS,
                             ids=lambda p: f"P{len(p)}")
    def test_noncommutative_exact_via_recursive_doubling(self, procs):
        """a - b is non-commutative AND non-associative; the
        doubling-allgather + ordered local fold must still match the
        linear fold bitwise (the exact-order fallback path)."""
        sub = ops.user_op("sub_t", lambda a, b: a - b, commute=False)
        rng = np.random.RandomState(7)
        data = {p: rng.randint(0, 50, 9).astype(np.int64)
                for p in procs}
        want = _linear_fold([data[p] for p in procs], sub)
        P = len(procs)
        out = simulate(procs, lambda x, p: _linear_fold(
            hs.allgather_bruck(x, procs, p, data[p], [9] * P), sub))
        for p in procs:
            np.testing.assert_array_equal(np.asarray(out[p]), want)


def _rabenseifner_whole(x, procs, me, mine, op, identity):
    """``allreduce_rabenseifner`` as one loop after the other, as it
    was written before its halving half became
    ``reduce_scatter_halving``: the reference the split is held to."""
    P = len(procs)
    mi = procs.index(me)
    flat, L, per = hs._pad_chunks(mine, P, identity)
    lo, hi = 0, P
    d = P // 2
    while d >= 1:
        partner = procs[mi ^ d]
        half = (hi - lo) // 2
        if mi & d:
            keep, send = (lo + half, hi), (lo, lo + half)
        else:
            keep, send = (lo, lo + half), (lo + half, hi)
        payload = flat[send[0] * per:send[1] * per]
        got = hs._flat(hs._round(x, {partner: [payload]},
                                 {partner: 1})[partner][0])
        seg = flat[keep[0] * per:keep[1] * per]
        left, right = (got, seg) if mi & d else (seg, got)
        hs.fold(op, left, right, into=seg)
        lo, hi = keep
        d //= 2
    d = 1
    blk = mi
    while d < P:
        partner = procs[mi ^ d]
        plo = blk ^ d
        payload = flat[blk * per:(blk + d) * per]
        got = hs._flat(hs._round(x, {partner: [payload]},
                                 {partner: 1})[partner][0])
        flat[plo * per:(plo + d) * per] = got
        blk = min(blk, plo)
        d *= 2
    return flat[:L]


def _recorded(procs, fn):
    """``simulate`` with every process's rounds recorded: {pidx:
    (result, rounds as ``RoundRecorder`` keeps them, bytes sent)}."""
    from ompi_release_tpu.coll.plan import RoundRecorder

    def run(x, p):
        rec = RoundRecorder(x)
        out = fn(rec, p)
        sent = sum(math.prod(shape) * np.dtype(dt).itemsize
                   for sends, _ in rec.rounds for _, msgs in sends
                   for shape, dt in msgs)
        return out, tuple(rec.rounds), sent

    return simulate(procs, run)


class TestRabenseifnerSplit:
    """``allreduce_rabenseifner`` = ``reduce_scatter_halving`` + the
    doubling loop: the same rounds, bytes and bits as the one-piece
    schedule; the halving half alone is its first log2 P rounds, and
    its chunk ``mi`` is the allreduce's, bit for bit."""

    @pytest.mark.parametrize("P", [2, 4, 8])
    @pytest.mark.parametrize("length", [4096, 4099])
    @pytest.mark.parametrize("opname", ["sum", "skew"])
    def test_split_is_the_whole_schedule(self, P, length, opname):
        """``skew`` weighs its left operand: any change of operand
        order or association shows in its bits."""
        procs = list(range(10, 10 + P))
        rng = np.random.default_rng(P * length)
        data = {p: rng.standard_normal(length).astype(np.float32)
                for p in procs}
        if opname == "sum":
            npop = lambda a, b: np.asarray(ops.SUM(a, b))  # noqa: E731
        else:
            npop = lambda a, b: (np.float32(0.75) * a  # noqa: E731
                                 + b).astype(np.float32)
        ident = np.float32(0)
        now = _recorded(procs, lambda x, p: hs.allreduce_rabenseifner(
            x, procs, p, data[p].copy(), npop, ident))
        was = _recorded(procs, lambda x, p: _rabenseifner_whole(
            x, procs, p, data[p].copy(), npop, ident))
        half = _recorded(procs, lambda x, p: hs.reduce_scatter_halving(
            x, procs, p, data[p].copy(), npop, ident))
        steps = int(math.log2(P))
        per = -(-length // P)
        for p in procs:
            out, rounds, sent = now[p]
            assert rounds == was[p][1] and sent == was[p][2]
            assert out.tobytes() == was[p][0].tobytes()  # BITWISE
            assert len(rounds) == 2 * steps
            flat, L, h_per, mi = half[p][0]
            assert (L, h_per, mi) == (length, per, procs.index(p))
            assert half[p][1] == rounds[:steps]
            assert half[p][2] == (P - 1) * per * 4
            chunk = flat[mi * per:(mi + 1) * per]
            if per * P == L:  # a padded tail folds the identity
                assert chunk.tobytes() == \
                    out[mi * per:(mi + 1) * per].tobytes()
            else:
                assert chunk[:L - mi * per].tobytes() == \
                    out[mi * per:(mi + 1) * per].tobytes()


class TestMovementSchedules:
    @pytest.mark.parametrize("procs", PROC_SETS,
                             ids=lambda p: f"P{len(p)}")
    def test_bcast_binomial_every_root(self, procs):
        rng = np.random.RandomState(1)
        val = rng.randint(0, 99, (4, 3)).astype(np.int32)
        for root in procs:
            out = simulate(procs, lambda x, p: hs.bcast_binomial(
                x, procs, p, root, val if p == root else None))
            for p in procs:
                np.testing.assert_array_equal(np.asarray(out[p]), val)

    @pytest.mark.parametrize("procs", PROC_SETS,
                             ids=lambda p: f"P{len(p)}")
    def test_gather_scatter_binomial(self, procs):
        P = len(procs)
        rng = np.random.RandomState(2)
        counts = [(i % 3) + 1 for i in range(P)]
        data = {p: rng.randint(0, 99, counts[i] * 4).astype(np.int32)
                for i, p in enumerate(procs)}
        for root in (procs[0], procs[-1], procs[P // 2]):
            out = simulate(procs, lambda x, p: hs.gather_binomial(
                x, procs, p, root, data[p],
                [c * 4 for c in counts]))
            for i, p in enumerate(procs):
                if p == root:
                    for j, q in enumerate(procs):
                        np.testing.assert_array_equal(out[p][j], data[q])
                else:
                    assert out[p] is None
            sc = simulate(procs, lambda x, p: hs.scatter_binomial(
                x, procs, p, root,
                [data[q] for q in procs] if p == root else None,
                counts, np.asarray([4], np.int64) if p == root else None))
            for i, p in enumerate(procs):
                flat, meta = sc[p]
                np.testing.assert_array_equal(flat, data[p])
                assert list(meta) == [4]

    @pytest.mark.parametrize("procs", PROC_SETS,
                             ids=lambda p: f"P{len(p)}")
    def test_allgather_bruck_and_ring_heterogeneous(self, procs):
        P = len(procs)
        rng = np.random.RandomState(3)
        blocks = {p: rng.randint(0, 99, ((i % 2) + 1, 5)).astype(np.int32)
                  for i, p in enumerate(procs)}
        counts = [blocks[p].size for p in procs]
        out = simulate(procs, lambda x, p: hs.allgather_bruck(
            x, procs, p, blocks[p].ravel(), counts))
        for p in procs:
            for i, q in enumerate(procs):
                np.testing.assert_array_equal(
                    out[p][i], blocks[q].ravel())
        out = simulate(procs, lambda x, p: hs.allgather_ring(
            x, procs, p, blocks[p]))
        for p in procs:
            for i, q in enumerate(procs):
                np.testing.assert_array_equal(out[p][i], blocks[q])

    @pytest.mark.parametrize("procs", PROC_SETS,
                             ids=lambda p: f"P{len(p)}")
    def test_alltoall_bruck_and_pairwise(self, procs):
        P = len(procs)
        rng = np.random.RandomState(4)
        mlen = [(i % 2) + 1 for i in range(P)]
        cf = 3
        pc = [[mlen[o] * mlen[j] * cf for j in range(P)]
              for o in range(P)]
        send = {p: [rng.randint(0, 99, pc[i][j]).astype(np.int32)
                    for j in range(P)]
                for i, p in enumerate(procs)}
        out = simulate(procs, lambda x, p: hs.alltoall_bruck(
            x, procs, p, send[p], pc))
        for i, p in enumerate(procs):
            for j, q in enumerate(procs):
                if q == p:
                    assert out[p][j] is None
                else:
                    np.testing.assert_array_equal(out[p][j], send[q][i])
        if P > 1:
            payloads = {p: {q: send[p][j]
                            for j, q in enumerate(procs) if q != p}
                        for p in procs}
            out = simulate(procs, lambda x, p: hs.alltoall_pairwise(
                x, procs, p, payloads[p]))
            for i, p in enumerate(procs):
                for j, q in enumerate(procs):
                    if q != p:
                        np.testing.assert_array_equal(
                            out[p][q], send[q][i])


# ---------------------------------------------------------------------------
# selection + packing units
# ---------------------------------------------------------------------------

class TestSelection:
    def test_fixed_constants(self):
        assert hs.pick("allreduce", 4, 1024) == "recursive_doubling"
        assert hs.pick("allreduce", 4, 1 << 20) == "rabenseifner"
        assert hs.pick("allreduce", 3, 1 << 20) == "ring"
        # non-commutative / identity-less large messages keep the
        # exact-order schedule
        assert hs.pick("allreduce", 4, 1 << 20,
                       commutative=False) == "recursive_doubling"
        assert hs.pick("allreduce", 4, 1 << 20,
                       has_identity=False) == "recursive_doubling"
        assert hs.pick("bcast", 8, 1 << 20) == "binomial"
        assert hs.pick("reduce", 8, 1024) == "binomial"
        assert hs.pick("reduce", 8, 1 << 20) == "linear"
        assert hs.pick("allgather", 8, 1024) == "bruck"
        assert hs.pick("allgather", 8, 1 << 20) == "linear"
        assert hs.pick("alltoall", 8, 1024) == "bruck"
        assert hs.pick("alltoall", 8, 1 << 20) == "pairwise"

    def test_forcing(self):
        mca_var.set_value("hier_inter_algorithm", "ring")
        try:
            assert hs.pick("allreduce", 4, 64) == "ring"
            # forcing an order-waiving schedule for a non-commutative
            # op is an ERROR (mirrors coll/tuned), not a silent downgrade
            with pytest.raises(MPIError):
                hs.pick("allreduce", 4, 64, commutative=False)
            # collectives with no 'ring'... bcast has no ring variant:
            # auto selection applies rather than a crash
            assert hs.pick("bcast", 4, 64) == "binomial"
        finally:
            mca_var.VARS.unset("hier_inter_algorithm")

    def test_dynamic_rules_and_noncommutative_downgrade(self, tmp_path):
        # the coll_tuned_* cvars register at framework open (runtime
        # init); this device-free test opens just the tuned component
        from ompi_release_tpu.coll.base import COLL_FRAMEWORK

        COLL_FRAMEWORK.lookup("tuned").register_vars()
        rules = tmp_path / "hier.conf"
        rules.write_text(textwrap.dedent("""
            hier_allreduce  0  0       linear
            hier_allreduce  0  4096    ring
            hier_bcast      0  0       linear
        """))
        mca_var.set_value("coll_tuned_use_dynamic_rules", True)
        mca_var.set_value("coll_tuned_dynamic_rules_filename",
                          str(rules))
        try:
            assert hs.pick("allreduce", 4, 100) == "linear"
            assert hs.pick("allreduce", 4, 8192) == "ring"
            # the rule file cannot waive MPI semantics
            assert hs.pick("allreduce", 4, 8192,
                           commutative=False) == "recursive_doubling"
            assert hs.pick("bcast", 4, 8192) == "linear"
            # no hier_alltoall rule: fixed constants apply
            assert hs.pick("alltoall", 4, 100) == "bruck"
        finally:
            mca_var.VARS.unset("coll_tuned_use_dynamic_rules")
            mca_var.VARS.unset("coll_tuned_dynamic_rules_filename")

    def test_shipped_rules_file_parses_with_hier_lines(self):
        from ompi_release_tpu.coll import dynamic_rules

        rules = dynamic_rules.load_rules(
            os.path.join(REPO, "tuning", "cpu8_rules.conf"))
        assert any(k.startswith("hier_") for k in rules), rules.keys()


class TestPairPacking:
    @pytest.mark.parametrize("vdt,idt", [(np.float32, np.int32),
                                         (np.float32, np.int64),
                                         (np.float64, np.int32)])
    def test_roundtrip(self, vdt, idt):
        from ompi_release_tpu.coll.hier import _HierModule

        rng = np.random.RandomState(0)
        pv = rng.randn(3, 5).astype(vdt)
        pi = rng.randint(0, 99, (3, 5)).astype(idt)
        buf = _HierModule._pack_pair(pv, pi)
        assert buf.dtype == np.uint8
        assert buf.nbytes == pv.nbytes + pi.nbytes  # ONE payload
        v, i = _HierModule._unpack_pair(buf, pv, pi)
        np.testing.assert_array_equal(v, pv)
        np.testing.assert_array_equal(i, pi)

    def test_roundtrip_odd_offset(self):
        """A value block whose byte length is not a multiple of the
        index itemsize still splits correctly (the unaligned-view
        path)."""
        from ompi_release_tpu.coll.hier import _HierModule

        pv = np.arange(3, dtype=np.float32)      # 12 bytes
        pi = np.arange(3, dtype=np.int64)        # 8-byte items at +12
        buf = _HierModule._pack_pair(pv, pi)
        v, i = _HierModule._unpack_pair(buf, pv, pi)
        np.testing.assert_array_equal(v, pv)
        np.testing.assert_array_equal(i, pi)


# ---------------------------------------------------------------------------
# real tpurun jobs: one per schedule family + leader tier + hang
# ---------------------------------------------------------------------------

APP_PRELUDE = textwrap.dedent("""
    import os, sys
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
    from ompi_release_tpu import ops as _ops
    from ompi_release_tpu.mca import pvar, var as mca_var
    from ompi_release_tpu.runtime.runtime import Runtime

    def _pv(name):
        p = pvar.PVARS.lookup(name)
        return float(p.read()) if p is not None else 0.0

    def force(alg):
        mca_var.set_value("hier_inter_algorithm", alg)
""" % REPO)


def _run(tmp_path, capfd, body, n=3, timeout=240, mca=()):
    app = tmp_path / "app.py"
    app.write_text(APP_PRELUDE + textwrap.dedent(body))
    job = Job(n, [sys.executable, str(app)], list(mca),
              heartbeat_s=0.5, miss_limit=8)
    rc = job.run(timeout_s=timeout)
    out = capfd.readouterr()
    assert rc == 0, out.out + out.err
    assert job.job_state.visited(JobState.TERMINATED)
    return out.out


class TestScheduleJobs:
    def test_allreduce_family_job(self, tmp_path, capfd):
        """linear/recursive_doubling/ring/rabenseifner forced in turn
        on a 3-process 6-rank world: numpy parity (bitwise for int32),
        pair-op parity through the packed payload, the split message
        pvars consistent with their alias, and ring's inter bytes
        strictly below linear's."""
        out = _run(tmp_path, capfd, """
            world = mpi.init()
            rt = Runtime.current()
            off = rt.local_rank_offset
            n = world.size
            x = np.stack([np.arange(64, dtype=np.int32) * (off + i + 1)
                          for i in range(2)])
            want = sum(np.arange(64, dtype=np.int32) * (r + 1)
                       for r in range(n))
            xf = x.astype(np.float32) * 0.125
            wantf = want.astype(np.float32) * 0.125
            bytes_by_alg = {}
            for alg in ("linear", "recursive_doubling", "ring",
                        "rabenseifner"):
                force(alg)
                b0 = _pv("hier_inter_bytes")
                got = np.asarray(world.allreduce(x))
                bytes_by_alg[alg] = _pv("hier_inter_bytes") - b0
                for i in range(2):
                    np.testing.assert_array_equal(got[i], want)
                gf = np.asarray(world.allreduce(xf))
                np.testing.assert_allclose(gf[0], wantf, rtol=1e-6)
                # pair op rides ONE packed message per peer per step
                pv_ = np.asarray([3., 1., 7., 2., 9., 0.],
                                 np.float32).reshape(n, 1)
                pi_ = np.arange(n, dtype=np.int32).reshape(n, 1)
                rv, ri = world.allreduce(
                    (pv_[off:off+2], pi_[off:off+2]), _ops.MAXLOC)
                assert float(np.asarray(rv)[0, 0]) == 9.0
                assert int(np.asarray(ri)[0, 0]) == 4
            # ring reduce-scatter+allgather ships ~2n*(P-1)/P, linear
            # (P-1)*n: at P=3 that is 4/3 n vs 2n per process
            assert bytes_by_alg["ring"] < bytes_by_alg["linear"], \\
                bytes_by_alg
            # every process both sent and received across the boundary
            assert _pv("hier_inter_msgs_sent") > 0
            assert _pv("hier_inter_msgs_recvd") > 0
            world.barrier()
            print(f"ALLREDUCE-FAM-OK {off}")
            mpi.finalize()
        """)
        for off in (0, 2, 4):
            assert f"ALLREDUCE-FAM-OK {off}" in out

    def test_tree_family_job(self, tmp_path, capfd):
        """Binomial bcast/reduce/gather/scatter on 3 processes: parity
        vs numpy, and the root's bcast send count drops from P-1 to
        ceil(log2 P) — the auditable O(log P) claim."""
        out = _run(tmp_path, capfd, """
            import math
            world = mpi.init()
            rt = Runtime.current()
            off = rt.local_rank_offset
            n = world.size
            full = np.stack([np.arange(8, dtype=np.int32) + 10 * r
                             for r in range(n)])
            mine = full[off:off + 2]
            P = 3
            sent_by_alg = {}
            for alg in ("linear", "binomial"):
                force(alg)
                s0 = _pv("hier_inter_msgs_sent")
                got = np.asarray(world.bcast(mine, root=5))
                sent_by_alg[alg] = _pv("hier_inter_msgs_sent") - s0
                for i in range(2):
                    np.testing.assert_array_equal(got[i], full[5])
            if off == 4:  # root's owner
                assert sent_by_alg["linear"] == P - 1, sent_by_alg
                assert sent_by_alg["binomial"] == math.ceil(
                    math.log2(P)), sent_by_alg

            for alg in ("linear", "binomial"):
                force(alg)
                red = np.asarray(world.reduce(mine, root=2))
                if off == 2:
                    np.testing.assert_array_equal(red[0], full.sum(0))
                else:
                    assert (red == 0).all()
                # non-commutative reduce keeps the documented fold
                # order: members fold within their process, process
                # partials fold in process-index order (MPI ops are
                # associative, so this regrouping is legal; the order
                # itself must be exact and deterministic)
                sub = _ops.user_op("sub_j", lambda a, b: a - b,
                                   commute=False)
                sred = np.asarray(world.reduce(mine, sub, root=2))
                parts = [full[2 * q] - full[2 * q + 1]
                         for q in range(3)]
                wsub = (parts[0] - parts[1]) - parts[2]
                if off == 2:
                    np.testing.assert_array_equal(sred[0], wsub)
                # MINLOC pair reduce through the packed gather
                apv = np.asarray([3., 1., 7., 2., 9., 0.],
                                 np.float32).reshape(n, 1)
                api = np.arange(n, dtype=np.int32).reshape(n, 1)
                rv, ri = world.reduce(
                    (apv[off:off+2], api[off:off+2]), _ops.MINLOC,
                    root=3)
                if off == 2:
                    assert float(np.asarray(rv)[1, 0]) == 0.0
                    assert int(np.asarray(ri)[1, 0]) == 5

                g = np.asarray(world.gather(mine, root=4))
                if off == 4:
                    np.testing.assert_array_equal(
                        g[0], full.reshape(-1))
                else:
                    assert (g == 0).all()

                sc_full = np.arange(n * 3, dtype=np.int32) * 7
                sc_in = np.stack([sc_full, sc_full])
                sc = np.asarray(world.scatter(sc_in, root=1))
                for i in range(2):
                    np.testing.assert_array_equal(
                        sc[i], sc_full[(off + i) * 3:(off + i + 1) * 3])
            world.barrier()
            print(f"TREE-FAM-OK {off}")
            mpi.finalize()
        """)
        for off in (0, 2, 4):
            assert f"TREE-FAM-OK {off}" in out

    def test_exchange_family_job(self, tmp_path, capfd):
        """Bruck/ring allgather and bruck/pairwise alltoall forced on
        3 processes, bitwise parity vs the linear baseline results."""
        out = _run(tmp_path, capfd, """
            world = mpi.init()
            rt = Runtime.current()
            off = rt.local_rank_offset
            n = world.size
            full = np.stack([np.arange(6, dtype=np.int32) + 100 * r
                             for r in range(n)])
            mine = full[off:off + 2]
            a2a_in = np.stack([
                np.asarray([(off + i) * 100 + j for j in range(n)],
                           dtype=np.int32)
                for i in range(2)])
            for alg in ("linear", "bruck", "ring"):
                force(alg)
                ag = np.asarray(world.allgather(mine))
                np.testing.assert_array_equal(ag[0], full.reshape(-1))
                # scans ride the same row-exchange schedule
                sc = np.asarray(world.scan(mine))
                for i in range(2):
                    np.testing.assert_array_equal(
                        sc[i], full[:off + i + 1].sum(0))
            for alg in ("linear", "bruck", "pairwise"):
                force(alg)
                a2a = np.asarray(world.alltoall(a2a_in))
                for i in range(2):
                    want = np.asarray(
                        [s * 100 + (off + i) for s in range(n)],
                        dtype=np.int32)
                    np.testing.assert_array_equal(a2a[i], want)
            world.barrier()
            print(f"XCHG-FAM-OK {off}")
            mpi.finalize()
        """)
        for off in (0, 2, 4):
            assert f"XCHG-FAM-OK {off}" in out

    def test_leader_tier_job(self, tmp_path, capfd):
        """Fake two-host topology (procs 0,1 on one host, proc 2 on
        another): allreduce/bcast parity holds, the leader performs
        the cross-host combine (hier_leader_combines), and the
        non-leader's inter traffic collapses to its shm pair with the
        leader (one packed send per combine)."""
        out = _run(tmp_path, capfd, """
            import os
            nid = int(os.environ["OMPITPU_NODE_ID"])
            os.environ["OMPITPU_HOST_ID"] = (
                "hostA" if nid <= 2 else "hostB")
            world = mpi.init()
            rt = Runtime.current()
            off = rt.local_rank_offset
            me = rt.bootstrap["process_index"]
            n = world.size
            x = np.stack([np.arange(32, dtype=np.int32) * (off + i + 1)
                          for i in range(2)])
            want = sum(np.arange(32, dtype=np.int32) * (r + 1)
                       for r in range(n))
            s0 = _pv("hier_inter_msgs_sent")
            got = np.asarray(world.allreduce(x))
            d_sent = _pv("hier_inter_msgs_sent") - s0
            np.testing.assert_array_equal(got[0], want)
            lc = _pv("hier_leader_combines")
            if me == 0:
                assert lc >= 1, lc        # hostA's leader combined
            if me == 1:
                assert lc == 0 and d_sent == 1, (lc, d_sent)
            # bcast through the leader fan-out, remote root
            full = np.stack([np.arange(8, dtype=np.int32) + 10 * r
                             for r in range(n)])
            got = np.asarray(world.bcast(full[off:off+2], root=5))
            np.testing.assert_array_equal(got[0], full[5])
            # float parity within tolerance (per-host regrouped fold)
            xf = x.astype(np.float32) * 0.5
            gf = np.asarray(world.allreduce(xf))
            np.testing.assert_allclose(
                gf[0], want.astype(np.float32) * 0.5, rtol=1e-6)
            # opt-out restores the flat schedule
            mca_var.set_value("hier_leader_tier", False)
            l0 = _pv("hier_leader_combines")
            got = np.asarray(world.allreduce(x))
            np.testing.assert_array_equal(got[0], want)
            assert _pv("hier_leader_combines") == l0
            world.barrier()
            print(f"LEADER-OK {me}")
            mpi.finalize()
        """)
        for me in (0, 1, 2):
            assert f"LEADER-OK {me}" in out

    def test_hang_postmortem_names_ring_neighbor(self, tmp_path, capfd):
        """Hang injection under a FORCED ring schedule: process 1
        sleeps before the allreduce; the stalled peers' postmortems
        must name the stuck round (op + algorithm) and the specific
        ring neighbor being awaited — proc 0 waits on proc 2 (its ring
        predecessor), NOT on the sleeping proc directly, which is
        exactly the who-waits-on-whom chain tpu-doctor reconstructs."""
        pm_dir = tmp_path / "pm"
        out = _run(tmp_path, capfd, """
            import time
            world = mpi.init()
            rt = Runtime.current()
            me = rt.bootstrap["process_index"]
            off = rt.local_rank_offset
            n = world.size
            if me == 1:
                time.sleep(4.0)
            x = np.stack([np.full(8192, off + i + 1, np.float32)
                          for i in range(2)])
            got = np.asarray(world.allreduce(x))
            want = float(sum(r + 1 for r in range(n)))
            assert got[0][0] == want, got[0][0]
            world.barrier()
            print(f"HANG-RING-OK {me}")
            mpi.finalize()
        """, mca=[("hier_inter_algorithm", "ring"),
                  ("obs_enable", "1"),
                  ("obs_stall_timeout", "1.2"),
                  ("obs_postmortem_dir", str(pm_dir))])
        for me in (0, 1, 2):
            assert f"HANG-RING-OK {me}" in out
        pms = sorted(pm_dir.glob("postmortem-*-stall-*.json"))
        assert pms, f"no stall postmortem in {pm_dir}"
        named = []
        for p in pms:
            pm = json.loads(p.read_text())
            rounds = pm.get("hier_rounds", {})
            ring_round = any(
                st.get("op") == "allreduce" and st.get("alg") == "ring"
                for st in rounds.values())
            for st in pm.get("stalled", []):
                info = st.get("info") or {}
                if st.get("op") == "allreduce" and ring_round:
                    named.append(tuple(info.get("awaiting_procs") or ()))
        assert named, pms
        # the awaited process is a specific ring predecessor (proc 2
        # waits on the sleeper; proc 0 waits on proc 2 downstream)
        assert any(t in ((1,), (2,)) for t in named), named
