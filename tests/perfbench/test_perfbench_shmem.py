"""The OSU OpenSHMEM operations (``oshm_put_mr``, ``oshm_get``,
``oshm_amo_post``, ``oshm_amo_fetch``) and the cell PR 36 adds: their
reference rows and least bytes against small cases written out by hand;
each operation called on two ``tpurun`` ranks on the CPU and held to its
reference over odd and even calls; CPU rehearsals of ``osu_shmem.rate``
with the OpenSHMEM path broken underneath (``faulty_shmem_worker.py``) or
the lower-precision control in the program's place, seen to come out not
correct through ``run.main``; and a library without the counters the
configuration requires, which ends non-zero before an allocation exists.
The cell's own rehearsals, traced and untraced, are
``test_perfbench_rehearse_tpurun.py``'s over every ``tpurun`` cell."""

import json
import os
import sys
import textwrap

import numpy as np
import pytest

from ompi_release_tpu.tools.tpurun import Job

import perfbench_rehearsal as rh
from perfbench import manifest, run, traffic
from perfbench.ops import _shm

FAULTY = os.path.join(rh.HERE, "faulty_shmem_worker.py")
CELL = "osu_shmem.rate"
OPS = ["oshm_put_mr", "oshm_get", "oshm_amo_post", "oshm_amo_fetch"]
# two PEs' float rows: PE 0 holds 0..127, PE 1 holds 1000..1127
X = np.stack([np.arange(128, dtype=np.float32),
              1000 + np.arange(128, dtype=np.float32)])
# a table of 16 words: word k names index k % 8 and the value k
WORD = (np.arange(16) % 8 << 8) + 128 + np.arange(16)
T = np.stack([WORD, WORD + 5]).astype(np.int32)
SHM_SMALL = {f"{k}.shm_small" for k in (
    "shm_quiet_us", "shm_drain_us", "shm_get_us", "shm_amo_us",
    "osc_sync_us", "osc_wait_us", "osc_pack_us", "osc_unpack_us",
    "osc_d2h_us", "osc_h2d_us", "shm_ops_per_call", "shm_batches_per_call",
    "shm_wire_kb", "plan_hit_pct", "home_turn_us", "home_recv_us",
    "home_program_us", "home_out_us", "home_back_us")}
SPAN_SMALL = {f"{k}.span_small" for k in (
    "device_busy_us", "peak_share_pct", "device_idle_pct")}


def by_hand_posted():
    # the 64 posted AMOs read the 16 words four times over: adds from the
    # first 32 (each word twice: 2 * (i + (i + 8)) at index i), then 32
    # incs (each word twice: 4 at index i)
    table = T[0].copy()
    table[:8] += 4 * np.arange(8, dtype=np.int32) + 16 + 4
    return table


def by_hand_fetching():
    # four groups of fetch_add, fetch_inc, swap, compare_swap on words 0
    # and 4 in turn; the compares of groups 0 and 2 hit
    old = [128, 129, 130, 2, 1156, 1161, 1162, 6,
           3, 12, 13, 10, 6, 19, 20, 14]
    table = T[0].copy()
    table[0], table[4] = 11, 14
    return np.int32(old), table


@pytest.mark.parametrize("op, x, rank, want", [
    # PE 1's allocation holds PE 0's whole row; PE 0 returns its notice
    ("oshm_put_mr", X, 1, X[0]), ("oshm_put_mr", X, 0, np.float32([0])),
    # PE 0 holds what it got of PE 1's allocation; PE 1 returns its head
    ("oshm_get", X, 0, X[1]), ("oshm_get", X, 1, np.float32([1000])),
    # PE 1's table: the base and every contribution; PE 0 its notice
    ("oshm_amo_post", T, 1, by_hand_posted()),
    ("oshm_amo_post", T, 0, T[0][:1]),
    # PE 0 holds the 16 old values, PE 1 the table they left
    ("oshm_amo_fetch", T, 0, by_hand_fetching()[0]),
    ("oshm_amo_fetch", T, 1, by_hand_fetching()[1]),
])
def test_expected_row_of_each_rank(op, x, rank, want):
    row, scale = manifest.operation(op).expected(x, {}, None)
    assert scale is None  # compared exactly
    got = row(rank)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)


def test_the_sums_wrap_as_int32_does():
    top = np.int32(2**31 - 1)
    x = np.stack([np.full(16, top), np.zeros(16, np.int32)])
    # every word is 0x7fffffff: index (w >> 8) % 8 == 7, value 127
    post, _ = manifest.operation("oshm_amo_post").expected(x, {}, None)
    want = np.int64(int(top) + 32 * 127 + 32).astype(np.int32)
    assert post(1)[7] == want < 0
    fetch, _ = manifest.operation("oshm_amo_fetch").expected(x, {}, None)
    assert list(fetch(0)[:4]) == [top, np.int32(-2**31 + 126),
                                  np.int32(-2**31 + 127), 127]


@pytest.mark.parametrize("op, size, want", [
    ("oshm_put_mr", 4096, 64 * 4096), ("oshm_get", 4096, 16 * 4096),
    ("oshm_amo_post", 4096, 4096), ("oshm_amo_fetch", 4096, 4096 + 64)])
def test_least_bytes_are_what_the_chip_reads_or_writes(op, size, want):
    cell = rh.MAN.cell(CELL)
    assert (op, size) in traffic.round_of(cell)
    s = traffic.payload_bytes(cell, op, size)
    assert manifest.operation(op).least_bytes(2, s) == (want, want)


def test_the_plans_indices_repeat_and_half_of_the_compares_hit():
    rng = np.random.default_rng(5)
    row = rng.integers(-2**31, 2**31, size=1024).astype(np.int32)
    posted = _shm.posted_plan(row)
    assert len(posted) == _shm.WINDOW
    assert len({i for i, _ in posted}) <= _shm.HOT < len(posted)
    assert all(-128 <= v <= 127 for _, v in posted[:32])
    assert all(v == 1 for _, v in posted[32:])
    plan = _shm.fetching_plan(row)
    assert [k for k, *_ in plan] == ["fetch_add", "fetch_inc", "swap",
                                     "cswap"] * 4
    swaps, compares = plan[2::4], plan[3::4]
    assert [c[3] - s[2] for s, c in zip(swaps, compares)] == [0, 1, 0, 1]
    # a table of two words (8 bytes) gives its words again
    assert all(i < 2 for i, _ in _shm.posted_plan(row[:2]))


def test_the_cell_and_its_rounds():
    cell = rh.MAN.cell(CELL)
    assert traffic.round_of(cell) == [(op, s) for s in (8, 4096, 65536)
                                      for op in OPS]
    # the tenth cell (later ones come after it: no test pins the end)
    assert list(rh.MAN.cells)[9] == CELL and cell["chips"] == 1
    assert [c for c in rh.MAN.cells if c.startswith("osu_shmem.")] == [CELL]
    cfg = cell["config"]
    assert cfg["operations"] == OPS and cfg["host_ranks"] == [1]
    assert cfg["launcher"] == "tpurun" and cfg["ranks"] == 2
    assert _shm.WINDOW == cfg["window"] == 64
    assert _shm.BLOCKING == cfg["blocking"] == 16
    assert [traffic.dtype_of(cfg, op).name for op in OPS] == [
        "float32", "float32", "int32", "int32"]
    # allocations of 64 x and 16 x the size, tables of the size
    assert [traffic.payload_bytes(cell, op, 65536) for op in OPS] == [
        4 << 20, 1 << 20, 65536, 65536]
    assert {m["name"] for m in rh.MAN.metrics_of(CELL, "end_to_end")} == {
        "span_call_us", "span_call_p95_us", "setup_s"}
    # by name: an entry appended later, for this cell or another, moves
    # no pin
    layer = {m["name"] for m in rh.MAN.metrics_of(CELL, "per_layer")}
    assert SHM_SMALL <= layer and SPAN_SMALL <= layer
    assert CELL in rh.cells("tpurun")
    # of the first ten cells four are on four chips
    assert [w["chips"] for w in rh.MAN.doc["workloads"][:10]].count(4) == 4


def test_a_library_without_the_shmem_counters_cannot_run_the_configuration():
    import ompi_release_tpu.oshmem.shmem  # noqa: F401  (its counters)

    cfg = rh.MAN.cell(CELL)["config"]
    assert cfg["requires"]["pvars"] == ["shmem_blocking_ops", "shmem_quiets"]
    assert _shm.require(cfg) is None
    lacking = {"requires": {"pvars": ["shmem_quiets", "no_such_counter"]}}
    with pytest.raises(SystemExit, match="no_such_counter"):
        _shm.require(lacking)


def test_it_ends_non_zero_before_an_allocation_exists(capfd, monkeypatch,
                                                      tmp_path):
    """Through ``run.main``, as the driver tries the cell on the commit
    before PR 36: every rank stops at its first warm-up call, the job ends
    with a non-zero code in seconds, and no allocation was made (nothing
    can hang in a collective ``malloc``)."""
    made = tmp_path / "allocation_made"
    monkeypatch.setenv("PERFBENCH_FAULT", "no_counters")
    monkeypatch.setenv("PERFBENCH_ALLOCATION_MADE", str(made))
    rc = run.main(["--workload", CELL, "--seed", "7", "--seconds", "0.3",
                   "--trace", "0", "--rehearse-cpu"], worker=FAULTY)
    out, err = capfd.readouterr()
    assert rc != 0 and not made.exists()
    assert "needs the library's OpenSHMEM counters" in out + err
    assert "PERFBENCH-RANK" not in out


APP = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, %r)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax
    import numpy as np
    import ompi_release_tpu as mpi
    from perfbench import manifest, traffic

    world = mpi.init()
    cfg = manifest.Manifest().cell("osu_shmem.rate")["config"]
    me = world.local_comm_ranks[0]
    rng = np.random.default_rng(36)
    verdict = {}
    for op in cfg["operations"]:
        mod = manifest.operation(op)
        dt = traffic.dtype_of(cfg, op)
        n = mod.elems(2, 32, dt.itemsize)
        x = (rng.integers(-2**31, 2**31, size=(2, n)).astype(dt)
             if dt.kind == "i" else
             np.arange(2 * n, dtype=dt).reshape(2, -1) + 0.5)
        mine = jax.device_put(x[me:me + 1], jax.sharding.NamedSharding(
            world.submesh, jax.sharding.PartitionSpec("rank")))
        row, _ = mod.expected(x, cfg, None)
        verdict[op] = []
        for _ in range(3):  # even, odd, even: what an odd call trades
            got = mod.call(world, mine, cfg)
            verdict[op].append(bool(
                isinstance(got, jax.Array) and got.shape[0] == 1
                and got.dtype == dt
                and np.array_equal(np.asarray(got).reshape(-1), row(me))))
            # PE 1 has read its allocation before PE 0 puts to it again, as
            # the cell's round between two calls on one allocation ensures
            world.barrier()
    with open(os.path.join(sys.argv[1], "rank%%d.json" %% me), "w") as f:
        json.dump(verdict, f)
    world.barrier()
    mpi.finalize()
""") % manifest.ROOT


def test_each_operation_on_two_ranks_matches_its_reference(tmp_path):
    app = tmp_path / "app.py"
    app.write_text(APP)
    job = Job(2, [sys.executable, str(app), str(tmp_path)], [],
              heartbeat_s=0.5, miss_limit=8)
    assert job.run(timeout_s=240) == 0
    for rank in (0, 1):
        with open(tmp_path / f"rank{rank}.json") as f:
            assert json.load(f) == {op: [True] * 3 for op in OPS}


@pytest.mark.parametrize("fault", [
    "amo_dropped", "amo_twice", "amo_before_put", "fetch_new", "early_quiet",
    "put_neighbour", "get_own"])
def test_a_broken_openshmem_path_is_not_correct(capfd, monkeypatch, fault):
    line = rh.check_fault(capfd, monkeypatch, CELL, fault, "moved_mismatch",
                          worker=FAULTY)
    # every call returned an array where it belongs: only the comparison
    # with the reference can see these
    assert line["compared"]["missing"]["value"] == 0
    assert line["compared"]["misplaced"]["value"] == 0


def test_the_lower_precision_control_is_not_correct(capfd):
    rh.check_control(capfd, CELL)
