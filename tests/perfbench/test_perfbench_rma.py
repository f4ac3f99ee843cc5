"""The OSU one-sided operations (``put_bw``, ``get_bw``) and the two cells
PR 34 adds: their reference rows and least bytes against small cases
written out by hand; each operation called on two ``tpurun`` ranks on the
CPU and held to its reference over odd and even calls; CPU rehearsals of
``osu_rma.stream`` with the one-sided path broken underneath
(``faulty_rma_worker.py``) or the lower-precision control in the program's
place, seen to come out not correct through ``run.main``; a library
without the counters the configuration requires, which ends non-zero
before a window exists; and ``osu_span4.small``, entries alone, whose
rehearsals (traced and untraced, like ``osu_rma.stream``'s) are
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
from perfbench.ops import _rma

FAULTY = os.path.join(rh.HERE, "faulty_rma_worker.py")
# two ranks' rows: rank 0 holds 0..127, rank 1 holds 1000..1127
X = np.stack([np.arange(128, dtype=np.float32),
              1000 + np.arange(128, dtype=np.float32)])
MIB = 1 << 20
RMA_LARGE = {f"{k}.rma_large" for k in (
    "osc_sync_ms", "osc_pack_ms", "osc_d2h_ms", "osc_wait_ms",
    "osc_unpack_ms", "osc_h2d_ms", "rma_ops_per_call", "batches_per_call",
    "wire_mb", "plan_hit_pct", "home_turn_ms", "home_recv_ms",
    "home_program_ms", "home_out_ms", "home_back_ms")}
SPAN_LARGE = {f"{k}.span_large" for k in (
    "device_busy_ms", "peak_share_pct", "stall_ms", "fallback_copies",
    "device_idle_pct")}
SPAN_SMALL = {f"{k}.span_small" for k in (
    "dispatch_us", "device_busy_us", "peak_share_pct", "native_fire_pct",
    "device_idle_pct", "d2h_us", "h2d_us", "xchg_us")}


@pytest.mark.parametrize("op, rank, want", [
    # rank 1's slot holds rank 0's whole row; rank 0 returns its notice
    ("put_bw", 1, X[0]), ("put_bw", 0, np.float32([0])),
    # rank 0 holds what it got of rank 1's slot; rank 1 returns its head
    ("get_bw", 0, X[1]), ("get_bw", 1, np.float32([1000])),
])
def test_expected_row_of_each_rank(op, rank, want):
    row, scale = manifest.operation(op).expected(X, {}, None)
    assert scale is None  # data movement: compared exactly
    np.testing.assert_array_equal(row(rank), want)


@pytest.mark.parametrize("op", ["put_bw", "get_bw"])
def test_least_bytes_are_a_windows_worth(op):
    # the chip reads 64 blocks to put them, writes 64 to hold what it got
    assert manifest.operation(op).least_bytes(2, 64 * 4096) == (
        64 * 4096, 64 * 4096)


@pytest.mark.parametrize("op, size, payload", [
    ("put_bw", 65536, 4 * MIB), ("get_bw", 65536, 4 * MIB),
    ("put_bw", 1048576, 64 * MIB), ("get_bw", 4194304, 256 * MIB),
])
def test_payload_of_a_call_is_64_messages(op, size, payload):
    cell = rh.MAN.cell("osu_rma.stream")
    assert (op, size) in traffic.round_of(cell)
    assert traffic.payload_bytes(cell, op, size) == payload


def test_the_cells_and_their_rounds():
    rma = rh.MAN.cell("osu_rma.stream")
    assert traffic.round_of(rma) == [
        (op, s) for s in (65536, 1048576, 4194304)
        for op in ("put_bw", "get_bw")]
    assert [c for c in rh.MAN.cells if c.startswith("osu_rma.")] == [
        "osu_rma.stream"]
    # the two operations share a rank's row: 324 MiB of inputs a rank, a
    # round of six calls moves 648 MiB
    assert sum(n * 4 for _, n in traffic.inputs_of(rma)) == 324 * MIB
    assert sum(traffic.payload_bytes(rma, op, s)
               for op, s in traffic.round_of(rma)) == 648 * MIB
    cfg = rma["config"]
    assert cfg["operations"] == ["put_bw", "get_bw"] and rma["chips"] == 1
    assert _rma.WINDOW == cfg["window"] == 64 and cfg["host_ranks"] == [1]
    assert {m["name"] for m in rh.MAN.metrics_of("osu_rma.stream",
                                                 "end_to_end")} == {
        "span_algbw", "setup_s"}
    # by name: an entry appended later, for this cell or another, moves
    # no pin
    layer = {m["name"] for m in rh.MAN.metrics_of("osu_rma.stream",
                                                  "per_layer")}
    assert RMA_LARGE <= layer and SPAN_LARGE <= layer
    # after it, on the configuration that is there: entries alone
    names = list(rh.MAN.cells)
    assert names.index("osu_rma.stream") < names.index("osu_span4.small")
    small = rh.MAN.cell("osu_span4.small")
    assert small["chips"] == 4 and small["config"]["ranks"] == 4
    assert len(traffic.round_of(small)) == 15  # five collectives, three sizes
    assert {m["name"] for m in rh.MAN.metrics_of("osu_span4.small",
                                                 "end_to_end")} == {
        "span_call_us", "span_call_p95_us", "setup_s"}
    assert SPAN_SMALL <= {m["name"] for m in rh.MAN.metrics_of(
        "osu_span4.small", "per_layer")}
    assert {"osu_rma.stream", "osu_span4.small"} <= set(rh.cells("tpurun"))


def test_a_library_without_the_rma_counters_cannot_run_the_configuration():
    from ompi_release_tpu.osc import wire_win  # noqa: F401  (its counters)

    cfg = rh.MAN.cell("osu_rma.stream")["config"]
    assert cfg["requires"]["pvars"] == ["osc_wire_bytes", "osc_wire_ops"]
    assert _rma.require(cfg) is None
    lacking = {"requires": {"pvars": ["osc_wire_bytes", "no_such_counter"]}}
    with pytest.raises(SystemExit, match="no_such_counter"):
        _rma.require(lacking)


def test_it_ends_non_zero_before_a_window_exists(capfd, monkeypatch, tmp_path):
    """Through ``run.main``, as the driver tries the cell on the commit
    before PR 34: every rank stops at its first warm-up call, the job ends
    with a non-zero code in seconds, and no window was made (nothing can
    hang in a collective creation)."""
    made = tmp_path / "window_made"
    monkeypatch.setenv("PERFBENCH_FAULT", "no_counters")
    monkeypatch.setenv("PERFBENCH_WINDOW_MADE", str(made))
    rc = run.main(["--workload", "osu_rma.stream", "--seed", "7",
                   "--seconds", "0.3", "--trace", "0", "--rehearse-cpu"],
                  worker=FAULTY)
    out, err = capfd.readouterr()
    assert rc != 0 and not made.exists()
    assert "needs the library's RMA wire counters" in out + err
    assert "PERFBENCH-RANK" not in out


APP = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, %r)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax
    import numpy as np
    import ompi_release_tpu as mpi
    from perfbench import manifest

    world = mpi.init()
    cfg = manifest.Manifest().cell("osu_rma.stream")["config"]
    me = world.local_comm_ranks[0]
    x = np.arange(2 * 64 * 8, dtype=np.float32).reshape(2, -1) + 0.5
    mine = jax.device_put(x[me:me + 1], jax.sharding.NamedSharding(
        world.submesh, jax.sharding.PartitionSpec("rank")))
    verdict = {}
    for op in ("put_bw", "get_bw"):
        mod = manifest.operation(op)
        row, _ = mod.expected(x, cfg, None)
        verdict[op] = []
        for _ in range(3):  # even, odd, even: put_bw trades blocks on odd
            got = mod.call(world, mine, cfg)
            verdict[op].append(bool(
                isinstance(got, jax.Array) and got.shape[0] == 1
                and np.array_equal(np.asarray(got).reshape(-1), row(me))))
            # rank 1 has read its slot before rank 0 writes it again, as
            # the cell's round between two calls on one window ensures
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
            assert json.load(f) == {"put_bw": [True] * 3,
                                    "get_bw": [True] * 3}


@pytest.mark.parametrize("fault", ["dropped", "swapped", "get_kept",
                                   "stale_flush"])
def test_a_broken_one_sided_path_is_not_correct(capfd, monkeypatch, fault):
    line = rh.check_fault(capfd, monkeypatch, "osu_rma.stream", fault,
                          "moved_mismatch", worker=FAULTY)
    # every call returned an array where it belongs: only the comparison
    # with the reference can see these
    assert line["compared"]["missing"]["value"] == 0
    assert line["compared"]["misplaced"]["value"] == 0


def test_the_lower_precision_control_is_not_correct(capfd):
    rh.check_control(capfd, "osu_rma.stream")
