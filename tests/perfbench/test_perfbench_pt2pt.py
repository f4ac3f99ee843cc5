"""The OSU pt2pt operations (``pingpong``, ``bw``, ``bibw``): their reference
rows and least bytes against small cases written out by hand; each of them
called on two ``tpurun`` ranks on the CPU and held to its reference (``bibw``
is in the configuration and in no cell: this is its rehearsal); and CPU
rehearsals of the cell with the point-to-point path broken underneath
(``faulty_pt2pt_worker.py``) or the lower-precision control in the program's
place, seen to come out not correct through ``run.main``. The ping-pong cell
waits (PERF.md section 7): ``mix`` builds it and the ``bibw`` mix from the
configuration as ``manifest.cell`` would."""

import json
import os
import sys
import textwrap

import numpy as np
import pytest

from ompi_release_tpu.tools.tpurun import Job

import perfbench_rehearsal as rh
from perfbench import manifest, traffic
from perfbench.ops import _pt2pt

FAULTY = os.path.join(rh.HERE, "faulty_pt2pt_worker.py")
# two ranks' buffers: rank 0 holds 0..127, rank 1 holds 1000..1127
X = np.stack([np.arange(128, dtype=np.float32),
              1000 + np.arange(128, dtype=np.float32)])


@pytest.mark.parametrize("op, rank, want", [
    ("pingpong", 0, X[1]), ("pingpong", 1, X[0]),
    # rank 1 holds rank 0's whole window, rank 0 the one-element acknowledgement
    ("bw", 1, X[0]), ("bw", 0, np.float32([1000])),
    ("bibw", 0, X[1]), ("bibw", 1, X[0]),
])
def test_expected_row_of_each_rank(op, rank, want):
    row, scale = manifest.operation(op).expected(X, {}, None)
    assert scale is None  # data movement: compared exactly
    np.testing.assert_array_equal(row(rank), want)


@pytest.mark.parametrize("op, s, mem", [
    ("pingpong", 4096, 2 * 4096),       # reads what it sends, writes what arrives
    ("bw", 64 * 4096, 64 * 4096),       # reads the window; s is the whole window
    ("bibw", 64 * 4096, 2 * 64 * 4096),  # a window each way
])
def test_least_bytes_are_the_messages_alone(op, s, mem):
    link, memory = manifest.operation(op).least_bytes(2, s)
    assert (link, memory) == (s, mem)


def mix(operations, sizes):
    """A mix of the configuration that no cell has (yet), as
    ``manifest.cell`` would hand it to the generator."""
    stream = rh.MAN.cell("osu_pt2pt.stream")
    return dict(stream, operations=operations,
                traffic={"sizes_bytes": sizes, "operations": operations})


@pytest.mark.parametrize("cell, op, size, payload", [
    (mix(["pingpong"], [8, 4096, 65536]), "pingpong", 8, 8),
    (mix(["pingpong"], [8, 4096, 65536]), "pingpong", 65536, 65536),
    # OSU's bytes per iteration: window x message size
    (rh.MAN.cell("osu_pt2pt.stream"), "bw", 65536, 64 * 65536),
    (rh.MAN.cell("osu_pt2pt.stream"), "bw", 4194304, 64 * 4194304),
    (mix(["bw", "bibw"], [1048576]), "bibw", 1048576, 64 * 1048576),
])
def test_payload_of_a_call(cell, op, size, payload):
    assert (op, size) in traffic.round_of(cell)
    assert traffic.payload_bytes(cell, op, size) == payload
    assert payload % (_pt2pt.WINDOW * 4) == 0 or op == "pingpong"


def test_the_cell_and_its_rounds():
    stream = rh.MAN.cell("osu_pt2pt.stream")
    lat = mix(["pingpong"], [8, 4096, 65536])
    assert traffic.round_of(lat) == [("pingpong", s) for s in (8, 4096, 65536)]
    assert traffic.round_of(stream) == [
        ("bw", s) for s in (65536, 1048576, 4194304)]
    assert [c for c in rh.MAN.cells if c.startswith("osu_pt2pt.")] == [
        "osu_pt2pt.stream"]
    # a round's payload, and what each rank's inputs hold: 324 MiB
    assert sum(n * 4 for _, n in traffic.inputs_of(stream)) == 324 << 20
    assert stream["config"]["operations"] == ["pingpong", "bw", "bibw"]
    assert _pt2pt.WINDOW == stream["config"]["window"] == 64
    assert {op: manifest.operation(op).TAG for op in lat["config"]["operations"]} \
        == lat["config"]["tags"]


def test_a_library_without_the_p2p_counters_cannot_run_the_configuration():
    """The deployment is measured with ``pml_wire_sends/_recvs/_bytes``
    (the configuration's ``requires``): with them the check passes and is
    silent; a library that lacks one ends the run before anything is sent,
    which is how the commit before PR 28 answers either cell."""
    import ompi_release_tpu.p2p.pml  # noqa: F401  (registers the counters)

    cfg = rh.MAN.cell("osu_pt2pt.stream")["config"]
    assert cfg["requires"]["pvars"] == [
        "pml_wire_sends", "pml_wire_recvs", "pml_wire_bytes"]
    assert _pt2pt.require(cfg) is None
    lacking = {"requires": {"pvars": ["pml_wire_sends", "no_such_counter"]}}
    with pytest.raises(SystemExit, match="no_such_counter"):
        _pt2pt.require(lacking)


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
    cfg = manifest.Manifest().cell("osu_pt2pt.stream")["config"]
    me = world.local_comm_ranks[0]
    x = np.arange(2 * 64 * 8, dtype=np.float32).reshape(2, -1) + 0.5
    mine = jax.device_put(x[me:me + 1])
    verdict = {}
    for op in ("pingpong", "bw", "bibw"):
        mod = manifest.operation(op)
        for _ in range(2):  # the second call sends fresh copies again
            got = mod.call(world, mine, cfg)
        row, _ = mod.expected(x, cfg, None)
        verdict[op] = bool(
            isinstance(got, jax.Array) and got.shape[0] == 1
            and np.array_equal(np.asarray(got).reshape(-1), row(me)))
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
            assert json.load(f) == {"pingpong": True, "bw": True,
                                    "bibw": True}


@pytest.mark.parametrize("cell, fault", [
    ("osu_pt2pt.stream", "swapped"),
    ("osu_pt2pt.stream", "altered_host"),
    ("osu_pt2pt.stream", "recv_kept"),
])
def test_a_broken_point_to_point_path_is_not_correct(capfd, monkeypatch, cell,
                                                     fault):
    line = rh.check_fault(capfd, monkeypatch, cell, fault, "moved_mismatch",
                          worker=FAULTY)
    # every byte arrived somewhere: nothing is missing or misplaced
    assert line["compared"]["missing"]["value"] == 0
    assert line["compared"]["misplaced"]["value"] == 0


def test_the_lower_precision_control_is_not_correct(capfd):
    rh.check_control(capfd, "osu_pt2pt.stream")
