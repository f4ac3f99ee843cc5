"""NAS IS class C on four chips (``npb_is4.class_c``): the cell and its
files by name, its rehearsals on the CPU traced and untraced through
``run.main`` (correct, with no byte of the exchange through the host),
the lower-precision control and four faults planted in the device-form
alltoallv underneath (``faulty_npb_is4_worker.py``), each seen to come
out not correct; a library without the device form, which ends the run
before anything collective is made; IS's keys from the seed; and the numpy
reference against a ranking by ``np.sort``."""

import os

import numpy as np
import pytest

import perfbench_rehearsal as rh
from perfbench import run, traffic
from perfbench.ops import _npb, npb_is

CELL = "npb_is4.class_c"
FAULTY = os.path.join(rh.HERE, "faulty_npb_is4_worker.py")
NPB = {f"{k}.npb" for k in ("alltoallv_ms", "host_mb", "device_busy_ms",
                            "device_idle_pct", "peak_share_pct",
                            "roofline_pct")}
MIB = 1 << 20


def test_the_cell_its_configuration_and_its_metrics():
    cell = rh.MAN.cell(CELL)
    cfg = cell["config"]
    assert cell["chips"] == 4 and cfg["launcher"] == "driver"
    assert cfg["ranks"] == 4 and cfg["host_ranks"] == []
    assert traffic.round_of(cell) == [("npb_is", 128 * MIB)]
    assert traffic.inputs_of(cell) == {("int32", 1 << 25): ["npb_is"]}
    # class C from the keys: 2^27 in all, MAX_KEY 2^23, 1024 buckets
    p = _npb.params(4, 1 << 25, cfg)
    assert 4 * p.keys == 1 << cfg["total_keys_log2"]
    assert p.max_key_log2 == cfg["max_key_log2"] == 23
    assert p.buckets == 1 << cfg["num_buckets_log2"] == 1024
    assert p.shift == 13 and p.cap == 3 * (1 << 24)
    assert cfg["requires"]["pvars"] == ["vcoll_alltoallv_device_calls"]
    assert cfg["reduced"] == ["class"] and cfg["class"] == "C"
    assert {m["name"] for m in rh.MAN.metrics_of(CELL, "end_to_end")} == {
        "call_us", "call_p95_us", "setup_s"}
    assert NPB == {m["name"] for m in rh.MAN.metrics_of(CELL, "per_layer")}
    assert "vcoll_alltoallv_host_bytes" in rh.MAN.pvars_of(CELL)
    assert CELL in rh.cells("driver")


def test_least_bytes_of_an_iteration():
    s = 128 * MIB
    link, mem = npb_is.least_bytes(4, s)
    assert link == 96 * MIB  # the three quarters of its keys that leave
    # bucketing, exchange (read and write each), ranking's read, and
    # its histogram of 2^23 int32 values
    assert mem == 5 * s + 32 * MIB


@pytest.mark.parametrize("trace", [0, 1])
def test_a_rehearsal_is_correct_with_nothing_through_the_host(capfd, trace):
    line, err = rh.rehearse(capfd, CELL, trace)
    rh.check_line(line, CELL, trace, err)
    assert line["compared"]["moved_mismatch"]["value"] == 0
    if trace:
        assert line["metrics"]["host_mb.npb"]["value"] == 0.0
        assert line["metrics"]["alltoallv_ms.npb"]["value"] > 0


def test_the_lower_precision_control_is_not_correct(capfd):
    rh.check_control(capfd, CELL)


@pytest.mark.parametrize("fault, number", [
    ("dropped", "moved_mismatch"), ("swapped", "moved_mismatch"),
    ("chip0", "missing"), ("truncated", "moved_mismatch")])
def test_a_broken_exchange_is_not_correct(capfd, monkeypatch, fault, number):
    rh.check_fault(capfd, monkeypatch, CELL, fault, number, worker=FAULTY)


def test_without_the_device_form_it_ends_before_anything_collective(
        capfd, monkeypatch, tmp_path):
    """As the driver tries the cell on the commit before the device form:
    the first warm-up call ends the run with a non-zero code, no result,
    and no collective made."""
    made = tmp_path / "collective_made"
    monkeypatch.setenv("PERFBENCH_FAULT", "no_counters")
    monkeypatch.setenv("PERFBENCH_COLLECTIVE_MADE", str(made))
    rc = run.main(["--workload", CELL, "--seed", "7", "--seconds", "0.3",
                   "--trace", "0", "--rehearse-cpu"], worker=FAULTY)
    out, err = capfd.readouterr()
    assert rc != 0 and not made.exists()
    assert "needs the library's device-form alltoallv" in out + err
    assert "PERFBENCH-RANK" not in out


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_the_keys_are_the_bell_of_four_uniforms(seed):
    """The keys from the generator's uniform words: int32 on [0,
    MAX_KEY), the same from the same seed, on the chips' side (jax) and
    the reference's (numpy) alike, the edge buckets far below the middle
    ones (the density of a mean of four uniforms is 2/3 at the middle,
    1/384 on average over the outer sixteenth), and the mean MAX_KEY/2
    within six standard errors."""
    import jax.numpy as jnp

    cfg = rh.MAN.cell(CELL)["config"]
    n, keys = 4, 1 << 14
    p = _npb.params(n, keys, cfg)
    (words,) = traffic.make_inputs(seed, [("int32", keys)], n).values()
    x = np.asarray(_npb.keys_of(words, p.max_key_log2, jnp))
    assert x.dtype == np.int32 and x.shape == (n, keys)
    assert 0 <= x.min() and x.max() < p.max_key
    np.testing.assert_array_equal(
        _npb.keys_of(np.asarray(words), p.max_key_log2, np), x)
    sizes = np.bincount(x.reshape(-1) >> p.shift, minlength=p.buckets)
    edge = sizes[:p.buckets // 16].sum() + sizes[-p.buckets // 16:].sum()
    middle = sizes[p.buckets * 7 // 16:p.buckets * 9 // 16].sum()
    assert edge * 50 < middle
    # a mean of four uniforms on [0, MAX_KEY) has the standard deviation
    # MAX_KEY / sqrt(48)
    sem = p.max_key / np.sqrt(48 * n * keys)
    assert abs(x.mean() - p.max_key / 2) < 6 * sem
    (again,) = traffic.make_inputs(seed, [("int32", keys)], n).values()
    np.testing.assert_array_equal(
        np.asarray(_npb.keys_of(again, p.max_key_log2, jnp)), x)


@pytest.mark.parametrize("n, keys", [(4, 1 << 10), (8, 1 << 9), (2, 512)])
def test_the_reference_ranks_as_a_sort_does(n, keys):
    """NAS's ranking at a tiny class against one by ``np.sort``: each
    rank's key_buff1 at value v of its range is how many of its keys are
    at or below v, and every key is ranked on exactly one rank."""
    cfg = {"num_buckets_log2": 10, "recv_capacity": 1.5}
    p = _npb.params(n, keys, cfg)
    rng = np.random.default_rng(n)
    x = (rng.integers(0, p.max_key, (4, n, keys)).sum(0) >> 2).astype(
        np.int32)
    row = _npb.nas_rank(x, p)
    start, last = _npb.nas_cut(
        np.bincount(x.reshape(-1) >> p.shift, minlength=p.buckets), p)
    ranked = 0
    for r in range(n):
        lo, hi = start[r] << p.shift, (last[r] + 1) << p.shift
        mine = np.sort(x[(x >= lo) & (x < hi)])
        want = np.zeros(p.max_key, np.int64)
        want[:hi - lo] = np.searchsorted(mine, np.arange(lo, hi), "right")
        np.testing.assert_array_equal(row(r), want)
        ranked += mine.size
    assert ranked == x.size


@pytest.mark.parametrize("case, keys", [
    ("bell", 1 << 10), ("silent_source", 1 << 10), ("one_value", 1 << 10),
    ("bell", 1 << 14), ("sparse", 1 << 14)])
def test_the_device_ranking_of_the_sorted_runs_is_nas(case, keys):
    """The ranking program on receive rows as the exchange leaves them
    (each source's keys of the rank's range, sorted, in source order,
    zero past them) gives NAS's key_buff1 of every rank: also where a
    source sends a rank nothing, and where every key of a range is one
    value, and where each source spreads a tenth of its keys thinly
    over the values and puts the rest on one."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    n = 4
    cfg = {"num_buckets_log2": 10, "recv_capacity": 1.5}
    rng = np.random.default_rng(len(case))
    p = _npb.params(n, keys, cfg)
    x = (rng.integers(0, p.max_key, (4, n, keys)).sum(0) >> 2).astype(
        np.int32)
    if case == "silent_source":
        x[0] = np.sort(x[0]) // 4  # rank 0's keys all in the low ranges
    elif case == "one_value":
        x[1, :keys // 2] = p.max_key // 2
    elif case == "sparse":  # a tenth spread, the rest on one value a source
        for k in range(n):
            x[k, keys // 10:] = (2 * k + 1) * p.max_key // (2 * n)
            x[k, :keys // 10] = rng.integers(0, p.max_key, keys // 10)
    totals = np.bincount(x.reshape(-1) >> p.shift, minlength=p.buckets)
    start, last = _npb.nas_cut(totals, p)
    recv = np.zeros((n, p.cap), np.int32)
    counts = np.zeros((n, n), np.int32)
    spans = np.zeros((n, 2), np.int32)
    for r in range(n):
        lo, hi = start[r] << p.shift, (last[r] + 1) << p.shift
        runs = [np.sort(x[k][(x[k] >= lo) & (x[k] < hi)]) for k in range(n)]
        got = np.concatenate(runs)
        assert got.size <= p.cap
        recv[r, :got.size] = got
        counts[r] = [len(run) for run in runs]
        spans[r] = lo, hi - lo
    if case == "silent_source":
        assert (counts[n - 1:, 0] == 0).all()
    if case == "one_value":
        assert (recv == p.max_key // 2).sum() > keys // 2
    mesh = Mesh(np.array(jax.devices()[:n]), ("rank",))
    rank = _npb.programs(mesh, p)[3]
    sh = NamedSharding(mesh, P("rank"))
    out = np.asarray(rank(*[jax.device_put(a, sh)
                            for a in (recv, counts, spans)]))
    row = _npb.nas_rank(x, p)
    for r in range(n):
        np.testing.assert_array_equal(out[r], row(r))
