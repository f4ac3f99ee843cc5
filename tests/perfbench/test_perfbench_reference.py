"""The yardstick's arithmetic: least bytes and least time by hand, the
readers, the comparison, and its control (which has to fail)."""

import numpy as np
import pytest

from perfbench import least, manifest, reference

MiB = 1 << 20
PEAKS = {"hbm_bytes_per_s": 819e9, "ici_bytes_per_s": 200e9}


@pytest.mark.parametrize("op, n, s, link, mem", [
    ("allreduce", 4, 4 * MiB, 6 * MiB, 8 * MiB),       # 2(n-1)/n S; S in, S out
    ("allgather", 4, 4 * MiB, 12 * MiB, 20 * MiB),     # (n-1) S; S in, nS out
    ("reduce_scatter_block", 4, 4 * MiB, 3 * MiB, 5 * MiB),
    ("alltoall", 4, 4 * MiB, 3 * MiB, 8 * MiB),
    ("bcast", 4, 4 * MiB, 4 * MiB, 4 * MiB),
    ("allreduce", 2, 64 * MiB, 64 * MiB, 128 * MiB),
])
def test_least_bytes_by_hand(op, n, s, link, mem):
    assert manifest.operation(op).least_bytes(n, s) == (link, mem)


def test_least_time_takes_the_larger_bound_and_no_link_term_when_spanning():
    s = 256 * MiB
    # allreduce, 4 ranks: 1.5 S over the links (2.01 ms) beats 2 S through
    # memory (0.66 ms)
    assert least.seconds("allreduce", 4, s, PEAKS, "ici") == \
        pytest.approx(1.5 * s / 200e9)
    assert least.seconds("allreduce", 2, s, PEAKS, "host") == \
        pytest.approx(2 * s / 819e9)


def test_block_operations_round_the_element_count_up():
    assert manifest.operation("alltoall").elems(4, 8, 4) == 4
    assert manifest.operation("reduce_scatter_block").elems(4, 4096, 4) == 1024
    assert manifest.operation("allreduce").elems(4, 8, 4) == 2


def test_readers_arithmetic():
    facts = {"calls": 4, "seconds": 2.0, "payload_bytes": 4e9, "setup_s": 7.5,
             "call_seconds": [0.4, 0.1, 0.2, 0.3] * 5,
             "pvars": {"t": 0.002, "agg": {"sum": 3, "count": 4},
                       "fires": 0, "fallbacks": 0},
             "slice": {"calls": 2, "busy_s": 0.5, "window_s": 1.0,
                       "least_s": 0.05, "spans": [(0, 10**9, "x.stage")]}}
    r = manifest.reader
    assert r("window_per_call")(facts, scale=1e6) == 500000.0
    assert r("window_percentile")(facts, pct=95, scale=1) == 0.4
    assert r("window_percentile")(facts, pct=50, scale=1) == 0.2
    assert r("window_bytes_rate")(facts, scale=1e-9) == 2.0
    assert r("setup_seconds")(facts) == 7.5
    assert r("pvar_delta_per_call")(facts, pvars=["t"], scale=1e6) == 500.0
    assert r("pvar_delta_per_call")(facts, pvars=["absent"], scale=1) is None
    assert r("pvar_ratio_pct")(facts, pvars=["agg"], num="agg.sum",
                               den=["agg.count"]) == 75.0
    # a pvar pair that did not tick reads nothing, never 0/0
    assert r("pvar_ratio_pct")(facts, pvars=[], num="fires",
                               den=["fires", "fallbacks"]) is None
    assert r("trace_busy_per_call")(facts, scale=1e3) == 250.0
    assert r("trace_idle_pct")(facts) == 50.0
    assert r("peak_share_pct")(facts) == pytest.approx(5.0)
    assert r("roofline_pct")(facts) == pytest.approx(10.0)
    assert r("trace_span_per_call")(facts, pattern=r"\.stage$", scale=1) == 0.5
    assert r("trace_span_per_call")(facts, pattern="nothing", scale=1) is None
    untraced = dict(facts, slice=None)
    for name in ("trace_busy_per_call", "trace_idle_pct", "peak_share_pct",
                 "roofline_pct"):
        assert r(name)(untraced, **({"scale": 1} if "busy" in name else {})) \
            is None


def _inputs(n, elems, dtype, seed=0):
    rng = np.random.default_rng(seed)
    if dtype == np.int32:
        return rng.integers(-2**31, 2**31 - 1, (n, elems), dtype=np.int32)
    return (rng.uniform(2**-7, 1, (n, elems))
            * rng.choice([-1.0, 1.0], (n, elems))).astype(np.float32)


CFG = {"reduce_op": "SUM", "bcast_root": 0}


def _program(op, x):
    """What a sound program returns, by the definition of each call."""
    n = x.shape[0]
    if op == "allreduce":
        return {r: x[::-1].sum(0) for r in range(n)}  # another order
    if op == "bcast":
        return {r: x[0].copy() for r in range(n)}
    if op == "allgather":
        return {r: x.reshape(-1).copy() for r in range(n)}
    if op == "reduce_scatter_block":
        return {r: x[::-1].sum(0).reshape(n, -1)[r] for r in range(n)}
    return {r: x.reshape(n, n, -1)[:, r].reshape(-1) for r in range(n)}


@pytest.mark.parametrize("op", ["allreduce", "bcast", "allgather",
                                "reduce_scatter_block", "alltoall"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_sound_results_pass_and_the_lower_precision_control_fails(op, seed):
    dtype = np.int32 if op == "alltoall" else np.float32
    x = _inputs(4, 1 << 14, dtype, seed)
    lim = reference.limits()
    good = reference.compare(op, CFG, x, _program(op, x).items(),
                             reference.Sums())
    ctrl = reference.compare(op, CFG, x, _program(op, x).items(),
                             reference.Sums(), control=True)
    if manifest.operation(op).KIND == "reduce":
        assert good["err_ulp"] <= lim["sum_err_ulp"] / 4
        assert ctrl["err_ulp"] >= 3 * lim["sum_err_ulp"]
    else:
        assert good["mismatch"] == 0 == lim["moved_mismatch"]
        assert ctrl["mismatch"] > 0.9 * ctrl["elements"]


def test_an_altered_answer_and_a_wrong_shape_do_not_pass():
    x = _inputs(2, 1024, np.float32)
    rows = _program("bcast", x)
    rows[1][17] += 1.0
    got = reference.compare("bcast", CFG, x, rows.items(), reference.Sums())
    assert got["mismatch"] == 1
    short = {r: v[:-1] for r, v in _program("allreduce", x).items()}
    got = reference.compare("allreduce", CFG, x, short.items(),
                            reference.Sums())
    assert got["err_ulp"] == reference.BROKEN
    assert got["mismatch"] == got["elements"]


def test_a_nan_in_one_block_is_not_lost_in_the_blocks_after_it():
    x = _inputs(2, 3 << 19, np.float32)  # two blocks of the comparison
    rows = _program("allreduce", x)
    rows[1][5] = np.nan
    got = reference.compare("allreduce", CFG, x, rows.items(),
                            reference.Sums())
    assert got["err_ulp"] == reference.BROKEN  # one rank's NaN fails the cell
    assert got["mismatch"] == x.shape[1]
    assert not reference.verdict({"sum_err_ulp": got["err_ulp"]})[1]
    lazy = {r: (lambda v=v: v) for r, v in _program("allreduce", x).items()}
    got = reference.compare("allreduce", CFG, x, lazy.items(),
                            reference.Sums())
    assert got["err_ulp"] <= 2 and got["mismatch"] == 0


def test_verdict_holds_each_number_to_its_own_limit():
    lim = {"sum_err_ulp": 16, "moved_mismatch": 0}
    compared, ok = reference.verdict({"sum_err_ulp": 1.5, "moved_mismatch": 0,
                                      "absent": None} | {}, dict(lim, absent=0))
    assert ok and "absent" not in compared
    assert compared["sum_err_ulp"] == {"value": 1.5, "limit": 16}
    assert not reference.verdict({"sum_err_ulp": 17.0}, lim)[1]
    assert not reference.verdict({"sum_err_ulp": float("inf")}, lim)[1]
    assert not reference.verdict({"moved_mismatch": 1}, lim)[1]
    assert len(reference.lines(compared)) == 2
