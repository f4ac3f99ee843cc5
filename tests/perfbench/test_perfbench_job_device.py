"""The job's ``device``: the chips of every rank that is not a declared
host rank, together, and the fullest of them — on hand-made rank reports,
and the two cells of PR 27 with the timed path broken underneath:
``osu_span4.large`` (four one-chip ranks) on every rank and on the last
rank alone, ``osu_span2.moved_large`` (data movement only) with an answer
altered and with the exchange left out."""

import os

import pytest

import perfbench_rehearsal as rh
from perfbench import reference, run

SPAN4, MOVED = "osu_span4.large", "osu_span2.moved_large"
MOVES = os.path.join(rh.HERE, "faulty_moves_worker.py")
SOUND = {"sum_err_ulp": 0.5, "moved_mismatch": 0, "misplaced": 0,
         "missing": 0, "window_compiles": 0}


def report(rank, platform, peak, host_rank=False, **more):
    device = {"platform": platform, "count": 1, "memory_peak_bytes": peak,
              "kind": "TPU v5 lite" if platform == "tpu" else "cpu"}
    return dict({"rank": rank, "host_rank": host_rank, "device": device,
                 "attempted": 9, "failed": 0, "numbers": dict(SOUND)}, **more)


def test_a_chip_rank_with_a_host_rank_is_one_chip_and_rank_0s_peak():
    ranks = [report(0, "tpu", 864, metrics={"m": 1}),
             report(1, "cpu", 0, host_rank=True)]
    result, _ = run.merge(ranks, 0, reference)
    assert result["device"] == ranks[0]["device"]  # as before this cell class
    assert list(result["device"]) == list(ranks[0]["device"])
    assert result["correct"] is True and result["metrics"] == {"m": 1}


def test_four_chip_ranks_are_four_chips_and_the_fullest_of_them():
    ranks = [report(r, "tpu", peak, metrics={"m": r})
             for r, peak in enumerate([700, 900, 1300, 800])]
    ranks[0]["device"].update(window_s=2.0, busy_s=0.5)  # rank 0 is traced
    ranks[0]["breakdown"] = {"device_ops": [], "idle_gaps": []}
    result, _ = run.merge(ranks, 1, reference)
    assert result["device"] == {
        "platform": "tpu", "count": 4, "memory_peak_bytes": 1300,
        "kind": "TPU v5 lite", "window_s": 2.0, "busy_s": 0.5}
    assert result["metrics"] == {"m": 0} and "breakdown" in result
    assert ranks[0]["device"]["count"] == 1  # the rank's report is not edited


def test_one_process_that_holds_every_chip_reads_as_it_reports():
    one = report(0, "tpu", 3730, metrics={})
    one["device"]["count"] = 4
    assert run.merge([one], 0, reference)[0]["device"] == one["device"]


@pytest.mark.parametrize("on_cpu", [0, 3])
def test_a_chip_rank_on_the_cpu_fails_the_run_outside_a_rehearsal(on_cpu):
    ranks = [report(r, "cpu" if r == on_cpu else "tpu", 1, metrics={})
             for r in range(4)]
    with pytest.raises(SystemExit) as e:
        run.merge(ranks, 0, reference)
    assert f"[{on_cpu}]" in str(e.value.code) and "TPU" in str(e.value.code)
    assert run.merge(ranks, 0, reference, rehearsal=True)[0]["device"]["count"] == 4


def test_every_ranks_comparison_counts_in_the_merged_verdict():
    ranks = [report(r, "tpu", 1, metrics={}) for r in range(4)]
    ranks[3]["numbers"]["moved_mismatch"] = 1
    ranks[3]["failed"] = 1
    result, compared = run.merge(ranks, 0, reference)
    assert result["correct"] is False and result["failed"] == 1
    assert compared["moved_mismatch"] == {"value": 1, "limit": 0}


def test_no_exchange_on_every_rank_is_not_correct(capfd, monkeypatch):
    rh.check_fault(capfd, monkeypatch, SPAN4, "no_exchange", "sum_err_ulp")


def test_an_answer_altered_on_the_last_rank_alone_is_not_correct(
        capfd, monkeypatch):
    """Ranks 0 to 2 are sound: only every rank's own comparison, merged,
    can see what rank 3 was handed."""
    line = rh.check_fault(capfd, monkeypatch, SPAN4, "altered_last_rank",
                          "moved_mismatch", worker=MOVES)
    assert line["compared"]["moved_mismatch"]["value"] == 3  # 1, 16, 64 "MiB"
    assert line["compared"]["sum_err_ulp"]["value"] <= \
        line["compared"]["sum_err_ulp"]["limit"]
    assert line["device"]["count"] == 4


@pytest.mark.parametrize("fault", ["altered_last_rank", "bcast_kept"])
def test_the_moved_cell_with_its_path_broken_is_not_correct(
        capfd, monkeypatch, fault):
    """``osu_span2.moved_large`` has no reduction: an altered answer and an
    exchange left out both show in ``moved_mismatch``."""
    line = rh.check_fault(capfd, monkeypatch, MOVED, fault, "moved_mismatch",
                          worker=MOVES)
    assert "sum_err_ulp" not in line["compared"]
    assert line["device"]["count"] == 1
