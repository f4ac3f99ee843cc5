"""CPU rehearsals of the tpurun cells (a chip rank with a host rank; both
on the CPU backend here) at toy sizes: the last line has exactly the
contract's keys, traced and untraced, and a run with the timed path broken
underneath, on both ranks, comes out not correct."""

import pytest

import perfbench_rehearsal as rh

CELLS = rh.cells("tpurun")


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_last_line_of_a_rehearsal(capfd, cell, trace):
    line, err = rh.rehearse(capfd, cell, trace)
    rh.check_line(line, cell, trace, err)


def test_the_lower_precision_control_is_not_correct(capfd):
    rh.check_control(capfd, CELLS[0])


@pytest.mark.parametrize("fault, number", [("no_exchange", "sum_err_ulp"),
                                           ("altered", "moved_mismatch")])
def test_a_broken_timed_path_is_not_correct(capfd, monkeypatch, fault, number):
    rh.check_fault(capfd, monkeypatch, CELLS[0], fault, number)
