"""CPU rehearsals of the tpurun cells (a chip rank with a host rank; both
on the CPU backend here) at toy sizes: the last line has exactly the
contract's keys, traced and untraced, and a run with the timed path broken
underneath, on both ranks, comes out not correct."""

import pytest

import perfbench_rehearsal as rh

CELLS = rh.cells("tpurun")
# the cell the control and the planted faults are written against: it
# makes an allreduce and a bcast on both ranks
FAULTS_ON = "osu_span2.large"


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_last_line_of_a_rehearsal(capfd, cell, trace):
    line, err = rh.rehearse(capfd, cell, trace)
    rh.check_line(line, cell, trace, err)


def test_the_faults_cell_is_one_of_the_launchers_cells():
    assert FAULTS_ON in CELLS
    ops = rh.MAN.cell(FAULTS_ON)["operations"]
    assert "allreduce" in ops and "bcast" in ops


def test_the_lower_precision_control_is_not_correct(capfd):
    rh.check_control(capfd, FAULTS_ON)


@pytest.mark.parametrize("fault, number", [("no_exchange", "sum_err_ulp"),
                                           ("altered", "moved_mismatch")])
def test_a_broken_timed_path_is_not_correct(capfd, monkeypatch, fault, number):
    rh.check_fault(capfd, monkeypatch, FAULTS_ON, fault, number)
