"""CPU rehearsals of the one-process (driver) cells at toy sizes: the last
line has exactly the contract's keys, traced and untraced; a run with the
timed path broken underneath comes out not correct; alone in a directory
the benchmark prints no result."""

import os
import shutil
import subprocess
import sys

import pytest

import perfbench_rehearsal as rh
from perfbench import manifest

CELLS = rh.cells("driver")
# the cell the control and the planted faults are written against: it
# makes an allreduce and a bcast
FAULTS_ON = "osu_ici4.large"


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


def test_alone_in_a_directory_it_exits_non_zero_and_prints_no_result(tmp_path):
    shutil.copytree(manifest.HERE, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", FAULTS_ON,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=60)
    assert p.returncode != 0 and p.stdout.strip() == ""
