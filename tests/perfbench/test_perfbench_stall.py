"""A host that stands still for some seconds does not end a ``tpurun``
cell: ``run.py`` gives the launcher's failure detector ``--heartbeat
HEARTBEAT_S`` (four intervals of silence end the job: 2 s at the
launcher's default of 0.5 s, which one stall of the check's machines
passes). The launcher of a rehearsal is stopped for 3.5 s once its
monitor runs, as a stalled host would stop it, and the run still ends
with its result."""

import glob
import json
import os
import signal
import subprocess
import sys
import time

from perfbench import run

STALL_S = 3.5


def test_the_detector_gives_a_rank_more_silence_than_a_stall_lasts():
    assert 4 * run.HEARTBEAT_S >= 10 * STALL_S
    assert 4 * run.HEARTBEAT_S < run.LIMIT_S  # a dead rank still ends the run


def test_a_launcher_stopped_for_seconds_does_not_end_the_run(tmp_path):
    env = dict(os.environ, TMPDIR=str(tmp_path))
    p = subprocess.Popen(
        [sys.executable, os.path.join(run.HERE, "run.py"), "--workload",
         "osu_span2.small", "--seed", str(2**31 + 11), "--seconds", "6",
         "--trace", "0", "--rehearse-cpu"],
        env=env, text=True, stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    try:
        # tpurun writes its contact file once its heartbeat monitor runs
        contact, deadline = [], time.time() + 120
        while not contact and p.poll() is None and time.time() < deadline:
            time.sleep(0.1)
            contact = glob.glob(str(tmp_path / "ompitpu-sessions-*" / "*.json"))
        assert contact, "the launcher never wrote its contact file"
        launcher = int(os.path.basename(contact[0])[:-len(".json")])
        os.kill(launcher, signal.SIGSTOP)
        time.sleep(STALL_S)
        os.kill(launcher, signal.SIGCONT)
        out, err = p.communicate(timeout=300)
    finally:
        if p.poll() is None:
            p.kill()
            p.communicate()
    assert p.returncode == 0, err[-3000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
