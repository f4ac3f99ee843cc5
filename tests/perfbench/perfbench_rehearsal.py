"""What the rehearsal tests of both launchers share: run one cell through
run.py as a CPU rehearsal at toy sizes (the parent never imports jax;
the workers are child processes) and hold its last line to the
contract."""

import json
import os

from perfbench import manifest, run
from perfbench.trace import CALL

HERE = os.path.dirname(os.path.abspath(__file__))
FAULTY = os.path.join(HERE, "faulty_worker.py")
MAN = manifest.Manifest()
NEEDED = ["correct", "attempted", "failed", "metrics", "device"]


def cells(launcher):
    return sorted(c for c in MAN.cells
                  if MAN.cell(c)["config"]["launcher"] == launcher)


def rehearse(capfd, cell, trace, seed=2**31 + 7, worker=run.WORKER, more=()):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds", "0.3",
                   "--trace", str(trace), "--rehearse-cpu", *more],
                  worker=worker)
    out, err = capfd.readouterr()
    assert rc == 0, err[-3000:]
    return json.loads(out.strip().splitlines()[-1]), err


def check_line(line, cell, trace, err):
    assert list(line)[:5] == NEEDED and list(line)[-1] == "compared"
    assert line["rehearsal"].startswith("cpu")  # labelled, never a chip result
    group = "per_layer" if trace else "end_to_end"
    want = {m["name"]: m["unit"] for m in MAN.metrics_of(cell, group)}
    assert {k: v["unit"] for k, v in line["metrics"].items()} == want
    for v in line["metrics"].values():
        assert isinstance(v["value"], float) and v["value"] == v["value"]
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    assert dev["count"] == MAN.cell(cell)["chips"]
    if trace:
        assert 0 < dev["busy_s"] <= dev["window_s"]
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert all(len(v) <= 10 for v in line["breakdown"].values())
        # idle gaps go by the call, and inside the library by its span
        gaps = [name for name, _ in line["breakdown"]["idle_gaps"]]
        assert all(g == "between calls" or g.startswith(CALL)
                   for g in gaps)
        assert any(":ompi." in g for g in gaps), gaps
    else:
        assert "busy_s" not in dev and "breakdown" not in line
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # each number beside its limit: the last lines of standard error
    last = err.strip().splitlines()[-len(line["compared"]):]
    for ln, (name, c) in zip(last, line["compared"].items()):
        assert set(c) == {"value", "limit"}
        assert f"compared {name} = " in ln and "limit" in ln


def check_fault(capfd, monkeypatch, cell, fault, number, worker=FAULTY):
    monkeypatch.setenv("PERFBENCH_FAULT", fault)
    line, _ = rehearse(capfd, cell, 0, worker=worker)
    assert line["correct"] is False and line["failed"] >= 1
    c = line["compared"][number]
    assert c["value"] > c["limit"]
    return line


def check_control(capfd, cell):
    """The reference in the next lower precision, put in the program's
    place, goes through the harness's own verdict and comes out not
    correct, on the run's seed and on each further one; the program's own
    results, on the same seeds, come out correct."""
    line, err = rehearse(capfd, cell, 0,
                         more=["--control", "--extra-seeds", "5,2147483655"])
    assert line["correct"] is True and line["control_correct"] is False
    assert "perfbench control_correct = false" in err
    assert any(c["value"] > c["limit"] for c in line["control"].values())
    assert [e["seed"] for e in line["extra_seeds"]] == [5, 2147483655]
    for e in line["extra_seeds"]:
        assert e["correct"] is True and e["control_correct"] is False
    assert list(line)[-1] == "compared"
