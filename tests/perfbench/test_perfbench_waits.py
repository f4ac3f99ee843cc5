"""The metrics over what rank 0 waits for: the home's turn of a one-sided
batch (five timers the origin fills from the stamps on the reply), for
``osu_rma.stream`` and ``osu_shmem.rate``, and the folds, the pad and the
hand-out of arrivals inside ``ompi.nbc.wait`` (three spans). Data files
and entries alone: each names a reader that is there and a counter or span
the library has. Traced CPU rehearsals of ``osu_rma.stream``,
``osu_shmem.rate`` and ``osu_span2.large`` report them, and the idle time
inside a folding allreduce is credited to the three spans by name.

Run as a program this file is the rehearsals' worker: the benchmark's own,
with its trace written under a directory of this test's — the traced
rehearsals of ``test_perfbench_rehearse_tpurun.py`` write the same cells'
traces under the checkout, maybe at the same moment in another process."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
OUT = "PERFBENCH_WAITS_OUT"

if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    from perfbench import worker

    worker.ROOT = os.environ[OUT]
    sys.exit(worker.main(sys.argv[1:]))

import re

import pytest

import perfbench_rehearsal as rh
from perfbench import manifest, trace

HOME = ("turn", "recv", "program", "out", "back")
SPANS = {"fold": "ompi.hier.fold", "pad": "ompi.hier.pad",
         "arrivals": "ompi.plan.arrivals"}
ENTERED = {**{f"home_{k}_ms.rma_large": ["osu_rma.stream"] for k in HOME},
           **{f"{k}_ms.span_large": ["osu_span2.large", "osu_span4.large"]
              for k in SPANS}}
# the same five for osu_shmem.rate, entered after the eight
SHMEM_HOME = {f"home_{k}_us.shm_small": ["osu_shmem.rate"] for k in HOME}


def spec_of(name):
    return manifest.load_json(os.path.join(manifest.HERE, "metrics",
                                           name + ".json"))


@pytest.mark.parametrize("name", sorted(ENTERED) + list(SHMEM_HOME))
def test_a_metric_names_a_reader_and_a_counter_or_a_span_that_exist(name):
    from ompi_release_tpu.mca import pvar
    from ompi_release_tpu.obs import spans
    from ompi_release_tpu.osc import wire_win  # noqa: F401  (its timers)

    spec = spec_of(name)
    assert spec["name"] == name and callable(manifest.reader(spec["reader"]))
    if name.startswith("home_"):
        assert spec["reader"] == "pvar_delta_per_call"
        (counter,) = spec["params"]["pvars"]
        assert counter == "osc_home_%s_seconds" % name.split("_")[1]
        assert pvar.PVARS.lookup(counter).pclass is pvar.PvarClass.TIMER
        assert spec["params"]["scale"] == (1e3 if "_ms." in name else 1e6)
    else:
        assert spec["reader"] == "trace_span_per_call"
        rx = re.compile(spec["params"]["pattern"])
        assert [n for n in spans.NAMES if rx.search(n)] == [
            SPANS[name.split("_")[0]]]
        assert spec["params"]["scale"] == 1e3


def test_eight_are_entered_and_five_wait_for_a_benchmark_pr():
    """All thirteen are entered, picked out by name in their order (an
    entry appended after them moves no pin): the eight, then the five that
    waited for a benchmark PR as files without entries; the test keeps
    the name it had while they waited."""
    layer = rh.MAN.doc["per_layer"]
    ours = [m for m in layer
            if m["name"] in ENTERED or m["name"] in SHMEM_HOME]
    assert [m["name"] for m in ours] == list(ENTERED) + list(SHMEM_HOME)
    for m in ours:
        entered = m["name"] in ENTERED
        assert m["workloads"] == (ENTERED if entered
                                  else SHMEM_HOME)[m["name"]]
        assert (m["unit"], m["better"], m["moves"]) == (
            ("ms", "lower", "span_algbw") if entered
            else ("us", "lower", "span_call_us"))
        assert (m["source"], m["layer"]) == (
            ("program_counter", "p2p, RMA, OSHMEM")
            if m["name"].startswith("home_")
            else ("program_span", "spanning round"))
        assert "NOT ENTERED" not in spec_of(m["name"]).get("what", "")


def rehearse_traced(capfd, monkeypatch, tmp_path, cell):
    monkeypatch.setenv(OUT, str(tmp_path))
    line, err = rh.rehearse(capfd, cell, 1, worker=__file__)
    rh.check_line(line, cell, 1, err)  # every entered metric, none else
    return {k: v["value"] for k, v in line["metrics"].items()}


def test_osu_rma_stream_reports_the_homes_turn(capfd, monkeypatch, tmp_path):
    m = rehearse_traced(capfd, monkeypatch, tmp_path, "osu_rma.stream")
    turn, recv, program, out, back = (m[f"home_{k}_ms.rma_large"]
                                      for k in HOME)
    assert 0 < recv and 0 < program and recv + program <= turn
    # the turn lies inside the origin's wait, the wait inside its flush
    assert turn <= m["osc_sync_ms.rma_large"]
    # both ranks of the rehearsal share a host, so a clock: these tick
    assert out > 0 and back > 0


def test_osu_shmem_rate_reports_the_homes_turn(capfd, monkeypatch, tmp_path):
    m = rehearse_traced(capfd, monkeypatch, tmp_path, "osu_shmem.rate")
    turn, recv, program, out, back = (m[f"home_{k}_us.shm_small"]
                                      for k in HOME)
    assert 0 < recv and 0 < program and recv + program <= turn
    assert turn <= m["osc_sync_us.shm_small"]
    # both PEs of the rehearsal share a host, so a clock: these tick
    assert out > 0 and back > 0


def test_osu_span2_large_reports_what_its_wait_holds(capfd, monkeypatch,
                                                     tmp_path):
    cell = "osu_span2.large"
    m = rehearse_traced(capfd, monkeypatch, tmp_path, cell)
    fold, pad, arrivals = (m[f"{k}_ms.span_large"] for k in SPANS)
    assert fold > 0 and pad > 0 and arrivals > 0
    assert fold + pad + arrivals <= m["xchg_ms.span_large"]  # toy sizes
    # an idle moment inside one of the three goes by its name, not by
    # ``ompi.nbc.wait``'s (at toy sizes none is among the ten largest
    # gaps the line keeps, so the pieces are read from the trace itself)
    _, spans_, _ = trace.load(trace.newest_xplane(os.path.join(
        str(tmp_path), "perfbench_out", "trace", cell)), rehearsal=True)
    owned = {name for _, _, name in trace.owners(spans_)}
    for span in SPANS.values():
        assert any(n.startswith(trace.CALL + "allreduce:")
                   and n.endswith(":" + span) for n in owned), span
    assert not any(n.startswith(trace.CALL + "bcast:")
                   and n.endswith(":" + SPANS["fold"]) for n in owned)
