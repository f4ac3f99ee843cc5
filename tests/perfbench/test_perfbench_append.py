"""A configuration that is not OSU's, appended as a later PR would append
it: its files and its entries after everything that is there, its
operation drawing its own skewed keys from the seed. No pin of
``tests/perfbench`` may break, the cell has to rehearse ``correct``, and
with the operation's ``make`` taken away (uniform keys) not correct."""

import json
import os
import shutil
import sys

import pytest

import perfbench_rehearsal as rh
import test_perfbench_manifest
import test_perfbench_pt2pt
import test_perfbench_rma
import test_perfbench_shmem
import test_perfbench_waits
import toy_keys_worker
from perfbench import manifest, traffic

CONFIG = "toy_keys2"
TRAFFIC = "toy_keys_4k_64k"
CELL = "toy_keys2.sort"
LAYER = {"device_busy_us.toy_keys": "device_busy_us.span_small",
         "device_idle_pct.toy_keys": "device_idle_pct.span_small"}
WORKER = toy_keys_worker.__file__
OP_MODULE = f"perfbench.ops.{toy_keys_worker.NAME}"


def appended(tmp_path):
    """A copy of BENCHMARK.json and perfbench/ with one configuration,
    one one-chip cell in osu_span2's 'D H' layout, its traffic file and
    two per-layer metrics appended; the cell joins ``span_call_us``."""
    bench = tmp_path / "perfbench"
    shutil.copytree(manifest.HERE, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    doc = json.loads(json.dumps(rh.MAN.doc))
    cfg = manifest.load_json(os.path.join(manifest.HERE, "configs",
                                          "osu_span2.json"))
    cfg.update(name=CONFIG, operations=[toy_keys_worker.NAME],
               dtype={"default": "int32"}, max_key=1 << 11, reduced=[],
               source="NAS Parallel Benchmarks IS: keys the mean of four "
                      "uniforms, ranked by a counting sort",
               guarantees={"ranking": "every key of a rank in its place"})
    (bench / "configs" / f"{CONFIG}.json").write_text(json.dumps(cfg))
    doc["configs"].append({"name": CONFIG, "source": cfg["source"],
                           "file": f"perfbench/configs/{CONFIG}.json",
                           "reduced": [], "why": "a test"})
    (bench / "traffic" / f"{TRAFFIC}.json").write_text(json.dumps(
        {"name": TRAFFIC, "sizes_bytes": [4096, 65536]}))
    doc["workloads"].append({"name": CELL, "config": CONFIG,
                             "traffic": TRAFFIC, "chips": 1,
                             "why": "a test"})
    by_name = {m["name"]: m for m in doc["per_layer"]}
    for name, like in LAYER.items():
        spec = manifest.load_json(os.path.join(manifest.HERE, "metrics",
                                               like + ".json"))
        (bench / "metrics" / f"{name}.json").write_text(
            json.dumps(dict(spec, name=name)))
        doc["per_layer"].append(dict(by_name[like], name=name,
                                     workloads=[CELL]))
    for m in doc["end_to_end"]:
        if m["name"] == "span_call_us":
            m["workloads"].append(CELL)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc, indent=1))
    return manifest.Manifest(root=str(tmp_path), bench=str(bench))


def test_an_appended_configuration_breaks_no_pin(capfd, monkeypatch,
                                                 tmp_path):
    man = appended(tmp_path)
    monkeypatch.setattr(rh, "MAN", man)
    monkeypatch.setenv("PERFBENCH_TOY_MAKE", "1")
    monkeypatch.setitem(sys.modules, OP_MODULE, toy_keys_worker)
    listed = test_perfbench_manifest
    monkeypatch.setattr(listed, "MAN", man)
    monkeypatch.setattr(listed, "DOC", man.doc)
    monkeypatch.setattr(listed, "ALL_METRICS",
                        man.doc["end_to_end"] + man.doc["per_layer"])
    # every pin that an appended entry could move
    test_perfbench_rma.test_the_cells_and_their_rounds()
    test_perfbench_pt2pt.test_the_cell_and_its_rounds()
    test_perfbench_shmem.test_the_cell_and_its_rounds()
    waits = test_perfbench_waits
    waits.test_eight_are_entered_and_five_wait_for_a_benchmark_pr()
    listed.test_shape_of_the_manifest()
    listed.test_names_and_units_are_legal()
    listed.test_every_file_of_a_cell_is_found_by_name(CELL)
    # the operation's keys have a key of their own
    cell = man.cell(CELL, toy=True)
    assert all(k[2:] == (toy_keys_worker.NAME,)
               for k in traffic.inputs_of(cell))

    # the cell as the harness runs it, pointed at the copy
    monkeypatch.setattr(manifest, "Manifest", lambda: man)
    monkeypatch.setenv("PERFBENCH_TOY_ROOT", str(tmp_path))
    line, err = rh.rehearse(capfd, CELL, 0, worker=WORKER)
    rh.check_line(line, CELL, 0, err)
    assert line["compared"]["moved_mismatch"]["value"] == 0

    # without ``make`` the keys are uniform bits: the counting sort over
    # [0, max_key) is then wrong, and the comparison sees it
    monkeypatch.setenv("PERFBENCH_TOY_MAKE", "0")
    line, _ = rh.rehearse(capfd, CELL, 0, worker=WORKER)
    assert line["correct"] is False and line["failed"] >= 1
    c = line["compared"]["moved_mismatch"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_made_keys_are_the_bell_that_the_operation_states(monkeypatch, seed):
    """``make`` inside the harness's one program: int32 keys in
    [0, max_key), piled up in the middle as the mean of four uniforms
    piles them, and the same keys from the same seed."""
    import numpy as np

    monkeypatch.setitem(sys.modules, OP_MODULE, toy_keys_worker)
    cfg = {"max_key": 1 << 11}
    keys = [("int32", 4096, toy_keys_worker.NAME)]
    (x,) = traffic.make_inputs(seed, keys, 2, cfg=cfg).values()
    x = np.asarray(x)
    assert x.dtype == np.int32 and x.shape == (2, 4096)
    assert 0 <= x.min() and x.max() < cfg["max_key"]
    half = cfg["max_key"] / 2
    middle = np.count_nonzero(abs(x - half) < half / 4)
    # a uniform key lands in the middle quarter a quarter of the time, the
    # mean of four uniforms about 60 % of the time
    assert 0.55 < middle / x.size < 0.65
    (again,) = traffic.make_inputs(seed, keys, 2, cfg=cfg).values()
    np.testing.assert_array_equal(np.asarray(again), x)
