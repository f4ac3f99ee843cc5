"""BENCHMARK.json and the benchmark's data files: legal names, every file
found by name, and a cell, a configuration and a metric added as files
plus entries with no edit to what is there."""

import json
import os
import shutil

import pytest

from perfbench import manifest, traffic

MAN = manifest.Manifest()
DOC = MAN.doc
ALL_METRICS = DOC["end_to_end"] + DOC["per_layer"]


def test_names_and_units_are_legal():
    names = ([w["name"] for w in DOC["workloads"]]
             + [c["name"] for c in DOC["configs"]]
             + [m["name"] for m in ALL_METRICS]
             + [w["traffic"] for w in DOC["workloads"]]
             + [k for c in DOC["configs"] for k in c["reduced"]])
    for name in names:
        assert manifest.NAME.match(name), name
    for m in ALL_METRICS:
        assert manifest.UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert len({m["name"] for m in ALL_METRICS}) == len(ALL_METRICS)
    for text in ([w["why"] for w in DOC["workloads"]]
                 + [c["source"] for c in DOC["configs"]]
                 + [c["why"] for c in DOC["configs"]] + DOC["command"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_shape_of_the_manifest():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= DOC["run_seconds"] <= 51
    cells = DOC["workloads"]
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    assert {c["name"] for c in DOC["configs"]} == {w["config"] for w in cells}
    # every prefix of the order is a legal benchmark: at most half of its
    # cells (rounded down, one always allowed) take four chips
    for k in range(1, len(cells) + 1):
        four = sum(w["chips"] == 4 for w in cells[:k])
        assert four <= max(1, k // 2), cells[k - 1]["name"]
    for m in DOC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in DOC["end_to_end"])


@pytest.mark.parametrize("cell", sorted(MAN.cells))
def test_every_file_of_a_cell_is_found_by_name(cell):
    c = MAN.cell(cell)
    assert c["config"]["name"] == MAN.cells[cell]["config"]
    assert c["chips"] in (1, 4)
    for op, size in traffic.round_of(c):
        mod = manifest.operation(op)
        assert mod.KIND in ("reduce", "move") and size > 0
    e2e = MAN.metrics_of(cell, "end_to_end")
    layer = MAN.metrics_of(cell, "per_layer")
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2 and layer
    for m in e2e + layer:
        assert callable(manifest.reader(m["reader"]))
    # a per-layer metric moves an end-to-end metric that this cell reports
    assert {m["moves"] for m in layer} <= {m["name"] for m in e2e}
    for p in MAN.pvars_of(cell):
        assert manifest.NAME.match(p)


def test_configuration_files_state_what_the_manifest_says():
    for c in DOC["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in DOC["paths"]))
        body = manifest.load_json(os.path.join(manifest.ROOT, c["file"]))
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
        assert body["ranks"] >= 2 and body["guarantees"]


def test_a_cell_a_configuration_and_a_metric_are_added_as_files(tmp_path):
    bench = tmp_path / "perfbench"
    shutil.copytree(manifest.HERE, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    doc = json.loads(json.dumps(DOC))
    # a configuration: a file of its own
    cfg = manifest.load_json(os.path.join(manifest.HERE, "configs",
                                          "osu_ici4.json"))
    cfg.update(name="osu_new", operations=["alltoall"])
    (bench / "configs" / "osu_new.json").write_text(json.dumps(cfg))
    doc["configs"].append({"name": "osu_new", "source": cfg["source"],
                           "file": "perfbench/configs/osu_new.json",
                           "reduced": [], "why": "a test"})
    # a traffic mix: a data file
    (bench / "traffic" / "one_size.json").write_text(json.dumps(
        {"name": "one_size", "sizes_bytes": [4096]}))
    doc["workloads"].append({"name": "osu_new.one", "config": "osu_new",
                             "traffic": "one_size", "chips": 1, "why": "x"})
    # a single-operation cell of a configuration that is there: data only
    (bench / "traffic" / "alltoall_one.json").write_text(json.dumps(
        {"name": "alltoall_one", "sizes_bytes": [4096, 8192],
         "operations": ["alltoall"]}))
    doc["workloads"].append({"name": "osu_ici4.alltoall_one",
                             "config": "osu_ici4", "traffic": "alltoall_one",
                             "chips": 4, "why": "x"})
    # a per-layer metric over a pvar no metric reads yet
    (bench / "metrics" / "new_frames.one.json").write_text(json.dumps(
        {"reader": "pvar_delta_per_call",
         "params": {"pvars": ["wire_native_frames"], "scale": 1}}))
    doc["end_to_end"][0]["workloads"].append("osu_new.one")
    doc["per_layer"].append({
        "name": "new_frames.one", "unit": "frames/call", "better": "lower",
        "source": "program_counter", "layer": "spanning round",
        "moves": doc["end_to_end"][0]["name"], "workloads": ["osu_new.one"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    man = manifest.Manifest(root=str(tmp_path), bench=str(bench))
    cell = man.cell("osu_new.one")
    assert traffic.round_of(cell) == [("alltoall", 4096)]
    assert traffic.round_of(man.cell("osu_ici4.alltoall_one")) == [
        ("alltoall", 4096), ("alltoall", 8192)]
    assert man.pvars_of("osu_new.one") == ["wire_native_frames"]
    facts = {"calls": 10, "pvars": {"wire_native_frames": 30}}
    got = manifest.read_metrics(man.metrics_of("osu_new.one", "per_layer"),
                                facts)
    assert got == {"new_frames.one": {"value": 3.0, "unit": "frames/call"}}
    # nothing that was there was edited
    assert all(p.read_bytes() == body for p, body in before.items())


def test_peaks_are_keyed_by_device_kind_and_an_unknown_kind_is_an_error():
    v5e = manifest.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["ici_bytes_per_s"] == 200e9
    assert "source" in v5e
    with pytest.raises(SystemExit):
        manifest.peaks("TPU v9 imaginary")
