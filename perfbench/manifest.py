"""BENCHMARK.json and the data files it names. No jax here.

Everything that belongs to one configuration, one traffic mix, one
operation or one metric sits in a file of its own and is found by the
name in BENCHMARK.json, so a later PR adds files and entries and edits
nothing that is there:

    configs/<config>.json   a deployment: launcher, ranks, operations, dtypes
    traffic/<traffic>.json  a mix: sizes per rank, slice to trace
    ops/<operation>.py      how to call it, its reference, its least bytes,
                            and optionally its own inputs (make)
    metrics/<metric>.json   a reader from readers/ and its parameters
    readers/<reader>.py     read(facts, **parameters) -> number or None
"""

import importlib
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load_json(path):
    with open(path) as f:
        return json.load(f)


class Manifest:
    """BENCHMARK.json at ``root`` with the files under ``bench`` (this
    directory unless a test points at a copy)."""

    def __init__(self, root=ROOT, bench=HERE):
        self.root, self.bench = root, bench
        self.doc = load_json(os.path.join(root, "BENCHMARK.json"))
        self.cells = {w["name"]: w for w in self.doc["workloads"]}
        self.configs = {c["name"]: c for c in self.doc["configs"]}

    def cell(self, name, toy=False):
        """One cell with its configuration and traffic files read. ``toy``
        (a CPU rehearsal) divides the sizes so that the largest is
        64 KiB."""
        if name not in self.cells:
            raise SystemExit(f"perfbench: no workload {name!r} in "
                             f"BENCHMARK.json (has {sorted(self.cells)})")
        w = self.cells[name]
        cfg = load_json(os.path.join(self.root,
                                     self.configs[w["config"]]["file"]))
        traffic = load_json(os.path.join(self.bench, "traffic",
                                         w["traffic"] + ".json"))
        ops = traffic.get("operations") or cfg["operations"]
        if toy:
            cut = max(1, max(traffic["sizes_bytes"]) >> 16)
            traffic["sizes_bytes"] = [max(8, s // cut)
                                      for s in traffic["sizes_bytes"]]
        return {"name": name, "chips": w["chips"], "config": cfg,
                "traffic": traffic, "operations": ops}

    def metrics_of(self, cell, group):
        """The metrics of ``group`` (``end_to_end`` or ``per_layer``)
        that ``cell`` reports, each with its reader file read: a metric
        with no ``workloads`` key is every cell's."""
        out = []
        for m in self.doc[group]:
            if "workloads" in m and cell not in m["workloads"]:
                continue
            spec = load_json(os.path.join(self.bench, "metrics",
                                          m["name"] + ".json"))
            out.append(dict(m, reader=spec["reader"],
                            params=spec.get("params", {})))
        return out

    def pvars_of(self, cell):
        """Every pvar a metric of this cell reads, so the worker
        snapshots those and no others."""
        names = set()
        for group in ("end_to_end", "per_layer"):
            for m in self.metrics_of(cell, group):
                names.update(m["params"].get("pvars", ()))
        return sorted(names)


def operation(name):
    return importlib.import_module(f"perfbench.ops.{name}")


def reader(name):
    return importlib.import_module(f"perfbench.readers.{name}").read


def read_metrics(specs, facts):
    """{name: {"value", "unit"}} for the metrics whose reader found
    something to read; one that returns None is left out of the line."""
    out = {}
    for m in specs:
        value = reader(m["reader"])(facts, **m["params"])
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def peaks(kind):
    """The published peaks of one chip of ``kind``; a device that is
    not in the table is an error, never a default."""
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table:
        raise SystemExit(f"perfbench: no published peaks for device_kind "
                         f"{kind!r} in peaks.json (has {sorted(table)})")
    return table[kind]
