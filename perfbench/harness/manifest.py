"""``BENCHMARK.json`` and the files its names lead to.

A cell is resolved by name alone: ``workloads[name]`` gives a
configuration and a traffic mix, whose files are
``<configs[config].file>`` and ``perfbench/traffic/<traffic>.json``; the
traffic names its driver, ``perfbench/drivers/<driver>.py``; a per-layer
metric ``m`` is described by ``perfbench/metrics/<m>.json``, which names
its reader, ``perfbench/readers/<reader>.py``; the cell's limits for
``correct`` are ``perfbench/limits/<cell>.json``. A later PR adds files
and entries and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path):
    with open(path) as fh:
        return json.load(fh)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Manifest:
    def __init__(self, root=ROOT):
        self.root = root
        self.bench_dir = os.path.join(root, "perfbench")
        self.doc = load_json(os.path.join(root, "BENCHMARK.json"))

    def cell(self, name):
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                       f"{[w['name'] for w in self.doc['workloads']]}")

    def config(self, cell):
        for c in self.doc["configs"]:
            if c["name"] == cell["config"]:
                return load_json(os.path.join(self.root, c["file"]))
        raise KeyError(f"no config {cell['config']!r} in BENCHMARK.json")

    def traffic(self, cell):
        return load_json(os.path.join(self.bench_dir, "traffic",
                                      cell["traffic"] + ".json"))

    def limits(self, cell):
        return load_json(os.path.join(self.bench_dir, "limits",
                                      cell["name"] + ".json"))

    def driver(self, traffic):
        kind = traffic["driver"]
        return load_module(os.path.join(self.bench_dir, "drivers",
                                        kind + ".py"),
                           "perfbench_driver_" + kind)

    def metrics(self, cell, group):
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [m for m in self.doc[group]
                if "workloads" not in m or cell["name"] in m["workloads"]]

    def read_metric(self, metric, obs):
        """A per-layer metric's value from a run's observations, or
        ``None`` where its reader finds nothing to read."""
        desc = load_json(os.path.join(self.bench_dir, "metrics",
                                      metric["name"] + ".json"))
        reader = load_module(os.path.join(self.bench_dir, "readers",
                                          desc["reader"] + ".py"),
                             "perfbench_reader_" + desc["reader"])
        return reader.read(obs, desc.get("args", {}))
