"""``BENCHMARK.json`` and the files it names, found by name.

A cell names a configuration (``bench/configs/<name>.json``) and a traffic
mix (``bench/traffic/<name>.json``); every metric, end-to-end or per-layer,
is read by ``bench/metrics/<name>.py``, which defines ``read(run)`` and
returns a number, or None where the run holds nothing to read.  So a cell,
a traffic mix or a metric is added as files alone.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))


class Spec:
    def __init__(self, root: str):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.doc = json.load(f)
        self.bench = os.path.join(root, "bench")

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in self.doc['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return self._json(c["file"])
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return self._json(os.path.join("bench", "traffic", name + ".json"))

    def _json(self, rel: str) -> dict:
        with open(os.path.join(self.root, rel)) as f:
            return json.load(f)

    def metrics(self, cell: str, traced: bool) -> List[dict]:
        """The cell's end-to-end metrics (``traced`` False) or per-layer
        metrics (True): those whose ``workloads`` name the cell, or that
        have no ``workloads`` key."""
        key = "per_layer" if traced else "end_to_end"
        return [m for m in self.doc[key]
                if "workloads" not in m or cell in m["workloads"]]

    def reader(self, metric: str) -> Callable[[dict], Optional[float]]:
        path = os.path.join(self.bench, "metrics", metric + ".py")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def read_metrics(spec: Spec, cell: str, traced: bool,
                 run: dict) -> Dict[str, dict]:
    out = {}
    for m in spec.metrics(cell, traced):
        value = spec.reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
