"""Finding a cell's pieces by the names ``BENCHMARK.json`` gives them.

* a configuration: the ``file`` its entry names;
* a traffic mix: ``<paths[0]>/traffic/<traffic>.json``;
* a metric: ``<paths[0]>/metrics/<name>.py``, whose ``read(record)``
  returns the metric's value, or ``None`` where the run has nothing to read.

A later cell, configuration, traffic mix or metric is a new file and a new
entry; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Dict, List

REPO = Path(__file__).resolve().parent.parent


class Spec:
    def __init__(self, root: Path = REPO):
        self.root = Path(root)
        self.data = json.loads((self.root / "BENCHMARK.json").read_text())
        self.home = self.root / self.data["paths"][0]

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in self.data['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads((self.home / "traffic" / f"{name}.json").read_text())

    def metrics(self, cell: str, traced: bool) -> List[dict]:
        """The metrics a run of ``cell`` reports: end-to-end ones untraced,
        per-layer ones traced; each only where its ``workloads`` (if any)
        name the cell."""
        group = self.data["per_layer" if traced else "end_to_end"]
        return [m for m in group if cell in m.get("workloads", [cell])]

    def reader(self, name: str):
        path = self.home / "metrics" / f"{name}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{name.replace('.', '_')}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read

    def read_metrics(self, cell: str, traced: bool, record: Dict) -> Dict:
        out = {}
        for m in self.metrics(cell, traced):
            value = self.reader(m["name"])(record)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out
