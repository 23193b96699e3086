"""Finding a cell's pieces by the names ``BENCHMARK.json`` gives them.

* a configuration: the ``file`` its entry names;
* a traffic mix: ``<paths[0]>/traffic/<traffic>.json``;
* a metric: ``<paths[0]>/metrics/<name>.py``, whose ``read(record)``
  returns the metric's value, or ``None`` where the run has nothing to read;
* an architecture: ``<paths[0]>/arch/<arch>.py``, named by the
  configuration's ``model.arch``: everything a run needs that depends on the
  model and its data (corpus, input pipeline, state, step, work counts, the
  input's check and the plain reference; see ``arch/alexnet.py``).

A later cell, configuration, traffic mix, metric or architecture is a new
file and a new entry; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import sys
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

    def arch(self, name: str):
        """The module of architecture ``name``, from its own file."""
        path = self.home / "arch" / f"{name}.py"
        if not path.is_file():
            raise FileNotFoundError(f"no module for arch {name!r}: looked "
                                    f"for {path}")
        spec = importlib.util.spec_from_file_location(
            f"bench_arch_{name.replace('.', '_')}", path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = module     # dataclasses look themselves up
        spec.loader.exec_module(module)
        return module

    def read_metrics(self, cell: str, traced: bool, record: Dict) -> Dict:
        out = {}
        for m in self.metrics(cell, traced):
            value = self.reader(m["name"])(record)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out
