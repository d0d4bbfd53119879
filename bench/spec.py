"""Finds what ``BENCHMARK.json`` names, by name, in files of its own.

* a cell is an entry of ``workloads``;
* its configuration is the file its ``configs`` entry gives;
* its traffic mix is ``bench/traffic/<traffic>.json``;
* a per-layer metric is read by ``bench/metrics/<metric>.py``, a module
  with a function ``read(run)`` that returns a number, or None where the
  run has nothing for it to read.

Adding a configuration, a mix, a metric or a cell therefore means adding
files and entries, and editing none.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List


class Bench:
    def __init__(self, root: Path):
        self.root = Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self.bench_dir = self.root / "bench"

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def mix(self, name: str) -> dict:
        from .traffic import load_mix
        return load_mix(self.bench_dir / "traffic" / f"{name}.json")

    @staticmethod
    def _applies(metric: dict, cell: str, e2e_names: List[str]) -> bool:
        if "workloads" in metric:
            return cell in metric["workloads"]
        return metric.get("moves", metric["name"]) in e2e_names

    def end_to_end(self, cell: str) -> List[dict]:
        return [m for m in self.spec["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]]

    def per_layer(self, cell: str) -> List[dict]:
        e2e = [m["name"] for m in self.end_to_end(cell)]
        return [m for m in self.spec["per_layer"]
                if self._applies(m, cell, e2e)]

    def reader(self, metric: str) -> Callable:
        path = self.bench_dir / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_').replace('-', '_')}",
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    def readers(self, cell: str) -> Dict[str, tuple]:
        return {m["name"]: (m["unit"], self.reader(m["name"]))
                for m in self.per_layer(cell)}
