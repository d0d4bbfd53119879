"""Finds what ``BENCHMARK.json`` names, by name, in files of its own.

* a cell is an entry of ``workloads``;
* its configuration is the file its ``configs`` entry gives;
* its traffic mix is ``bench/traffic/<traffic>.json``;
* the configuration's file names its architecture under ``"arch"``, and
  ``bench/archs/<arch>.py`` holds all that is specific to it: the layer
  equations of its weights, its plain reference and its FLOP count. The
  module provides

  - ``make(m, seed)``: the canonical weights, made on the device from the
    seed (``m`` is the configuration's ``"model"`` object);
  - ``program_tree(m, seed, expected)``: the same weights in the layout the
    serving model takes, checked against ``expected``, the model's own
    parameter tree of shapes and dtypes;
  - ``gaps(w, m, prompt, output, mode)``: for each served token, how far
    its logit in the plain fp32 reference over ``w = make(m, seed)`` lies
    below the reference's best; with ``mode="fp8"`` the gap of the token
    the fp8 control puts first;
  - ``flop_model(cfg)``: an object whose ``token_flops(lo, hi)`` gives the
    model FLOPs of computing positions [lo, hi) of one sequence, and
    ``logits_flops()`` those of one sampled token's unembedding row;
* a per-layer metric is read by ``bench/metrics/<metric>.py``, a module
  with a function ``read(run)`` that returns a number, or None where the
  run has nothing for it to read.

Adding a configuration, an architecture, a mix, a metric or a cell
therefore means adding files and entries, and editing none.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
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

    def _config_entry(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return json.loads(
            (self.root / self._config_entry(name)["file"]).read_text())

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

    def arch(self, config: str) -> ModuleType:
        """The module of the configuration's architecture, from this root's
        ``bench/archs/``."""
        arch = self.config(config).get("arch")
        if not arch:
            raise ValueError(
                f"configuration {config!r} "
                f"({self._config_entry(config)['file']}) names no "
                f'"arch": give the architecture whose module '
                f"bench/archs/<arch>.py makes its weights, reference and "
                f"FLOP count")
        path = self.bench_dir / "archs" / f"{arch}.py"
        if not path.is_file():
            raise FileNotFoundError(
                f'configuration {config!r} names "arch": {arch!r}, and '
                f"there is no {path}")
        return _load(path, "bench_arch_")

    def reader(self, metric: str) -> Callable:
        return _load(self.bench_dir / "metrics" / f"{metric}.py",
                     "bench_metric_").read

    def readers(self, cell: str) -> Dict[str, tuple]:
        return {m["name"]: (m["unit"], self.reader(m["name"]))
                for m in self.per_layer(cell)}


def _load(path: Path, prefix: str) -> ModuleType:
    name = prefix + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
