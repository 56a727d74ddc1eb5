"""Finds every piece of the benchmark by its name, under one directory.

    <bench>/cells/<cell>.json          config + traffic + BER + engine geometry
    <bench>/configs/<config>.json      published sizes, what was cut, the
                                       deployment, the reference to compare with
    <bench>/traffic/<mix>.json         lengths, arrivals, lead-in, rate
    <bench>/metrics/<metric>.py        one reader per metric
    <bench>/references/<name>.py       a plain reference implementation
    <bench>/../BENCHMARK.json          which cells and metrics exist

A later PR adds a cell, a mix, a configuration or a metric by adding files
and entries only; nothing here names any of them.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from types import ModuleType
from typing import Any, Dict, List

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]


class Registry:
    def __init__(self, bench_dir: pathlib.Path = BENCH_DIR):
        self.dir = pathlib.Path(bench_dir)
        self.spec_path = self.dir.parent / "BENCHMARK.json"
        self._modules: Dict[pathlib.Path, ModuleType] = {}

    # ------------------------------------------------------------------ data
    def _json(self, kind: str, name: str) -> Dict[str, Any]:
        path = self.dir / kind / f"{name}.json"
        if not path.is_file():
            raise FileNotFoundError(f"no {name!r} under {self.dir / kind}")
        return json.loads(path.read_text())

    def spec(self) -> Dict[str, Any]:
        return json.loads(self.spec_path.read_text())

    def workload(self, name: str) -> Dict[str, Any]:
        """The BENCHMARK.json entry of cell ``name``."""
        for w in self.spec()["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in {self.spec_path}")

    def cell(self, name: str) -> Dict[str, Any]:
        return self._json("cells", name)

    def config(self, name: str) -> Dict[str, Any]:
        return self._json("configs", name)

    def traffic(self, name: str) -> Dict[str, Any]:
        return self._json("traffic", name)

    def metrics_for(self, cell: str, kind: str) -> List[Dict[str, Any]]:
        """The ``kind`` ("end_to_end" | "per_layer") metrics cell ``cell``
        reports: those without a ``workloads`` key, and those naming it."""
        return [
            m for m in self.spec()[kind]
            if "workloads" not in m or cell in m["workloads"]
        ]

    # ----------------------------------------------------------------- code
    def _module(self, path: pathlib.Path) -> ModuleType:
        if path not in self._modules:
            if not path.is_file():
                raise FileNotFoundError(path)
            mod_name = "bench_" + path.parent.name + "_" + "".join(
                c if c.isalnum() else "_" for c in path.stem
            )
            spec = importlib.util.spec_from_file_location(mod_name, path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._modules[path] = mod
        return self._modules[path]

    def metric(self, name: str) -> ModuleType:
        """The reader of metric ``name``: a module with ``read(run)``."""
        return self._module(self.dir / "metrics" / f"{name}.py")

    def reference(self, name: str) -> ModuleType:
        return self._module(self.dir / "references" / f"{name}.py")
