"""Finds every piece of the benchmark by the name ``BENCHMARK.json`` gives it.

- configuration ``<c>``:   ``bench/configs/<c>.json``
- traffic mix ``<t>``:     ``bench/traffic/<t>.json``
- per-layer metric ``<m>``: ``bench/metrics/<m>.py``, a module with
  ``read(run) -> float | None``
- limits of cell ``<w>``:  ``bench/limits/<w>.json``

A new cell, mix or metric is new files plus new entries in
``BENCHMARK.json``; nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def _load_json(path: pathlib.Path) -> Dict:
    with open(path) as fh:
        return json.load(fh)


class Benchmark:
    def __init__(self, root: pathlib.Path = ROOT):
        self.root = pathlib.Path(root)
        self.spec = _load_json(self.root / "BENCHMARK.json")
        self._workloads = {w["name"]: w for w in self.spec["workloads"]}
        self._configs = {c["name"]: c for c in self.spec["configs"]}

    @property
    def workload_names(self) -> List[str]:
        return list(self._workloads)

    def workload(self, name: str) -> Dict:
        if name not in self._workloads:
            raise KeyError(f"unknown workload {name!r}; known: {sorted(self._workloads)}")
        return self._workloads[name]

    def config(self, name: str) -> Dict:
        """The configuration file's contents, as run."""
        return _load_json(self.root / self._configs[name]["file"])

    def traffic(self, name: str) -> Dict:
        return _load_json(BENCH_DIR / "traffic" / f"{name}.json")

    def limits(self, workload: str) -> Dict:
        return _load_json(BENCH_DIR / "limits" / f"{workload}.json")

    def end_to_end(self, workload: str) -> List[Dict]:
        return [m for m in self.spec["end_to_end"] if _applies(m, workload)]

    def per_layer(self, workload: str) -> List[Dict]:
        return [m for m in self.spec["per_layer"] if _applies(m, workload)]

    def reader(self, metric: str):
        """The ``read`` function of a per-layer metric's module."""
        path = BENCH_DIR / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(f"bench.metrics.{metric}", path)
        if spec is None or not path.exists():
            raise KeyError(f"no reader for metric {metric!r} at {path}")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def _applies(metric: Dict, workload: str) -> bool:
    cells: Optional[List[str]] = metric.get("workloads")
    return cells is None or workload in cells
