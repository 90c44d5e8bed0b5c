"""Finds everything by name: cells and metrics in ``BENCHMARK.json``, and
one file each for a configuration (``bench/configs/<config>.json``), a
traffic mix (``bench/traffic/<traffic>.json``) and a per-layer metric's
reader (``bench/metrics/<metric>.py``).  Adding a cell, a mix or a metric
adds files and entries; no code here changes."""

from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Callable, Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parent


class Spec:
    """``BENCHMARK.json`` plus the bench directory its files live in."""

    def __init__(self, spec_path: pathlib.Path,
                 bench_dir: Optional[pathlib.Path] = None):
        self.path = pathlib.Path(spec_path)
        self.data = json.loads(self.path.read_text())
        self.dir = pathlib.Path(bench_dir) if bench_dir else BENCH_DIR

    def cell(self, name: str) -> dict:
        for c in self.data["workloads"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no workload {name!r} in {self.path}; known: "
                       f"{[c['name'] for c in self.data['workloads']]}")

    def config(self, name: str) -> dict:
        return json.loads((self.dir / "configs" / f"{name}.json").read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.dir / "traffic" / f"{name}.json").read_text())

    def metrics(self, cell: str, kind: str) -> List[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [m for m in self.data[kind]
                if "workloads" not in m or cell in m["workloads"]]

    def reader(self, metric: str) -> Callable[[dict], Optional[float]]:
        """``read(ctx)`` of ``bench/metrics/<metric>.py``."""
        path = self.dir / "metrics" / f"{metric}.py"
        mod_spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read


def read_per_layer(spec: Spec, cell: str, ctx: dict) -> Dict[str, dict]:
    """Every per-layer metric of the cell that finds something to read."""
    out = {}
    for m in spec.metrics(cell, "per_layer"):
        v = spec.reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out
