"""Finds everything by name: cells and metrics in ``BENCHMARK.json``, and
one file each for a configuration (``bench/configs/<config>.json``), a
traffic mix (``bench/traffic/<traffic>.json``) and a per-layer metric's
reader (``bench/metrics/<metric>.py``), and one directory for a model
family (``bench/families/<family>/``, named by a configuration's
``family`` key).  Adding a cell, a mix, a metric or a family adds files
and entries; no code here changes.

A family is a package whose ``__init__.py`` gives the three entry points
the harness calls, each reading what it needs from the configuration:

* ``draw(config, seed) -> w``: the weights, from the seed;
* ``build_server(config, w, n_slots, seed, vad) -> StreamServer``;
* ``stream_reference(config, w, audio, n_taken, hop, key, vad,
  dtype=jnp.float32)``: the plain reference of one served stream, a dict
  of ``fates``, ``keyword``, ``score``, ``smoothed`` and ``ambiguous``
  (``bench/reference.py`` holds what every family shares).
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import pathlib
import sys
from types import ModuleType
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

    def family(self, config_name: str) -> ModuleType:
        """The package ``bench/families/<family>/`` that configuration
        ``config_name`` names in its ``family`` key."""
        root = self.dir / "families"
        known = sorted(p.parent.name for p in root.glob("*/__init__.py"))
        name = self.config(config_name).get("family")
        if name not in known:
            raise KeyError(f"configuration {config_name!r} names model "
                           f"family {name!r}; known families: {known}")
        path = root / name
        # one module per directory, so that a copied bench directory loads
        # its own family beside this one's
        mod_name = "bench_family_{}_{}".format(
            name.replace(".", "_").replace("-", "_"),
            hashlib.sha1(str(path).encode()).hexdigest()[:12])
        if mod_name not in sys.modules:
            mod_spec = importlib.util.spec_from_file_location(
                mod_name, path / "__init__.py",
                submodule_search_locations=[str(path)])
            mod = importlib.util.module_from_spec(mod_spec)
            sys.modules[mod_name] = mod      # its relative imports need it
            try:
                mod_spec.loader.exec_module(mod)
            except BaseException:
                del sys.modules[mod_name]
                raise
        return sys.modules[mod_name]

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
