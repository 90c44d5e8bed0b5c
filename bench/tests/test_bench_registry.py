"""The benchmark finds configurations, traffic mixes and per-layer metrics
by name, and refuses to run without a TPU."""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

from bench import registry

ROOT = pathlib.Path(__file__).resolve().parents[2]


def test_new_files_are_found_by_name(tmp_path):
    """A configuration, a traffic mix, a cell and a per-layer metric added
    as new files (and entries) are found with no edit to existing files."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec_data = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p.relative_to(tmp_path): p.read_bytes()
              for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    d = tmp_path / "bench"
    cfg = json.loads((d / "configs" / "kws-paper-ideal.json").read_text())
    cfg["name"] = "kws-new"
    (d / "configs" / "kws-new.json").write_text(json.dumps(cfg))
    (d / "traffic" / "new-mix.json").write_text(json.dumps(
        {"loop": "realtime", "streams": 3, "bank": {"kind": "speech",
                                                    "seconds": 2}}))
    (d / "metrics" / "new_metric.rt.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx['ticks']\n")
    spec_data["configs"].append({"name": "kws-new", "source": "x",
                                 "file": "bench/configs/kws-new.json",
                                 "reduced": [], "why": "x"})
    spec_data["workloads"].append({"name": "new-cell", "config": "kws-new",
                                   "traffic": "new-mix", "chips": 1,
                                   "why": "x"})
    spec_data["per_layer"].append({"name": "new_metric.rt", "unit": "x",
                                   "better": "lower", "source": "host_clock",
                                   "layer": "x", "moves": "setup_s",
                                   "workloads": ["new-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec_data))

    spec = registry.Spec(tmp_path / "BENCHMARK.json", d)
    cell = spec.cell("new-cell")
    assert spec.config(cell["config"])["name"] == "kws-new"
    assert spec.traffic(cell["traffic"])["streams"] == 3
    got = registry.read_per_layer(spec, "new-cell", {"ticks": 4})
    assert got == {"new_metric.rt": {"value": 8.0, "unit": "x"}}
    for rel, data in before.items():
        assert (tmp_path / rel).read_bytes() == data, rel


def test_every_named_file_exists():
    spec = registry.Spec(ROOT / "BENCHMARK.json")
    for c in spec.data["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert spec.config(c["name"])["name"] == c["name"]
    for cell in spec.data["workloads"]:
        spec.config(cell["config"])
        spec.traffic(cell["traffic"])
    for m in spec.data["per_layer"]:
        assert callable(spec.reader(m["name"]))


def test_refuses_without_a_tpu():
    """On a machine whose JAX finds no TPU the command exits non-zero and
    prints no result."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, *spec["command"][1:], "--workload",
         spec["workloads"][0]["name"], "--seed", "7", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
