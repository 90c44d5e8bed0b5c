"""The benchmark finds configurations, model families, traffic mixes and
per-layer metrics by name, and refuses to run without a TPU."""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

import _bench_tiny as tiny
from bench import registry

ROOT = pathlib.Path(__file__).resolve().parents[2]

ENTRY_POINTS = ("draw", "build_server", "stream_reference")


def _files(d: pathlib.Path) -> dict:
    return {p.relative_to(d): p.read_bytes() for p in d.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_files_are_found_by_name(tmp_path):
    """A configuration, a model family, a traffic mix, a cell and a
    per-layer metric added as new files (and entries) are found with no
    edit to existing files."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec_data = json.loads((ROOT / "BENCHMARK.json").read_text())
    d = tmp_path / "bench"
    before = _files(d)
    cfg = json.loads((d / "configs" / "kws-paper-ideal.json").read_text())
    cfg.update(name="kws-new", family="new_family")
    (d / "configs" / "kws-new.json").write_text(json.dumps(cfg))
    (d / "families" / "new_family").mkdir()
    (d / "families" / "new_family" / "__init__.py").write_text(
        "def draw(config, seed):\n    return {'seed': seed}\n\n\n"
        "def build_server(config, w, n_slots, seed, vad):\n    return None"
        "\n\n\ndef stream_reference(config, w, audio, n_taken, hop, key,"
        " vad, dtype=None):\n    return {}\n")
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
    assert spec.family(cell["config"]).draw(cfg, 9) == {"seed": 9}
    # the copy's families are its own
    assert spec.family("kws-paper-ideal").__file__ == str(
        d / "families" / "kws_paper" / "__init__.py")
    got = registry.read_per_layer(spec, "new-cell", {"ticks": 4})
    assert got == {"new_metric.rt": {"value": 8.0, "unit": "x"}}
    for rel, data in before.items():
        assert (d / rel).read_bytes() == data, rel


def test_every_named_file_exists():
    spec = registry.Spec(ROOT / "BENCHMARK.json")
    for c in spec.data["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert spec.config(c["name"])["name"] == c["name"]
        family = spec.family(c["name"])
        assert all(callable(getattr(family, f)) for f in ENTRY_POINTS)
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


WRAPPED = '''"""The kws_paper family's entry points under a second name."""

from bench.families import kws_paper

CALLS = []


def draw(config, seed):
    CALLS.append("draw")
    return kws_paper.draw(config, seed)


def build_server(config, w, n_slots, seed, vad):
    CALLS.append("build_server")
    return kws_paper.build_server(config, w, n_slots, seed, vad)


def stream_reference(config, w, audio, n_taken, hop, key, vad, **kw):
    CALLS.append("stream_reference")
    return kws_paper.stream_reference(config, w, audio, n_taken, hop, key,
                                      vad, **kw)
'''


def test_second_family_runs_from_new_files(tmp_path, capsys, monkeypatch):
    """A second family with its configuration, cell and metric, added as
    new files (and entries) to a tiny bench, runs ``correct`` through the
    harness, which calls only the family's entry points."""
    spec = tiny.make(tmp_path, "speech-rt")
    d = spec.dir
    before = _files(d)
    (d / "families" / "kws_wrapped").mkdir()
    (d / "families" / "kws_wrapped" / "__init__.py").write_text(WRAPPED)
    cfg = dict(spec.config("tiny"), name="tiny-wrapped",
               family="kws_wrapped")
    (d / "configs" / "tiny-wrapped.json").write_text(json.dumps(cfg))
    (d / "metrics" / "wrapped_hops.rt.py").write_text(
        "def read(ctx):\n    return float(ctx['counters']['speech_hops'])\n")
    data = spec.data
    data["workloads"].append(dict(data["workloads"][0], name="wrapped",
                                  config="tiny-wrapped"))
    for m in data["end_to_end"]:
        m["workloads"].append("wrapped")
    data["per_layer"].append({"name": "wrapped_hops.rt", "unit": "hops",
                              "better": "higher", "source":
                              "program_counter", "layer": "served step",
                              "moves": "hop_latency_p50_ms",
                              "workloads": ["wrapped"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    spec = registry.Spec(tmp_path / "BENCHMARK.json", d)

    out = tiny.run(spec, capsys, monkeypatch, workload="wrapped")
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"setup_s", "hop_latency_p50_ms"}
    assert set(spec.family("tiny-wrapped").CALLS) == set(ENTRY_POINTS)
    got = registry.read_per_layer(spec, "wrapped",
                                  {"counters": {"speech_hops": 12}})
    assert got == {"wrapped_hops.rt": {"value": 12.0, "unit": "hops"}}
    for rel, data in before.items():
        assert (d / rel).read_bytes() == data, rel


@pytest.mark.parametrize("family", [None, "no_such_family"])
def test_unknown_family_fails_at_once(tmp_path, monkeypatch, family):
    """A configuration with no family, or one that names no family that
    exists, stops the run before it looks for a chip, naming the known
    families."""
    from bench import run as bench_run
    spec = tiny.make(tmp_path, "speech-rt")
    path = spec.dir / "configs" / "tiny.json"
    cfg = json.loads(path.read_text())
    cfg.pop("family")
    if family is not None:
        cfg["family"] = family
    path.write_text(json.dumps(cfg))

    def no_chip(chips):
        raise AssertionError("looked for a chip first")
    monkeypatch.setattr(bench_run, "find_devices", no_chip)
    with pytest.raises(KeyError, match=r"known families: \['kws_paper'\]"):
        bench_run.measure(tiny.args(), spec, spec.cell("tiny"), 0.0)
