"""A tiny copy of the benchmark (configuration, traffic, metrics, model
families) for CPU tests: the paper net's topology at 8/16 channels and a
1024-sample window, a handful of streams, on the jnp path."""

from __future__ import annotations

import json
import pathlib
import shutil
import types

BENCH = pathlib.Path(__file__).resolve().parents[1]

MODEL = {"channels": [8, 16, 16, 16, 16, 16], "kernels": [15, 3, 3, 3, 3, 3],
         "strides": [4, 1, 1, 1, 1, 1], "pools": [1, 2, 2, 1, 2, 2],
         "channels_per_group": 8, "num_classes": 10, "sample_len": 1024,
         "sample_rate": 16000}


def make(tmp: pathlib.Path, traffic: str, *, silicon: bool = True,
         use_kernel: bool = False, streams: int = 3,
         check_streams: int = 2) -> "object":
    """A bench directory under ``tmp`` with one cell ``tiny`` of the named
    traffic mix, and its ``BENCHMARK.json``; returns the loaded Spec."""
    from bench import registry
    d = tmp / "bench"
    (d / "configs").mkdir(parents=True)
    (d / "traffic").mkdir()
    shutil.copytree(BENCH / "metrics", d / "metrics")
    shutil.copytree(BENCH / "families", d / "families",
                    ignore=shutil.ignore_patterns("__pycache__"))
    base = json.loads((BENCH / "configs" / (
        "kws-paper-silicon.json" if silicon else "kws-paper-ideal.json"))
        .read_text())
    base.update(name="tiny", model=MODEL)
    base["serving"] = dict(base["serving"], use_kernel=use_kernel)
    (d / "configs" / "tiny.json").write_text(json.dumps(base))
    t = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    t.update(streams=streams, check_streams=check_streams, trace_seconds=1)
    t["bank"] = dict(t["bank"], seconds=4)
    (d / "traffic" / "tiny-mix.json").write_text(json.dumps(t))
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    # the tiny cell reports what the cells of this traffic mix report
    cells = {c["name"] for c in spec["workloads"] if c["traffic"] == traffic}
    spec["workloads"] = [{"name": "tiny", "config": "tiny",
                          "traffic": "tiny-mix", "chips": 1, "why": "test"}]
    for kind in ("end_to_end", "per_layer"):
        spec[kind] = [dict(m, workloads=["tiny"]) for m in spec[kind]
                      if "workloads" not in m or cells & set(m["workloads"])]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return registry.Spec(tmp / "BENCHMARK.json", d)


def args(seconds: float = 0.3, trace: int = 0, seed: int = 2 ** 31 + 5,
         workload: str = "tiny"):
    return types.SimpleNamespace(workload=workload, seed=seed,
                                 seconds=seconds, trace=trace, streams=None)


def cpu_device(chips: int) -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def run(spec, capsys, monkeypatch, **kw) -> dict:
    """One tiny run of the harness on the CPU, past its look for a chip;
    returns the parsed result line."""
    from bench import run as bench_run
    monkeypatch.setattr(bench_run, "find_devices", cpu_device)
    monkeypatch.setattr(bench_run, "enable_cache", lambda: "off")
    import jax
    from bench import peaks
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.PEAKS["TPU v5 lite"])
    a = args(**kw)
    bench_run.measure(a, spec, spec.cell(a.workload), 0.0)
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
