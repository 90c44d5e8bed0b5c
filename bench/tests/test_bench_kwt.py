"""The ``kwt`` family: found by name, seeded weights, its operation counts,
its per-layer readers, and a tiny cell served through the harness and
checked against the family's reference on the CPU."""

from __future__ import annotations

import json
import pathlib
import shutil

import jax
import numpy as np
import pytest

import _bench_tiny as tiny
from bench import check, registry

ROOT = pathlib.Path(__file__).resolve().parents[2]
KWT3 = json.loads((ROOT / "bench" / "configs" / "kwt3.json").read_text())
TINY_MODEL = dict(KWT3["model"], sample_len=4000, dim=32, heads=2, depth=2,
                  mlp_dim=64)


def _copy(tmp: pathlib.Path) -> registry.Spec:
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    return registry.Spec(tmp / "BENCHMARK.json", tmp / "bench")


def test_family_loads_by_name_from_a_copy(tmp_path):
    spec = _copy(tmp_path)
    cell = spec.cell("kwt3-speech-backlog")
    fam = spec.family(cell["config"])
    assert fam.__file__ == str(tmp_path / "bench" / "families" / "kwt"
                               / "__init__.py")
    assert all(callable(getattr(fam, f)) for f in
               ("draw", "build_server", "stream_reference"))
    assert spec.config("kwt3")["family"] == "kwt"
    names = {m["name"] for m in spec.metrics(cell["name"], "per_layer")}
    assert names == {"step_mfu.kwt-backlog", "busy_mfu.kwt-backlog",
                     "device_idle_pct.kwt-backlog"}
    e2e = {m["name"] for m in spec.metrics(cell["name"], "end_to_end")}
    assert e2e == {"setup_s", "hops_per_s"}


def test_draw_is_seeded(tmp_path):
    spec = _copy(tmp_path)
    fam = spec.family("kwt3")
    cfg = dict(KWT3, model=TINY_MODEL)
    big = 2 ** 31 + 11
    a, b, c = fam.draw(cfg, big), fam.draw(cfg, big), fam.draw(cfg, 11)
    la, lb, lc = (jax.tree_util.tree_leaves(x) for x in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert not any(np.array_equal(x, y) for x, y in zip(la, lc))
    # the program's layout: it serves the benchmark's weights as drawn
    from repro.models import kwt
    want = jax.eval_shape(lambda: kwt.init_params(
        jax.random.PRNGKey(0), kwt.KWTConfig(**TINY_MODEL)))
    assert jax.tree_util.tree_structure(a) == \
        jax.tree_util.tree_structure(want)
    assert [x.shape for x in la] == [
        x.shape for x in jax.tree_util.tree_leaves(want)]


def test_work_counts_at_the_published_widths():
    from bench.families.kwt import work
    m = KWT3["model"]
    d, f, t, depth = 192, 768, 99, 12
    proj = 2 * (4 * d * d + 2 * d * f) * t          # 87.6 MFLOP a layer
    attn = 4 * t * t * d                             # 7.5 MFLOP a layer
    assert (proj, attn) == (87_588_864, 7_527_168)
    embed, head = 2 * 98 * 40 * d, 2 * d * 12
    full = embed + depth * (proj + attn) + head
    # the last layer for the class token: K and V of every token, then
    # one query row, its attention, output projection and MLP
    last = 2 * (2 * d * d * t + d * d + d * d + 2 * t * d + 2 * d * f)
    least = embed + (depth - 1) * (proj + attn) + last + head
    assert work.hop_flops(m) == full
    assert work.hop_flops(m, least=True) == least
    assert round(full / 1e9, 3) == 1.143 and round(least / 1e9, 3) == 1.063
    # the program's own count adds one MFCC frame's multiply-adds
    from repro.models import kwt
    cfg = kwt.KWTConfig(**m)
    assert 2 * kwt.decision_macs(cfg, 1) - least == 2 * (
        480 * 2 * 257 + 257 * 40 + 40 * 40)
    assert work.params(m)["total"] == cfg.param_count() == 5_367_756
    # bf16 matmul weights (10.7 MB) dominate a call's least traffic
    assert 10.7e6 < work.call_bytes(m, 0) < 11e6
    assert work.call_bytes(m, 128) - work.call_bytes(m, 0) == \
        2 * 128 * 4 * (320 + 98 * 40)


def test_readers_share_of_peak(tmp_path):
    """The three readers over a synthetic traced window: 1 s of window,
    half of it busy, 100 000 stream-hops."""
    spec = _copy(tmp_path)
    pk = {"bf16_flops": 197e12}
    ctx = {"model": KWT3["model"], "hop": 160, "peaks": pk,
           "window_s": 1.0, "counters": {"speech_hops": 100_000},
           "trace": {"busy_s": 0.5}}
    got = registry.read_per_layer(spec, "kwt3-speech-backlog", ctx)
    step = 100 * 100_000 * 1_063_197_696 / 197e12
    assert got["step_mfu.kwt-backlog"]["value"] == pytest.approx(step)
    assert got["busy_mfu.kwt-backlog"]["value"] == pytest.approx(2 * step)
    assert got["device_idle_pct.kwt-backlog"]["value"] == pytest.approx(50)
    ctx["counters"]["speech_hops"] = 0
    assert set(registry.read_per_layer(spec, "kwt3-speech-backlog", ctx)) \
        == {"device_idle_pct.kwt-backlog"}


def _tiny_kwt_cell(tmp: pathlib.Path) -> registry.Spec:
    """A bench copy with one cell ``tiny``: the kwt family at width 32
    over a 4000-sample window, 3 streams of the backlog mix."""
    spec = _copy(tmp)
    d = spec.dir
    (d / "configs" / "tiny.json").write_text(json.dumps(
        dict(KWT3, name="tiny", model=TINY_MODEL)))
    t = spec.traffic("speech-backlog")
    t.update(streams=3, check_streams=2, trace_seconds=1)
    t["bank"] = dict(t["bank"], seconds=4)
    (d / "traffic" / "tiny-mix.json").write_text(json.dumps(t))
    data = spec.data
    data["workloads"] = [{"name": "tiny", "config": "tiny",
                          "traffic": "tiny-mix", "chips": 1, "why": "test"}]
    for kind in ("end_to_end", "per_layer"):
        data[kind] = [dict(m, workloads=["tiny"]) for m in data[kind]
                      if "workloads" not in m
                      or "kwt3-speech-backlog" in m["workloads"]]
    (tmp / "BENCHMARK.json").write_text(json.dumps(data))
    return registry.Spec(tmp / "BENCHMARK.json", d)


def test_tiny_cell_is_correct(tmp_path, capsys, monkeypatch):
    """Served through the harness's backlog loop (step_block and the
    compiled block), every sampled decision matches the family's float32
    reference within the configuration's limits."""
    spec = _tiny_kwt_cell(tmp_path)
    out = tiny.run(spec, capsys, monkeypatch)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "hops_per_s"}
    assert out["checks"]["decision_count_mismatch"]["value"] == 0
    assert out["checks"]["hop_mirror_mismatch"]["value"] == 0


def test_control_is_coarser_than_the_program(tmp_path):
    """The bfloat16 reference (the control) lies farther from the float32
    reference than the served decisions do, on the tiny cell."""
    from bench import control
    spec = _tiny_kwt_cell(tmp_path)
    ctl = control.control_numbers(spec, spec.cell("tiny"), 5, 16)
    assert ctl["decision_count_mismatch"] == 0
    assert ctl["score_gap"] > spec.config("tiny")["check"]["score_gap"]
    assert ctl["decisions_compared"] == 2 * (16 + 24 + 1)


def test_reference_windows_come_from_the_raw_stream():
    """The reference's windows are rebuilt from the raw stream: hop h of
    stream audio is samples [160 h, 160 h + window)."""
    from bench.families.kwt import reference as ref
    cfg = dict(KWT3, model=TINY_MODEL)
    from bench.families.kwt import weights
    w = weights.draw(cfg, 3)
    rng = np.random.default_rng(0)
    audio = (np.round(rng.uniform(-0.5, 0.5, 4000 + 160 * 5) * 127)
             / 127).astype(np.float32)
    r = ref.stream_reference(cfg, w, audio, 5, 160, None, None)
    assert len(r["keyword"]) == 6 and not r["ambiguous"]
    lg = ref.window_logits(TINY_MODEL, w, np.stack(
        [audio[160 * h:160 * h + 4000] for h in range(6)]))
    post = np.exp(lg - lg.max(1, keepdims=True))
    post /= post.sum(1, keepdims=True)
    np.testing.assert_allclose(r["smoothed"][0], post[0], rtol=1e-5)
    assert check.compare({"s": list(zip(r["keyword"].tolist(),
                                        r["score"].tolist()))},
                         {"s": r})["score_gap"] == 0.0
