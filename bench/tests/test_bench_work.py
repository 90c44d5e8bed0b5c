"""The benchmark's operation counts against the program's own accounting
(``serving.stream.streaming_layer_stats``) and geometry."""

from __future__ import annotations

import json
import pathlib

from bench.families.kws_paper import reference as ref
from bench.families.kws_paper import work

MODEL = json.loads((pathlib.Path(__file__).resolve().parents[1] / "configs"
                    / "kws-paper-silicon.json").read_text())["model"]


def test_geometry_matches_the_program():
    from repro.models import kws
    from repro.serving import make_stream_geometry
    geom = make_stream_geometry(kws.KWSConfig(), 64)
    for mine, lg in zip(ref.geometry(MODEL, 64), geom.layers):
        assert (mine["t_conv"], mine["t_out"], mine["d_out"]) == (
            lg.t_conv, lg.t_out, lg.d_out)


def test_imc_macs_against_streaming_layer_stats():
    """Per stream-hop the benchmark counts each new conv column once; the
    program's streaming tail also recomputes the pool-phase column."""
    from repro.models import kws
    from repro.serving import make_stream_geometry, streaming_layer_stats
    cfg = kws.KWSConfig()
    geom = make_stream_geometry(cfg, 64)
    prog = streaming_layer_stats(cfg, geom)
    full = kws.layer_stats(cfg)
    for i, layer in enumerate(work.imc_layers(MODEL, 64), start=1):
        lg = geom.layers[i]
        n_new = lg.d_out * cfg.pools[i]
        assert layer["macs"] * lg.t_conv == full[i]["macs"] * n_new
        assert layer["macs"] * (lg.t_conv - lg.conv_lo) == \
            prog[i]["macs"] * n_new
    total = sum(layer["macs"] for layer in work.imc_layers(MODEL, 64))
    assert total == 497_664                 # about 0.5 M MACs a stream-hop


def test_hop_flops_and_bound():
    assert work.hop_flops(MODEL, 64) == 2 * (16 * 24 * 15 + 497_664
                                             + 576 * 10)
    pk = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = work.imc_least_seconds(MODEL, 64, 1000, 10, pk)
    assert bound == "bytes"                 # under the v5e ridge
    assert least > 1000 * 2 * 497_664 / pk["bf16_flops"]
