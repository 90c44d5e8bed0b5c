"""Operations and bytes the paper net needs, from its shapes.

Counts are per *stream-hop* (one stream advanced by one hop) and per call,
for the least work the algorithm needs: each conv column is computed once
(the streaming tail's recomputed pool-phase column is not counted), +-1
activations and weights move as one byte each, and the SA noise is not
counted as traffic (a kernel may draw it in place).  A roofline share
computed from these can only err low.
"""

from __future__ import annotations

from .reference import geometry, groups


def imc_layers(model: dict, hop: int) -> list:
    """Per IMC layer (conv1..): MACs and bytes moved per stream-hop, and
    the weight bytes one call reads."""
    geo = geometry(model, hop)
    out = []
    for i in range(1, len(model["channels"])):
        c_in, c_out = model["channels"][i - 1], model["channels"][i]
        k = model["kernels"][i]
        fan_in = k * c_in // groups(model, i)
        n_new = geo[i]["d_out"] * model["pools"][i]   # conv columns a hop
        d_in = geo[i - 1]["d_out"]                    # input columns a hop
        out.append({"name": f"conv{i}",
                    "macs": n_new * c_out * fan_in,
                    "bytes": d_in * c_in + geo[i]["d_out"] * c_out,
                    "weight_bytes": fan_in * c_out})
    return out


def hop_flops(model: dict, hop: int) -> int:
    """FLOPs (2 per MAC) of one stream-hop through the whole net: the
    digital sinc layer, the five IMC layers and the FC head."""
    geo = geometry(model, hop)
    macs = geo[0]["d_out"] * model["channels"][0] * model["kernels"][0]
    macs += sum(l["macs"] for l in imc_layers(model, hop))
    macs += model["channels"][-1] * model["num_classes"]
    return 2 * macs


def imc_least_seconds(model: dict, hop: int, stream_hops: float,
                      calls_per_layer: float, pk: dict):
    """Least time the chip could take for the IMC layers' calls that
    computed ``stream_hops`` stream-hops in ``calls_per_layer`` calls a
    layer, and which bound sets it, summed over the five layers."""
    total, bound = 0.0, {"flops": 0.0, "bytes": 0.0}
    for l in imc_layers(model, hop):
        t_f = 2 * l["macs"] * stream_hops / pk["bf16_flops"]
        t_b = (l["bytes"] * stream_hops
               + l["weight_bytes"] * calls_per_layer) / pk["hbm_bytes_per_s"]
        total += max(t_f, t_b)
        bound["flops" if t_f >= t_b else "bytes"] += max(t_f, t_b)
    return total, max(bound, key=bound.get)
