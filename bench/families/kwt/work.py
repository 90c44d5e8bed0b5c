"""Operations and bytes KWT needs, from its shapes.

Counts are per *stream-hop* (one stream advanced by one hop: one fresh
MFCC frame, then the encoder over the window's 99 tokens), 2 FLOPs per
multiply-add:

* ``hop_flops(model, least=False)``: the whole encoder over every token
  (the embedding of the 98 frames, ``depth`` full layers, the head);
* ``hop_flops(model, least=True)``: the least the decision needs: the last
  layer only for what the head reads (the keys and values of every token,
  the query, attention row, output projection and MLP of the class token).

A layer's matmuls are the Q/K/V and output projections (``4 d^2`` per
token), the MLP (``2 d f`` per token) and the attention scores and their
weighted sum (``2 t^2 d``).  The MFCC frame (about 0.26 M multiply-adds
in float32) is not counted, so a share computed from these can only err
low.

``call_bytes``: the HBM bytes one served step over ``streams`` streams
must move at least: the bfloat16 matmul weights and the float32 rest,
read once, and each stream's state (the 320-sample tail and the 98 x 40
MFCC ring, float32) read and written.
"""

from __future__ import annotations


def _shape(model: dict):
    tokens = (model["sample_len"] - model["frame_len"]) // model[
        "frame_shift"] + 2
    return model["dim"], model["mlp_dim"], tokens


def layer_flops(model: dict) -> int:
    """FLOPs of one full encoder layer over every token."""
    d, f, t = _shape(model)
    return 2 * (t * (4 * d * d + 2 * d * f) + 2 * t * t * d)


def hop_flops(model: dict, least: bool = False) -> int:
    """FLOPs of one stream-hop (see the module docstring)."""
    d, f, t = _shape(model)
    embed = 2 * (t - 1) * model["n_mfcc"] * d
    head = 2 * d * model["num_classes"]
    last = layer_flops(model)
    if least:
        last = 2 * (t * 2 * d * d + 2 * d * d + 2 * t * d + 2 * d * f)
    return embed + (model["depth"] - 1) * layer_flops(model) + last + head


def params(model: dict) -> dict:
    """Parameter counts: matmul weights and the float32 rest."""
    d, f, t = _shape(model)
    mats = (model["n_mfcc"] * d + model["depth"] * (4 * d * d + 2 * d * f)
            + d * model["num_classes"])
    rest = (d + d + t * d + model["depth"] * (3 * d + d + f + d + 4 * d)
            + model["num_classes"])
    return {"matmul": mats, "other": rest, "total": mats + rest}


def call_bytes(model: dict, streams: int) -> int:
    """Least HBM bytes one served step over ``streams`` streams moves."""
    p = params(model)
    frames = (model["sample_len"] - model["frame_len"]) // model[
        "frame_shift"] + 1
    state = 4 * (model["frame_len"] - model["frame_shift"]
                 + frames * model["n_mfcc"])
    return 2 * p["matmul"] + 4 * p["other"] + 2 * streams * state
