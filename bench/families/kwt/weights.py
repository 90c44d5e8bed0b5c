"""Seeded KWT weights, drawn on the device in one jitted call.

The benchmark draws the weights itself, so that the reference and the
program get the very same numbers and the reference takes nothing the
program made.  The net is untrained; the scales keep every layer's
activations of order one, so that attention is far from uniform and the
class token reads its input (the check then sees a wrong frame):

* matrices: N(0, 1 / fan_in);
* biases, LayerNorm offsets: N(0, 0.1^2); LayerNorm scales: 1 + N(0, 0.1^2);
* class token and position embeddings: N(0, 1).

The layout is the program's (``repro.models.kwt.init_params``): ``embed``,
``cls``, ``pos``, ``layers`` (per layer ``qkv``, ``out``, ``ln1``,
``mlp1``, ``mlp2``, ``ln2``) and ``head``; a linear layer is ``{"w": (in,
out), "b": (out,)}`` and ``qkv``'s columns are the queries, keys and
values, each head's 64 columns together.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def n_frames(model: dict) -> int:
    return (model["sample_len"] - model["frame_len"]) // model[
        "frame_shift"] + 1


@functools.partial(jax.jit, static_argnums=(1,))
def _draw(key, shape: tuple):
    n_mfcc, d, f, depth, tokens, classes = shape
    keys = iter(jax.random.split(key, 6 + 12 * depth))

    def linear(d_in, d_out):
        return {"w": jax.random.normal(next(keys), (d_in, d_out))
                / jnp.sqrt(float(d_in)),
                "b": 0.1 * jax.random.normal(next(keys), (d_out,))}

    def norm():
        return {"scale": 1.0 + 0.1 * jax.random.normal(next(keys), (d,)),
                "bias": 0.1 * jax.random.normal(next(keys), (d,))}

    w = {"embed": linear(n_mfcc, d),
         "cls": jax.random.normal(next(keys), (d,)),
         "pos": jax.random.normal(next(keys), (tokens, d)),
         "layers": [{"qkv": linear(d, 3 * d), "out": linear(d, d),
                     "ln1": norm(), "mlp1": linear(d, f),
                     "mlp2": linear(f, d), "ln2": norm()}
                    for _ in range(depth)]}
    w["head"] = linear(d, classes)
    return w


def draw(config: dict, seed: int) -> dict:
    """The float32 weights of the configuration's ``model`` for ``seed``
    (device arrays)."""
    m = config["model"]
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed % (2 ** 31))
    key = jax.random.fold_in(key, seed // (2 ** 31))
    return _draw(key, (m["n_mfcc"], m["dim"], m["mlp_dim"], m["depth"],
                       n_frames(m) + 1, m["num_classes"]))
