"""Seeded folded weights of the paper net, drawn on the device.

The benchmark draws the hardware-folded parameters itself, in one jitted
call from the seed, so that the reference and the program get the very
same numbers and the reference takes nothing the program made:

* conv weights: +-1, shape (k, C_in / groups, C_out);
* layer-0 (digital sinc) bias: on the adder's 1/128 grid, with an odd
  numerator, so that no layer-0 pre-activation is an exact tie (8-bit audio
  over 127 plus m / 128 is never 0 for odd m);
* IMC biases: even integers in [-8, 8] (the in-memory grid is the even
  integers in [-64, 64]);
* BN-decoder flips: +-1, nine in ten +1;
* FC: Q1.7 weights and biases;
* chip offsets (the silicon configuration): ``mav_offset_std`` times a
  standard normal per IMC output channel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .reference import groups


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw(key, shapes: tuple, offset_std: float):
    convs, (d, k_cls) = shapes[:-1], shapes[-1]
    keys = jax.random.split(key, 4 * len(convs) + 2)
    w, bias, flip, offsets = [], [], [], []
    for i, (kk, cin_g, c) in enumerate(convs):
        kw, kb, kf, ko = keys[4 * i:4 * i + 4]
        w.append(jnp.where(jax.random.bernoulli(kw, 0.5, (kk, cin_g, c)),
                           1.0, -1.0))
        if i == 0:
            m = 2 * jax.random.randint(kb, (c,), -200, 200) + 1
            bias.append(m.astype(jnp.float32) / 128.0)
        else:
            bias.append(2.0 * jax.random.randint(kb, (c,), -4, 5))
        flip.append(jnp.where(jax.random.bernoulli(kf, 0.9, (c,)), 1.0,
                              -1.0))
        offsets.append(offset_std * jax.random.normal(ko, (c,)))
    fc_w = jnp.clip(jnp.round(jax.random.normal(keys[-2], (d, k_cls))
                              * 128.0 / jnp.sqrt(d)), -128, 127) / 128.0
    fc_b = jnp.clip(jnp.round(jax.random.normal(keys[-1], (k_cls,))
                              * 16.0), -128, 127) / 128.0
    return w, bias, flip, offsets, fc_w, fc_b


def draw(config: dict, seed: int) -> dict:
    """The folded weights of the configuration's ``model`` on its
    ``silicon`` for ``seed`` (device arrays)."""
    model, silicon = config["model"], config["silicon"]
    chans = model["channels"]
    shapes = []
    for i, k in enumerate(model["kernels"]):
        cin = 1 if i == 0 else chans[i - 1]
        shapes.append((k, cin // groups(model, i), chans[i]))
    shapes.append((chans[-1], model["num_classes"]))
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed % (2 ** 31))
    key = jax.random.fold_in(key, seed // (2 ** 31))
    w, bias, flip, offsets, fc_w, fc_b = _draw(
        key, tuple(shapes), float(silicon.get("mav_offset_std", 0.0)))
    return {"w": w, "bias": bias, "flip": flip,
            "offsets": offsets if silicon.get("mav_offset_std") else None,
            "fc_w": fc_w, "fc_b": fc_b}
