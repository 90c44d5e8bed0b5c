"""Pallas TPU kernel: int8 x int8 -> int32 matmul with fixed-point requant.

The digital FC classifier + on-chip-training datapath of the chip (§V-C):
8-bit operands, 32-bit accumulate, shift-based rescale back onto the Q-grid
(multiplication by the error-scaling factor 1.375 = shift-and-add on chip;
here the shift exponent is a kernel scalar).  No float in the datapath.

MXU note: TPU MXUs execute s8xs8->s32 natively; interpret mode validates the
integer semantics on CPU bit-exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _int8_kernel(x_ref, w_ref, b_ref, o_ref, *, shift: int, out_max: int):
    acc = jnp.dot(x_ref[...].astype(jnp.int32), w_ref[...].astype(jnp.int32),
                  preferred_element_type=jnp.int32)
    acc = acc + b_ref[...][None, :]
    # rounding right-shift: (acc + 2^(s-1)) >> s, saturate to the out grid
    if shift > 0:
        acc = (acc + (1 << (shift - 1))) >> shift
    acc = jnp.clip(acc, -out_max - 1, out_max)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("shift", "out_max", "bm", "bn",
                                             "interpret"))
def int8_matmul(x: jax.Array, w: jax.Array, bias: jax.Array, *,
                shift: int = 7, out_max: int = 127, bm: int = 256,
                bn: int = 128, interpret: bool) -> jax.Array:
    """x: (M, K) int8, w: (K, N) int8, bias: (N,) int32 ->
    (M, N) int8 codes = clip((x@w + bias) >> shift)."""
    m, k = x.shape
    _, n = w.shape
    grid = (m // bm, n // bn)
    kern = functools.partial(_int8_kernel, shift=shift, out_max=out_max)
    return pl.pallas_call(
        kern, grid=grid,
        in_specs=[pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
                  pl.BlockSpec((k, bn), lambda i, j: (0, j)),
                  pl.BlockSpec((bn,), lambda i, j: (j,))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int8),
        interpret=interpret,
    )(x, w, bias)
