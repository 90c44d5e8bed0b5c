"""Pallas TPU kernel: fused Small-Gradient-Accumulation optimizer update.

One elementwise pass implementing paper Algorithm 1 + the SGD weight update
+ Q1.7 weight quantization: reads (w, g, accum), writes (w', accum') — the
whole optimizer state transition in a single VMEM-resident sweep (on chip
this is the gradient-SRAM + threshold-compare unit; on TPU it saves 2x HBM
round-trips vs separate ops for large embedding/FC tables).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _sga_kernel(w_ref, g_ref, a_ref, wo_ref, ao_ref, *,
                lr: float, g_th: float, w_scale: float, w_max: float,
                a_scale: float):
    w, g, a = w_ref[...], g_ref[...], a_ref[...]
    small = jnp.abs(g) < g_th
    banked = jnp.round((a + jnp.where(small, g, 0.0)) / a_scale) * a_scale
    fire = small & (jnp.abs(banked) >= g_th)
    g_upd = jnp.where(small, jnp.where(fire, banked, 0.0), g)
    new_a = jnp.where(fire, 0.0, banked)
    new_w = w - lr * g_upd
    new_w = jnp.clip(jnp.round(new_w / w_scale) * w_scale, -w_max - w_scale,
                     w_max)
    wo_ref[...] = new_w.astype(wo_ref.dtype)
    ao_ref[...] = new_a.astype(ao_ref.dtype)


def _sga_rows_kernel(lr_ref, th_ref, w_ref, g_ref, a_ref, wo_ref, ao_ref, *,
                     w_scale: float, w_max: float, a_scale: float):
    """Row-batched variant: each grid row is one session's flattened
    optimizer state with its OWN (lr, g_th) scalars — the learning-rate
    schedule position differs per enrollment session, so the scalars ride
    in SMEM as operands instead of static compile-time constants."""
    row = pl.program_id(0)
    lr, g_th = lr_ref[row], th_ref[row]
    w, g, a = w_ref[...], g_ref[...], a_ref[...]
    small = jnp.abs(g) < g_th
    banked = jnp.round((a + jnp.where(small, g, 0.0)) / a_scale) * a_scale
    fire = small & (jnp.abs(banked) >= g_th)
    g_upd = jnp.where(small, jnp.where(fire, banked, 0.0), g)
    new_a = jnp.where(fire, 0.0, banked)
    new_w = w - lr * g_upd
    new_w = jnp.clip(jnp.round(new_w / w_scale) * w_scale, -w_max - w_scale,
                     w_max)
    wo_ref[...] = new_w.astype(wo_ref.dtype)
    ao_ref[...] = new_a.astype(ao_ref.dtype)


_LANES = 128


@functools.partial(jax.jit, static_argnames=("w_scale", "w_max", "a_scale",
                                             "block", "interpret"))
def sga_update_rows(w: jax.Array, g: jax.Array, accum: jax.Array,
                    lr: jax.Array, g_th: jax.Array, *,
                    w_scale: float = 1.0 / 128, w_max: float = 127.0 / 128,
                    a_scale: float = 2.0 ** -15, block: int = 1024,
                    interpret: bool):
    """Batched fused SGA update: one ``pallas_call`` for B sessions.

    w/g/accum: (B, N) with N % block == 0 (ops.py pads); lr/g_th: (B,)
    per-row scalars.  Row b transitions exactly like
    ``sga_update(w[b], g[b], accum[b], lr=lr[b], g_th=g_th[b])`` — the
    serving customization scheduler stacks every active session's
    (head, bias, SGA bank) into rows so a mixed tick's optimizer work is
    one launch regardless of how many users are enrolling.  Each row is
    viewed as (N / 128, 128) so a block is a full (block / 128, 128)
    sublane x lane tile."""
    b, n = w.shape
    kern = functools.partial(_sga_rows_kernel, w_scale=w_scale, w_max=w_max,
                             a_scale=a_scale)
    rows = block // _LANES
    spec = pl.BlockSpec((None, rows, _LANES), lambda i, j: (i, j, 0))
    s_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    shape3 = (b, n // _LANES, _LANES)
    nw, na = pl.pallas_call(
        kern, grid=(b, n // block),
        in_specs=[s_spec, s_spec, spec, spec, spec],
        out_specs=(spec, spec),
        out_shape=(jax.ShapeDtypeStruct(shape3, w.dtype),
                   jax.ShapeDtypeStruct(shape3, accum.dtype)),
        interpret=interpret,
    )(lr.reshape(b).astype(jnp.float32), g_th.reshape(b).astype(jnp.float32),
      w.reshape(shape3), g.reshape(shape3), accum.reshape(shape3))
    return nw.reshape(b, n), na.reshape(b, n)


@functools.partial(jax.jit, static_argnames=("lr", "g_th", "w_scale",
                                             "w_max", "a_scale", "block",
                                             "interpret"))
def sga_update(w: jax.Array, g: jax.Array, accum: jax.Array, *,
               lr: float, g_th: float, w_scale: float = 1.0 / 128,
               w_max: float = 127.0 / 128, a_scale: float = 2.0 ** -15,
               block: int = 1024, interpret: bool):
    """All inputs flat (N,) with N % block == 0 (ops.py pads).
    Returns (new_w, new_accum)."""
    n = w.shape[0]
    kern = functools.partial(_sga_kernel, lr=lr, g_th=g_th, w_scale=w_scale,
                             w_max=w_max, a_scale=a_scale)
    spec = pl.BlockSpec((block,), lambda i: (i,))
    return pl.pallas_call(
        kern, grid=(n // block,),
        in_specs=[spec, spec, spec],
        out_specs=(spec, spec),
        out_shape=(jax.ShapeDtypeStruct((n,), w.dtype),
                   jax.ShapeDtypeStruct((n,), accum.dtype)),
        interpret=interpret,
    )(w, g, accum)
