"""Pallas TPU kernel: the IMC macro's MAV + in-memory-BN + SA epilogue.

TPU-native adaptation of the SRAM crossbar (DESIGN.md §3): the ±1 inner
product runs on the MXU as a bf16 matmul over VMEM-resident tiles; the
in-memory BN bias add, optional analog-noise injection and the SA 1-bit
decision are fused into the epilogue so pre-activations never touch HBM —
mirroring how the macro never digitizes the analog MAV value.

Layout: X (M, K) ±1 activations/patches, W (K, N) ±1 weights, bias (N,)
integer word-line bias, flip (N,) BN-decoder sign, optional noise (M, N)
(MAV offset + SA variation realization).  K is the macro fan-in (<= 64 per
bank physically; padded to 128 here for MXU lane alignment — zero padding
contributes 0 to the count, exactly like unused word lines).  The W tile is
grid-invariant along M so weights stay VMEM-resident across the batch grid,
the TPU analogue of weight-stationary in-SRAM storage.

Two kernels live here:

* ``imc_mav`` — the original single-matmul tile kernel (one launch per group;
  kept as the per-group reference path and for generic ±1 matmuls);
* ``imc_fused`` — the whole-IMC-layer kernel used by the model's hardware
  path.  Grid/packing layout (see ``repro.core.imc.GroupPackLayout``):

    grid = (packs, M-tiles), packs = ceil(groups / gpb), gpb = 128 // cog

  Each grid step multiplies one pack of ``gpb`` groups at once: their im2col
  patches are concatenated along the contraction axis (k_pack = gpb*kg) and
  their weights sit on the diagonal of a (k_pack, n_pack) block-diagonal
  matrix, so small per-group channel counts (24-96) share the 128 MXU lanes
  instead of each padding to 128.  The epilogue fuses the entire digital
  block after the macro — static chip offset, integer word-line bias, SA
  noise, BN-decoder flip, SA sign, OR-maxpool over ``pool`` adjacent window
  positions.  Every block is Mosaic-tileable: a pool window's ``pool``
  patch rows sit side by side on the lane axis (a free row-major reshape),
  so the OR-pool is an elementwise max of ``pool`` matmul epilogues, and
  the kernel writes a lane-dense (packs, M/pool, n_pad) slab.  The channel
  shuffle is one XLA transpose of that slab to (M/pool, cog, groups), which
  flattens to the shuffled channel order a*groups + g.  ±1 activations
  therefore go conv -> pool without any pre-activation ever touching HBM,
  mirroring how the macro never digitizes the analog MAV value.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _mav_kernel(x_ref, w_ref, b_ref, f_ref, o_ref):
    counts = jnp.dot(x_ref[...], w_ref[...],
                     preferred_element_type=jnp.float32)
    pre = (counts + b_ref[...][None, :]) * f_ref[...][None, :]
    o_ref[...] = jnp.where(pre >= 0, 1.0, -1.0).astype(o_ref.dtype)


def _mav_kernel_noise(x_ref, w_ref, b_ref, f_ref, n_ref, o_ref):
    counts = jnp.dot(x_ref[...], w_ref[...],
                     preferred_element_type=jnp.float32)
    pre = counts + b_ref[...][None, :] + n_ref[...]
    pre = pre * f_ref[...][None, :]
    o_ref[...] = jnp.where(pre >= 0, 1.0, -1.0).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "interpret"))
def imc_mav(x: jax.Array, w: jax.Array, bias: jax.Array, flip: jax.Array,
            noise: jax.Array | None = None, *, bm: int = 256, bn: int = 128,
            interpret: bool) -> jax.Array:
    """sign((x @ w + bias [+ noise]) * flip) with VMEM-fused epilogue.

    x: (M, K) ±1; w: (K, N) ±1; bias/flip: (N,); noise: (M, N) or None.
    M, N must be multiples of (bm, bn) — ops.py pads.  K is unblocked (macro
    fan-in, small).
    """
    m, k = x.shape
    _, n = w.shape
    grid = (m // bm, n // bn)
    x_spec = pl.BlockSpec((bm, k), lambda i, j: (i, 0))
    w_spec = pl.BlockSpec((k, bn), lambda i, j: (0, j))   # M-invariant: stays
    b_spec = pl.BlockSpec((bn,), lambda i, j: (j,))       # resident in VMEM
    o_spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    if noise is None:
        return pl.pallas_call(
            _mav_kernel, grid=grid,
            in_specs=[x_spec, w_spec, b_spec, b_spec],
            out_specs=o_spec,
            out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
            interpret=interpret,
        )(x, w, bias, flip)
    n_spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    return pl.pallas_call(
        _mav_kernel_noise, grid=grid,
        in_specs=[x_spec, w_spec, b_spec, b_spec, n_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
    )(x, w, bias, flip, noise)


# ---------------------------------------------------------------------------
# Whole-layer fused kernel (grouped conv + epilogue + OR-pool)
# ---------------------------------------------------------------------------


def _fused_kernel(x_ref, w_ref, off_ref, b_ref, f_ref, *rest, pool, k_pad,
                  n_pad):
    """One (pack, M-tile) step: ``pool`` matmuls, one per pool phase, each
    with the fused epilogue ((counts + off) + bias [+ noise]) * flip ->
    sign, then the OR-maxpool as an elementwise max across the phases.

    Row r of the x block holds the ``pool`` adjacent window positions of
    output row r side by side on the lane axis (phase j at lanes
    [j*k_pad, (j+1)*k_pad)), so every slice here is lane-aligned and the
    pool never reshapes across sublanes.  The float-add order matches
    core.imc.mav_sa exactly (counts + chip offset, then bias, then SA
    noise, then the BN-decoder flip), so the fused path is bit-identical
    to the jnp oracle, noise included."""
    n_ref = rest[0] if len(rest) == 2 else None
    o_ref = rest[-1]
    w = w_ref[...]
    off, bias, flip = off_ref[...], b_ref[...], f_ref[...]     # (1, n_pad)
    out = None
    for j in range(pool):
        counts = jnp.dot(x_ref[:, j * k_pad:(j + 1) * k_pad], w,
                         preferred_element_type=jnp.float32)
        pre = (counts + off) + bias
        if n_ref is not None:
            pre = pre + n_ref[:, j * n_pad:(j + 1) * n_pad]
        act = jnp.where(pre * flip >= 0, 1.0, -1.0)
        out = act if out is None else jnp.maximum(out, act)   # OR-pool
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("gpb", "cog", "pool", "bm", "interpret"))
def imc_fused(xp: jax.Array, wp: jax.Array, off: jax.Array, bias: jax.Array,
              flip: jax.Array, noise: jax.Array | None = None, *,
              gpb: int, cog: int, pool: int = 1, bm: int = 256,
              interpret: bool) -> jax.Array:
    """One ``pallas_call`` for a whole grouped IMC layer.

    xp:   (packs, M, k_pad)  packed ±1 im2col patches (zero K-padding);
    wp:   (packs, k_pad, n_pad) block-diagonal ±1 weights;
    off/bias/flip: (packs, n_pad) per-channel chip offset / word-line bias /
          BN-decoder sign;
    noise: (packs, M, n_pad) optional SA-noise realization.

    M must be a multiple of ``bm`` and ``bm`` a multiple of ``pool`` (the
    caller pads on pool-window boundaries, so padded rows never share an
    OR-pool window with real rows).  The kernel writes a lane-dense
    (packs, M // pool, n_pad) slab; the channel shuffle is one XLA
    transpose after it.  Returns (M // pool, cog, packs*gpb): flattening
    the last two axes is exactly the post-shuffle channel order.
    """
    packs, m, k_pad = xp.shape
    n_pad = wp.shape[-1]
    bm_out = bm // pool
    grid = (packs, m // bm)
    # free row-major reshapes: a pool window's rows become one row
    xr = xp.reshape(packs, m // pool, pool * k_pad)
    x_spec = pl.BlockSpec((None, bm_out, pool * k_pad),
                          lambda p, i: (p, i, 0))
    w_spec = pl.BlockSpec((None, k_pad, n_pad), lambda p, i: (p, 0, 0))
    c_spec = pl.BlockSpec((None, 1, n_pad), lambda p, i: (p, 0, 0))
    o_spec = pl.BlockSpec((None, bm_out, n_pad), lambda p, i: (p, i, 0))
    chans = [v.reshape(packs, 1, n_pad) for v in (off, bias, flip)]
    operands = [xr, wp, *chans]
    in_specs = [x_spec, w_spec, c_spec, c_spec, c_spec]
    if noise is not None:
        operands.append(noise.reshape(packs, m // pool, pool * n_pad))
        in_specs.append(pl.BlockSpec((None, bm_out, pool * n_pad),
                                     lambda p, i: (p, i, 0)))
    out = pl.pallas_call(
        functools.partial(_fused_kernel, pool=pool, k_pad=k_pad,
                          n_pad=n_pad),
        grid=grid, in_specs=in_specs, out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((packs, m // pool, n_pad), xp.dtype),
        interpret=interpret,
    )(*operands)
    # channel shuffle: pack p, slot s, channel a -> (a, group p*gpb + s)
    out = out[:, :, :gpb * cog].reshape(packs, m // pool, gpb, cog)
    return out.transpose(1, 3, 0, 2).reshape(m // pool, cog, packs * gpb)
