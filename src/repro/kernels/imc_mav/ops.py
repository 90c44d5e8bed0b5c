"""jit'd public wrappers around the imc_mav Pallas kernels: padding to tile
boundaries, im2col for the binary group conv, and the (B, T, C) activation
interface used by repro.models.kws.

**One-launch-per-layer invariant.**  ``fused_conv_mav`` is the inference
hot path: the whole IMC layer (grouped binary conv + static chip offset +
in-memory BN bias + SA noise + BN-decoder flip + SA sign + channel shuffle
+ OR-maxpool) in exactly one ``pallas_call``, with the weight packs in the
kernel grid and the batch in the M tiling — so the launch count of a
forward (or of a whole fleet of batched streams, see
repro.serving.scheduler) is one per IMC layer, period.
``fused_conv_mav_step`` is the time-sliced streaming entry: same packed
operands, same single launch, but M covers only a hop's carry + fresh
columns (~hop/window of the full-window work — repro.serving.stream owns
the geometry).

**Per-absolute-column SA-noise field.**  The ``sa_noise`` operand is an
explicit pre-pool noise realization, (B, t_conv, C_out).  The streaming
path evaluates it from a field keyed by
``fold_in(fold_in(stream_key, layer), absolute_column)``: a column's noise
sample is a property of its single sense-amplifier evaluation, so it rides
along with the cached activation across hops, and an offline window that
evaluates the same field reproduces the streaming output bit-exactly.
``sa_key``/``sa_noise_std`` is the alternative fresh-draw form used by the
non-streaming forward; the two are mutually exclusive.

Both fused entries are shape-stable jit-pure functions, so they compose
under ``lax.scan``: the compiled whole-tick block (repro.serving.compiled)
traces ``fused_conv_mav_step`` once per layer inside its scan body and the
runtime re-issues that single launch per fused tick — the
one-launch-per-layer invariant carries into the K-tick fast path for free.

The per-group ``conv_mav`` loop below is kept as the seed baseline the
fused kernel is benchmarked against (benchmarks/run.py::imc_fused_bench).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import imc
from repro.kernels import default_interpret
from repro.kernels.imc_mav.imc_mav import imc_fused, imc_mav


def _pad_to(x: jax.Array, axis: int, mult: int, value: float = 0.0):
    # one pad implementation shared with the fold-time packing
    # (core.imc.pack_layer) so both paths stay bit-identical by construction
    padded = imc._pad_axis(x, axis, mult, value)
    return padded, padded.shape[axis] - x.shape[axis]


def mav_matmul(x: jax.Array, w: jax.Array, bias: jax.Array, flip: jax.Array,
               noise: jax.Array | None = None, interpret: bool | None = None
               ) -> jax.Array:
    """Tile-padded entry: x (M,K) ±1, w (K,N) ±1 -> (M,N) ±1."""
    if interpret is None:
        interpret = default_interpret()
    m0, n0 = x.shape[0], w.shape[1]
    bm, bn = 256, 128
    x, _ = _pad_to(x, 0, bm)
    x, _ = _pad_to(x, 1, 128)
    w, _ = _pad_to(w, 0, 128)
    w, _ = _pad_to(w, 1, bn)
    bias, _ = _pad_to(bias, 0, bn)
    flip = jnp.pad(flip, (0, bias.shape[0] - flip.shape[0]),
                   constant_values=1.0)
    if noise is not None:
        noise, _ = _pad_to(noise, 0, bm)
        noise, _ = _pad_to(noise, 1, bn)
    out = imc_mav(x, w, bias, flip, noise, bm=bm, bn=bn, interpret=interpret)
    return out[:m0, :n0]


def _im2col(x: jax.Array, k: int, stride: int) -> jax.Array:
    """x (B, T, C) -> patches (B, T_out, k*C)."""
    b, t, c = x.shape
    t_out = (t - k) // stride + 1
    idx = jnp.arange(t_out)[:, None] * stride + jnp.arange(k)[None, :]
    patches = x[:, idx, :]                       # (B, T_out, k, C)
    return patches.reshape(b, t_out, k * c)


def conv_mav(x: jax.Array, w: jax.Array, bias: jax.Array, flip: jax.Array,
             groups: int, stride: int = 1,
             sa_key: jax.Array | None = None, sa_noise_std: float = 0.0,
             interpret: bool | None = None) -> jax.Array:
    """Seed per-group-loop path: one tiny ``pallas_call`` per conv group,
    each padding its output channels to 128 lanes.  Superseded by
    ``fused_conv_mav`` on the hot path; kept as the benchmark baseline.

    x: (B, T, C_in) ±1;  w: (K, C_in//groups, C_out) ±1.
    """
    b, t, c_in = x.shape
    k, cpg, c_out = w.shape
    cog = c_out // groups
    t_out = (t - k) // stride + 1
    outs = []
    key = sa_key
    for g in range(groups):
        xg = x[..., g * cpg:(g + 1) * cpg]
        wg = w[..., g * cog:(g + 1) * cog]            # (K, cpg, cog)
        patches = _im2col(xg, k, stride).reshape(b * t_out, k * cpg)
        wmat = wg.reshape(k * cpg, cog)
        noise = None
        if key is not None and sa_noise_std > 0:
            key, sub = jax.random.split(key)
            noise = sa_noise_std * jax.random.normal(
                sub, (b * t_out, cog), jnp.float32)
        og = mav_matmul(patches, wmat, bias[g * cog:(g + 1) * cog],
                        flip[g * cog:(g + 1) * cog], noise,
                        interpret=interpret)
        outs.append(og.reshape(b, t_out, cog))
    return jnp.concatenate(outs, axis=-1)


def fused_conv_mav(x: jax.Array, w: jax.Array, bias: jax.Array,
                   flip: jax.Array, groups: int, stride: int = 1,
                   pool: int = 1,
                   chip_offset: jax.Array | None = None,
                   sa_key: jax.Array | None = None,
                   sa_noise_std: float = 0.0,
                   sa_noise: jax.Array | None = None,
                   interpret: bool | None = None,
                   packed: imc.PackedLayer | None = None) -> jax.Array:
    """The whole IMC layer in one ``pallas_call``: grouped binary conv +
    static chip offset + in-memory BN bias + SA noise + BN-decoder flip +
    SA sign + channel shuffle + OR-maxpool.

    x: (B, T, C_in) ±1;  w: (K, C_in//groups, C_out) ±1;
    bias/flip/chip_offset: (C_out,).  Returns (B, T_pool, C_out) ±1 in the
    *post-shuffle* channel order — the shuffle is one transpose of the
    kernel's lane-dense output (see imc_mav.py).

    The SA noise realization is either drawn here from ``sa_key`` (same
    key/shape as ``core.imc.mav_sa``) or supplied explicitly as ``sa_noise``
    (B, t_out, C_out) — the streaming path evaluates a per-absolute-column
    noise field so cached columns keep their realization across hops.
    ``packed`` (see ``core.imc.pack_layer``) supplies the fold-time packed
    weights/bias/flip so only the data-dependent patches are packed per call.

    Bit-identical (noise path included) to

        counts = imc.binary_group_conv_counts(x, w, groups, stride)
        h = imc.mav_sa(counts + chip_offset, bias, flip, sa_key=...,
                       sa_noise_std=..., sa_noise=...)
        h = or_maxpool(channel_shuffle(h, groups), pool, axis=1)
    """
    if interpret is None:
        interpret = default_interpret()
    b, t, c_in = x.shape
    k, cpg, c_out = w.shape
    cog = c_out // groups
    t_out = (t - k) // stride + 1
    t_pool = t_out // pool
    t_use = t_pool * pool
    if t_use <= 0:
        raise ValueError(
            f"fused_conv_mav: input T={t} yields no complete pool window "
            f"(k={k}, stride={stride}, pool={pool}) — input too short for "
            f"this layer")
    if packed is None:
        packed = imc.pack_layer(w, bias, flip, groups)
    layout = packed.layout
    assert layout == imc.make_group_pack_layout(groups, cog, k, cpg), \
        "packed operands do not match this layer's shape"
    k_pad, n_pad = packed.wp.shape[1], packed.wp.shape[2]

    xp = imc.pack_grouped_patches(x, layout, k, stride, t_use)
    off = (jnp.zeros((c_out,), jnp.float32) if chip_offset is None
           else chip_offset.astype(jnp.float32))
    offp = imc.pack_channel_param(off, layout)

    noisep = None
    if sa_key is not None and sa_noise_std > 0:
        # Same draw as the jnp path (imc.mav_sa over (B, t_out, C_out)) so
        # the fused layer is bit-identical noise included.
        noise = sa_noise_std * jax.random.normal(sa_key, (b, t_out, c_out))
    elif sa_noise is not None:
        noise = sa_noise
    else:
        noise = None
    if noise is not None:
        noise = noise[:, :t_use].reshape(b * t_use, c_out)
        noise = jnp.pad(noise, ((0, 0), (0, layout.g_pad * cog - c_out)))
        noisep = noise.reshape(b * t_use, layout.packs,
                               layout.n_pack).transpose(1, 0, 2)

    # M-tile: multiple of the pool window (windows never straddle a tile or
    # the zero padding — M0 = B*t_use is already a whole number of windows).
    m0 = b * t_use
    bm_out = -(-min(256, -(-m0 // pool)) // 8) * 8
    bm = bm_out * pool
    xp, _ = _pad_to(xp, 1, bm)
    xp, _ = _pad_to(xp, 2, k_pad)
    offp, _ = _pad_to(offp, 1, n_pad)
    if noisep is not None:
        noisep, _ = _pad_to(noisep, 1, bm)
        noisep, _ = _pad_to(noisep, 2, n_pad)

    out = imc_fused(xp, packed.wp, offp, packed.bias_p, packed.flip_p,
                    noisep, gpb=layout.gpb, cog=cog,
                    pool=pool, bm=bm, interpret=interpret)
    # (M_pad/pool, cog, g_pad): crop pad rows/groups; flattening (cog,
    # groups) is exactly channel_shuffle's a*groups + g order.
    out = out[:b * t_pool, :, :groups]
    return out.reshape(b, t_pool, c_out)


def fused_conv_mav_step(x_tail: jax.Array, w: jax.Array, bias: jax.Array,
                        flip: jax.Array, groups: int, stride: int = 1,
                        pool: int = 1,
                        chip_offset: jax.Array | None = None,
                        sa_noise: jax.Array | None = None,
                        interpret: bool | None = None,
                        packed: imc.PackedLayer | None = None) -> jax.Array:
    """Time-sliced (frame-incremental) entry into the fused IMC layer.

    ``x_tail`` (B, T_tail, C_in) is the layer's streaming tail: the carry
    columns cached from the previous hop (the k-1 conv overlap plus, on
    odd-length pooling layers, the conv column the previous window's
    OR-maxpool truncated) followed by the hop's fresh input columns
    (repro.serving.stream computes the geometry).  Same pack layout as the
    full-window call; the kernel grid is restricted to the new output
    columns because M = B * T_tail_use instead of B * T_window — the
    per-hop work is the hop/window fraction of a full decision.

    The caller guarantees the tail starts on a pool-window boundary of the
    full window, so the fused OR-maxpool pairs exactly the columns the
    offline path pairs.  ``sa_noise`` (B, t_conv_tail, C_out) must hold the
    noise-field values of the tail's absolute conv columns for the noisy
    path to stay bit-identical to the offline window."""
    t_tail = x_tail.shape[1]
    k = w.shape[0]
    t_conv = (t_tail - k) // stride + 1
    if t_conv < pool:
        raise ValueError(
            f"fused_conv_mav_step: tail T={t_tail} yields {t_conv} conv "
            f"columns — not enough for one pool-{pool} window")
    return fused_conv_mav(x_tail, w, bias, flip, groups=groups,
                          stride=stride, pool=pool, chip_offset=chip_offset,
                          sa_noise=sa_noise, interpret=interpret,
                          packed=packed)
