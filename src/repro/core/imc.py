"""Functional model of the SRAM in-memory-computing (IMC) macro (paper §IV).

The physical macro ([17], Fig 6): 8 banks of 64x64 8T SRAM cells per macro
(4KB).  Binary weights live in the array; activations precharge read bitlines;
multiply-and-average (MAV) happens by charge sharing on AVG_P/AVG_N lines, and a
sense amplifier (SA) converts the analog difference to a 1-bit output.  Batch
norm executes *in memory*: the BN bias is one word-line of +/-1 cells driven by
input 1, so

  - the bias is an integer in [-64, 64],
  - its parity is fixed by the array width (even for a 64-wide array),
  - the SA output is sign(sum_i x_i w_i + bias + analog noise).

This module provides the bit/count-exact functional model of all of that, plus
the two non-ideal effects the paper compensates:

  * MAV offset  — a static per-bank (per output channel) analog mismatch,
                  drawn once per *chip* (Monte-Carlo over PVT corners),
  * SA variation — per-evaluation comparator noise near the threshold.

Everything is expressed in the integer "count" domain of the array (the analog
averaging /64 divides both sides of the comparison and is absorbed into the
threshold — DESIGN.md §3), so the model is exact and jit-friendly.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core.binary import binarize

# ---------------------------------------------------------------------------
# Macro geometry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class IMCMacroConfig:
    rows: int = 64                 # word lines per bank
    cols: int = 64                 # bit lines per bank
    banks_per_macro: int = 8       # one bank computes one output channel slice
    bias_rows: int = 1             # word lines reserved for in-memory BN

    @property
    def macro_bits(self) -> int:
        return self.rows * self.cols * self.banks_per_macro

    @property
    def macro_bytes(self) -> int:
        return self.macro_bits // 8

    @property
    def bias_range(self) -> int:
        """|bias| <= cols (one word-line of +/-1 cells)."""
        return self.cols

    @property
    def bias_parity_even(self) -> bool:
        """Sum of an even number of +/-1 cells is even."""
        return self.cols % 2 == 0


DEFAULT_MACRO = IMCMacroConfig()


@dataclasses.dataclass(frozen=True)
class IMCNoiseParams:
    """Noise magnitudes in array-count units (1 count = one +/-1 product)."""

    mav_offset_std: float = 4.0    # static per-channel MAV mismatch
    sa_noise_std: float = 1.0      # per-evaluation SA comparator noise

    def none(self) -> "IMCNoiseParams":
        return IMCNoiseParams(0.0, 0.0)


# ---------------------------------------------------------------------------
# In-memory BN folding + bias mapping (paper §IV-A)
# ---------------------------------------------------------------------------


def fold_bn_to_bias(gamma: jax.Array, beta: jax.Array, mean: jax.Array,
                    var: jax.Array, act_offset: jax.Array,
                    eps: float = 1e-5) -> Tuple[jax.Array, jax.Array]:
    """Fold BN (+ the learnable pre-binarization offset, Fig 2) into a single
    integer-domain threshold.

    The binary activation is sign(gamma*(a-mean)/sigma + beta + act_offset).
    For gamma > 0 this equals sign(a + b) with
        b = (beta + act_offset) * sigma / gamma - mean,
    and for gamma < 0 the SA output must be inverted (the digital "BN decoder"
    in Fig 9 handles the sign).  Returns (bias_real, flip) with flip in
    {+1, -1}.
    """
    sigma = jnp.sqrt(var + eps)
    g = jnp.where(gamma == 0, 1e-12, gamma)
    b = (beta + act_offset) * sigma / g - mean
    flip = jnp.where(gamma >= 0, 1.0, -1.0)
    return b, flip


def map_bias(bias: jax.Array, method: str = "best",
             macro: IMCMacroConfig = DEFAULT_MACRO) -> jax.Array:
    """Quantize a real BN bias onto the in-memory grid.

    The grid: integers of fixed parity (even for a 64-wide array) clipped to
    [-cols, cols].  The paper evaluates four mappings — ``add`` (round toward
    +inf), ``sub`` (toward -inf), ``abs_add`` (away from zero), ``abs_sub``
    (toward zero) — and keeps the best; ``best`` here selects round-to-nearest
    on the parity grid, which is what "lowest accuracy drop" converges to.
    """
    step = 2 if macro.bias_parity_even else 1
    half = step / 2.0
    if method == "add":
        q = jnp.ceil(bias / step) * step
    elif method == "sub":
        q = jnp.floor(bias / step) * step
    elif method == "abs_add":
        q = jnp.sign(bias) * jnp.ceil(jnp.abs(bias) / step) * step
    elif method == "abs_sub":
        q = jnp.sign(bias) * jnp.floor(jnp.abs(bias) / step) * step
    elif method == "best":
        q = jnp.round(bias / step) * step
    else:
        raise ValueError(f"unknown bias mapping method: {method}")
    return jnp.clip(q, -macro.bias_range, macro.bias_range)


BIAS_MAPPING_METHODS = ("add", "sub", "abs_add", "abs_sub", "best")


# ---------------------------------------------------------------------------
# Chip instance: static Monte-Carlo noise realization
# ---------------------------------------------------------------------------


def sample_chip_offsets(key: jax.Array, channels_per_layer: Dict[str, int],
                        noise: IMCNoiseParams) -> Dict[str, jax.Array]:
    """Draw the static MAV offsets of one fabricated chip.

    One offset per output channel per IMC layer (each output channel is served
    by one bank / AVG-line pair, so the mismatch is static per channel).
    """
    offsets = {}
    for name, c in sorted(channels_per_layer.items()):
        key, sub = jax.random.split(key)
        offsets[name] = noise.mav_offset_std * jax.random.normal(sub, (c,))
    return offsets


# ---------------------------------------------------------------------------
# The MAV + SA forward path
# ---------------------------------------------------------------------------


def mav_sa(counts: jax.Array, bias_int: jax.Array, flip: jax.Array,
           mav_offset: jax.Array | None = None,
           sa_key: jax.Array | None = None,
           sa_noise_std: float = 0.0,
           sa_noise: jax.Array | None = None) -> jax.Array:
    """The macro's analog epilogue: sign(counts + bias + noise) with BN-decoder
    sign correction.  ``counts`` has channels on the last axis; ``bias_int``,
    ``flip`` and ``mav_offset`` are per-channel.

    The SA-noise realization comes either from ``sa_key``/``sa_noise_std``
    (drawn here, one value per evaluation) or as an explicit ``sa_noise``
    array broadcastable to ``counts`` — the streaming serving path draws its
    noise from a per-absolute-column field so cached columns keep the exact
    realization they were evaluated with (repro.serving.stream).  Both are
    added at the same point in the float chain, so the paths stay
    bit-identical."""
    pre = counts + bias_int
    if mav_offset is not None:
        pre = pre + mav_offset
    if sa_key is not None and sa_noise_std > 0.0:
        pre = pre + sa_noise_std * jax.random.normal(sa_key, pre.shape)
    elif sa_noise is not None:
        pre = pre + sa_noise
    return binarize(pre * flip)


def binary_group_conv_counts(x_bin: jax.Array, w_bin: jax.Array,
                             groups: int, stride: int = 1) -> jax.Array:
    """Integer conv counts for a 1-D binary group convolution.

    x_bin: (B, T, C_in) in {-1,+1};  w_bin: (K, C_in//groups, C_out) in {-1,+1}.
    Returns (B, T_out, C_out) integer-valued counts (sum of +/-1 products) —
    exactly what accumulates on the AVG lines before the SA.

    ``HIGHEST`` precision keeps the counts exact on a TPU: at the default
    precision a grouped convolution of ±1 float32 operands came back off
    by up to 24 counts on a v5e (jax 0.9.0), and the digital layer 0, whose
    input is 8-bit audio, would round its operands to bf16.
    """
    dn = jax.lax.conv_dimension_numbers(x_bin.shape, w_bin.shape,
                                        ("NWC", "WIO", "NWC"))
    out = jax.lax.conv_general_dilated(
        x_bin.astype(jnp.float32), w_bin.astype(jnp.float32),
        window_strides=(stride,), padding="VALID",
        dimension_numbers=dn, feature_group_count=groups,
        precision=jax.lax.Precision.HIGHEST)
    return out


# ---------------------------------------------------------------------------
# Group-pack layout for the fused IMC layer kernel
# ---------------------------------------------------------------------------
#
# One IMC layer is `groups` independent small matmuls (fan-in k*cpg = 72,
# 24-96 output channels each).  Launching one MXU matmul per group pads every
# group's outputs to 128 lanes (~5x wasted columns for cog=24-48).  Instead we
# pack `gpb = lanes // cog` groups into one grid step: their patches are
# concatenated along the contraction axis and their weights placed on the
# diagonal of a (gpb*kg, gpb*cog) block-diagonal matrix, so one 128-lane MXU
# pass computes gpb groups at once (off-diagonal zeros contribute nothing,
# exactly like unused word lines).  The kernel grid is then
# (packs = ceil(groups/gpb), M-tiles).


@dataclasses.dataclass(frozen=True)
class GroupPackLayout:
    """Static geometry of one packed grouped layer.

    groups/cog: conv groups and output channels per group;
    kg: per-group fan-in (k * c_in_per_group);
    gpb: groups packed per grid step (share one 128-lane MXU pass);
    packs: grid extent over packed group blocks;
    lanes: MXU lane width the pack is sized against.
    """

    groups: int
    cog: int
    kg: int
    gpb: int
    packs: int
    lanes: int = 128

    @property
    def g_pad(self) -> int:
        """Groups padded up to a whole number of packs."""
        return self.packs * self.gpb

    @property
    def k_pack(self) -> int:
        """Contraction extent of one pack (gpb groups' fan-ins stacked)."""
        return self.gpb * self.kg

    @property
    def n_pack(self) -> int:
        """Output lanes of one pack (gpb groups' channels side by side)."""
        return self.gpb * self.cog


# Static pytree node: layouts ride inside PackedHWParams through jit
# boundaries as aux data (they are shape metadata, not arrays).
jax.tree_util.register_static(GroupPackLayout)


def make_group_pack_layout(groups: int, cog: int, k: int, cpg: int,
                           lanes: int = 128) -> GroupPackLayout:
    kg = k * cpg
    gpb = max(1, min(groups, lanes // cog)) if cog <= lanes else 1
    packs = -(-groups // gpb)
    return GroupPackLayout(groups=groups, cog=cog, kg=kg, gpb=gpb,
                           packs=packs, lanes=lanes)


def pack_grouped_weights(w: jax.Array, layout: GroupPackLayout) -> jax.Array:
    """(k, cpg, c_out) grouped weights -> (packs, k_pack, n_pack) block-diag.

    Pack p, slot j holds group g = p*gpb + j at diagonal block
    [j*kg:(j+1)*kg, j*cog:(j+1)*cog]; groups beyond `groups` are zero.
    """
    k, cpg, c_out = w.shape
    lt = layout
    wall = w.reshape(lt.kg, c_out)
    wall = jnp.pad(wall, ((0, 0), (0, lt.g_pad * lt.cog - c_out)))
    wg = wall.reshape(lt.kg, lt.g_pad, lt.cog).transpose(1, 0, 2)
    wg = wg.reshape(lt.packs, lt.gpb, lt.kg, lt.cog)
    bd = jnp.zeros((lt.packs, lt.gpb, lt.kg, lt.gpb, lt.cog), w.dtype)
    for j in range(lt.gpb):
        bd = bd.at[:, j, :, j, :].set(wg[:, j])
    return bd.reshape(lt.packs, lt.k_pack, lt.n_pack)


def pack_channel_param(v: jax.Array, layout: GroupPackLayout,
                       fill: float = 0.0) -> jax.Array:
    """Per-output-channel vector (c_out,) -> (packs, n_pack).

    Channels are group-contiguous pre-shuffle, so a pack's n_pack channels
    are one contiguous span; padded groups get `fill` (0 for bias/offset,
    1 for flip)."""
    lt = layout
    v = jnp.pad(v, (0, lt.g_pad * lt.cog - v.shape[0]), constant_values=fill)
    return v.reshape(lt.packs, lt.n_pack)


def pack_grouped_patches(x: jax.Array, layout: GroupPackLayout, k: int,
                         stride: int, t_use: int | None = None) -> jax.Array:
    """im2col per group, packed: (B, T, C_in) -> (packs, B*t_use, k_pack).

    Column layout within a pack matches pack_grouped_weights: slot j's fan-in
    is flattened (tap-major, channel-minor) at offset j*kg.  ``t_use`` limits
    the window positions (the caller truncates to a whole number of pool
    windows so OR-pooling can fuse into the kernel)."""
    b, t, c_in = x.shape
    lt = layout
    cpg = lt.kg // k
    t_out = (t - k) // stride + 1
    if t_use is None:
        t_use = t_out
    idx = jnp.arange(t_use)[:, None] * stride + jnp.arange(k)[None, :]
    win = x[:, idx, :]                                  # (B, t_use, k, C_in)
    win = jnp.pad(win, ((0, 0), (0, 0), (0, 0), (0, lt.g_pad * cpg - c_in)))
    win = win.reshape(b, t_use, k, lt.g_pad, cpg).transpose(0, 1, 3, 2, 4)
    win = win.reshape(b * t_use, lt.packs, lt.k_pack)
    return win.transpose(1, 0, 2)


class PackedLayer(NamedTuple):
    """Fold-time packed operands of one fused IMC layer.

    The block-diagonal weights and per-channel bias/flip are packed once
    (``pack_layer``) and MXU-lane padded, so the per-decision path only packs
    the data-dependent im2col patches — the programming of the SRAM arrays
    happens at fold time, not per decision.  ``layout`` is a static pytree
    node, so a PackedLayer passes transparently through jit."""

    layout: GroupPackLayout
    wp: jax.Array          # (packs, k_pad, n_pad) block-diagonal ±1 weights
    bias_p: jax.Array      # (packs, n_pad) word-line bias
    flip_p: jax.Array      # (packs, n_pad) BN-decoder sign (pad lanes = +1)


def _pad_axis(x: jax.Array, axis: int, mult: int, value: float = 0.0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def pack_layer(w: jax.Array, bias: jax.Array, flip: jax.Array,
               groups: int, lanes: int = 128) -> PackedLayer:
    """Pack one grouped layer's static operands for the fused kernel.

    Identical padding to what ops.fused_conv_mav applies per call, so the
    precomputed and on-the-fly paths are bit-identical."""
    k, cpg, c_out = w.shape
    layout = make_group_pack_layout(groups, c_out // groups, k, cpg, lanes)
    k_pad = -(-layout.k_pack // lanes) * lanes
    n_pad = -(-layout.n_pack // lanes) * lanes
    wp = _pad_axis(_pad_axis(pack_grouped_weights(w, layout), 1, k_pad),
                   2, n_pad)
    bias_p = _pad_axis(pack_channel_param(bias, layout), 1, n_pad)
    flip_p = _pad_axis(pack_channel_param(flip, layout, fill=1.0), 1, n_pad,
                       value=1.0)
    return PackedLayer(layout=layout, wp=wp, bias_p=bias_p, flip_p=flip_p)


# ---------------------------------------------------------------------------
# Macro allocation / utilization accounting (paper Fig 8, §V-A)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LayerMapping:
    name: str
    weight_bits: int
    products_per_output: int      # fan-in of one SA decision
    out_channels: int
    macros: int
    banks: int
    utilization: float            # temporal utilization (pooling idles layers)


def map_layer_to_macros(name: str, c_out: int, c_in_per_group: int, k: int,
                        utilization: float,
                        macro: IMCMacroConfig = DEFAULT_MACRO) -> LayerMapping:
    """Allocate IMC banks for one binary conv layer.

    Each output channel needs ceil(fan_in / rows_available) bank columns plus
    the BN bias word-line; banks are grouped 8-to-a-macro (each bank serves one
    output at a time, Fig 6).
    """
    fan_in = c_in_per_group * k
    rows_avail = macro.rows - macro.bias_rows
    banks_per_channel = max(1, -(-fan_in // rows_avail))
    # 64 columns per bank hold 64 output channels' worth of one weight row each;
    # capacity-wise a bank stores rows*cols bits.
    weight_bits = c_out * fan_in + c_out * macro.cols  # weights + bias lines
    banks = -(-weight_bits // (macro.rows * macro.cols))
    macros = -(-banks // macro.banks_per_macro)
    return LayerMapping(name=name, weight_bits=weight_bits,
                        products_per_output=fan_in, out_channels=c_out,
                        macros=macros, banks=banks, utilization=utilization)
