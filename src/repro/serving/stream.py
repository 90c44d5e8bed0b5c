"""Frame-incremental streaming inference over the folded KWS model.

The paper's accelerator is *always-on*: it emits one decision per hop of a
sliding audio window.  Recomputing the whole window per decision (what
``hw_forward`` does) wastes exactly the work the 14uJ/decision budget
forbids — the overlap between consecutive windows is ``1 - hop/window`` of
every layer.  This module computes each hop incrementally:

* every layer's activation columns are indexed by *absolute time*.  When the
  hop is a multiple of ``hop_alignment(cfg)`` (the product of all strides
  and pool windows, 64 samples for the paper net), consecutive windows'
  overlapping columns are **identical** at every layer, pool pairs included,
  so cached columns can be reused verbatim;
* per hop, each layer only computes its tail: the hop's fresh columns plus a
  tiny carry — the k-1 conv overlap columns and, on layers whose conv length
  is odd, the one conv column the previous window's OR-maxpool truncated
  (that carry IS the pool ring state: the truncated column is recomputed and
  pooled next hop, exactly as the offline window would);
* SA noise is drawn from a **per-absolute-column field**
  (``fold_in(fold_in(stream_key, layer), abs_col)``), mirroring the silicon:
  each column is evaluated by the sense amplifier exactly once, and its
  realization rides along with the cached activation.  Column ``a`` of
  layer ``l`` yields the same (C_out,) realization no matter which hop or
  which code path evaluates it, so cached columns never need re-noising and
  offline windows can evaluate the same field (``window_sa_noise``) and
  feed it to ``hw_forward(sa_noise=...)`` — which is how the streaming path
  is test-enforced bit-identical to per-window ``hw_forward`` on every hop,
  noise and chip offsets included.  The per-hop evaluation is hoisted into
  ONE batched key derivation for all layers and streams
  (``hop_sa_noise_fields``), not a vmapped ``fold_in`` per layer.

``StreamEngine`` wraps init/step as jitted functions over a batch of
streams.  **One-launch-per-layer invariant:** a ``stream_step`` over a
batch of B streams issues exactly one fused ``pallas_call`` per IMC layer
(conv1..conv5) regardless of B — the scheduler
(repro.serving.scheduler) rides every live slot on the same launch, masked
slots included.  ``stream_multi_step`` advances n consecutive hops in the
same single launch per layer (each layer's tail just extends by the extra
hops' fresh columns) — the wake replay's batched drain.  Per-stream
customization (repro.serving.customize) rides two optional operands:
``bias_delta`` — integer compensated-bias deltas entering the kernel on
the pre-sign (noise) operand, exactly where the word-line bias lands —
and ``head_w``/``head_b``, a per-stream FC head; both are bit-exact
against refolding the params (integer adds; the GAP/FC math has no float
rounding on the fixed-point grids).  ``streaming=False`` selects the
recompute fallback, which calls ``hw_forward`` on the full window per hop
and is bit-identical to it by construction.

``gated_step`` is the voice-activity-gated no-op advance: a hop the VAD
(repro.serving.vad) classified as silence shifts the layer carries and the
GAP ring by their per-hop column counts **without launching any IMC
kernel** — the shifted-in columns are the folded net's constant
steady-state response to silent audio (``repro.models.kws.silence_columns``),
so the state geometry stays hop-exact while the chip sleeps (leakage-only
in the energy model, ``repro.core.energy.gated_energy_summary``).

Everything here is pure pytree-in / pytree-out over ``StreamState``, which
is what the compiled whole-tick fast path (repro.serving.compiled) relies
on: it puts ONE ``stream_step`` / ``gated_step`` pair inside a
``lax.scan`` body and fuses K ticks into a single dispatch — the scan
re-issues the same one-launch-per-layer step per tick at run time, so the
invariant (and bit-identity to K interpreted ticks) is structural, not
re-proved per block.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.quantize import ACT_Q
from repro.core.sa_noise import sa_noise_columns
from repro.models import kws

# ---------------------------------------------------------------------------
# Geometry: what each layer computes per hop
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LayerGeom:
    """Static per-layer streaming geometry (one hop).

    t_in/t_conv/t_out: the layer's full-window input / conv / post-pool
    lengths; d_in/d_out: fresh input/output columns per hop; conv_lo: local
    conv column where the per-hop tail starts (pool-aligned by
    construction); tail_in: input columns consumed per hop; carry =
    tail_in - d_in: columns cached across hops (conv overlap + pool phase).
    """

    t_in: int
    t_conv: int
    t_out: int
    d_in: int
    d_out: int
    conv_lo: int
    tail_in: int
    carry: int


@dataclasses.dataclass(frozen=True)
class StreamGeometry:
    window: int
    hop: int
    layers: Tuple[LayerGeom, ...]          # one per conv layer (0..5)

    @property
    def t_feat(self) -> int:
        """Final layer's pooled length — the GAP ring extent."""
        return self.layers[-1].t_out

    @property
    def d_feat(self) -> int:
        """Fresh final-layer columns per hop (GAP ring shift)."""
        return self.layers[-1].d_out


jax.tree_util.register_static(LayerGeom)
jax.tree_util.register_static(StreamGeometry)


def hop_alignment(cfg: kws.KWSConfig) -> int:
    """Smallest hop (in samples) with full column reuse: the product of all
    strides and pool windows (64 for the paper net).  Any multiple works."""
    a = 1
    for i in range(cfg.num_conv_layers):
        a *= cfg.strides[i] * cfg.pools[i]
    return a


def make_stream_geometry(cfg: kws.KWSConfig, hop: int) -> StreamGeometry:
    """Static per-layer tail/carry geometry for a hop size.

    Raises if ``hop`` is not a multiple of ``hop_alignment(cfg)`` (pool
    pairs would straddle hops and cached columns would go stale) or if the
    hop is too small to produce at least one fresh column everywhere."""
    align = hop_alignment(cfg)
    if hop % align or hop <= 0:
        raise ValueError(
            f"hop={hop} must be a positive multiple of {align} "
            f"(prod of strides*pools) for bit-exact column reuse")
    if hop >= cfg.sample_len:
        raise ValueError(f"hop={hop} must be smaller than the "
                         f"window ({cfg.sample_len})")
    layers = []
    t_in, d_in = cfg.sample_len, hop
    for i in range(cfg.num_conv_layers):
        k, s, p = cfg.kernels[i], cfg.strides[i], cfg.pools[i]
        t_conv = (t_in - k) // s + 1
        t_out = t_conv // p
        n_new = d_in // s                  # fresh conv columns per hop
        d_out = n_new // p
        assert d_in % s == 0 and n_new % p == 0, "hop_alignment violated"
        if d_out < 1 or d_out > t_out:
            raise ValueError(
                f"layer {i}: hop yields {d_out} fresh columns of {t_out} — "
                f"hop/window ratio unusable at this depth")
        conv_lo = p * (t_out - d_out)      # pool-aligned tail start
        tail_in = t_in - s * conv_lo
        layers.append(LayerGeom(t_in=t_in, t_conv=t_conv, t_out=t_out,
                                d_in=d_in, d_out=d_out, conv_lo=conv_lo,
                                tail_in=tail_in, carry=tail_in - d_in))
        t_in, d_in = t_out, d_out
    return StreamGeometry(window=cfg.sample_len, hop=hop,
                          layers=tuple(layers))


# ---------------------------------------------------------------------------
# Per-absolute-column SA-noise field (primitives live in repro.core.sa_noise
# — the hardware-model layer — so the offline oracle side can evaluate the
# same field without importing serving; this module keeps the hop-geometry
# views of it)
# ---------------------------------------------------------------------------


def window_sa_noise(key: jax.Array, cfg: kws.KWSConfig,
                    geom: StreamGeometry, hop_index,
                    std: float) -> Dict[str, jax.Array]:
    """The full-window view of the noise field: per-layer (1, t_conv, C)
    arrays for window ``hop_index``, in ``hw_forward(sa_noise=...)`` layout.
    Feeding this to hw_forward reproduces the streaming path bit-exactly —
    the offline oracle for the equivalence tests and the recompute engine's
    noise source."""
    noise = {}
    for i in range(1, cfg.num_conv_layers):
        lg = geom.layers[i]
        n_new = lg.d_out * cfg.pools[i]
        cols = hop_index * n_new + jnp.arange(lg.t_conv)
        noise[f"conv{i}"] = sa_noise_columns(key, i, cols, cfg.channels[i],
                                             std)[None]
    return noise


def _hop_sa_noise(keys: jax.Array, hops: jax.Array, layer: int,
                  cfg: kws.KWSConfig, geom: StreamGeometry,
                  std: float) -> jax.Array:
    """Field values for one hop's tail conv columns, batched over streams:
    keys (B, 2), hops (B,) -> (B, t_conv_tail, C).  Single-layer form —
    ``stream_step`` uses the cross-layer ``hop_sa_noise_fields`` hoist."""
    lg = geom.layers[layer]
    n_new = lg.d_out * cfg.pools[layer]
    n_tail = lg.t_conv - lg.conv_lo

    def one(key, hop):
        cols = hop * n_new + lg.conv_lo + jnp.arange(n_tail)
        return sa_noise_columns(key, layer, cols, cfg.channels[layer], std)

    return jax.vmap(one)(keys, hops)


def hop_sa_noise_fields(keys: jax.Array, hops: jax.Array,
                        cfg: kws.KWSConfig, geom: StreamGeometry,
                        std: float, n_hops: int = 1) -> Dict[str, jax.Array]:
    """All IMC layers' tail noise-field values for one hop in ONE batched
    key derivation: keys (B, 2), hops (B,) -> {conv_i: (B, n_tail_i, C_i)}.

    ``n_hops > 1`` extends each layer's tail to cover a run of consecutive
    hops starting at ``hops`` (the wake-replay batching: the deferred
    silent hops plus the onset hop advance in ONE multi-hop launch).  The
    field itself is per-absolute-column, so the multi-hop evaluation is
    bit-identical to evaluating the same columns hop by hop.

    Bit-identical to calling ``_hop_sa_noise`` per layer (the field is
    unchanged), but the ``fold_in(fold_in(key, layer), col)`` chain for
    every (layer, column) pair of the hop is flattened into a single
    vmapped hash over ~sum(n_tail_i) elements instead of one tiny vmapped
    draw per layer per hop — the cross-stream dedup the ROADMAP called out
    (~5 separate key-derivation kernels per hop per stream batch in noisy
    mode).  The per-layer normal draws remain separate because each
    layer's channel width differs (threefry draws are not prefix-stable
    across shapes, so a single padded draw would change the field)."""
    specs = []                       # (layer, n_tail, c_out) + static cols
    col_chunks = []
    lid_chunks = []
    for i in range(1, cfg.num_conv_layers):
        lg = geom.layers[i]
        n_new = lg.d_out * cfg.pools[i]
        n_tail = lg.t_conv - lg.conv_lo + (n_hops - 1) * n_new
        specs.append((i, n_tail, cfg.channels[i]))
        col_chunks.append((n_new, lg.conv_lo, n_tail))
        lid_chunks.append(jnp.full((n_tail,), i, jnp.int32))
    layer_ids = jnp.concatenate(lid_chunks)            # (sum n_tail,)

    def one_stream(key, hop):
        cols = jnp.concatenate([
            hop * n_new + lo + jnp.arange(n)
            for (n_new, lo, n) in col_chunks])
        flat_keys = jax.vmap(
            lambda l, a: jax.random.fold_in(jax.random.fold_in(key, l), a)
        )(layer_ids, cols)                             # one batched hash
        out, base = {}, 0
        for (i, n_tail, c_out) in specs:
            ks = flat_keys[base:base + n_tail]
            with jax.named_scope(f"conv{i}"):
                out[f"conv{i}"] = std * jax.vmap(
                    lambda k: jax.random.normal(k, (c_out,)))(ks)
            base += n_tail
        return out

    return jax.vmap(one_stream)(keys, hops)


# ---------------------------------------------------------------------------
# Stream state + init/step
# ---------------------------------------------------------------------------


class StreamState(NamedTuple):
    """Per-stream incremental state (leading axis = batch of streams).

    ``audio_carry``/``carries`` are the layers' ring tails (the only
    activation columns that must survive a hop); ``ring`` is the final
    layer's full pooled window, feeding GAP; ``hop`` counts decided windows
    (window t's columns live at absolute index t*shift + local); ``key`` is
    the per-stream noise-field key."""

    audio_carry: jax.Array                 # (B, carry_0) raw samples
    carries: Tuple[jax.Array, ...]         # (B, carry_i, C_{i-1}), i=1..
    ring: jax.Array                        # (B, t_feat, C_last)
    hop: jax.Array                         # (B,) int32
    key: jax.Array                         # (B, 2) uint32


class WindowState(NamedTuple):
    """Recompute-fallback state: the raw audio window only."""

    window: jax.Array                      # (B, window)
    hop: jax.Array                         # (B,) int32
    key: jax.Array                         # (B, 2) uint32


def zeros_state(cfg: kws.KWSConfig, geom: StreamGeometry,
                n: int) -> StreamState:
    carries = tuple(
        jnp.zeros((n, geom.layers[i].carry, cfg.channels[i - 1]))
        for i in range(1, cfg.num_conv_layers))
    return StreamState(
        audio_carry=jnp.zeros((n, geom.layers[0].carry)),
        carries=carries,
        ring=jnp.zeros((n, geom.t_feat, cfg.channels[-1])),
        hop=jnp.zeros((n,), jnp.int32),
        key=jnp.zeros((n, 2), jnp.uint32))


def zeros_window_state(cfg: kws.KWSConfig, n: int) -> WindowState:
    return WindowState(window=jnp.zeros((n, cfg.sample_len)),
                       hop=jnp.zeros((n,), jnp.int32),
                       key=jnp.zeros((n, 2), jnp.uint32))


def _tail(x: jax.Array, n: int) -> jax.Array:
    """Last ``n`` columns of axis 1 — unlike ``x[:, -n:]`` this stays an
    empty slice when a layer's carry is 0 (k == stride, no pool phase)."""
    return x[:, x.shape[1] - n:]


def _gap_fc(hw: kws.HWParams, ring: jax.Array):
    feats = ACT_Q.quantize(jnp.mean(ring, axis=1))
    return feats @ hw.fc_w + hw.fc_b, feats


def _ring_logits(hwp: kws.HWParams, ring: jax.Array,
                 head_w: Optional[jax.Array],
                 head_b: Optional[jax.Array]) -> jax.Array:
    """GAP + FC with an optional per-stream head: ``head_w`` (B, D, C) /
    ``head_b`` (B, C) replace the shared folded FC for every stream (the
    scheduler broadcasts the base head into the rows of uncustomized
    slots, so only hot-swapped slots actually diverge).  The per-row
    matvec is the same contraction the shared matmul performs row-wise, so
    a row whose head equals the base head produces the base logits."""
    with jax.named_scope("head"):
        if head_w is None:
            return _gap_fc(hwp, ring)[0]
        feats = ACT_Q.quantize(jnp.mean(ring, axis=1))
        return jax.vmap(lambda f, w, b: f @ w + b)(feats, head_w, head_b)


def _merge_bias_delta(noise: Optional[jax.Array],
                      delta: Optional[jax.Array],
                      n_cols: int) -> Optional[jax.Array]:
    """Fold a per-stream bias delta (B, C) into the per-column pre-SA
    operand (B, n_cols, C).  The fused kernel adds this operand exactly
    where the word-line bias lands (pre-sign), so an integer delta rides
    the existing SA-noise input and a customized stream's IMC layers run in
    the SAME batched launch as every other slot — per-slot compensated
    biases without per-slot kernels.  With no SA noise the operand is the
    broadcast delta alone (integers: bit-exact vs refolding the bias)."""
    if delta is None:
        return noise
    d = delta[:, None, :]
    if noise is None:
        return jnp.broadcast_to(d, (delta.shape[0], n_cols, delta.shape[1]))
    return noise + d


def stream_init(hw, window: jax.Array, keys: jax.Array,
                cfg: kws.KWSConfig, geom: StreamGeometry, *,
                chip_offsets: Optional[Dict[str, jax.Array]] = None,
                sa_noise_std: float = 0.0,
                use_kernel: bool = True,
                bias_delta: Optional[Dict[str, jax.Array]] = None,
                head_w: Optional[jax.Array] = None,
                head_b: Optional[jax.Array] = None):
    """Process a stream's first full window (B, window) and build its
    incremental state.  Equivalent to hw_forward on the window (hop 0 of
    the noise field), plus capturing each layer's ring tail.

    ``bias_delta`` ({conv_i: (B, C_i)}) and ``head_w``/``head_b`` are the
    per-stream customization riders (repro.serving.customize): integer
    bias deltas from bias compensation and a fine-tuned FC head, applied
    per batch row."""
    hwp, packed = kws.as_hw_params(hw)
    b = window.shape[0]
    hops0 = jnp.zeros((b,), jnp.int32)
    h = window[..., None]
    carries = []
    for i in range(cfg.num_conv_layers):
        with jax.named_scope(f"conv{i}"):
            noise = off = packed_i = None
            if i > 0:
                carries.append(_tail(h, geom.layers[i].carry))
                lg = geom.layers[i]
                if sa_noise_std > 0.0:
                    cols = jnp.arange(lg.t_conv)
                    noise = jax.vmap(lambda k: sa_noise_columns(
                        k, i, cols, cfg.channels[i], sa_noise_std))(keys)
                if bias_delta is not None:
                    noise = _merge_bias_delta(noise, bias_delta[f"conv{i}"],
                                              lg.t_conv)
                if chip_offsets is not None:
                    off = chip_offsets[f"conv{i}"]
                packed_i = packed[f"conv{i}"] if packed else None
            h = kws.hw_conv_layer(hwp, i, h, cfg, packed=packed_i,
                                  chip_offset=off, sa_noise=noise,
                                  use_kernel=use_kernel)
    logits = _ring_logits(hwp, h, head_w, head_b)
    state = StreamState(audio_carry=_tail(window, geom.layers[0].carry),
                        carries=tuple(carries), ring=h,
                        hop=hops0 + 1, key=keys)
    return logits, state


def _stream_advance(hw, state: StreamState, audio: jax.Array,
                    cfg: kws.KWSConfig, geom: StreamGeometry, n_hops: int, *,
                    chip_offsets, sa_noise_std, use_kernel, bias_delta,
                    head_w, head_b):
    """Shared body of ``stream_step`` / ``stream_multi_step``: advance a
    batch of streams by ``n_hops`` consecutive hops with ONE fused-kernel
    launch per IMC layer — each layer's tail simply extends by the extra
    hops' fresh columns, and the per-absolute-column noise field covers
    the extended tail (``hop_sa_noise_fields(n_hops=...)``).  Returns
    (per-hop logits [(B, C)] * n_hops, new state)."""
    hwp, packed = kws.as_hw_params(hw)
    with jax.named_scope("conv0"):
        x = jnp.concatenate([state.audio_carry, audio], axis=1)
        new_audio_carry = _tail(x, geom.layers[0].carry)
        h = kws.hw_conv_layer(hwp, 0, x[..., None], cfg)
    noise_all = None
    if sa_noise_std > 0.0:
        noise_all = hop_sa_noise_fields(state.key, state.hop, cfg, geom,
                                        sa_noise_std, n_hops=n_hops)
    new_carries = []
    for i in range(1, cfg.num_conv_layers):
        lg = geom.layers[i]
        name = f"conv{i}"
        with jax.named_scope(name):
            inp = jnp.concatenate([state.carries[i - 1], h], axis=1)
            new_carries.append(_tail(inp, lg.carry))
            noise = noise_all[name] if noise_all is not None else None
            if bias_delta is not None:
                t_conv_tail = ((inp.shape[1] - cfg.kernels[i])
                               // cfg.strides[i] + 1)
                noise = _merge_bias_delta(noise, bias_delta[name],
                                          t_conv_tail)
            off = chip_offsets[name] if chip_offsets is not None else None
            if use_kernel:
                from repro.kernels.imc_mav import ops as mav_ops
                h = mav_ops.fused_conv_mav_step(
                    inp, hwp.w_bin[name], hwp.bias[name], hwp.flip[name],
                    groups=cfg.groups(i), stride=cfg.strides[i],
                    pool=cfg.pools[i], chip_offset=off, sa_noise=noise,
                    packed=packed[name] if packed else None)
            else:
                h = kws.hw_conv_layer(hwp, i, inp, cfg, chip_offset=off,
                                      sa_noise=noise, use_kernel=False)
    logits_hops = []
    for j in range(1, n_hops + 1):
        with jax.named_scope("gap"):
            ring = jnp.concatenate([state.ring, h[:, :j * geom.d_feat]],
                                   axis=1)[:, -geom.t_feat:]
        logits_hops.append(_ring_logits(hwp, ring, head_w, head_b))
    new_state = StreamState(audio_carry=new_audio_carry,
                            carries=tuple(new_carries), ring=ring,
                            hop=state.hop + n_hops, key=state.key)
    return logits_hops, new_state


def stream_step(hw, state: StreamState, audio: jax.Array,
                cfg: kws.KWSConfig, geom: StreamGeometry, *,
                chip_offsets: Optional[Dict[str, jax.Array]] = None,
                sa_noise_std: float = 0.0,
                use_kernel: bool = True,
                bias_delta: Optional[Dict[str, jax.Array]] = None,
                head_w: Optional[jax.Array] = None,
                head_b: Optional[jax.Array] = None):
    """Advance a batch of streams by one hop: audio (B, hop) -> (logits,
    new state).  Each layer computes only its tail (carry + fresh columns)
    — one fused-kernel launch per IMC layer for the whole batch — and the
    decision is re-formed from the GAP ring.  Bit-identical to hw_forward
    on the corresponding full window (the equivalence tests drive both).
    ``bias_delta``/``head_w``/``head_b`` are the per-stream customization
    riders (see ``stream_init``)."""
    logits_hops, new_state = _stream_advance(
        hw, state, audio, cfg, geom, 1, chip_offsets=chip_offsets,
        sa_noise_std=sa_noise_std, use_kernel=use_kernel,
        bias_delta=bias_delta, head_w=head_w, head_b=head_b)
    return logits_hops[0], new_state


def stream_multi_step(hw, state: StreamState, audio: jax.Array,
                      cfg: kws.KWSConfig, geom: StreamGeometry,
                      n_hops: int, *,
                      chip_offsets: Optional[Dict[str, jax.Array]] = None,
                      sa_noise_std: float = 0.0,
                      use_kernel: bool = True,
                      bias_delta: Optional[Dict[str, jax.Array]] = None,
                      head_w: Optional[jax.Array] = None,
                      head_b: Optional[jax.Array] = None):
    """Advance by ``n_hops`` consecutive hops in ONE fused-kernel launch
    per IMC layer: audio (B, n_hops*hop) -> (logits (B, n_hops, C), new
    state).  Bit-identical to ``n_hops`` sequential ``stream_step`` calls
    (same columns, same per-absolute-column noise realizations — the
    columns are just computed in one tail instead of n) — the VAD wake
    replay uses this to drain its deferred hops in one launch instead of
    one launch per deferred hop."""
    logits_hops, new_state = _stream_advance(
        hw, state, audio, cfg, geom, n_hops, chip_offsets=chip_offsets,
        sa_noise_std=sa_noise_std, use_kernel=use_kernel,
        bias_delta=bias_delta, head_w=head_w, head_b=head_b)
    return jnp.stack(logits_hops, axis=1), new_state


def window_init(hw, window: jax.Array, keys: jax.Array,
                cfg: kws.KWSConfig, geom: StreamGeometry, *,
                chip_offsets=None, sa_noise_std: float = 0.0,
                use_kernel: bool = True, bias_delta=None,
                head_w=None, head_b=None):
    """Recompute-fallback init: hw_forward on the first window."""
    logits, state = _window_forward(hw, window, keys,
                                    jnp.zeros((window.shape[0],), jnp.int32),
                                    cfg, geom, chip_offsets=chip_offsets,
                                    sa_noise_std=sa_noise_std,
                                    use_kernel=use_kernel,
                                    bias_delta=bias_delta,
                                    head_w=head_w, head_b=head_b)
    return logits, state


def window_step(hw, state: WindowState, audio: jax.Array,
                cfg: kws.KWSConfig, geom: StreamGeometry, *,
                chip_offsets=None, sa_noise_std: float = 0.0,
                use_kernel: bool = True, bias_delta=None,
                head_w=None, head_b=None):
    """Recompute-fallback hop: slide the audio window, rerun hw_forward on
    all of it.  Bit-identical to the streaming path (same noise field),
    just ~window/hop times the work — the baseline --streaming benches
    against."""
    window = jnp.concatenate([state.window[:, geom.hop:], audio], axis=1)
    return _window_forward(hw, window, state.key, state.hop, cfg, geom,
                           chip_offsets=chip_offsets,
                           sa_noise_std=sa_noise_std, use_kernel=use_kernel,
                           bias_delta=bias_delta, head_w=head_w,
                           head_b=head_b)


def window_multi_step(hw, state: WindowState, audio: jax.Array,
                      cfg: kws.KWSConfig, geom: StreamGeometry,
                      n_hops: int, *, chip_offsets=None,
                      sa_noise_std: float = 0.0, use_kernel: bool = True,
                      bias_delta=None, head_w=None, head_b=None):
    """Recompute-fallback twin of ``stream_multi_step``: ``n_hops``
    sequential full-window recomputes in one call — the recompute path has
    no launch-count story to improve, so this only unifies the scheduler's
    wake-replay entry.  Returns (logits (B, n_hops, C), state)."""
    logits = []
    for j in range(n_hops):
        lg, state = window_step(hw, state,
                                audio[:, j * geom.hop:(j + 1) * geom.hop],
                                cfg, geom, chip_offsets=chip_offsets,
                                sa_noise_std=sa_noise_std,
                                use_kernel=use_kernel,
                                bias_delta=bias_delta, head_w=head_w,
                                head_b=head_b)
        logits.append(lg)
    return jnp.stack(logits, axis=1), state


def _window_forward(hw, window, keys, hops, cfg, geom, *, chip_offsets,
                    sa_noise_std, use_kernel, bias_delta=None,
                    head_w=None, head_b=None):
    noise = None
    if sa_noise_std > 0.0:
        per_layer = jax.vmap(
            lambda k, t: window_sa_noise(k, cfg, geom, t, sa_noise_std))(
                keys, hops)
        noise = {name: v[:, 0] for name, v in per_layer.items()}
    if bias_delta is not None:
        b = window.shape[0]
        noise = dict(noise) if noise is not None else {}
        for i in range(1, cfg.num_conv_layers):
            name = f"conv{i}"
            noise[name] = _merge_bias_delta(noise.get(name),
                                            bias_delta[name],
                                            geom.layers[i].t_conv)
    logits, feats = kws.hw_forward(hw, window, cfg,
                                   chip_offsets=chip_offsets,
                                   sa_noise_std=sa_noise_std, sa_noise=noise,
                                   use_kernel=use_kernel)
    if head_w is not None:
        logits = jax.vmap(lambda f, w, b: f @ w + b)(feats, head_w, head_b)
    return logits, WindowState(window=window, hop=hops + 1, key=keys)


# ---------------------------------------------------------------------------
# Voice-activity-gated no-op advance (no IMC launch)
# ---------------------------------------------------------------------------


def silence_fills(cfg: kws.KWSConfig,
                  sil: Dict[str, jax.Array]) -> Tuple[jax.Array, ...]:
    """Order the per-layer silence columns (``kws.silence_columns``) into
    the tuple ``gated_step`` consumes: fills[i] is the constant (C_i,)
    steady-state output column of conv layer i on silent audio — the value
    shifted into layer i+1's carry (and, for the last layer, the GAP ring)
    on a gated hop."""
    return tuple(sil[f"conv{i}"] for i in range(cfg.num_conv_layers))


def retention_fills(hw, cfg: kws.KWSConfig, *, key: jax.Array,
                    sa_noise_std: float,
                    chip_offsets: Optional[Dict[str, jax.Array]] = None
                    ) -> Tuple[jax.Array, ...]:
    """SA-retention ("comfort noise") silence fills: the chip-accurate
    alternative to the noiseless constant of ``silence_fills``.

    ``kws.silence_columns`` models a gated hop as the *ideal* constant
    response to silence — correct for an array whose outputs are recomputed
    on wake.  On silicon the sleeping macros instead *retain* the last
    latched sense-amplifier read of the silent input, which carries one
    frozen SA-noise realization: each layer's fill is its silence response
    evaluated once WITH a deterministic SA read (one noise draw per layer,
    derived from ``key``), and that retained column — not the fresh ideal
    one — feeds the next layer's retention evaluation.  Deterministic in
    ``key``, so gated advances stay reproducible and snapshot-safe.  With
    ``sa_noise_std=0`` this degenerates to exactly ``silence_fills``
    (the default the tests pin)."""
    hwp, _ = kws.as_hw_params(hw)
    h = jnp.zeros((1, cfg.sample_len, 1))
    fills = []
    for i in range(cfg.num_conv_layers):
        off = sa_key = None
        if i > 0:
            if chip_offsets is not None:
                off = chip_offsets[f"conv{i}"]
            if sa_noise_std > 0.0:
                sa_key = jax.random.fold_in(key, i)
        h = kws.hw_conv_layer(hwp, i, h, cfg, chip_offset=off,
                              sa_key=sa_key, sa_noise_std=sa_noise_std,
                              use_kernel=False)
        col = h[0, 0]
        fills.append(col)
        # the retained column is what downstream layers see while asleep
        h = jnp.broadcast_to(col, (1, h.shape[1], col.shape[0]))
    return tuple(fills)


@jax.named_scope("fill")
def gated_step(state: StreamState, cfg: kws.KWSConfig, geom: StreamGeometry,
               fills: Tuple[jax.Array, ...]) -> StreamState:
    """Advance a batch of streams by one *silent* hop without computing.

    The VAD classified the hop as silence, so no IMC kernel launches:
    every carry and the GAP ring shift by their per-hop column counts, the
    shifted-in columns being each layer's constant response to silent
    audio (valid convolutions of a constant input are constant, so the
    fill is a single (C_i,) vector per layer).  The audio carry shifts in
    zeros (the unsampled microphone).  ``hop`` still advances, keeping the
    absolute-column noise field aligned for the next computed hop.

    This is the energy model's leakage-only hop: the only digital activity
    is the VAD front end (see ``repro.core.energy.gated_energy_summary``).
    On all-speech audio ``gated_step`` never runs, which is why gating with
    the VAD forced to "speech" stays bit-identical to ungated streaming.

    Each ``fills`` entry is either a shared (C_i,) silence column or a
    per-stream (B, C_i) one — hot-swapped slots carry compensated biases,
    so their silence response differs from the base chip's
    (repro.serving.customize recomputes it at swap time)."""
    b = state.hop.shape[0]

    def _fill(f, d):
        if f.ndim == 1:
            return jnp.broadcast_to(f, (b, d, f.shape[0]))
        return jnp.broadcast_to(f[:, None, :], (b, d, f.shape[-1]))

    audio_carry = _tail(
        jnp.concatenate([state.audio_carry,
                         jnp.zeros((b, geom.hop))], axis=1),
        geom.layers[0].carry)
    new_carries = []
    for i in range(1, cfg.num_conv_layers):
        lg = geom.layers[i]
        new_carries.append(_tail(
            jnp.concatenate([state.carries[i - 1],
                             _fill(fills[i - 1], lg.d_in)], axis=1),
            lg.carry))
    ring_fill = _fill(fills[-1], geom.d_feat)
    ring = jnp.concatenate([state.ring[:, geom.d_feat:], ring_fill], axis=1)
    return StreamState(audio_carry=audio_carry, carries=tuple(new_carries),
                       ring=ring, hop=state.hop + 1, key=state.key)


def gated_window_step(state: WindowState, geom: StreamGeometry
                      ) -> WindowState:
    """Recompute-fallback twin of ``gated_step``: slide the raw window by
    one hop of zeros (silence) without running hw_forward."""
    b = state.hop.shape[0]
    window = jnp.concatenate(
        [state.window[:, geom.hop:], jnp.zeros((b, geom.hop))], axis=1)
    return WindowState(window=window, hop=state.hop + 1, key=state.key)


# ---------------------------------------------------------------------------
# Jitted engine over a fixed batch of streams
# ---------------------------------------------------------------------------


class StreamEngine:
    """Init/step over a fixed-size batch of streams, jit-compiled once.

    ``streaming=True`` runs the frame-incremental path; ``streaming=False``
    the recompute fallback (full hw_forward per hop, bit-identical by
    construction).  The scheduler (repro.serving.scheduler) owns slots,
    masking and admission; this class owns the pure compute."""

    def __init__(self, hw, cfg: kws.KWSConfig, hop: int, *,
                 chip_offsets: Optional[Dict[str, jax.Array]] = None,
                 sa_noise_std: float = 0.0, use_kernel: bool = True,
                 streaming: bool = True):
        self.cfg = cfg
        self.geom = make_stream_geometry(cfg, hop)
        self.streaming = streaming
        kw = dict(chip_offsets=chip_offsets, sa_noise_std=sa_noise_std,
                  use_kernel=use_kernel)
        self._kw = kw
        self._hw = hw
        init = stream_init if streaming else window_init
        step = stream_step if streaming else window_step
        geom = self.geom
        self._init = jax.jit(lambda w, k: init(hw, w, k, cfg, geom, **kw))
        # the pure step the compiled block's scan calls (repro.serving.
        # compiled), and its jitted form
        self.pure_step = lambda s, a: step(hw, s, a, cfg, geom, **kw)
        self._step = jax.jit(self.pure_step)
        # customized (per-stream bias delta + head) and multi-hop variants,
        # jitted on first use so the plain serving path never pays for them
        self._init_cust = None
        self._step_cust = None
        self._multi: Dict[int, object] = {}
        self._multi_cust: Dict[int, object] = {}

    def zeros_state(self, n: int):
        if self.streaming:
            return zeros_state(self.cfg, self.geom, n)
        return zeros_window_state(self.cfg, n)

    def init(self, window: jax.Array, keys: jax.Array):
        """First full window (B, window) -> (logits, state)."""
        return self._init(window, keys)

    def step(self, state, audio: jax.Array):
        """One hop (B, hop) -> (logits, state)."""
        return self._step(state, audio)

    def init_custom(self, window: jax.Array, keys: jax.Array,
                    bias_delta, head_w, head_b):
        """``init`` with the per-stream customization riders."""
        if self._init_cust is None:
            hw, cfg, geom, kw = self._hw, self.cfg, self.geom, self._kw
            fn = stream_init if self.streaming else window_init
            self._init_cust = jax.jit(
                lambda w, k, d, hwt, hb: fn(hw, w, k, cfg, geom, **kw,
                                            bias_delta=d, head_w=hwt,
                                            head_b=hb))
        return self._init_cust(window, keys, bias_delta, head_w, head_b)

    def step_custom(self, state, audio: jax.Array, bias_delta,
                    head_w, head_b):
        """``step`` with the per-stream customization riders — still one
        fused-kernel launch per IMC layer for the whole batch."""
        if self._step_cust is None:
            hw, cfg, geom, kw = self._hw, self.cfg, self.geom, self._kw
            fn = stream_step if self.streaming else window_step
            self._step_cust = jax.jit(
                lambda s, a, d, hwt, hb: fn(hw, s, a, cfg, geom, **kw,
                                            bias_delta=d, head_w=hwt,
                                            head_b=hb))
        return self._step_cust(state, audio, bias_delta, head_w, head_b)

    def multi_step(self, state, audio: jax.Array, n_hops: int,
                   bias_delta=None, head_w=None, head_b=None):
        """``n_hops`` hops in one call — and, on the streaming path, one
        fused-kernel launch per IMC layer (the wake-replay batching).
        Returns (logits (B, n_hops, C), state)."""
        hw, cfg, geom, kw = self._hw, self.cfg, self.geom, self._kw
        fn = stream_multi_step if self.streaming else window_multi_step
        if bias_delta is None and head_w is None:
            if n_hops not in self._multi:
                self._multi[n_hops] = jax.jit(
                    lambda s, a: fn(hw, s, a, cfg, geom, n_hops, **kw))
            return self._multi[n_hops](state, audio)
        if n_hops not in self._multi_cust:
            self._multi_cust[n_hops] = jax.jit(
                lambda s, a, d, hwt, hb: fn(hw, s, a, cfg, geom, n_hops,
                                            **kw, bias_delta=d, head_w=hwt,
                                            head_b=hb))
        return self._multi_cust[n_hops](state, audio, bias_delta, head_w,
                                        head_b)


# ---------------------------------------------------------------------------
# Work accounting (feeds core.energy's streaming report)
# ---------------------------------------------------------------------------


def streaming_layer_stats(cfg: kws.KWSConfig, geom: StreamGeometry):
    """Per-decision op counts of the *streaming* path, same schema as
    ``kws.layer_stats``: each conv layer only touches its tail columns, so
    MACs / SRAM traffic / controller cycles scale by the tail fraction.
    The GAP+FC row is unchanged (it runs in full every decision)."""
    base = kws.layer_stats(cfg)
    out = []
    for i, s in enumerate(base):
        if i >= cfg.num_conv_layers:        # gap+fc row
            out.append(dict(s))
            continue
        lg = geom.layers[i]
        frac = (lg.t_conv - lg.conv_lo) / lg.t_conv
        cin = 1 if i == 0 else cfg.channels[i - 1]
        out.append({
            **s,
            "macs": int(round(s["macs"] * frac)),
            "in_bits": int(lg.tail_in * cin * (8 if i == 0 else 1)),
            "out_bits": int(lg.d_out * cfg.channels[i]),
            "cycles": int(round(s["cycles"] * frac)),
        })
    return out
