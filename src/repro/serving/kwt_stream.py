"""Streaming KWT (``repro.models.kwt``): an MFCC frame ring and the whole
encoder per hop.

A hop of ``hop`` samples (a multiple of the 160-sample frame shift) adds
``hop / 160`` MFCC frames.  Frame ``f`` of a stream covers samples
``[160 f, 160 f + 480)`` and depends on nothing else, so a stream's state is
its last 320 samples (the part of the next frame already heard) and the
ring of its window's 98 frames, oldest first: a hop computes the new frames
from ``tail + audio``, shifts them into the ring and runs the encoder over
the ring.  The ring is exact — the window's frames computed from scratch
are the same numbers.  The encoder itself carries nothing across hops
(absolute positions, full attention), so every hop recomputes all 99
tokens.

``KWTEngine`` has ``StreamEngine``'s surface (``zeros_state``, ``init``,
``step``, ``multi_step`` and ``pure_step``, the step the compiled block's
scan calls), and ``StreamServer`` builds it when its config is a
``KWTConfig``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

from repro.models import kwt, mfcc


@dataclasses.dataclass(frozen=True)
class KWTGeometry:
    window: int
    hop: int
    frames: int              # MFCC frames in a window
    frames_per_hop: int      # fresh frames one hop adds
    tail: int                # samples carried into the next frame


jax.tree_util.register_static(KWTGeometry)


def make_geometry(cfg: kwt.KWTConfig, hop: int) -> KWTGeometry:
    """Raises unless ``hop`` is a positive multiple of the frame shift
    below the window (then the ring reuses whole frames)."""
    if hop <= 0 or hop % cfg.frame_shift or hop >= cfg.sample_len:
        raise ValueError(f"hop={hop} must be a positive multiple of the "
                         f"frame shift ({cfg.frame_shift}) below the "
                         f"window ({cfg.sample_len})")
    return KWTGeometry(window=cfg.sample_len, hop=hop, frames=cfg.n_frames,
                       frames_per_hop=hop // cfg.frame_shift,
                       tail=cfg.frame_len - cfg.frame_shift)


class KWTState(NamedTuple):
    """Per-stream state (leading axis = batch of streams)."""

    tail: jax.Array          # (B, frame_len - frame_shift) samples
    frames: jax.Array        # (B, frames, n_mfcc) MFCC ring, oldest first
    hop: jax.Array           # (B,) int32 decided windows


def zeros_state(cfg: kwt.KWTConfig, geom: KWTGeometry, n: int) -> KWTState:
    return KWTState(tail=jnp.zeros((n, geom.tail)),
                    frames=jnp.zeros((n, geom.frames, cfg.n_mfcc)),
                    hop=jnp.zeros((n,), jnp.int32))


def kwt_init(params: Dict, window: jax.Array, cfg: kwt.KWTConfig,
             geom: KWTGeometry):
    """A stream's first window (B, window): all its frames and the
    encoder.  Returns (logits, state)."""
    frames = mfcc.audio_mfcc(window, cfg)
    state = KWTState(tail=window[:, window.shape[1] - geom.tail:],
                     frames=frames,
                     hop=jnp.ones((window.shape[0],), jnp.int32))
    return kwt.forward(params, frames, cfg), state


def kwt_multi_step(params: Dict, state: KWTState, audio: jax.Array,
                   cfg: kwt.KWTConfig, geom: KWTGeometry, n_hops: int):
    """``n_hops`` consecutive hops, audio (B, n_hops * hop): the hops'
    frames in one MFCC call, then the encoder once per hop.  Returns
    (logits (B, n_hops, C), state)."""
    x = jnp.concatenate([state.tail, audio], axis=1)
    new = mfcc.audio_mfcc(x, cfg)
    ring = jnp.concatenate([state.frames, new], axis=1)
    k = geom.frames_per_hop
    logits = [kwt.forward(params, ring[:, (j + 1) * k:(j + 1) * k
                                       + geom.frames], cfg)
              for j in range(n_hops)]
    new_state = KWTState(tail=x[:, x.shape[1] - geom.tail:],
                         frames=ring[:, ring.shape[1] - geom.frames:],
                         hop=state.hop + n_hops)
    return jnp.stack(logits, axis=1), new_state


def kwt_step(params: Dict, state: KWTState, audio: jax.Array,
             cfg: kwt.KWTConfig, geom: KWTGeometry):
    """One hop, audio (B, hop) -> (logits (B, C), state)."""
    logits, new_state = kwt_multi_step(params, state, audio, cfg, geom, 1)
    return logits[:, 0], new_state


class KWTEngine:
    """Init/step over a fixed-size batch of streams, jit-compiled once;
    ``params`` as ``kwt.init_params`` gives them (the engine serves the
    ``compute_dtype`` copy)."""

    def __init__(self, params: Dict, cfg: kwt.KWTConfig, hop: int):
        self.cfg = cfg
        self.geom = geom = make_geometry(cfg, hop)
        self._hw = p = kwt.cast_params(params, cfg)
        self._kw: dict = {}
        self.pure_step = lambda s, a: kwt_step(p, s, a, cfg, geom)
        self._init = jax.jit(lambda w, k: kwt_init(p, w, cfg, geom))
        self._step = jax.jit(self.pure_step)
        self._multi: Dict[int, object] = {}

    def zeros_state(self, n: int) -> KWTState:
        return zeros_state(self.cfg, self.geom, n)

    def init(self, window: jax.Array, keys: jax.Array):
        """First full window (B, window) -> (logits, state); ``keys`` (the
        noise-field keys of the paper net's engine) are not read."""
        return self._init(window, keys)

    def step(self, state: KWTState, audio: jax.Array):
        """One hop (B, hop) -> (logits, state)."""
        return self._step(state, audio)

    def multi_step(self, state: KWTState, audio: jax.Array, n_hops: int):
        """``n_hops`` hops in one call -> (logits (B, n_hops, C), state)."""
        if n_hops not in self._multi:
            p, cfg, geom = self._hw, self.cfg, self.geom
            self._multi[n_hops] = jax.jit(
                lambda s, a: kwt_multi_step(p, s, a, cfg, geom, n_hops))
        return self._multi[n_hops](state, audio)
