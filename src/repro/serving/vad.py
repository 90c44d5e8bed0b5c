"""Voice-activity gating: the always-on power front end (paper §VI, Fig 16).

The chip's 14uJ/decision budget is an *always-on* story: leakage dominates
at 1 MHz, so the decisive lever is not making a decision cheaper but not
making one at all when nobody is speaking (DeltaKWS, arXiv 2405.03905,
reaches 36nJ/decision almost entirely on temporal sparsity).  This module
is the cheap digital detector that buys that sparsity: a per-hop
log-energy estimate, smoothed by an EMA and classified speech/silence
through hysteresis thresholds — the same smoothing + hysteresis shape as
the decision head (repro.serving.decision), because it plays the same
role one stage earlier.

Semantics per hop of audio:

* **level** — the hop's mean-square energy in dBFS, folded into an EMA
  (``ema`` keeps the detector from chattering on single quiet frames);
* **hysteresis** — silence -> speech at ``threshold_on_db``; speech ->
  silence only below ``threshold_off_db``, so a keyword whose energy dips
  mid-utterance is not cut;
* **hangover** — after the level falls below the off threshold the
  detector holds "speech" for ``hang`` more hops, covering trailing
  low-energy phonemes;
* **wake margin** — ``wake_margin`` is consumed by the scheduler, not
  here: the last ``wake_margin`` silent hops are buffered (deferred, not
  discarded) so a speech onset replays them through the real IMC path and
  no keyword prefix is lost to detector latency.

Everything is batched over streams (leading axis) and mask-aware, exactly
like the decision head; ``force`` pins the classification for tests and
for the gated-vs-ungated equivalence contract (``force="speech"`` must
make the gated scheduler bit-identical to the ungated one).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

_FLOOR_DB = -120.0                 # silence level the EMA starts from
_EPS = 1e-12                       # keeps log10 finite on all-zero hops


@dataclasses.dataclass(frozen=True)
class VADConfig:
    threshold_on_db: float = -40.0   # silence -> speech above this level
    threshold_off_db: float = -50.0  # speech -> silence below this level
    ema: float = 0.6                 # log-energy EMA (0 = no smoothing)
    hang: int = 2                    # hops speech is held after the level
    #                                  drops below threshold_off_db
    wake_margin: int = 2             # silent hops buffered for replay on a
    #                                  speech onset (scheduler-side)
    force: Optional[str] = None      # 'speech' | 'silence' override (tests,
    #                                  equivalence gate)

    def __post_init__(self):
        if self.force not in (None, "speech", "silence"):
            raise ValueError(f"force={self.force!r} must be None, "
                             f"'speech' or 'silence'")
        if self.threshold_off_db > self.threshold_on_db:
            raise ValueError("threshold_off_db must not exceed "
                             "threshold_on_db (hysteresis band)")
        if self.hang < 0 or self.wake_margin < 0:
            raise ValueError("hang and wake_margin must be >= 0")


jax.tree_util.register_static(VADConfig)


class VADState(NamedTuple):
    """Per-stream detector state (leading axis = batch of streams)."""

    level_db: jax.Array             # (B,) smoothed log-energy, dBFS
    speech: jax.Array               # (B,) bool — current classification
    hang: jax.Array                 # (B,) int32 hangover countdown
    seen: jax.Array                 # (B,) int32 hops observed


def vad_init(n: int) -> VADState:
    return VADState(level_db=jnp.full((n,), _FLOOR_DB),
                    speech=jnp.zeros((n,), bool),
                    hang=jnp.zeros((n,), jnp.int32),
                    seen=jnp.zeros((n,), jnp.int32))


def frame_energy_db(audio: jax.Array) -> jax.Array:
    """Mean-square energy of one hop in dBFS: (B, hop) -> (B,)."""
    return 10.0 * jnp.log10(jnp.mean(jnp.square(audio), axis=-1) + _EPS)


@jax.named_scope("vad")
def vad_step(vcfg: VADConfig, state: VADState, audio: jax.Array,
             active: Optional[jax.Array] = None
             ) -> Tuple[VADState, jax.Array]:
    """Classify one hop of audio (B, hop) per stream.

    ``active`` masks which streams actually have a fresh hop: inactive
    streams keep their state verbatim and report their previous
    classification.  Returns (new_state, speech_flags (B,) bool).
    """
    b = audio.shape[0]
    if active is None:
        active = jnp.ones((b,), bool)
    e = frame_energy_db(audio)
    level = jnp.where(state.seen > 0,
                      vcfg.ema * state.level_db + (1.0 - vcfg.ema) * e, e)
    # hysteresis: the live threshold depends on the current classification
    hot = jnp.where(state.speech,
                    level >= vcfg.threshold_off_db,
                    level >= vcfg.threshold_on_db)
    hang = jnp.where(hot, jnp.int32(vcfg.hang),
                     jnp.maximum(state.hang - 1, 0))
    # the pre-decrement counter gates the hold, so hang=N keeps speech for
    # exactly N hops after the level falls below threshold_off_db
    speech = hot | (state.speech & (state.hang > 0))
    if vcfg.force == "speech":
        speech = jnp.ones((b,), bool)
    elif vcfg.force == "silence":
        speech = jnp.zeros((b,), bool)

    new_state = VADState(
        level_db=jnp.where(active, level, state.level_db),
        speech=jnp.where(active, speech, state.speech),
        hang=jnp.where(active, hang, state.hang),
        seen=jnp.where(active, state.seen + 1, state.seen))
    return new_state, jnp.where(active, speech, state.speech)


def vad_scan(vcfg: VADConfig, state: VADState, audio: jax.Array,
             active: jax.Array) -> Tuple[VADState, jax.Array]:
    """Classify K hops in one ``lax.scan``: audio (K, B, hop) + active
    (K, B) -> (final state, speech flags (K, B)).

    One dispatch for a whole compiled serving block
    (repro.serving.compiled) instead of K ``vad_step`` calls; the body IS
    ``vad_step``, so the state trajectory and every flag are bit-identical
    to K sequential steps (the masked writes also make padded all-inactive
    rows/steps exact no-ops, which is what lets the block pad K up to a
    power of two without perturbing the detector)."""

    def body(st, xs):
        a, act = xs
        st, flags = vad_step(vcfg, st, a, act)
        return st, flags

    return jax.lax.scan(body, state, (audio, active))


def vad_reset_slot(state: VADState, slot: int) -> VADState:
    """Zero one slot's detector state (stream admission / eviction)."""
    return VADState(level_db=state.level_db.at[slot].set(_FLOOR_DB),
                    speech=state.speech.at[slot].set(False),
                    hang=state.hang.at[slot].set(0),
                    seen=state.seen.at[slot].set(0))
