"""Per-hop decision logic: posterior smoothing + hysteresis + refractory.

One logit vector per hop is a noisy instantaneous view of a keyword; the
deployment-standard decision rule (as in the "Hello Edge" MCU pipeline and
the paper's decision-per-window semantics) smooths posteriors over a few
hops and gates triggers so one utterance fires exactly once:

* **smoothing** — the posterior is averaged over the last ``smooth`` hops
  (a ring of softmax outputs; the average divides by the number of hops
  actually seen, so young streams are not diluted by zero padding);
* **hysteresis** — after a trigger the detector disarms until the smoothed
  score falls below ``threshold_off``; it re-arms only then, so a keyword
  that stays above ``threshold_on`` across many hops fires once;
* **refractory** — a hard minimum of ``refractory`` hops between triggers,
  bounding the decision rate even with pathological score trajectories.

Everything is batched over streams (leading axis) and mask-aware: the
scheduler advances only the slots that actually hopped this step.
``decision_step`` is a pure function of ``(DecisionState, logits, mask)``,
so the compiled whole-tick fast path (repro.serving.compiled) scans it
unchanged right behind ``stream_step`` — the decision emitted inside a
fused K-tick block is bitwise the one the interpreted tick would emit.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class DecisionConfig:
    smooth: int = 5                 # hops of posterior smoothing
    threshold_on: float = 0.7       # smoothed posterior to fire
    threshold_off: float = 0.5      # re-arm level (hysteresis)
    refractory: int = 10            # min hops between triggers
    background_class: Optional[int] = None   # class that never triggers


jax.tree_util.register_static(DecisionConfig)


class DecisionState(NamedTuple):
    posteriors: jax.Array           # (B, smooth, K) softmax ring
    seen: jax.Array                 # (B,) hops accumulated (<= smooth)
    armed: jax.Array                # (B,) bool — hysteresis state
    refractory: jax.Array           # (B,) int32 hops until re-fire allowed
    last_kw: jax.Array              # (B,) int32 keyword of the last trigger


class DecisionOut(NamedTuple):
    trigger: jax.Array              # (B,) bool — keyword fired this hop
    keyword: jax.Array              # (B,) int32 argmax keyword
    score: jax.Array                # (B,) smoothed posterior of `keyword`
    posterior: jax.Array            # (B, K) smoothed posterior vector


def decision_init(n: int, num_classes: int,
                  dcfg: DecisionConfig = DecisionConfig()) -> DecisionState:
    return DecisionState(
        posteriors=jnp.zeros((n, dcfg.smooth, num_classes)),
        seen=jnp.zeros((n,), jnp.int32),
        armed=jnp.ones((n,), bool),
        refractory=jnp.zeros((n,), jnp.int32),
        last_kw=jnp.zeros((n,), jnp.int32))


@jax.named_scope("decision")
def decision_step(dcfg: DecisionConfig, state: DecisionState,
                  logits: jax.Array,
                  active: Optional[jax.Array] = None):
    """Advance the decision state with one hop of logits (B, K).

    ``active`` masks which streams actually hopped: inactive streams keep
    their state verbatim and never trigger.  Returns (new_state, DecisionOut).
    """
    b = logits.shape[0]
    if active is None:
        active = jnp.ones((b,), bool)
    post = jax.nn.softmax(logits, axis=-1)
    ring = jnp.concatenate([state.posteriors[:, 1:], post[:, None]], axis=1)
    seen = jnp.minimum(state.seen + 1, dcfg.smooth)
    smoothed = jnp.sum(ring, axis=1) / jnp.maximum(seen, 1)[:, None]

    scored = smoothed
    if dcfg.background_class is not None:
        scored = scored.at[:, dcfg.background_class].set(-jnp.inf)
    keyword = jnp.argmax(scored, axis=-1).astype(jnp.int32)
    score = jnp.take_along_axis(smoothed, keyword[:, None], axis=1)[:, 0]

    can_fire = (state.armed & (state.refractory == 0)
                & (score >= dcfg.threshold_on))
    trigger = can_fire & active
    # hysteresis tracks the *last-fired* keyword: re-arm when ITS smoothed
    # posterior decays below threshold_off (the utterance actually ended),
    # not when the instantaneous argmax moves elsewhere
    last_score = jnp.take_along_axis(smoothed, state.last_kw[:, None],
                                     axis=1)[:, 0]
    rearm = last_score <= dcfg.threshold_off
    new_armed = jnp.where(trigger, False, state.armed | rearm)
    new_refractory = jnp.where(trigger, dcfg.refractory,
                               jnp.maximum(state.refractory - 1, 0))
    new_last_kw = jnp.where(trigger, keyword, state.last_kw)

    mask = active
    new_state = DecisionState(
        posteriors=jnp.where(mask[:, None, None], ring, state.posteriors),
        seen=jnp.where(mask, seen, state.seen),
        armed=jnp.where(mask, new_armed, state.armed),
        refractory=jnp.where(mask, new_refractory, state.refractory),
        last_kw=jnp.where(mask, new_last_kw, state.last_kw))
    return new_state, DecisionOut(trigger=trigger, keyword=keyword,
                                  score=score, posterior=smoothed)


def reset_slot(state: DecisionState, slot: int) -> DecisionState:
    """Zero one slot's decision state (stream admission / eviction)."""
    return DecisionState(
        posteriors=state.posteriors.at[slot].set(0.0),
        seen=state.seen.at[slot].set(0),
        armed=state.armed.at[slot].set(True),
        refractory=state.refractory.at[slot].set(0),
        last_kw=state.last_kw.at[slot].set(0))
