"""What every model family's plain reference shares: the serving semantics
of a stream's hops and the decision head's smoothing.

It imports nothing of the program.  A family's ``stream_reference``
(``bench/families/<family>/``) takes each hop's fate from
``stream_fates`` and hands its network's logits at the computed hops to
``smoothed_decisions``:

* the VAD and the fate rules (wake margin, replay, fill) are
  re-implemented here from the serving semantics, in float64;
* per decided hop: a softmax, the mean over the last ``smooth`` decided
  hops, and the argmax and its score.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

# fates of a hop taken from a stream's buffer
COMPUTED, GATED, DEFERRED = 0, 1, 2

AMBIGUOUS_DB = 1e-3      # a level this close to a threshold is a float tie


def vad_flags(vad: dict, hops: np.ndarray):
    """Speech flags of consecutive hops (n, hop) of one stream, and whether
    any level came within ``AMBIGUOUS_DB`` of the threshold it was held to
    (there the program's float32 detector may decide either way)."""
    e = 10.0 * np.log10(np.mean(np.square(hops.astype(np.float64)),
                                axis=1) + 1e-12)
    level, speech, hang = -120.0, False, 0
    flags = np.zeros(len(hops), bool)
    ambiguous = False
    for j, ej in enumerate(e):
        level = ej if j == 0 else vad["ema"] * level + (1 - vad["ema"]) * ej
        thr = vad["threshold_off_db"] if speech else vad["threshold_on_db"]
        ambiguous |= abs(level - thr) < AMBIGUOUS_DB
        hot = level >= thr
        new_speech = hot or (speech and hang > 0)
        hang = vad["hang"] if hot else max(hang - 1, 0)
        speech = new_speech
        flags[j] = speech
    return flags, ambiguous


def fates(n_taken: int, speech: Optional[np.ndarray],
          wake_margin: int) -> np.ndarray:
    """Fate of hop 0 (the first window) and of each of the ``n_taken`` hops
    taken after it: computed, gated (aged out of the wake margin) or still
    deferred at the end."""
    out = np.full(n_taken + 1, COMPUTED, np.int8)
    if speech is None:
        return out
    pending: List[int] = []
    for j in range(n_taken):
        h = j + 1
        if speech[j]:
            out[pending] = COMPUTED   # replayed with this hop
            pending = []
        else:
            out[h] = DEFERRED
            pending.append(h)
            if len(pending) > wake_margin:
                out[pending.pop(0)] = GATED
    return out


def stream_fates(window: int, audio: np.ndarray, n_taken: int, hop: int,
                 vad: Optional[dict]):
    """The fates of a stream whose first ``window`` samples and ``n_taken``
    further hops are ``audio``, and whether its VAD was ambiguous."""
    speech, ambiguous = None, False
    if vad is not None:
        hops = np.asarray(audio[window:window + n_taken * hop]).reshape(
            n_taken, hop)
        speech, ambiguous = vad_flags(vad, hops)
    return fates(n_taken, speech, vad["wake_margin"] if vad else 0), ambiguous


def smoothed_decisions(logits, smooth: int, dtype):
    """(keyword, score, smoothed posterior) of consecutive decided hops'
    logits ``(n, classes)``: the softmax averaged over the last ``smooth``
    hops (fewer at the start).  Traced inside a family's jitted reference."""
    post = jax.nn.softmax(logits, axis=-1)
    n = post.shape[0]
    padded = jnp.concatenate([jnp.zeros((smooth - 1,) + post.shape[1:],
                                        dtype), post])
    ring = padded[0:n]
    for j in range(1, smooth):
        ring = ring + padded[j:j + n]
    seen = jnp.minimum(jnp.arange(1, n + 1), smooth).astype(dtype)
    smoothed = ring / seen[:, None]
    kw = jnp.argmax(smoothed, axis=-1)
    score = jnp.take_along_axis(smoothed, kw[:, None], axis=1)[:, 0]
    return kw, score.astype(jnp.float32), smoothed.astype(jnp.float32)
