"""Traffic generator: per-stream microphone audio and arrival phases.

One general generator reads a traffic file (``bench/traffic/<name>.json``)
and the run's seed.  Every stream reads one shared *audio bank* circularly
from its own offset, so a stream's audio is a pure function of (traffic,
seed, stream index) and the reference can rebuild it after the window.

Bank kinds:

* ``speech`` — back-to-back 1 s keyword clips over a -40 dBFS noise floor
  (always speech);
* ``duty`` — the same clips placed over the noise floor with gaps, so that
  clips cover ``duty`` of the bank.  The gap lengths are one fixed set for
  every seed, drawn in a seeded order, so every seed gives the same amount
  of speech and the same number of wakes per stream.

All audio is on the 8-bit grid ``k / 127`` (the paper's raw audio input).

The keyword synthesizer is a copy of the one in ``repro.data.audio`` (the
program's synthetic GSCD stand-in), kept here so that a change to the
program cannot change the benchmark's traffic.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

SAMPLE_RATE = 16_000
NUM_CLASSES = 10
NOISE_STD = 0.01          # -40 dBFS noise floor, as chip_smoke.py uses


@dataclasses.dataclass(frozen=True)
class Speaker:
    pitch: float
    formant_scale: float
    tempo: float
    noise_floor: float


def _speaker(rng: np.random.Generator) -> Speaker:
    return Speaker(pitch=float(rng.uniform(95, 240)),
                   formant_scale=float(rng.uniform(0.95, 1.05)),
                   tempo=float(rng.uniform(0.92, 1.08)),
                   noise_floor=float(rng.uniform(0.002, 0.006)))


def _class_segments(c: int) -> list:
    base = 1050.0 * (1.23 ** c)
    segs = []
    for j in range(2 + (c % 2)):
        f1 = base * (1.0 + 0.10 * j)
        f2 = min(f1 * 1.55, 7500.0)
        segs.append((f1, f2, (-1) ** (c + j) * 0.12,
                     4.0 + 3.0 * ((c * 3 + j) % 4)))
    return segs


def keyword_clip(c: int, spk: Speaker, rng: np.random.Generator,
                 length: int = SAMPLE_RATE) -> np.ndarray:
    """One augmented keyword utterance of class ``c``, peak 0.9, float64."""
    segs = _class_segments(c)
    dur = int(0.55 * length / spk.tempo)
    seg_len = max(8, min(dur, length) // len(segs))
    sig = np.zeros(length, dtype=np.float64)
    start = max(0, (length - seg_len * len(segs)) // 2)
    t = np.arange(seg_len) / SAMPLE_RATE
    for j, (f1, f2, chirp, am) in enumerate(segs):
        f1 *= spk.formant_scale
        f2 *= spk.formant_scale
        env = np.sin(np.pi * np.arange(seg_len) / seg_len) ** 2
        ph1 = 2 * np.pi * np.cumsum(f1 * (1.0 + chirp * t)) / SAMPLE_RATE
        ph2 = 2 * np.pi * np.cumsum(f2 * (1.0 - 0.5 * chirp * t)) / SAMPLE_RATE
        php = 2 * np.pi * spk.pitch * t
        mod = 0.6 + 0.4 * np.cos(2 * np.pi * am * t)
        s0 = start + j * seg_len
        sig[s0:s0 + seg_len] += env * mod * (0.55 * np.sin(ph1)
                                             + 0.3 * np.sin(ph2)
                                             + 0.15 * np.sin(php))
    sig += spk.noise_floor * rng.standard_normal(length)
    sig += rng.uniform(0.001, 0.015) * rng.standard_normal(length)
    shift = int(rng.uniform(-0.22, 0.22) * length)
    sig = np.roll(sig, shift)
    if shift > 0:
        sig[:shift] = 0.0
    elif shift < 0:
        sig[shift:] = 0.0
    return sig / (np.max(np.abs(sig)) + 1e-9) * 0.9


def to_8bit(x: np.ndarray) -> np.ndarray:
    """Clip to [-1, 1] and round onto the 8-bit grid k/127 (float32)."""
    return (np.round(np.clip(x, -1.0, 1.0) * 127.0) / 127.0).astype(
        np.float32)


def make_bank(traffic: dict, seed: int, hop: int) -> np.ndarray:
    """The shared audio bank of a traffic mix, a multiple of ``hop`` long."""
    bank = traffic["bank"]
    rng = np.random.default_rng([seed, 0xBA4C])
    n = int(bank["seconds"] * SAMPLE_RATE) // hop * hop
    speakers = [_speaker(rng) for _ in range(bank.get("speakers", 8))]
    audio = NOISE_STD * rng.standard_normal(n)

    def clip(k: int) -> np.ndarray:
        return keyword_clip(int(rng.integers(NUM_CLASSES)),
                            speakers[k % len(speakers)], rng)

    if bank["kind"] == "speech":
        for k, at in enumerate(range(0, n, SAMPLE_RATE)):
            c = clip(k)[:n - at]
            audio[at:at + len(c)] += c
    elif bank["kind"] == "duty":
        n_clips = int(round(bank["duty"] * n / SAMPLE_RATE))
        gap_total = n - n_clips * SAMPLE_RATE
        # one fixed set of gap lengths (hop multiples, mean gap_total /
        # n_clips, from half to one and a half of it), in a seeded order
        w = np.linspace(0.5, 1.5, n_clips)
        gaps = np.floor(w / w.sum() * gap_total / hop).astype(int) * hop
        gaps = rng.permutation(gaps)
        at = 0
        for k in range(n_clips):
            at += int(gaps[k])
            audio[at:at + SAMPLE_RATE] += clip(k)
            at += SAMPLE_RATE
    else:
        raise ValueError(f"unknown bank kind {bank['kind']!r}")
    return to_8bit(audio)


def stream_offsets(n_streams: int, bank_len: int, hop: int,
                   seed: int) -> np.ndarray:
    """Each stream's start offset into the bank: evenly spread, in a seeded
    order, on hop boundaries."""
    rng = np.random.default_rng([seed, 0x0FF5])
    slots = rng.permutation(n_streams)
    return (slots * (bank_len // hop) // n_streams * hop).astype(np.int64)


def hop_phases(n_streams: int, period_s: float, seed: int) -> np.ndarray:
    """Each stream's phase inside the hop period: the evenly spread set
    (j + 1/2) / N of the period, in a seeded order."""
    rng = np.random.default_rng([seed, 0x9A5E])
    return (rng.permutation(n_streams) + 0.5) / n_streams * period_s


def stream_audio(bank: np.ndarray, offset: int, start: int,
                 n: int) -> np.ndarray:
    """Samples ``[start, start + n)`` of the stream that reads ``bank``
    circularly from ``offset``.  ``bank`` holds the bank twice over, so a
    read of at most one bank's length is a slice."""
    size = len(bank) // 2
    if n > size:
        idx = (offset + start + np.arange(n)) % size
        return bank[idx]
    at = (offset + start) % size
    return bank[at:at + n]


def warmup_audio(pattern: str, hop: int) -> np.ndarray:
    """Set-up audio for warming every shape a gated cell uses: one hop per
    letter, ``L`` a loud hop (full-scale square wave, 0 dBFS) and ``Q`` a
    quiet one (the -40 dBFS noise floor as a fixed square wave)."""
    loud = np.where(np.arange(hop) % 2 == 0, 1.0, -1.0)
    quiet = loud * (1.0 / 127.0)
    return to_8bit(np.concatenate(
        [loud if ch == "L" else quiet for ch in pattern]))


def audio_plan(traffic: dict, n_streams: int, seed: int,
               hop: int) -> Dict[str, object]:
    """Everything the run and the reference need to rebuild each stream's
    audio: the bank, the offsets and the warm-up prefix."""
    bank = make_bank(traffic, seed, hop)
    return {"bank": np.concatenate([bank, bank]),
            "offsets": stream_offsets(n_streams, len(bank), hop, seed),
            "warmup": warmup_audio(traffic.get("warmup", ""), hop)}


def full_stream(plan: dict, i: int, window: int, n_hops: int,
                hop: int) -> np.ndarray:
    """Stream ``i``'s audio as the server received it: its first window
    from the bank, the warm-up prefix, then ``n_hops`` further hops from the
    bank (``n_hops`` counts every hop after the first window, warm-up hops
    included)."""
    w = plan["warmup"]
    n_warm = len(w) // hop
    first = stream_audio(plan["bank"], int(plan["offsets"][i]), 0, window)
    rest = stream_audio(plan["bank"], int(plan["offsets"][i]), window,
                        max(n_hops - n_warm, 0) * hop)
    return np.concatenate([first, w[:n_hops * hop], rest])


def stream_ids(n: int) -> List[str]:
    return [f"mic{i}" for i in range(n)]
