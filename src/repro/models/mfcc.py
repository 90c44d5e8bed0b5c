"""Log-mel MFCC front end: one feature frame per 10 ms of audio.

The Keyword Transformer (``repro.models.kwt``) reads 40 MFCCs per frame:
a 30 ms Hann-windowed frame (480 samples at 16 kHz) every 10 ms (160
samples), its 512-point FFT magnitude, 40 triangular mel bands (the HTK mel
scale of ``tf.signal.linear_to_mel_weight_matrix``), the log with a small
floor, and an orthonormal DCT-II down to 40 coefficients.

Every stage is float32: a real FFT, then the mel and DCT matrices as
matmuls at ``Precision.HIGHEST``.  A frame depends only on its own 480
samples and there is no per-window normalization, so a streaming ring of
frames is exact: the frames of a window are the frames of its 98 hops,
each computed once.  The FFT transforms each frame on its own, so a frame
comes out the same, to float32 rounding, whether a stream's first window
computed it or a hop did; a DFT matmul over the frame's 480 samples would
round with the number of frames in the call, and its cancellations reach
the log of the quiet mel bands.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def hz_to_mel(f):
    """The HTK mel scale, in the natural-log form TensorFlow uses."""
    return 1127.0 * np.log1p(np.asarray(f, np.float64) / 700.0)


@functools.lru_cache(maxsize=None)
def _hann(frame_len: int) -> np.ndarray:
    """The periodic Hann window."""
    n = np.arange(frame_len, dtype=np.float64)
    return 0.5 - 0.5 * np.cos(2.0 * np.pi * n / frame_len)


@functools.lru_cache(maxsize=None)
def _mel(n_fft: int, n_mels: int, sample_rate: int, lo_hz: float,
         hi_hz: float) -> np.ndarray:
    """(n_fft // 2 + 1, n_mels) triangular mel weights; the DC bin gets
    none."""
    bins = n_fft // 2 + 1
    freqs = np.linspace(0.0, sample_rate / 2.0, bins)[1:]
    mel = hz_to_mel(freqs)[:, None]
    edges = np.linspace(hz_to_mel(lo_hz), hz_to_mel(hi_hz), n_mels + 2)
    lower, center, upper = edges[:-2], edges[1:-1], edges[2:]
    w = np.maximum(0.0, np.minimum((mel - lower) / (center - lower),
                                   (upper - mel) / (upper - center)))
    return np.concatenate([np.zeros((1, n_mels)), w], axis=0)


@functools.lru_cache(maxsize=None)
def _dct(n_in: int, n_out: int) -> np.ndarray:
    """(n_in, n_out) orthonormal DCT-II."""
    n = np.arange(n_in, dtype=np.float64)
    k = np.arange(n_out, dtype=np.float64)
    m = np.cos(np.pi / n_in * np.outer(n + 0.5, k)) * np.sqrt(2.0 / n_in)
    m[:, 0] *= np.sqrt(0.5)
    return m


def n_frames(cfg, n_samples: int) -> int:
    """Whole frames in ``n_samples`` of audio."""
    return (n_samples - cfg.frame_len) // cfg.frame_shift + 1


def frame(audio: jax.Array, cfg) -> jax.Array:
    """(B, T) samples -> (B, F, frame_len) frames every ``frame_shift``."""
    f = n_frames(cfg, audio.shape[-1])
    idx = (cfg.frame_shift * np.arange(f)[:, None]
           + np.arange(cfg.frame_len)[None, :])
    return audio[:, idx]


def mfcc(frames: jax.Array, cfg) -> jax.Array:
    """(..., frame_len) float32 frames -> (..., n_mfcc) float32 MFCCs."""
    with jax.named_scope("mfcc"):
        hann = jnp.asarray(_hann(cfg.frame_len), jnp.float32)
        mel_w = jnp.asarray(_mel(cfg.n_fft, cfg.n_mels, cfg.sample_rate,
                                 cfg.mel_lo_hz, cfg.mel_hi_hz), jnp.float32)
        dct = jnp.asarray(_dct(cfg.n_mels, cfg.n_mfcc), jnp.float32)
        mag = jnp.abs(jnp.fft.rfft(frames.astype(jnp.float32) * hann,
                                   n=cfg.n_fft))
        mel = jnp.matmul(mag, mel_w, precision=HIGHEST)
        return jnp.matmul(jnp.log(mel + cfg.log_floor), dct,
                          precision=HIGHEST)


def audio_mfcc(audio: jax.Array, cfg) -> jax.Array:
    """(B, T) samples -> (B, F, n_mfcc): the MFCCs of every whole frame."""
    return mfcc(frame(audio, cfg), cfg)
