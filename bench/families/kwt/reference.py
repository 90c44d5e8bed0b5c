"""KWT's plain reference of a served stream: what every decision must be.

It imports nothing of the program.  It takes the family's weights
(``weights.py``), a stream's audio as the server received it and the
stream's per-hop fates (``bench.reference``), and for every computed hop,
in straightforward ``jax.numpy``:

* rebuilds the hop's 16000-sample window from the raw audio and computes
  its 98 MFCC frames from scratch (no ring): 480-sample frames every 160
  samples, a periodic Hann window, the 512-point DFT's magnitude (a cosine
  and sine matmul), 40 triangular HTK-mel bands from 20 to 7600 Hz, the log
  with the configuration's floor, an orthonormal DCT-II to 40;
* runs the class token and the frames through the embedding, the
  position embeddings, the post-norm encoder layers (every token of every
  layer) and the head on the class token;
* hands the logits to the shared smoothing (``bench.reference``).

Windows go through in blocks of ``BLOCK_HOPS`` hops, so that any stream
length fits.  ``dtype=float32`` runs every matmul at ``Precision.HIGHEST``;
``dtype=bfloat16`` is the control: every operand, product, sum, LayerNorm,
softmax and the residual stream in bfloat16.
"""

from __future__ import annotations

import functools
import json
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as ref

BLOCK_HOPS = 64           # windows per jitted call
BUCKET_HOPS = 1024        # decision sequences are padded to this


def _mel_hz(f):
    return 1127.0 * np.log(1.0 + np.asarray(f, np.float64) / 700.0)


def front_end(model: dict):
    """(window-times-DFT basis (480, 514), mel weights (257, 40), DCT
    (40, 40)) in float64."""
    n, nfft = model["frame_len"], model["n_fft"]
    t = np.arange(n)
    hann = 0.5 * (1.0 - np.cos(2.0 * np.pi * t / n))
    bins = nfft // 2 + 1
    phase = 2.0 * np.pi * np.outer(t, np.arange(bins)) / nfft
    dft = np.concatenate([np.cos(phase), -np.sin(phase)], axis=1)
    dft *= hann[:, None]
    hz = np.linspace(0.0, model["sample_rate"] / 2.0, bins)
    edges = np.linspace(_mel_hz(model["mel_lo_hz"]),
                        _mel_hz(model["mel_hi_hz"]), model["n_mels"] + 2)
    mel = np.zeros((bins, model["n_mels"]))
    for j in range(model["n_mels"]):
        lo, mid, hi = edges[j], edges[j + 1], edges[j + 2]
        m = _mel_hz(hz[1:])              # the DC bin gets no weight
        up = (m - lo) / (mid - lo)
        down = (hi - m) / (hi - mid)
        mel[1:, j] = np.clip(np.minimum(up, down), 0.0, None)
    k, c = np.arange(model["n_mels"]), np.arange(model["n_mfcc"])
    dct = np.sqrt(2.0 / model["n_mels"]) * np.cos(
        np.pi * np.outer(k + 0.5, c) / model["n_mels"])
    dct[:, 0] /= np.sqrt(2.0)
    return dft, mel, dct


def _mm(a, b, dtype):
    return jnp.matmul(a.astype(dtype), b.astype(dtype),
                      preferred_element_type=dtype)


def _norm(p, x, eps, dtype):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return ((x - mu) / jnp.sqrt(var + jnp.asarray(eps, dtype))
            * p["scale"].astype(dtype) + p["bias"].astype(dtype))


def _linear(p, x, dtype):
    return _mm(x, p["w"], dtype) + p["b"].astype(dtype)


def _logits(model: dict, dtype, w: dict, windows):
    """Logits of whole windows (n, sample_len)."""
    dft, mel, dct = front_end(model)
    n_fr = (model["sample_len"] - model["frame_len"]) // model[
        "frame_shift"] + 1
    idx = (model["frame_shift"] * np.arange(n_fr)[:, None]
           + np.arange(model["frame_len"])[None, :])
    frames = windows.astype(dtype)[:, idx]                 # (n, 98, 480)
    spec = _mm(frames, jnp.asarray(dft, dtype), dtype)
    half = dft.shape[1] // 2
    mag = jnp.sqrt(spec[..., :half] ** 2 + spec[..., half:] ** 2)
    logmel = jnp.log(_mm(mag, jnp.asarray(mel, dtype), dtype)
                     + jnp.asarray(model["log_floor"], dtype))
    feats = _mm(logmel, jnp.asarray(dct, dtype), dtype)    # (n, 98, 40)

    n, d, h = windows.shape[0], model["dim"], model["heads"]
    hd = d // h
    x = _linear(w["embed"], feats, dtype)
    cls = jnp.broadcast_to(w["cls"].astype(dtype), (n, 1, d))
    x = jnp.concatenate([cls, x], axis=1) + w["pos"].astype(dtype)
    t = x.shape[1]
    for p in w["layers"]:
        qkv = _linear(p["qkv"], x, dtype)
        q, k, v = (qkv[..., i * d:(i + 1) * d].reshape(n, t, h, hd)
                   for i in range(3))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=dtype) / jnp.sqrt(
                           jnp.asarray(hd, dtype))
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", a, v,
                       preferred_element_type=dtype).reshape(n, t, d)
        x = _norm(p["ln1"], x + _linear(p["out"], o, dtype),
                  model["ln_eps"], dtype)
        m = jax.nn.gelu(_linear(p["mlp1"], x, dtype), approximate=False)
        x = _norm(p["ln2"], x + _linear(p["mlp2"], m, dtype),
                  model["ln_eps"], dtype)
    return _linear(w["head"], x[:, 0], dtype)


def window_logits(model: dict, w: dict, windows, dtype=jnp.float32):
    """Logits of whole windows (n, sample_len), as a numpy array."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(_jitted(_logits, model, dtype)(
            w, jnp.asarray(windows, jnp.float32)))


def _smoothed(smooth: int, dtype, logits):
    return ref.smoothed_decisions(logits.astype(dtype), smooth, dtype)


_JIT: Dict[tuple, object] = {}


def _jitted(fn, *static):
    k = (fn.__name__, json.dumps(static[0], sort_keys=True)) + static[1:]
    if k not in _JIT:
        _JIT[k] = jax.jit(functools.partial(fn, *static))
    return _JIT[k]


def stream_reference(config: dict, w: dict, audio: np.ndarray, n_taken: int,
                     hop: int, key, vad: Optional[dict],
                     dtype=jnp.float32) -> Dict[str, object]:
    """Everything the check compares for one stream that had ``n_taken``
    hops taken after its first window (``key`` is not read: KWT draws no
    noise)."""
    model = config["model"]
    window = model["sample_len"]
    f, ambiguous = ref.stream_fates(window, audio, n_taken, hop, vad)
    computed = np.nonzero(f == ref.COMPUTED)[0]
    x = np.asarray(audio, np.float32)
    logits = []
    for b0 in range(0, len(computed), BLOCK_HOPS):
        hs = np.zeros(BLOCK_HOPS, np.int64)
        part = computed[b0:b0 + BLOCK_HOPS]
        hs[:len(part)] = part
        starts = hs * hop
        windows = x[starts[:, None] + np.arange(window)[None, :]]
        logits.append(window_logits(model, w, windows, dtype)[:len(part)])
    n = len(computed)
    n_pad = -(-max(n, 1) // BUCKET_HOPS) * BUCKET_HOPS
    lg = np.zeros((n_pad, model["num_classes"]), np.float32)
    if n:
        lg[:n] = np.concatenate(logits)
    kw, score, smoothed = _jitted(_smoothed, config["decision"]["smooth"],
                                  dtype)(jnp.asarray(lg))
    return {"fates": f, "keyword": np.asarray(kw)[:n],
            "score": np.asarray(score)[:n],
            "smoothed": np.asarray(smoothed, np.float64)[:n],
            "ambiguous": ambiguous}
