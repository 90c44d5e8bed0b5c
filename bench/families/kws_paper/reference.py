"""The paper net's plain reference of a served stream: what every decision
must be.

It imports nothing of the program.  It takes the family's own folded
weights (``weights.py``), a stream's audio as the server received it,
and the stream's per-hop fates (``bench.reference``), and computes each
decided hop's keyword and smoothed score in straightforward ``jax.numpy``:

* the whole stream is run once through the network as one long valid
  convolution per layer (hop 64 is a multiple of the net's total stride and
  pool, so every window's columns are columns of the whole-stream run);
* each layer: binary group-conv counts, then ``((counts + chip offset) +
  word-line bias) + SA noise``, the BN-decoder flip and the sign, the
  channel shuffle and the OR max-pool.  The SA noise of conv column ``a`` of
  layer ``l`` is ``std * normal(fold_in(fold_in(stream_key, l), a))``, the
  silicon model's per-absolute-column field;
* a VAD-gated hop computes nothing: its samples count as zeros and every
  layer's columns of that hop are the layer's constant response to silence;
* per decided hop: global average pool over the window's last-layer
  columns (247 for the paper net), the 8-bit feature grid (step 1/16), the
  Q1.7 FC, and the shared smoothing (``bench.reference``).

``dtype=float32`` runs at ``Precision.HIGHEST``; ``dtype=bfloat16`` is the
control (every operand, sum and product in bfloat16).
"""

from __future__ import annotations

import functools
import json
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as ref


def geometry(model: dict, hop: int) -> List[dict]:
    """Per conv layer: full-window conv / pooled lengths and the fresh
    pooled columns one hop adds."""
    out, t_in, d_in = [], model["sample_len"], hop
    for k, s, p in zip(model["kernels"], model["strides"], model["pools"]):
        t_conv = (t_in - k) // s + 1
        n_new = d_in // s
        out.append({"t_conv": t_conv, "t_out": t_conv // p,
                    "d_out": n_new // p})
        t_in, d_in = t_conv // p, n_new // p
    return out


def groups(model: dict, i: int) -> int:
    return 1 if i == 0 else model["channels"][i - 1] // model[
        "channels_per_group"]


def _noise(key, layer: int, n_cols: int, c_out: int, std: float, dtype):
    base = jax.random.fold_in(key, layer)
    cols = jnp.arange(n_cols)
    v = jax.vmap(lambda a: jax.random.normal(jax.random.fold_in(base, a),
                                             (c_out,)))(cols)
    return (std * v).astype(dtype)


def _layer(model: dict, w: dict, i: int, h, dtype, key=None,
           noise_std: float = 0.0):
    """One conv layer over a whole stream: h (T, C_in) -> (T_pool, C_out)."""
    g = groups(model, i)
    prec = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    counts = jax.lax.conv_general_dilated(
        h[None].astype(dtype), w["w"][i].astype(dtype),
        window_strides=(model["strides"][i],), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=g,
        precision=prec, preferred_element_type=dtype)[0]
    pre = counts
    if i > 0 and w["offsets"] is not None:
        pre = pre + w["offsets"][i].astype(dtype)
    pre = pre + w["bias"][i].astype(dtype)
    if i > 0 and noise_std > 0.0:
        pre = pre + _noise(key, i, pre.shape[0], pre.shape[1], noise_std,
                           dtype)
    act = jnp.where(pre * w["flip"][i].astype(dtype) >= 0, 1.0,
                    -1.0).astype(dtype)
    t, c = act.shape
    if g > 1:
        act = act.reshape(t, g, c // g).swapaxes(1, 2).reshape(t, c)
    p = model["pools"][i]
    if p > 1:
        act = act[:t // p * p].reshape(t // p, p, c).max(axis=1)
    return act


def silence_columns(model: dict, w: dict, dtype=jnp.float32) -> list:
    """Each layer's constant response to silent audio (no SA noise)."""
    h = jnp.zeros((model["sample_len"], 1), dtype)
    fills = []
    for i in range(len(model["channels"])):
        h = _layer(model, w, i, h, dtype)
        fills.append(h[0])
    return fills


BUCKET_HOPS = 1024        # stream lengths are padded to this, so that the
#                           jitted reference compiles once per bucket


def _columns(model: dict, hop: int, noise_std: float, dtype, x, gated,
             w: dict, key):
    """Last-layer pooled columns of padded audio ``x`` with the hops marked
    in ``gated`` (hop 0 = first window) filled with silence columns."""
    geo = geometry(model, hop)
    fills = silence_columns(model, w, dtype)
    h_act = x[:, None]
    for i in range(len(model["channels"])):
        h_act = _layer(model, w, i, h_act, dtype, key, noise_std)
        g = geo[i]
        col = np.arange(h_act.shape[0])
        hop_of = np.where(col < g["t_out"], 0,
                          (col - g["t_out"]) // g["d_out"] + 1)
        mask = gated[np.minimum(hop_of, gated.shape[0] - 1)]
        h_act = jnp.where(mask[:, None], fills[i][None], h_act)
    return h_act


def _decisions(model: dict, hop: int, smooth: int, dtype, cols, hs, w):
    """(keyword, score, smoothed posterior) at window indices ``hs``."""
    geo = geometry(model, hop)
    t_feat, d_feat = geo[-1]["t_out"], geo[-1]["d_out"]
    csum = jnp.concatenate([jnp.zeros((1, cols.shape[1]), jnp.float32),
                            jnp.cumsum(cols.astype(jnp.float32), axis=0)])
    starts = hs * d_feat
    sums = (csum[starts + t_feat] - csum[starts]).astype(dtype)
    mean = sums / jnp.asarray(t_feat, dtype)
    feats = jnp.clip(jnp.round(mean / jnp.asarray(0.0625, dtype)),
                     -128, 127) * jnp.asarray(0.0625, dtype)
    prec = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    logits = (jnp.matmul(feats, w["fc_w"].astype(dtype), precision=prec,
                         preferred_element_type=dtype)
              + w["fc_b"].astype(dtype))
    return ref.smoothed_decisions(logits, smooth, dtype)


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
    hops taken after its first window."""
    model = config["model"]
    noise_std = float(config["silicon"]["sa_noise_std"])
    smooth = config["decision"]["smooth"]
    window = model["sample_len"]
    f, ambiguous = ref.stream_fates(window, audio, n_taken, hop, vad)
    n_pad = -(-(n_taken + 1) // BUCKET_HOPS) * BUCKET_HOPS
    x = np.zeros(window + n_pad * hop, np.float32)
    x[:window + n_taken * hop] = audio[:window + n_taken * hop]
    gated = np.zeros(n_pad + 1, bool)
    gated[:n_taken + 1] = f == ref.GATED
    x[window:][np.repeat(gated[1:], hop)] = 0.0   # a gated hop is silence
    cols = _jitted(_columns, model, hop, noise_std, dtype)(
        jnp.asarray(x), jnp.asarray(gated), w, key)
    hs = np.zeros(n_pad + 1, np.int32)
    computed = np.nonzero(f == ref.COMPUTED)[0]
    hs[:len(computed)] = computed
    kw, score, smoothed = _jitted(_decisions, model, hop, smooth, dtype)(
        cols, jnp.asarray(hs), w)
    n = len(computed)
    return {"fates": f, "keyword": np.asarray(kw)[:n],
            "score": np.asarray(score)[:n],
            "smoothed": np.asarray(smoothed, np.float64)[:n],
            "ambiguous": ambiguous}
