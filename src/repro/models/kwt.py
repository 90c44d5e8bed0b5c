"""KWT, the Keyword Transformer (Berg, O'Connor and Tairum Cruz,
"Keyword Transformer: A Self-Attention Model for Keyword Spotting",
Interspeech 2021, arXiv:2104.00769).

A ViT over MFCC time frames: each of a 1 s window's 98 frames of 40 MFCCs
(``repro.models.mfcc``) is one token, linearly embedded to width ``dim``;
a learned class token and learned absolute position embeddings make 99
tokens; ``depth`` post-norm encoder layers (LayerNorm after each residual
add) of plain bidirectional multi-head attention and a GELU MLP; a linear
head on the class token.  KWT-3 (the defaults) is 12 layers of width 192,
3 heads of 64 and an MLP of 768 over 12 classes: 5.37 M parameters.

Precision: matmul operands in ``compute_dtype`` (bfloat16 for KWT-3) with
float32 products; the MFCC, LayerNorm, softmax, GELU and the residual
stream are float32.  ``compute_dtype="float32"`` runs the whole net in
float32 (the tests' exact comparison).

The last layer computes only what the head reads: keys and values of all
tokens, but the query, attention, output projection and MLP of the class
token alone.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp

from repro.models import layers, mfcc


@dataclasses.dataclass(frozen=True)
class KWTConfig:
    sample_len: int = 16_000          # the decision window, in samples
    sample_rate: int = 16_000
    frame_len: int = 480              # 30 ms analysis window
    frame_shift: int = 160            # 10 ms between frames
    n_fft: int = 512
    n_mels: int = 40
    n_mfcc: int = 40
    mel_lo_hz: float = 20.0
    mel_hi_hz: float = 7600.0
    log_floor: float = 1e-6
    dim: int = 192
    depth: int = 12
    heads: int = 3
    mlp_dim: int = 768
    num_classes: int = 12
    ln_eps: float = 1e-6
    compute_dtype: str = "bfloat16"

    @property
    def n_frames(self) -> int:
        """MFCC frames (tokens besides the class token) in a window."""
        return mfcc.n_frames(self, self.sample_len)

    @property
    def head_dim(self) -> int:
        return self.dim // self.heads

    def param_count(self) -> int:
        d, f = self.dim, self.mlp_dim
        per_layer = 3 * d * d + 3 * d + d * d + d + 2 * d * f + f + d + 4 * d
        return (self.n_mfcc * d + d + d + (self.n_frames + 1) * d
                + self.depth * per_layer + d * self.num_classes
                + self.num_classes)


jax.tree_util.register_static(KWTConfig)

KWT3 = KWTConfig()


def init_params(key: jax.Array, cfg: KWTConfig = KWT3) -> Dict:
    """Seeded float32 parameters: matrices LeCun-normal (variance 1 /
    fan-in), biases and LayerNorm offsets N(0, 0.1^2), LayerNorm scales
    1 + N(0, 0.1^2), the class token and position embeddings N(0, 1)."""
    d, f = cfg.dim, cfg.mlp_dim
    keys = iter(jax.random.split(key, 6 + 12 * cfg.depth))

    def mat(d_in, d_out):
        return {"w": jax.random.normal(next(keys), (d_in, d_out))
                * d_in ** -0.5,
                "b": 0.1 * jax.random.normal(next(keys), (d_out,))}

    def norm():
        return {"scale": 1.0 + 0.1 * jax.random.normal(next(keys), (d,)),
                "bias": 0.1 * jax.random.normal(next(keys), (d,))}

    params = {"embed": mat(cfg.n_mfcc, d),
              "cls": jax.random.normal(next(keys), (d,)),
              "pos": jax.random.normal(next(keys), (cfg.n_frames + 1, d)),
              "layers": []}
    for _ in range(cfg.depth):
        params["layers"].append({"qkv": mat(d, 3 * d), "out": mat(d, d),
                                 "ln1": norm(), "mlp1": mat(d, f),
                                 "mlp2": mat(f, d), "ln2": norm()})
    params["head"] = mat(d, cfg.num_classes)
    return params


def cast_params(params: Dict, cfg: KWTConfig) -> Dict:
    """The served copy: every matmul weight in ``compute_dtype``, the rest
    float32."""
    dt = jnp.dtype(cfg.compute_dtype)

    def cast(path, x):
        last = path[-1]
        is_w = isinstance(last, jax.tree_util.DictKey) and last.key == "w"
        return jnp.asarray(x, dt if is_w else jnp.float32)

    return jax.tree_util.tree_map_with_path(cast, params)


def _dense(p: Dict, x: jax.Array, dt) -> jax.Array:
    return layers.dense(p, x.astype(dt), preferred_element_type=jnp.float32)


def encoder_layer(p: Dict, x: jax.Array, cfg: KWTConfig,
                  cls_only: bool = False) -> jax.Array:
    """One post-norm layer over float32 tokens (B, T, dim); with
    ``cls_only`` it returns the class token's row alone (B, 1, dim)."""
    dt = jnp.dtype(cfg.compute_dtype)
    b, t, d = x.shape
    h, hd = cfg.heads, cfg.head_dim
    w, bias = p["qkv"]["w"], p["qkv"]["b"]
    if cls_only:
        res = x[:, :1]
        q = _dense({"w": w[:, :d], "b": bias[:d]}, res, dt)
        kv = _dense({"w": w[:, d:], "b": bias[d:]}, x, dt)
    else:
        res = x
        qkv = _dense(p["qkv"], x, dt)
        q, kv = qkv[..., :d], qkv[..., d:]
    tq = res.shape[1]
    q = (q * hd ** -0.5).reshape(b, tq, h, hd)
    k = kv[..., :d].reshape(b, t, h, hd)
    v = kv[..., d:].reshape(b, t, h, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(dt), k.astype(dt),
                   preferred_element_type=jnp.float32)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a.astype(dt), v.astype(dt),
                   preferred_element_type=jnp.float32).reshape(b, tq, d)
    x = layers.layernorm(p["ln1"], res + _dense(p["out"], o, dt),
                         cfg.ln_eps)
    m = jax.nn.gelu(_dense(p["mlp1"], x, dt), approximate=False)
    return layers.layernorm(p["ln2"], x + _dense(p["mlp2"], m, dt),
                            cfg.ln_eps)


def forward(params: Dict, feats: jax.Array, cfg: KWTConfig) -> jax.Array:
    """MFCC frames (B, n_frames, n_mfcc) -> logits (B, num_classes)."""
    dt = jnp.dtype(cfg.compute_dtype)
    b = feats.shape[0]
    with jax.named_scope("embed"):
        x = _dense(params["embed"], feats, dt)
        cls = jnp.broadcast_to(params["cls"], (b, 1, cfg.dim))
        x = jnp.concatenate([cls, x], axis=1) + params["pos"]
    for i, p in enumerate(params["layers"]):
        with jax.named_scope(f"enc{i}"):
            x = encoder_layer(p, x, cfg, cls_only=i == cfg.depth - 1)
    with jax.named_scope("head"):
        return _dense(params["head"], x[:, 0], dt)


def decision_macs(cfg: KWTConfig, new_frames: int) -> int:
    """Multiply-adds of one decision that computes ``new_frames`` MFCC
    frames (all of them for a whole window, one per hop on the ring): the
    frames' DFT, mel and DCT, the embedding, the encoder (the last layer for
    the class token only) and the head."""
    d, f, t = cfg.dim, cfg.mlp_dim, cfg.n_frames + 1
    bins = cfg.n_fft // 2 + 1
    front = new_frames * (cfg.frame_len * 2 * bins + bins * cfg.n_mels
                          + cfg.n_mels * cfg.n_mfcc)
    layer = t * (4 * d * d + 2 * d * f) + 2 * t * t * d
    last = t * 2 * d * d + 2 * d * d + 2 * t * d + 2 * d * f
    return (front + cfg.n_frames * cfg.n_mfcc * d
            + (cfg.depth - 1) * layer + last + d * cfg.num_classes)
