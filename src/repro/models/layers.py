"""Shared layer library for the assigned LM-family architectures.

Pure-pytree JAX (no flax): every layer is (init_fn, apply_fn) over explicit
parameter dicts.  All matmuls run in bf16 with fp32 params (cast at use),
reductions in fp32.  Sharding constraints are injected through a
ShardingPolicy so the same code serves single-device smoke tests and the
512-chip production mesh.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

COMPUTE_DTYPE = jnp.bfloat16


# ---------------------------------------------------------------------------
# Sharding policy
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    """Maps logical activation/parameter axes onto mesh axes.

    data_axes: mesh axes carrying the batch (e.g. ("pod", "data")).
    model_axis: mesh axis for tensor/expert parallelism.
    fsdp_axis: mesh axis over which parameters/optimizer state are sharded
      (ZeRO-3); None disables FSDP.
    enabled=False turns every constraint into a no-op (single-device tests).
    """

    data_axes: Tuple[str, ...] = ()
    model_axis: Optional[str] = None
    fsdp_axis: Optional[str] = None
    enabled: bool = False
    # sizes for divisibility checks (filled from the mesh)
    axis_sizes: Optional[Dict[str, int]] = None
    # §Perf knobs (see EXPERIMENTS.md):
    # MoE expert-parallel axis: "model" (baseline) or "data" (experts
    # stationary over data, TP over model — kills per-step expert gathers)
    ep_axis: str = "model"
    # serving: masked (elementwise) KV-cache writes instead of
    # dynamic-update-slice — DUS at a runtime index across a seq-sharded
    # cache trips XLA's replicate-then-repartition fallback
    serve_mode: bool = False

    def size(self, axis) -> int:
        if not self.axis_sizes:
            return 1
        if isinstance(axis, tuple):
            n = 1
            for a in axis:
                n *= self.axis_sizes.get(a, 1)
            return n
        return self.axis_sizes.get(axis, 1)

    def _maybe(self, x: jax.Array, spec: P) -> jax.Array:
        if not self.enabled:
            return x
        return jax.lax.with_sharding_constraint(x, spec)

    # logical constraint helpers ------------------------------------------
    def btd(self, x):            # (batch, seq, d_model)
        return self._maybe(x, P(self.data_axes or None, None, None))

    def btf(self, x):            # (batch, seq, ff/hidden) — TP-sharded cols
        return self._maybe(x, P(self.data_axes or None, None,
                                self.model_axis))

    def bthd(self, x):           # (batch, seq, heads, head_dim)
        h = x.shape[2]
        tp = self.size(self.model_axis)
        head_ax = self.model_axis if (tp > 1 and h % tp == 0) else None
        return self._maybe(x, P(self.data_axes or None, None, head_ax, None))

    def btv(self, x):            # (batch, seq, vocab) — logits
        return self._maybe(x, P(self.data_axes or None, None,
                                self.model_axis))

    def bt_seq_sharded(self, x):  # sequence parallelism for long KV caches
        return self._maybe(x, P(None, self.data_axes or None, None, None))


NO_SHARDING = ShardingPolicy()


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int) -> Dict:
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(p: Dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    return y.astype(x.dtype)


def layernorm_init(d: int) -> Dict:
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def layernorm(p: Dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float = 1e4) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float = 1e4) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) or (S,)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,hd/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense projections
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, bias: bool = False,
               scale: Optional[float] = None) -> Dict:
    scale = scale if scale is not None else d_in ** -0.5
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale)}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def dense(p: Dict, x: jax.Array, preferred_element_type=None) -> jax.Array:
    """``x @ w (+ b)`` with the weights cast to ``x``'s dtype; the product
    (and the bias) in ``preferred_element_type`` if given: bfloat16
    operands with a float32 result (``repro.models.kwt``)."""
    y = jnp.matmul(x, p["w"].astype(x.dtype),
                   preferred_element_type=preferred_element_type)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Attention (MHA / GQA, optional QK-norm & bias), with KV-cache support
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    qk_norm: bool = False            # qwen3 style
    rope_theta: float = 1e4
    causal: bool = True


def attn_init(key, cfg: AttnConfig) -> Dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": dense_init(k1, cfg.d_model, cfg.n_heads * cfg.head_dim,
                         cfg.qkv_bias),
        "wk": dense_init(k2, cfg.d_model, cfg.n_kv_heads * cfg.head_dim,
                         cfg.qkv_bias),
        "wv": dense_init(k3, cfg.d_model, cfg.n_kv_heads * cfg.head_dim,
                         cfg.qkv_bias),
        "wo": dense_init(k4, cfg.n_heads * cfg.head_dim, cfg.d_model),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(cfg.head_dim)
        p["k_norm"] = rmsnorm_init(cfg.head_dim)
    return p


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.repeat(k, n_rep, axis=2)


def attention(p: Dict, cfg: AttnConfig, x: jax.Array,
              policy: ShardingPolicy = NO_SHARDING,
              positions: Optional[jax.Array] = None,
              cache: Optional[Dict] = None,
              cache_index: Optional[jax.Array] = None,
              kv_override: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, Optional[Dict]]:
    """Self- (or cross-, via kv_override) attention.

    cache: {"k","v"} of (B, S_max, Hkv, hd) for incremental decoding; the new
    kv is written at cache_index.  Returns (out, new_cache).
    """
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :] + (0 if cache_index is None
                                              else cache_index)
    q = dense(p["wq"], x).reshape(b, s, cfg.n_heads, cfg.head_dim)
    kv_src = x if kv_override is None else kv_override
    sk = kv_src.shape[1]
    k = dense(p["wk"], kv_src).reshape(b, sk, cfg.n_kv_heads, cfg.head_dim)
    v = dense(p["wv"], kv_src).reshape(b, sk, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q)
        k = rmsnorm(p["k_norm"], k)
    if kv_override is None:                     # RoPE only for self-attn
        q = apply_rope(q, positions, cfg.rope_theta)
        kv_pos = (jnp.arange(sk)[None, :] if cache_index is None
                  else jnp.arange(sk)[None, :] * 0 + positions)
        k = apply_rope(k, kv_pos if cache_index is not None
                       else jnp.arange(sk)[None, :], cfg.rope_theta)
    if cache is None:
        # decode (cache present): q is a single position — head-sharding it
        # would force the whole KV cache to reshard from its seq layout to a
        # head layout (an SPMD replicate-fallback); let q follow the cache.
        q = policy.bthd(q)

    new_cache = None
    if cache is not None:
        # decode: write new kv at cache_index, attend over the whole cache
        if policy.serve_mode and s == 1:
            # elementwise masked write: shardable across any seq sharding
            s_iota = jnp.arange(cache["k"].shape[1])[None, :, None, None]
            hit = (s_iota == cache_index)
            ck = jnp.where(hit, k.astype(cache["k"].dtype), cache["k"])
            cv = jnp.where(hit, v.astype(cache["v"].dtype), cache["v"])
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), cache_index, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), cache_index, axis=1)
        new_cache = {"k": ck, "v": cv}
        k, v = ck, cv
        sk = k.shape[1]

    n_rep = cfg.n_heads // cfg.n_kv_heads
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)

    scale = cfg.head_dim ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale  # (B,H,S,Sk)
    logits = logits.astype(jnp.float32)
    if cfg.causal and cache is None and kv_override is None and s == sk:
        mask = jnp.tril(jnp.ones((s, sk), bool))
        logits = jnp.where(mask[None, None], logits, -1e30)
    elif cache is not None:
        # decode: mask future cache slots
        valid = jnp.arange(sk)[None, None, None, :] <= (
            cache_index + jnp.arange(s)[None, None, :, None])
        logits = jnp.where(valid, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v)
    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
    return dense(p["wo"], out), new_cache


# ---------------------------------------------------------------------------
# MLP: SwiGLU (llama-family) or GeLU (starcoder2-family)
# ---------------------------------------------------------------------------


def mlp_init(key, d_model: int, d_ff: int, gated: bool = True,
             bias: bool = False) -> Dict:
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], d_model, d_ff, bias),
         "w_down": dense_init(ks[1], d_ff, d_model, bias)}
    if gated:
        p["w_gate"] = dense_init(ks[2], d_model, d_ff, bias)
    return p


def mlp(p: Dict, x: jax.Array, policy: ShardingPolicy = NO_SHARDING,
        gated: bool = True) -> jax.Array:
    up = dense(p["w_up"], x)
    if gated:
        h = jax.nn.silu(dense(p["w_gate"], x)) * up
    else:
        h = jax.nn.gelu(up)
    h = policy.btf(h)
    return dense(p["w_down"], h)


# ---------------------------------------------------------------------------
# Chunked gated linear attention core
# ---------------------------------------------------------------------------
# Both mLSTM (xLSTM) and SSD (Mamba2) are linear recurrences
#     S_t = a_t * S_{t-1} + b_t * k_t v_t^T ,   y_t = q_t . S_t
# with per-(head, step) scalar decay a_t and input gate b_t.  This single
# chunkwise-parallel kernel-shaped implementation serves both, giving
# MXU-friendly matmuls instead of a length-T sequential scan.


def gated_linear_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           log_a: jax.Array, b: jax.Array,
                           chunk: int = 128,
                           initial_state: Optional[jax.Array] = None,
                           return_state: bool = False,
                           policy: "ShardingPolicy" = None):
    """q,k: (B,T,H,Dk); v: (B,T,H,Dv); log_a,b: (B,T,H) scalar gates.

    Returns y: (B,T,H,Dv) (+ final state (B,H,Dk,Dv) if return_state).
    T must be a multiple of ``chunk`` (pad upstream).
    """
    B, T, H, Dk = q.shape
    Dv = v.shape[-1]
    assert T % chunk == 0, (T, chunk)
    n = T // chunk
    rs = lambda x: x.reshape(B, n, chunk, *x.shape[2:]).swapaxes(0, 1)
    qc, kc, vc = rs(q), rs(k), rs(v)            # (n, B, c, H, D)
    lac, bc = rs(log_a), rs(b)                  # (n, B, c, H)

    # cumulative log-decay within the chunk, inclusive of step t
    cum = jnp.cumsum(lac, axis=2)               # (n,B,c,H)
    total = cum[:, :, -1:, :]                   # (n,B,1,H)

    if initial_state is None:
        s0 = jnp.zeros((B, H, Dk, Dv), jnp.float32)
    else:
        s0 = initial_state.astype(jnp.float32)

    def body(state, xs):
        qi, ki, vi, cumi, toti, bi = xs
        # inter-chunk: y_inter[t] = a(<=t) * q_t . S_prev
        decay_t = jnp.exp(cumi)                               # (B,c,H)
        y_inter = jnp.einsum("bchd,bhdv->bchv",
                             (qi * decay_t[..., None]).astype(jnp.float32),
                             state)
        # intra-chunk: y_intra[t] = sum_{j<=t} (a(j+1..t) b_j) (q_t.k_j) v_j
        rel = cumi[:, :, None, :] - cumi[:, None, :, :]        # (B,c,c,H) t,j
        mask = (jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :])
        # clamp BEFORE exp: future positions have rel > 0 (potentially huge);
        # where(mask, exp(rel), 0) still differentiates exp there -> inf*0
        # = NaN in the backward pass
        rel = jnp.where(mask[None, :, :, None], rel, -1e30)
        gate = jnp.exp(rel)
        att = jnp.einsum("bchd,bjhd->bcjh", qi.astype(jnp.float32),
                         ki.astype(jnp.float32))
        att = att * gate * bi[:, None, :, :]                   # b_j
        y_intra = jnp.einsum("bcjh,bjhv->bchv", att,
                             vi.astype(jnp.float32))
        # state update: S = a(chunk) S + sum_j a(j+1..end) b_j k_j v_j^T
        tail = jnp.exp(toti - cumi) * bi                       # (B,c,H)
        kv = jnp.einsum("bchd,bchv->bhdv",
                        (ki * tail[..., None]).astype(jnp.float32),
                        vi.astype(jnp.float32))
        new_state = jnp.exp(toti[:, 0, :])[..., None, None] * state + kv
        # emit the chunk in compute dtype, head-sharded: the stacked scan
        # output is (n,B,c,H,Dv) — fp32 unsharded it dominated peak memory
        # (44GB/device on zamba2 prefill_32k)
        y_out = (y_inter + y_intra).astype(v.dtype)
        if policy is not None:
            y_out = policy.bthd(y_out)
        return new_state, y_out

    state, ys = jax.lax.scan(body, s0, (qc, kc, vc, cum, total, bc))
    y = ys.swapaxes(0, 1).reshape(B, T, H, Dv).astype(v.dtype)
    if return_state:
        return y, state
    return y


def gla_step(q, k, v, log_a, b, state):
    """Single decode step of the same recurrence.
    q,k: (B,H,Dk); v: (B,H,Dv); log_a,b: (B,H); state: (B,H,Dk,Dv)."""
    a = jnp.exp(log_a.astype(jnp.float32))[..., None, None]
    kv = jnp.einsum("bhd,bhv->bhdv", k.astype(jnp.float32),
                    v.astype(jnp.float32)) * b[..., None, None]
    new_state = a * state + kv
    y = jnp.einsum("bhd,bhdv->bhv", q.astype(jnp.float32), new_state)
    return y.astype(v.dtype), new_state
