"""Shared neural layers: norms, RoPE, MLPs, embeddings, init helpers.

Parameters are plain nested dicts of jnp arrays (fp32 masters); compute casts
to ``cfg.compute_dtype``. Sharding lives in ``distributed/sharding.py`` as a
parallel tree of PartitionSpecs keyed by the same structure.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# ------------------------------------------------------------------- init
def dense_init(key, shape, in_axis: int = -2, scale: float = 1.0,
               dtype=jnp.float32):
    """Truncated-normal fan-in init (matches common LM practice)."""
    fan_in = shape[in_axis]
    std = scale / math.sqrt(fan_in)
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * 0.02


# ------------------------------------------------------------------- norms
def rms_norm(x, weight, eps: float = 1e-5):
    dt = x.dtype
    # barrier: keeps the fp32 upcast from being fused across the TP
    # all-reduce feeding the norm (§Perf iteration 3; ~2% on zamba2,
    # neutral elsewhere — measured both ways on dbrx)
    x = jax.lax.optimization_barrier(x)
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + weight.astype(jnp.float32))).astype(dt)


def layer_norm(x, weight, bias=None, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps)
    out = out * (1.0 + weight.astype(jnp.float32))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(dt)


def apply_norm(cfg, x, w):
    if cfg.norm == "layernorm":
        return layer_norm(x, w, eps=cfg.norm_eps)
    return rms_norm(x, w, eps=cfg.norm_eps)


def norm_init(d):
    return jnp.zeros((d,), jnp.float32)


# -------------------------------------------------------------------- RoPE
def rope_freqs(dims: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, dims, 2, jnp.float32) / dims))


def apply_rope(x, positions, theta: float = 10_000.0):
    """x: (..., S, H, dh) with dh even; positions: (..., S)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                       # (dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, dh/2)
    cos = jnp.cos(angles)[..., None, :]                 # (..., S, 1, dh/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, d: int) -> jnp.ndarray:
    """Whisper-style fixed sinusoidal embeddings (S, d)."""
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    inv = jnp.exp(-math.log(10_000.0) * dim / (d // 2 - 1))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# --------------------------------------------------------------------- MLP
def mlp_init(key, cfg, d_ff: Optional[int] = None) -> dict:
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.mlp == "gelu":
        return {
            "wi": dense_init(k1, (D, F)),
            "wo": dense_init(k2, (F, D), scale=1.0 / math.sqrt(
                2 * cfg.n_layers)),
        }
    return {
        "wg": dense_init(k1, (D, F)),
        "wu": dense_init(k2, (D, F)),
        "wo": dense_init(k3, (F, D), scale=1.0 / math.sqrt(2 * cfg.n_layers)),
    }


def mlp_apply(cfg, p, x):
    dt = x.dtype
    if "wi" in p:  # gelu
        h = jax.nn.gelu(x @ p["wi"].astype(dt))
        return h @ p["wo"].astype(dt)
    g = jax.nn.silu(x @ p["wg"].astype(dt))
    u = x @ p["wu"].astype(dt)
    return (g * u) @ p["wo"].astype(dt)


# --------------------------------------------------------------- embedding
def embedding_init(key, cfg) -> dict:
    p = {"tok": embed_init(key, (cfg.vocab, cfg.d_model))}
    return p


def unembed_init(key, cfg) -> Optional[jnp.ndarray]:
    if cfg.tie_embeddings:
        return None
    return dense_init(key, (cfg.d_model, cfg.vocab))


def logits_from_hidden(cfg, params, h):
    """h: (..., D) -> (..., V); fp32 logits for a stable softmax/CE."""
    if cfg.tie_embeddings:
        w = params["embed"]["tok"].astype(h.dtype).T
    else:
        w = params["head"].astype(h.dtype)
    return (h @ w).astype(jnp.float32)
