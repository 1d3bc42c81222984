"""Shared model primitives: norms, RoPE, activations, init helpers.

Everything is pure-functional JAX (no flax): params are nested dicts of
jnp arrays; layer stacks are stacked along axis 0 for ``lax.scan``.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


def dtype_of(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, scale: jax.Array | None, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    if scale is not None:
        x = x * (1.0 + scale.astype(jnp.float32)) if scale.ndim else x
    return x.astype(dt)


def layernorm(x: jax.Array, scale: jax.Array | None, bias: jax.Array | None,
              eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        x = x * scale.astype(jnp.float32)
    if bias is not None:
        x = x + bias.astype(jnp.float32)
    return x.astype(dt)


def apply_norm(cfg, p: Params | None, x: jax.Array) -> jax.Array:
    if cfg.norm == "rmsnorm":
        return rmsnorm(x, p["scale"] if p else None, cfg.norm_eps)
    if cfg.norm == "layernorm":
        return layernorm(x, p["scale"] if p else None, p["bias"] if p else None,
                         cfg.norm_eps)
    if cfg.norm == "nonparam_ln":  # OLMo: LN without learned affine
        return layernorm(x, None, None, cfg.norm_eps)
    raise ValueError(cfg.norm)


def init_norm(cfg, key) -> Params:
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.zeros((cfg.d_model,), dtype_of(cfg))}
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((cfg.d_model,), dtype_of(cfg)),
                "bias": jnp.zeros((cfg.d_model,), dtype_of(cfg))}
    return {}  # nonparam_ln


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                          # (hd/2,)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(ang)[..., :, None, :]                    # (..., seq, 1, hd/2)
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, d_model: int) -> jax.Array:
    """Whisper-style sinusoidal embeddings (traced jnp — no giant HLO
    constants)."""
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d_model // 2, dtype=jnp.float32)[None, :]
    ang = pos / (10000 ** (2 * dim / d_model))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def sinusoidal_at(pos, d_model: int) -> jax.Array:
    """Sinusoidal embedding at a dynamic scalar position -> (d_model,)."""
    dim = jnp.arange(d_model // 2, dtype=jnp.float32)
    ang = pos.astype(jnp.float32) / (10000 ** (2 * dim / d_model))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------

def gelu(x):
    return jax.nn.gelu(x, approximate=True)


def silu(x):
    return x * jax.nn.sigmoid(x)


ACTS = {"swiglu": silu, "geglu": gelu, "gelu": gelu}


# ---------------------------------------------------------------------------
# Init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: float | None = None):
    """Normal init scaled by fan-in (abstract-safe under eval_shape)."""
    fan_in = shape[0]
    s = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)


def split_keys(key, n):
    return list(jax.random.split(key, n))


def count_params(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))


def tree_bytes(params) -> int:
    return sum(int(np.prod(p.shape)) * p.dtype.itemsize
               for p in jax.tree_util.tree_leaves(params))


def cast_tree(tree, dtype):
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)
