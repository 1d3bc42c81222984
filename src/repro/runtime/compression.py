"""Gradient compression for DP all-reduce: int8 + error feedback.

Standard large-scale trick: quantize gradients to int8 with a per-tensor
scale before the data-parallel reduction (4x wire bytes saved), carry the
quantization residual into the next step (error feedback keeps convergence
unbiased to first order).  ``compressed_psum`` composes with shard_map or
plain pytree reduction; the hillclimb in EXPERIMENTS.md §Perf measures the
collective-term delta on the dry-run.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def quantize_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    x32 = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x32)), 1e-9) / 127.0
    q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compress_with_feedback(grads, error_state):
    """-> (quantized pytree {q, scale}, new_error_state).
    error_state mirrors grads (fp32 residuals)."""
    def one(g, e):
        g32 = g.astype(jnp.float32) + e
        q, s = quantize_int8(g32)
        deq = dequantize_int8(q, s)
        return {"q": q, "scale": s}, g32 - deq

    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_e = treedef.flatten_up_to(error_state)
    qs, es = zip(*[one(g, e) for g, e in zip(flat_g, flat_e)])
    return treedef.unflatten(list(qs)), treedef.unflatten(list(es))


def decompress(qtree):
    is_q = lambda x: isinstance(x, dict) and "q" in x and "scale" in x
    return jax.tree_util.tree_map(
        lambda d: dequantize_int8(d["q"], d["scale"]), qtree, is_leaf=is_q)


def init_error_state(grads):
    return jax.tree_util.tree_map(
        lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def compressed_psum(grads, error_state, axis_name: str):
    """int8 ring-friendly psum: quantize locally (with feedback), psum the
    int32-widened codes, dequantize with the max scale.  Inside shard_map.
    """
    def one(g, e):
        g32 = g.astype(jnp.float32) + e
        q, s = quantize_int8(g32)
        s_max = jax.lax.pmax(s, axis_name)
        # requantize against the shared scale so the sum is exact in int32
        q2 = jnp.clip(jnp.round(g32 / s_max), -127, 127).astype(jnp.int32)
        total = jax.lax.psum(q2, axis_name)
        new_e = g32 - q2.astype(jnp.float32) * s_max
        return (total.astype(jnp.float32) * s_max).astype(g.dtype), new_e

    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_e = treedef.flatten_up_to(error_state)
    outs, errs = zip(*[one(g, e) for g, e in zip(flat_g, flat_e)])
    return treedef.unflatten(list(outs)), treedef.unflatten(list(errs))


def compressed_allreduce(grads, error_state, mesh, axis_name: str):
    """:func:`compressed_psum` wrapped in a shard_map.

    ``grads``/``error_state``: pytrees whose leaves are sharded on their
    leading dim over ``axis_name``.  Returns (reduced grads, new error
    state) with the same sharding.  This is the standalone entry point the
    DP hillclimb and the distributed tests drive; inside a larger
    shard_map call :func:`compressed_psum` directly.
    """
    spec = jax.sharding.PartitionSpec(axis_name)

    def body(g, e):
        return compressed_psum(g, e, axis_name)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec),
                       out_specs=(spec, spec), check_vma=False)
    return fn(grads, error_state)
