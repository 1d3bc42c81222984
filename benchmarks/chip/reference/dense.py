"""Plain float32 reference of a dense decoder-only transformer, and the
random weights that it and the program under test both run with.

The model is the one the configuration file describes, in the form its
source publishes (Llama-style, as OLMo and Mistral are): token embedding;
per layer a pre-norm attention block (Q, K, V projections without bias,
rotary positions in the rotate-half form, causal softmax attention with
grouped K/V heads, output projection) and a pre-norm SwiGLU block, both
added to the residual stream; a final norm and the vocabulary head (the
embedding, transposed, where the configuration ties them).  The norm is
RMSNorm or OLMo's layer norm without learned scale or bias, as the file's
``norm`` says.  Every matrix product runs in float32 at "highest"
precision, so that the TPU does not round its inputs to bfloat16.

This module imports nothing of the program.  The weights are made here,
from the seed: each matrix from its own key (a hash of the seed, the
layer and the matrix), uniform with standard deviation 0.02 for the
embedding and 1/sqrt(fan-in) for every other matrix, rounded to the
configuration's dtype.  The norms' scales are 1.  Each element is a hash
of its index, not a draw of ``jax.random``: on the TPU the counter-based
generator made a 1.2-billion-parameter model in tens of seconds, which
every run and every reference would pay.  The benchmark hands the
same weights to the program (``programs/dense.py``), so the two compare
logits of one model.

``token_gaps`` is the comparison: for prompts and the tokens served after
them, the reference reads each served token's logit against its best
logit.  With ``control=True`` it also runs the same forward with every
matrix product's operands rounded to float8 (e4m3, scaled per row of
activations and per output column of weights), one step below the
bfloat16 the configuration states, and reads the gap of the token that
this lower precision puts first.  That is the control the limit is set
against; the benchmark's own runs never run it.
"""
from __future__ import annotations

import functools
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def sizes(c: Dict) -> Dict[str, int]:
    d = c["hidden_size"]
    hd = c.get("head_dim") or d // c["num_attention_heads"]
    return {"d": d, "hd": hd, "hq": c["num_attention_heads"],
            "hkv": c["num_key_value_heads"], "ff": c["intermediate_size"],
            "L": c["num_hidden_layers"], "V": c["vocab_size"]}


def matrix_shapes(c: Dict) -> Dict[str, tuple]:
    s = sizes(c)
    q, kv = s["hq"] * s["hd"], s["hkv"] * s["hd"]
    return {"wq": (s["d"], q), "wk": (s["d"], kv), "wv": (s["d"], kv),
            "wo": (q, s["d"]), "w_gate": (s["d"], s["ff"]),
            "w_up": (s["d"], s["ff"]), "w_down": (s["ff"], s["d"])}


def dtype(c: Dict):
    return jnp.dtype(c.get("torch_dtype", "bfloat16"))


def _mix(x):
    """A bijective 32-bit integer hash (lowbias32)."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def root_key(seed: int):
    """The seed as two 32-bit words, passed to jitted code as an argument so
    that one compiled program serves every seed."""
    seed %= 2**64
    return jnp.asarray([seed & 0xFFFFFFFF, seed >> 32], jnp.uint32)


def derive(key, *ids):
    """A 32-bit key for the stream named by ``ids`` (ints, may be traced)."""
    h = _mix(key[0] ^ _mix(key[1] ^ jnp.uint32(0x85EBCA6B)))
    for i in ids:
        h = _mix(h ^ _mix(jnp.asarray(i).astype(jnp.uint32) + jnp.uint32(0x9E3779B9)))
    return h


def _matrix(key, shape, std, dt):
    """Uniform on [-std*sqrt(3), std*sqrt(3)] (standard deviation ``std``),
    element i from a hash of (key, i): cheap on any device, the same on
    every device, and the same whether made alone or under vmap."""
    n = 1
    for s in shape:
        n *= s
    idx = jax.lax.iota(jnp.uint32, n).reshape(shape)
    h = _mix(_mix(idx + key) ^ _mix(key))
    u = (h >> 8).astype(jnp.float32) * (2.0 ** -24) + 2.0 ** -25   # (0, 1)
    return ((2.0 * u - 1.0) * (std * 3.0 ** 0.5)).astype(dt)


def embedding(c: Dict, key):
    s = sizes(c)
    return _matrix(derive(key, 0), (s["V"], s["d"]), 0.02, dtype(c))


def head(c: Dict, key):
    """The vocabulary head, (d, V): the embedding transposed when tied."""
    if c.get("tie_word_embeddings"):
        return embedding(c, key).T
    s = sizes(c)
    return _matrix(derive(key, 1), (s["d"], s["V"]), s["d"] ** -0.5, dtype(c))


def layer(c: Dict, key, i) -> Dict[str, jax.Array]:
    """Layer ``i``'s matrices (``i`` may be traced, e.g. under vmap)."""
    return {name: _matrix(derive(key, 2, i, j), shape, shape[0] ** -0.5, dtype(c))
            for j, (name, shape) in enumerate(matrix_shapes(c).items())}


# ---------------------------------------------------------------------------
# Forward, one layer at a time
# ---------------------------------------------------------------------------

def _fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def _mm(a, w, low):
    if low:
        a, w = _fp8(a, -1), _fp8(w, 0)
    return jnp.matmul(a, w, precision=HIGHEST)


def norm(c: Dict, x):
    if c["norm"] == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + c["rms_norm_eps"])
    if c["norm"] == "layernorm_nonparametric":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + c["norm_eps"])
    raise ValueError(f"unknown norm {c['norm']!r}")


def rope(x, theta: float):
    """Rotary positions, rotate-half form; x: (N, S, H, D), positions 0..S-1."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def layer_forward(c: Dict, w: Dict, x, low: bool):
    """One layer over x: (N, S, d) float32."""
    s = sizes(c)
    N, S, _ = x.shape
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    h = norm(c, x)
    q = _mm(h, w["wq"], low).reshape(N, S, s["hq"], s["hd"])
    k = _mm(h, w["wk"], low).reshape(N, S, s["hkv"], s["hd"])
    v = _mm(h, w["wv"], low).reshape(N, S, s["hkv"], s["hd"])
    q, k = rope(q, c["rope_theta"]), rope(k, c["rope_theta"])
    if low:
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, -1)
    g = s["hq"] // s["hkv"]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    scores = jnp.einsum("nqhd,nkhd->nhqk", q, k, precision=HIGHEST) * s["hd"] ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("nhqk,nkhd->nqhd", p, v, precision=HIGHEST)
    x = x + _mm(o.reshape(N, S, s["hq"] * s["hd"]), w["wo"], low)
    h = norm(c, x)
    gate = _mm(h, w["w_gate"], low)
    return x + _mm(jax.nn.silu(gate) * _mm(h, w["w_up"], low), w["w_down"], low)


class Reference:
    """The forward of one configuration, compiled once, run layer by layer
    and one sequence at a time, so that a layer's float32 weights and one
    sequence's attention scores are all it holds beside the hidden states."""

    def __init__(self, c: Dict):
        self.c = c
        self._layer_w = jax.jit(functools.partial(layer, c))
        self._layer = jax.jit(functools.partial(layer_forward, c),
                              static_argnums=(2,))
        self._embed = jax.jit(lambda key, t: embedding(c, key)[t].astype(jnp.float32))
        self._logits = jax.jit(
            lambda key, x, low: _mm(norm(c, x), head(c, key).astype(jnp.float32), low),
            static_argnums=(2,))

    def logits(self, key, tokens: np.ndarray, positions: np.ndarray, *, low: bool,
               stages: Dict[str, float]):
        """Logits (N, T, V) at ``positions`` (T,) of ``tokens`` (N, S), on
        the device; adds each stage's seconds to ``stages``."""
        t = time.perf_counter()
        x = jax.block_until_ready(self._embed(key, jnp.asarray(tokens, jnp.int32)))
        stages["embed"] = stages.get("embed", 0.0) + time.perf_counter() - t
        for i in range(self.c["num_hidden_layers"]):
            t = time.perf_counter()
            w = jax.block_until_ready(self._layer_w(key, jnp.int32(i)))
            stages["weights"] = stages.get("weights", 0.0) + time.perf_counter() - t
            t = time.perf_counter()
            x = jax.block_until_ready(jnp.concatenate(
                [self._layer(w, x[j:j + 1], low) for j in range(x.shape[0])]))
            stages["layers"] = stages.get("layers", 0.0) + time.perf_counter() - t
            del w
        t = time.perf_counter()
        out = jax.block_until_ready(self._logits(key, x[:, positions], low))
        stages["head"] = stages.get("head", 0.0) + time.perf_counter() - t
        return out


@jax.jit
def gaps(ref_logits, tokens):
    """How far below its row's best reference logit each token's logit lies,
    in units of that row's standard deviation."""
    chosen = jnp.take_along_axis(ref_logits, tokens[..., None], -1)[..., 0]
    return (ref_logits.max(-1) - chosen) / ref_logits.std(-1)


def token_gaps(c: Dict, seed: int, prompts: np.ndarray, served: np.ndarray,
               *, control: bool = False) -> Dict[str, np.ndarray]:
    """Gaps (N, T) of the served tokens, and with ``control`` of the tokens
    that the float8 forward puts first at the same positions; ``stage_s``
    holds the seconds each stage of the forward took.

    prompts: (N, P); served: (N, T), the tokens served after each prompt.
    Position P-1+j of prompt+served predicts served token j."""
    prompts, served = np.asarray(prompts), np.asarray(served)
    P, T = prompts.shape[1], served.shape[1]
    tokens = np.concatenate([prompts, served[:, :-1]], axis=1)
    positions = np.arange(P - 1, P - 1 + T)
    key = root_key(seed)
    ref = Reference(c)
    stages: Dict[str, float] = {}
    f32 = ref.logits(key, tokens, positions, low=False, stages=stages)
    out = {"served": np.asarray(gaps(f32, jnp.asarray(served, jnp.int32)))}
    if control:
        low = ref.logits(key, tokens, positions, low=True, stages=stages)
        out["control"] = np.asarray(gaps(f32, low.argmax(-1)))
    out["stage_s"] = stages
    return out
