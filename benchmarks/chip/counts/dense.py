"""The work a dense decoder-only transformer needs for one call, counted
from shapes: floating-point operations and bytes of device memory moved.

These are the numerators of the roofline shares and of ``step_mfu``.  They
count what the algorithm needs, never what a compiled program does, so
that a program which stops doing needless work moves its share up and can
never push it past 100%:
  * decode reads every weight once, only the B rows of the embedding it
    looks up, and K/V for the positions each row has filled, not for the
    whole allocated cache;
  * prefill computes causal attention over the S(S+1)/2 query-key pairs
    that the mask keeps, and the vocabulary head for the last position only.
A multiply-add counts as two operations.  Norms, softmax, RoPE and other
elementwise work are left out: they are a small share, and a floor may only
leave work out, never add it.
"""
from __future__ import annotations

from typing import Dict

from reference.dense import sizes


def _sizes(c: Dict) -> Dict[str, int]:
    s = sizes(c)
    return dict(s, q=s["hq"] * s["hd"], kv=s["hkv"] * s["hd"])


def layer_params(c: Dict) -> int:
    """Weights of one layer's matrix multiplications: Q, K, V, O and the
    three SwiGLU matrices."""
    s = _sizes(c)
    return s["d"] * (2 * s["q"] + 2 * s["kv"]) + 3 * s["d"] * s["ff"]


def weight_bytes(c: Dict, itemsize: int = 2) -> int:
    """Every weight a forward step reads once: the layers' matrices, the
    norms' scales where they have them, and the vocabulary head."""
    s = _sizes(c)
    norms = (2 * s["L"] + 1) * s["d"] if c.get("norm") == "rmsnorm" else 0
    return itemsize * (s["L"] * layer_params(c) + norms + s["V"] * s["d"])


def decode(c: Dict, *, batch: int, context: int, itemsize: int = 2) -> Dict[str, float]:
    """One decode step of ``batch`` rows, each attending over ``context``
    positions (the cache length after this step's token is appended)."""
    s = _sizes(c)
    per_row = (s["L"] * (layer_params(c) + 2 * s["q"] * context)
               + s["d"] * s["V"])
    flops = 2.0 * batch * per_row
    embed_rows = 0 if c.get("tie_word_embeddings") else batch * s["d"]
    kv_read = s["L"] * batch * context * 2 * s["kv"]
    kv_write = s["L"] * batch * 2 * s["kv"]
    byts = weight_bytes(c, itemsize) + itemsize * (embed_rows + kv_read + kv_write)
    return {"flops": flops, "bytes": float(byts)}


def prefill(c: Dict, *, batch: int, prompt: int, itemsize: int = 2) -> Dict[str, float]:
    """One prefill of ``batch`` prompts of ``prompt`` tokens each, writing
    their K/V and computing the next token's logits at the last position."""
    s = _sizes(c)
    pairs = prompt * (prompt + 1) / 2
    flops = 2.0 * batch * (prompt * s["L"] * layer_params(c)
                           + s["L"] * 2 * s["q"] * pairs
                           + s["d"] * s["V"])
    embed_rows = 0 if c.get("tie_word_embeddings") else batch * prompt * s["d"]
    kv_write = s["L"] * batch * prompt * 2 * s["kv"]
    byts = weight_bytes(c, itemsize) + itemsize * (embed_rows + kv_write)
    return {"flops": flops, "bytes": float(byts)}
