"""The program under test's dense decoder, as a configuration file meets it.

A configuration file names this module under ``program``.  It is the one
place that knows how the program (``repro.models``) spells a dense
decoder-only transformer:
  * ``config(conf)``: the program's ``ModelConfig`` with the file's sizes,
    checked to compute what the file describes;
  * ``params(ref, conf, seed, like, shardings)``: the reference's weights
    laid out as the program's parameter tree.
The program keeps a dense decoder's weights as ``{"embed": (V, d),
"final_norm": {...}, ["lm_head": (d, V)], "layers": {"b0_dense": {"ln1",
"attn": {wq, wk, wv, wo}, "ln2", "mlp": {w_gate, w_up, w_down}}}}``, each
layer's leaves stacked on a leading axis.  Its RMSNorm computes
``x * (1 + scale)``, so a norm whose weight is 1 has ``scale`` 0; its
non-parametric layer norm has no leaf.  A model of another layout (experts,
other blocks) brings a module of its own beside this one.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

# the program's config fields that a configuration file's keys set
FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings", "torch_dtype": "dtype",
}
NORMS = {"rmsnorm": "rmsnorm", "layernorm_nonparametric": "nonparam_ln"}


def config(c):
    """The program's config for the file ``c``, refused where the program
    would compute another model than the file describes."""
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(c["program_arch"]),
                              **{f: c[k] for k, f in FIELDS.items() if k in c})
    want = {"family": "dense", "mlp": "swiglu" if c["hidden_act"] == "silu" else None,
            "norm": NORMS.get(c["norm"]), "use_rope": True,
            "sliding_window": c.get("sliding_window"), "logit_softcap": None,
            "is_encoder_decoder": False, "n_prefix_tokens": 0}
    for field, value in want.items():
        if getattr(cfg, field) != value:
            raise ValueError(f"program config {field}={getattr(cfg, field)!r}, "
                             f"the configuration file needs {value!r}")
    return cfg


def _norm_leaf(c, shape):
    if c["norm"] == "rmsnorm":
        return {"scale": jnp.zeros(shape, jnp.dtype(c.get("torch_dtype", "bfloat16")))}
    return {}


def params(ref, c, seed, like, shardings):
    """The program's parameter tree holding ``ref``'s weights for ``seed``,
    made in one jitted call on the device in the dtype they are served in.

    ``like`` is the program's own tree (arrays or shape structs) to match;
    a tree whose structure, shapes or dtypes differ is refused.
    ``shardings`` is the tree of shardings to create the leaves with."""
    L, d = c["num_hidden_layers"], c["hidden_size"]

    def build(key):
        stacked = jax.vmap(lambda i: ref.layer(c, key, i))(jnp.arange(L))
        tree = {
            "embed": ref.embedding(c, key),
            "final_norm": _norm_leaf(c, (d,)),
            "layers": {"b0_dense": {
                "ln1": _norm_leaf(c, (L, d)),
                "attn": {k: stacked[k] for k in ("wq", "wk", "wv", "wo")},
                "ln2": _norm_leaf(c, (L, d)),
                "mlp": {k: stacked[k] for k in ("w_gate", "w_up", "w_down")},
            }},
        }
        if not c.get("tie_word_embeddings"):
            tree["lm_head"] = ref.head(c, key)
        return tree

    key = ref.root_key(seed)
    ours = jax.eval_shape(build, key)
    theirs = jax.eval_shape(lambda t: t, like)
    if jax.tree.structure(ours) != jax.tree.structure(theirs):
        raise ValueError("the program's parameter tree has another layout: "
                         f"{jax.tree.structure(theirs)} vs {jax.tree.structure(ours)}")
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        if (a.shape, a.dtype) != (b.shape, b.dtype):
            raise ValueError(f"parameter {b.shape} {b.dtype} vs {a.shape} {a.dtype}")
    return jax.jit(build, out_shardings=shardings)(key)
